"""Pallas TPU kernel: bit-serial element-parallel PIM gate-schedule executor.

TPU-native adaptation of the paper's crossbar column ops (DESIGN.md §2): a
crossbar column over R rows becomes a lane-packed ``uint32`` bit-plane of
``R/32`` words; the serial gate schedule becomes a sequence of bitwise VPU
ops over VMEM-resident planes.  Both logic bases execute here — memristive
NOR rows and the DRAM basis' MAJ3/NOT rows — so one executor serves every
``(program, basis, passes)`` compile, including fused multi-op programs from
the ``repro.pim`` frontend.  HBM traffic is exactly the program's boundary
planes (``CostReport.hbm_planes``) — independent of schedule length, the
in-memory property the paper models.

Two executor modes share the registry (DESIGN.md §5):

* ``loop`` — the original ``fori_loop`` kernel: one gate per iteration,
  a dynamic single-row read and write of the state plus a five-deep
  ``jnp.where`` opcode select, with the five gate arrays held in SMEM (5
  int32 words per gate; v5e's 1 MiB of SMEM holds schedules of up to about
  50k gates, and the TPU compiler refuses longer ones).  O(1) compile in
  schedule length, but each gate pays dynamic-indexing and select overhead.
* ``unrolled`` — a **wave-scheduled straight-line** kernel generated from
  the fact that ``(op, a, b, c, o)`` are static per ``CompiledSchedule``:
  the body is Python-unrolled bitwise ops on fixed ``state[col]`` indices —
  no dynamic indexing, no opcode-select chain, no scalar gate arrays on the
  device.  Gates are grouped into hazard-free *wave chunks* (no gate reads
  a column written earlier in its chunk), emitted read-then-write so every
  chunk is a batch of mutually independent VPU ops; long schedules are
  split into segments of ``UNROLL_SEGMENT_GATES`` at chunk boundaries
  (XLA compile time is superlinear in straight-line length) with the
  column state threaded between segment kernels.  Each segment is a
  ``pl.pallas_call`` with the grid over word-blocks and the state block
  aliased in/out.

Pallas runs in interpret mode exactly when the input planes live on the CPU
(``repro.kernels.interpret_mode``), so tests on the CPU trace the same kernel
bodies that the TPU compiles.

The ``pallas`` backend picks the mode automatically by gate count
(``UNROLL_AUTO_MAX_GATES``): short schedules unroll, very long ones fall
back to the loop kernel.  ``pallas-unrolled`` / ``pallas-loop`` force one
mode (``benchmarks/exec_modes.py`` runs both on the f32 fused MAC).
Per-schedule artifacts — the gate arrays and their device
upload for the loop kernel, the wave-chunked segments for the unrolled
kernel — are cached by schedule key, so repeat dispatches stop rebuilding
and re-transferring them.

Tiling: the grid runs over blocks of the packed-words axis; each program
holds the *entire* (column-allocated) crossbar state for its word-block in
``[num_cols, BLOCK_WORDS]`` — with ``num_cols ≤ 133`` for float ops (see
``ir.lower`` and the ``reorder`` pass) and ``BLOCK_WORDS = 256`` that is a
~136 KiB working set, comfortably inside VMEM and an exact analogue of one
crossbar's 1024-column budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ir
from repro.core.machine import (
    OP_INIT0,
    OP_INIT1,
    OP_MAJ3,
    OP_NOR,
    OP_NOT,
    Schedule,
    operand_slots,
)
from repro.kernels import interpret_mode

BLOCK_WORDS = 256
UMAX32 = 0xFFFFFFFF  # python int: folded into the kernel, not a captured array

# Mode-auto threshold: schedules at or below this many gates unroll; longer
# ones keep the fori_loop kernel (straight-line XLA compile time grows
# superlinearly, so unrolling a 20k-gate divider buys compile pain for a
# win the loop kernel amortizes anyway).  Force a mode with the
# ``pallas-unrolled`` / ``pallas-loop`` backends.
UNROLL_AUTO_MAX_GATES = 1024
# Straight-line gates per generated segment kernel; boundaries snap to wave
# chunk edges.  ~4 s of XLA-CPU compile per segment, amortized by the
# per-key segment cache.
UNROLL_SEGMENT_GATES = 1024


# ---------------------------------------------------------------------------
# fori_loop kernel (the `loop` mode)
# ---------------------------------------------------------------------------


def _kernel(op_ref, a_ref, b_ref, c_ref, o_ref, in_ref, out_ref, state, *,
            input_slots, output_slots):
    # Load this block's input planes into their crossbar columns (static slots).
    for i, col in enumerate(input_slots):
        state[col, :] = in_ref[i, :]

    n_gates = op_ref.shape[0]

    def body(g, _):
        op = op_ref[g]
        a = a_ref[g]
        b = b_ref[g]
        c = c_ref[g]
        o = o_ref[g]
        va = state[pl.ds(a, 1), :]
        vb = state[pl.ds(b, 1), :]
        vc = state[pl.ds(c, 1), :]
        nor = ~(va | vb)
        maj = (va & vb) | (va & vc) | (vb & vc)
        res = jnp.where(
            op == OP_NOR, nor,
            jnp.where(op == OP_MAJ3, maj,
                      jnp.where(op == OP_NOT, ~va,
                                jnp.where(op == OP_INIT0, jnp.zeros_like(nor),
                                          jnp.where(op == OP_INIT1,
                                                    jnp.full_like(nor, UMAX32),
                                                    va)))),
        )
        state[pl.ds(o, 1), :] = res
        return 0

    jax.lax.fori_loop(0, n_gates, body, 0)

    for i, col in enumerate(output_slots):
        out_ref[i, :] = state[col, :]


@functools.partial(jax.jit, static_argnames=("schedule_key", "gen", "interpret"))
def _run(op, a, b, c, o, planes, *, schedule_key, gen, interpret):
    # `gen` bumps when a different schedule is registered under this key, so
    # traces that baked the old static slot maps are never reused.
    compiled = _SCHEDULES[schedule_key]
    input_slots = compiled.input_slots
    output_slots = compiled.output_slots
    n_in, W = planes.shape
    n_out = len(output_slots)
    # The gate arrays are read one scalar per iteration at a dynamic index,
    # which Mosaic allows from SMEM but not from VMEM vectors.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, input_slots=tuple(input_slots), output_slots=tuple(output_slots)),
        grid=(W // BLOCK_WORDS,),
        in_specs=[smem] * 5 + [
            pl.BlockSpec((n_in, BLOCK_WORDS), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n_out, BLOCK_WORDS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_out, W), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((compiled.num_cols, BLOCK_WORDS), jnp.uint32)],
        interpret=interpret,
    )(op, a, b, c, o, planes)


# ---------------------------------------------------------------------------
# Wave-scheduled straight-line kernel (the `unrolled` mode)
# ---------------------------------------------------------------------------


def _wave_chunks(rows):
    """Greedy hazard-free chunking of allocated schedule rows.

    A gate joins the current chunk while it reads no column written earlier
    in the chunk (and does not re-write one).  All reads of a chunk then see
    pre-chunk state, so the generated read-then-write code — every result
    computed before any column is stored — is exactly program-order
    semantics, and each chunk is a batch of mutually independent VPU ops
    (the executable counterpart of ``ir.levelize``'s dependency waves;
    wave-major schedules chunk at full wave width).
    """
    chunks: list[list[tuple[int, int, int, int, int]]] = []
    cur: list[tuple[int, int, int, int, int]] = []
    written: set[int] = set()
    for row in rows:
        op, a, b, c, o = row
        reads = {(a, b, c)[s] for s in operand_slots(op)}
        if cur and (reads & written or o in written):
            chunks.append(cur)
            cur, written = [], set()
        cur.append(row)
        written.add(o)
    if cur:
        chunks.append(cur)
    return chunks


def _segments(compiled: ir.CompiledSchedule):
    """Wave chunks grouped into straight-line segments of at most
    ``UNROLL_SEGMENT_GATES`` gates (chunk boundaries are never split)."""
    rows = [tuple(int(x) for x in row) for row in compiled.ops]
    segments: list[list[list[tuple[int, int, int, int, int]]]] = [[]]
    count = 0
    for chunk in _wave_chunks(rows):
        if count and count + len(chunk) > UNROLL_SEGMENT_GATES:
            segments.append([])
            count = 0
        segments[-1].append(chunk)
        count += len(chunk)
    return segments


def _emit_chunks(cols, chunks):
    """Generate the straight-line body: per chunk, compute every gate from
    pre-chunk column values, then commit the writes.  ``cols`` is a Python
    list of per-column arrays/ref-reads, so the emitted jaxpr is pure SSA
    dataflow — no dynamic indexing and no opcode select survive tracing."""
    zero = None
    for chunk in chunks:
        results = []
        for op, a, b, c, o in chunk:
            if op == OP_NOR:
                r = ~(cols[a] | cols[b])
            elif op == OP_MAJ3:
                r = (cols[a] & cols[b]) | (cols[a] & cols[c]) | (cols[b] & cols[c])
            elif op == OP_NOT:
                r = ~cols[a]
            elif op == OP_INIT0:
                if zero is None:
                    zero = jnp.zeros_like(cols[0])
                r = zero
            elif op == OP_INIT1:
                r = jnp.full_like(cols[0], UMAX32)
            else:  # OP_COPY
                r = cols[a]
            results.append((o, r))
        for o, r in results:
            cols[o] = r


def _unrolled_segment_kernel(state_ref, out_ref, *, chunks, num_cols):
    cols = [state_ref[i, :] for i in range(num_cols)]
    _emit_chunks(cols, chunks)
    for i in range(num_cols):
        out_ref[i, :] = cols[i]


# XLA-CPU's MLIR fusion emitter fails ("Unknown MLIR failure") on the deep,
# multi-use NOR full-adder DAGs that fuse inside a segment; its classic
# emitter compiles them.  The option is read only by the CPU compiler.
_SEGMENT_COMPILER_OPTIONS = {"xla_cpu_use_fusion_emitters": False}


def _unrolled_segment(state, *, schedule_key, gen, seg, interpret):
    # `gen` bumps when a different schedule is registered under this key, so
    # traces that baked the old gate list are never reused.
    chunks = _segment_cache(schedule_key)[seg]
    num_cols, W = state.shape
    return pl.pallas_call(
        functools.partial(_unrolled_segment_kernel, chunks=chunks,
                          num_cols=num_cols),
        grid=(W // BLOCK_WORDS,),
        in_specs=[pl.BlockSpec((num_cols, BLOCK_WORDS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((num_cols, BLOCK_WORDS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((num_cols, W), jnp.uint32),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(state)


_SEGMENT_JIT = dict(static_argnames=("schedule_key", "gen", "seg", "interpret"),
                    donate_argnums=0)
_run_unrolled_segment = jax.jit(_unrolled_segment, **_SEGMENT_JIT,
                                compiler_options=_SEGMENT_COMPILER_OPTIONS)
# A jit nested in the caller's jit may not carry compiler options; there the
# caller's compile settings apply.
_run_unrolled_segment_nested = jax.jit(_unrolled_segment, **_SEGMENT_JIT)


def _run_unrolled(compiled: ir.CompiledSchedule, key: str, planes, interpret):
    gen = _GENERATIONS.get(key, 0)
    state = jnp.zeros((compiled.num_cols, planes.shape[1]), jnp.uint32)
    state = state.at[jnp.asarray(compiled.input_slots)].set(
        jnp.asarray(planes, jnp.uint32))
    run = (_run_unrolled_segment_nested if isinstance(planes, jax.core.Tracer)
           else _run_unrolled_segment)
    for seg in range(len(_segment_cache(key))):
        state = run(state, schedule_key=key, gen=gen, seg=seg,
                    interpret=interpret)
    return state[jnp.asarray(compiled.output_slots)]


# ---------------------------------------------------------------------------
# Per-schedule caches and dispatch
# ---------------------------------------------------------------------------

# Registry of compiled schedules (keyed so jit can treat them as static).
_SCHEDULES: dict[str, ir.CompiledSchedule] = {}
# Device-resident gate arrays for the loop kernel, built/uploaded once per
# key instead of per call.
_GATE_ARRAYS: dict[str, tuple] = {}
# Wave-chunked straight-line segments for the unrolled kernel.
_SEGMENTS: dict[str, list] = {}
# Bumped when a key is rebound to different schedule content; part of the
# kernels' static jit keys, so stale traces are never replayed.
_GENERATIONS: dict[str, int] = {}


def _invalidate(key: str) -> None:
    _GATE_ARRAYS.pop(key, None)
    _SEGMENTS.pop(key, None)
    _GENERATIONS[key] = _GENERATIONS.get(key, 0) + 1


def _gate_arrays(key: str) -> tuple:
    arrays = _GATE_ARRAYS.get(key)
    if arrays is None:
        # Concrete device arrays even when first built under a caller's jit,
        # so no tracer is cached.
        with jax.ensure_compile_time_eval():
            arrays = _GATE_ARRAYS[key] = tuple(
                jax.device_put(a) for a in _SCHEDULES[key].as_arrays())
    return arrays


def _segment_cache(key: str) -> list:
    segments = _SEGMENTS.get(key)
    if segments is None:
        segments = _SEGMENTS[key] = _segments(_SCHEDULES[key])
    return segments


def register_compiled(compiled: ir.CompiledSchedule, key: str | None = None) -> str:
    key = key or compiled.key
    if _SCHEDULES.get(key) is not compiled:
        _invalidate(key)
    _SCHEDULES[key] = compiled
    return key


def register_schedule(key: str, schedule: Schedule | ir.CompiledSchedule) -> None:
    """Register a schedule under ``key``.  Accepts a ``CompiledSchedule`` or a
    legacy (column-allocated) ``machine.Schedule``, which is wrapped as-is."""
    if isinstance(schedule, ir.CompiledSchedule):
        register_compiled(schedule, key)
        return
    _invalidate(key)
    _SCHEDULES[key] = ir.CompiledSchedule.from_legacy(schedule, key=key)


def resolve_mode(compiled: ir.CompiledSchedule, mode: str = "auto") -> str:
    """``auto`` picks by gate count; ``unrolled``/``loop`` force a kernel."""
    if mode == "auto":
        return ("unrolled" if compiled.num_gates <= UNROLL_AUTO_MAX_GATES
                else "loop")
    if mode not in ("unrolled", "loop"):
        raise ValueError(f"unknown executor mode {mode!r} "
                         "(expected 'auto', 'unrolled' or 'loop')")
    return mode


def run_schedule(key: str, planes: jnp.ndarray,
                 mode: str = "auto") -> jnp.ndarray:
    """Execute registered schedule ``key`` over stacked input planes.

    planes: ``[n_inputs, W]`` uint32 — inputs concatenated in sorted-name
    order (matching ``CompiledSchedule.input_slots``).  Returns
    ``[n_outputs, W]``.  W is padded to a BLOCK_WORDS multiple internally.
    ``mode`` selects the kernel: ``auto`` (by gate count), ``unrolled``
    (wave-scheduled straight line) or ``loop`` (fori_loop dispatch).
    Pallas runs in interpret mode exactly when ``planes`` live on the CPU.
    """
    compiled = _SCHEDULES[key]
    planes = jnp.asarray(planes)
    if planes.shape[0] != len(compiled.input_slots):
        expected = {name: len(cols)
                    for name, cols in sorted(compiled.input_cols.items())}
        raise ValueError(
            f"schedule {key!r} expects {len(compiled.input_slots)} stacked "
            f"input planes ({expected}, in sorted-name order), got "
            f"{planes.shape[0]}")
    W = planes.shape[1]
    pad = (-W) % BLOCK_WORDS
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, pad)))
    interpret = interpret_mode(planes)
    if resolve_mode(compiled, mode) == "unrolled":
        out = _run_unrolled(compiled, key, planes, interpret)
    else:
        op, a, b, c, o = _gate_arrays(key)
        out = _run(op, a, b, c, o, planes, schedule_key=key,
                   gen=_GENERATIONS.get(key, 0), interpret=interpret)
    return out[:, :W]


class PallasBackend(ir.Backend):
    """TPU executor: one VMEM-resident crossbar per word-block, kernel mode
    chosen by gate count (on the CPU the same kernels run in Pallas
    interpret mode).  ``opts['mode']`` overrides the selection per call."""

    name = "pallas"
    mode = "auto"

    def run(self, compiled, planes=None, mode: str | None = None, **opts):
        if planes is None:
            raise ValueError(f"{self.name} backend needs input planes")
        key = register_compiled(compiled)
        out = run_schedule(key, planes, mode=mode or self.mode)
        return ir.ExecutionResult(out, self.cost(compiled))


class PallasUnrolledBackend(PallasBackend):
    """Forces the wave-scheduled straight-line kernel regardless of size."""

    name = "pallas-unrolled"
    mode = "unrolled"


class PallasLoopBackend(PallasBackend):
    """Forces the fori_loop kernel (the unrolled mode's perf baseline)."""

    name = "pallas-loop"
    mode = "loop"


ir.register_backend(PallasBackend())
ir.register_backend(PallasUnrolledBackend())
ir.register_backend(PallasLoopBackend())
