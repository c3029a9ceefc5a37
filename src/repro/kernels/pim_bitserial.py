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

Two kernels run an element-wise schedule (DESIGN.md §5):

* ``pim_loop`` — the ``fori_loop`` kernel: one gate per iteration,
  a dynamic read and write of whole column tiles plus a five-deep
  ``jnp.where`` opcode select, with the five gate arrays held in SMEM (5
  int32 words per gate; v5e's 1 MiB of SMEM holds schedules of up to about
  50k gates, and the TPU compiler refuses longer ones).  O(1) compile in
  schedule length, but each gate pays dynamic-indexing and select overhead.
* ``pim_segment_<k>`` — a **wave-scheduled straight-line** kernel generated from
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

A third kernel, ``pim_contract``, runs a contraction (``a @ b``, an
``ir.Contraction``): the step schedule K times per word-block, with the
crossbar state and the carried accumulator in VMEM across the steps and
only each step's operand planes read from HBM (DESIGN.md §5).

Pallas runs in interpret mode exactly when the input planes live on the CPU
(``repro.kernels.interpret_mode``), so tests on the CPU trace the same kernel
bodies that the TPU compiles.

The one ``pallas`` backend runs ``run_unrolled`` up to ``UNROLL_AUTO_MAX_GATES``
gates and ``run_loop`` past it (``auto_kernel``).  Every kernel takes as its
jit-static argument the schedule's ``ExecutorPlan`` (device gate arrays, slot
maps, lazily built segments), built once per ``CompiledSchedule`` object and
hashed by identity: O(1) per call, and no trace is shared between schedules.

Tiling: the grid runs over blocks of the packed-words axis; each program
holds the *entire* (column-allocated) crossbar state for its word-block.
The loop and contraction kernels keep column ``col`` as a full-vreg tile
``state[col]`` of ``(8, L)`` words, ``L = 128·u``: a word-block is
``1024·u`` words, and a gate reads and writes whole vregs at a dynamic
leading-axis offset (no sublane shift, no masked store).  ``u`` is chosen
per call from the padded word count and the planes held in VMEM
(``loop_block_units``): the fewest blocks whose footprint fits
``LOOP_VMEM_BYTES``, then the least padding — 8 for the f32 MAC's 160
columns at 6.4M elements, 1 block of 4 for the ResNet-50 conv's 3,136
words.  The planes move between ``[n, W]`` in HBM and ``(8, L)`` tiles by
an XLA pad and reshape inside the kernel's jitted program.  ``BLOCK_WORDS = 256``
is the unrolled segment kernel's block alone: ``[num_cols, 256]`` rows.
"""

from __future__ import annotations

import dataclasses
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
    operand_slots,
)
from repro.kernels import interpret_mode

# Word-block of the unrolled segment kernel: ``[num_cols, BLOCK_WORDS]``.
BLOCK_WORDS = 256
# The loop and contraction kernels' tiles: a column's words of one block are
# ``(SUBLANES, LANES·u)``, ``u`` full vregs of ``TILE_WORDS`` words.
SUBLANES, LANES = 8, 128
TILE_WORDS = SUBLANES * LANES
# VMEM the loop kernels' planes may take: under v5e's 16 MiB scoped default,
# with room for what Mosaic keeps besides.
LOOP_VMEM_BYTES = 14 * 2**20
UMAX32 = 0xFFFFFFFF  # python int: folded into the kernel, not a captured array

# Kernel choice: schedules at or below this many gates unroll; longer ones
# run the fori_loop kernel (straight-line XLA compile time grows
# superlinearly, so unrolling a 20k-gate divider buys compile pain for a
# win the loop kernel amortizes anyway).
UNROLL_AUTO_MAX_GATES = 1024
# Straight-line gates per generated segment kernel; boundaries snap to wave
# chunk edges.  ~4 s of XLA-CPU compile per segment, paid once per schedule.
UNROLL_SEGMENT_GATES = 1024
# Kernel names in the compiled HLO and a profiler trace: the loop kernel,
# and ``<SEGMENT_KERNEL>_<k>`` for the k-th unrolled segment.
LOOP_KERNEL = "pim_loop"
SEGMENT_KERNEL = "pim_segment"
# The contraction kernel (``a @ b``): the step schedule run K times.
CONTRACT_KERNEL = "pim_contract"


# ---------------------------------------------------------------------------
# fori_loop kernel (`pim_loop`)
# ---------------------------------------------------------------------------


def _gate_loop(op_ref, a_ref, b_ref, c_ref, o_ref, state):
    """Run the schedule's gates over ``state``, one per iteration (the body
    ``pim_loop`` and ``pim_contract`` share)."""
    n_gates = op_ref.shape[0]

    def body(g, _):
        op = op_ref[g]
        a = a_ref[g]
        b = b_ref[g]
        c = c_ref[g]
        o = o_ref[g]
        va = state[a]
        vb = state[b]
        vc = state[c]
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
        state[o] = res
        return 0

    jax.lax.fori_loop(0, n_gates, body, 0)


def loop_block_units(words: int, planes: int) -> int:
    """``u`` of a loop-kernel word-block of ``TILE_WORDS·u`` words over
    ``words`` packed words, ``planes`` ``uint32`` planes per word held in
    VMEM: the fewest blocks whose planes fit ``LOOP_VMEM_BYTES``, then the
    least padding."""
    units = -(-words // TILE_WORDS)
    u_max = max(1, LOOP_VMEM_BYTES // (4 * TILE_WORDS * planes))
    blocks = -(-units // u_max)
    return -(-units // blocks)


def _to_tiles(planes, units):
    """``[..., W]`` planes → ``[..., rows, LANES·units]``, W padded to whole
    word-blocks of ``SUBLANES`` rows; block ``i`` is rows
    ``[SUBLANES·i, SUBLANES·(i + 1))``."""
    W = planes.shape[-1]
    block = TILE_WORDS * units
    pad = (-W) % block
    planes = jnp.pad(planes, [(0, 0)] * (planes.ndim - 1) + [(0, pad)])
    return planes.reshape(*planes.shape[:-1], (W + pad) // (LANES * units),
                          LANES * units)


def _kernel(op_ref, a_ref, b_ref, c_ref, o_ref, in_ref, out_ref, state, *,
            input_slots, output_slots):
    # Load this block's input planes into their crossbar columns (static slots).
    for i, col in enumerate(input_slots):
        state[col] = in_ref[i]
    _gate_loop(op_ref, a_ref, b_ref, c_ref, o_ref, state)
    for i, col in enumerate(output_slots):
        out_ref[i] = state[col]


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _run(op, a, b, c, o, planes, *, plan, interpret):
    """``planes`` ``[n_in, W]`` → ``[n_out, W]``, run over word-blocks of
    ``loop_block_units`` full-vreg tiles per column."""
    n_in, W = planes.shape
    n_out = len(plan.output_slots)
    units = loop_block_units(W, plan.num_cols + 2 * (n_in + n_out))
    tiles = _to_tiles(planes, units)
    rows, L = tiles.shape[1:]
    # The gate arrays are read one scalar per iteration at a dynamic index,
    # which Mosaic allows from SMEM but not from VMEM vectors.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, input_slots=plan.input_slots,
                          output_slots=plan.output_slots),
        grid=(rows // SUBLANES,),
        in_specs=[smem] * 5 + [
            pl.BlockSpec((n_in, SUBLANES, L), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((n_out, SUBLANES, L), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, rows, L), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((plan.num_cols, SUBLANES, L), jnp.uint32)],
        interpret=interpret,
        name=LOOP_KERNEL,
    )(op, a, b, c, o, tiles)
    return out.reshape(n_out, rows * L)[:, :W]


# ---------------------------------------------------------------------------
# Contraction kernel: the step schedule run K times per word-block
# ---------------------------------------------------------------------------


def _contract_kernel(op_ref, a_ref, b_ref, c_ref, o_ref, steps_hbm, out_ref,
                     state, carry, buf, sem, *, operand_slots, acc_slots,
                     output_slots):
    # Grid (word-block i, step k), k innermost: the crossbar state and the
    # carried result stay in VMEM over all K steps of a block.  Only each
    # step's operand planes come in from HBM, by a DMA started one step
    # ahead (so it overlaps the previous step's gates), and the result goes
    # out after the last step.
    i, k = pl.program_id(0), pl.program_id(1)

    def fetch(step):
        return pltpu.make_async_copy(
            steps_hbm.at[step, :, pl.ds(i * SUBLANES, SUBLANES)], buf, sem)

    @pl.when(k == 0)
    def _():
        fetch(0).start()
        for col in acc_slots:
            state[col] = jnp.zeros(buf.shape[1:], jnp.uint32)

    @pl.when(k > 0)
    def _():
        for j, col in enumerate(acc_slots):
            state[col] = carry[j]

    fetch(k).wait()
    for n, col in enumerate(operand_slots):
        state[col] = buf[n]

    @pl.when(k + 1 < pl.num_programs(1))
    def _():
        fetch(k + 1).start()

    _gate_loop(op_ref, a_ref, b_ref, c_ref, o_ref, state)
    # The output columns overlap the accumulator's and the operands', so the
    # result is set aside before the next step loads its inputs.
    for j, col in enumerate(output_slots):
        carry[j] = state[col]

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = carry[...]


@functools.partial(jax.jit, static_argnames=("plan", "slots", "interpret"))
def _run_contract(op, a, b, c, o, steps, *, plan, slots, interpret):
    """``steps`` ``[K, operand planes, W]`` → the carry's ``[width, W]``
    planes, run over word-blocks of ``loop_block_units`` full-vreg tiles per
    column.  ``slots`` is ``Contraction.slots`` of the schedule."""
    operand_slots, acc_slots, output_slots = slots
    n_steps, n_in, W = steps.shape
    n_out = len(output_slots)
    # VMEM: the state, the carry, one step's operands, the output block
    # double-buffered.
    units = loop_block_units(W, plan.num_cols + n_in + 3 * n_out)
    tiles = _to_tiles(steps, units)
    rows, L = tiles.shape[2:]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_contract_kernel, operand_slots=operand_slots,
                          acc_slots=acc_slots, output_slots=output_slots),
        grid=(rows // SUBLANES, n_steps),
        in_specs=[smem] * 5 + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((n_out, SUBLANES, L), lambda i, k: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, rows, L), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((plan.num_cols, SUBLANES, L), jnp.uint32),
                        pltpu.VMEM((n_out, SUBLANES, L), jnp.uint32),
                        pltpu.VMEM((n_in, SUBLANES, L), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=CONTRACT_KERNEL,
    )(op, a, b, c, o, tiles)
    return out.reshape(n_out, rows * L)[:, :W]


# ---------------------------------------------------------------------------
# Wave-scheduled straight-line kernel (`pim_segment_<k>`)
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


def _unrolled_segment(state, *, plan, seg, interpret):
    chunks = plan.segments[seg]
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
        name=f"{SEGMENT_KERNEL}_{seg}",
    )(state)


_SEGMENT_JIT = dict(static_argnames=("plan", "seg", "interpret"),
                    donate_argnums=0)
_run_unrolled_segment = jax.jit(_unrolled_segment, **_SEGMENT_JIT,
                                compiler_options=_SEGMENT_COMPILER_OPTIONS)
# A jit nested in the caller's jit may not carry compiler options; there the
# caller's compile settings apply.
_run_unrolled_segment_nested = jax.jit(_unrolled_segment, **_SEGMENT_JIT)


# ---------------------------------------------------------------------------
# Executor plans and dispatch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class ExecutorPlan:
    """What the kernels take of one ``CompiledSchedule``: their jit-static
    argument, identity-hashed, so O(1) per call and no shared trace."""

    schedule: ir.CompiledSchedule
    gates: tuple  # (op, a, b, c, o) int32 device arrays, for the loop kernels
    num_cols: int
    input_slots: tuple[int, ...]
    output_slots: tuple[int, ...]

    @functools.cached_property
    def segments(self) -> list:
        """The unrolled kernel's wave-chunked segments, built on first use."""
        return _segments(self.schedule)


@functools.cache
def executor_plan(compiled: ir.CompiledSchedule) -> ExecutorPlan:
    """The plan of ``compiled``, built once per schedule object."""
    # Concrete device arrays even when first built under a caller's jit,
    # so no tracer is cached.
    with jax.ensure_compile_time_eval():
        gates = tuple(jax.device_put(a) for a in compiled.as_arrays())
    return ExecutorPlan(compiled, gates, compiled.num_cols,
                        tuple(compiled.input_slots),
                        tuple(compiled.output_slots))


def _plan_for(compiled: ir.CompiledSchedule, planes) -> ExecutorPlan:
    plan = executor_plan(compiled)
    if planes.shape[0] != len(plan.input_slots):
        expected = {k: len(v) for k, v in sorted(compiled.input_cols.items())}
        raise ValueError(
            f"schedule {compiled.key!r} expects {len(plan.input_slots)} stacked"
            f" input planes ({expected}, in sorted-name order), got "
            f"{planes.shape[0]}")
    return plan


def run_loop(compiled: ir.CompiledSchedule, planes) -> jnp.ndarray:
    """``compiled`` on ``pim_loop``: ``planes`` ``[n_inputs, W]`` uint32, the
    inputs in sorted-name order (``input_slots``) → ``[n_outputs, W]``."""
    planes = jnp.asarray(planes)
    plan = _plan_for(compiled, planes)
    return _run(*plan.gates, planes, plan=plan,
                interpret=interpret_mode(planes))


def run_unrolled(compiled: ir.CompiledSchedule, planes) -> jnp.ndarray:
    """As ``run_loop``, on the ``pim_segment_<k>`` kernels over the
    ``[num_cols, W]`` state, W padded to a ``BLOCK_WORDS`` multiple."""
    planes = jnp.asarray(planes)
    plan = _plan_for(compiled, planes)
    interpret = interpret_mode(planes)
    W = planes.shape[1]
    pad = (-W) % BLOCK_WORDS
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, pad)))
    state = jnp.zeros((plan.num_cols, W + pad), jnp.uint32)
    state = state.at[jnp.asarray(plan.input_slots)].set(
        jnp.asarray(planes, jnp.uint32))
    run = (_run_unrolled_segment_nested if isinstance(planes, jax.core.Tracer)
           else _run_unrolled_segment)
    for seg in range(len(plan.segments)):
        state = run(state, plan=plan, seg=seg, interpret=interpret)
    return state[jnp.asarray(plan.output_slots)][:, :W]


KERNELS = {"loop": run_loop, "unrolled": run_unrolled}


def auto_kernel(compiled: ir.CompiledSchedule) -> str:
    """The ``KERNELS`` entry the ``pallas`` backend runs ``compiled`` on."""
    return ("unrolled" if compiled.num_gates <= UNROLL_AUTO_MAX_GATES
            else "loop")


class PallasBackend(ir.Backend):
    """TPU executor: one VMEM-resident crossbar per word-block, the kernel
    chosen by gate count (on the CPU the same kernels run in Pallas
    interpret mode)."""

    name = "pallas"

    def run(self, compiled, planes=None, **opts):
        if planes is None:
            raise ValueError(f"{self.name} backend needs input planes")
        out = KERNELS[auto_kernel(compiled)](compiled, planes)
        return ir.ExecutionResult(out, self.cost(compiled))

    def contract(self, compiled, contraction, steps):
        """One ``pim_contract`` launch, whatever K is."""
        plan = executor_plan(compiled)
        return _run_contract(*plan.gates, steps, plan=plan,
                             slots=contraction.slots(compiled),
                             interpret=interpret_mode(steps))


ir.register_backend(PallasBackend())
