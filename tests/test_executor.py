"""Executor kernels and gate scheduling (DESIGN.md §5): bit-exactness of the
wave-scheduled straight-line kernel (``run_unrolled``) vs the fori_loop
kernel (``run_loop``) vs the interpreter oracle — across the ``_OP_TABLE``,
fused MAC programs, both logic bases and every frontend dtype — plus the
``levelize``/``reorder`` pass invariants (topological order preserved, peak
columns never increased) and the per-schedule executor plans."""

import numpy as np
import pytest

import jax.numpy as jnp

import repro.pim as pim
from repro.core import aritpim, bitplanes, ir
from repro.core.machine import operand_slots
from repro.kernels import pim_bitserial

np.seterr(all="ignore")

# Unrolled-kernel parity is bounded: straight-line XLA-CPU compile time is
# superlinear in schedule length, and the backend runs schedules past the
# threshold on the loop kernel anyway (which the same test still checks).
# The bound still covers every opcode on both bases and multi-segment
# straight-line kernels (> UNROLL_SEGMENT_GATES gates).
_UNROLL_TEST_CAP = 2500

_STRIPPED = tuple(p for p in ir.DEFAULT_PASSES if p != "reorder")

_MAC = lambda a, b, c: a * b + c  # noqa: E731


def _basis_nbits(op: str) -> int:
    if op.startswith("fixed"):
        return 8
    return 16 if op.startswith("bf16") else 32


def _random_planes(n_planes, n_words, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, 2**32, (n_planes, n_words), dtype=np.uint64).astype(np.uint32)
    )


def _call_on(kernel, fn, args, basis="memristive"):
    """``fn(*args)`` with its schedule run on ``KERNELS[kernel]``: the
    frontend's pack and unpack around one kernel entry point."""
    planes = bitplanes.pack(fn.in_types, *args)
    out = pim_bitserial.KERNELS[kernel](fn.compiled(basis), planes)
    return bitplanes.unpack(fn.out_types, out, jnp.shape(args[0])[0])[0]


# ---------------------------------------------------------- kernel parity


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("op", sorted(aritpim._OP_TABLE))
def test_executor_modes_bit_exact_all_ops(op, basis):
    """Acceptance: on both bases, every _OP_TABLE op executes bit-for-bit
    identically on the loop kernel, the unrolled kernel (size-capped — past
    the cap the auto selector must pick the loop kernel) and the
    interpreter, at the plane level on random bit patterns."""
    nbits = _basis_nbits(op)
    compiled = ir.compile_op(op, nbits, basis=basis)
    wa, wb = aritpim._OP_TABLE[op].in_widths(nbits)
    planes = _random_planes(wa + wb, 2, seed=sum(map(ord, op + basis)))
    exp = np.asarray(ir.get_backend("interpreter").run(compiled, planes).planes)

    got_loop = np.asarray(pim_bitserial.run_loop(compiled, planes))
    assert np.array_equal(got_loop, exp), (op, basis, "loop")

    if compiled.num_gates <= _UNROLL_TEST_CAP:
        got_unrolled = np.asarray(pim_bitserial.run_unrolled(compiled, planes))
        assert np.array_equal(got_unrolled, exp), (op, basis, "unrolled")
    else:
        assert pim_bitserial.auto_kernel(compiled) == "loop", (
            op, compiled.num_gates)

    got_auto = np.asarray(
        ir.get_backend("pallas").run(compiled, planes).planes)
    assert np.array_equal(got_auto, exp), (op, basis, "auto")


_DTYPES = {"int8": pim.int8, "int16": pim.int16, "bf16": pim.bf16}


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
def test_fused_mac_unrolled_bit_exact(dtype, basis):
    """Fused multi-op MAC programs run the straight-line kernel bit-exactly
    (these schedules span several straight-line segments)."""
    dt = _DTYPES[dtype]
    mac = pim.compile(_MAC, dtype=dt)
    rng = np.random.default_rng(sum(map(ord, dtype + basis)))
    if dt.kind == "fixed":
        lo, hi = -(2 ** (dt.nbits - 1)), 2 ** (dt.nbits - 1)
        args = [jnp.asarray(rng.integers(lo, hi, 70).astype(np.int32))
                for _ in range(3)]
    else:
        bits = [rng.integers(0, 2**16, 70, dtype=np.uint32) for _ in range(3)]
        args = [jnp.asarray(b.astype(np.uint16)).view(jnp.bfloat16) for b in bits]
    got_u = _call_on("unrolled", mac, args, basis)
    got_l = _call_on("loop", mac, args, basis)
    got_i = mac(*args, basis=basis, backend="interpreter")
    vu, vl, vi = (
        np.asarray(x).view(np.uint16) if dt.kind == "bf16" else np.asarray(x)
        for x in (got_u, got_l, got_i))
    assert np.array_equal(vu, vi), (dtype, basis)
    assert np.array_equal(vl, vi), (dtype, basis)


@pytest.mark.parametrize("kernel", ["unrolled", "loop"])
def test_fused_mac_under_outer_jit(kernel):
    """A program called inside the caller's ``jax.jit`` runs the same
    kernels (traced arrays pick interpret mode from the default backend)."""
    import jax

    mac = pim.compile(_MAC, dtype=pim.int8)
    x = jnp.asarray(np.arange(-40, 40, dtype=np.int32))
    args = (x, x[::-1], x)
    jitted = jax.jit(lambda *a: _call_on(kernel, mac, a, "dram"))
    got = np.asarray(jitted(*args))
    exp = np.asarray(_call_on(kernel, mac, args, "dram"))
    assert np.array_equal(got, exp)
    assert np.array_equal(got, np.asarray(mac(*args, basis="dram")))


def test_fused_f32_mac_unrolled_bit_exact():
    """The flagship 13k-gate f32 fused MAC: the straight-line kernel
    (multi-segment) reproduces the interpreter bit-for-bit.  One basis —
    this is the most expensive straight-line compile in the suite."""
    mac = pim.compile(_MAC, dtype=pim.f32)
    rng = np.random.default_rng(7)
    args = [jnp.asarray(
        rng.integers(0, 2**32, 96, dtype=np.uint64).astype(np.uint32)
        .view(np.float32)) for _ in range(3)]
    got_u = np.asarray(_call_on("unrolled", mac, args)).view(np.uint32)
    got_i = np.asarray(mac(*args, backend="interpreter")).view(np.uint32)
    assert np.array_equal(got_u, got_i)


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("words", [1, 1023, 1024, 1025, 3136, 17413])
def test_loop_kernel_word_blocks_bit_exact(words, basis):
    """The loop kernel's ``(8, L)`` column tiles at any word count: padded
    to whole word-blocks and trimmed, on one block or on several (17,413
    words of the f32 MAC are 3 blocks at u = 6), bit-exact against the
    interpreter."""
    compiled = pim.compile(_MAC, dtype=pim.f32).compiled(basis=basis)
    planes = _random_planes(len(compiled.input_slots), words, seed=words)
    exp = np.asarray(ir.get_backend("interpreter").run(compiled, planes).planes)
    got = pim_bitserial.run_loop(compiled, planes)
    assert got.shape == exp.shape
    assert np.array_equal(np.asarray(got), exp)


def _vmem_planes(compiled):
    """VMEM planes per word of ``pim_loop``: the state, the input and output
    blocks double-buffered."""
    return compiled.num_cols + 2 * (len(compiled.input_slots)
                                    + len(compiled.output_slots))


@pytest.mark.parametrize("words,units,blocks", [
    (200704, 8, 25), (25088, 7, 4), (3136, 4, 1)],
    ids=["mac_b8", "mac_b1", "conv"])
def test_loop_block_units_at_the_cells_sizes(words, units, blocks):
    """The benchmark cells' word counts at the memristive f32 MAC's 160
    columns, 96 input and 32 output planes."""
    compiled = pim.compile(_MAC, dtype=pim.f32).compiled(basis="memristive")
    assert compiled.num_cols == 160
    u = pim_bitserial.loop_block_units(words, _vmem_planes(compiled))
    assert u == units
    assert -(-words // (pim_bitserial.TILE_WORDS * u)) == blocks


@pytest.mark.parametrize("planes", [1, 20, 320, 411, 416, 2000])
def test_loop_block_units_fewest_blocks_then_least_padding(planes):
    """Against a search over every width that fits ``LOOP_VMEM_BYTES``:
    the fewest word-blocks, then the least padding; never past the VMEM
    cap, and a prime unit count does not fall back to u = 1."""
    tile_bytes = 4 * pim_bitserial.TILE_WORDS * planes
    u_max = max(1, pim_bitserial.LOOP_VMEM_BYTES // tile_bytes)
    for words in [1, 1023, 1024, 1025, 13 * 1024, 17 * 1024 - 1, 100_003,
                  200_704]:
        u = pim_bitserial.loop_block_units(words, planes)
        units = -(-words // pim_bitserial.TILE_WORDS)
        best = min(range(1, u_max + 1),
                   key=lambda v: (-(-units // v), -(-units // v) * v))
        assert u == best, (words, planes)
        assert u <= u_max
    if u_max > 1:
        assert pim_bitserial.loop_block_units(13 * 1024, planes) > 1


# --------------------------------------------------- scheduling invariants


def _check_topological(sir: ir.ScheduleIR) -> None:
    defined = {v for cols in sir.inputs.values() for v in cols}
    for op, a, b, c, out in sir.ops:
        op, a, b, c, out = (int(x) for x in (op, a, b, c, out))
        for s in operand_slots(op):
            assert (a, b, c)[s] in defined, "operand used before definition"
        defined.add(out)


@pytest.mark.parametrize("op", ["fixed_add", "fixed_mul", "float_add"])
def test_levelize_preserves_topological_order(op):
    """Acceptance: wave-major reordering keeps every operand defined before
    use, waves are non-decreasing, and the wave count matches a direct
    recomputation of the DAG depth."""
    sir = ir.run_passes(ir.record_op(op, 32), (*_STRIPPED, "levelize"))
    _check_topological(sir)
    waves = ir._dataflow_waves(ir._gate_rows(sir))
    assert waves == sorted(waves)  # wave-major order
    assert sir.meta["num_waves"] == max(waves)


def test_levelize_preserves_semantics():
    x = np.array([3, -7, 120, -128], np.int32)
    y = np.array([5, 9, 100, -1], np.int32)
    compiled = ir.compile_op("fixed_add", 8, passes=(*_STRIPPED, "levelize"))
    from repro.core import bitplanes
    planes = jnp.stack(bitplanes.int_to_planes(jnp.asarray(x), 8)
                       + bitplanes.int_to_planes(jnp.asarray(y), 8))
    out = ir.get_backend("interpreter").run(compiled, planes).planes
    got = np.asarray(bitplanes.planes_to_int([out[i] for i in range(8)],
                                             len(x), signed=True))
    exp = ((x + y + 128) % 256 - 128).astype(np.int32)
    assert np.array_equal(got, exp)


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("op", sorted(aritpim._OP_TABLE))
def test_reorder_never_increases_cols(op, basis):
    """Acceptance: the pressure scheduler never increases peak columns
    relative to the same pipeline without it, on either basis."""
    nbits = _basis_nbits(op)
    with_r = ir.compile_op(op, nbits, basis=basis)
    without = ir.compile_op(op, nbits, passes=_STRIPPED, basis=basis)
    assert with_r.num_cols <= without.num_cols, (op, basis)
    assert with_r.num_gates == without.num_gates  # pure reordering


def test_reorder_reduces_float_cols():
    """Acceptance: the scheduler strictly cuts peak columns for at least one
    float op (float_mul is the known win)."""
    wins = []
    for op, nbits in (("float_mul", 32), ("bf16_mul", 16)):
        with_r = ir.compile_op(op, nbits)
        without = ir.compile_op(op, nbits, passes=_STRIPPED)
        wins.append(with_r.num_cols < without.num_cols)
    assert any(wins)


def test_parallel_cycles_reported():
    from repro.core.costmodel import MEMRISTIVE_PIM

    rep = ir.op_cost("fixed_add", 8)
    assert 0 < rep.parallel_cycles <= rep.schedule_len
    assert MEMRISTIVE_PIM.report_parallel_throughput(rep) == (
        MEMRISTIVE_PIM.total_rows * MEMRISTIVE_PIM.clock_hz
        / rep.parallel_cycles)
    # a ripple adder has real parallelism: strictly fewer waves than rows
    assert rep.parallel_cycles < rep.schedule_len
    compiled = ir.compile_op("fixed_add", 8)
    assert rep.parallel_cycles == compiled.num_waves
    # reordering passes never change the DAG depth
    unsched = ir.op_cost("fixed_add", 8, passes=_STRIPPED)
    assert rep.parallel_cycles == unsched.parallel_cycles


# ------------------------------------------------- wave chunks & segments


def test_wave_chunks_hazard_free():
    """No gate in a chunk reads (or rewrites) a column written earlier in
    the same chunk — the invariant that makes read-then-write emission
    program-order-correct."""
    compiled = ir.compile_op("fixed_mul", 8)
    rows = [tuple(int(x) for x in r) for r in compiled.ops]
    chunks = pim_bitserial._wave_chunks(rows)
    assert sum(len(c) for c in chunks) == len(rows)
    for chunk in chunks:
        written = set()
        for op, a, b, c, o in chunk:
            reads = {(a, b, c)[s] for s in operand_slots(op)}
            assert not (reads & written)
            assert o not in written
            written.add(o)


def test_segments_respect_budget():
    compiled = ir.compile_op("float_mul", 32)
    segments = pim_bitserial._segments(compiled)
    assert len(segments) > 1  # float_mul is a genuine multi-segment case
    for seg in segments:
        n = sum(len(c) for c in seg)
        assert n <= pim_bitserial.UNROLL_SEGMENT_GATES or len(seg) == 1
    total = sum(len(c) for seg in segments for c in seg)
    assert total == compiled.num_gates


def test_auto_mode_threshold():
    small = ir.compile_op("fixed_add", 8)
    big = ir.compile_op("float_div", 32)
    assert small.num_gates <= pim_bitserial.UNROLL_AUTO_MAX_GATES
    assert big.num_gates > pim_bitserial.UNROLL_AUTO_MAX_GATES
    assert pim_bitserial.auto_kernel(small) == "unrolled"
    assert pim_bitserial.auto_kernel(big) == "loop"


# ------------------------------------------------------- executor plans


def test_gate_arrays_cached_per_key():
    """The executor plan, its device gate arrays and its segments are built
    once per ``CompiledSchedule`` object; another schedule gets its own."""
    compiled = ir.compile_op("fixed_add", 8)
    plan = pim_bitserial.executor_plan(compiled)
    assert pim_bitserial.executor_plan(compiled) is plan
    assert pim_bitserial.executor_plan(compiled).gates is plan.gates
    assert plan.segments is plan.segments
    assert plan.input_slots == tuple(compiled.input_slots)
    assert plan.output_slots == tuple(compiled.output_slots)
    assert [np.asarray(g).tolist() for g in plan.gates] == (
        compiled.ops.T.tolist())
    # the same program compiled with other passes is another schedule
    clone = ir.compile_op("fixed_add", 8, passes=())
    assert pim_bitserial.executor_plan(clone) is not plan


def test_run_schedule_plane_count_error():
    compiled = ir.compile_op("fixed_add", 8)
    planes = _random_planes(3, 2, seed=0)
    with pytest.raises(ValueError, match="expects 16 stacked input planes"):
        ir.get_backend("pallas").run(compiled, planes)


def test_rebound_key_does_not_replay_stale_kernel():
    """Two programs with the same traced name and different bodies never
    share a kernel trace: run alternately in one process, on both kernel
    entry points, each gives its own result."""
    # Two ops each, so neither canonicalizes to a single op's name.
    def prog(a, b):
        return a + b + a

    add = pim.compile(prog, dtype=pim.int8)

    def prog(a, b):  # noqa: F811 — the same name, another body
        return a - b + a

    sub = pim.compile(prog, dtype=pim.int8)
    assert add.program.name == sub.program.name == "prog"
    x = jnp.asarray(np.array([10, 7], np.int32))
    y = jnp.asarray(np.array([3, 2], np.int32))

    def run(kernel, fn):
        return np.asarray(_call_on(kernel, fn, (x, y))).tolist()

    for _ in range(2):
        for kernel in ("unrolled", "loop"):
            assert run(kernel, add) == [23, 16]
            assert run(kernel, sub) == [17, 12]
