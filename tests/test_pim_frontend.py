"""repro.pim trace-and-compile frontend: fused multi-op programs bit-exact
vs the jnp per-op oracle on both bases and both executor backends, the
fused-MAC cost acceptance (fewer gates + fewer HBM planes than separate
dispatches), cache canonicalization, the new one-line public wrappers, and
the compress_schedule deprecation."""

import numpy as np
import pytest

import jax.numpy as jnp

try:  # hypothesis is optional: fall back to deterministic seeded cases
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hyp import given, settings, st

import repro.pim as pim
from repro.core import ir, machine, simulate
from repro.core.machine import PlaneVM

np.seterr(all="ignore")

N_VEC = 96

_MAC = lambda a, b, c: a * b + c  # noqa: E731
_CHAIN = lambda a, b, c: (a + b) * c + a  # noqa: E731 — 3 ops, reuses a


def _rand(dtype, rng):
    if dtype.kind == "fixed":
        lo, hi = -(2 ** (dtype.nbits - 1)), 2 ** (dtype.nbits - 1)
        return jnp.asarray(rng.integers(lo, hi, N_VEC).astype(np.int32))
    bits = rng.integers(0, 2**32, N_VEC, dtype=np.uint64).astype(np.uint32)
    if dtype.kind == "bf16":
        return jnp.asarray((bits >> 16).astype(np.uint16)).view(jnp.bfloat16)
    return jnp.asarray(bits.view(np.float32))


def _oracle(fn, dtype, args):
    """Per-op rounding/wrapping oracle: numpy ops on the carrier dtype for
    floats (numpy honors gradual underflow; XLA CPU flushes subnormal
    operands), masked int64 steps for fixed.  bf16 args arrive as ml_dtypes
    arrays via np.asarray, whose ufuncs round per-op."""
    if dtype.kind != "fixed":
        return fn(*(np.asarray(a) for a in args))

    n = dtype.nbits

    class W:  # wrapping int of width n, per-op truncation
        def __init__(self, v):
            m = np.int64(v) & ((1 << n) - 1)
            self.v = np.where(m >= 1 << (n - 1), m - (1 << n), m).astype(np.int64)

        def __add__(self, o):
            return W(self.v + o.v)

        def __mul__(self, o):
            return W(self.v * o.v)

    return jnp.asarray(fn(*(W(np.asarray(a)) for a in args)).v.astype(np.int32))


def _check(dtype, got, exp):
    if dtype.kind == "fixed":
        assert np.array_equal(np.asarray(got), np.asarray(exp))
        return
    width = np.uint16 if dtype.kind == "bf16" else np.uint32
    f = np.float32
    gb = np.asarray(got).view(width)
    eb = np.asarray(exp).view(width)
    nan = np.isnan(np.asarray(got, f)) & np.isnan(np.asarray(exp, f))
    ok = (gb == eb) | nan
    assert ok.all(), f"{(~ok).sum()} mismatches"


_DTYPES = {"f32": pim.f32, "bf16": pim.bf16, "int8": pim.int8, "int16": pim.int16}


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
@pytest.mark.parametrize("prog", ["mac", "chain"])
def test_fused_programs_bit_exact_property(prog, dtype, basis):
    """Property test: fused MAC and the 3-op chain are bit-exact vs the
    per-op jnp oracle on both bases through the interpreter backend."""
    dt = _DTYPES[dtype]
    fn = _MAC if prog == "mac" else _CHAIN
    compiled = pim.compile(fn, dtype=dt, backend="interpreter")

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=3, deadline=None)
    def inner(seed):
        rng = np.random.default_rng(seed)
        args = [_rand(dt, rng) for _ in range(3)]
        got = compiled(*args, basis=basis)
        _check(dt, got, _oracle(fn, dt, args))

    inner()


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
def test_fused_mac_pallas_matches_interpreter(dtype, basis):
    """The Pallas (interpret) backend executes the same fused CompiledSchedule
    as the interpreter, bit-for-bit, at every dtype on both bases."""
    dt = _DTYPES[dtype]
    mac = pim.compile(_MAC, dtype=dt)
    rng = np.random.default_rng(sum(map(ord, dtype + basis)))
    args = [_rand(dt, rng) for _ in range(3)]
    got_p = mac(*args, basis=basis, backend="pallas")
    got_i = mac(*args, basis=basis, backend="interpreter")
    _check(dt, got_p, got_i)
    _check(dt, got_p, _oracle(_MAC, dt, args))


# ------------------------------------------------------- cost acceptance


def test_fused_f32_mac_beats_separate_dispatches():
    """Acceptance: compile(a*b+c) reports strictly fewer total gates and
    strictly fewer HBM plane transfers than separate float_mul + float_add
    dispatches (cross-op CSE/fuse/DCE fire across the region boundary), and
    peak live columns stay within the paper's 1024 budget."""
    rep = pim.compile(_MAC, dtype=pim.f32).cost()
    sep = [ir.op_cost("float_mul"), ir.op_cost("float_add")]
    assert rep.gates < sum(r.gates for r in sep)
    assert rep.cycles < sum(r.cycles for r in sep)
    assert rep.hbm_planes < sum(r.hbm_planes for r in sep)
    assert rep.hbm_planes == 4 * 32  # 3 inputs + 1 output; no intermediates
    assert rep.num_cols <= 1024
    # recorded NORs also shrink: the shared record-mode VM dedups across ops
    assert rep.recorded_gates < sum(r.recorded_gates for r in sep)
    # the dram lowering of the same program still wins on data movement and
    # stays within a whisker on gates (pass-interaction noise, < 0.5%)
    repd = pim.compile(_MAC, dtype=pim.f32).cost(basis="dram")
    sepd = [ir.op_cost("float_mul", basis="dram"), ir.op_cost("float_add", basis="dram")]
    assert repd.hbm_planes < sum(r.hbm_planes for r in sepd)
    assert repd.gates <= 1.005 * sum(r.gates for r in sepd)
    assert repd.peak_rows <= 1024


@pytest.mark.parametrize("basis", ["memristive", "dram"])
def test_fused_int_mac_dce_across_boundary(basis):
    """The fused fixed-point MAC's int8 result type makes the high product
    half dead, so DCE deletes its gates — strictly fewer gates AND cycles
    than the full-width ``_OP_TABLE`` dispatches on both bases, and strictly
    fewer HBM planes than even truncated separate dispatches."""
    rep = pim.compile(_MAC, dtype=pim.int8).cost(basis=basis)
    sep_full = [ir.op_cost("fixed_mul", 8, basis=basis),
                ir.op_cost("fixed_add", 8, basis=basis)]
    assert rep.gates < sum(r.gates for r in sep_full)
    assert rep.cycles < sum(r.cycles for r in sep_full)
    # vs what the public wrappers dispatch (truncated mul): fusion's win is
    # the boundary traffic — the 8 product planes never leave the array
    sep_trunc = [pim.compile(lambda a, b: a * b, dtype=pim.int8).cost(basis=basis),
                 pim.compile(lambda a, b: a + b, dtype=pim.int8).cost(basis=basis)]
    assert rep.gates <= sum(r.gates for r in sep_trunc)
    assert rep.hbm_planes < sum(r.hbm_planes for r in sep_trunc)


def test_report_hbm_bytes():
    from repro.core.costmodel import MEMRISTIVE_PIM

    rep = pim.compile(_MAC, dtype=pim.f32).cost()
    # 128 boundary planes × 4096 elems / 8 bits per byte = 64 KiB
    assert MEMRISTIVE_PIM.report_hbm_bytes(rep, 4096) == 128 * 4096 / 8


def test_single_op_trace_canonicalizes_to_compile_op_cache():
    """pim.compile(lambda a, b: a + b) and ir.compile_op('float_add') share
    one cache entry — compile_op is the one-op special case."""
    add = pim.compile(lambda a, b: a + b, dtype=pim.f32)
    assert add.compiled() is ir.compile_op("float_add")
    assert add.compiled(basis="dram") is ir.compile_op("float_add", basis="dram")
    stats = ir.cache_stats()
    assert stats["hits"] >= 1 and stats["misses"] >= 1


def test_multi_output_program():
    fn = pim.compile(lambda a, b: (a + b, a * b), dtype=pim.int8,
                     backend="interpreter")
    rng = np.random.default_rng(3)
    x, y = (_rand(pim.int8, rng) for _ in range(2))
    s, p = fn(x, y)
    _check(pim.int8, s, _oracle(lambda a, b: a + b, pim.int8, (x, y)))
    _check(pim.int8, p, _oracle(lambda a, b: a * b, pim.int8, (x, y)))
    rep = fn.cost()
    assert rep.hbm_planes_out == 16  # two int8 outputs


def test_trace_errors():
    with pytest.raises(pim.TraceError):  # non-scalar constants stay errors
        pim.compile(lambda a, b: a + "one", dtype=pim.f32)
    with pytest.raises(pim.TraceError):  # non-integral constant in fixed
        pim.compile(lambda a, b: a + 1.5, dtype=pim.int8)
    with pytest.raises(pim.TraceError):
        pim.compile(lambda a, b: a + b, dtype=(pim.f32, pim.bf16))
    with pytest.raises(KeyError):  # no bf16 division netlist registered
        pim.compile(lambda a, b: a / b, dtype=pim.bf16)
    with pytest.raises(pim.TraceError):
        pim.compile(lambda a: 7, dtype=pim.f32)
    with pytest.raises(pim.TraceError):  # *args is not traceable
        pim.compile(lambda *args: args[0] + args[1], dtype=pim.f32)
    with pytest.raises(pim.TraceError, match="overflows"):  # 10**400 > f64
        pim.compile(lambda a: a + 10**400, dtype=pim.f32)
    with pytest.raises(ValueError, match="only applies to the pallas"):
        pim.compile(lambda a, b: a + b, dtype=pim.int8)(
            np.arange(3, dtype=np.int32), np.arange(3, dtype=np.int32),
            backend="interpreter", mode="unrolled")


# --------------------------------------------------- scalar constants


def test_scalar_constants_f32():
    """Python scalars trace to immediate INIT planes: bit-exact vs numpy
    (same rounding as runtime data) with no extra HBM input planes."""
    fn = pim.compile(lambda a, b: a * b + 2.5, dtype=pim.f32,
                     backend="interpreter")
    rng = np.random.default_rng(21)
    x = rng.standard_normal(N_VEC).astype(np.float32)
    y = rng.standard_normal(N_VEC).astype(np.float32)
    _check(pim.f32, fn(x, y), (x * y + np.float32(2.5)).astype(np.float32))
    rep = fn.cost()
    assert rep.hbm_planes_in == 64  # the constant is not an input plane


def test_scalar_constants_reverse_and_fixed():
    two_minus = pim.compile(lambda a: 2 - a, dtype=pim.int8,
                            backend="interpreter")
    x = np.array([5, -3, 127, -128, 0], np.int32)
    exp = ((2 - x + 128) % 256 - 128).astype(np.int32)
    assert np.array_equal(np.asarray(two_minus(x)), exp)

    scale = pim.compile(lambda a: a * 3 + 1, dtype=pim.int8,
                        backend="interpreter")
    exp2 = ((x * 3 + 1 + 128) % 256 - 128).astype(np.int32)
    assert np.array_equal(np.asarray(scale(x)), exp2)

    # negative constants wrap to the signed representative at every width,
    # including the full-int32 case whose raw mask overflows the carrier
    neg32 = pim.compile(lambda a: a + (-5), dtype=pim.int32,
                        backend="interpreter")
    xw = np.array([100, -100, 2**31 - 1], np.int32)
    expw = (((xw.astype(np.int64) - 5) + 2**31) % 2**32 - 2**31).astype(np.int32)
    assert np.array_equal(np.asarray(neg32(xw)), expw)


def test_scalar_constants_fold_and_dedup():
    """Repeated constants trace to one node; constant folding then chews
    through the INIT planes, so `a * 1.0` costs no more gates than `a + 0.0`
    costs planes — and the program key distinguishes different immediates."""
    f1 = pim.compile(lambda a, b: a * 2.0 + b * 2.0, dtype=pim.f32)
    consts = [n for n in f1.program.body if n.op == ir.CONST_OP]
    assert len(consts) == 1  # deduplicated per bit pattern
    k2 = pim.compile(lambda a, b: a * 2.0 + b * 4.0, dtype=pim.f32)
    assert f1.program.key != k2.program.key

    # big integer constants in float traces round like floats (2**35 would
    # overflow the fixed-point carrier path)
    big = pim.compile(lambda a: a + 2**35, dtype=pim.f32,
                      backend="interpreter")
    xb = np.array([1.0, -(2.0**35)], np.float32)
    _check(pim.f32, big(xb), (xb + np.float32(2**35)).astype(np.float32))

    # constant dedup is per dtype: int16 16256 and bf16 1.0 share a bit
    # pattern but must not share a tracer in a multi-dtype trace
    mixed = pim.compile(lambda a, b: (a + 16256, b + 1.0),
                        dtype=(pim.int16, pim.bf16), backend="interpreter")
    xi = np.array([1, -2], np.int32)
    xf = np.array([0.5, -3.0], np.float32)
    s, f = mixed(xi, jnp.asarray(xf, jnp.bfloat16))
    exp_i = (((xi + 16256) + 2**15) % 2**16 - 2**15).astype(np.int32)
    assert np.array_equal(np.asarray(s), exp_i)
    import ml_dtypes
    _check(pim.bf16, f, (xf.astype(np.float64) + 1.0).astype(ml_dtypes.bfloat16))


def test_simulate_float_mac_oracle_and_cost():
    rng = np.random.default_rng(5)
    x, y, c = (rng.standard_normal(64).astype(np.float32) for _ in range(3))
    got, rep = simulate.float_mac(x, y, c)
    exp = (x * y + c).astype(np.float32)
    _check(pim.f32, got, exp)
    assert rep.hbm_planes == 128
    assert rep.gates == pim.compile(_MAC, dtype=pim.f32).cost().gates


# ------------------------------------------------- new one-line wrappers


def test_new_public_wrappers_bit_exact():
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    xi = rng.integers(-128, 128, 200).astype(np.int32)
    yi = rng.integers(-128, 128, 200).astype(np.int32)
    yi[yi == 0] = 1
    got = np.asarray(ops.pim_fixed_sub(xi, yi, nbits=8))
    exp = ((xi - yi) & 0xFF)
    exp = np.where(exp >= 128, exp - 256, exp).astype(np.int32)
    assert np.array_equal(got, exp)

    got = np.asarray(ops.pim_fixed_div(xi, yi, nbits=8))
    exp = np.trunc(xi / yi).astype(np.int64) & 0xFF
    exp = np.where(exp >= 128, exp - 256, exp).astype(np.int32)
    assert np.array_equal(got, exp)

    xf = rng.standard_normal(128).astype(np.float32)
    yf = rng.standard_normal(128).astype(np.float32)
    got = np.asarray(ops.pim_float_sub(xf, yf))
    _check(pim.f32, got, (xf - yf).astype(np.float32))
    got = np.asarray(ops.pim_float_div(xf, yf))
    _check(pim.f32, got, (xf / yf).astype(np.float32))


# --------------------------------------------------- deprecation (satellite)


def test_compress_schedule_deprecation_warns():
    """machine.compress_schedule survives only as a deprecated wrapper and
    must warn; its result still matches ir.lower directly."""
    vm = PlaneVM(mode="record")
    a, b = vm.input_plane(), vm.input_plane()
    out = vm.nor(a, b)
    sched = vm.finish_schedule({"a": [a], "b": [b]}, {"out": [out]})
    with pytest.warns(DeprecationWarning, match="compress_schedule"):
        compressed = machine.compress_schedule(sched)
    direct = ir.lower(ir.from_schedule(sched)).to_schedule()
    assert np.array_equal(compressed.ops, direct.ops)
    assert compressed.num_cols == direct.num_cols


def test_call_records_pack_execute_unpack_spans(tmp_path):
    """One call under the profiler writes the spans ``pim.pack``,
    ``pim.execute`` and ``pim.unpack`` once each, in that order, inside the
    caller's own span; a repeated call re-runs no compiler pass."""
    import jax

    from repro.pim import frontend

    fn = pim.compile(lambda a, b: a + b, dtype=pim.int8, backend="interpreter")
    rng = np.random.default_rng(5)
    x, y = (_rand(pim.int8, rng) for _ in range(2))
    jax.block_until_ready(fn(x, y))
    misses = ir.cache_stats()["misses"]
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            jax.block_until_ready(fn(x, y))
    assert ir.cache_stats()["misses"] == misses
    [path] = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = ("caller", frontend.PACK_SPAN, frontend.EXECUTE_SPAN,
             frontend.UNPACK_SPAN)
    events = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for plane in jax.profiler.ProfileData.from_file(
                        str(path)).planes if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name in names)
    assert [name for _, _, name in events] == list(names)
    (c0, c1, _), *spans = events
    assert c0 <= spans[0][0] and spans[-1][1] <= c1
    assert all(e <= s for (_, e, _), (s, _, _) in zip(spans, spans[1:]))


_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/backend_compile_duration")


@pytest.mark.parametrize("dtype,max_eqns", [("f32", 6), ("int8", 16)])
def test_call_is_a_few_programs(dtype, max_eqns):
    """One call is a handful of top-level equations, pack and unpack one
    jitted program each: the f32 MAC runs the loop kernel (pack, pad,
    kernel, trim, unpack); the int8 MAC runs the unrolled kernel, whose
    placement (zero-fill, scatter, gather and their index arithmetic) is
    still eager.  A repeated call with the same shapes traces and compiles
    nothing."""
    import jax
    from jax._src import monitoring

    fn = pim.compile(_MAC, dtype=_DTYPES[dtype])
    rng = np.random.default_rng(11)
    args = [_rand(_DTYPES[dtype], rng) for _ in range(3)]
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    jitted = [e.params["name"] for e in eqns if e.primitive.name == "jit"]
    assert jitted.count("pack") == 1 and jitted.count("unpack") == 1
    assert len(eqns) <= max_eqns, [e.primitive.name for e in eqns]

    jax.block_until_ready(fn(*args))
    events = []

    def listener(event, duration, **kwargs):
        if event in _TRACE_EVENTS:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        _check(_DTYPES[dtype], fn(*args), _oracle(_MAC, _DTYPES[dtype], args))
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert events == []
