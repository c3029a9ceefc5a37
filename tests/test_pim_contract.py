"""``a @ b`` in ``pim.compile``: a serial MAC contraction run as K steps of
the fused-MAC schedule in one ``pim_contract`` kernel, the accumulator kept
in VMEM.  Results are compared bit for bit (a NaN matches any NaN) with a
numpy loop over k in order, on both bases, with specials in the operands so
that NaN, infinities, -0.0 and subnormals cross k-steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

import repro.pim as pim
from repro.core import ir
from repro.kernels import pim_bitserial

_MATMUL = lambda a, b: a @ b  # noqa: E731
_MAC = lambda a, b, c: a * b + c  # noqa: E731
_DTYPES = {"f32": pim.f32, "int16": pim.int16}
_SPECIALS = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0, 1e-45, -2.5e-39,
                      1.1754944e-38, 3.4028235e38, -3.0e38], np.float32)
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/backend_compile_duration")


def _operand(dtype, rng, shape):
    if dtype.kind == "fixed":
        lo, hi = -(2 ** (dtype.nbits - 1)), 2 ** (dtype.nbits - 1)
        return rng.integers(lo, hi, shape).astype(np.int16)
    x = rng.standard_normal(shape).astype(np.float32)
    x *= np.exp2(rng.integers(-130, 100, shape)).astype(np.float32)
    special = rng.random(shape) < 0.1
    x[special] = rng.choice(_SPECIALS, special.sum())
    return x


def _sequential(a, b):
    """acc = 0, then acc = a[:, k] * b[k, :] + acc for k in order, each
    operation rounded (float32) or wrapped (int16) on its own."""
    acc = np.zeros((a.shape[0], b.shape[1]), a.dtype)
    with np.errstate(all="ignore"):
        for k in range(a.shape[1]):
            acc = a[:, k, None] * b[None, k, :] + acc
    return acc


def _assert_bits_equal(got, expect):
    got = np.asarray(got)
    assert got.shape == expect.shape
    if expect.dtype.kind != "f":
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, expect.astype(np.int32))
        return
    assert got.dtype == np.float32
    ok = (got.view(np.uint32) == expect.view(np.uint32)) | (
        np.isnan(got) & np.isnan(expect))
    assert ok.all(), f"{(~ok).sum()} of {ok.size} outputs differ"


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
@pytest.mark.parametrize("mkn", [(1, 1, 32), (5, 3, 33), (40, 9, 64),
                                 (360, 2, 1000)],
                         ids=lambda s: "x".join(map(str, s)))
def test_contraction_matches_sequential_reference(mkn, dtype, basis):
    m, k, n = mkn
    t = _DTYPES[dtype]
    rng = np.random.default_rng([m, k, n, len(dtype), len(basis)])
    a, b = _operand(t, rng, (m, k)), _operand(t, rng, (k, n))
    got = pim.compile(_MATMUL, dtype=t)(a, b, basis=basis)
    _assert_bits_equal(got, _sequential(a, b))


def test_step_is_the_fused_mac_schedule():
    """The step compiles through the MAC's own cache entry: the same
    schedule object, 12968 memristive and 8697 dram gates."""
    fn = pim.compile(_MATMUL, dtype=pim.f32)
    mac = pim.compile(_MAC, dtype=pim.f32)
    assert isinstance(fn.program, ir.Contraction)
    assert fn.program.step == mac.program
    assert (fn.program.carry_in, fn.program.carry_out) == (2, 0)
    for basis, gates in (("memristive", 12968), ("dram", 8697)):
        assert fn.compiled(basis) is mac.compiled(basis)
        assert fn.compiled(basis).num_gates == gates
        assert fn.cost(basis) == mac.cost(basis)
    operands, acc, out = fn.program.slots(fn.compiled("memristive"))
    assert (len(operands), len(acc), len(out)) == (64, 32, 32)
    assert not set(operands) & set(acc)


@pytest.mark.parametrize("fn,match", [
    (lambda a, b: (a @ b) + a, "whole body"),
    (lambda a, b: (a @ b) * 2.0, "whole body"),
    (lambda a, b: (a @ b) @ b, "at most one"),
    (lambda a, b: (a + b) @ b, "whole body"),
    (lambda a, b: b @ a, "in order"),
    (lambda a, b, c: a @ b, "two arguments"),
    (lambda a, b: (a @ b, a @ b), "at most one"),
    (lambda a, b: (a @ b, a), "alone"),
    (lambda a, b: a @ 2.0, "two tracers"),
], ids=["epilogue", "scaled", "second", "prologue", "swapped", "three_args",
        "two_outputs", "extra_output", "scalar"])
def test_contraction_trace_errors(fn, match):
    with pytest.raises(pim.TraceError, match=match):
        pim.compile(fn, dtype=pim.f32)


def test_contraction_mixed_dtypes_is_a_trace_error():
    with pytest.raises(pim.TraceError, match="dtype mismatch"):
        pim.compile(_MATMUL, dtype=(pim.f32, pim.int16))


@pytest.mark.parametrize("shapes", [((4,), (4, 32)), ((2, 3), (4, 32)),
                                    ((2, 3, 1), (3, 32)), ((2, 0), (0, 32)),
                                    ((0, 3), (3, 32))],
                         ids=["a_1d", "mismatched", "a_3d", "k0", "m0"])
def test_contraction_shape_errors(shapes):
    fn = pim.compile(_MATMUL, dtype=pim.f32)
    a, b = (np.zeros(s, np.float32) for s in shapes)
    with pytest.raises(ValueError, match=r"a \[M, K\] and b \[K, N\]"):
        fn(a, b)


def test_contraction_runs_on_no_other_backend():
    fn = pim.compile(_MATMUL, dtype=pim.f32, backend="interpreter")
    with pytest.raises(ValueError, match="runs no contraction"):
        fn(np.ones((1, 2), np.float32), np.ones((2, 32), np.float32))


@pytest.mark.parametrize("k", [3, 9])
def test_contraction_call_is_three_programs(k):
    """Pack, the ``pim_contract`` kernel (its pad and trim inside) and
    unpack, one jitted equation each, at any K; a repeated call traces and
    compiles nothing."""
    fn = pim.compile(_MATMUL, dtype=pim.f32)
    rng = np.random.default_rng(k)
    a, b = _operand(pim.f32, rng, (6, k)), _operand(pim.f32, rng, (k, 40))
    eqns = jax.make_jaxpr(fn)(a, b).jaxpr.eqns
    assert [(e.primitive.name, e.params["name"]) for e in eqns] == [
        ("jit", "pack_contraction"), ("jit", "_run_contract"),
        ("jit", "unpack_contraction")]

    jax.block_until_ready(fn(a, b))
    events = []

    def listener(event, duration, **kwargs):
        if event in _TRACE_EVENTS:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        _assert_bits_equal(fn(a, b), _sequential(a, b))
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert events == []


def test_contract_kernel_takes_step_planes_and_returns_the_result():
    """The ``pallas_call`` takes the ``[K, 64, W]`` step planes, as full-vreg
    ``(8, L)`` tiles of whole word-blocks, and returns only the 32 result
    planes: no per-step accumulator array in HBM."""
    fn = pim.compile(_MATMUL, dtype=pim.f32)
    compiled = fn.compiled("memristive")
    key = pim_bitserial.register_compiled(compiled)
    steps = jax.ShapeDtypeStruct((5, 64, 512), jnp.uint32)
    jaxpr = jax.make_jaxpr(lambda s: pim_bitserial._run_contract(
        *pim_bitserial._gate_arrays(key), s, num_cols=compiled.num_cols,
        slots=fn.program.slots(compiled), interpret=True))(steps)
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [(32, 512)]
    inner = jaxpr.jaxpr.eqns[0].params["jaxpr"]
    calls = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call = calls[0]
    assert call.params["name"] == pim_bitserial.CONTRACT_KERNEL
    # 512 words pad to one word-block of 1024: one (8, 128) tile per plane.
    assert [v.aval.shape for v in call.invars][-1] == (5, 64, 8, 128)
    assert [v.aval.shape for v in call.outvars] == [(32, 8, 128)]
