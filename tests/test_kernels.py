"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp ref oracles
(on the CPU the Pallas kernel bodies run in interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aritpim, bitplanes, ir
from repro.kernels import ops, ref

np.seterr(all="ignore")


@pytest.mark.parametrize("n", [1, 31, 32, 33, 255, 1000])
def test_bitserial_float_add_sweep(n):
    rng = np.random.default_rng(n)
    x = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32).view(np.float32)
    y = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32).view(np.float32)
    got = np.asarray(ops.pim_float_add(x, y))
    exp = (x + y).astype(np.float32)
    ok = (got.view(np.uint32) == exp.view(np.uint32)) | (np.isnan(got) & np.isnan(exp))
    assert ok.all()


@pytest.mark.parametrize("nbits", [8, 16, 32])
def test_bitserial_fixed_add_sweep(nbits):
    rng = np.random.default_rng(nbits)
    lo, hi = -(2 ** (nbits - 1)), 2 ** (nbits - 1)
    x = rng.integers(lo, hi, 300, dtype=np.int64).astype(np.int32)
    y = rng.integers(lo, hi, 300, dtype=np.int64).astype(np.int32)
    got = np.asarray(ops.pim_fixed_add(x, y, nbits=nbits))
    mask = (1 << nbits) - 1
    exp = (x.astype(np.int64) + y.astype(np.int64)) & mask
    exp = np.where(exp >= hi, exp - (1 << nbits), exp).astype(np.int32)
    assert np.array_equal(got, exp)


def test_bitserial_matches_scan_oracle():
    """Pallas executor vs machine.execute_schedule on the same schedule."""
    sched = aritpim.build_schedule("float_mul", compress=True)
    compiled = ir.CompiledSchedule.from_legacy(sched, key="float_mul32")
    rng = np.random.default_rng(7)
    x = rng.normal(size=64).astype(np.float32)
    y = rng.normal(size=64).astype(np.float32)
    planes = jnp.stack(
        bitplanes.f32_to_planes(jnp.asarray(x)) + bitplanes.f32_to_planes(jnp.asarray(y))
    )
    got = ir.get_backend("pallas").run(compiled, planes).planes
    oracle = ref.bitserial_ref(sched, planes)
    assert np.array_equal(np.asarray(got), np.asarray(oracle))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 128, 128), (2, 256, 384, 512), (3, 128, 256, 128)])
def test_matmul_kernel_sweep(shape, dtype):
    G, M, K, N = shape
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(G, M, K)), dtype)
    b = jnp.asarray(rng.normal(size=(G, K, N)), dtype)
    got = ops.pim_matmul_op(a, b)
    exp = ref.matmul_ref(a, b)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32), rtol=tol, atol=tol * 8
    )


@pytest.mark.parametrize("blocks", [(128, 128, 128), (64, 128, 256)])
def test_matmul_kernel_block_shapes(blocks):
    bm, bk, bn = blocks
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(1, 256, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1, 256, 256)), jnp.float32)
    got = ops.pim_matmul_op(a, b, bm=bm, bk=bk, bn=bn)
    exp = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-4, atol=1e-3)


def test_schedule_info_reports_gates_and_columns():
    gates, cols = ops.schedule_info("fixed_add")
    assert gates >= 288 and cols <= 1024
