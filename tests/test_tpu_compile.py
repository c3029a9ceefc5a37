"""The executor and matmul kernels compiled for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at a real width for device 0 of a
``v5e:2x2`` topology and asserts that the TPU compiler accepted it and
emitted a Mosaic kernel (``tpu_custom_call``).  This catches what interpret
mode cannot — unaligned slices, scalar reads from vector memory, a gate
schedule too long for SMEM — without a chip.  The topology is described
inside a fixture only: only one process may load the TPU library, and every
xdist worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.pim as pim
from repro.core import ir
from repro.kernels import pim_bitserial, pim_matmul

W = 2 ** 15  # packed words per plane: 2^20 elements

_MAC = lambda a, b, c: a * b + c  # noqa: E731


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but cannot
    be read back without a chip; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _register(fn, dtype, basis):
    compiled = pim.compile(fn, dtype=dtype).compiled(basis=basis)
    return compiled, pim_bitserial.register_compiled(compiled)


@pytest.mark.parametrize("dtype,basis", [(pim.f32, "memristive"),
                                         (pim.int8, "dram")],
                         ids=["f32_mac-memristive", "int8_mac-dram"])
def test_unrolled_segment_compiles(one_chip, dtype, basis):
    compiled, key = _register(_MAC, dtype, basis)
    state = _sds((compiled.num_cols, W), jnp.uint32, one_chip)
    lowered = pim_bitserial._run_unrolled_segment.lower(
        state, schedule_key=key, gen=pim_bitserial._GENERATIONS.get(key, 0),
        seg=0, interpret=False)
    _assert_mosaic(lowered)


@pytest.mark.parametrize("op", ["f32_mac", "float_div"])
def test_loop_kernel_compiles(one_chip, op):
    """The SMEM gate arrays fit for the MAC and the longest schedule
    (``float_div`` on the memristive basis, ~19.5k gates)."""
    if op == "f32_mac":
        compiled, key = _register(_MAC, pim.f32, "memristive")
    else:
        compiled = ir.compile_op("float_div", 32)
        key = pim_bitserial.register_compiled(compiled)
    gates = [_sds((compiled.num_gates,), jnp.int32, one_chip)] * 5
    planes = _sds((len(compiled.input_slots), W), jnp.uint32, one_chip)
    lowered = pim_bitserial._run.lower(
        *gates, planes, schedule_key=key,
        gen=pim_bitserial._GENERATIONS.get(key, 0), interpret=False)
    _assert_mosaic(lowered)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_matmul_compiles(one_chip, dtype):
    a = _sds((2, 128, 128), dtype, one_chip)
    b = _sds((2, 128, 128), dtype, one_chip)
    _assert_mosaic(pim_matmul.matmul.lower(a, b, interpret=False))
