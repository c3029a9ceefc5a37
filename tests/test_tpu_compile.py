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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.pim as pim
from repro.core import ir
from repro.kernels import pim_bitserial, pim_matmul

W = 2 ** 15  # packed words per plane: 2^20 elements

_MAC = lambda a, b, c: a * b + c  # noqa: E731
_MATMUL = lambda a, b: a @ b  # noqa: E731


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


def _assert_mosaic(lowered, kernel=None):
    """The compile emitted a Mosaic kernel, named ``kernel`` if given: the
    HLO instruction of the custom call takes the ``pallas_call``'s name,
    which a profiler trace shows as the kernel's op name."""
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert calls
        assert all(re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", line)
                   for line in calls), calls


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
    _assert_mosaic(lowered, kernel="pim_segment_0")


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
    _assert_mosaic(lowered, kernel="pim_loop")


@pytest.mark.parametrize("basis", ["memristive", "dram"])
def test_contract_kernel_compiles(one_chip, basis):
    """``pim_contract`` runs the f32 MAC step K = 3 times per word-block,
    its step planes read from HBM by DMA."""
    fn = pim.compile(lambda a, b: a @ b, dtype=pim.f32)
    compiled = fn.compiled(basis)
    gates = [_sds((compiled.num_gates,), jnp.int32, one_chip)] * 5
    steps = _sds((3, 64, W), jnp.uint32, one_chip)
    lowered = pim_bitserial._run_contract.lower(
        *gates, steps, num_cols=compiled.num_cols,
        slots=fn.program.slots(compiled), interpret=False)
    _assert_mosaic(lowered, kernel="pim_contract")


@pytest.mark.parametrize("kernel", ["pim_loop", "pim_contract"])
@pytest.mark.parametrize("basis", ["memristive", "dram"])
def test_widest_tiles_compile(one_chip, kernel, basis):
    """Each loop kernel at the widest ``(8, L)`` tiles its width rule picks
    for the f32 MAC (160 memristive, 155 dram columns), over two
    word-blocks: the planes the rule counts, and what Mosaic keeps besides,
    fit the scoped VMEM."""
    fn = pim.compile(_MATMUL if kernel == "pim_contract" else _MAC,
                     dtype=pim.f32)
    compiled = fn.compiled(basis)
    n_out = len(compiled.output_slots)
    if kernel == "pim_contract":
        n_in = len(compiled.input_slots) - n_out
        vmem_planes = compiled.num_cols + n_in + 3 * n_out
    else:
        n_in = len(compiled.input_slots)
        vmem_planes = compiled.num_cols + 2 * (n_in + n_out)
    u_max = pim_bitserial.LOOP_VMEM_BYTES // (
        4 * pim_bitserial.TILE_WORDS * vmem_planes)
    words = 2 * u_max * pim_bitserial.TILE_WORDS
    assert pim_bitserial.loop_block_units(words, vmem_planes) == u_max > 1
    gates = [_sds((compiled.num_gates,), jnp.int32, one_chip)] * 5
    if kernel == "pim_contract":
        steps = _sds((2, n_in, words), jnp.uint32, one_chip)
        lowered = pim_bitserial._run_contract.lower(
            *gates, steps, num_cols=compiled.num_cols,
            slots=fn.program.slots(compiled), interpret=False)
    else:
        key = pim_bitserial.register_compiled(compiled)
        planes = _sds((n_in, words), jnp.uint32, one_chip)
        lowered = pim_bitserial._run.lower(
            *gates, planes, schedule_key=key,
            gen=pim_bitserial._GENERATIONS.get(key, 0), interpret=False)
    _assert_mosaic(lowered, kernel=kernel)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_matmul_compiles(one_chip, dtype):
    a = _sds((2, 128, 128), dtype, one_chip)
    b = _sds((2, 128, 128), dtype, one_chip)
    _assert_mosaic(pim_matmul.matmul.lower(a, b, interpret=False))


@pytest.mark.parametrize("dtype", [pim.f32, pim.bf16, pim.int8],
                         ids=lambda t: t.name)
def test_pack_unpack_compile(one_chip, dtype):
    """Pack and unpack compile as plain XLA programs: no Mosaic kernel,
    which a trace would count as executor kernel time."""
    from repro.core import bitplanes

    carrier = {"float32": jnp.float32, "bf16": jnp.bfloat16}.get(
        dtype.kind, jnp.int32)
    n = 32 * W
    x = _sds((n,), carrier, one_chip)
    planes = _sds((3 * dtype.width, W), jnp.uint32, one_chip)
    for lowered in (bitplanes.pack.lower((dtype,) * 3, x, x, x),
                    bitplanes.unpack.lower((dtype,) * 3, planes, n)):
        assert "tpu_custom_call" not in lowered.compile().as_text()
