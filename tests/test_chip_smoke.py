"""``chip_smoke.py`` on the CPU: the script refuses to run without a TPU,
and its phases — inputs with inf, NaN and subnormals, the numpy host
oracle, the bit-for-bit comparison — pass at a tiny size with the kernels
in interpret mode.  The f32 MAC phase on the unrolled kernel is left out
here: its 13 straight-line segments take about two minutes to compile on
XLA-CPU, and ``test_executor.py::test_fused_f32_mac_unrolled_bit_exact``
checks that kernel bit for bit; the int8 MAC stands in for it on each
kernel."""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

_SMALL = 4096  # elements: the specials head needs 12^3 = 1728
_PHASES = [p for p in chip_smoke.PHASES if p.name != "f32_mac_unrolled"]


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.mark.parametrize("phase", _PHASES, ids=lambda p: p.name)
def test_phase_on_cpu(phase):
    if isinstance(phase, chip_smoke.Matmul):
        small = dataclasses.replace(phase, shape=(1, 128, 256, 128))
    else:
        small = dataclasses.replace(phase, elements=_SMALL)
    result = chip_smoke.run_phase(small)
    assert result["mismatches"] == 0, result
    if getattr(small, "dtype", None) == "f32":
        # the inputs reach the IEEE corner cases the oracle must agree on
        assert result["subnormal_out"] and result["nan_out"] and result["inf_out"]
    if isinstance(small, chip_smoke.Matmul):
        assert result["f32_accurate"], result


@pytest.mark.parametrize("kernel", ["loop", "unrolled"])
def test_phase_on_one_kernel_on_cpu(kernel):
    """A phase that names its kernel runs that entry point between the
    frontend's pack and unpack, and reports it."""
    int8_mac = next(p for p in chip_smoke.PHASES if p.name == "int8_mac_dram")
    small = dataclasses.replace(int8_mac, kernel=kernel, elements=_SMALL)
    result = chip_smoke.run_phase(small)
    assert result["mismatches"] == 0, result
    assert result["kernel"] == kernel
    assert result["segments"] >= 1
