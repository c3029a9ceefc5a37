"""Smoke run of the ``pim.compile`` executor on one TPU chip.

Drives the main path once per phase through the public entry points, at
full size: pack arrays into bit-planes, run the compiled gate schedule in
the Pallas executor (``kernels/pim_bitserial.py``), unpack the result.  The
batched matmul kernel (``kernels/pim_matmul.py``) runs at fig5's shape and
at a CNN-sized GEMM.  Inputs are made from a fixed seed and include inf,
NaN and subnormal values; every output is checked bit for bit against a
numpy host oracle (a NaN matches any NaN), the matmuls against a float64
host reference.

Usage::

    python3 chip_smoke.py

It refuses to run unless JAX's first device is a TPU: it then exits
non-zero and prints no result.  Each phase prints one line with its set-up
seconds (trace and passes, then kernel compile), its steady seconds per
dispatch and its mismatch count.  Any mismatch or error exits non-zero;
otherwise the last line is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import sys
import time
import traceback
from pathlib import Path
from typing import Callable

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

STEADY_ITERS = 3
SEED = 0

# Every combination of these fills the head of each elementwise input.
F32_SPECIALS = np.array(
    [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.5, 3.4028235e38,
     1.1754944e-38,  # smallest normal
     1e-45,          # smallest subnormal
     -2.5e-39,       # a subnormal
     5.877472e-39],  # half the smallest normal
    np.float32)

# A matmul element is wrong when it is further than this from the float64
# reference, relative to the largest reference magnitude.  f32 accumulation
# of f32 products stays near 1e-6 at these shapes; one bf16 MXU pass gives
# about 1e-3.
MATMUL_TOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Elementwise:
    """One ``pim.compile`` program run on one basis and executor kernel."""

    name: str
    fn: Callable
    dtype: str  # "f32" | "int8"
    basis: str
    kernel: str | None  # a ``pim_bitserial.KERNELS`` name; None = the backend's choice
    elements: int


@dataclasses.dataclass(frozen=True)
class Matmul:
    name: str
    shape: tuple[int, int, int, int]  # (G, M, K, N)


def _mac(a, b, c):
    return a * b + c


def _div(a, b):
    return a / b


PHASES = (
    Elementwise("f32_mac", _mac, "f32", "memristive", None, 2 ** 24),
    Elementwise("f32_mac_unrolled", _mac, "f32", "memristive", "unrolled",
                2 ** 24),
    Elementwise("int8_mac_dram", _mac, "int8", "dram", None, 2 ** 24),
    Elementwise("f32_div", _div, "f32", "memristive", None, 2 ** 22),
    Matmul("matmul_fig5", (2, 128, 128, 128)),
    Matmul("matmul_cnn", (8, 512, 1152, 256)),
)


# ------------------------------------------------------------------ inputs


def _f32_inputs(rng, n: int, n_args: int) -> list[np.ndarray]:
    """Random bit patterns (NaN, inf and subnormals among them) on even
    elements, normal values scaled by 2^-150..2^0 on odd ones (subnormal
    and underflowing results), and every combination of ``F32_SPECIALS``
    at the head."""
    k = len(F32_SPECIALS)
    head = min(n, k ** n_args)
    idx = np.arange(head)
    xs = []
    for i in range(n_args):
        x = rng.integers(0, 2 ** 32, n, dtype=np.uint32).view(np.float32)
        odd = rng.standard_normal(n // 2, dtype=np.float32)
        x[1::2] = np.ldexp(odd, rng.integers(-150, 1, n // 2)).astype(np.float32)
        x[:head] = F32_SPECIALS[(idx // k ** i) % k]
        xs.append(x)
    return xs


def _int8_inputs(rng, n: int, n_args: int) -> list[np.ndarray]:
    return [rng.integers(-128, 128, n).astype(np.int32) for _ in range(n_args)]


def _oracle(phase: Elementwise, args: list[np.ndarray]) -> np.ndarray:
    if phase.dtype == "f32":
        with np.errstate(all="ignore"):
            return np.asarray(phase.fn(*args), np.float32)
    wide = phase.fn(*(a.astype(np.int64) for a in args))
    return ((wide + 128) % 256 - 128).astype(np.int32)  # int8 wraparound


def _mismatches(got: np.ndarray, exp: np.ndarray) -> int:
    if exp.dtype == np.float32:
        same = got.view(np.uint32) == exp.view(np.uint32)
        ok = same | (np.isnan(got) & np.isnan(exp))
    else:
        ok = got == exp
    return int(np.count_nonzero(~ok))


# ------------------------------------------------------------------ timing


def _steady_seconds(call) -> float:
    import jax

    times = []
    for _ in range(STEADY_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


# ------------------------------------------------------------------ phases


def run_elementwise(phase: Elementwise, seed: int = SEED) -> dict:
    import jax

    import repro.pim as pim
    from repro.core import bitplanes
    from repro.kernels import pim_bitserial

    rng = np.random.default_rng(seed)
    n_args = phase.fn.__code__.co_argcount
    make = _f32_inputs if phase.dtype == "f32" else _int8_inputs
    args = make(rng, phase.elements, n_args)
    expect = _oracle(phase, args)
    dev_args = [jax.device_put(a) for a in args]

    t0 = time.perf_counter()
    fn = pim.compile(phase.fn, dtype=pim.f32 if phase.dtype == "f32" else pim.int8)
    compiled = fn.compiled(basis=phase.basis)
    trace_s = time.perf_counter() - t0

    def call():
        if phase.kernel is None:
            return fn(*dev_args, basis=phase.basis)
        planes = bitplanes.pack(fn.in_types, *dev_args)
        out = pim_bitserial.KERNELS[phase.kernel](compiled, planes)
        return bitplanes.unpack(fn.out_types, out, phase.elements)[0]

    t0 = time.perf_counter()
    out = jax.block_until_ready(call())
    first_s = time.perf_counter() - t0
    steady_s = _steady_seconds(call)

    got = np.asarray(out)
    kernel = phase.kernel or pim_bitserial.auto_kernel(compiled)
    segments = (len(pim_bitserial.executor_plan(compiled).segments)
                if kernel == "unrolled" else 1)
    result = {
        "kernel": kernel,
        "segments": segments,
        "gates": compiled.num_gates,
        "elements": phase.elements,
        "trace_s": trace_s,
        "compile_s": max(first_s - steady_s, 0.0),
        "steady_s": steady_s,
        "mismatches": _mismatches(got, expect),
    }
    if phase.dtype == "f32":
        tiny = np.finfo(np.float32).tiny
        sub = lambda x: int(np.count_nonzero((x != 0) & (np.abs(x) < tiny)))  # noqa: E731
        result.update(
            subnormal_in=sum(sub(a) for a in args),
            subnormal_out=sub(expect),
            nan_out=int(np.count_nonzero(np.isnan(expect))),
            inf_out=int(np.count_nonzero(np.isinf(expect))))
    return result


def run_matmul(phase: Matmul, seed: int = SEED) -> dict:
    import jax

    from repro.kernels import ops

    G, M, K, N = phase.shape
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((G, M, K), dtype=np.float32)
    b = rng.standard_normal((G, K, N), dtype=np.float32)
    ref = np.einsum("gmk,gkn->gmn", a.astype(np.float64), b.astype(np.float64))
    da, db = jax.device_put(a), jax.device_put(b)

    def call():
        return ops.pim_matmul_op(da, db)

    t0 = time.perf_counter()
    out = jax.block_until_ready(call())
    first_s = time.perf_counter() - t0
    steady_s = _steady_seconds(call)

    err = np.abs(np.asarray(out, np.float64) - ref) / np.abs(ref).max()
    return {
        "shape": "x".join(map(str, phase.shape)),
        "elements": G * M * N,
        "compile_s": max(first_s - steady_s, 0.0),
        "steady_s": steady_s,
        "max_rel_err": float(err.max()),
        "f32_accurate": bool(err.max() <= MATMUL_TOL),
        "mismatches": int(np.count_nonzero(err > MATMUL_TOL)),
    }


def run_phase(phase) -> dict:
    if isinstance(phase, Matmul):
        return run_matmul(phase)
    return run_elementwise(phase)


def _format(name: str, result: dict) -> str:
    setup = result.get("trace_s", 0.0) + result["compile_s"]
    fields = " ".join(f"{k}={v!r}" if isinstance(v, float) else f"{k}={v}"
                      for k, v in result.items())
    return f"phase {name}: setup_s={setup!r} {fields}"


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro import compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: kind={device['kind']} count={device['count']} "
          f"compile_cache={compile_cache.enable()}", flush=True)

    failed = []
    for phase in PHASES:
        try:
            result = run_phase(phase)
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            print(f"phase {phase.name}: ERROR", flush=True)
            failed.append(phase.name)
            continue
        print(_format(phase.name, result), flush=True)
        if result["mismatches"]:
            failed.append(phase.name)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
