"""Executor-kernel shootout: wave-scheduled straight-line vs fori_loop.

Races the unrolled kernel (``pim_bitserial.run_unrolled``) against the loop
kernel (``run_loop``) and the ``interpreter`` scan oracle on fused MAC
programs, reporting per kernel the schedule shape the compiler produced —
gates, peak columns after the ``reorder`` pass, dependency waves
(``parallel_cycles``) — next to measured wall time per dispatch, and the
kernel the ``pallas`` backend picks (``auto_kernel``).  Rows are named
``exec/<case>/<basis>/{interpreter,loop,unrolled}``.
``benchmarks/run.py --json BENCH_exec.json`` emits the rows as JSON, and
``benchmarks/smoke.py`` checks that they are there.

The first unrolled dispatch pays the straight-line XLA compile (tens of
seconds for the 13k-gate f32 MAC — the schedule splits into
``UNROLL_SEGMENT_GATES`` kernels); ``us_per_call`` times the steady state,
which is what a benchmarking sweep runs thousands of times.

On the CPU both Pallas kernels run in interpret mode, so these timings
measure XLA-CPU and the Pallas interpreter, not the chip; the ``interpreter``
row is the plain ``lax.scan`` of the same per-gate dispatch.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import repro.pim as pim
from repro.core import ir

from .common import run_cli, time_fn

N_ELEMS = 4096

# dtype rows: f32 is the CI-gated case; int8 shows the gate-count threshold
# picking `unrolled` on its own.
_CASES = {"f32_mac": pim.f32, "int8_mac": pim.int8}


def _planes(mac, dtype, rng):
    if dtype.kind == "fixed":
        lo, hi = -(2 ** (dtype.nbits - 1)), 2 ** (dtype.nbits - 1)
        arrays = [jnp.asarray(rng.integers(lo, hi, N_ELEMS).astype(np.int32))
                  for _ in range(3)]
    else:
        arrays = [jnp.asarray(rng.standard_normal(N_ELEMS).astype(np.float32))
                  for _ in range(3)]
    return jnp.stack([p for t, x in zip(mac.in_types, arrays)
                      for p in t.to_planes(t.cast(x))])


def run(bases: tuple[str, ...] = ("memristive",),
        passes: tuple[str, ...] | None = None) -> list[dict]:
    from repro.kernels import pim_bitserial

    interpreter = ir.get_backend("interpreter")
    runners = {"interpreter": lambda c, p: interpreter.run(c, p).planes,
               **pim_bitserial.KERNELS}
    passes = ir.DEFAULT_PASSES if passes is None else passes
    rng = np.random.default_rng(0)
    rows = []
    for name, dtype in _CASES.items():
        mac = pim.compile(lambda a, b, c: a * b + c, dtype=dtype)
        # Time the executor dispatch alone, on pre-packed planes — plane
        # pack/unpack is shared by every kernel and would otherwise drown the
        # kernel difference.
        planes = _planes(mac, dtype, rng)
        for basis in bases:
            compiled = mac.compiled(basis=basis, passes=passes)
            for kernel, run_kernel in runners.items():
                us = time_fn(
                    lambda run_kernel=run_kernel, c=compiled:
                        run_kernel(c, planes),
                    warmup=1, iters=3)
                rows.append({
                    "name": f"exec/{name}/{basis}/{kernel}",
                    "us_per_call": f"{us:.0f}",
                    "gates": compiled.num_gates,
                    "num_cols": compiled.num_cols,
                    "waves": compiled.num_waves,
                    "auto_kernel": pim_bitserial.auto_kernel(compiled),
                })
    return rows


def main():
    run_cli(run)


if __name__ == "__main__":
    main()
