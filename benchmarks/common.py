"""Shared benchmark utilities: timing, CSV emission, and the common CLI.

Every compiler-facing benchmark (fig3/fig4/fig5/fig_fused) accepts the same
flags instead of per-script argument handling:

  --basis {memristive,dram,both}   which logic basis' columns to emit
  --passes fold,cse,fuse,cse,dce   override the IR pass pipeline (empty
                                   string = raw, no optimization passes)

``run_cli(run)`` parses them and calls ``run(basis=..., passes=...)``.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro import compile_cache

BASES = ("memristive", "dram")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="ConvPIM benchmark")
    p.add_argument("--basis", choices=(*BASES, "both"), default="both",
                   help="logic basis to report (default: both)")
    p.add_argument("--passes", default=None, metavar="P1,P2,...",
                   help="comma-separated IR pass list overriding the default "
                        "pipeline; pass an empty string for no passes")
    return p.parse_args(argv)


def passes_from_args(args) -> tuple[str, ...] | None:
    """``--passes`` as a pass tuple, or None to keep the default pipeline."""
    if args.passes is None:
        return None
    return tuple(p for p in args.passes.split(",") if p)


def bases_from_args(args) -> tuple[str, ...]:
    return BASES if args.basis == "both" else (args.basis,)


def run_cli(run_fn, argv=None) -> None:
    """Shared benchmark main: parse the common flags, run, emit CSV."""
    args = parse_args(argv)
    compile_cache.enable()
    emit(run_fn(bases=bases_from_args(args), passes=passes_from_args(args)))


def time_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time per call in microseconds (jit-compiled; the warmup
    calls absorb compilation)."""
    assert iters >= 1
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def emit(rows: list[dict]) -> None:
    """Print ``name,us_per_call,derived`` CSV (harness convention)."""
    for r in rows:
        name = r.pop("name")
        us = r.pop("us_per_call", "")
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        print(f"{name},{us},{derived}")
