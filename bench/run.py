"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout.  It exits non-zero and prints no result
line unless JAX's first device is a TPU whose ``device_kind`` is in
``bench/peaks.json`` and there are as many devices as the cell asks for.
Otherwise the last line of standard output is the result: one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` with ``--trace 1``), then ``checks``: each number
compared with its limit, which also end standard error.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

# JAX's persistent compilation cache, so that only a checkout's first run of
# a cell compiles.  A fixed path inside the checkout (the path is part of
# every cache key), or JAX_COMPILATION_CACHE_DIR where that is set.
CACHE_DIR = ROOT / ".jax_cache"


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = _parse(argv)
    benchmark = harness.load_benchmark()
    entry = {w["name"]: w for w in benchmark["workloads"]}.get(args.workload)
    if entry is None:
        print(f"run: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < entry["chips"]:
        print(f"run: the cell needs {entry['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    try:
        peaks = harness.load_peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"run: {e.args[0]}", file=sys.stderr)
        return 1

    print(f"run: {args.workload} seed={args.seed} device={devices[0].device_kind} "
          f"compile_cache={enable_compile_cache()}", file=sys.stderr, flush=True)
    cell = harness.load_cell(args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              benchmark=benchmark, peaks=peaks,
                              t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
