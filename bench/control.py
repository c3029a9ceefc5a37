"""Readings the limit of ``correct`` is set from, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds 3

For each of ``--seeds`` it runs the cell's program through the harness
(a short window at the cell's own size and load) and prints the number
compared, ``mismatched_elements``: the lower reading is the largest of
these.  For each of ``--control-seeds`` it runs the same with the control
in the program's place (the configuration's reference one precision lower,
jitted on the chip): the upper reading is the smallest of these.  The last
line is a JSON object with both lists.  The benchmark's own runs never run
this.  Like ``run.py`` it refuses to run without a TPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import run as run_cmd  # noqa: E402


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_seeds, required=True)
    p.add_argument("--control-seeds", type=_seeds, required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    args = p.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"control: needs a TPU, JAX found {device.platform!r}",
              file=sys.stderr)
        return 1
    peaks = harness.load_peaks(device.device_kind)
    run_cmd.enable_compile_cache()
    benchmark = harness.load_benchmark()
    cell = harness.load_cell(args.workload)
    control = jax.jit(cell.config.control)

    readings = {"workload": args.workload, "program": [], "control": []}
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        wrap = None if kind == "program" else (lambda _program: control)
        for seed in seeds:
            result = harness.run_cell(cell, seed, args.seconds, False,
                                      benchmark=benchmark, peaks=peaks,
                                      t_process=time.perf_counter(), wrap=wrap)
            value = result["checks"]["mismatched_elements"]["value"]
            readings[kind].append([seed, value, result["attempted"]])
            print(f"{kind} seed={seed} mismatched_elements={value} "
                  f"correct={result['correct']} "
                  f"dispatches={result['attempted']}", flush=True)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
