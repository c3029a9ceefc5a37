"""Benchmark harness: one module per paper table/figure.

  fig3_arith      — §3 vectored arithmetic throughput/efficiency
  fig4_cc         — §3 compute-complexity vs improvement
  fig_fused       — fused multi-op programs (MAC) vs separate dispatches
  fig5_matmul     — §4 batched matmul reuse crossover
  fig6_cnn_infer  — §5 CNN inference
  fig7_cnn_train  — §5 CNN training

Prints ``name,us_per_call,derived`` CSV.  The executor-mode shootout
(``exec_modes``, unrolled vs fori_loop) is not part of the default sweep —
its straight-line compile is expensive; run it via ``--json PATH`` (which
runs only that benchmark and writes its rows as JSON, the
``BENCH_exec.json`` perf-trajectory artifact checked by CI), via
``python -m benchmarks.exec_modes``, or via ``benchmarks.smoke``.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

from repro import compile_cache


def write_exec_json(path: str) -> list[dict]:
    """Run the executor-mode benchmark and write its rows to ``path``."""
    from . import exec_modes
    from .common import emit

    rows = exec_modes.run()
    with open(path, "w") as f:
        json.dump({"benchmark": "exec_modes", "rows": rows}, f, indent=2,
                  sort_keys=True)
        f.write("\n")
    emit([dict(r) for r in rows])
    return rows


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="ConvPIM benchmark harness")
    parser.add_argument(
        "--json", metavar="BENCH_exec.json", default=None,
        help="run only the executor-mode benchmark and write its rows "
             "(gates, num_cols, waves, us per executor mode) as JSON")
    args = parser.parse_args(argv)

    compile_cache.enable()
    if args.json is not None:
        write_exec_json(args.json)
        return

    from . import (fig3_arith, fig4_cc, fig5_matmul, fig6_cnn_infer,
                   fig7_cnn_train, fig_fused)
    from .common import emit

    failures = 0
    for mod in (fig3_arith, fig4_cc, fig_fused, fig5_matmul, fig6_cnn_infer,
                fig7_cnn_train):
        try:
            emit(mod.run())
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{mod.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
