"""CI benchmark smoke: run the compiler-facing tables end-to-end, fail loudly.

Benchmark modules are import-time consumers of the whole compiler pipeline
(both logic bases, single-op and fused multi-op programs), so running them
on CPU catches silent rot — an op that stops compiling, a basis whose
columns go missing, a table that comes back empty — without asserting any
particular performance number.  The compile-cache hit/miss counters are
printed at the end so cache regressions (e.g. a wrapper recompiling what
``compile_op`` already built) are visible in CI logs.

The executor-kernel benchmark (``exec_modes``, emitted to
``BENCH_exec.json``) must produce its f32 MAC rows with their exact schedule
counts.  Its CPU timings are not checked: on the CPU both Pallas kernels run
in interpret mode, which says nothing about their speed on a TPU.

Usage: ``PYTHONPATH=src python -m benchmarks.smoke``  (exits non-zero on any
exception, empty table or row with missing values).
"""

from __future__ import annotations

import sys

from repro.core import ir

from . import fig3_arith, fig4_cc, fig5_matmul, fig_fused

# Columns every row of each table must carry a non-empty value for.
_REQUIRED = {
    "fig3_arith": ("gates_recorded", "dram_maj_gates", "dram_cycles",
                   "dram_peak_rows", "memristive_tops_ours", "dram_tops_ours",
                   "parallel_cycles", "cols_peak_unsched"),
    "fig4_cc": ("cc", "pim_tops", "dram_cycles", "improvement_vs_gpu_membound"),
    "fig5_matmul": ("reuse_flops_per_byte", "pim_pairs_per_s",
                    "memristive_fusedmac_pairs_per_s", "dram_fusedmac_pairs_per_s",
                    "tpu_membound_pairs_per_s"),
    "fig_fused": ("memristive_gates_fused", "memristive_gates_separate",
                  "memristive_hbm_planes_fused", "dram_cycles_fused",
                  "dram_hbm_planes_separate", "memristive_macs_per_s",
                  "hbm_bytes_fused", "memristive_parallel_cycles_fused",
                  "memristive_peak_cols_unsched"),
}


def check(name: str, rows: list[dict]) -> None:
    if not rows:
        raise SystemExit(f"smoke: {name} produced no rows")
    for row in rows:
        for col in _REQUIRED[name]:
            if row.get(col) in (None, ""):
                raise SystemExit(f"smoke: {name} row {row.get('name')} missing {col!r}")
    print(f"smoke: {name} ok ({len(rows)} rows)", file=sys.stderr)


def check_exec(rows: list[dict]) -> None:
    """Both executor kernels ran the f32 MAC and reported its counts."""
    names = {r["name"]: r for r in rows}
    for kernel in ("loop", "unrolled"):
        row = names.get(f"exec/f32_mac/memristive/{kernel}")
        if row is None or not all(row.get(k) for k in ("gates", "num_cols", "waves")):
            raise SystemExit(f"smoke: exec_modes is missing the f32 MAC {kernel} row")
    print(f"smoke: exec ok ({len(rows)} rows)", file=sys.stderr)


def main() -> None:
    from repro import compile_cache

    from .common import emit
    from .run import write_exec_json

    compile_cache.enable()
    for name, mod in (("fig3_arith", fig3_arith), ("fig4_cc", fig4_cc),
                      ("fig_fused", fig_fused), ("fig5_matmul", fig5_matmul)):
        rows = mod.run()
        check(name, rows)
        emit(rows)
    check_exec(write_exec_json("BENCH_exec.json"))
    stats = ir.cache_stats()
    print(f"smoke: compile cache hits={stats['hits']} misses={stats['misses']}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
