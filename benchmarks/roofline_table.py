"""§Roofline table: collect dry-run records into the per-cell three-term
table + the PIM-offload (Fig 8) verdict for every cell."""

from __future__ import annotations

import glob
import json
import os

from repro.core.analyzer import Workload, analyze

RESULTS = os.environ.get("DRYRUN_DIR", "results/dryrun_baseline")


def load_records(directory: str = RESULTS) -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def _compute_term(r: dict) -> float:
    """Per-cell compute seconds (EXPERIMENTS.md §Dry-run methodology):
    min(cost_analysis, walker) for dense; analytic (ideal × remat × capacity)
    for MoE, whose routing cumsum inflates cost_analysis."""
    ideal = r["model_flops"] / (r["chips"] * 197e12)
    if "moe" in r["arch"] or "grok" in r["arch"]:
        return ideal * (1.33 * 1.25 if "train" in r["shape"] else 1.25)
    return min(r["flops_per_device"] / 197e12, r["compute_s"])


def run() -> list[dict]:
    rows = []
    for r in load_records():
        if r["mesh"] != "16x16":
            continue  # the roofline table is single-pod (exact unrolled accounting)
        w = Workload(
            f'{r["arch"]}×{r["shape"]}',
            flops=max(r["flops_per_device"], 1.0) * r["chips"],
            hbm_bytes=max(r["fused_bytes_per_device"], 1.0) * r["chips"],
            collective_wire_bytes=r["collective_wire_bytes_per_dev"],
        )
        v = analyze(w, chips=r["chips"])
        comp = _compute_term(r)
        bound = max(comp, r["memory_s"], r["collective_s"])
        dom = "compute" if bound == comp else ("memory" if bound == r["memory_s"] else "collective")
        ideal = r["model_flops"] / (r["chips"] * 197e12)
        rows.append({
            "name": f'roofline/{r["arch"]}__{r["shape"]}',
            "us_per_call": "",
            "compute_ms": f'{comp*1e3:.2f}',
            "memory_ms": f'{r["memory_s"]*1e3:.2f}',
            "collective_ms": f'{r["collective_s"]*1e3:.2f}',
            "dominant": dom,
            "mfu_at_bound": f'{ideal/bound:.1%}',
            "fits_hbm": str(r.get("residency", {}).get("fits_16gb_hbm", "?")),
            "pim_offload_quadrant": v.quadrant,
            "pim_wins": str(v.pim_wins),
        })
    if not rows:
        rows.append({"name": "roofline/none", "us_per_call": "",
                     "note": f"no records in {RESULTS}; run launch.dryrun first"})
    return rows


def main():
    from repro import compile_cache

    from .common import emit

    compile_cache.enable()
    emit(run())


if __name__ == "__main__":
    main()
