"""Chip benchmark of ``pim.compile``: one cell, one seed, one process.

Run from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cells and metrics.  Each configuration is a
module under ``configs/``, each cell a JSON file under ``cells/``, each
per-layer metric a reader under ``metrics/``; the harness finds them by
name, so adding one adds files and edits none.
"""
