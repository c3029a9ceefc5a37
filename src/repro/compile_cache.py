"""JAX's persistent compilation cache, for the repo's entry points.

``chip_smoke.py`` and the ``benchmarks`` mains call :func:`enable` before
they compile anything; no library module calls it.  A fixed directory is
part of every cache key, so the path never holds a temp name, a pid or a
time: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
otherwise ``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's default (1 s) skips the straight-line segment kernels, which compile
# in about a second each on a TPU.
MIN_COMPILE_SECS = 0.1


def enable() -> str | None:
    """Turn the persistent cache on and return the directory it uses.

    On the CPU it stays off unless ``JAX_COMPILATION_CACHE_DIR`` is set:
    XLA-CPU logs a machine-feature mismatch error for every entry it loads
    back, and CPU runs are tests, not chip measurements."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
