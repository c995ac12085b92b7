"""Persistent JAX compilation cache for the entry points.

The store's kernels compile once per shape; on a cold process that is
seconds per program. Entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/``) call :func:`enable_compile_cache`
before their first compile so later runs of the same checkout reuse the
programs. The library itself and the tests never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
directory and nothing else is set. Otherwise the cache lives at a fixed
path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): the path is part of the cache key, so it must not move
between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    if path == str(REPO_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second: the
    # small per-shape wrappers around the kernels add up on a cold run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
