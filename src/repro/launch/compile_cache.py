"""JAX's persistent compilation cache for the entry points.

``enable()`` is called by the scripts a user runs (``chip_smoke.py``,
``repro.launch.sssp``, ``repro.launch.serve``, ``benchmarks/run.py``),
never while a library module is imported. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is set here. Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed
path (the path is part of the cache key, so one that moved between
runs would never hit), listed in ``.gitignore``.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
