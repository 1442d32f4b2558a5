"""JAX's persistent compilation cache, placed for the entry points.

Every entry point (``chip_smoke.py``, ``benchmarks.run``, the sweep CLI, the
examples) calls ``enable_compile_cache()`` before it compiles anything. The
library never calls it on import, so tests run without a cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper sets nothing. Otherwise the cache goes to ``<repo>/.jax_cache``, a
fixed path: the directory is part of what a cached entry is found by, so a
path built from a temporary name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
