"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once at start-up; nothing calls it on
import, so library users and the compile-only tests keep JAX's own setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``:
the directory is part of a cache entry's key, so a path built from a
temporary directory, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
