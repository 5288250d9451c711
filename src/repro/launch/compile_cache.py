"""JAX's persistent compilation cache, placed for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it. Otherwise the cache lives in ``.jax_cache/`` at
the repository root: a fixed directory, because the path is part of the
cache key and a cache that moves never hits.

    python chip_smoke.py            # -> <repo>/.jax_cache/
    JAX_COMPILATION_CACHE_DIR=/x python chip_smoke.py   # -> /x only
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it writes to."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
