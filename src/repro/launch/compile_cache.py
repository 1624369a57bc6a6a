"""JAX's persistent compilation cache for the entry points.

`enable_compile_cache()` is called by `chip_smoke.py`, `repro.launch.serve`
and `benchmarks/engine_hotpath.py` once their arguments are parsed — never
at import time.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` (git-ignored): the directory is part of the cache key,
so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
