"""Where JAX keeps its persistent compilation cache for the entry points.

The cache key includes the directory, so the path is fixed: it never
depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to DEFAULT_CACHE_DIR.
    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
