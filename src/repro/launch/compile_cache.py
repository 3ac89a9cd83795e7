"""JAX's persistent compilation cache, placed from outside the program.

The cache key includes the cache directory, so a directory that moves never
hits.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this module sets nothing.  Otherwise the cache goes to one fixed directory
inside the checkout (``.jax_cache/``, listed in ``.gitignore``): never a
temporary, per-process or time-stamped path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory: <repo>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
