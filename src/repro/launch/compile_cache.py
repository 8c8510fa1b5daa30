"""JAX's persistent compilation cache, kept at one fixed place.

A restarted job that finds its train step in the cache loads it instead of
compiling it, and compiling is part of the time from a kill to the first
step on restored state.  The cache path is part of what a later process must
find, so it never moves: ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one path and return
    the path.  Called once by each entry point, before anything compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
