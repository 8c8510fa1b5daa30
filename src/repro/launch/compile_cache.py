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

from repro.utils import tracing

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one path and return
    the path.  Called once by each entry point, before anything compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# JAX's compile events, as the tracer's counters (``utils/tracing.py``).
# ``compile_s`` wraps the persistent cache's lookup, so on a hit it holds
# ``cache_load_s``; a miss is counted when the compiled program is written.
JIT_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "repro.jit.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "repro.jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "repro.jit.compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "repro.jit.cache_load_s",
}
JIT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "repro.jit.cache_hits",
    "/jax/compilation_cache/cache_misses": "repro.jit.cache_misses",
}


def _on_jit_duration(event: str, duration: float, **_) -> None:
    name = JIT_DURATIONS.get(event)
    if name is not None:
        tracing.count(name, duration)


def _on_jit_event(event: str, **_) -> None:
    name = JIT_EVENTS.get(event)
    if name is not None:
        tracing.count(name)


_watching = False


def watch_jit(on: bool) -> None:
    """Register (or remove) the listeners that feed ``repro.jit.*``."""
    global _watching
    if on and not _watching:
        jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)
        jax.monitoring.register_event_listener(_on_jit_event)
    elif _watching and not on:
        jax.monitoring.unregister_event_duration_listener(_on_jit_duration)
        jax.monitoring.unregister_event_listener(_on_jit_event)
    _watching = on
