"""JAX's persistent compilation cache, placed where the next run finds it.

Entry points call :func:`enable_compile_cache` once, before their first
compile; nothing here runs at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing — whoever set the variable owns the cache and its knobs.
* Otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path (the
  path is part of what a later run must match to hit), and every program is
  cached however fast it compiled, so a repeated command compiles nothing.

:func:`compile_cache_stats` reports the directory and the hits and misses
counted since the cache was enabled.
"""
from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache", "compile_cache_stats"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py → the checkout root is three levels up.
_CHECKOUT = Path(__file__).resolve().parents[3]
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts: collections.Counter = collections.Counter()
_state: dict = {}


def _count(event: str, **_kw) -> None:
    if event in _EVENTS:
        _counts[_EVENTS[event]] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on (idempotent); returns its directory."""
    if "dir" in _state:
        return _state["dir"]
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.monitoring.register_event_listener(_count)
    _state["dir"] = path
    return path


def compile_cache_stats() -> dict:
    """``{'dir', 'hits', 'misses'}`` since :func:`enable_compile_cache`."""
    return {"dir": _state.get("dir"), "hits": _counts["hits"],
            "misses": _counts["misses"]}
