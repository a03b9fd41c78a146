"""The one place that decides where JAX's persistent compilation cache
lives. Called once by each process that compiles: the serve.llm replica,
the train worker and chip_smoke.py.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
stands and no directory is set in code. Otherwise the cache is
``<checkout>/.jax_cache`` (gitignored): the path is part of how a cache is
found again, so it is never under ``~``, a temporary directory, a pid or a
timestamp.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"
_stats: dict | None = None


def enable_compile_cache() -> dict:
    """Turn the persistent compilation cache on (idempotent) and return
    this process's live ``{"dir", "hits", "misses"}`` record: a miss is a
    program this process compiled and wrote, a hit one it read back."""
    global _stats
    if _stats is not None:
        return _stats
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # keep every program: a warm start should compile nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _stats = {"dir": path, "hits": 0, "misses": 0}

    def _count(event: str, **_) -> None:
        if event == _HITS:
            _stats["hits"] += 1
        elif event == _MISSES:
            _stats["misses"] += 1

    jax.monitoring.register_event_listener(_count)
    return _stats
