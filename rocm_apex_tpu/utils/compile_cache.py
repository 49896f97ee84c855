"""The one persistent-compile-cache policy, shared by every entry point
(chip_smoke.py, bench.py, the examples, tools/graphlint.py, the test
suite).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and
this module sets no directory: whoever runs the program places the
cache. Otherwise the cache lives at one fixed path inside the checkout.
The path is part of the cache key, so it is never a temporary name, a
pid or a timestamp: two runs from the same checkout share it.
"""

import os
import pathlib

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache", "CompileCacheCounters"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile and
    return the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    # JAX skips programs that compile in under a second by default; the
    # serving engine's small programs and the test suite's jits are
    # exactly those, and a second run should compile nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class CompileCacheCounters:
    """Counts the backend's own cache events from construction on, in
    ``counts``: ``requests`` (compiles that consulted the cache),
    ``hits`` and ``misses``."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        self.counts = dict.fromkeys(self._EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        name = self._EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1
