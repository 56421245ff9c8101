"""Compile seconds and persistent-cache traffic, from JAX's monitoring
events (compiles on worker threads included).

``backend_compile_duration`` covers every executable a process builds:
an XLA compile, or a load from the persistent compilation cache.  Its
count inside the measured window is the number of programs the window
had to build; it should be zero.
"""
from __future__ import annotations

import threading
from typing import Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileStats:
    """Running totals: build seconds, builds, cache hits, cache misses."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.builds = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.builds += 1

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == HIT_EVENT:
                self.hits += 1
            elif event == MISS_EVENT:
                self.misses += 1

    def snapshot(self) -> Tuple[float, int, int, int]:
        with self._lock:
            return self.seconds, self.builds, self.hits, self.misses

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
