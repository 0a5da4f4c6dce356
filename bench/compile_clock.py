"""Compile seconds and persistent-cache hits from JAX's monitoring events
(copied from ``chip_smoke.CompileClock``)."""
from __future__ import annotations

import threading


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling or fetching from
    the persistent cache, summed over threads, the number of such events
    (any of them inside a measured window means something compiled
    there), and the cache's hits."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.events = 0
        self.cache_hits = 0
        # Compiles run on the engine's threads too.
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            with self._lock:
                self.total += secs
                self.events += 1

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
