"""Compile accounting from ``jax.monitoring`` events."""
from __future__ import annotations


class CompileClock:
    """Seconds XLA spent compiling, or loading compiled programs from the
    persistent cache, the number of such compiles, and the programs
    loaded from the cache, since this clock was made."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self.EVENT:
            self.seconds += seconds
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits
