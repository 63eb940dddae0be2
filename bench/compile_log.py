"""Counts XLA backend compiles and persistent-cache hits in this process
through ``jax.monitoring`` (copied from the program's on-chip smoke test)."""
from __future__ import annotations

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
