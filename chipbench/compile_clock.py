"""Backend compiles, their seconds and persistent-cache hits, from JAX's
monitoring events.  A cache hit is counted among the compiles, with its
retrieval time as its seconds."""
from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.secs += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1
