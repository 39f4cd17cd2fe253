"""Compile accounting from JAX's monitoring events.

Copied from the smoke run's ``CompileClock``: seconds spent in the backend
compiler (or fetching a program from the persistent cache) and the number of
persistent-cache hits.  ``compiles`` counts backend compiles, so a window
that compiles anything shows a count above 0.
"""
from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self, jax):
        self.secs = 0.0
        self.hits = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
