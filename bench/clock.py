"""Counts JAX compilations while active (a copy of the program's bring-up
listener, extended with the persistent cache's hits).

``compiles`` counts backend compilations, persistent-cache loads included,
and ``seconds`` sums their durations; ``cache_misses`` counts the programs
that the persistent cache did not hold.  Inside a measured window all three
should stay 0.
"""
from __future__ import annotations

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0

    @property
    def cache_misses(self) -> int:
        return self.cache_requests - self.cache_hits

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_REQUEST:
            self.cache_requests += 1
        elif event == CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
