"""Counts XLA compiles and persistent-cache loads from JAX's monitoring
events, so that a run can show that nothing compiles inside its window."""

from __future__ import annotations

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Sums XLA backend-compile seconds and persistent-cache hits, read
    from JAX's monitoring events (a hit's duration is its cache load)."""

    def __init__(self):
        self.secs = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.secs += secs
            self.count += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HITS:
            self.hits += 1

    def snapshot(self):
        return self.secs, self.count, self.hits
