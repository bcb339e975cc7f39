"""Seconds JAX spends compiling, read from its monitoring events."""
from __future__ import annotations


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and cache hits, read as deltas between marks."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits, self.compiles

    def since(self, mark):
        """``(seconds, cache hits, compiles)`` since ``mark``."""
        return (self.seconds - mark[0], self.hits - mark[1],
                self.compiles - mark[2])
