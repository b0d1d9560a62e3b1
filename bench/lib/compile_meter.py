"""Compile accounting from ``jax.monitoring`` events."""
from __future__ import annotations


class CompileMeter:
    """Backend compiles (count and seconds) and persistent-cache hits.

    A program served from the persistent cache still reports a backend
    compile event, of its retrieval time, so ``compiles`` counts every
    program the process had to obtain, from the cache or not.
    """

    def __init__(self, monitoring):
        self.compiles, self.compile_s, self.hits, self.misses = 0, 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "hits": self.hits, "misses": self.misses}
