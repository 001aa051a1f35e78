"""Counts XLA programs compiled or loaded from the persistent cache, via
``jax.monitoring``, so that set-up and the measured window can each report
what they compiled. JAX reports a backend-compile duration for every
program it builds, loaded from the cache or not, and a cache hit for each
one it loaded: programs compiled are the difference."""
from __future__ import annotations

import jax


class CompileCounter:
    def __init__(self):
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.seconds, self.cache_hits

    def since(self, snap: tuple[int, float, int]) -> dict:
        built, hits = self.compiles - snap[0], self.cache_hits - snap[2]
        return {"programs": built, "cache_hits": hits, "compiled": built - hits,
                "seconds": self.seconds - snap[1]}
