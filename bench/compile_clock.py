"""Backend-compile seconds and persistent-cache hits and misses, read from
JAX's monitoring events (a copy of the program's smoke-run clock)."""

from __future__ import annotations

import jax


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
