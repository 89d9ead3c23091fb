"""Compile work JAX does, counted through `jax.monitoring`: traces of a
function for new arguments, backend compiles and their seconds, and
persistent-cache hits.  Inside a measured window all three stay 0."""
from __future__ import annotations

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == COMPILE:
            self.seconds += secs
            self.compiles += 1
        elif event == TRACE:
            self.traces += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"traces": self.traces, "compiles": self.compiles,
                "compile_s": self.seconds, "cache_hits": self.cache_hits}
