"""Seconds JAX spends compiling, from its own monitoring events."""
from __future__ import annotations

# JAX's compile-duration events: trace, lower, compile or cache load
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Compile seconds, backend compiles and persistent-cache hits while
    the ``with`` block runs.  Nested events (a jit traced inside another)
    count once: ``seconds`` is the length of the union of their spans."""

    def __enter__(self):
        import jax
        self._spans, self.cache_hits, self.backend_compiles = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.backend_compiles += 1

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self._spans.append((start, end))

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total
