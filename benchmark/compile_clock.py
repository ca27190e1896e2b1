"""Compile seconds, compile count and persistent-cache hits, from JAX's
own monitoring events (a copy of ``chip_smoke.CompileClock``)."""

from __future__ import annotations


class CompileClock:
    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self):
        import jax

        self.t = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        key = self.EVENTS.get(event)
        if key is not None:
            self.t[key] += secs
            self.compiles += key == "compile_s"

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def snapshot(self) -> dict:
        return {**self.t, "compiles": self.compiles,
                "cache_hits": self.cache_hits}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
