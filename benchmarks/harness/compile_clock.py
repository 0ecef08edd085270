"""Seconds JAX spent tracing, lowering and compiling, from its own
monitoring events (a copy of chip_smoke.CompileClock, kept here so that
the set-up split does not move when the smoke does)."""


class CompileClock:
    """Counts whichever thread compiled: the engine's worker, the load
    generator or the main thread."""

    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self, jax):
        self.trace_s = self.backend_s = 0.0
        self.programs = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self.TRACE:
            self.trace_s += seconds
        elif event == self.BACKEND:
            # a read of the persistent cache reports here too
            self.backend_s += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def snapshot(self):
        return {"trace_s": self.trace_s, "backend_s": self.backend_s,
                "programs": self.programs, "hits": self.hits,
                "misses": self.misses}
