"""Counts what JAX compiles, from JAX's own monitoring events.

Copied from ``chip_smoke.py`` (``CompileMeter``) and extended by a count of
backend compilations, so that a driver can show that nothing compiled (or
was fetched from the persistent cache) inside its measured window.
"""


class CompileMeter:
    _PHASES = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.programs = 0     # backend compilations or cache retrievals
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self._PHASES:
            self.compile_s += seconds
        if event == self._PHASES[2]:
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1
