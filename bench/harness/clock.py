"""Seconds spent lowering and compiling XLA programs, and persistent
compile-cache hits, from JAX's monitoring events.

Copied from the bring-up check so that a later change to the program
cannot change how the benchmark counts compilation.  Loading a program
from the persistent cache is inside ``backend_compile_duration``, so a
cache hit counts its load time.
"""
from __future__ import annotations

EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")
SPANS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
         "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
         "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Running totals, and the wall-clock spans of tracing, lowering and
    compiling (``(what: function, start, end)`` in seconds since the
    epoch), which name the device's idle gaps in a traced run.  Make one
    per process (JAX keeps the listeners)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.spans = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_time_span_listener(self._span)

    def _span(self, event, start, end, **kw):
        if event in SPANS:
            self.spans.append((f"{SPANS[event]}: {kw.get('fun_name', '?')}",
                               start, end))

    def _dur(self, event, duration, **_):
        if event in EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def read(self):
        return self.seconds, self.cache_hits
