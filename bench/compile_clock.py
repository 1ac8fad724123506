"""Running totals of JAX's compile-time events (copied from the repo's
``chip_smoke.py``): seconds spent tracing, lowering and backend-compiling,
and persistent-cache hits and misses.  Trace spans nest, so only the
backend compile is a clean total; it also covers a persistent-cache read.
"""
from __future__ import annotations

from typing import Dict

_DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "backend"}
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


class CompileClock:
    """Listens to ``jax.monitoring`` from construction on."""

    def __init__(self):
        import jax
        self.totals: Dict[str, float] = dict.fromkeys(
            [*_DURATIONS.values(), "compiles", *_EVENTS.values()], 0)

        def on_duration(event, duration, **_):
            name = _DURATIONS.get(event)
            if name is not None:
                self.totals[name] += duration
                if name == "backend":
                    self.totals["compiles"] += 1

        def on_event(event, **_):
            name = _EVENTS.get(event)
            if name is not None:
                self.totals[name] += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)

    def since(self, start: Dict[str, float]) -> Dict[str, float]:
        return {k: v - start[k] for k, v in self.totals.items()}
