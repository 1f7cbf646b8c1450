"""JAX's own compile events, summed over the process."""
from __future__ import annotations

import jax


class CompileClock:
    """Backend compile seconds (or, on a persistent-cache hit, the
    cache read), trace-and-lower seconds, cache hits, and the number of
    backend compiles, so that a window can check it compiled nothing."""

    def __init__(self):
        self.backend_s = self.trace_lower_s = 0.0
        self.compiles = self.cache_hits = 0

        def on_duration(event, secs, **kw):
            del kw
            if event == "/jax/core/compile/backend_compile_duration":
                self.backend_s += secs
                self.compiles += 1
            elif event in ("/jax/core/compile/jaxpr_trace_duration",
                           "/jax/core/compile/jaxpr_to_mlir_module_duration"):
                self.trace_lower_s += secs

        def on_event(event, **kw):
            del kw
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"backend_compile_s={self.backend_s:.3f} "
                f"trace_lower_s={self.trace_lower_s:.3f} "
                f"compiles={self.compiles} "
                f"persistent_cache_hits={self.cache_hits}")
