"""JAX's own compile events, to split set-up into compiling and loading.

A persistent-cache hit still raises a backend-compile event, whose seconds
are then the cache read.
"""
from __future__ import annotations

import time

import jax


class CompileClock:
    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = []      # (program, seconds) per backend compile
        self.ends = []          # the host clock at the end of each
        self.cache_hits = 0
        self.front_s = 0.0      # tracing + lowering; nested jits count again

    def on_duration(self, event, duration_secs, fun_name="?", **kwargs):
        if event == self.BACKEND:
            self.compiles.append((fun_name, duration_secs))
            self.ends.append(time.monotonic())
        elif event in self.FRONT:
            self.front_s += duration_secs

    def on_event(self, event, **kwargs):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)

    def between(self, t0: float, t1: float) -> int:
        """Backend compiles (cache loads included) that ended in [t0, t1]."""
        return sum(t0 <= t <= t1 for t in self.ends)

    def report(self) -> str:
        name, slowest = max(self.compiles, key=lambda c: c[1],
                            default=("-", 0.0))
        return (f"backend compile {sum(s for _, s in self.compiles):.3f} s "
                f"over {len(self.compiles)} programs ({self.cache_hits} from "
                f"the persistent cache; slowest {name} {slowest:.3f} s), "
                f"trace+lower {self.front_s:.3f} s")
