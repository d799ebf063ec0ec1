"""What JAX traced, compiled or loaded from its cache, and what Python's
garbage collector cost, in each phase of a run.

A run names its phases (``watch.phase = "window"``); the listeners add
each event to the phase current when it fires.  Nothing may compile inside
the measured window, so the run logs the window's counts beside the
warm-up's.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Dict

DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
             "/jax/core/compile/backend_compile_duration": "compiles"}
EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}


class Watch:
    def __init__(self) -> None:
        import jax

        self.phase = "setup"
        self.counts: Dict[str, collections.Counter] = \
            collections.defaultdict(collections.Counter)
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in DURATIONS:
            c = self.counts[self.phase]
            c[DURATIONS[event]] += 1
            c[DURATIONS[event] + "_s"] += secs

    def _event(self, event: str, **_) -> None:
        if event in EVENTS:
            self.counts[self.phase][EVENTS[event]] += 1

    def _gc(self, stage: str, info: Dict) -> None:
        if stage == "start":
            self._gc_t0 = time.perf_counter()
            return
        c = self.counts[self.phase]
        c[f"gc{info['generation']}"] += 1
        c["gc_s"] += time.perf_counter() - self._gc_t0

    def summary(self, phase: str) -> str:
        c = self.counts.get(phase, {})
        return ", ".join(f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                         for k, v in sorted(c.items())) or "nothing"
