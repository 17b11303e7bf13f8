"""Compile accounting off ``jax.monitoring`` (after chip_smoke.py's
``CompileProbe``): every ``/jax/core/compile/*`` duration (trace, lowering,
backend compile or cache read) with the wall time at which it ended, and
persistent-cache hits and misses."""

from __future__ import annotations

import time

BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileProbe:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self.events: list[tuple[float, float, bool]] = []  # wall, s, backend
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.events.append((time.perf_counter(), secs, event == BACKEND))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def trace_compile_before(self, t: float) -> float:
        """Seconds of tracing, lowering and compiling that ended before
        wall ``t``."""
        return sum(s for (w, s, _b) in self.events if w < t)

    def compiles_between(self, t0: float, t1: float) -> list[float]:
        """Seconds of each backend compile that ended in ``[t0, t1]``."""
        return [s for (w, s, b) in self.events if b and t0 <= w <= t1]
