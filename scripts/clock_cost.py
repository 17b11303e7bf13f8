"""What the host-phase clock (shadow_tpu/obs/clock.py) costs per hybrid
turn, on this host: a loop of stub turns of the hybrid's shape — ten spans
a turn, eight notes, real ``TraceAnnotation``s — (a) with no profiler
session and no Recorder, which is every product run; (b) inside a
``jax.profiler`` session opened as the benchmark opens it (host tracer level
1, Python tracer off), so the TraceMes record; (c) with a Recorder that
traces (``--obs-trace``); and docs/observability.md's own "per window"
sequence (one Recorder phase span + three metric updates + one
``[window-agg]`` emit) for its 11.3 us figure.

Usage: python scripts/clock_cost.py [turns]     (prints microseconds)
"""

import io
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402

from shadow_tpu.backend.hybrid import (  # noqa: E402
    _OBS_PHASE, TURN_NOTES, TURN_PHASES)
from shadow_tpu.engine.run_control import PerfLog  # noqa: E402
from shadow_tpu.obs import Recorder  # noqa: E402
from shadow_tpu.obs.clock import TurnClock  # noqa: E402


def stub_turns(n: int, obs=None) -> float:
    """Microseconds per stub turn."""
    clock = TurnClock(SimpleNamespace(obs=obs), "hybrid", TURN_PHASES,
                      notes=TURN_NOTES, turn_phase="walk",
                      obs_map=_OBS_PHASE)
    t0 = time.perf_counter()
    for i in range(n):
        with clock.turn():
            for p in TURN_PHASES[:-1]:
                with clock.span(p, 3):
                    pass
            for note in TURN_NOTES:
                clock.note(note, i)
    return (time.perf_counter() - t0) / n * 1e6


def obs_window_sequence(n: int) -> float:
    rec = Recorder()
    log = PerfLog(out=io.StringIO())
    t0 = time.perf_counter()
    for i in range(n):
        with rec.phase("window_compute", window_end=i):
            pass
        rec.metrics.count("windows")
        rec.metrics.observe("window_active_hosts", 3)
        rec.metrics.observe("window_span_ns", 1000)
        log.window_agg(3, i, i + 1, i + 2)
    return (time.perf_counter() - t0) / n * 1e6


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000
    stub_turns(2_000)  # warm
    print(f"clock, no session, no Recorder: {stub_turns(n):.2f} us/turn")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            print(f"clock, profiler session open:   {stub_turns(n):.2f} "
                  "us/turn")
        finally:
            jax.profiler.stop_trace()
    print(f"clock, Recorder (metrics only): "
          f"{stub_turns(n, Recorder()):.2f} us/turn")
    rec = Recorder(trace=True, trace_capacity=12 * n)
    print(f"clock, Recorder tracing:        {stub_turns(n, rec):.2f} us/turn "
          f"({rec.tracer.span_count() / n:.0f} Chrome-trace spans a turn)")
    print(f"obs per-window sequence:        {obs_window_sequence(n):.2f} "
          "us/window")


if __name__ == "__main__":
    main()
