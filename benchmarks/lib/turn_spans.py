"""The window's rows of the hybrid engine's per-turn ring
(``raw["sync_stats"]["turn_spans"]``: one row per turn of the program's
host-phase clock, ``shadow_tpu/obs/clock.py``; a row names its phases'
seconds, its ``perf_counter`` bounds, ``window_end_ns``, ``dispatches``
and ``worker_exec_max_s``).

The measured window is the run's last ``--seconds`` of wall, which are its
last ``raw["window_sim_s"]`` simulated seconds, so the window's rows are
those whose ``window_end_ns`` is later than the last row's less
``window_sim_s`` x 1e9.  A ``raw`` without the ring (a program that has no
such clock) gives ``None``, and so does every reader built on this.
"""

from __future__ import annotations

from typing import Optional, Sequence


def window_rows(raw: dict) -> Optional[list]:
    ring = (raw.get("sync_stats") or {}).get("turn_spans")
    sim_s = raw.get("window_sim_s")
    if not ring or not sim_s:
        return None
    rows = list(ring)
    opens_ns = rows[-1].window_end_ns - sim_s * 1e9
    return [r for r in rows if r.window_end_ns > opens_ns]


def phase_seconds(rows: Sequence, phases: Sequence[str]) -> float:
    return sum(getattr(r, p) for r in rows for p in phases)


def ms_per_dispatch(raw: dict, phases: Sequence[str]) -> Optional[float]:
    """Milliseconds of ``phases`` per device dispatch inside the window:
    per ``device_turns``, as ``device_sync_ms_per_turn`` is."""
    rows = window_rows(raw)
    if not rows:
        return None
    dispatches = sum(r.dispatches for r in rows)
    if not dispatches:
        return None
    return 1e3 * phase_seconds(rows, phases) / dispatches
