"""Arithmetic on host-clock samples: the percentile rule, the measured
window of a run that has to finish, and the 10-sim-ms sampler."""

from __future__ import annotations

import math
from typing import Sequence

SIM_STEP_NS = 10_000_000  # the 10 simulated ms a run-control user steps by


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q`` %
    of the samples at or below it.  No interpolation, so the value is one a
    request really took; ``q`` = 95 over 220 samples leaves 11 beyond it."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def last_window(samples: Sequence[tuple[float, int]], seconds: float):
    """The measured window of a run that must finish to be compared.

    ``samples`` are ``(wall, sim_ns)`` pairs in order, one per engine
    window, ending at the run's last.  The window is the last ``seconds``
    of wall: it opens at the first sample whose wall is at or after
    ``end - seconds`` and closes at the last sample.  Returns
    ``(i_open, whole)``: the index of the opening sample and whether the
    whole run was shorter than ``seconds`` (the window is then all of it).
    """
    if len(samples) < 2:
        raise ValueError("a window needs at least two samples")
    end = samples[-1][0]
    whole = end - samples[0][0] <= seconds
    if whole:
        return 0, True
    i_open = next(i for i, (w, _s) in enumerate(samples) if w >= end - seconds)
    return min(i_open, len(samples) - 2), False


def window_rate(samples: Sequence[tuple[float, int]], i_open: int):
    """``(sim_seconds, wall_seconds)`` between sample ``i_open`` and the
    last: all the work and all the time of the window."""
    w0, s0 = samples[i_open]
    w1, s1 = samples[-1]
    return (s1 - s0) / 1e9, w1 - w0


def sim_step_walls(samples: Sequence[tuple[float, int]],
                   step_ns: int = SIM_STEP_NS) -> list[float]:
    """Wall seconds each successive ``step_ns`` of simulated time took.

    Crossing k is the wall of the first sample whose sim time reached
    ``k * step_ns``; the result is the differences of successive crossings
    that lie inside ``samples``.  A window that jumps several steps at once
    gives the later ones a wall of 0 beyond the first: they were delivered
    together, which is what a user stepping by 10 ms would see."""
    crossings: list[float] = []
    k = None
    for wall, sim in samples:
        reached = sim // step_ns
        if k is None:
            k = reached  # steps before the first sample are not in the span
            continue
        while k < reached:
            k += 1
            crossings.append(wall)
    first = samples[0][0] if samples else 0.0
    out, prev = [], first
    for c in crossings:
        out.append(c - prev)
        prev = c
    return out


def change_walls(samples: Sequence[tuple[float, int]]) -> list[float]:
    """Wall seconds between successive changes of a counter sampled as
    ``(wall, count)``: one entry per change (a jump of n counts n - 1
    further entries of 0)."""
    out: list[float] = []
    if not samples:
        return out
    prev_wall, prev = samples[0]
    for wall, count in samples[1:]:
        if count != prev:
            out.append(wall - prev_wall)
            out.extend([0.0] * (count - prev - 1))
            prev_wall, prev = wall, count
    return out

