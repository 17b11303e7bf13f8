"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device busy
time, the operations that took most of it, and the idle gaps.

Written against a trace recorded on a TPU v5e
(tests/data/small_tpu.xplane.pb, tests/data/record_trace.py): each chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO operation (start and duration in ns on the trace's clock) and
whose line ``XLA Modules`` holds one event per executed program.  A
``while`` / ``conditional`` / ``call`` is an event that CONTAINS the events
of its body, so "an operation ran" means a LEAF event: the time a loop
spends between two body operations is idle, which is what many small
launches cost.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start_trace(trace_dir: str) -> None:
    """Open the profiler with the Python tracer off and the host tracer at
    its lowest level: the device planes are what is read, and a Python
    tracer slows the very host path a traced span is meant to show."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def leaf_intervals(events: list) -> list:
    """``(start, end, name)`` of the events that contain no other event.
    ``events`` are ``(start, duration, name)``; containers are recognised
    by interval nesting, not by name."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves = []
    for i, (start, dur, name) in enumerate(ordered):
        end = start + dur
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[0] < end and nxt[0] + nxt[1] <= end \
                and nxt[:2] != (start, dur):
            continue  # the next event lies inside this one: a container
        leaves.append((start, end, name))
    return leaves


def union_length(intervals: list) -> float:
    """Total length of the union of ``(start, end, ...)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for iv in sorted(intervals, key=lambda iv: iv[0]):
        s, e = iv[0], iv[1]
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def op_label(name: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:80] or "unnamed"


def _gaps(leaves: list, modules: list) -> dict:
    """Idle seconds between leaf operations, by where they fall: inside an
    executing program (loop control, launch gaps) or between programs (the
    host was doing something else: building state, servicing syscalls,
    reading back)."""
    out = defaultdict(float)
    spans = sorted((s, s + d) for s, d, _n in modules)
    ordered = sorted(leaves)
    j = 0
    for (s0, e0, _a), (s1, _e1, _b) in zip(ordered, ordered[1:]):
        if s1 <= e0:
            continue
        while j < len(spans) and spans[j][1] < e0:
            j += 1
        inside = j < len(spans) and spans[j][0] <= e0 and s1 <= spans[j][1]
        out["inside_program" if inside else "between_programs"] += s1 - e0
    return out


def reduce_trace(path: str, window_s: float, n_chips: int = 1) -> dict:
    """Busy seconds (union of leaf device operations, averaged over the
    chips that ran any), the ten operations with most device time and the
    idle gaps, from one ``.xplane.pb``.  ``window_s`` is the host-clock
    length of the traced span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy_per_chip = []
    op_time = defaultdict(float)
    gaps = defaultdict(float)
    n_ops = n_modules = 0
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(e.start_ns, e.duration_ns, e.name)
                       for e in line.events]
            elif line.name == MODULES_LINE:
                modules = [(e.start_ns, e.duration_ns, e.name)
                           for e in line.events]
        if not ops:
            continue
        leaves = leaf_intervals(ops)
        busy_per_chip.append(union_length(leaves) / 1e9)
        n_ops += len(leaves)
        n_modules += len(modules)
        for s, e, name in leaves:
            op_time[op_label(name)] += (e - s) / 1e9
        for k, v in _gaps(leaves, modules).items():
            gaps[k] += v / 1e9
        if leaves:
            first = min(iv[0] for iv in leaves)
            last = max(iv[1] for iv in leaves)
            # what the window holds before the first and after the last
            # operation: the host's state build and collect
            gaps["outside_first_to_last_op"] += max(
                window_s - (last - first) / 1e9, 0.0)
    if not busy_per_chip:
        raise RuntimeError(
            f"{path}: no device plane with {OPS_LINE!r} events — no "
            "operation ran on a TPU inside the traced span")
    chips = max(n_chips, len(busy_per_chip))
    busy = sum(busy_per_chip) / chips
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy,
        "window_s": window_s,
        "leaf_ops": n_ops,
        "programs": n_modules,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v / chips] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])][:10],
        },
    }
