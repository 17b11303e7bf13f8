"""What the host was doing while the device was idle: one ``.xplane.pb``
(a ``jax.profiler`` trace of a run of this program) reduced to idle seconds
per host phase.

Usage: python scripts/trace_idle.py <trace_dir or file.xplane.pb>

The device's idle gaps are the gaps between LEAF operations of a
``/device:TPU:<n>`` plane's ``XLA Ops`` line (an event that contains no
other: a ``while`` contains its body), as ``benchmarks/lib/trace.py``
defines them: ``inside_program`` (both neighbours in one ``XLA Modules``
event), ``between_programs``, and ``outside_first_to_last_op`` (from the
first host phase to the first operation, and from the last operation to the
last phase's end).  Each gap is cut by the host plane's ``hybrid/*`` and
``fused/*`` TraceMe intervals (the drivers' host-phase clock,
``shadow_tpu/obs/clock.py``; the INNERMOST phase takes a nested stretch)
and what no phase covers is ``(no phase)``.  A fused run is one turn of
its clock, a ``fused/run`` interval around its phases, so what a run
spends outside every ``fused/<phase>`` is ``fused/run``'s and only the
time outside every run stays ``(no phase)``.  Each run's closed row (its
phases' seconds and its notes: the stats of the empty ``fused/row`` span
the clock leaves at the turn's end) is printed beside the gaps.

The two planes' clocks differ by a constant of the order of a millisecond
(the device's events are stamped on its own clock).  It is read off the
trace: when a ``*/device_wait`` phase ends, the program it waited for has
ended; the offset is the one under which most (program-time-weighted)
waits end within 0.5 ms after a program does.  ``--offset-us`` overrides.
"""

import argparse
import bisect
import glob
import os
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PHASE = re.compile(r"^((?:hybrid|fused)/\w+)")
ROW = re.compile(r"^(?:hybrid|fused)/row$")  # a closed turn's row, no phase
NO_PHASE = "(no phase)"
SETTLE_NS = 500_000  # a wait ends this soon after the program it waited for


def leaf_intervals(events):
    """``(start, end)`` of the events that contain no other event."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves = []
    for i, (start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[0] < start + dur \
                and nxt[0] + nxt[1] <= start + dur and nxt != (start, dur):
            continue
        leaves.append((start, start + dur))
    return leaves


def innermost(phases):
    """Properly nested ``(start, end, name)`` intervals of one thread, cut
    into disjoint ``(start, end, name)`` stretches of the innermost."""
    out, stack = [], []  # a frame: [end, name, booked up to]

    def close(frame):
        end, name, cur = frame
        if end > cur:
            out.append((cur, end, name))

    for s, e, n in sorted(phases, key=lambda p: (p[0], -p[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            top = stack[-1]
            if s > top[2]:
                out.append((top[2], s, top[1]))
            top[2] = max(top[2], min(e, top[0]))
        stack.append([e, n, s])
    while stack:
        close(stack.pop())
    return sorted(out)


def clock_offset(waits, programs):
    """Nanoseconds to ADD to the device plane's stamps.  ``waits``: the
    ends of the ``*/device_wait`` phases; ``programs``: ``(start, end)``
    of the plane's executed programs."""
    if not waits or not programs:
        return 0.0
    ends = sorted(e for _s, e in programs)
    length = {e: e - s for s, e in programs}

    def score(off):
        total, slack = 0.0, []
        for w in waits:
            i = bisect.bisect_right(ends, w - off) - 1
            if i >= 0 and w - off - ends[i] <= SETTLE_NS:
                total += length[ends[i]]
                slack.append(w - off - ends[i])
        return total, slack

    cands = {w - e for w in waits for e in ends if abs(w - e) < 20e6}
    if not cands:
        return 0.0
    best = max(cands, key=lambda off: (score(off)[0], -abs(off)))
    return best + min(score(best)[1])  # the tightest wait ends AT its program


def attribute(gaps, stretches):
    """Seconds of each ``(start, end, kind)`` gap per ``(kind, phase)``."""
    out = defaultdict(float)
    starts = [s for s, _e, _n in stretches]
    for g0, g1, kind in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(stretches) and stretches[i][0] < g1:
            s, e, name = stretches[i]
            lap = min(e, g1) - max(s, g0)
            if lap > 0:
                out[kind, name] += lap / 1e9
                covered += lap
            i += 1
        if g1 - g0 > covered:
            out[kind, NO_PHASE] += (g1 - g0 - covered) / 1e9
    return out


def idle_gaps(leaves, programs, lo, hi):
    """The idle gaps of one device between ``lo`` and ``hi``, by kind."""
    leaves = sorted(leaves)
    spans = sorted(programs)
    gaps, j, reach = [], 0, leaves[0][1]
    if leaves[0][0] > lo:
        gaps.append((lo, leaves[0][0], "outside_first_to_last_op"))
    for s1, e1 in leaves[1:]:
        if s1 > reach:
            while j < len(spans) and spans[j][1] < reach:
                j += 1
            inside = j < len(spans) and spans[j][0] <= reach \
                and s1 <= spans[j][1]
            gaps.append((reach, s1, "inside_program" if inside
                         else "between_programs"))
        reach = max(reach, e1)
    if hi > reach:
        gaps.append((reach, hi, "outside_first_to_last_op"))
    return gaps


def reduce(path, offset_ns=None):
    from jax.profiler import ProfileData

    phases, devices, rows = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            ops = programs = ()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    programs = [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
            if ops:
                devices.append((plane.name, leaf_intervals(ops), programs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if ROW.match(e.name):
                        rows.append((e.start_ns, dict(e.stats)))
                        continue
                    m = PHASE.match(e.name)
                    if m:
                        phases.append((e.start_ns, e.start_ns + e.duration_ns,
                                       m.group(1)))
    if not devices:
        raise SystemExit(f"{path}: no operation ran on a TPU in this trace")
    stretches = innermost(phases)
    waits = [e for _s, e, n in phases if n.endswith("/device_wait")]
    report = {"phases_seen": len(phases), "devices": {},
              "rows": [stats for _t, stats in sorted(
                  rows, key=lambda r: r[0])]}
    for name, leaves, programs in devices:
        off = clock_offset(waits, programs) if offset_ns is None else offset_ns
        leaves = [(s + off, e + off) for s, e in leaves]
        programs = [(s + off, e + off) for s, e in programs]
        lo = min([leaves[0][0]] + [s for s, _e, _n in stretches[:1]])
        hi = max([max(e for _s, e in leaves)]
                 + [e for _s, e, _n in stretches[-1:]])
        gaps = idle_gaps(leaves, programs, lo, hi)
        report["devices"][name] = {
            "offset_us": off / 1e3, "span_s": (hi - lo) / 1e9,
            "idle_s": sum(g1 - g0 for g0, g1, _k in gaps) / 1e9,
            "idle_by_phase": attribute(gaps, stretches)}
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--offset-us", type=float, default=None)
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found[-1]
    rep = reduce(path, None if args.offset_us is None
                 else args.offset_us * 1e3)
    print(f"{path}: {rep['phases_seen']} host phase intervals")
    for dev, d in rep["devices"].items():
        print(f"{dev}: span {d['span_s']:.4f}s, idle {d['idle_s']:.4f}s "
              f"({100 * d['idle_s'] / d['span_s']:.2f} %), device clock "
              f"{d['offset_us']:+.1f} us")
        kinds = defaultdict(float)
        for (kind, _p), v in d["idle_by_phase"].items():
            kinds[kind] += v
        for kind, total in sorted(kinds.items(), key=lambda kv: -kv[1]):
            print(f"  {kind}: {total:.6f}s")
            rows = sorted(((v, p) for (k, p), v in d["idle_by_phase"].items()
                           if k == kind), reverse=True)
            for v, p in rows:
                print(f"    {p:<24} {v:.6f}s  {100 * v / total:6.2f} %")
    for row in rep["rows"]:
        print("run row: " + ", ".join(
            f"{k} {v:.6f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items() if k not in ("t_start", "t_end")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
