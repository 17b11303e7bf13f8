"""Runner ``fused_mesh``: a lane-only configuration on the fused device
driver — one ``while_loop`` per simulation, no host hook — so the unit of
work is a whole simulation of the cell's fixed horizon.

Reads from the program: ``TpuEngine(cfg, log_capacity=...)``,
``eng.run(mode="device", precompile=...)``, ``eng.attach_mesh``,
``parallel.make_mesh``, ``SimResult`` (``wall_seconds``, ``rounds``,
``counters``, ``log_tuples``) and the CPU oracle ``CpuEngine(cfg).run()``.

Set-up: build the engine (event log off), precompile, one warm-up
simulation.  Window: repeat ``eng.run(mode="device")`` on that same object
until ``--seconds`` have passed, finishing the one in flight.  After the
window, outside set-up and outside the measured peak of memory:

1. the oracle runs the TIMED horizon, and the counters (less the backends'
   own bookkeeping), rounds and process errors of the window's last repeat
   — the timed object's own result — must equal the oracle's;
2. record order, which the timed program keeps no log of: the same
   configuration at the same width with ``stop_time`` cut to the traffic's
   ``check_ms`` and the event log on (``stop_time`` is static in the
   compiled program, so this is a second program) runs on the device and
   on the oracle, and everything including the whole log must be equal,
   record for record.
"""

from __future__ import annotations

import tempfile
import time

from lib import compare
from lib.cells import subst

NS = 1_000_000_000
MS = 1_000_000
#: the check program's event log: the size the program's facade
#: (``Simulation``) gives every user; each traffic mix's ``check_ms`` fits it
CHECK_LOG_CAPACITY = 200_000


def _engine(ctx, cfg, log_capacity: int):
    from shadow_tpu.backend.tpu_engine import TpuEngine

    eng = TpuEngine(cfg, log_capacity=log_capacity)
    if ctx.cell.chips > 1:
        from shadow_tpu import parallel

        eng.attach_mesh(parallel.make_mesh(ctx.cell.chips))
    return eng


def _traced_repeat(ctx, eng) -> dict:
    """One more repeat under the profiler: the steady span the device's
    busy and idle time are read from."""
    import jax

    from lib import trace as tr

    tdir = tempfile.mkdtemp(prefix="trace_", dir=ctx.tmp)
    tr.start_trace(tdir)
    t0 = time.perf_counter()
    try:
        eng.run(mode="device")
        span = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return tr.reduce_trace(tr.find_xplane(tdir), span, ctx.cell.chips)


def run(ctx) -> dict:
    from shadow_tpu.backend.cpu_engine import CpuEngine

    traffic, params = ctx.cell.traffic, ctx.cell.params
    horizon_ns = int(traffic["horizon_sim_s"] * NS)
    check_ns = int(traffic["check_ms"] * MS)
    cmp = compare.Comparison()

    # -- set-up: the timed program (event log off), warmed up -------------
    eng = _engine(ctx, ctx.build("tpu", horizon_ns, "timed"), 0)
    first = eng.run(mode="device", precompile=True)
    ctx.say(f"warm-up repeat: {first.rounds} rounds, "
            f"{first.counters.get('lane_iters')} iterations, "
            f"{first.counters.get('lane_delivered', 0)} deliveries, device "
            f"{first.wall_seconds:.3f}s")

    # -- the window --------------------------------------------------------
    good, raised = [], 0  # (result, call wall) of the repeats that ran
    t_open = t0 = time.perf_counter()
    while t0 - t_open < ctx.seconds:
        try:
            r = eng.run(mode="device")
            good.append((r, time.perf_counter() - t0))
        except Exception as e:  # a repeat that raises is a failed repeat
            ctx.say(f"repeat {len(good) + raised} raised "
                    f"{type(e).__name__}: {e}")
            raised += 1
        t0 = time.perf_counter()
    t_close = t0
    failed = raised + sum(
        1 for r, _w in good
        if r.rounds != first.rounds
        or compare.counters_diff(r.counters, first.counters))
    sim_s = len(good) * horizon_ns / NS
    wall = sum(w for _r, w in good)
    ctx.say(f"window: {len(good) + raised} repeats of {horizon_ns / NS:g} "
            f"sim-s in {t_close - t_open:.3f}s, call wall {wall:.3f}s")
    cmp.add("timed repeats: failed or counters unlike the first", failed)

    trace = _traced_repeat(ctx, eng) if ctx.trace else None

    # -- after the window: the timed object's own result -------------------
    last = good[-1][0] if good else first
    for key in traffic.get("forbid_counters", []):
        cmp.add(f"timed {key}", last.counters.get(key, 0))
    for key, want in traffic.get("expect_counters", {}).items():
        want = int(subst(want, params))
        got = last.counters.get(key, 0)
        cmp.add(f"timed {key} (want {want}, got {got})", int(got != want))
    with ctx.reference(f"CPU oracle over the timed {horizon_ns / NS:g} sim-s"):
        oracle = CpuEngine(ctx.build("cpu", horizon_ns, "oracle")).run()
        compare.compare_results(cmp, "timed vs oracle", last, oracle,
                                log=False)
    ctx.say(f"timed: counters, {last.rounds} rounds and process errors of "
            f"the window's last repeat compared with the oracle's over "
            f"{horizon_ns / NS:g} sim-s ({len(oracle.event_log)} oracle "
            "records)")

    # -- record order: the check horizon, log on, against the oracle ------
    with ctx.reference(f"check program and CPU oracle over "
                       f"{check_ns / MS:g} sim-ms"):
        check = _engine(ctx, ctx.build("tpu", check_ns, "check"),
                        CHECK_LOG_CAPACITY).run(mode="device",
                                                precompile=True)
        oracle = CpuEngine(ctx.build("cpu", check_ns, "check_oracle")).run()
        seen = compare.compare_results(cmp, "check vs oracle", check, oracle)
    ctx.say(f"check: {seen['records']} event-log records compared over "
            f"{check_ns / MS:g} sim-ms at the timed width")

    return {
        "comparison": cmp,
        "attempted": len(good) + raised,
        "failed": failed,
        "window": (t_open, t_close),
        "device_info": eng.device_info(),
        "end_to_end": {"sim_s_per_wall_s": sim_s / wall if wall else 0.0},
        "raw": {
            "horizon_sim_s": horizon_ns / NS,
            "call_wall_s": [w for _r, w in good],
            "device_wall_s": [r.wall_seconds for r, _w in good],
            "lane_iters": [r.counters.get("lane_iters", 0) for r, _w in good],
            "rounds": [r.rounds for r, _w in good],
            "events_per_repeat": first.counters.get("lane_delivered", 0),
        },
        "trace": trace,
    }

