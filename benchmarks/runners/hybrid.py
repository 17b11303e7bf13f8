"""Runner ``hybrid``: real OS processes (their syscall plane on the host's
cores) over lane hosts whose packets — and the processes' — ride the device
(``MpHybridEngine``).  It is ONE simulation run to its ``stop_time``,
because it has to finish to be compared with the oracle.

Reads from the program: ``MpHybridEngine(cfg, workers=...)``,
``eng.run(on_window=...)`` (``on_window(start, end, next_event)`` at every
window the host takes part in), ``eng.sync_stats`` (``device_turns``,
``device_sync_s``, ``syscall_service_s``), ``eng.workers``,
``eng.external_hosts``, ``eng.device_info()``, ``SimResult`` and the CPU
oracle ``CpuEngine(cfg).run()``; ``native/`` is built with ``make``.

The measured window is the LAST ``--seconds`` of wall before ``stop_time``;
what comes before it is warm-up (process start, first-turn compile, the
lazy egress-size compiles) and counts as set-up.  After the run the same
configuration runs on ``network_backend: cpu`` and counters, rounds,
sorted process errors, the event log and every process output file must be
equal, byte for byte.
"""

from __future__ import annotations

import shutil
import subprocess
import tempfile
import time

from lib import compare, stats
from lib.cells import REPO

NS = 1_000_000_000


def build_native(say) -> None:
    """``native/`` from tracked sources (a no-op when it is up to date)."""
    for tool in ("make", "cc"):
        if shutil.which(tool) is None:
            raise RuntimeError(f"the hybrid runner needs `{tool}` to build native/")
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", str(REPO / "native")], check=True,
                   capture_output=True)
    say(f"native/ built in {time.perf_counter() - t0:.1f}s")


class Sampler:
    """The ``on_window`` callback: host clock, sim time and the running
    ``sync_stats`` at every window; with ``--trace 1`` it also opens and
    closes the profiler around a steady span of turns."""

    def __init__(self, eng, trace_from_ns, trace_wall_s, trace_dir) -> None:
        self.eng = eng
        self.rows: list[tuple] = []  # wall, sim_ns, turns, sync_s, service_s
        self.trace_from_ns = trace_from_ns
        self.trace_wall_s = trace_wall_s
        self.trace_dir = trace_dir
        self.trace_span = None  # (wall0, wall1) once closed
        self._trace_t0 = None

    def __call__(self, _start: int, end: int, _next_ev: int) -> None:
        st = self.eng.sync_stats
        now = time.perf_counter()
        self.rows.append((now, end, st["device_turns"], st["device_sync_s"],
                          st["syscall_service_s"]))
        if self.trace_dir is None or self.trace_span is not None:
            return
        from lib.trace import start_trace

        if self._trace_t0 is None:
            if end >= self.trace_from_ns:
                start_trace(self.trace_dir)
                self._trace_t0 = time.perf_counter()
        elif now - self._trace_t0 >= self.trace_wall_s:
            self.close()

    def close(self) -> None:
        """Stop a trace that is still open (also at the end of the run)."""
        if self._trace_t0 is not None and self.trace_span is None:
            import jax

            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_span = (self._trace_t0, t1)


def run(ctx) -> dict:
    from shadow_tpu.backend.cpu_engine import CpuEngine
    from shadow_tpu.backend.hybrid import MpHybridEngine

    traffic = ctx.cell.traffic
    horizon_ns = int(traffic["horizon_sim_s"] * NS)
    cmp = compare.Comparison()
    build_native(ctx.say)

    cfg = ctx.build("tpu", horizon_ns, "device")
    eng = MpHybridEngine(cfg, workers=cfg.experimental.hybrid_workers)
    n_procs = len(eng.external_hosts)
    ctx.say(f"hybrid: {n_procs} managed processes over "
            f"{len(cfg.hosts) - n_procs} lane hosts, {eng.workers} syscall "
            "workers (the rate depends on the count)")
    ctx.say("engine built; starting the run")
    trace_dir = tempfile.mkdtemp(prefix="trace_", dir=ctx.tmp) \
        if ctx.trace else None
    sampler = Sampler(eng, int(traffic.get("trace_from_sim_s", 0) * NS),
                      float(traffic.get("trace_wall_s", 3)), trace_dir)
    t_run0 = time.perf_counter()
    try:
        result = eng.run(on_window=sampler)
    finally:
        sampler.close()
    t_run1 = time.perf_counter()
    sync = dict(eng.sync_stats)
    ctx.say(f"run ended: {t_run1 - t_run0:.2f}s for run(), of which the "
            f"engine's own wall {result.wall_seconds:.2f}s (the rest is "
            "spawning and joining the workers)")

    # -- the window: the last --seconds of wall before stop_time -----------
    rows = sampler.rows
    samples = [(r[0], r[1]) for r in rows]
    i_open, whole = stats.last_window(samples, ctx.seconds)
    if whole:
        ctx.say(f"the whole run ({samples[-1][0] - samples[0][0]:.2f}s) is "
                f"shorter than --seconds {ctx.seconds:g}: the window is the "
                "whole run")
    sim_s, wall_s = stats.window_rate(samples, i_open)
    steps = stats.sim_step_walls(samples[i_open:])
    turn_walls = stats.change_walls([(r[0], r[2]) for r in rows[i_open:]])
    ctx.say(f"window: {wall_s:.3f}s wall for {sim_s:.4f} sim-s from sim "
            f"{samples[i_open][1] / NS:.4f}s; {len(steps)} samples of 10 "
            f"sim-ms, {len(turn_walls)} device turns; engine wall "
            f"{result.wall_seconds:.2f}s for {horizon_ns / NS:g} sim-s")
    end_to_end = {"sim_s_per_wall_s": sim_s / wall_s if wall_s else 0.0}
    if steps:
        end_to_end["sim10ms_wall_p95_ms"] = stats.percentile(steps, 95) * 1e3
    w0, w1 = rows[i_open], rows[-1]

    trace = None
    if ctx.trace:
        from lib import trace as tr

        if sampler.trace_span is None:
            raise RuntimeError("the run ended before the traced span opened")
        t0, t1 = sampler.trace_span
        trace = tr.reduce_trace(tr.find_xplane(trace_dir), t1 - t0,
                                ctx.cell.chips)

    # -- after the run: the same configuration on the CPU oracle ----------
    with ctx.reference("network_backend: cpu over the same horizon"):
        oracle = CpuEngine(ctx.build("cpu", horizon_ns, "oracle")).run()
        seen = compare.compare_results(
            cmp, "hybrid vs oracle", result, oracle,
            counter_keys=compare.HYBRID_COUNTERS,
            out_got=compare.host_outputs(ctx.tmp / "device"),
            out_ref=compare.host_outputs(ctx.tmp / "oracle"))
        cmp.add("clean exits of managed processes (want > 0)",
                int(result.counters.get("managed_exit_clean", 0) <= 0))
    ctx.say(f"check: {seen['records']} event-log records, process output and "
            f"{len(oracle.process_errors)} process errors compared with the "
            "oracle's")

    return {
        "comparison": cmp,
        "attempted": n_procs,
        "failed": seen["bad_errors"] + seen["bad_files"],
        "window": (w0[0], w1[0]),
        "device_info": eng.device_info(),
        "end_to_end": end_to_end,
        "raw": {
            "horizon_sim_s": horizon_ns / NS,
            "engine_wall_s": t_run1 - t_run0,
            "sync_stats": sync,
            "window_wall_s": wall_s,
            "window_sim_s": sim_s,
            "window_device_turns": w1[2] - w0[2],
            "window_device_sync_s": w1[3] - w0[3],
            "window_syscall_service_s": w1[4] - w0[4],
            "sim10ms_walls_s": steps,
            "turn_walls_s": turn_walls,
            "events_per_run": len(result.event_log),
        },
        "trace": trace,
    }
