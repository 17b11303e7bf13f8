"""Simulation facade: config -> backend -> results on disk.

The user-facing runner, covering the reference's L0-L3 surface
(shadow.rs:33-480 run_shadow, controller.rs, manager.rs): pick the network
backend, run the round loop, emit heartbeat progress, and write the data
directory (``sim-stats.json``, the counter dump the reference writes at
manager.rs:844-846, plus an optional event log for determinism diffs).

Also owns the fork-feature surface: in-process restart (RestartRequest
unwound from the round loop and re-run from a fresh engine, the analog of
shadow.rs:233-241) and the run-control / perf-logging hooks of
:mod:`shadow_tpu.engine.run_control`.
"""

from __future__ import annotations

import json
import logging
import sys
import time as wall_time  # bench/heartbeat timing only; sim time is core.time
from pathlib import Path
from typing import Optional

from ..backend.cpu_engine import OUTCOME_NAMES, CpuEngine, SimResult
from ..config.options import ConfigError, ConfigOptions
from ..core import time as stime
from .checkpoint import (
    CheckpointError,
    CheckpointManager,
    GracefulShutdown,
    ResumeRequest,
    read_checkpoint,
    validate_for_config,
)
from .run_control import PerfLog, RestartRequest, RunControl

log = logging.getLogger("shadow_tpu")


class _CkptHook:
    """Facade-side checkpoint trigger, composed into the per-window
    callback (docs/robustness.md): counts window-clamp epochs, writes a
    checkpoint every ``checkpoint_every_windows`` boundaries and/or when
    the run-control ``checkpoint`` verb requested one, and provides the
    forced final write the graceful-shutdown path takes."""

    def __init__(self, mgr: CheckpointManager, every: int, payload_fn,
                 backend_kind: str, resume_windows: int = 0) -> None:
        self.mgr = mgr
        self.every = max(0, int(every))
        self.payload_fn = payload_fn
        self.kind = backend_kind
        self.windows = resume_windows  # continues the interrupted count
        self.request = False
        self.last_epoch: Optional[int] = None

    def request_checkpoint(self) -> str:
        """The run-control ``checkpoint`` verb sink: the write happens
        at this boundary, when the hook runs after the console returns."""
        self.request = True
        return "checkpoint requested: written at this window boundary"

    def at_window(self, window_end: int) -> None:
        self.windows += 1
        if not (
            self.request
            or (self.every > 0 and self.windows % self.every == 0)
        ):
            return
        self.request = False
        self._save(window_end)

    def final(self, window_end: int) -> None:
        """The graceful-shutdown write: skip only if this exact boundary
        was already checkpointed by the periodic law."""
        if self.last_epoch != window_end:
            self._save(window_end)

    def _save(self, window_end: int) -> None:
        path = self.mgr.save(
            self.payload_fn(),
            backend_kind=self.kind,
            epoch_ns=window_end,
            windows=self.windows,
            summary={"epoch": stime.fmt(window_end)},
        )
        self.last_epoch = window_end
        log.info(
            "checkpoint written: %s (epoch %s, %d windows)",
            path, stime.fmt(window_end), self.windows,
        )


#: packet_outcomes of a run that kept no device log: the lane engine's own
#: totals, one per outcome code the log would have carried ("queue" for
#: completeness: strict capacity raises on it, so no finished run shows one)
_LANE_OUTCOME_COUNTERS = (
    ("delivered", "lane_delivered"),
    ("loss", "lane_drop_loss"),
    ("codel", "lane_drop_codel"),
    ("queue", "lane_drop_queue"),
)


def device_log_readers(cfg: ConfigOptions) -> list[str]:
    """What in ``cfg`` reads rows of the tpu backend's device event log
    (besides ``result.event_log`` itself): pcap capture of lane hosts,
    which rides the log as PCAP_TX records.  Empty for the cpu backend
    and for hybrid runs, whose logs the facade's ``event_log`` argument
    does not govern."""
    if cfg.experimental.network_backend != "tpu":
        return []
    from ..backend.hybrid import config_has_managed

    if config_has_managed(cfg):
        return []
    pcap = [h.hostname for h in cfg.hosts if h.pcap_enabled]
    if not pcap:
        return []
    more = f" and {len(pcap) - 1} more" if len(pcap) > 1 else ""
    return [f"pcap capture (host {pcap[0]}{more})"]


class Simulation:
    """Owns one simulation run end to end (the reference's Controller +
    Manager collapsed: config in, data directory out)."""

    def __init__(
        self, cfg: ConfigOptions, run_control: Optional[RunControl] = None,
        event_log: bool = True,
    ) -> None:
        """``event_log``: keep the tpu backend's DEVICE event log (the
        CLI's ``--event-log``).  A run keeps that log only when something
        will read it: ``result.event_log``, ``write_event_log`` and pcap
        capture do, so the default keeps it; ``event_log=False`` runs the
        lane program without one — counters, rounds and ``sim-stats.json``
        are unchanged, ``result.event_log`` is empty, and a run whose
        records outnumber the log's fixed capacity can finish.  The cpu
        engine's and the hybrid engine's own logs are not governed by it."""
        cfg.validate()
        self.cfg = cfg
        self.event_log = bool(event_log)
        if not self.event_log:
            readers = device_log_readers(cfg)
            if readers:
                raise ConfigError(
                    "event_log=False, but the device event log is read by "
                    f"{', '.join(readers)}: keep the log "
                    "(event_log=True / --event-log) or drop what reads it"
                )
        self.data_dir = Path(cfg.general.data_directory)
        self.run_control = run_control
        if run_control is None and cfg.experimental.run_control:
            self.run_control = RunControl()
            if sys.stdin is not None and not sys.stdin.closed:
                # works for interactive terminals and piped command scripts
                # alike; a stdin already drained for the config just EOFs
                self.run_control.start_stdin_thread()
        self.restarts = 0
        self.failovers = 0  # TPU->CPU graceful degradations this run
        self.engine = None  # the backend engine of the most recent run()
        self._announced = False  # start-up log line emitted (once per run)
        self.obs = None  # the run's obs Recorder (shadow_tpu/obs/)
        # crash-safety state (docs/robustness.md): pending resume source
        # (--resume / experimental.resume_from / run-control `resume`),
        # the run's checkpoint manager, the sim-time a checkpoint-anchored
        # failover did NOT have to replay, and the pending shutdown signal
        self._resume_path: Optional[str] = cfg.experimental.resume_from
        self._ckpt_mgr: Optional[CheckpointManager] = None
        self.restart_work_saved = 0  # ns of prefix recovered from a ckpt
        self._shutdown_signum: Optional[int] = None
        self._signals_armed = False

    # -- running -----------------------------------------------------------

    def run(self, write_data: bool = True) -> SimResult:
        cfg = self.cfg
        backend = cfg.experimental.network_backend
        t0 = wall_time.perf_counter()
        # the async logger's sim-time prefix reads the live engine's
        # window clock (an attribute the round loop maintains anyway —
        # no extra per-round work); cleared in the finally so a later
        # Simulation in the same process cannot inherit a stale clock
        from ..utils import shadow_log

        shadow_log.set_sim_time_provider(
            lambda: getattr(self.engine, "window_end", 0) or 0
        )
        self.obs = self._make_obs()
        if self.obs is not None and self.run_control is not None:
            # the stats/trace console verbs answer from the live recorder
            self.run_control.set_obs(self.obs)
        prev_handlers = self._install_signals()
        try:
            return self._run_logged(write_data, t0)
        finally:
            self._restore_signals(prev_handlers)
            shadow_log.set_sim_time_provider(None)
            if self.obs is not None and self.obs.finalized is None:
                # failed/aborted run: still flush the partial artifacts —
                # a crash is exactly when the phase breakdown matters
                self.obs.finalize()

    # -- graceful shutdown (docs/robustness.md) ----------------------------

    def _install_signals(self):
        """Arm SIGINT/SIGTERM for a graceful stop: the first signal asks
        the round loop to stop at the next window boundary (final
        checkpoint + artifact flush + worker reap); a second signal
        restores the default disposition and re-raises itself — an
        immediate, non-graceful exit.  Main thread only (the signal
        module refuses handlers elsewhere); returns the previous handlers
        for the paired ``_restore_signals``."""
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return None

        def handler(signum, frame):
            if self._shutdown_signum is not None:
                # second signal: force immediate exit via the default
                # disposition (resume from the last checkpoint later)
                signal.signal(signum, signal.SIG_DFL)
                import os

                os.kill(os.getpid(), signum)
                return
            self._shutdown_signum = signum
            log.warning(
                "received %s: stopping at the next window boundary "
                "(final checkpoint + artifact flush; signal again to "
                "force immediate exit)",
                signal.Signals(signum).name,
            )

        prev = {}
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                prev[sig] = signal.signal(sig, handler)
            except (ValueError, OSError):  # pragma: no cover - exotic env
                pass
        self._signals_armed = bool(prev)
        return prev

    def _restore_signals(self, prev) -> None:
        if not prev:
            return
        import signal

        self._signals_armed = False
        for sig, old in prev.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):  # pragma: no cover
                pass

    def _make_obs(self):
        """Build the run's obs Recorder from ``experimental.obs_*``
        (None = everything off = zero engine overhead)."""
        exp = self.cfg.experimental
        if not (exp.obs_metrics or exp.obs_trace or exp.obs_jsonl
                or exp.netobs or exp.obs_turns or exp.flowtrace):
            # netobs/obs_turns/flowtrace imply a Recorder: the NETOBS_/
            # TURNS_/FLOWS_*.json artifacts ride the same run-id/out-dir
            # lifecycle as METRICS_*.json
            return None
        from ..obs import Recorder

        out_dir = Path(exp.obs_dir) if exp.obs_dir else self.data_dir
        run_id = f"{exp.network_backend}-seed{self.cfg.general.seed}"
        return Recorder(
            run_id=run_id,
            out_dir=out_dir,
            trace=exp.obs_trace,
            jsonl=exp.obs_jsonl,
            turns=exp.obs_turns,
        )

    def _run_logged(self, write_data: bool, t0: float) -> SimResult:
        cfg = self.cfg
        backend = cfg.experimental.network_backend
        if cfg.experimental.interface_qdisc == "round-robin":
            log.warning(
                "interface_qdisc: round-robin is modeled by the "
                "endpoint-bucket law (per-host FIFO; docs/SEMANTICS.md "
                "deviation 1) — there is no interface queue to interleave"
            )
        self._announced = False
        # in-process restart loop: a RestartRequest aborts the round loop,
        # the engine is torn down, and a fresh deterministic run begins;
        # a ResumeRequest (run-control `resume <ckpt>`) aborts it too and
        # the next iteration loads the named checkpoint instead
        while True:
            try:
                if backend == "tpu":
                    result = self._run_tpu_guarded()
                else:
                    result = self._run_cpu()
                break
            except RestartRequest as rr:
                self.restarts += 1
                log.info(
                    "restarting simulation (restart #%d, run_until=%s)",
                    self.restarts,
                    "-" if rr.run_until_ns is None else stime.fmt(rr.run_until_ns),
                )
                if self.run_control is not None:
                    self.run_control.arm_after_restart(rr.run_until_ns)
            except ResumeRequest as rq:
                self.restarts += 1
                self._resume_path = rq.path
                log.info(
                    "resuming simulation from checkpoint %s (restart #%d)",
                    rq.path, self.restarts,
                )
                if self.run_control is not None:
                    self.run_control.arm_after_restart(None)
        total = wall_time.perf_counter() - t0
        for err in result.process_errors:
            log.error("process final-state mismatch: %s", err)
        log.info(
            "simulation done: %s simulated in %.2fs wall (%.2fx real time), "
            "%d rounds, %d log records",
            stime.fmt(result.sim_time_ns),
            result.wall_seconds,
            result.sim_seconds_per_wall_second,
            result.rounds,
            len(result.event_log),
        )
        if self.obs is not None:
            extra = {
                "backend": backend,
                "device": self._device_info(),
                "seed": cfg.general.seed,
                "num_hosts": len(cfg.hosts),
                "sim_time_ns": result.sim_time_ns,
                "wall_seconds": result.wall_seconds,
                "total_wall_seconds": total,
                "rounds": result.rounds,
                "restarts": self.restarts,
                "failovers": self.failovers,
                "restart_work_saved": self.restart_work_saved,
                "sim_counters": dict(sorted(result.counters.items())),
            }
            sync = getattr(self.engine, "sync_stats", None)
            if sync is not None:
                # the totals (phase_s among them); the per-turn ring
                # stays in memory (with --obs-trace its spans are in
                # trace_*.json already)
                extra["hybrid_sync"] = {
                    k: v for k, v in sync.items() if k != "turn_spans"
                }
            self._write_netobs(extra)
            self._write_flows(extra)
            fin = self.obs.finalize(extra=extra)
            for k in ("metrics_path", "trace_path", "turns_path"):
                if k in fin:
                    log.info("obs artifact: %s", fin[k])
        if write_data:
            self._write_data(result, total)
        return result

    def _device_info(self) -> Optional[dict]:
        """``{platform, kind, count}`` of the JAX devices the current
        engine's lane state is placed on; None for the cpu engines, which
        hold no device state.  ``backend`` names the program, this names
        where it ran (shadow_tpu/device.py)."""
        info = getattr(self.engine, "device_info", None)
        return info() if info is not None else None

    def _announce(self) -> None:
        """The start-up log line.  Emitted once per run, as soon as the
        first engine exists, so it names the devices the lane state is
        placed on rather than the backend switch alone."""
        if self._announced:
            return
        self._announced = True
        from ..device import format_device

        cfg = self.cfg
        log.info(
            "starting simulation: %d hosts, stop_time=%s, backend=%s, "
            "device=%s, seed=%d",
            len(cfg.hosts),
            stime.fmt(cfg.general.stop_time),
            cfg.experimental.network_backend,
            format_device(self._device_info()),
            cfg.general.seed,
        )

    def _write_netobs(self, extra: dict) -> None:
        """Write the NETOBS_<run_id>.json telemetry artifact through the
        Recorder lifecycle (docs/observability.md) and fold the totals
        into the metrics registry so the ``stats`` verb and the METRICS
        report carry the network counters too."""
        cfg = self.cfg
        snap_fn = getattr(self.engine, "netobs_snapshot", None)
        if not cfg.experimental.netobs or snap_fn is None:
            return
        snap = snap_fn()
        if snap is None:
            return
        from ..obs import netobs as nom

        names = [h.hostname for h in cfg.hosts]
        report = nom.build_report(
            self.obs.run_id,
            cfg.experimental.network_backend,
            cfg.general.seed,
            names,
            snap["arrays"],
            snap["window_hist"],
            host_window_hist=snap.get("host_window_hist"),
            log_lost=snap.get("log_lost", 0),
        )
        if self.obs.out_dir is not None:
            path = nom.write_report(
                self.obs.out_dir / f"NETOBS_{self.obs.run_id}.json", report
            )
            log.info("obs artifact: %s", path)
        m = self.obs.metrics
        for k, v in report["totals"].items():
            if v:
                m.count(f"net_{k}", v)
        extra["netobs"] = {
            "drops_by_cause": report["drops_by_cause"],
            "drop_total": report["drop_total"],
            "windows": report["window_hist"]["windows"],
        }

    def _write_flows(self, extra: dict) -> None:
        """Write the FLOWS_<run_id>.json lifecycle artifact through the
        Recorder lifecycle (docs/observability.md): canonical event
        stream, per-flow breakdowns, burst attribution — plus Chrome-
        trace flow arrows when span tracing is on, and the
        ``flow_events_lost`` counter in the metrics registry."""
        cfg = self.cfg
        snap_fn = getattr(self.engine, "flowtrace_snapshot", None)
        if not cfg.experimental.flowtrace or snap_fn is None:
            return
        snap = snap_fn()
        if snap is None:
            return
        from ..obs import flowtrace as ftr

        cap = cfg.experimental.flowtrace_capacity
        events, trunc = ftr.canonical_events(snap["raw"], cap)
        lost = trunc + snap.get("ring_lost", 0)
        thresh, all_pass = ftr.sample_thresh(
            cfg.experimental.flowtrace_sample
        )
        names = [h.hostname for h in cfg.hosts]
        report = ftr.build_report(
            self.obs.run_id,
            cfg.experimental.network_backend,
            cfg.general.seed,
            names,
            events,
            lost,
            thresh,
            all_pass,
            cap,
        )
        if self.obs.out_dir is not None:
            path = ftr.write_report(
                self.obs.out_dir / f"FLOWS_{self.obs.run_id}.json", report
            )
            log.info("obs artifact: %s", path)
        m = self.obs.metrics
        m.count("flow_events", len(events))
        m.count("flow_events_lost", lost)
        if self.obs.tracer is not None:
            ftr.render_flows(self.obs.tracer, events, names)
        extra["flows"] = {
            "num_events": report["num_events"],
            "num_flows": report["num_flows"],
            "events_lost": report["events_lost"],
        }

    def _make_on_window(self, describe_source, runahead, t0: float,
                        ckpt: Optional[_CkptHook] = None):
        """Compose the per-round callback: heartbeat lines + run-control
        boundary processing + checkpoint writes + the graceful-shutdown
        check.  ``describe_source(until)`` names the hosts with events
        before ``until`` (for the pause console).  ``runahead`` is an int
        or a live callable (dynamic runahead widens it)."""
        heartbeat = self.cfg.general.heartbeat_interval
        rc = self.run_control
        if not heartbeat and rc is None and ckpt is None \
                and not self._signals_armed:
            return None  # no consumer: keep the round loop free of the hook
        state = {"next_beat": heartbeat or 0, "rounds": 0}
        stop_time = self.cfg.general.stop_time

        def on_window(window_start: int, window_end: int, next_ev: int) -> None:
            state["rounds"] += 1
            if heartbeat:
                while window_end >= state["next_beat"]:
                    log.info(
                        "heartbeat: sim-time %s, %d rounds, %.1fs wall",
                        stime.fmt(state["next_beat"]),
                        state["rounds"],
                        wall_time.perf_counter() - t0,
                    )
                    state["next_beat"] += heartbeat
            if rc is not None:
                # next_ev == NEVER means no next window: describe nothing
                # rather than listing every idle host
                ra = runahead() if callable(runahead) else runahead
                until = next_ev + ra if next_ev < stime.NEVER else 0
                rc.at_window_boundary(
                    window_start,
                    window_end,
                    next_ev,
                    describe=(
                        (lambda: describe_source(until)) if describe_source else None
                    ),
                    # drained queue / nothing before stop: a step or
                    # run-until pause here would block on a window that
                    # will never come — report terminal status instead
                    terminal=next_ev >= stop_time,
                )
                rc.consume_run_for(window_end)
            if ckpt is not None:
                # runs AFTER the console: a `checkpoint` verb typed at a
                # pause lands at this very boundary on resume
                ckpt.at_window(window_end)
            if self._shutdown_signum is not None:
                if ckpt is not None:
                    ckpt.final(window_end)
                raise GracefulShutdown(self._shutdown_signum)

        return on_window

    # -- checkpoint/resume plumbing (docs/robustness.md) -------------------

    def _take_resume(self, kind: str):
        """Consume the pending resume source (``--resume`` /
        ``experimental.resume_from`` / run-control ``resume``): load,
        verify, and validate the checkpoint against this config and
        backend.  Returns ``(header, payload)`` or None.  Consuming means
        a later in-process restart runs fresh from t=0, as restarts
        always have."""
        path = self._resume_path
        self._resume_path = None
        if path is None:
            return None
        hdr, payload = read_checkpoint(path)
        validate_for_config(hdr, self.cfg)
        if hdr.get("backend_kind") != kind:
            raise CheckpointError(
                f"{path}: checkpoint was written by the"
                f" {hdr.get('backend_kind')!r} backend; this run uses"
                f" {kind!r} — resume on the matching backend"
            )
        log.info(
            "resuming from checkpoint %s: epoch %s, %d windows",
            path, stime.fmt(hdr["epoch_ns"]), hdr["windows"],
        )
        return hdr, payload

    def _make_ckpt_hook(self, kind: str, payload_fn,
                        resume_windows: int = 0,
                        unsupported: Optional[str] = None):
        """Build the per-run checkpoint hook, or None when checkpointing
        is off.  Armed when periodic checkpointing is configured, when a
        checkpoint directory is named, or when a run-control console is
        live (so its ``checkpoint`` verb has somewhere to write) — an
        armed-but-idle hook costs one int increment per window."""
        exp = self.cfg.experimental
        configured = (
            exp.checkpoint_every_windows > 0 or exp.checkpoint_dir is not None
        )
        if not configured and self.run_control is None:
            return None
        if unsupported:
            if configured:
                log.warning("checkpointing disabled: %s", unsupported)
            return None
        ckdir = (
            Path(exp.checkpoint_dir) if exp.checkpoint_dir
            else self.data_dir / "checkpoints"
        )
        run_id = f"{exp.network_backend}-seed{self.cfg.general.seed}"
        mgr = self._ckpt_mgr = CheckpointManager(
            ckdir, run_id, self.cfg, keep=exp.checkpoint_keep
        )
        hook = _CkptHook(
            mgr, exp.checkpoint_every_windows, payload_fn, kind,
            resume_windows,
        )
        if self.run_control is not None:
            self.run_control.set_checkpoint_sink(hook.request_checkpoint)
        return hook

    def _obs_payload(self):
        return self.obs.checkpoint_state() if self.obs is not None else None

    def _restore_obs(self, payload: dict) -> None:
        """Reset the live accumulators and restore the checkpointed ones
        (replace, not merge): the resumed run's deterministic counters
        then byte-match an uninterrupted run's, and nothing from an
        abandoned attempt lingers."""
        if self.obs is None:
            return
        self.obs.reset_for_replay()
        if payload.get("obs") is not None:
            self.obs.restore_checkpoint_state(payload["obs"])

    def _run_tpu_guarded(self) -> SimResult:
        """The graceful-degradation boundary (docs/faults.md,
        docs/robustness.md): when ``faults.failover`` is enabled, any
        failure of the TPU path — an injected ``backend_stall``, a
        watchdog-detected stall, a run-control ``failover`` command, or a
        real backend error — degrades to a **deterministic replay from
        the newest valid checkpoint**, or from t=0 when none exists.
        Replay is exact recovery: determinism makes the replayed suffix
        (or whole run) reproduce the event log an unfaulted CPU-only run
        of the same config yields, bit-for-bit.  A checkpointed pure-lane
        run replays on a fresh TPU engine with the injected stalls
        disarmed (the fault already fired; cross-backend parity makes the
        result identical to the CPU replay), reporting the recovered
        prefix as ``restart_work_saved``; the hybrid backend and
        checkpoint-less runs replay on the CPU engine from t=0."""
        from ..faults.watchdog import BackendStallError, FailoverRequest

        try:
            return self._run_tpu()
        except (RestartRequest, ResumeRequest):
            raise
        except (BackendStallError, FailoverRequest) as e:
            if not self.cfg.faults.failover_enabled:
                raise
            reason: Exception = e
        except Exception as e:
            if not self.cfg.faults.failover_enabled:
                raise
            reason = e
        self.failovers += 1
        # (c) checkpoint-anchored failover: scan for the newest valid
        # tpu checkpoint and replay only the suffix
        if self._ckpt_mgr is not None:
            got = self._ckpt_mgr.newest_valid(backend_kind="tpu")
            if got is not None:
                hdr, payload, path = got
                log.warning(
                    "tpu backend failed (%s: %s); replaying from "
                    "checkpoint %s (epoch %s — restart_work_saved=%d ns)",
                    type(reason).__name__, reason, path,
                    stime.fmt(hdr["epoch_ns"]), hdr["epoch_ns"],
                )
                try:
                    return self._failover_resume_tpu(hdr, payload)
                except (RestartRequest, ResumeRequest, GracefulShutdown):
                    raise
                except Exception as e:
                    log.warning(
                        "checkpoint-anchored failover failed (%s: %s); "
                        "falling back to a cpu replay from t=0",
                        type(e).__name__, e,
                    )
        log.warning(
            "tpu backend failed (%s: %s); degrading to the cpu engine "
            "(deterministic replay from t=0)",
            type(reason).__name__,
            reason,
        )
        self.restart_work_saved = 0
        if self.obs is not None:
            # the replay re-earns every accumulator from t=0
            self.obs.reset_for_replay()
        return self._run_cpu()

    def _failover_resume_tpu(self, hdr: dict, payload: dict) -> SimResult:
        """Replay the run's suffix on a fresh TPU engine from a verified
        checkpoint, stalls disarmed (the injected fault already fired —
        replaying it would livelock the recovery law)."""
        epoch = int(hdr["epoch_ns"])
        self.restart_work_saved = epoch
        engine = self._lane_engine()
        if self.cfg.experimental.perf_logging:
            engine.perf_log = PerfLog()
        self._restore_obs(payload)
        if self.obs is not None:
            m = self.obs.metrics
            m.count("failovers")
            m.count("restart_work_saved", epoch)
        t0 = wall_time.perf_counter()
        ckpt = self._make_ckpt_hook(
            "tpu",
            lambda: {
                "state": engine.checkpoint_payload(),
                "obs": self._obs_payload(),
            },
            resume_windows=int(hdr["windows"]),
        )
        on_window = self._make_on_window(
            None, engine.current_runahead, t0, ckpt
        )
        return engine.run(
            mode="step",
            on_window=on_window,
            resume_state=payload["state"],
            resume_epoch=epoch,
            disarm_stalls=True,
        )

    def _lane_engine(self, **kwargs):
        """The pure-lane ``TpuEngine`` of this run — the ONE place that
        decides whether it keeps a device event log: only when something
        will read it (``event_log``; ``__init__`` has already refused a
        log-off run that captures pcap).  ``None`` is the engine's own
        fixed capacity, 0 is no log."""
        from ..backend.tpu_engine import TpuEngine

        engine = self.engine = TpuEngine(
            self.cfg, log_capacity=None if self.event_log else 0, **kwargs
        )
        engine.obs = self.obs
        return engine

    def _run_cpu(self) -> SimResult:
        resume = self._take_resume("cpu")
        if resume is not None:
            hdr, payload = resume
            # the whole-engine pickle IS the run prefix: hosts, queues,
            # in-flight transport state, RNG counters, fault runtime —
            # run() on the restored engine simply continues
            engine = self.engine = CpuEngine.from_checkpoint(
                payload["engine"]
            )
            self._restore_obs(payload)
            resume_windows = int(hdr["windows"])
        else:
            engine = self.engine = CpuEngine(self.cfg)
            resume_windows = 0
        if self.run_control is not None:
            # the `fault ...` console verb schedules faults at the next
            # window boundary (cpu backend only: the device program's
            # tables are baked per epoch and cannot take ad-hoc edits)
            self.run_control.set_fault_sink(engine.console_fault_sink)
            if engine.netobs is not None:
                # the `netstats [host]` verb answers from live counters
                self.run_control.set_netobs_sink(engine.netobs_lines)
            if engine.flowtrace is not None:
                # the `flows [host]` verb answers from live events
                self.run_control.set_flows_sink(engine.flowtrace_lines)
        if self.cfg.experimental.perf_logging:
            engine.perf_log = PerfLog()
        engine.obs = self.obs
        self._announce()
        t0 = wall_time.perf_counter()
        ckpt = self._make_ckpt_hook(
            "cpu",
            lambda: {
                "engine": engine.checkpoint_payload(),
                "obs": self._obs_payload(),
            },
            resume_windows=resume_windows,
            unsupported=engine.checkpoint_unsupported_reason(),
        )
        on_window = self._make_on_window(
            engine.describe_next_window, engine.current_runahead, t0, ckpt
        )
        try:
            return engine.run(on_window=on_window)
        except (RestartRequest, ResumeRequest):
            engine.finalize()  # reap managed processes before the re-run
            raise
        except GracefulShutdown:
            engine.finalize()  # reap managed processes before exiting
            raise

    def _run_tpu(self) -> SimResult:
        from ..backend.hybrid import HybridEngine, config_has_managed
        from ..backend.tpu_engine import LaneCompatError

        if config_has_managed(self.cfg):
            if self.cfg.faults.events and any(
                ev.get("kind") != "backend_stall"
                for ev in self.cfg.faults.events
            ):
                # the guarded caller degrades this to a CPU replay when
                # failover is enabled — managed hosts run there natively.
                # backend_stall-only schedules ARE supported: the hybrid
                # window loop raises at the stall epoch and the failover
                # boundary replays on the CPU engine (docs/robustness.md)
                raise LaneCompatError(
                    "link/host fault schedules are not supported on the "
                    "hybrid tpu backend; use the cpu backend"
                )
            # the HYBRID backend: managed hosts' syscall plane on the host
            # CPU, the packet data plane (theirs included) on the device.
            # Run-control needs the per-round pause seam, which the device
            # free-run deliberately elides — it is disabled here (use the
            # cpu backend for console debugging).  Perf-logging IS
            # supported: [hybrid-agg] sync-cost lines per window.
            if self.run_control is not None:
                log.warning(
                    "run-control is not supported on the hybrid tpu "
                    "backend; running without it"
                )
                self.run_control = None
            if self._resume_path is not None:
                raise CheckpointError(
                    "the hybrid tpu backend does not support resume: "
                    "managed (real-binary) processes hold live OS state "
                    "that cannot be snapshotted (docs/robustness.md); "
                    "use the cpu backend to resume this checkpoint"
                )
            if (self.cfg.experimental.checkpoint_every_windows > 0
                    or self.cfg.experimental.checkpoint_dir is not None):
                log.warning(
                    "checkpointing disabled on the hybrid tpu backend: "
                    "managed (real-binary) processes hold live OS state "
                    "that cannot be snapshotted (docs/robustness.md)"
                )
            # parallel syscall servicing: hybrid_workers != 1 spawns the
            # multiprocess engine (0 = one worker per core); results are
            # bit-identical at any worker count
            hw = self.cfg.experimental.hybrid_workers
            if hw != 1:
                from ..backend.hybrid import MpHybridEngine

                engine = self.engine = MpHybridEngine(self.cfg, workers=hw)
            else:
                engine = self.engine = HybridEngine(self.cfg)
            if self.cfg.experimental.perf_logging:
                engine.perf_log = PerfLog()
            engine.obs = self.obs
            self._announce()
            t0 = wall_time.perf_counter()
            on_window = self._make_on_window(
                engine.describe_next_window, engine.current_runahead, t0
            )
            return engine.run(on_window=on_window)

        from .. import parallel

        # multi-chip sharded lane plane (parallel/mesh.py,
        # docs/multichip.md): a negotiated device mesh attaches to the
        # SAME engine/driver stack — fused free-run and step driver both
        # compile under it, netobs included (the per-host counter block
        # shards with its lanes, the window histogram shard-then-reduces)
        # — with bit-identical results at any mesh shape.  Only faults,
        # resume, and flowtrace stay single-device.
        n_mesh = parallel.negotiate_from_config(self.cfg, len(self.cfg.hosts))
        multi_mesh = n_mesh > 1
        # flowtrace stays single-device for now: the device event ring
        # drains through the unsharded snapshot path
        engine = self._lane_engine(flowtrace=False if multi_mesh else None)
        if multi_mesh:
            if self.cfg.faults.events:
                raise LaneCompatError(
                    "fault schedules are not supported on the sharded-mesh "
                    "driver; drop experimental.mesh_devices or use the cpu "
                    "backend"
                )
            if self._resume_path is not None:
                raise CheckpointError(
                    "checkpoint resume is not supported on the sharded-"
                    "mesh driver; drop experimental.mesh_devices to resume"
                )
            if self.cfg.experimental.flowtrace:
                log.warning(
                    "flowtrace is not supported on the sharded-mesh "
                    "driver; running without it — drop "
                    "experimental.mesh_devices to trace flows"
                )
            engine.attach_mesh(parallel.make_mesh(n_mesh))
        self._announce()
        # run-control / perf logging / checkpointing / resume force the
        # step-wise driver (one device call per round, pausable, with
        # host-visible lane state at every boundary); otherwise the
        # fused on-device loop
        exp = self.cfg.experimental
        resume = self._take_resume("tpu")
        needs_steps = (
            self.run_control is not None
            or exp.perf_logging
            or resume is not None
            or exp.checkpoint_every_windows > 0
            or exp.checkpoint_dir is not None
        )
        if not needs_steps:
            return engine.run(mode="device")
        t0 = wall_time.perf_counter()
        resume_state = resume_epoch = None
        resume_windows = 0
        if resume is not None:
            hdr, payload = resume
            resume_state = payload["state"]
            resume_epoch = int(hdr["epoch_ns"])
            resume_windows = int(hdr["windows"])
            self._restore_obs(payload)
        ckpt = self._make_ckpt_hook(
            "tpu",
            lambda: {
                "state": engine.checkpoint_payload(),
                "obs": self._obs_payload(),
            },
            resume_windows=resume_windows,
        )
        on_window = self._make_on_window(
            None, engine.current_runahead, t0, ckpt
        )
        if self.run_control is not None:
            # the `failover` console verb is live on the pausable tpu
            # driver: it unwinds a FailoverRequest to the guarded caller
            self.run_control.failover_armed = True
            if exp.netobs:
                # `netstats` reads the live device counters at a paused
                # boundary (a snapshot epoch, not a new per-window sync)
                self.run_control.set_netobs_sink(engine.netobs_lines)
            if exp.flowtrace:
                # `flows` drains the live device event ring the same way
                self.run_control.set_flows_sink(engine.flowtrace_lines)
        if exp.perf_logging:
            engine.perf_log = PerfLog()
        if resume is not None:
            return engine.run(
                mode="step", on_window=on_window,
                resume_state=resume_state, resume_epoch=resume_epoch,
            )
        return engine.run(mode="step", on_window=on_window)

    # -- output ------------------------------------------------------------

    def _write_data(self, result: SimResult, total_wall: float) -> None:
        self.data_dir.mkdir(parents=True, exist_ok=True)
        stats = {
            "sim_time_ns": result.sim_time_ns,
            "wall_seconds": result.wall_seconds,
            "total_wall_seconds": total_wall,
            "sim_seconds_per_wall_second": result.sim_seconds_per_wall_second,
            "rounds": result.rounds,
            "restarts": self.restarts,
            "failovers": self.failovers,
            "restart_work_saved": self.restart_work_saved,
            "backend": self.cfg.experimental.network_backend,
            "device": self._device_info(),
            "lane_plane": self._lane_plane(),
            "fused_run": self._fused_run(),
            "num_hosts": len(self.cfg.hosts),
            "seed": self.cfg.general.seed,
            "counters": dict(sorted(result.counters.items())),
            "packet_outcomes": self._outcome_counts(result),
        }
        (self.data_dir / "sim-stats.json").write_text(
            json.dumps(stats, indent=2) + "\n"
        )
        hosts_dir = self.data_dir / "hosts"
        hosts_dir.mkdir(exist_ok=True)
        if result.per_host_counters:
            for hopt, counters in zip(self.cfg.hosts, result.per_host_counters):
                d = hosts_dir / hopt.hostname
                d.mkdir(exist_ok=True)
                (d / "counters.json").write_text(
                    json.dumps(dict(sorted(counters.items())), indent=2) + "\n"
                )

    def _lane_plane(self) -> Optional[dict]:
        """``{lanes, mesh_devices, device_log_capacity,
        device_log_records}`` of the pure-lane engine's last collected
        run; None for the engines that hold no lane plane of their own."""
        info = getattr(self.engine, "lane_plane", None)
        return dict(info) if info else None

    def _fused_run(self) -> Optional[dict]:
        """The pure-lane engine's row of its last run (``TpuEngine.
        run_row``: the run's host phases in seconds and its notes); None
        for the engines whose driver is not the fused one."""
        row = getattr(self.engine, "run_row", None)
        return row() if row is not None else None

    def _outcome_counts(self, result: SimResult) -> dict[str, int]:
        out: dict[str, int] = {}
        plane = self._lane_plane()
        if plane is not None and plane["device_log_capacity"] == 0:
            # the run kept no device log: the lane engine's own totals
            # (equal to a walk over the log it would have kept — held by
            # tests/test_mesh100k_support.py)
            for name, key in _LANE_OUTCOME_COUNTERS:
                if result.counters.get(key):
                    out[name] = result.counters[key]
        else:
            for r in result.event_log:
                name = OUTCOME_NAMES.get(r.outcome, str(r.outcome))
                out[name] = out.get(name, 0) + 1
        # flows the lTCP sender abandoned after MAX_RTO_BACKOFFS consecutive
        # timeouts (net/ltcp.py): not a wire event, but an outcome operators
        # need next to the drop counts when links stay dark
        retry_drops = result.counters.get("stream_retry_drops", 0)
        if retry_drops:
            out["retry_drop"] = out.get("retry_drop", 0) + retry_drops
        return out

    def write_event_log(self, result: SimResult, path: Optional[Path] = None) -> Path:
        """Canonical sorted event log — the determinism-diff artifact
        (src/test/determinism/ compares exactly this across runs)."""
        path = path or (self.data_dir / "event-log.tsv")
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w") as f:
            f.write("time\tsrc\tdst\tseq\tsize\toutcome\n")
            for row in result.log_tuples():
                f.write("\t".join(str(x) for x in row) + "\n")
        return path
