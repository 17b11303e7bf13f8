"""Multiprocessing CPU backend: process-parallel round execution.

The reference's CPU path is genuinely parallel (thread-per-core with work
stealing, thread_per_core.rs:17-50).  Python threads cannot parallelize
pure-model hosts (GIL), so this backend SPAWNS real worker processes, each
REBUILDING a complete deterministic world replica from the config (same
seeds, IPs, routing — construction is deterministic, so every replica is
identical; spawn rather than fork because the parent has usually
initialized JAX by then, and forking a runtime-threaded process is a
documented deadlock) and EXECUTING only its host partition each round:

- cross-partition packets fall out naturally: ``send_packet`` already
  appends to the destination's inbox, and a non-owned destination's inbox
  is never drained locally — the worker sweeps those inboxes at the
  barrier and ships the events to the owner through its pipe;
- the parent runs the Controller role: folds the workers' reported
  next-event times (including in-flight cross-partition packets), computes
  each window, and broadcasts it;
- determinism is insertion-order-free by construction: event queues order
  by the total (time, kind, src, seq) key, log comparisons use the sorted
  ``log_tuples`` contract, and counters merge by key — so any worker
  count produces identical results (asserted by tests against the serial
  engine).

Crash safety (engine/supervisor.py, docs/robustness.md): every parent
pipe read goes through poll+deadline with liveness checks — a dead or
hung worker surfaces as a diagnostic ``WorkerDiedError`` instead of an
indefinite hang.  With supervision enabled (``worker_restart_max > 0``)
a dead worker is respawned and its rounds replayed from the journaled
(deterministic) round messages; repeated failures escalate to a serial
from-t=0 replay — bit-identical output either way, by the
parallelism-invariance law.  The worker protocol additionally speaks
``checkpoint`` (reply: the worker engine's cloudpickle blob),
``restore`` (rebuild from a blob instead of fresh construction), and
``replay`` (silent round re-execution) for the on-disk checkpoint/resume
layer (engine/checkpoint.py).

Gates: pure-model hosts only (managed OS processes need the fd/channel
machinery of the owning process — they keep the threaded scheduler, which
genuinely parallelizes them because futex waits release the GIL), and no
pcap (every replica would open the same capture files).
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import time as wall_time
from typing import Optional

import numpy as np

from ..config.options import ConfigOptions
from ..core import time as stime
from ..core.event import Event, EventKind
from .cpu_engine import CpuEngine, SimResult

log = logging.getLogger("shadow_tpu.cpu_mp")


def _partition(n_hosts: int, workers: int) -> list[list[int]]:
    """Round-robin by host id — the reference's per-thread queue fill."""
    return [list(range(w, n_hosts, workers)) for w in range(workers)]


def spawn_cpu_workers(target, arg_tuples):
    """Spawn one daemon worker per arg tuple (``target(*args, conn)``)
    with a dedicated pipe, via the SPAWN start method (forking a process
    whose runtime threads may hold locks is a documented deadlock, and
    the parent has usually initialized JAX by now).  Children import
    shadow_tpu (which imports jax) at spawn: JAX_PLATFORMS is pinned to
    the CPU platform around the spawns because the parent owns the chip
    and libtpu admits one process per chip — a worker that initialized
    the TPU backend would fail or hang; workers never need a device.
    Shared by MpCpuEngine and backend.hybrid.MpHybridEngine.
    Returns ``(conns, procs)``."""
    ctx = mp.get_context("spawn")
    conns, procs = [], []
    saved_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for args in arg_tuples:
            parent_conn, child_conn = ctx.Pipe()
            p = ctx.Process(
                target=target, args=(*args, child_conn), daemon=True
            )
            p.start()
            child_conn.close()
            conns.append(parent_conn)
            procs.append(p)
    finally:
        if saved_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved_platform
    return conns, procs


def _worker_round(
    engine: CpuEngine,
    owned_hosts: list,
    owned_set: set,
    managed_owned: list,
    record_turns: bool,
    window_end: int,
    incoming: list,
) -> tuple:
    """Execute one deterministic round and build the 7-tuple reply.

    Shared by the live ``round`` message and the supervision ``replay``
    path: a replayed round runs the identical code and merely discards
    the reply (the parent already routed its outbound packets and folded
    its telemetry), so the replica's state transition is byte-identical
    to the original execution."""
    engine.window_end = window_end
    for dst, t, src, seq, data in incoming:
        engine.hosts[dst].queue.push(
            Event(t, EventKind.PACKET, src_host=src, seq=seq, data=data)
        )
    wparts = ()
    if record_turns:
        wparts = engine._ledger_participants(managed_owned, window_end)
    for h in owned_hosts:
        h.execute(window_end)
    # ship cross-partition sends: the local replicas of non-owned
    # destinations collected them in their inboxes
    outbound = []
    for hid, h in enumerate(engine.hosts):
        if hid not in owned_set and h.inbox:
            outbound.extend(
                (hid, ev.time, ev.src_host, ev.seq, ev.data)
                for ev in h.inbox
            )
            h.inbox.clear()
    # own-partition barrier merge (inbox drain, log/latency fold) —
    # only owned hosts ever have content
    engine._barrier_merge()
    next_t = min(
        (h.queue.next_time() for h in owned_hosts),
        default=stime.NEVER,
    )
    return (
        next_t, outbound, engine._min_used_lat,
        engine.perf_log.drain() if engine.perf_log is not None else (),
        # netobs: this round's pop count (the parent owns the global
        # window histogram)
        engine.netobs.take_round_pops() if engine.netobs is not None else 0,
        # device-turn ledger: (participants, staged sends)
        wparts,
        engine._ledger_take_sends(managed_owned) if record_turns else 0,
    )


def _worker_main(
    cfg: ConfigOptions,
    owned: list[int],
    record_turns: bool,
    worker_id: int,
    conn,
) -> None:
    # spawn start method: each worker REBUILDS its world replica from the
    # config — deterministic construction makes every replica identical,
    # and no JAX-threaded parent is ever forked (forking a process whose
    # runtime threads may hold locks is a documented deadlock, and the
    # parent has usually initialized a device backend by now).  The build
    # is lazy: a supervised respawn may substitute a ``restore`` blob for
    # fresh construction.
    from ..engine.supervisor import maybe_test_hang, worker_recv

    engine: Optional[CpuEngine] = None
    owned_hosts: list = []
    managed_owned: list = []
    owned_set = set(owned)
    hang_armed: list = []

    def _attach(eng: CpuEngine) -> None:
        nonlocal engine, owned_hosts, managed_owned
        engine = eng
        if cfg.experimental.perf_logging:
            # worker perf lines buffer locally and ride the round reply
            # to the parent's locked sink (run_control.BufferedPerfLog)
            from ..engine.run_control import BufferedPerfLog

            engine.perf_log = BufferedPerfLog()
        owned_hosts = [engine.hosts[i] for i in owned]
        managed_owned = []
        if record_turns:
            # device-turn ledger (obs/turns.py): this worker accounts
            # the managed hosts it owns — participants before execution,
            # staged send counts after — and ships both with the round
            # reply so the parent's ledger matches the serial engine's
            managed = set(h.host_id for h in engine._ledger_enable())
            managed_owned = [h for h in owned_hosts if h.host_id in managed]

    try:
        while True:
            msg = worker_recv(conn)
            kind = msg[0]
            if kind == "round":
                if engine is None:
                    _attach(CpuEngine(cfg))
                _, window_end, incoming = msg
                # test-only fault injection: hang on the first LIVE
                # round past the trigger (replay is exempt)
                maybe_test_hang(worker_id, window_end, hang_armed)
                conn.send(_worker_round(
                    engine, owned_hosts, owned_set, managed_owned,
                    record_turns, window_end, incoming,
                ))
            elif kind == "replay":
                if engine is None:
                    _attach(CpuEngine(cfg))
                for window_end, incoming in msg[1]:
                    _worker_round(
                        engine, owned_hosts, owned_set, managed_owned,
                        record_turns, window_end, incoming,
                    )
            elif kind == "restore":
                _attach(CpuEngine.from_checkpoint(msg[1]))
            elif kind == "checkpoint":
                if engine is None:
                    _attach(CpuEngine(cfg))
                conn.send(engine.checkpoint_payload())
            elif kind == "finish":
                if engine is None:
                    _attach(CpuEngine(cfg))
                engine.finalize()
                counters: dict[str, int] = {}
                for h in owned_hosts:
                    for k, v in h.counters.items():
                        counters[k] = counters.get(k, 0) + v
                conn.send((
                    engine.event_log,
                    counters,
                    {i: dict(engine.hosts[i].counters) for i in owned},
                    list(getattr(engine, "process_errors", [])),
                    # netobs per-host arrays: only owned hosts ever
                    # executed here, so the parent's elementwise sum
                    # over workers reconstructs the full plane
                    engine.netobs_snapshot(),
                    # flowtrace events: each event is emitted by exactly
                    # one worker (the owner of the executing host), so
                    # the parent's concatenation + canonical sort equals
                    # the serial engine's stream
                    (
                        engine.flowtrace.raw_events()
                        if engine.flowtrace is not None else None
                    ),
                ))
                return
            else:  # pragma: no cover - protocol error
                return
    except (EOFError, OSError):
        # the parent tore the pipe down (shutdown, or a supervision
        # reap racing this worker's send): exit quietly, never strand
        return
    finally:
        conn.close()


class MpCpuEngine:
    """Fork-based parallel twin of CpuEngine for pure-model workloads."""

    def __init__(self, cfg: ConfigOptions, workers: int = 0) -> None:
        cfg.validate()
        for hopt in cfg.hosts:
            if hopt.pcap_enabled:
                raise ValueError(
                    "MpCpuEngine does not support pcap capture (every "
                    "worker replica would open the capture files); use "
                    "CpuEngine"
                )
        # obs Recorder + perf sink: attach before run() (the facade
        # pattern); perf_logging in the config makes run() build the
        # default stderr sink itself so worker lines have somewhere to go
        self.obs = None
        self.perf_log = None
        # Managed (native-shim) hosts are supported: every worker replica
        # instantiates all ManagedApp objects, but a process LAUNCHES only
        # when its host's start task executes — and workers execute owned
        # hosts only, so each OS process, its futex channels, and its
        # stdout files belong to exactly one worker.  Cross-partition
        # traffic (TcpSegment/bytes payloads) pickles through the pipes
        # like any model payload.
        self.cfg = cfg
        self.workers = workers if workers > 0 else (os.cpu_count() or 1)
        self.workers = max(1, min(self.workers, len(cfg.hosts)))
        # netobs (obs/netobs.py): the parent owns the global window
        # histogram and the merged per-host arrays; populated by run()
        self._netobs = None
        # flowtrace (obs/flowtrace.py): concatenated worker event
        # streams; populated by run()
        self._flowtrace = None
        # checkpoint/resume (engine/checkpoint.py): set a CheckpointManager
        # before run() to checkpoint every
        # ``experimental.checkpoint_every_windows`` rounds; run(...,
        # resume_payload=...) continues from a saved payload.  This is an
        # engine-level API (the facade's cpu path is the serial engine);
        # exercised by tests and scripts/checkpoint_smoke.py.
        self.checkpoint_mgr = None
        self.checkpoints_written: list = []
        self.checkpoint_request = False
        # supervision outcome markers (tests + telemetry)
        self.worker_restarts = 0
        self.escalated = False

    def netobs_snapshot(self):
        """The merged telemetry snapshot of the last run (None when
        netobs is off)."""
        return self._netobs

    def flowtrace_snapshot(self):
        """The merged flow-event snapshot of the last run (None when
        flowtrace is off)."""
        return self._flowtrace

    def flowtrace_lines(self, host=None) -> list[str]:
        from ..obs import flowtrace as ftr

        snap = self._flowtrace
        if snap is None:
            return ["flowtrace is not enabled (set experimental.flowtrace)"]
        events, lost = ftr.canonical_events(
            snap["raw"], self.cfg.experimental.flowtrace_capacity
        )
        names = [h.hostname for h in self.cfg.hosts]
        return ftr.snapshot_lines(
            events, lost + snap["ring_lost"], names, host=host
        )

    # -- escalation (supervisor.EscalateToSerial) --------------------------

    def _run_serial_fallback(self, on_window, cause) -> SimResult:
        """A worker exhausted its restart budget: abandon the parallel
        run and replay serially from t=0.  The parallelism-invariance
        law makes the serial result bit-identical to what the parallel
        run would have produced; the obs accumulators are zeroed first
        so the abandoned prefix never double-counts."""
        log.warning(
            "escalating to the serial engine (deterministic from-t=0 "
            "replay): %s", cause,
        )
        self.escalated = True
        if self.obs is not None:
            self.obs.reset_for_replay()
        eng = CpuEngine(self.cfg)
        eng.perf_log = self.perf_log
        eng.obs = self.obs
        result = eng.run(on_window=on_window)
        self._netobs = eng.netobs_snapshot()
        self._flowtrace = eng.flowtrace_snapshot()
        return result

    # -- checkpoint assembly -----------------------------------------------

    def _write_checkpoint(
        self, pool, window_end, next_times, pending, min_used_lat,
        rounds, window_hist,
    ) -> None:
        blobs = pool.checkpoint()
        payload = {
            "workers": blobs,
            "ctl": {
                "workers": self.workers,
                "next_times": list(next_times),
                "pending": [list(p) for p in pending],
                "min_used_lat": min_used_lat,
                "rounds": rounds,
                "window_hist": (
                    window_hist.copy() if window_hist is not None else None
                ),
            },
            "obs": (
                self.obs.checkpoint_state() if self.obs is not None else None
            ),
        }
        path = self.checkpoint_mgr.save(
            payload,
            backend_kind="cpu_mp",
            epoch_ns=window_end,
            windows=rounds,
            summary={"rounds": rounds, "workers": self.workers},
        )
        self.checkpoints_written.append(path)
        log.info("checkpoint written: %s (epoch %d ns)", path, window_end)

    def run(self, on_window=None, resume_payload=None) -> SimResult:
        from ..engine.supervisor import CpuWorkerPool, EscalateToSerial

        if self.cfg.experimental.perf_logging and self.perf_log is None:
            from ..engine.run_control import PerfLog

            self.perf_log = PerfLog()
        if self.workers == 1:
            # degenerate case (single-core box): forking one worker only
            # adds pipe overhead — run in-process, same results.
            # Checkpoint/resume for the serial engine belongs to the
            # facade (engine/sim.py), not this wrapper.
            if resume_payload is not None:
                raise ValueError(
                    "MpCpuEngine resume requires workers >= 2 (the "
                    "single-worker path delegates to CpuEngine; resume "
                    "it through the facade)"
                )
            eng = CpuEngine(self.cfg)
            eng.perf_log = self.perf_log
            eng.obs = self.obs
            result = eng.run(on_window=on_window)
            self._netobs = eng.netobs_snapshot()
            self._flowtrace = eng.flowtrace_snapshot()
            return result
        # the parent's replica serves the Controller role: initial
        # next-event times, runahead, stop time (no host ever executes
        # here)
        ctl = CpuEngine(self.cfg)
        stop = ctl.stop_time
        n = len(ctl.hosts)
        parts = _partition(n, self.workers)
        owner_of = [hid % self.workers for hid in range(n)]

        ckpt_every = 0
        if self.checkpoint_mgr is not None:
            reason = ctl.checkpoint_unsupported_reason()
            if reason is None:
                ckpt_every = max(
                    0, self.cfg.experimental.checkpoint_every_windows
                )
            else:
                log.warning("checkpointing disabled: %s", reason)
                self.checkpoint_mgr = None

        turns = self.obs.turns if self.obs is not None else None
        exp = self.cfg.experimental
        resume_blobs = None
        if resume_payload is not None:
            ctl_state = resume_payload["ctl"]
            if ctl_state["workers"] != self.workers:
                raise ValueError(
                    f"checkpoint was taken with {ctl_state['workers']} "
                    f"worker(s); this engine has {self.workers} — the "
                    "journal/partition layout is worker-count-specific"
                )
            resume_blobs = resume_payload["workers"]
            if self.obs is not None and resume_payload.get("obs"):
                self.obs.restore_checkpoint_state(resume_payload["obs"])
                turns = self.obs.turns
        pool = CpuWorkerPool(
            self.cfg, parts, turns is not None,
            heartbeat_s=exp.worker_heartbeat_s,
            restart_max=exp.worker_restart_max,
            resume_blobs=resume_blobs,
        )

        t0 = wall_time.perf_counter()
        try:
            if resume_payload is not None:
                ctl_state = resume_payload["ctl"]
                next_times = list(ctl_state["next_times"])
                pending = [list(p) for p in ctl_state["pending"]]
                min_used_lat = ctl_state["min_used_lat"]
                rounds = ctl_state["rounds"]
            else:
                next_times = [
                    min((ctl.hosts[i].queue.next_time() for i in owned),
                        default=stime.NEVER)
                    for owned in parts
                ]
                pending = [[] for _ in range(self.workers)]
                min_used_lat = None
                rounds = 0
            obs = self.obs
            netobs_on = self.cfg.experimental.netobs
            window_hist = None
            if netobs_on:
                from ..obs import netobs as nom

                if resume_payload is not None and (
                    resume_payload["ctl"].get("window_hist") is not None
                ):
                    window_hist = resume_payload["ctl"][
                        "window_hist"].copy()
                else:
                    window_hist = np.zeros(nom.HIST_BUCKETS, dtype=np.int64)
            while True:
                start = min(next_times)
                if start >= stop or start == stime.NEVER:
                    break
                # one source of truth for the window law: feed the folded
                # latency into the serial engine's own formula
                ctl._min_used_lat = min_used_lat
                window_end = min(start + ctl.current_runahead(), stop)
                pool.round_no = rounds
                t_round = wall_time.perf_counter() if obs is not None else 0.0
                for w in range(self.workers):
                    pool.send_round(w, window_end, pending[w])
                    pending[w] = []
                t_ship = wall_time.perf_counter() if obs is not None else 0.0
                perf_lines: list[str] = []
                round_pops = 0
                round_parts: list[int] = []
                round_sends = 0
                for w in range(self.workers):
                    (next_t, outbound, mul, wlines, wpops, wparts,
                     wsends) = pool.recv_round(w)
                    next_times[w] = next_t
                    if mul is not None and (
                        min_used_lat is None or mul < min_used_lat
                    ):
                        min_used_lat = mul
                    for pkt in outbound:
                        pending[owner_of[pkt[0]]].append(pkt)
                    if wlines:
                        perf_lines.extend(wlines)
                    round_pops += wpops
                    if wparts:
                        round_parts.extend(wparts)
                    round_sends += wsends
                if netobs_on and round_pops > 0:
                    window_hist[nom.hist_bucket(round_pops)] += 1
                if turns is not None:
                    # the controller's ledger row (obs/turns.py): sorted
                    # union of the workers' participant sets normalizes
                    # the round-robin partition back to host-id order —
                    # identical rows to the serial engine's
                    parts_t = tuple(sorted(round_parts))
                    if round_sends:
                        cause = "injection"
                    elif parts_t:
                        cause = "host_window"
                    else:
                        cause = "free_run"
                    turns.turn(
                        cause, start, window_end,
                        inject_rows=round_sends, participants=parts_t,
                    )
                # in-flight cross-partition packets lower the owners'
                # next-event times before the next window is computed
                for w in range(self.workers):
                    for pkt in pending[w]:
                        if pkt[1] < next_times[w]:
                            next_times[w] = pkt[1]
                rounds += 1
                if obs is not None:
                    # the collect leg IS the workers' window execution as
                    # seen from the controller; the ship leg is pure pipe
                    t1 = wall_time.perf_counter()
                    obs.record("worker_pipe", "pipe_ship", t_round,
                               t_ship - t_round)
                    obs.record("window_compute", "mp_round", t_ship,
                               t1 - t_ship, window_end=window_end)
                    m = obs.metrics
                    m.count("windows")
                    m.count("pipe_messages", 2 * self.workers)
                    m.observe("window_span_ns", window_end - start)
                # worker perf lines route through the parent's locked
                # sink, in (round, worker-id) order — one coherent stream
                if perf_lines and self.perf_log is not None:
                    self.perf_log.emit_many(perf_lines)
                if self.checkpoint_mgr is not None and (
                    self.checkpoint_request
                    or (ckpt_every > 0 and rounds % ckpt_every == 0)
                ):
                    self.checkpoint_request = False
                    self._write_checkpoint(
                        pool, window_end, next_times, pending,
                        min_used_lat, rounds, window_hist,
                    )
                if on_window is not None:
                    on_window(start, window_end, min(next_times))

            event_log: list = []
            counters: dict[str, int] = {}
            per_host: list[dict] = [{} for _ in range(n)]
            process_errors: list[str] = []
            nb_arrays = None
            ft_raw: list = []
            flowtrace_on = self.cfg.experimental.flowtrace
            for logw, cnt, per, errs, wsnap, wflows in pool.finish():
                event_log.extend(logw)
                for k, v in cnt.items():
                    counters[k] = counters.get(k, 0) + v
                for hid, c in per.items():
                    per_host[hid] = c
                process_errors.extend(errs)
                if wsnap is not None:
                    if nb_arrays is None:
                        nb_arrays = nom.empty_arrays(n)
                    nom.merge_arrays(nb_arrays, wsnap["arrays"])
                if wflows:
                    ft_raw.extend(tuple(e) for e in wflows)
            if netobs_on and nb_arrays is not None:
                self._netobs = {
                    "arrays": nb_arrays,
                    "window_hist": window_hist,
                    "log_lost": 0,
                }
            if flowtrace_on:
                self._flowtrace = {"raw": ft_raw, "ring_lost": 0}
        except EscalateToSerial as esc:
            pool.close()
            self.worker_restarts = pool.restarts
            return self._run_serial_fallback(on_window, esc)
        finally:
            pool.close()
            self.worker_restarts = max(self.worker_restarts, pool.restarts)
        wall = wall_time.perf_counter() - t0
        return SimResult(
            sim_time_ns=stop,
            wall_seconds=wall,
            rounds=rounds,
            event_log=event_log,
            counters=counters,
            per_host_counters=per_host,
            process_errors=process_errors,
        )
