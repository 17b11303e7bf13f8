"""Hybrid backend: managed (real-binary) hosts riding the TPU data plane.

This is BASELINE.json's literal design — "keep syscall emulation on host
CPU, offload the per-round packet-scheduling hot path" — applied to this
framework's engines: hosts whose processes are real managed binaries (or
any host-only app) execute on the host CPU exactly as in
:class:`~shadow_tpu.backend.cpu_engine.CpuEngine`, while the network data
plane — per-lane arrival queues, latency/loss lookup, token buckets,
CoDel, and every lane-model host — runs on the device
(:mod:`~shadow_tpu.backend.lanes`).  The seam mirrors the reference's
``Worker::send_packet`` offload target (worker.rs:330-404):

- a managed host's **send** runs the source half of the packet lifecycle
  host-side (up bucket, pcap, loss draw — identical law to
  ``CpuEngine.send_packet``) and stages the PACKET arrival event for
  device injection (``lanes._inject_merge``), with the payload bytes
  parked host-side keyed by ``(src, seq)``;
- the device advances windows over ALL lanes; deliveries destined to
  external lanes exit through the egress buffer at their exact
  ``t_deliver`` (down bucket + CoDel applied on device — the dst half of
  the lifecycle) and are queued host-side as DELIVERY events carrying the
  parked payload;
- the window law stays global and bit-identical to the scalar oracle:
  the device folds the host side's next event times into every window
  start (``lanes._build_hybrid_fused_run``), free-runs windows the host
  has no events in, and returns after completing up to
  ``experimental.hybrid_fuse_k`` windows the host participates in — at
  most one device call per host sync instead of one per round; the
  covered rounds are serviced and validated post-hoc
  (``HybridEngine._fused_turn``).

Two engines drive that seam:

- :class:`HybridEngine` — the serial driver: one process services every
  managed host's syscall plane (threads only help managed futex waits);
- :class:`MpHybridEngine` — PARALLEL syscall servicing: N spawned worker
  processes each own a partition of the external hosts (the analog of the
  reference's thread-per-core syscall workers, thread_per_core.rs:17-50,
  which its 6.38x headline used at parallelism 16) and run their syscall
  plane concurrently, while the parent owns the device and the window
  law.  Staged sends and egressed deliveries ride the worker pipes at
  round barriers, so the host<->device boundary stays one packed block
  in + one packed readback out per device turn regardless of worker
  count.

Event ordering is worker-count-invariant by construction: event queues
order by the total (time, kind, src, seq) key, injection decomposition is
order-invariant (the device queue merge sorts on the full key), and logs
and counters merge at barriers in deterministic (worker-id, host-id)
order.  Event logs diff bit-identical against ``CpuEngine`` on the same
config at any worker count (tests/test_hybrid.py, tests/test_hybrid_mp.py)
— the determinism contract the reference's determinism suite checks
(src/test/determinism/).

The host<->device sync-cost accounting (``sync_stats``: per-turn transfer
counts/bytes, blocking device-sync seconds, syscall-service seconds) is
always on — the counters are a handful of Python ints per window — and is
surfaced per window through the perf-log plumbing when
``experimental.perf_logging`` is set (docs/hybrid.md).  So is the clock
every host phase of a turn is a span of (``TURN_PHASES`` below,
:mod:`shadow_tpu.obs.clock`): the seconds per phase
(``sync_stats["phase_s"]``), one row per turn
(``sync_stats["turn_spans"]``), the same pairs to the obs Recorder when
there is one, and ``hybrid/<phase>`` annotations on the profiler's clock
(docs/observability.md "Reading a turn").
"""

from __future__ import annotations

import logging
import os
import time as wall_time
from typing import Optional

import jax
import numpy as np

from ..config.options import ConfigOptions
from ..core import time as stime
from ..core.event import Event, EventKind
from ..core.event_queue import EventQueue
from ..engine.supervisor import recv_with_deadline, worker_recv
from ..obs.clock import TurnClock
from . import lanes
from .cpu_engine import DELIVERED, CpuEngine, Delivery, Host, SimResult

NEVER = stime.NEVER

log = logging.getLogger("shadow_tpu.hybrid")

# The host phases of a turn (docs/observability.md "reading a turn"), each
# a span of the engine's clock where the work happens; ``walk`` is the
# turn's own residual: scalar decode, the validation walk's Python,
# ledger calls, rollback bookkeeping.
TURN_PHASES = (
    "inject",           # _build_inj: host packing (overflow blocks ship)
    "peek",             # _fuse_depth, _peek_ext_times, _pack_schedule
    "dispatch",         # fused_fn(state, block) until it RETURNS, the
                        # block's ONE transfer in it (and the eager one)
    "device_wait",      # the blocking device_get: device_sync_s
    "egress_read",      # _read_egress: the head's rows; a tail's slice, D2H
    "egress_apply",     # every _apply_egress call
    "service_ship",     # _mp_round's send leg
    "service_collect",  # its receive leg; the serial engine's whole round
    "callback",         # on_window: the caller's hook, never the engine's
    "walk",
)
# What a turn's row says beside its seconds: the last window end the
# device reached, the slowest worker's execution wall summed over the
# turn's rounds, device dispatches (2 when it rolled back: what
# device_turns counts), and what caused it — staged rows injected, egress
# rows read, windows the primary dispatch completed, whether it resumed a
# mid-window egress drain, whether it rolled back.
TURN_NOTES = (
    "window_end_ns", "worker_exec_max_s", "dispatches", "n_staged",
    "egress_rows", "k_done", "retry", "rolled",
)
# clock phase -> (obs phase, obs span name, what the span's detail is
# called): obs's documented phases keep their names; ``device_turn`` is
# the blocking wait alone and ``dispatch`` the call before it, so obs's
# phases tile a turn as the clock's do (docs/observability.md)
_OBS_PHASE = {
    "inject": ("injection", None, "rows"),
    "peek": ("peek", None, None),
    "dispatch": ("dispatch", None, None),
    "device_wait": ("device_turn", None, "window_end"),
    "egress_read": ("egress", None, "rows"),
    "egress_apply": ("egress_apply", None, "rows"),
    "service_ship": ("worker_pipe", "pipe_ship", None),
    "service_collect": ("syscall_service", None, "window_end"),
    "callback": ("callback", None, None),
    "walk": ("walk", None, None),
}

# fusion-effectiveness floor (obs_turns runs): warn when the achieved turn
# collapse falls below this fraction of the ledger's remaining
# kfusion_headroom_freerun prediction
_FUSE_WARN_FRACTION = 0.5


def config_has_managed(cfg: ConfigOptions) -> bool:
    """True when any process path is not a registered built-in model —
    i.e. a real binary that must execute host-side under the shim."""
    from ..models.base import _REGISTRY

    return any(
        p.path not in _REGISTRY for h in cfg.hosts for p in h.processes
    )


class _HostSideHybrid(CpuEngine):
    """The host-side half of the hybrid seam, shared by the serial engine
    and the multiprocess syscall workers: external-host bookkeeping, the
    staging send sink, and the delivery-application law.  Construction
    reuses ``CpuEngine.__init__`` wholesale (hosts, apps, pcap, hosts
    file, routing — one source of truth); ``_hybrid_host_init`` then
    strips the lane-covered hosts' host-side state."""

    # -- fused-turn peek/validation primitives (shared with the mp
    # -- syscall workers; docs/hybrid.md "k-window fusion law") -----------

    def _peek_head_horizon(self, slots: int, hosts=None,
                           floor_t: int = 0):
        """The next ``slots - 1`` DISTINCT event times (>= ``floor_t``)
        across this side's hosts (or an explicit host iterable — the mp
        parent uses its replica of a worker's partition), plus the
        horizon — the first time the list does NOT cover (NEVER when
        exhaustive).  The device must never free-run past the horizon:
        an uncovered external event could start a window there.  ONE
        definition shared by the serial dispatch peek, the worker-reply
        peeks, and the parent's initial partition replicas — the
        schedules agreeing across replicas is a determinism
        invariant."""
        if hosts is None:
            hosts = self._next_hosts
        seen = {
            ev.time for h in hosts for ev in h.queue._heap
            if ev.time >= floor_t
        }
        times = sorted(seen)
        head = tuple(times[: slots - 1])
        horizon = times[slots - 1] if len(times) >= slots else NEVER
        return head, horizon

    def _range_count(self, lo: int, hi: int) -> int:
        """Number of queued events with ``lo <= t < hi`` — the covered
        rounds' cleanliness probe: execution only pops events below the
        window end, so a post-round change in this count means the round
        CREATED an event inside the still-covered fused span (a window
        boundary the device could not have known -> rollback).

        Both probes scan the raw heaps — O(total queued events) per
        covered round, a few hundred entries at the measured scales
        (syscall service sits at ~5% of wall; see docs/hybrid.md).  If
        managed hosts ever hold deep timer queues, replace with
        incremental range counters maintained at push/pop."""
        n = 0
        for h in self._next_hosts:
            for ev in h.queue._heap:
                if lo <= ev.time < hi:
                    n += 1
        return n

    def _hybrid_host_init(self) -> None:
        from ..native.process import ManagedApp
        from .tpu_engine import LaneCompatError

        ext = np.array(
            [any(isinstance(a, ManagedApp) for a in h.apps) for h in self.hosts],
            dtype=bool,
        )
        if not ext.any():
            raise LaneCompatError(
                "no managed hosts in config; use the plain tpu backend"
            )
        self.external_mask = ext
        self.external_hosts: list[Host] = [
            h for h, e in zip(self.hosts, ext) if e
        ]
        for h, e in zip(self.hosts, ext):
            if e:
                h.staged = []  # sends awaiting device injection
            else:
                # lane-covered: the device runs this host; drop its
                # host-side apps, start events, and pcap writer (the
                # device log reconstructs lane pcaps at collect)
                h.apps = []
                h.queue = EventQueue()
                h.pcap = None
        # hosts whose queues feed next_event_time() and whose buffers the
        # barrier sweeps: every external host for the serial engine; a
        # worker narrows this to its owned partition
        self._next_hosts: list[Host] = self.external_hosts
        self._staged_merged: list = []
        self.host_rounds = 0

    # -- host-side packet source half (the law IS CpuEngine's) -------------

    def send_packet(self, src_host, dst, size_bytes, payload=None,
                    loopback=False, retx=False):
        """The shared source half (``CpuEngine._packet_source_half``: up
        bucket, outbound pcap, dynamic-runahead record, Bernoulli loss)
        with a device-injection sink: the surviving packet is STAGED for
        the device instead of pushed into a host queue — the dst half
        (down bucket, CoDel, delivery) runs on the device for every lane,
        external ones included.  Loopback traffic never touches the
        device: the lo interface is host-local by definition."""
        if loopback:
            return self._loopback_send(src_host, size_bytes, payload)
        seq, arr = self._packet_source_half(src_host, dst, size_bytes, payload,
                                            retx=retx)
        if arr is None:
            return seq
        src_host.staged.append(
            (arr, src_host.host_id, seq, size_bytes, dst, payload)
        )
        return seq

    def inbound(self, dst_host, ev):  # pragma: no cover - defensive
        raise AssertionError(
            "hybrid host queues never hold PACKET events (the device owns "
            "the dst half of the lifecycle)"
        )

    # -- barrier (external hosts only; lane hosts have no host state) ------

    def next_event_time(self) -> int:
        return min(
            (h.queue.next_time() for h in self._next_hosts), default=NEVER
        )

    def _barrier_merge(self) -> None:
        staged = self._staged_merged
        for h in self._next_hosts:
            if h.staged:
                staged.extend(h.staged)
                h.staged = []
            if h.log_buf:
                self.event_log.extend(h.log_buf)
                h.log_buf.clear()
            if h.min_used_lat is not None:
                if self._min_used_lat is None or h.min_used_lat < self._min_used_lat:
                    self._min_used_lat = h.min_used_lat
                h.min_used_lat = None

    # -- delivery application ----------------------------------------------

    def _apply_delivery_row(self, t, src, dst, seq, size, payload) -> None:
        """Queue one device-egressed delivery as a host-side DELIVERY
        event at its exact t_deliver (down bucket + CoDel already applied
        on device; the DELIVERED/DROP_CODEL log records live in the
        device log).  Mirrors the oracle's passive-delivery elision: an
        external host whose apps are all passive consumes the delivery
        inline."""
        h = self.hosts[dst]
        if h.pcap is not None:  # inbound capture at delivery
            h.pcap.capture(
                stime.sim_to_emu(t), self.ips.by_host[src],
                self.ips.by_host[dst], size, payload,
                key=(0, src, dst, seq),
            )
        if payload is None and h.passive_delivery:
            h.now = t
            for app in h.apps:
                h._current_app = app
                app.on_delivery(h, t, src, seq, size, payload=None)
            return
        h.queue.push(
            Event(
                t, EventKind.DELIVERY, src_host=src, seq=seq,
                data=Delivery(src, seq, size, payload),
            )
        )


class _HybridWorker(_HostSideHybrid):
    """A syscall-servicing worker's world replica: the host-side hybrid
    half restricted to an owned partition of the external hosts.  Spawned
    by :class:`MpHybridEngine`; deterministic construction makes every
    replica identical, and a managed OS process launches only when its
    host's start task executes — which happens in exactly one worker."""

    def __init__(self, cfg: ConfigOptions, owned: list[int]) -> None:
        super().__init__(cfg)
        self._hybrid_host_init()
        owned_set = set(owned)
        self.owned_hosts = [
            h for h in self.external_hosts if h.host_id in owned_set
        ]
        self._next_hosts = self.owned_hosts


def _hybrid_worker_main(
    cfg: ConfigOptions, owned: list[int], record_turns: bool,
    peek_slots: int, conn
) -> None:
    """Worker loop: apply shipped deliveries, execute the owned hosts'
    window (syscall servicing — the parallel hot path), sweep staged
    sends back to the parent.  Protocol mirrors cpu_mp._worker_main.
    Perf-log lines buffer locally and ride the round reply to the
    parent's locked sink (one coherent stream per run).  When the
    device-turn ledger is on, the reply also carries the owned hosts
    participating in this window (events < window_end, taken after the
    shipped deliveries land and before execution — the identical law the
    serial engine applies, so the parent's ledger is worker-count
    invariant).  The reply always carries the cleanliness flag for the
    shipped validation range (did this round create an event inside the
    still-covered fused span?) and the partition's refreshed
    ``peek_slots``-wide peek schedule, so the parent can bound the next
    dispatch's k before any further round trip (docs/hybrid.md "k-window
    fusion law"), and last the wall of the round's own execution (owned
    hosts + barrier merge): what of the parent's collect leg is this
    worker computing, the rest being pickling, pipes and wake-ups."""
    engine = _HybridWorker(cfg, owned)
    if cfg.experimental.perf_logging:
        from ..engine.run_control import BufferedPerfLog

        engine.perf_log = BufferedPerfLog()
    finished = False
    try:
        while True:
            # poll-sliced recv: a dead/vanished parent EOFs instead of
            # blocking forever, so the finally below still reaps the
            # managed OS processes this worker launched (no orphans)
            msg = worker_recv(conn)
            if msg[0] == "round":
                _, window_end, rows, we_final = msg
                engine.window_end = window_end
                for t, src, dst, seq, size, payload in rows:
                    engine._apply_delivery_row(t, src, dst, seq, size, payload)
                probe = we_final > window_end
                pre_range = (
                    engine._range_count(window_end, we_final)
                    if probe else 0
                )
                wparts = ()
                if record_turns:
                    wparts = tuple(
                        h.host_id for h in engine.owned_hosts
                        if h.queue.next_time() < window_end
                    )
                t_exec = wall_time.perf_counter()
                for h in engine.owned_hosts:
                    h.execute(window_end)
                engine._barrier_merge()
                exec_s = wall_time.perf_counter() - t_exec
                clean = (
                    not probe
                    or engine._range_count(window_end, we_final) == pre_range
                )
                staged = engine._staged_merged
                engine._staged_merged = []
                conn.send((
                    engine.next_event_time(),
                    staged,
                    engine._min_used_lat,
                    engine.perf_log.drain()
                    if engine.perf_log is not None else (),
                    wparts,
                    clean,
                    engine._peek_head_horizon(peek_slots),
                    exec_s,
                ))
            elif msg[0] == "finish":
                engine.finalize()
                finished = True
                counters: dict[str, int] = {}
                for h in engine.owned_hosts:
                    for k, v in h.counters.items():
                        counters[k] = counters.get(k, 0) + v
                conn.send((
                    engine.event_log,
                    counters,
                    {h.host_id: dict(h.counters) for h in engine.owned_hosts},
                    list(getattr(engine, "process_errors", [])),
                    # netobs host-side arrays (owned hosts only executed)
                    engine.netobs_snapshot(),
                    # flowtrace host-side events (each managed send's
                    # source half is emitted by exactly one worker)
                    (
                        engine.flowtrace.raw_events()
                        if engine.flowtrace is not None else None
                    ),
                ))
                return
            else:  # pragma: no cover - protocol error
                return
    except (EOFError, OSError):
        # parent tore the pipe down (normal teardown after an error on
        # its side, or parent death): exit quietly — the finally reaps
        return
    finally:
        if not finished:
            # abnormal teardown (parent died / raised): still reap the
            # managed OS processes this worker launched — no orphans
            try:
                engine.finalize()
            except Exception:
                pass
        conn.close()


class HybridEngine(_HostSideHybrid):
    """CpuEngine for the external (managed) hosts; TPU lanes for the rest.

    Owns the device, the window law, and the batched host<->device
    boundary: one packed block in (``lanes.TurnBlock``), one packed
    vector out — the scalars and the egress buffer's head — per device
    turn (``sync_stats`` records the exact transfer counts/bytes)."""

    def __init__(
        self, cfg: ConfigOptions, log_capacity: Optional[int] = None
    ) -> None:
        super().__init__(cfg)
        from .tpu_engine import TpuEngine

        self._hybrid_host_init()
        self.device = TpuEngine(
            cfg, log_capacity=log_capacity, external=self.external_mask,
            world=self.world,
        )
        # multi-chip data plane (parallel/mesh.py): a negotiated mesh
        # shards the lane axis; the window loops then compile the hybrid
        # kernels under it — ≤2 transfers per turn and the sync_stats
        # byte accounting are unchanged (tests/test_multichip.py)
        from .. import parallel

        n_dev = parallel.negotiate_from_config(cfg, len(cfg.hosts))
        if n_dev > 1:
            self.device.attach_mesh(parallel.make_mesh(n_dev))
        # parked payloads for in-flight packets, keyed (src_host, seq) —
        # popped when the device egresses the delivery
        self._parked: dict = {}
        self._dev_min_used: Optional[int] = None
        # host<->device sync-cost accounting (docs/hybrid.md): cheap
        # Python counters, always on; perf_logging surfaces them per
        # window through PerfLog.hybrid_agg
        self.sync_stats: dict = {
            "device_turns": 0,      # turn_fn calls (windows batched per)
            # the two walls accepted readers divide by; the clock below
            # keeps them equal to their phases' sums
            "device_sync_s": 0.0,   # blocking scalar-readback wall time
            "syscall_service_s": 0.0,  # host-side window execution wall
            "scalar_reads": 0,      # D2H transfers: packed scalars + head
            "h2d_copies": 0,        # H2D transfers: turn blocks, one per
                                    # fused call and per overflow block
            "inject_blocks": 0,     # of them, blocks that carried rows
            "inject_rows": 0,       # staged sends carried by those blocks
            "inject_bytes": 0,      # H2D bytes (int32[W] a copy)
            "egress_head_reads": 0,  # egress drains the readback's head held
            "egress_reads": 0,      # D2H transfers: egress TAIL slices
            "egress_rows": 0,       # delivery rows either way
            "egress_bytes": 0,      # D2H bytes (every head, padded tails)
            # k-window fusion + async dispatch (docs/hybrid.md):
            "fused_dispatches": 0,  # dispatches covering >= 2 validated windows
            "fused_windows": 0,     # validated windows those covered
            "turns_saved": 0,       # blocking dispatches fusion eliminated, net
            "fuse_rollbacks": 0,    # prefix-rebuild dispatches (mispredictions)
            "async_dispatch_hits": 0,    # eager dispatches adopted at the barrier
            "async_dispatch_misses": 0,  # eager dispatches discarded (inputs diverged)
            "dispatch_retries": 0,  # failed fused dispatches re-dispatched
            # how often the device's record appends engaged, read once
            # at collect (lanes._append_rows; per iteration: divide by
            # the lane_iters counter):
            "append_blocks": 0,       # block writes, log and egress
            "append_rows": 0,         # rows those blocks wrote
            "append_tail_blocks": 0,  # of them, for queue-overflow records
            # the syscall workers' own round walls (each reply carries
            # its worker's): per round the slowest worker's, and all of
            # them summed; the serial engine books its round to both
            "worker_exec_max_s": 0.0,
            "worker_exec_sum_s": 0.0,
        }
        # the host-phase clock (obs/clock.py, always on): every host
        # phase of a turn is a span of it.  It keeps device_sync_s and
        # syscall_service_s (above) as the sums of their phases, the
        # per-phase totals in phase_s and one row per turn in turn_spans
        self.clock = TurnClock(
            self, "hybrid", TURN_PHASES, notes=TURN_NOTES,
            turn_phase="walk", obs_map=_OBS_PHASE,
            totals=(self.sync_stats, {
                "device_sync_s": ("device_wait",),
                "syscall_service_s": ("service_ship", "service_collect"),
            }),
        )
        self.sync_stats["phase_s"] = self.clock.phase_s
        self.sync_stats["turn_spans"] = self.clock.ring
        exp = cfg.experimental
        # dispatch retry-with-backoff law (docs/robustness.md): a failed
        # fused device dispatch re-dispatches from the pre-turn device
        # checkpoint (purity makes the retry bit-identical) up to this
        # many times before escalating to the watchdog/failover boundary
        self._dispatch_retry_max = max(0, int(exp.dispatch_retry_max))
        # injected backend_stall support (docs/faults.md): the hybrid
        # window loop raises BackendStallError when the sim clock crosses
        # the earliest scheduled stall — the facade's failover boundary
        # then replays on the CPU engine (managed hosts run there
        # natively).  Other fault kinds stay gated off this backend.
        self._stall_after = NEVER
        if cfg.faults.events:
            from .tpu_engine import LaneCompatError

            sched = cfg.faults.schedule()
            stalls = [
                ev.at for ev in sched.events if ev.kind == "backend_stall"
            ]
            if len(stalls) != len(sched.events):
                raise LaneCompatError(
                    "only backend_stall fault events are supported on the "
                    "hybrid tpu backend; use the cpu backend for "
                    "link/host fault schedules"
                )
            if stalls:
                self._stall_after = min(stalls)
        # the k-window fusion depth cap (docs/hybrid.md "k-window fusion
        # law"): a dispatch covers at most this many participating
        # windows.  At a cap of 1 the walk below accepts its one window
        # unconditionally (no rollback) and there is nothing for an eager
        # dispatch to overlap, so that stays off
        self._fuse_k = max(1, int(exp.hybrid_fuse_k))
        self._async_on = self._fuse_k > 1
        # peeked-schedule width: enough slots that multi-event windows do
        # not exhaust the schedule mid-span (last slot = the horizon)
        self._ext_slots = max(2 * self._fuse_k, 9)
        # the turn's one host->device block and its two reused staging
        # arrays: the turn's own (a rollback's rebuild re-ships it with
        # the validated depth) and the eager dispatch's, whose injection
        # part stays empty and which the turn's packing never touches
        self._lay = lanes.TurnBlock(
            self.device.params.inject_batch, self._ext_slots
        )
        self._turn_np = self._lay.empty()
        self._eager_np = self._lay.empty()
        self._fuse_we_final = None  # covered-round validation range end
        self._round_clean = True    # set by _service_round/_mp_round
        self._eager = None          # double-buffered speculative dispatch
        # 2-bit saturating adoption predictor for the eager dispatch:
        # issue for real at >= 2, otherwise record a PHANTOM speculation
        # (inputs only, no device work) whose would-have-hit outcome
        # keeps training the predictor — so a cold predictor can re-arm.
        # Purely an efficiency device: adopted results are bit-equal to
        # the blocking dispatch, misses are discarded, so the predictor
        # cannot affect any observable output
        self._eager_pred = 2
        # the provable external lookahead (the Chandy-Misra per-source
        # bound, docs/hybrid.md): the min latency on any edge OUT of a
        # managed host's node.  A send staged while servicing a covered
        # window departs inside that window and cannot arrive earlier
        # than departure + this bound, so a dispatch may cover about
        # L_ext / runahead windows before speculation even begins
        from ..net.graph import _UNREACHABLE

        idx = self.node_index
        ext_nodes = sorted({idx[h.host_id] for h in self.external_hosts})
        all_nodes = sorted(set(idx.values()))
        lat = self.graph.latency_ns[np.ix_(ext_nodes, all_nodes)]
        ok = lat != _UNREACHABLE
        self._ext_min_lat: Optional[int] = (
            int(lat[ok].min()) if ok.any() else None
        )
        # device-turn ledger plumbing (obs/turns.py; all inert when
        # obs/turns are off): the round's participant set and the pending
        # syscall_service->device_turn trace-flow anchor
        self._last_participants: tuple = ()
        self._flow_pending = None
        self._flow_seq = 0

    def device_info(self) -> dict:
        """Where the lane data plane ran (TpuEngine.device_info)."""
        return self.device.device_info()

    # -- dynamic runahead ---------------------------------------------------

    def current_runahead(self) -> int:
        """The global dynamic-runahead law: min over BOTH sides' smallest
        used latency (the device scalar is read back after every device
        turn; between turns it cannot change)."""
        if not self.dynamic_runahead:
            return self.runahead
        vals = [
            v for v in (self._min_used_lat, self._dev_min_used)
            if v is not None
        ]
        if not vals:
            return self.runahead
        return max(min(vals), self._runahead_floor, 1)

    # -- egress application -------------------------------------------------

    def _apply_egress(self, rows) -> None:
        with self.clock.span("egress_apply", len(rows)):
            for t, src, dst, seq, size, outcome in rows:
                t, src, dst, seq, size = (
                    int(t), int(src), int(dst), int(seq), int(size)
                )
                payload = self._parked.pop((src, seq), None)
                if int(outcome) != DELIVERED:
                    continue  # device-side drop: payload released, no event
                self._route_delivery(t, src, dst, seq, size, payload)

    def _route_delivery(self, t, src, dst, seq, size, payload) -> None:
        self._apply_delivery_row(t, src, dst, seq, size, payload)

    # -- device turn --------------------------------------------------------

    def _inj_block(self, staged) -> None:
        """Pack staged sends (at most ``inject_batch``) into the turn
        block's injection columns (payloads are parked here, keyed
        (src, seq))."""
        block = self._turn_np
        self._lay.clear(block)
        buf = self._lay.columns(block)
        for i, (arr, src, seq, sz, d, payload) in enumerate(staged):
            if payload is not None:
                self._parked[(src, seq)] = payload
            buf["valid"][i] = 1
            buf["dst"][i] = d
            buf["thi"][i] = arr >> 31
            buf["tlo"][i] = arr & lanes.MASK31
            buf["auxh"][i] = (lanes.PACKET << lanes.AUX_KIND_SHIFT) | (
                src << lanes.AUX_SRC_SHIFT
            )
            buf["auxl"][i] = seq
            buf["size"][i] = sz
        if staged:
            st = self.sync_stats
            st["inject_blocks"] += 1
            st["inject_rows"] += len(staged)

    def _ship(self, block):
        """A turn block on its way to the device: the ONE array a fused
        call (or an overflow merge) is handed, and the turn's one
        host->device transfer.  A host COPY, because the staging arrays
        are repacked while the previous dispatch may still be reading;
        handed over as numpy, because the jitted call's own argument path
        is the cheapest of the three ways to place it on the chip
        (PERF.md section 6, PR 40: ``jnp.array`` first costs ~0.4 ms
        more, ``jax.device_put`` ~0.15)."""
        st = self.sync_stats
        st["h2d_copies"] += 1
        st["inject_bytes"] += block.nbytes
        return block.copy()

    def _read_egress(self, state, count: int, lost: int, head) -> list:
        """The turn's egress rows, the ``egress_read`` span: ``head`` is
        the buffer's first rows as the scalar readback brought them
        (``lanes.hyb_egress_rows``); only a turn that egressed more reads
        the device again, for the tail alone.  The fused walk applies
        deliveries lazily per validated window (``egress_apply``).  Empty
        egress is no read and no span."""
        if lost:
            raise RuntimeError(
                "hybrid egress buffer overflowed despite the headroom "
                "guard (device invariant violation)"
            )
        if count == 0:
            return []
        with self.clock.span("egress_read", count):
            st = self.sync_stats
            st["egress_rows"] += count
            n_head = len(head)
            rows = head[:count].tolist()
            if count <= n_head:
                st["egress_head_reads"] += 1
            else:
                # pad the slice's end to a power of two: distinct slice
                # sizes compile distinct device programs, so this caps
                # churn at log2(E)
                span = 1
                while span < count:
                    span <<= 1
                span = min(span, self.device.params.egress_capacity)
                st["egress_reads"] += 1
                st["egress_bytes"] += (span - n_head) * 6 * 8
                rows += np.asarray(
                    state.egress[n_head:span]
                )[:count - n_head].tolist()
        self.clock.add("egress_rows", count)
        if self.obs is not None:
            self.obs.metrics.count("egress_rows", count)
        return rows

    def _build_inj(self, staged, inject_fn, state):
        """Pack staged sends into the turn block's injection columns:
        the ``inject`` span, host work alone (the block's copy is the
        dispatch's).  Oversized staging: overflow blocks ship and
        dispatch eagerly — JAX's async dispatch overlaps their H2D +
        queue merge with the host-side packing of the next block."""
        b = self.device.params.inject_batch
        n_staged = len(staged)
        with self.clock.span("inject", n_staged):
            while len(staged) > b:
                self._inj_block(staged[:b])
                state = inject_fn(state, self._ship(self._turn_np))
                staged = staged[b:]
            self._inj_block(staged)
        return state, n_staged

    # -- k-window fused turns (docs/hybrid.md "k-window fusion law") ---------

    def _pack_schedule(self, block, times, used_enc: int,
                       k_eff: int) -> None:
        """Write a peeked-time schedule into ``block`` as (hi, lo) int32
        words (NEVER maps to the (NEVER32, NEVER32) sentinel pair), and
        the dynamic-runahead fold and the depth beside it."""
        lay = self._lay
        t = np.asarray(times, dtype=np.int64)
        inf = t >= NEVER
        hi, lo = lay.schedule(block)
        hi[:] = np.where(inf, lanes.NEVER32, t >> 31)
        lo[:] = np.where(inf, lanes.NEVER32, t & lanes.MASK31)
        block[lay.used_at] = used_enc
        block[lay.k_at] = k_eff

    def _peek_ext_times(self, floor_t: int = 0) -> list:
        """The fused dispatch's external-event schedule: the next
        ``_ext_slots - 1`` distinct host-side event times (>= floor_t),
        padded with the horizon in the trailing slots (ascending, so the
        device's pointer-advance law stays a prefix count)."""
        es = self._ext_slots
        head, horizon = self._peek_head_horizon(es, floor_t=floor_t)
        head = list(head)
        return head + [horizon] * (es - len(head))

    def _drop_eager(self) -> None:
        if self._eager is not None:
            if self._eager["sc"] is not None:
                self.sync_stats["async_dispatch_misses"] += 1
            self._eager = None
            self._eager_pred = max(self._eager_pred - 1, 0)

    def _fuse_depth(self) -> int:
        """The per-dispatch fusion depth: the provable external-lookahead
        bound (windows the law covers before any speculation: a managed
        send departing in covered window 1 arrives >= L_ext past its
        start, i.e. about L_ext/runahead windows later) PLUS one
        speculative window, floored at 3 and capped by
        ``hybrid_fuse_k``.  The floor is statistical, not provable: the
        ledger measured ~half of covered rounds staging nothing and
        staged arrivals landing >= 1.3 windows out (TCP segments ride
        multi-hop latencies, not the global-min edge), so two windows of
        speculation pay for their occasional rollback; the validation
        law makes any depth safe, this only tunes the waste.  Recomputed
        per dispatch: dynamic runahead moves the bound."""
        k = self._fuse_k
        if self._ext_min_lat is not None:
            ra = self.current_runahead()
            k = min(k, max(3, self._ext_min_lat // ra + 1))
        return k

    def _issue_eager(self, fused_fn, state, lane_min: int,
                     floor_t: int) -> None:
        """Double-buffered async dispatch: while the covered rounds are
        serviced host-side, eagerly dispatch the NEXT fused turn under
        the speculation that they stage nothing and create no event the
        peek (taken at the covered span's end) does not show.  Resolved
        at the next dispatch barrier: adopted only when the real
        dispatch inputs match the speculated ones bit-exact — the
        provably-empty-injection condition that makes the (otherwise
        unsound, docs/hybrid.md) double-buffering a pure overlap."""
        clock = self.clock
        with clock.span("peek"):
            ext = self._peek_ext_times(floor_t)
            host_next = ext[0]
            start = min(host_next, lane_min)  # staged-empty speculation
            if start >= self.stop_time or start == NEVER:
                return
            end = min(start + self.current_runahead(), self.stop_time)
            if lane_min >= end:
                return  # next window would be host-only: nothing to overlap
            used_enc = (
                lanes.NEVER32 if self._min_used_lat is None
                else self._min_used_lat
            )
            k_eff = self._fuse_depth()
            if self._eager_pred < 2:
                # cold predictor: record the speculation's inputs WITHOUT
                # device work — its would-have-hit outcome re-trains the
                # predictor at the next dispatch
                self._eager = {
                    "base": state, "ext": ext, "used": used_enc, "k": k_eff,
                    "state": None, "sc": None,
                }
                return
            self._pack_schedule(self._eager_np, ext, used_enc, k_eff)
        with clock.span("dispatch"):
            state2, scalars = fused_fn(state, self._ship(self._eager_np))
        self._eager = {
            "base": state, "ext": ext, "used": used_enc, "k": k_eff,
            "state": state2, "sc": scalars,
        }

    def _dispatch_fused(self, state, fused_fn, ext, used_enc,
                        n_staged: int, k_eff: int):
        """Dispatch (or adopt the eagerly dispatched) fused device call
        and block on its packed readback.  The turn block holds the
        inputs; ``ext``, ``used_enc``, ``n_staged`` and ``k_eff`` are
        their HOST values, which adoption compares: it requires the real
        inputs to equal the speculated ones bit-exact — same base state
        object, same peeked schedule, same dynamic-runahead fold, and an
        empty injection — then the eager result IS the dispatch result
        by functional purity, and the readback blocks only for whatever
        device compute the overlapped syscall servicing did not hide (its
        ``dispatch`` was booked to the turn that issued it).  Returns
        (state, scalars, the closed ``device_wait`` span)."""
        st = self.sync_stats
        clock = self.clock
        e = self._eager
        state2 = scalars = None
        if e is not None:
            self._eager = None
            match = (
                e["base"] is state and e["ext"] == ext
                and e["used"] == used_enc and e["k"] == k_eff
                and n_staged == 0
            )
            self._eager_pred = min(self._eager_pred + 1, 3) if match \
                else max(self._eager_pred - 1, 0)
            if e["sc"] is None:
                pass  # phantom speculation: predictor trained, no result
            elif match:
                st["async_dispatch_hits"] += 1
                state2, scalars = e["state"], e["sc"]
            else:
                st["async_dispatch_misses"] += 1
        if scalars is None:
            with clock.span("dispatch"):
                state2, scalars = fused_fn(state, self._ship(self._turn_np))
        with clock.span("device_wait") as wait:
            sc = jax.device_get(scalars)  # the one blocking readback
            wait.detail = int(sc[lanes.HYB_DEV_WE])
        clock.add("dispatches", 1)
        st["device_turns"] += 1
        st["scalar_reads"] += 1
        st["egress_bytes"] += 6 * 8 * lanes.HYB_EGRESS_HEAD  # the head
        return state2, sc, wait

    def _dispatch_retrying(self, checkpoint, fused_fn, ext, used_enc,
                           n_staged: int, k_eff: int):
        """The dispatch retry-with-backoff law (docs/robustness.md): a
        failed fused dispatch (device runtime error raised at dispatch or
        at the blocking readback) re-dispatches from the pre-turn device
        checkpoint — ``fused_fn`` is pure, so a successful retry is
        bit-identical to a first-try success — with exponential backoff,
        up to ``experimental.dispatch_retry_max`` times.  Exhausted
        retries escalate to the watchdog/failover boundary as
        :class:`BackendStallError`; an injected stall passes through
        untouched (retrying an injected fault would defeat the test)."""
        from ..faults.watchdog import BackendStallError

        attempt = 0
        while True:
            try:
                return self._dispatch_fused(
                    checkpoint, fused_fn, ext, used_enc, n_staged, k_eff,
                )
            except BackendStallError:
                raise
            except Exception as e:
                attempt += 1
                # any outstanding speculation rode the failed timeline
                self._drop_eager()
                if attempt > self._dispatch_retry_max:
                    raise BackendStallError(
                        f"fused device dispatch failed after "
                        f"{attempt - 1} retr"
                        f"{'y' if attempt - 1 == 1 else 'ies'}: "
                        f"{type(e).__name__}: {e}"
                    ) from e
                self.sync_stats["dispatch_retries"] += 1
                backoff = min(0.05 * 2 ** (attempt - 1), 1.0)
                log.warning(
                    "fused dispatch failed (%s: %s); re-dispatching from "
                    "the pre-turn checkpoint in %.2fs (attempt %d/%d)",
                    type(e).__name__, e, backoff, attempt,
                    self._dispatch_retry_max,
                )
                wall_time.sleep(backoff)

    def _fused_turn(self, state, fused_fn, inject_fn, run_round,
                    on_window, t_start: int):
        """FUSED device turns until the span at ``t_start`` is through:
        one turn (``_turn``: one primary dispatch, with its rebuild if it
        rolls back), and one more for each mid-window egress-headroom
        pause — the device paused for room; covered rounds may have
        staged, so the next turn repacks and resumes.  Every turn is a
        turn of the clock and leaves one row.  Returns (state,
        dev_next)."""
        is_retry = False
        while True:
            with self.clock.turn():
                state, lane_min, t_start, paused = self._turn(
                    state, fused_fn, inject_fn, run_round, on_window,
                    t_start, is_retry,
                )
            if not paused:
                return state, lane_min
            is_retry = True

    def _turn(self, state, fused_fn, inject_fn, run_round, on_window,
              t_start: int, is_retry: bool):
        """One FUSED device turn: dispatch up to ``hybrid_fuse_k``
        consecutive participating windows in one device call, then
        service the covered syscall rounds window-by-window under the
        arrival-frontier validation law:

        - the frontier F starts unbounded; each covered round lowers it
          to its earliest staged-send arrival, and to its own window end
          when the round created an event inside the still-covered span
          or moved the dynamic-runahead fold;
        - window j+1 is accepted only while ``we_{j+1} <= F`` — a staged
          arrival at or past the span's remaining windows cannot change
          their boundaries or contents (it merges at the next dispatch,
          before the window containing it is computed), so the accepted
          prefix is bit-identical to one dispatch per window (a cap
          of 1, whose single window is accepted unconditionally) by
          construction;
        - on a misprediction the device ROLLS BACK: one rebuild dispatch
          from the pre-turn state with ``k_eff`` = the validated prefix
          reproduces exactly the accepted windows (pure function, same
          inputs), and the staged injection rides the next turn.

        Egress rows apply lazily per accepted window so a rollback never
        double-applies a delivery or double-pops a parked payload; the
        rebuild's egress buffer (all rows below the validated frontier,
        already applied) is deliberately never read back.  Runs inside
        the clock's open turn: every phase below is a span of it, what is
        left is its ``walk``.  Returns (state, dev_next, the last
        accepted window end, whether the device paused mid-window)."""
        st = self.sync_stats
        clock = self.clock
        obs = self.obs
        turns = obs.turns if obs is not None else None
        staged = self._staged_merged
        self._staged_merged = []
        state, n_staged = self._build_inj(staged, inject_fn, state)
        prev_we = t_start
        used_enc = (
            lanes.NEVER32 if self._min_used_lat is None
            else self._min_used_lat
        )
        with clock.span("peek"):
            k_eff = self._fuse_depth()
            ext = self._peek_ext_times()
            self._pack_schedule(self._turn_np, ext, used_enc, k_eff)
        checkpoint = state
        state, sc, wait = self._dispatch_retrying(
            state, fused_fn, ext, used_enc, n_staged, k_eff
        )
        lane_min = int(sc[lanes.HYB_LANE_MIN])
        dev_we = int(sc[lanes.HYB_DEV_WE])
        dev_used = int(sc[lanes.HYB_MIN_USED])
        self._dev_min_used = (
            None if dev_used >= lanes.NEVER32 else dev_used
        )
        k_done = int(sc[lanes.HYB_K_DONE])
        we_list = [
            int(sc[lanes.HYB_WE_BASE + i]) for i in range(k_done)
        ]
        clock.note("window_end_ns", dev_we)
        clock.note("n_staged", n_staged)
        clock.note("k_done", k_done)
        clock.note("retry", int(is_retry))
        if obs is not None:
            obs.metrics.count("device_turns")
            if (
                not is_retry
                and self._flow_pending is not None
                and turns is not None
                and obs.tracer is not None
            ):
                fid, anchor = self._flow_pending
                self._flow_pending = None
                tr = obs.tracer
                tr.flow("s", fid, "turn_cause", "turn_flow", anchor)
                tr.flow(
                    "f", fid, "turn_cause", "turn_flow",
                    wait.t0 + wait.dur / 2,
                )
        egress_count = int(sc[lanes.HYB_EGRESS_COUNT])
        rows = self._read_egress(
            state, egress_count, int(sc[lanes.HYB_EGRESS_LOST]),
            lanes.hyb_egress_rows(sc, self._fuse_k),
        )
        retry = lane_min < dev_we  # mid-window egress-headroom pause
        if self._async_on and not retry and we_list:
            self._issue_eager(fused_fn, state, lane_min, we_list[-1])
        # ---- the validated servicing walk ------------------------------
        w_valid = 0
        rounds_run = 0
        frontier = NEVER
        pend = rows
        parts_buf = []
        for j, we_j in enumerate(we_list):
            if we_j > frontier:
                break  # a staged arrival lands inside this window
            apply_now = [r for r in pend if int(r[0]) < we_j]
            if apply_now:
                pend = [r for r in pend if int(r[0]) >= we_j]
                self._apply_egress(apply_now)
            if self.next_event_time() < we_j:
                rounds_run += 1
                pre_len = len(self._staged_merged)
                pre_mul = self._min_used_lat
                self.window_end = we_j
                self._fuse_we_final = we_list[-1]
                try:
                    run_round(we_j)
                finally:
                    self._fuse_we_final = None
                if turns is not None:
                    parts_buf.append(self._last_participants)
                new = self._staged_merged[pre_len:]
                if new:
                    a = min(int(e[0]) for e in new)
                    if a < frontier:
                        frontier = a
                if not self._round_clean or (
                    pre_mul != self._min_used_lat
                ):
                    # the round created an event inside the covered
                    # span, or moved the dynamic-runahead fold: later
                    # window boundaries are unreproducible
                    frontier = min(frontier, we_j)
            w_valid = j + 1
            if on_window is not None:
                with clock.span("callback"):
                    on_window(prev_we, we_j, self.next_event_time())
            prev_we = we_j
        if w_valid < k_done:
            # misprediction: rebuild the validated prefix from the
            # checkpoint (same inputs + k_eff = prefix -> the prefix
            # windows reproduce bit-identically); the original
            # dispatch's unapplied egress rows are discarded (the rows
            # its invalidated windows generated must not land) and the
            # staged injection rides the next turn
            clock.note("rolled", 1)
            if self._eager is not None:
                # the eager speculation rode the invalidated timeline —
                # discard it without training the predictor: its miss
                # signals "rollback", not "the next injection will not
                # be empty"
                if self._eager["sc"] is not None:
                    st["async_dispatch_misses"] += 1
                self._eager = None
            st["fuse_rollbacks"] += 1
            if w_valid >= 2:
                st["fused_dispatches"] += 1
                st["fused_windows"] += w_valid
            st["turns_saved"] += w_valid - 2
            # the rebuild dispatch re-ships the turn's block (nothing
            # has repacked it since) with the validated depth, and goes
            # through the same timed dispatch/readback bookkeeping as a
            # primary dispatch, booking to the same phases of this turn
            # (the eager buffer was dropped above, so no adoption)
            self._turn_np[self._lay.k_at] = w_valid
            state, sc_r, _wait = self._dispatch_retrying(
                checkpoint, fused_fn, ext, used_enc, n_staged, w_valid,
            )
            assert int(sc_r[lanes.HYB_K_DONE]) == w_valid, (
                "fused prefix rebuild diverged from the original "
                "dispatch (determinism violation)"
            )
            lane_min = int(sc_r[lanes.HYB_LANE_MIN])
            dev_we = int(sc_r[lanes.HYB_DEV_WE])
            dev_used = int(sc_r[lanes.HYB_MIN_USED])
            self._dev_min_used = (
                None if dev_used >= lanes.NEVER32 else dev_used
            )
            clock.note("window_end_ns", dev_we)
            if obs is not None:
                obs.metrics.count("device_turns")
            # the rebuild regenerated the validated prefix
            # bit-identically, so its egress buffer holds exactly the
            # prefix-generated rows; those at or past the last validated
            # window end never passed the walk's apply filter
            # (down-bucket/CoDel queueing delays t_deliver into the
            # invalidated span) — apply them now, like the validated
            # path's trailing pend rows.  Invalidated-window rows exist
            # only in the original buffer and stay dropped: the rebuilt
            # device state still carries their packets in flight
            egr_r = int(sc_r[lanes.HYB_EGRESS_COUNT])
            rows_r = self._read_egress(
                state, egr_r, int(sc_r[lanes.HYB_EGRESS_LOST]),
                lanes.hyb_egress_rows(sc_r, self._fuse_k),
            )
            late = [
                r for r in rows_r
                if int(r[0]) >= we_list[w_valid - 1]
            ]
            if late:
                self._apply_egress(late)
            if turns is not None:
                self._ledger_fused_rows(
                    turns, t_start, dev_we, w_valid, n_staged,
                    egress_count, is_retry, parts_buf, rollback=True,
                    rollback_egr=egr_r, rounds_run=rounds_run,
                )
            return state, lane_min, prev_we, False
        # ---- span fully validated ----------------------------------------
        if k_done >= 2:
            st["fused_dispatches"] += 1
            st["fused_windows"] += k_done
            st["turns_saved"] += k_done - 1
        if turns is not None:
            self._ledger_fused_rows(
                turns, t_start, dev_we, w_valid, n_staged,
                egress_count, is_retry, parts_buf, rollback=False,
                rounds_run=rounds_run,
            )
        if pend:
            # trailing rows: deliveries of the in-progress (paused) or
            # post-span windows — host events the next dispatch's peek
            # schedule folds
            self._apply_egress(pend)
        if self.perf_log is not None:
            self.perf_log.hybrid_agg("device", dev_we, self.sync_stats)
        return state, lane_min, prev_we, retry

    def _ledger_fused_rows(self, turns, t_start, t_end, w_valid,
                           inj_rows, egr_rows, is_retry, parts_buf,
                           rollback, rollback_egr=0, rounds_run=0):
        """Record one fused dispatch's ledger rows (docs/observability.md)
        under the PR 11 cause precedence (injection > host_window >
        free_run): a dispatch that carried staged rows is an
        ``injection`` row even when fused — a cap of 1 would have
        blocked for it, and labeling it ``free_run`` would inflate
        ``strict_free_turns`` and the remaining free-run headroom the
        ``_FUSE_WARN_FRACTION`` soft check compares against; an
        injection-free dispatch covering >= 2 validated windows is a
        ``free_run`` row.  Either way ``windows`` carries the coverage
        (the fused accounting keys off it, not the cause).
        Single-window dispatches keep the full PR 11 law —
        ``host_window`` only when the window's round actually ran (a
        passive-inline delivery consumes no round and stays a strict
        ``free_run``), ``egress_drain`` for an egress-headroom
        resumption that completed no window; a
        prefix rebuild adds a ``rollback`` row with ``windows=0`` so the
        conservation law counts every dispatch while the implied-unfused
        accounting counts covered windows once."""
        if inj_rows:
            cause = "injection"
        elif w_valid >= 2:
            cause = "free_run"
        elif w_valid == 1 and rounds_run:
            cause = "host_window"
        elif is_retry and not w_valid:
            cause = "egress_drain"
        else:
            cause = "free_run"
        turns.turn(
            cause, t_start, t_end, windows=max(w_valid, 1),
            inject_rows=inj_rows, egress_rows=egr_rows,
        )
        for parts in parts_buf:
            if parts:
                turns.attach_participants(parts)
        if rollback:
            # the rebuild's egress re-read (prefix rows re-fetched to
            # recover post-span deliveries) rides the rollback row so
            # ledger egress_rows_total keeps matching the engine's
            # D2H row accounting
            turns.turn(
                "rollback", t_start, t_end, windows=0,
                egress_rows=rollback_egr,
            )

    # -- the hybrid round loop ----------------------------------------------

    def _service_round(self, scheduler, until: int) -> None:
        """One host-side syscall-service round + barrier: the
        ``service_collect`` span (which keeps ``syscall_service_s``; per
        window through the perf log / obs spans).  Inside a fused span
        (``_fuse_we_final`` set past the window) the round also runs the
        cleanliness probe: a changed event count in ``[until,
        we_final)`` means the round created an event inside the
        still-covered span — the fused-turn walk rolls back there."""
        obs = self.obs
        with self.clock.span("service_collect", until) as rnd:
            wf = self._fuse_we_final
            probe = wf is not None and wf > until
            pre_range = self._range_count(until, wf) if probe else 0
            if obs is not None and obs.turns is not None:
                # the turn ledger's participant set, taken BEFORE
                # execution mutates the queues: managed hosts with events
                # inside the window — the identical law the mp workers
                # apply, so the ledger is bit-identical at any worker
                # count
                self._last_participants = tuple(
                    h.host_id for h in self._next_hosts
                    if h.queue.next_time() < until
                )
            scheduler.run_round(until)
            self._barrier_merge()
            self._round_clean = (
                not probe or self._range_count(until, wf) == pre_range
            )
        # this process IS the one worker: its round's wall is both sums
        self._book_worker_exec(rnd.dur, rnd.dur)
        if obs is not None and obs.turns is not None \
                and obs.tracer is not None:
            self._flow_seq += 1
            self._flow_pending = (self._flow_seq, rnd.t0 + rnd.dur / 2)
        if self.perf_log is not None:
            self.perf_log.hybrid_agg("host", until, self.sync_stats)

    def _book_worker_exec(self, slowest_s: float, sum_s: float) -> None:
        """A round's worker execution walls: the slowest worker's (what
        of the collect leg is syscalls being serviced) and all of them
        summed (balance), cumulative and into the open turn's row."""
        st = self.sync_stats
        st["worker_exec_max_s"] += slowest_s
        st["worker_exec_sum_s"] += sum_s
        self.clock.add("worker_exec_max_s", slowest_s)

    def run(self, on_window=None) -> SimResult:
        from ..engine.scheduler import HostScheduler

        exp = self.cfg.experimental
        scheduler = HostScheduler(
            self.external_hosts,
            parallelism=self.cfg.general.parallelism,
            policy=exp.scheduler,
            pin_cpus=exp.use_cpu_pinning,
        )
        try:
            return self._run_hybrid(scheduler, on_window)
        finally:
            scheduler.shutdown()

    def _run_hybrid(self, scheduler, on_window) -> SimResult:
        t0 = wall_time.perf_counter()
        try:
            return self._hybrid_loop(scheduler, on_window, t0)
        except BaseException:
            self.finalize()
            raise

    def _maybe_stall(self, start: int) -> None:
        """Raise the injected ``backend_stall`` once the sim clock
        crosses its epoch (same law as the TPU step driver): the facade's
        failover boundary catches it and replays on the CPU engine, where
        the managed hosts run natively."""
        if start >= self._stall_after:
            from ..faults.watchdog import BackendStallError

            epoch = self._stall_after
            self._stall_after = NEVER  # raise once
            self._drop_eager()
            raise BackendStallError(
                f"injected backend stall at {epoch} ns "
                "(fault schedule backend_stall event)"
            )

    def _window_loop(self, run_round, on_window):
        """The hybrid window law, shared verbatim by the serial engine
        and the multiprocess controller: only the round executor differs
        (``run_round(until)`` = threaded scheduler round vs worker-pipe
        round).  Returns the final device state for collection.

        Device turns are delegated to ``_fused_turn`` (one dispatch
        covers up to ``hybrid_fuse_k`` participating windows; covered
        rounds are serviced and validated post-hoc), with the
        double-buffered eager dispatch resolving at adoption barriers.
        Host-only windows, the dynamic-runahead law, and the staged-send
        fold are the oracle's — the fusion is a pure scheduling change
        (tests/test_hybrid_fusion.py pins every depth cap to the CPU
        oracle)."""
        dev = self.device
        state = dev.place_state(dev.initial_state())
        fused_fn, inject_fn = dev.make_hybrid_fns(
            self._fuse_k, self._ext_slots
        )
        dev_next = dev.first_event_time()
        turns = self.obs.turns if self.obs is not None else None
        while True:
            host_next = self.next_event_time()
            staged_min = min(
                (e[0] for e in self._staged_merged), default=NEVER
            )
            dev_eff = min(dev_next, staged_min)
            start = min(host_next, dev_eff)
            if start >= self.stop_time or start == NEVER:
                self._drop_eager()
                return state
            self._maybe_stall(start)
            end = min(start + self.current_runahead(), self.stop_time)
            if self._staged_merged or dev_eff < end:
                state, dev_next = self._fused_turn(
                    state, fused_fn, inject_fn, run_round, on_window,
                    start,
                )
                continue
            # host-only window (device idle beyond it, nothing staged):
            # an outstanding eager dispatch assumed a device window next
            # and cannot match — discard before the round runs
            self._drop_eager()
            self.window_end = end
            run_round(end)  # its spans book to the totals: no turn is open
            if turns is not None:
                turns.host_round()
            self.host_rounds += 1
            if on_window is not None:
                with self.clock.span("callback"):
                    on_window(start, end, self.next_event_time())

    def _check_fusion_accounting(self) -> None:
        """End-of-run ledger cross-check (ISSUE 13 satellite): the
        fused-turn accounting must conserve — ``turns + turns_saved``
        equals the one-window-per-dispatch turn count implied by the
        cause rows — and
        the achieved collapse is compared against the ledger's remaining
        free-run headroom prediction (warn, never fail, below
        ``_FUSE_WARN_FRACTION`` of it)."""
        obs = self.obs
        if obs is None or obs.turns is None:
            return
        from ..obs import turns as tmod

        tmod.check_fusion_accounting(
            obs.turns, self.sync_stats, warn_fraction=_FUSE_WARN_FRACTION
        )

    def netobs_snapshot(self):
        """The combined telemetry plane: host-side counters (managed
        hosts' sends, loopback, throttles) summed with the device-side
        counters (every dst half, lane-model hosts' sends).  The window
        histogram is the device's: ALL packet arrivals pop on the lane
        plane on this backend (``inbound`` asserts host queues never
        hold PACKET events), so there is no host-plane arrival
        histogram to report."""
        host = super().netobs_snapshot()
        dev = self.device.netobs_snapshot()
        if host is None or dev is None:
            return None
        from ..obs import netobs as nom

        arrays = nom.merge_arrays(
            {k: v.copy() for k, v in dev["arrays"].items()},
            host["arrays"],
        )
        return {
            "arrays": arrays,
            "window_hist": dev["window_hist"],
            "log_lost": 0,
        }

    def flowtrace_snapshot(self):
        """The combined flow-event stream: host-side events (managed
        sends' source half, loopback) concatenated with the device ring
        (arrival halves, lane-model hosts' full lifecycles).  Each
        lifecycle stage is emitted by exactly one side, so the
        concatenation + canonical sort is the complete stream.  Drained
        here only — at collect — never per turn, so ``sync_stats``
        transfer counts are untouched by tracing."""
        host = super().flowtrace_snapshot()
        dev = self.device.flowtrace_snapshot()
        if host is None or dev is None:
            return None
        return {
            "raw": list(host["raw"]) + list(dev["raw"]),
            "ring_lost": host["ring_lost"] + dev["ring_lost"],
        }

    def _hybrid_loop(self, scheduler, on_window, t0) -> SimResult:
        state = self._window_loop(
            lambda until: self._service_round(scheduler, until), on_window
        )
        self._check_fusion_accounting()
        self.finalize()
        wall = wall_time.perf_counter() - t0

        dev_result = self.device.collect(state, wall)
        self.sync_stats.update(self.device.append_stats)
        counters: dict[str, int] = dict(dev_result.counters)
        for h in self.hosts:
            for k, v in h.counters.items():
                counters[k] = counters.get(k, 0) + v
        return SimResult(
            sim_time_ns=self.stop_time,
            wall_seconds=wall,
            rounds=dev_result.rounds + self.host_rounds,
            event_log=dev_result.event_log + self.event_log,
            counters=counters,
            per_host_counters=[dict(h.counters) for h in self.hosts],
            process_errors=list(getattr(self, "process_errors", [])),
        )


class MpHybridEngine(HybridEngine):
    """Hybrid backend with PARALLEL syscall servicing: N spawned worker
    processes own disjoint partitions of the external (managed) hosts and
    execute their syscall plane concurrently (real OS-process parallelism,
    no GIL), while the parent owns the device and the window law.

    The parent is the Controller: it folds the workers' next-event times
    (plus in-flight egressed deliveries), computes every window, ships
    delivery rows to the owners and collects staged sends at each round
    barrier — one pipe message per worker per round, so the host<->device
    boundary stays as batched as the serial engine's.  Determinism is
    worker-count-invariant (see the module docstring); ``workers=1``
    degenerates to the serial engine (no pipe overhead, same results)."""

    def __init__(
        self, cfg: ConfigOptions, workers: int = 0,
        log_capacity: Optional[int] = None,
    ) -> None:
        for hopt in cfg.hosts:
            if hopt.pcap_enabled:
                raise ValueError(
                    "MpHybridEngine does not support pcap capture (every "
                    "worker replica would open the capture files); use "
                    "the serial hybrid engine"
                )
        super().__init__(cfg, log_capacity=log_capacity)
        n_ext = len(self.external_hosts)
        self.workers = workers if workers > 0 else (os.cpu_count() or 1)
        self.workers = max(1, min(self.workers, n_ext))
        self._eff_next: Optional[list[int]] = None
        self._pending_rows: Optional[list[list]] = None
        self._owner_of: dict[int, int] = {}
        # supervision (engine/supervisor.py): deadline-bounded pipe reads
        # so a dead or hung worker surfaces as a diagnostic
        # WorkerDiedError instead of an indefinite hang.  No respawn on
        # this backend — workers hold live managed OS processes whose
        # kernel state cannot be resnapshotted — so a worker death
        # escalates straight to the facade's failover boundary.
        self._heartbeat_s = float(cfg.experimental.worker_heartbeat_s)
        self._round_no = 0

    # -- controller-side bookkeeping ---------------------------------------

    def next_event_time(self) -> int:
        if self._eff_next is not None:
            return min(self._eff_next, default=NEVER)
        return super().next_event_time()

    def _route_delivery(self, t, src, dst, seq, size, payload) -> None:
        """Ship the delivery to the worker owning ``dst`` at the next
        round message; fold its time into the owner's effective next-event
        time unless the replica consumes it inline (passive elision makes
        no queue event — the parent's replica knows which hosts are
        passive, construction being deterministic)."""
        if self._eff_next is None:
            # workers==1 degenerate run: the serial loop executes hosts
            # in-process, so deliveries apply directly
            super()._route_delivery(t, src, dst, seq, size, payload)
            return
        w = self._owner_of[dst]
        self._pending_rows[w].append((t, src, dst, seq, size, payload))
        if not (payload is None and self.hosts[dst].passive_delivery):
            if t < self._eff_next[w]:
                self._eff_next[w] = t

    def _mp_round(self, window_end: int) -> None:
        """One parallel syscall-service round: ship (window_end, delivery
        rows, validation range) to every worker, collect (next_t, staged
        sends, min-used latency, cleanliness, peeked schedule) — a single
        pipe message each way per worker.  Workers execute concurrently
        between the two loops; staged sends merge in (worker-id, host-id)
        order, which the device queue merge's total key makes
        order-invariant anyway.  Inside a fused span the workers run the
        cleanliness probe over their owned partition and ship their
        refreshed peek schedules, so the parent's next-event folds arrive
        early enough to bound the next dispatch's k."""
        obs = self.obs
        clock = self.clock
        conns, procs = self._mp
        self._round_no += 1
        with clock.span("service_ship"):
            wf = self._fuse_we_final
            for w, conn in enumerate(conns):
                conn.send((
                    "round", window_end, self._pending_rows[w],
                    wf if wf is not None else window_end,
                ))
                self._pending_rows[w] = []
        # disjoint attribution (same law as cpu_mp): the ship leg is obs's
        # worker_pipe, the collect leg its syscall_service — the barrier
        # wait that holds the workers' syscall execution (the slowest
        # worker's own wall says how much of it).  The two tile the round,
        # so phase sums never double-count; sync_stats' syscall_service_s
        # is their sum (the [hybrid-agg] counter)
        with clock.span("service_collect", window_end) as collect:
            staged = self._staged_merged
            perf_lines: list[str] = []
            parts_all: list[int] = []
            clean = True
            exec_max = exec_sum = 0.0
            for w, conn in enumerate(conns):
                next_t, out, mul, wlines, wparts, wclean, wpeek, wexec = (
                    recv_with_deadline(
                        conn, procs[w], self._heartbeat_s, w,
                        self._round_no, "round",
                    )
                )
                self._eff_next[w] = next_t
                if mul is not None and (
                    self._min_used_lat is None or mul < self._min_used_lat
                ):
                    self._min_used_lat = mul
                staged.extend(out)
                if wlines:
                    perf_lines.extend(wlines)
                if wparts:
                    parts_all.extend(wparts)
                clean = clean and wclean
                self._worker_peeks[w] = wpeek
                exec_sum += wexec
                if wexec > exec_max:
                    exec_max = wexec
            self._round_clean = clean
        self._book_worker_exec(exec_max, exec_sum)
        if obs is not None:
            obs.metrics.count("pipe_messages", 2 * len(conns))
            if obs.turns is not None:
                # the partition interleaves host ids round-robin across
                # workers; sorting normalizes the union to the serial
                # engine's host-id order (ledger worker-count invariance)
                self._last_participants = tuple(sorted(parts_all))
                if obs.tracer is not None:
                    self._flow_seq += 1
                    self._flow_pending = (
                        self._flow_seq, collect.t0 + collect.dur / 2,
                    )
        # worker-process perf lines route through the parent's locked
        # sink, in (round, worker-id) order — one coherent stream
        if perf_lines and self.perf_log is not None:
            self.perf_log.emit_many(perf_lines)
        if self.perf_log is not None:
            self.perf_log.hybrid_agg("host", window_end, self.sync_stats)

    def _peek_partition(self, owned):
        """A worker partition's initial (head, horizon) peek from the
        parent replica — literally the worker's ``_peek_head_horizon``
        law over its owned hosts (deterministic construction makes the
        replicas agree)."""
        return self._peek_head_horizon(
            self._ext_slots, [self.hosts[i] for i in owned]
        )

    def _peek_ext_times(self, floor_t: int = 0) -> list:
        """Merge the workers' shipped peek schedules: distinct times
        below the tightest worker horizon, padded with the merged
        horizon.  A worker's horizon marks where ITS schedule knowledge
        ends; beyond the min of all horizons the parent knows nothing,
        so the merged schedule must stop there too.

        Deliveries the parent has APPLIED but not yet shipped (trailing
        egress rows queued in ``_pending_rows`` for the next round
        message) are events the workers' schedules cannot know about yet
        — fold their times in directly, or the fused dispatch could
        free-run past a pending host event the serial law (which reads
        the queues) would have bounded."""
        if self._eff_next is None:
            return super()._peek_ext_times(floor_t)
        es = self._ext_slots
        merged: set = set()
        wh = NEVER
        for head, hz in self._worker_peeks:
            for t in head:
                if t >= floor_t:
                    merged.add(t)
            if hz < wh:
                wh = hz
        for rows in self._pending_rows:
            for t, _src, dst, _seq, _size, payload in rows:
                if t >= floor_t and not (
                    payload is None and self.hosts[dst].passive_delivery
                ):
                    merged.add(t)
        times = sorted(t for t in merged if t < wh)
        head = times[: es - 1]
        horizon = times[es - 1] if len(times) >= es else wh
        return head + [horizon] * (es - len(head))

    def netobs_snapshot(self):
        """Worker-merged host arrays + device arrays (the window
        histogram is the device's — see HybridEngine.netobs_snapshot)."""
        wnb = getattr(self, "_worker_nb", None)
        if wnb is None:
            # serial / degenerate (workers == 1) path ran in-process
            return super().netobs_snapshot()
        dev = self.device.netobs_snapshot()
        if dev is None:
            return None
        from ..obs import netobs as nom

        arrays = nom.merge_arrays(
            {k: v.copy() for k, v in dev["arrays"].items()}, wnb
        )
        return {
            "arrays": arrays,
            "window_hist": dev["window_hist"],
            "log_lost": 0,
        }

    def flowtrace_snapshot(self):
        """Worker-merged host events + device ring events (see
        HybridEngine.flowtrace_snapshot for the split law)."""
        wft = getattr(self, "_worker_ft", None)
        if wft is None:
            # serial / degenerate (workers == 1) path ran in-process
            return super().flowtrace_snapshot()
        dev = self.device.flowtrace_snapshot()
        if dev is None:
            return None
        return {
            "raw": list(wft) + list(dev["raw"]),
            "ring_lost": dev["ring_lost"],
        }

    # -- run ---------------------------------------------------------------

    def run(self, on_window=None) -> SimResult:
        if self.workers == 1:
            # degenerate case (single-core box): spawning one worker only
            # adds pipe overhead — run in-process, same results
            return super().run(on_window=on_window)
        from .cpu_mp import _partition, spawn_cpu_workers

        ext_ids = [h.host_id for h in self.external_hosts]
        parts = [
            [ext_ids[i] for i in p]
            for p in _partition(len(ext_ids), self.workers)
        ]
        self._owner_of = {
            hid: w for w, part in enumerate(parts) for hid in part
        }
        record_turns = self.obs is not None and self.obs.turns is not None
        conns, procs = spawn_cpu_workers(
            _hybrid_worker_main,
            [(self.cfg, owned, record_turns, self._ext_slots)
             for owned in parts],
        )
        self._mp = (conns, procs)
        self._pending_rows = [[] for _ in range(self.workers)]
        # initial next-event times from the parent replica (identical
        # deterministic construction — no startup round trip needed);
        # same for the initial per-worker peek schedules
        self._eff_next = [
            min((self.hosts[i].queue.next_time() for i in owned),
                default=NEVER)
            for owned in parts
        ]
        self._worker_peeks = [
            self._peek_partition(owned) for owned in parts
        ]
        t0 = wall_time.perf_counter()
        try:
            return self._mp_loop(on_window, t0)
        finally:
            self._eff_next = None
            for conn in conns:
                conn.close()
            for p in procs:
                p.join(timeout=10)
                if p.is_alive():
                    p.terminate()

    def _mp_loop(self, on_window, t0) -> SimResult:
        conns, procs = self._mp
        state = self._window_loop(self._mp_round, on_window)
        self._check_fusion_accounting()

        event_log: list = []
        counters: dict[str, int] = {}
        per_host: list[dict] = [{} for _ in range(len(self.hosts))]
        process_errors: list[str] = []
        self._worker_nb = None
        self._worker_ft = None
        for conn in conns:
            conn.send(("finish",))
        for w, conn in enumerate(conns):
            wlog, cnt, per, errs, wsnap, wflows = recv_with_deadline(
                conn, procs[w], self._heartbeat_s, w, self._round_no,
                "finish",
            )
            event_log.extend(wlog)
            for k, v in cnt.items():
                counters[k] = counters.get(k, 0) + v
            for hid, c in per.items():
                per_host[hid] = c
            process_errors.extend(errs)
            if wsnap is not None:
                from ..obs import netobs as nom

                if self._worker_nb is None:
                    self._worker_nb = nom.empty_arrays(len(self.hosts))
                nom.merge_arrays(self._worker_nb, wsnap["arrays"])
            if wflows is not None:
                if self._worker_ft is None:
                    self._worker_ft = []
                self._worker_ft.extend(tuple(e) for e in wflows)
        wall = wall_time.perf_counter() - t0

        dev_result = self.device.collect(state, wall)
        self.sync_stats.update(self.device.append_stats)
        for k, v in dev_result.counters.items():
            counters[k] = counters.get(k, 0) + v
        return SimResult(
            sim_time_ns=self.stop_time,
            wall_seconds=wall,
            rounds=dev_result.rounds + self.host_rounds,
            event_log=dev_result.event_log + event_log,
            counters=counters,
            per_host_counters=per_host,
            process_errors=process_errors,
        )
