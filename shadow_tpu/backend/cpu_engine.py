"""CPU reference backend: the scalar implementation of docs/SEMANTICS.md.

Structural analog of the reference's Controller/Manager/Host round loop
(controller.rs:81-113, manager.rs:541-770, host.rs:762-830), collapsed into
one process: rounds advance all hosts over a conservative lookahead window;
cross-host packets land in the destination's event queue for later windows.
This backend is the determinism oracle the TPU lane backend is diffed
against, and the fallback for configs the lane vocabulary can't express yet.
"""

from __future__ import annotations

import dataclasses
import time as wall_time
from typing import Optional

from ..config.options import ConfigOptions
from ..core import rng as rng_mod
from ..core import time as stime
from ..core.event import Event, EventKind, Task
from ..core.event_queue import EventQueue
from ..models import gossip as _gossip  # noqa: F401  (register built-ins)
from ..models import phold as _phold  # noqa: F401
from ..models import tcpflow as _tcpflow  # noqa: F401
from ..models import tgen as _tgen  # noqa: F401
from ..models import tgen_tcp as _tgen_tcp  # noqa: F401
from ..models.base import create_model
from ..net.codel import CoDel
from ..net.graph import IpAssignment, NetworkGraph, RoutingInfo
from ..net.stack import TcpSegment as _TcpSegment
from ..net.token_bucket import (
    FRAME_OVERHEAD_BYTES,
    TokenBucket,
    bucket_params,
)
from ..obs import flowtrace as ftr

# event-log outcome codes (SEMANTICS.md)
DELIVERED = 0
DROP_LOSS = 1
DROP_CODEL = 2
DROP_QUEUE = 3

OUTCOME_NAMES = {0: "delivered", 1: "loss", 2: "codel", 3: "queue"}

# the loopback interface's fixed one-way delay (the reference gives every
# host a localhost/internet interface pair, namespace.rs:25-60; here lo
# is a latency-only serial law: no token buckets, no CoDel, no loss —
# self-addressed 127/8 traffic from managed stacks rides it)
LOOPBACK_LATENCY_NS = 10_000
LOOPBACK_IP = "127.0.0.1"


@dataclasses.dataclass
class LogRecord:
    time: int
    src: int
    dst: int
    seq: int
    size: int
    outcome: int

    def as_tuple(self) -> tuple[int, int, int, int, int, int]:
        return (self.time, self.src, self.dst, self.seq, self.size, self.outcome)


@dataclasses.dataclass
class Delivery:
    """Payload of a LOCAL delivery event (step 6 of the lifecycle).

    ``payload`` is opaque engine-side cargo (managed processes ride their
    datagram bytes + ports here); it never affects event ordering or the
    event log, which record sizes only."""

    src: int
    seq: int
    size: int
    payload: object = None


class Host:
    """Per-host state: queue, buckets, CoDel, RNG counters, app models."""

    def __init__(
        self,
        host_id: int,
        hostname: str,
        engine: "CpuEngine",
        bw_up_bps: int,
        bw_down_bps: int,
    ) -> None:
        self.host_id = host_id
        self.hostname = hostname
        self.engine = engine
        self.queue = EventQueue()
        up_rate, up_burst = bucket_params(bw_up_bps)
        dn_rate, dn_burst = bucket_params(bw_down_bps)
        self.up_bucket = TokenBucket(rate=up_rate, burst=up_burst)
        self.down_bucket = TokenBucket(rate=dn_rate, burst=dn_burst)
        self.codel = CoDel()
        self.pcap = None  # PcapWriter when HostOptions.pcap_enabled
        # cross-host packet inbox: worker threads of OTHER hosts append
        # here under the lock; drained into the queue at the round barrier
        # (the push_packet_to_host discipline, worker.rs:603-615)
        import threading

        self.inbox: list = []
        self.inbox_lock = threading.Lock()
        # per-host event-log buffer + min-used-latency, merged at the
        # barrier in host-id order so results are worker-count-invariant
        self.log_buf: list = []
        self.min_used_lat: Optional[int] = None
        self.send_seq = 0  # per-host packet counter (RNG counter + FIFO prio)
        self.local_seq = 0  # per-host local-event counter
        self.app_draws = 0  # APP_STREAM counter
        self.apps: list = []
        self.counters: dict[str, int] = {}
        self.now = 0  # current event time while executing
        self._net = None  # lazy HostNetStack (TCP tier)
        self._passive = None  # lazy: all apps passive_delivery (or no apps)

    # device-turn ledger accounting (obs/turns.py; class defaults keep
    # the hot path to one engine-flag check when the ledger is off):
    # _ledger_managed marks hosts whose sends a hybrid run would stage,
    # _ledger_sends is the thread-owned per-window staged-send count
    _ledger_managed = False
    _ledger_sends = 0

    # -- checkpoint pickling (engine/checkpoint.py) ------------------------
    # the inbox lock is the one unpicklable object in the engine's
    # transitive state graph; at a checkpoint boundary the inbox is
    # empty and no worker threads are live, so drop it and recreate

    def __getstate__(self) -> dict:
        d = self.__dict__.copy()
        d.pop("inbox_lock", None)
        return d

    def __setstate__(self, d: dict) -> None:
        import threading

        self.__dict__.update(d)
        self.inbox_lock = threading.Lock()

    # -- HostApi ----------------------------------------------------------

    @property
    def num_hosts(self) -> int:
        return len(self.engine.hosts)

    def send(self, dst: int, size_bytes: int, payload: object = None,
             loopback: bool = False, retx: bool = False) -> int:
        return self.engine.send_packet(self, dst, size_bytes, payload,
                                       loopback=loopback, retx=retx)

    def ft_giveup(self, dst: int) -> None:
        """Flowtrace hook: a stream retry budget exhausted toward ``dst``
        (oracle-only — the device's pump retries unboundedly, so this
        event is structurally absent from parity scenarios)."""
        ft = self.engine.flowtrace
        if ft is not None and ft.sampled(self.host_id, dst):
            ft.emit(self.host_id, self.now, self.engine.window_end,
                    ftr.FT_DROP, self.host_id, dst, -1, 0,
                    ftr.CAUSE_RETRY_GIVEUP)

    def set_timer(self, t_abs_ns: int) -> None:
        app = self._current_app

        def fire(h: "Host", a=app) -> None:
            h._current_app = a
            a.on_timer(h, h.now)

        # strictly future: a timer armed for "now" (or the past) would pop in
        # the same window at the same instant and can live-lock the round
        self.push_local(max(t_abs_ns, self.now + 1), Task(fire, label="timer"))

    def set_timer_relative(self, delta_ns: int) -> None:
        self.set_timer(self.now + delta_ns)

    def schedule_at(self, t_abs_ns: int, fn) -> None:
        """Exact-time local event (``fn(host)``), the scalar twin of the
        lane backend's arm channels: unlike ``set_timer`` it may land at
        the current instant (pump events pop later in the same window, in
        (time, kind, src, seq) order)."""
        self.push_local(max(t_abs_ns, self.now), Task(fn, label="app"))

    def resolve(self, hostname: str) -> int:
        return self.engine.resolve(hostname)

    def ip_of(self, host_id: int) -> str:
        return self.engine.ips.by_host[host_id]

    @property
    def hosts_file_path(self):
        return self.engine.hosts_file_path

    @property
    def passive_delivery(self) -> bool:
        """True when every app's delivery handling is counters-only (or the
        host has no apps): plain-model deliveries are then applied inline at
        packet arrival and the DELIVERY queue event is elided — identical
        elision on the lane backend keeps the backends bit-compatible."""
        if self._passive is None:
            self._passive = all(
                getattr(a, "passive_delivery", False) for a in self.apps
            )
        return self._passive

    @property
    def net(self):
        """The host's transport stack (TCP sockets over the packet path)."""
        if self._net is None:
            from ..net.stack import HostNetStack

            self._net = HostNetStack(self)
        return self._net

    @property
    def data_directory(self) -> str:
        return self.engine.cfg.general.data_directory

    @property
    def master_seed(self) -> int:
        return self.engine.seed

    def rand_u32(self) -> int:
        v = rng_mod.rand_u32_scalar(
            self.engine.seed,
            self.host_id | rng_mod.APP_STREAM,
            self.app_draws,
        )
        self.app_draws += 1
        return v

    def count(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    # -- engine side ------------------------------------------------------

    def push_local(self, t: int, task: Task) -> None:
        self.queue.push(
            Event(t, EventKind.LOCAL, src_host=self.host_id, seq=self.local_seq, data=task)
        )
        self.local_seq += 1

    def execute(self, until: int) -> None:
        """Pop and run all events < until (Host::execute, host.rs:762-803)."""
        pl = self.engine.perf_log
        if pl is not None:
            t0 = pl.timer()
            self._execute(until)
            pl.host_exec(self.hostname, pl.timer() - t0, until)
        else:
            self._execute(until)

    def _execute(self, until: int) -> None:
        no = self.engine.netobs
        pops = 0
        try:
            while True:
                ev = self.queue.peek()
                if ev is None or ev.time >= until:
                    return
                if ev.kind == EventKind.PACKET:
                    # PACKET pops only: wire arrivals are the one event
                    # class whose per-window counts are bit-identical
                    # across backends (LOCAL/DELIVERY decomposition
                    # differs: start anchors, delivery elision), so the
                    # netobs window histogram buckets them
                    pops += 1
                ev = self.queue.pop()
                self.now = ev.time
                self._dispatch(ev)
        finally:
            if no is not None and pops:
                # one thread-owned row write per execute call
                no.pops[self.host_id] += pops

    def _dispatch(self, ev) -> None:
        if ev.kind == EventKind.PACKET:
            self.engine.inbound(self, ev)
        elif ev.kind == EventKind.DELIVERY:
            data = ev.data
            if isinstance(data.payload, _TcpSegment):
                self.net.on_segment(ev.time, data.payload)
            else:
                for app in self.apps:
                    self._current_app = app
                    app.on_delivery(
                        self, ev.time, data.src, data.seq, data.size,
                        payload=data.payload,
                    )
        else:
            ev.data.execute(self)

    _current_app = None


class CpuEngine:
    """Build hosts from a config and run the round loop."""

    def __init__(self, cfg: ConfigOptions) -> None:
        cfg.validate()
        self.cfg = cfg
        self.seed = cfg.general.seed
        self.stop_time = cfg.general.stop_time
        self.bootstrap_end = cfg.general.bootstrap_end_time

        from .setup import build_world

        # kept whole for engines that layer on top (backend/hybrid.py
        # hands it to its TpuEngine so topology/routing build once)
        self.world = build_world(cfg)
        (
            self.graph,
            self.ips,
            self.dns,
            self.routing,
            bw_up_arr,
            bw_dn_arr,
            self.runahead,
        ) = self.world
        self.node_index = self.routing.host_node_index
        # dynamic runahead (runahead.rs:44-118): the window may widen to the
        # smallest latency actually used so far (>= the static minimum);
        # packets record their path latency as they are sent
        self.dynamic_runahead = cfg.experimental.use_dynamic_runahead
        self._min_used_lat: Optional[int] = None
        self._runahead_floor = max(cfg.experimental.runahead or 0, 1)
        self.hosts = [
            Host(hid, hopt.hostname, self, int(bw_up_arr[hid]), int(bw_dn_arr[hid]))
            for hid, hopt in enumerate(cfg.hosts)
        ]

        # app models scheduled at their start times
        from ..native.process import ManagedApp as _ManagedApp

        for hid, hopt in enumerate(cfg.hosts):
            host = self.hosts[hid]
            for p in hopt.processes:
                app = create_model(p.path, list(p.args), dict(p.environment))
                if hasattr(app, "set_congestion"):
                    app.set_congestion(hopt.congestion)
                host.apps.append(app)
                host.push_local(
                    p.start_time, Task(lambda h, a=app: _start_app(h, a), label="start")
                )
                if isinstance(app, _ManagedApp):
                    app.configure_lifecycle(p.expected_final_state, p.shutdown_signal)
                    if p.shutdown_time is not None:
                        host.push_local(
                            p.shutdown_time,
                            Task(
                                lambda h, a=app: a.deliver_shutdown(h),
                                label="shutdown",
                            ),
                        )

        # per-host pcap capture (interface.rs:45-75; host option
        # pcap_enabled, configuration.rs:602-612)
        if any(h.pcap_enabled for h in cfg.hosts):
            from pathlib import Path as _Path

            from ..utils.pcap import PcapWriter

            for hid, hopt in enumerate(cfg.hosts):
                if hopt.pcap_enabled:
                    self.hosts[hid].pcap = PcapWriter(
                        _Path(cfg.general.data_directory)
                        / "hosts" / hopt.hostname / "eth0.pcap",
                        snaplen=hopt.pcap_capture_size,
                    )

        # managed (real-binary) processes resolve simulated names through an
        # /etc/hosts-style file (the reference passes plugins a memfd hosts
        # file, dns.rs:130-190); written once per run, only when needed
        from pathlib import Path

        from ..native.process import ManagedApp

        self.hosts_file_path = None
        if any(isinstance(a, ManagedApp) for h in self.hosts for a in h.apps):
            self.hosts_file_path = self.dns.write_hosts_file(
                Path(cfg.general.data_directory) / "etc-hosts"
            )

        self.event_log: list[LogRecord] = []
        self.window_end = 0
        self.rounds = 0
        # netobs telemetry plane (obs/netobs.py): per-host network
        # counters + window-occupancy histogram.  Config-driven (worker
        # replicas of the multiprocess engines need it too); None = off
        # = zero overhead, the same contract as obs/perf_log
        self.netobs = None
        if cfg.experimental.netobs:
            from ..obs.netobs import NetObs

            self.netobs = NetObs(len(self.hosts))
        # flowtrace lifecycle plane (obs/flowtrace.py): per-event traces
        # of deterministically-sampled flows; None = off = zero overhead
        self.flowtrace = None
        if cfg.experimental.flowtrace:
            self.flowtrace = ftr.FlowTrace(
                len(self.hosts), cfg.general.seed,
                cfg.experimental.flowtrace_sample,
                cfg.experimental.flowtrace_capacity,
            )
        # [window-agg]/[host-exec-agg] telemetry sink (set by the facade
        # when experimental.perf_logging is on; None = zero overhead)
        self.perf_log = None
        # device-turn ledger send accounting (obs/turns.py): armed by
        # _ledger_enable when obs.turns is on; False = zero overhead
        self._turns_sends = False
        # obs Recorder (shadow_tpu/obs/): phase spans + metrics, set by
        # the facade when experimental.obs_* is on; None = zero overhead
        self.obs = None

        # fault schedule (shadow_tpu/faults/): versioned routing tables
        # installed in place at window boundaries; every event time is a
        # window-clamp epoch so fault replay is bit-identical
        self.faults = None
        if cfg.faults.events:
            from ..faults.overlay import build_fault_runtime

            self.faults = build_fault_runtime(cfg, self.graph, self.routing)

    # -- checkpointing (engine/checkpoint.py) ------------------------------
    # The engine's whole state graph is host-picklable (cloudpickle for
    # the app-closure Tasks in the event queue) except for facade-owned
    # attachments: obs and perf_log carry locks/streams and belong to
    # the *run*, not the simulation state — the facade re-attaches them
    # on resume.  run() performs no state reset, so a restored engine's
    # run() continues the simulation exactly where the checkpoint left
    # it (docs/robustness.md "resume law").

    def __getstate__(self) -> dict:
        d = self.__dict__.copy()
        d["obs"] = None
        d["perf_log"] = None
        return d

    def checkpoint_unsupported_reason(self) -> Optional[str]:
        """None when this engine's state is fully serializable; else the
        reason checkpoints must stay off (managed OS processes hold
        kernel state, pcap writers hold open streams)."""
        from ..native.process import ManagedApp

        if any(
            isinstance(a, ManagedApp) for h in self.hosts for a in h.apps
        ):
            return ("managed (real-binary) processes hold live OS state"
                    " that cannot be snapshotted")
        if any(h.pcap is not None for h in self.hosts):
            return "pcap capture streams cannot be snapshotted"
        return None

    def checkpoint_payload(self) -> bytes:
        """Serialize the complete simulation state (hosts, event queue,
        RNG counters, transport stacks, fault runtime, event log) as
        one cloudpickle blob."""
        import cloudpickle

        reason = self.checkpoint_unsupported_reason()
        if reason is not None:
            raise RuntimeError(f"checkpoint unsupported: {reason}")
        return cloudpickle.dumps(self)

    @staticmethod
    def from_checkpoint(blob: bytes) -> "CpuEngine":
        import cloudpickle

        engine = cloudpickle.loads(blob)
        if not isinstance(engine, CpuEngine):
            raise RuntimeError(
                f"checkpoint payload is {type(engine).__name__},"
                " not a CpuEngine"
            )
        return engine

    # -- netobs telemetry plane (obs/netobs.py) ----------------------------

    def netobs_snapshot(self):
        """The run's per-host telemetry in the canonical array schema
        (None when netobs is off).  Completes the accumulator's counters
        with the values only the engine can attribute: token-bucket
        throttles (the buckets live on the hosts), stream retransmit /
        retry-give-up counters (host counter dicts), queue/shed causes
        (structurally zero here: the oracle's queues are unbounded)."""
        no = self.netobs
        if no is None:
            return None
        arrays = no.base_arrays()
        for hid, h in enumerate(self.hosts):
            arrays["throttled"][hid] = (
                h.up_bucket.throttles + h.down_bucket.throttles
            )
            arrays["retransmits"][hid] = h.counters.get(
                "stream_retransmits", 0
            )
            arrays["retry_giveup"][hid] = h.counters.get(
                "stream_retry_drops", 0
            )
        return {
            "arrays": arrays,
            "window_hist": no.window_hist.copy(),
            "log_lost": 0,
        }

    def netobs_lines(self, host=None) -> list[str]:
        """Run-control ``netstats [host]`` answer from live state."""
        from ..obs import netobs as nom

        snap = self.netobs_snapshot()
        if snap is None:
            return ["netobs is not enabled (set experimental.netobs)"]
        names = [h.hostname for h in self.hosts]
        return nom.snapshot_lines(
            snap["arrays"], snap["window_hist"], names, host
        )

    # -- flowtrace plane (obs/flowtrace.py) --------------------------------

    def flowtrace_snapshot(self):
        """The run's raw flow events (None when flowtrace is off).  The
        oracle has no ring, so ``ring_lost`` is structurally 0; the
        device capacity law is applied at export by
        ``flowtrace.canonical_events``."""
        ft = self.flowtrace
        if ft is None:
            return None
        return {"raw": ft.raw_events(), "ring_lost": 0}

    def flowtrace_lines(self, host=None) -> list[str]:
        """Run-control ``flows [host]`` answer from live state."""
        snap = self.flowtrace_snapshot()
        if snap is None:
            return ["flowtrace is not enabled (set experimental.flowtrace)"]
        events, lost = ftr.canonical_events(
            snap["raw"], self.flowtrace.capacity
        )
        names = [h.hostname for h in self.hosts]
        return ftr.snapshot_lines(
            events, lost + snap["ring_lost"], names, host=host
        )

    def console_fault_sink(self, tokens: list[str]) -> str:
        """Run-control ``fault ...`` verb: schedule a fault at the current
        window boundary (effective for all subsequent sends).  Dynamic
        injection is interactive by nature — an in-process restart (``r``)
        rebuilds from the config and forgets console faults."""
        from ..faults.overlay import empty_fault_runtime
        from ..faults.schedule import parse_console_fault

        if self.faults is None:
            self.faults = empty_fault_runtime(self.cfg, self.graph, self.routing)
        ev = parse_console_fault(tokens, at=max(self.window_end, 1))
        self.faults.inject(ev)
        return f"fault {ev.kind} scheduled at {stime.fmt(ev.at)}"

    # -- DNS (network/dns.rs) ----------------------------------------------

    def resolve(self, hostname: str) -> int:
        return self.dns.resolve(hostname)

    # -- packet path (SEMANTICS.md lifecycle) ------------------------------

    def _packet_source_half(
        self, src_host: Host, dst: int, size_bytes: int, payload: object,
        retx: bool = False,
    ) -> tuple[int, Optional[int]]:
        """The source half of the packet lifecycle (steps 1-4: seq, up
        bucket, outbound pcap, dynamic-runahead record, Bernoulli loss,
        arrival-time bump).  Returns ``(seq, arrival_time)`` — arrival is
        ``None`` when the packet was lost.  Shared verbatim by the CPU
        push sink below and the hybrid backend's device-injection sink
        (backend/hybrid.py), so the law cannot drift between them.

        ``retx`` marks a retransmitted stream segment: the flowtrace
        send-stage event becomes FT_RETRANSMIT (same wire lifecycle
        otherwise)."""
        t = src_host.now
        seq = src_host.send_seq
        src_host.send_seq += 1
        s, d = src_host.host_id, dst
        no = self.netobs
        if no is not None:
            no.on_send(s, size_bytes)
        ft = self.flowtrace
        ft_on = ft is not None and ft.sampled(s, d)
        if ft_on:
            we = self.window_end
            ft.emit(s, t, we, ftr.FT_RETRANSMIT if retx else ftr.FT_SEND,
                    s, d, seq, size_bytes)

        bits = (size_bytes + FRAME_OVERHEAD_BYTES) * 8
        t_dep = src_host.up_bucket.charge(t, bits)
        if ft_on and t_dep != t:
            # the up bucket is charged before the loss draw on both
            # backends, so the wait event lands for lost sends too
            ft.emit(s, t_dep, we, ftr.FT_TB_WAIT, s, d, seq, size_bytes,
                    ftr.TB_UP)

        if src_host.pcap is not None:  # outbound capture at departure
            src_host.pcap.capture(
                stime.sim_to_emu(t_dep), self.ips.by_host[s],
                self.ips.by_host[d], size_bytes, payload,
                key=(1, s, d, seq),
            )

        # loss (skipped during bootstrap)
        lat_ns, thresh = self.routing.path(s, d)
        if self.dynamic_runahead and (
            src_host.min_used_lat is None or lat_ns < src_host.min_used_lat
        ):
            src_host.min_used_lat = lat_ns
        if t >= self.bootstrap_end and thresh > 0:
            u = rng_mod.rand_u32_scalar(self.seed, s | rng_mod.LOSS_STREAM, seq)
            if u < thresh:
                if no is not None:
                    no.on_loss(s)
                if ft_on:
                    ft.emit(s, t, we, ftr.FT_DROP, s, d, seq, size_bytes,
                            ftr.CAUSE_LOSS)
                src_host.log_buf.append(LogRecord(t, s, d, seq, size_bytes, DROP_LOSS))
                # under the lane engine's name: a comparison of counter
                # sets holds the two backends' drop totals to each other
                src_host.count("lane_drop_loss")
                return seq, None

        arr = max(t_dep + lat_ns, self.window_end)
        if ft_on:
            ft.emit(s, arr, we, ftr.FT_QUEUE_ENTER, s, d, seq, size_bytes)
        return seq, arr

    def send_packet(
        self, src_host: Host, dst: int, size_bytes: int,
        payload: object = None, loopback: bool = False, retx: bool = False,
    ) -> int:
        if loopback:
            return self._loopback_send(src_host, size_bytes, payload)
        seq, arr = self._packet_source_half(src_host, dst, size_bytes, payload,
                                            retx=retx)
        if arr is None:
            return seq
        if self._turns_sends and src_host._ledger_managed:
            # the oracle analogue of a hybrid injection row: a managed
            # host's surviving non-loopback send (thread-owned bump)
            src_host._ledger_sends += 1
        ev = Event(
            arr, EventKind.PACKET, src_host=src_host.host_id, seq=seq,
            data=(size_bytes, payload),
        )
        dst_host = self.hosts[dst]
        if dst_host is src_host:
            dst_host.queue.push(ev)  # self-traffic never crosses threads
        else:
            with dst_host.inbox_lock:
                dst_host.inbox.append(ev)
        return seq

    def _loopback_send(self, host: Host, size_bytes: int,
                       payload: object) -> int:
        """The lo interface: self-addressed (127/8) traffic takes a
        dedicated serial lifecycle — fixed LOOPBACK_LATENCY_NS, no token
        buckets, no CoDel, no loss draw (the localhost half of the
        reference's per-host interface pair, namespace.rs:25-60).  The
        delivery never leaves the host, so it works identically under
        the threaded, multiprocessing, and hybrid engines."""
        seq = host.send_seq
        host.send_seq += 1
        t_deliver = host.now + LOOPBACK_LATENCY_NS
        no = self.netobs
        if no is not None:
            # lo is both halves on one host: a send and a delivery
            no.on_send(host.host_id, size_bytes)
            no.on_delivered(host.host_id, size_bytes)
        ft = self.flowtrace
        if ft is not None and ft.sampled(host.host_id, host.host_id):
            we = self.window_end
            h = host.host_id
            ft.emit(h, host.now, we, ftr.FT_SEND, h, h, seq, size_bytes)
            ft.emit(h, t_deliver, we, ftr.FT_DELIVERY, h, h, seq, size_bytes)
        host.log_buf.append(
            LogRecord(t_deliver, host.host_id, host.host_id, seq,
                      size_bytes, DELIVERED)
        )
        if host.pcap is not None:
            host.pcap.capture(
                stime.sim_to_emu(t_deliver), LOOPBACK_IP, LOOPBACK_IP,
                size_bytes, payload,
                key=(0, host.host_id, host.host_id, seq),
            )
        host.queue.push(
            Event(
                t_deliver,
                EventKind.DELIVERY,
                src_host=host.host_id,
                seq=seq,
                data=Delivery(host.host_id, seq, size_bytes, payload),
            )
        )
        return seq

    def inbound(self, dst_host: Host, ev: Event) -> None:
        """Steps 5a-5c: down bucket, CoDel, schedule delivery."""
        size_bytes, payload = ev.data
        bits = (size_bytes + FRAME_OVERHEAD_BYTES) * 8
        t_deliver = dst_host.down_bucket.charge(ev.time, bits)
        sojourn = t_deliver - ev.time
        no = self.netobs
        ft = self.flowtrace
        d = dst_host.host_id
        ft_on = ft is not None and ft.sampled(ev.src_host, d)
        if ft_on and t_deliver != ev.time:
            ft.emit(d, t_deliver, self.window_end, ftr.FT_TB_WAIT,
                    ev.src_host, d, ev.seq, size_bytes, ftr.TB_DN)
        if dst_host.codel.offer(t_deliver, sojourn):
            if no is not None:
                no.on_codel(dst_host.host_id)
            if ft_on:
                ft.emit(d, t_deliver, self.window_end, ftr.FT_DROP,
                        ev.src_host, d, ev.seq, size_bytes, ftr.CAUSE_CODEL)
            dst_host.log_buf.append(
                LogRecord(t_deliver, ev.src_host, dst_host.host_id, ev.seq, size_bytes, DROP_CODEL)
            )
            dst_host.count("lane_drop_codel")
            return
        if no is not None:
            no.on_delivered(dst_host.host_id, size_bytes)
        if ft_on:
            ft.emit(d, t_deliver, self.window_end, ftr.FT_DELIVERY,
                    ev.src_host, d, ev.seq, size_bytes)
        dst_host.log_buf.append(
            LogRecord(t_deliver, ev.src_host, dst_host.host_id, ev.seq, size_bytes, DELIVERED)
        )
        if dst_host.pcap is not None:  # inbound capture at delivery
            dst_host.pcap.capture(
                stime.sim_to_emu(t_deliver), self.ips.by_host[ev.src_host],
                self.ips.by_host[dst_host.host_id], size_bytes, payload,
                key=(0, ev.src_host, dst_host.host_id, ev.seq),
            )
        if payload is None and dst_host.passive_delivery:
            # passive fast path: counters apply now; no DELIVERY event.
            # now anchors at delivery time so even a contract-violating app
            # behaves like the queued path (the pop loop reassigns now per
            # event, so this is safe)
            dst_host.now = t_deliver
            for app in dst_host.apps:
                dst_host._current_app = app
                app.on_delivery(
                    dst_host, t_deliver, ev.src_host, ev.seq, size_bytes,
                    payload=None,
                )
            return
        dst_host.queue.push(
            Event(
                t_deliver,
                EventKind.DELIVERY,
                src_host=ev.src_host,
                seq=ev.seq,
                data=Delivery(ev.src_host, ev.seq, size_bytes, payload),
            )
        )

    # -- device-turn ledger (obs/turns.py) ---------------------------------

    def _ledger_enable(self) -> list[Host]:
        """Arm the oracle side of the device-turn ledger: mark the
        managed hosts (whose sends a hybrid run would stage for device
        injection) and enable the per-send counter.  Returns the managed
        hosts in host-id order."""
        from ..native.process import ManagedApp

        managed = [
            h for h in self.hosts
            if any(isinstance(a, ManagedApp) for a in h.apps)
        ]
        for h in managed:
            h._ledger_managed = True
            h._ledger_sends = 0
        self._turns_sends = True
        return managed

    @staticmethod
    def _ledger_participants(managed: list[Host], until: int) -> tuple:
        """Managed hosts with events inside the window — taken BEFORE
        execution mutates the queues (the same law the hybrid engines
        apply per device turn)."""
        return tuple(
            h.host_id for h in managed if h.queue.next_time() < until
        )

    @staticmethod
    def _ledger_take_sends(managed: list[Host]) -> int:
        """Drain the managed hosts' per-window staged-send counters
        (thread-owned bumps, swept post-barrier on the round loop)."""
        n = 0
        for h in managed:
            if h._ledger_sends:
                n += h._ledger_sends
                h._ledger_sends = 0
        return n

    # -- round loop (controller.rs:88-113 + manager.rs:541) ----------------

    def next_event_time(self) -> int:
        return min((h.queue.next_time() for h in self.hosts), default=stime.NEVER)

    def _barrier_merge(self) -> None:
        """Round barrier: drain cross-host inboxes into queues, merge
        per-host log buffers and min-used latencies — all in host-id order
        so any worker count produces identical results."""
        for h in self.hosts:
            if h.inbox:
                for ev in h.inbox:
                    h.queue.push(ev)
                h.inbox.clear()
            if h.log_buf:
                self.event_log.extend(h.log_buf)
                h.log_buf.clear()
            if h.min_used_lat is not None:
                if self._min_used_lat is None or h.min_used_lat < self._min_used_lat:
                    self._min_used_lat = h.min_used_lat
                h.min_used_lat = None

    def current_runahead(self) -> int:
        """Window width for the next round.  Static mode: the precomputed
        min possible latency.  Dynamic mode: the min latency of paths used
        so far (never below the configured floor) — wider windows while
        only slow paths carry traffic, exactly the reference's
        use_dynamic_runahead law (runahead.rs:44-57)."""
        if not self.dynamic_runahead or self._min_used_lat is None:
            return self.runahead
        return max(self._min_used_lat, self._runahead_floor, 1)

    def finalize(self) -> None:
        """End-of-simulation teardown: reap managed processes still parked
        past stop_time (the reference kills plugins at teardown too,
        manager.rs end-of-sim), then check every process's final state
        against expected_final_state (worker.rs:475-481)."""
        for h in self.hosts:
            for app in h.apps:
                shutdown = getattr(app, "shutdown", None)
                if shutdown is not None:
                    shutdown()
            if h.pcap is not None:
                h.pcap.close()
        self.process_errors = []
        for h in self.hosts:
            for app in h.apps:
                check = getattr(app, "final_state_matches", None)
                if check is not None:
                    err = check()
                    if err is not None:
                        self.process_errors.append(f"host {h.hostname}: {err}")

    def describe_next_window(self, until: int) -> list[tuple[str, int, list[int]]]:
        """Hosts with events before ``until`` + native PIDs of their managed
        processes — what the run-control console prints while paused so a
        debugger can attach (manager.rs:660-748)."""
        out = []
        for h in self.hosts:
            t = h.queue.next_time()
            if t < until:
                pids = [
                    app.proc.pid
                    for app in h.apps
                    if getattr(app, "proc", None) is not None
                    and app.proc.poll() is None
                ]
                out.append((h.hostname, t, pids))
        return out

    def run(self, on_window=None) -> "SimResult":
        """Round loop.  ``on_window(window_start, window_end,
        next_event_time)`` runs after every round — the seam where the
        facade hangs heartbeats, perf telemetry, and run-control pauses
        (and through which RestartRequest propagates)."""
        from ..engine.scheduler import HostScheduler
        from ..native.process import ManagedApp

        exp = self.cfg.experimental
        parallelism = self.cfg.general.parallelism
        if parallelism == 0 and exp.scheduler != "thread-per-host":
            # default "all cores" engages only where threads can help:
            # managed OS processes (futex waits release the GIL); pure
            # Python model hosts run serial to skip pool overhead
            has_managed = any(
                isinstance(a, ManagedApp) for h in self.hosts for a in h.apps
            )
            parallelism = 0 if has_managed else 1
        scheduler = HostScheduler(
            self.hosts,
            parallelism=parallelism,
            policy=exp.scheduler,
            pin_cpus=exp.use_cpu_pinning,
        )
        try:
            return self._run_rounds(scheduler, on_window)
        finally:
            scheduler.shutdown()

    def _run_rounds(self, scheduler, on_window) -> "SimResult":
        t0 = wall_time.perf_counter()
        try:
            return self._round_loop(scheduler, on_window, t0)
        except BaseException:
            # a failing round must still reap managed OS processes (and
            # their fork children) — no orphans outlive the simulation
            self.finalize()
            raise

    def _round_loop(self, scheduler, on_window, t0) -> "SimResult":
        obs = self.obs
        turns = obs.turns if obs is not None else None
        managed_hosts = self._ledger_enable() if turns is not None else None
        while True:
            start = self.next_event_time()
            if start >= self.stop_time or start == stime.NEVER:
                break
            swapped = False
            if self.faults is not None:
                # apply every fault epoch at or before this window's start,
                # then clamp the window at the next pending epoch: sends at
                # t >= epoch see the new tables, earlier sends never do —
                # the identical law the TPU engine's epoch segmentation
                # enforces, so windows (and logs) stay bit-identical
                prev_install = (
                    self.faults._installed_at if turns is not None else None
                )
                if obs is None:
                    self.faults.advance_to(start)
                else:
                    with obs.phase("fault_swap", window_start=start):
                        self.faults.advance_to(start)
                if turns is not None:
                    swapped = self.faults._installed_at != prev_install
            self.window_end = min(start + self.current_runahead(), self.stop_time)
            if self.faults is not None:
                self.window_end = min(
                    self.window_end, self.faults.window_bound(start)
                )
            pl = self.perf_log
            if pl is not None or obs is not None:
                active = sum(
                    1 for h in self.hosts if h.queue.next_time() < self.window_end
                )
            if turns is not None:
                parts = self._ledger_participants(
                    managed_hosts, self.window_end
                )
            if obs is None:
                scheduler.run_round(self.window_end)
                self._barrier_merge()
            else:
                with obs.phase(
                    "window_compute", window_end=self.window_end, active=active
                ):
                    scheduler.run_round(self.window_end)
                    self._barrier_merge()
            self.rounds += 1
            if self.netobs is not None:
                # one histogram entry per window (post-barrier, so every
                # pop of the round has landed)
                self.netobs.flush_window()
            if turns is not None:
                # the oracle ledger row: one window = one hypothetical
                # device turn, with the cause a hybrid run of this config
                # would have recorded (fault swap > staged managed sends
                # > managed participation > legal free-run)
                sends = self._ledger_take_sends(managed_hosts)
                if swapped:
                    cause = "fault_swap"
                elif sends:
                    cause = "injection"
                elif parts:
                    cause = "host_window"
                else:
                    cause = "free_run"
                turns.turn(
                    cause, start, self.window_end,
                    inject_rows=sends, participants=parts,
                )
            if obs is not None:
                m = obs.metrics
                m.count("windows")
                m.observe("window_active_hosts", active)
                m.observe("window_span_ns", self.window_end - start)
            if pl is not None or on_window is not None:
                next_ev = self.next_event_time()
                if pl is not None:
                    pl.window_agg(
                        active, start, self.window_end, min(next_ev, self.stop_time)
                    )
                if on_window is not None:
                    on_window(start, self.window_end, next_ev)
        self.finalize()
        wall = wall_time.perf_counter() - t0

        counters: dict[str, int] = {}
        for h in self.hosts:
            for k, v in h.counters.items():
                counters[k] = counters.get(k, 0) + v
        return SimResult(
            sim_time_ns=self.stop_time,
            wall_seconds=wall,
            rounds=self.rounds,
            event_log=self.event_log,
            counters=counters,
            per_host_counters=[dict(h.counters) for h in self.hosts],
            process_errors=list(getattr(self, "process_errors", [])),
        )


def _start_app(host: Host, app) -> None:
    host._current_app = app
    app.on_start(host)


@dataclasses.dataclass
class SimResult:
    sim_time_ns: int
    wall_seconds: float
    rounds: int
    event_log: list[LogRecord]
    counters: dict[str, int]
    per_host_counters: list[dict[str, int]]
    # expected_final_state mismatches; a non-empty list makes the CLI exit
    # nonzero (controller.rs:70-74)
    process_errors: list[str] = dataclasses.field(default_factory=list)

    def log_tuples(self) -> list[tuple[int, int, int, int, int, int]]:
        """Canonical ordered event log for determinism diffs."""
        return sorted(r.as_tuple() for r in self.event_log)

    @property
    def sim_seconds_per_wall_second(self) -> float:
        return (self.sim_time_ns / 1e9) / max(self.wall_seconds, 1e-9)
