"""TPU backend driver: config -> lane state -> device run -> SimResult.

The host-side counterpart of :mod:`shadow_tpu.backend.lanes`: builds the
device tables and the initial lane state from a :class:`ConfigOptions`
(mirroring ``CpuEngine``'s setup exactly — same host ordering, IPs, routing,
runahead, bucket parameters), runs the simulation on the selected JAX
backend, and reads the results back into the same :class:`SimResult` shape
the CPU engine produces, so the two backends are drop-in comparable.
"""

from __future__ import annotations

import contextlib
import time as wall_time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config.options import ConfigOptions
from ..core import rng as _rng
from ..core import time as stime
from ..models.base import create_model
from ..models.gossip import AGE_COUNTERS, Gossip, gossip_mesh
from ..models.phold import Phold
from ..models.tcpflow import StreamClient, StreamServer
from ..models.tgen import Ping, TgenClient, TgenMesh, TgenServer
from ..net import codel as codel_mod
from ..net.token_bucket import bucket_params
from ..obs import flowtrace as ftr
from ..obs import netobs as netobs_mod
from ..obs.clock import TurnClock
from . import lanes
from . import lanes_stream as lstr_mod
from .cpu_engine import LogRecord, SimResult

NEVER = stime.NEVER

# A run's host phases (spans of the engine's clock; ``fused/<phase>`` in a
# profiler trace): the start state, the device call until it returns, the
# wait for its result, the collect.  The step driver books every round's
# call and wait to the same two.
FUSED_PHASES = ("state_build", "dispatch", "device_wait", "collect")
# An engine with a fault schedule has one more: between a segment's wait
# and the next segment's call, the host's epoch swap (``_run_faulted``).
FAULT_PHASE = "fault_swap"
# A run is one TURN of that clock: ``run`` is the turn's residual (what a
# run spends outside the phases above), and the closed turn leaves one row
# — in the clock's ring and in ``obs.clock.journal["fused"]`` — with these
# notes, ints all (docs/observability.md "The fused run's row"): how the
# run was driven, what it covered, the static shapes a ledger count is
# divided by, and the loop ledger's gauges (0 where the program carries
# none).
LOOP_GAUGES = (
    "loop_pop_slots", "loop_tier_pop_slots", "loop_active_lanes",
    "loop_iters_no_send", "loop_exchange_passes", "loop_round_iters_max",
    "loop_round_iters_p50", "loop_round_iters_p95",
)
FUSED_NOTES = (
    "mode",  # 0 device, 1 step
    "rounds", "lane_iters",
    "segments",  # 1, or a faulted run's segments
    "state_reused", "log_capacity",
    "lanes", "pops_per_iter", "stream_pops", "flows",
) + LOOP_GAUGES
RUN_PHASE = "run"


class LaneCompatError(ValueError):
    """Raised when a config can't run on the lane backend (fall back to
    ``experimental.network_backend: cpu``)."""


# NOTE on ``strict_capacity=False``: queue overflow on this backend evicts
# the *latest-keyed* events of the full lane (the merge keeps the earliest C)
# and burst arrivals past the cross block's width per iteration are shed in
# an order chosen by the (unstable) exchange sort network — deterministic
# for a compiled program but unspecified — whereas the CPU reference never
# drops (its queues are unbounded).  Non-strict runs are therefore NOT
# log-parity comparable once any lane overflows; strict mode (the default)
# raises instead of diverging silently.


class TpuEngine:
    def __init__(
        self,
        cfg: ConfigOptions,
        log_capacity: Optional[int] = None,
        strict_capacity: bool = True,
        external=None,
        inject_batch: Optional[int] = None,
        world=None,
        netobs: Optional[bool] = None,
        flowtrace: Optional[bool] = None,
    ) -> None:
        """``external``: optional [N] bool mask — marked hosts are
        EXTERNAL (hybrid backend, backend/hybrid.py): their apps run on
        the host CPU; the device keeps only their network dn-side (down
        bucket, CoDel, arrival queue) and exchanges traffic through the
        injection/egress machinery instead of model slots.

        ``world``: optional prebuilt ``backend.setup.build_world`` tuple —
        the hybrid engine passes its own so topology/routing are built
        once per run, not once per engine."""
        cfg.validate()
        self.cfg = cfg
        self.strict_capacity = strict_capacity
        if netobs is None:
            netobs = cfg.experimental.netobs
        self._netobs_on = bool(netobs)
        # populated by collect() when netobs is on: the device-side
        # telemetry snapshot (obs/netobs.py array schema)
        self._netobs_data = None
        if flowtrace is None:
            flowtrace = cfg.experimental.flowtrace
        self._flowtrace_on = bool(flowtrace)
        # populated by collect() when flowtrace is on: decoded device
        # ring events + ring-overflow loss count (obs/flowtrace.py)
        self._flowtrace_data = None
        # populated by collect(): how often the record appends engaged
        # (lanes._append_rows) — block writes, rows written, and block
        # writes for merge-tail overflow records, over the whole run;
        # empty for a program with neither a log nor an egress buffer
        self.append_stats: dict[str, int] = {}
        # populated by collect(): the shape of the run that was collected
        # — lanes, mesh_devices, device_log_capacity, device_log_records,
        # exchange_bounds_wide, state_reused; the network the program was
        # compiled for (graph_nodes, window_ns, max_path_latency_ns,
        # has_loss, stream_wide_pop) and what it cost the run
        # (lane_drop_loss, stream_retransmits) (sim-stats.json's
        # ``lane_plane``, the obs gauges of those names)
        self.lane_plane: dict[str, int] = {}
        if inject_batch is None:
            inject_batch = cfg.experimental.tpu_inject_batch
        n = len(cfg.hosts)
        ext_mask = (
            np.zeros(n, dtype=bool) if external is None
            else np.asarray(external, dtype=bool)
        )
        self._external = ext_mask

        # topology (single-sourced with CpuEngine via backend.setup)
        from .setup import build_world

        (
            self.graph,
            self.ips,
            self.dns,
            self.routing,
            bw_up,
            bw_dn,
            runahead,
        ) = world if world is not None else build_world(cfg)

        # --- per-lane model tables and initial events ---------------------
        model = np.zeros(n, dtype=np.int32)
        p_size = np.zeros(n, dtype=np.int32)
        p_interval = np.ones(n, dtype=np.int64)
        p_peer = np.zeros(n, dtype=np.int32)
        p_count = np.zeros(n, dtype=np.int64)
        p_stride = np.ones(n, dtype=np.int64)
        st_segs = np.zeros(n, dtype=np.int32)
        st_mss = np.zeros(n, dtype=np.int32)
        st_last = np.zeros(n, dtype=np.int32)
        st_cc = np.zeros(n, dtype=np.int32)
        init_events: list[tuple[int, int, int, int, int, int]] = []  # lane,t,kind,src,seq,size
        local_seq0 = np.ones(n, dtype=np.int64)

        recv_mult = np.zeros(n, dtype=np.int32)
        # gossip lanes (models/gossip.py): each one's row of its mesh, and
        # their (publication instants, messages a burst): one static table
        # of the program — the instants a first delivery's age counts
        # from, and the message ids a lane can meet (the seen bitmap's bits)
        g_rows: dict[int, np.ndarray] = {}
        g_slots: set[tuple] = set()

        def assign_tgen(hid: int, a) -> None:
            """One source of truth for tgen model/table assignment —
            shared by the single-process and multi-process (driver)
            paths."""
            if isinstance(a, TgenMesh):
                model[hid] = lanes.M_TGEN_MESH
                p_size[hid] = a.size
                p_interval[hid] = a.interval
                p_stride[hid] = a.stride
            elif isinstance(a, TgenClient):
                model[hid] = lanes.M_TGEN_CLIENT
                p_size[hid] = a.size
                p_interval[hid] = a.interval
                p_peer[hid] = self._resolve(a.server, n)
            else:
                model[hid] = lanes.M_TGEN_SERVER

        # COLUMNAR configs (config/columnar.py): the scenario factory has
        # already built the per-lane model/param columns and the initial
        # event table as numpy arrays — skip the per-host Python loop
        # entirely (the 100k-host startup path, ROADMAP item 5)
        spec = getattr(cfg, "columnar", None)
        if spec is not None and ext_mask.any():
            raise LaneCompatError(
                "columnar configs are lane-only: the hybrid backend "
                "executes per-host process objects host-side; build the "
                "config without the columnar spec"
            )
        host_iter = () if spec is not None else enumerate(cfg.hosts)
        for hid, hopt in host_iter:
            # pcap: sends emit PCAP_TX records into the device log, and
            # collect() reconstructs per-host capture files byte-identical
            # to the CPU backend's (synthetic payloads either way)
            if ext_mask[hid]:
                # hybrid: the host side executes this host's apps; the
                # lane only runs its packet-arrival machinery
                model[hid] = lanes.M_NONE
                continue
            if not hopt.processes:
                model[hid] = lanes.M_NONE
                continue
            apps = [
                (p, create_model(p.path, list(p.args)))
                for p in hopt.processes
            ]
            for _, a in apps:
                if hasattr(a, "set_congestion"):
                    a.set_congestion(hopt.congestion)
            if len(apps) > 1:
                # MULTI-PROCESS hosts: supported for tgen mesh/client/
                # server combinations with at most one timer-driving
                # process — the lane's model id is the driver's, other
                # processes contribute start anchors and delivery
                # counting (recv_mult).  The CPU oracle dispatches every
                # delivery to every app, so k counting apps multiply the
                # recv accounting by k on both backends.
                trio = (TgenMesh, TgenClient, TgenServer)
                if not all(isinstance(a, trio) for _p, a in apps):
                    raise LaneCompatError(
                        f"host {hopt.hostname!r}: multi-process lane "
                        "hosts support tgen mesh/client/server "
                        "combinations only; use the cpu backend"
                    )
                drivers = [
                    (p, a) for p, a in apps
                    if isinstance(a, (TgenMesh, TgenClient))
                ]
                if len(drivers) > 1:
                    raise LaneCompatError(
                        f"host {hopt.hostname!r}: at most one "
                        "timer-driving process per lane host; use the "
                        "cpu backend"
                    )
                recv_mult[hid] = len(apps)
                driver = drivers[0] if drivers else apps[0]
                seq = 0
                for p, a in apps:
                    init_events.append((
                        hid, p.start_time, lanes.LOCAL, hid, seq,
                        -1 if a is driver[1] else lanes.SZ_ANCHOR,
                    ))
                    seq += 1
                local_seq0[hid] = seq
                assign_tgen(hid, driver[1])
                continue
            recv_mult[hid] = 1
            proc, app = apps[0]
            t0 = proc.start_time
            if isinstance(app, Phold):
                model[hid] = lanes.M_PHOLD
                p_size[hid] = app.size
                for i in range(app.messages):
                    init_events.append((hid, t0, lanes.LOCAL, hid, i, 0))
                local_seq0[hid] = max(app.messages, 1)
            elif isinstance(app, Gossip):
                model[hid] = lanes.M_GOSSIP
                p_size[hid] = app.size
                g_rows[hid] = gossip_mesh(n, app.degree, app.mesh_seed)[hid]
                g_slots.add((app.bursts, app.messages))
                # the start marker, then one publish timer a publication
                # in message order (the oracle's on_start arms them so);
                # the message id rides the LOCAL's size word
                init_events.append((hid, t0, lanes.LOCAL, hid, 0, -1))
                pubs = app.publications(hid, n)
                for i, (t, m) in enumerate(pubs):
                    if t <= t0:
                        raise LaneCompatError(
                            f"host {hopt.hostname!r}: gossip burst at {t} "
                            f"ns is not after the process start ({t0} ns)"
                        )
                    init_events.append((hid, t, lanes.LOCAL, hid, 1 + i, m))
                local_seq0[hid] = 1 + len(pubs)
            elif isinstance(app, (TgenMesh, TgenClient, TgenServer)):
                assign_tgen(hid, app)
                init_events.append((hid, t0, lanes.LOCAL, hid, 0, -1))
            elif isinstance(app, StreamClient):
                model[hid] = lanes.M_STREAM_CLIENT
                p_peer[hid] = self._resolve(app.server, n)
                # int32/packed-payload magnitude guards: seq units ride a
                # 26-bit payload field and rx_bytes an int32 counter
                if app.fs.segs + 2 >= (1 << lstr_mod.PAY_SEQ_BITS):
                    raise LaneCompatError(
                        f"stream flow of {app.fs.segs} segments exceeds the "
                        f"lane backend's {lstr_mod.PAY_SEQ_BITS}-bit sequence "
                        "space; use the cpu backend"
                    )
                if app.size >= (1 << 31):
                    raise LaneCompatError(
                        "stream transfer size exceeds the lane backend's "
                        "int32 byte counter; use the cpu backend"
                    )
                st_segs[hid], st_last[hid] = app.fs.segs, app.fs.last_bytes
                st_mss[hid] = app.mss
                st_cc[hid] = app.fs.cc
                init_events.append((hid, t0, lanes.LOCAL, hid, 0, -1))
            elif isinstance(app, StreamServer):
                model[hid] = lanes.M_STREAM_SERVER
                # the start marker anchors window boundaries exactly like
                # the CPU engine's start task (flows open on the first SYN)
                init_events.append((hid, t0, lanes.LOCAL, hid, 0, -1))
            elif isinstance(app, Ping):
                if app.peer is None:
                    model[hid] = lanes.M_PING_SERVER
                else:
                    model[hid] = lanes.M_PING_CLIENT
                    p_peer[hid] = self._resolve(app.peer, n)
                    p_count[hid] = app.count_target
                    p_interval[hid] = app.interval
                p_size[hid] = app.size
                init_events.append((hid, t0, lanes.LOCAL, hid, 0, -1))
            else:  # pragma: no cover - registry and this list must stay in sync
                raise LaneCompatError(
                    f"model {proc.path!r} is not lane-compiled yet; use the cpu backend"
                )

        # fault schedule: versioned latency/loss gather tables re-uploaded
        # at epoch boundaries (shadow_tpu/faults/overlay.py); the run is
        # segmented per epoch so no window straddles a fault
        self._fault_overlay = None
        self._watchdog_timeout = cfg.faults.watchdog_timeout
        if cfg.faults.events:
            if ext_mask.any():
                # hybrid backend: backend_stall-only schedules are owned
                # by the hybrid window loop (backend/hybrid.py raises at
                # the stall epoch for the failover boundary to catch) —
                # no overlay tables to build.  Link/host fault schedules
                # stay gated off the device lane tables.
                if any(
                    ev.get("kind") != "backend_stall"
                    for ev in cfg.faults.events
                ):
                    raise LaneCompatError(
                        "link/host fault schedules are not supported on "
                        "the hybrid tpu backend; use the cpu backend"
                    )
            else:
                from ..faults.overlay import build_overlay

                self._fault_overlay = build_overlay(
                    cfg, self.graph, self.routing
                )

        if spec is not None:
            (
                model, p_size, p_interval, p_peer, p_count, p_stride,
                recv_mult, local_seq0,
            ) = spec.model_columns(n)
            init_cols = spec.event_columns()
        else:
            ev = (
                np.asarray(init_events, dtype=np.int64).reshape(-1, 6)
            )
            init_cols = tuple(ev[:, j] for j in range(6))
        # (lane, t, kind, src, seq, size) int64 columns — the columnar
        # initial-event table, consumed vectorized by initial_state()
        self._init_cols = init_cols

        capacity = cfg.experimental.tpu_lane_queue_capacity
        if cfg.experimental.tpu_cross_capacity < 0:
            raise LaneCompatError(
                f"tpu_cross_capacity={cfg.experimental.tpu_cross_capacity} "
                "must be >= 0 (0 = queue capacity)"
            )
        ev_lane = init_cols[0]
        max_init = (
            int(np.bincount(ev_lane, minlength=max(n, 1)).max())
            if ev_lane.size else 0
        )
        if capacity < max_init + 8:
            raise LaneCompatError(
                f"tpu_lane_queue_capacity={capacity} too small for {max_init} "
                "initial events per lane (+8 headroom)"
            )

        node_idx, lat, thresh = self.routing.device_tables()
        if log_capacity is None:
            log_capacity = 200_000

        # one-to-one stream pairing (every stream server is the peer of
        # exactly one client) only affects the POP rule now: flow state is
        # COMPACTED per flow slot either way (rows 0..S-1 = clients,
        # S..2S-1 = servers — lanes_stream.endpoint_cols), so the lane
        # layout no longer depends on the pairing shape
        client_ids = np.nonzero(model == lanes.M_STREAM_CLIENT)[0]
        server_ids = set(np.nonzero(model == lanes.M_STREAM_SERVER)[0].tolist())
        peer_counts: dict[int, int] = {}
        for cid in client_ids:
            peer_counts[int(p_peer[cid])] = peer_counts.get(int(p_peer[cid]), 0) + 1
        one_to_one = bool(client_ids.size) and all(
            peer_counts.get(sid, 0) == 1 for sid in server_ids
        ) and all(pid in server_ids for pid in peer_counts)
        # TIERED stream backend: one-to-one flows move to a dedicated
        # [2S]-row tier (docs/tpu-backend.md).  Hybrid (external) runs
        # keep the older split-exchange path: host injections land in
        # [N] rows, which the tier would orphan for stream lanes.
        # flowtrace instruments the [N] untiered path only: tracing a run
        # drops the tier (equivalent execution strategy, bit-identical
        # events, slower — fine for untimed evidence runs)
        tiered = bool(
            one_to_one
            and not ext_mask.any()
            and not self._flowtrace_on
        )
        self._tiered = tiered

        # wide stream co-pop is sound only when every possible lookahead
        # window ends before RTO_MIN (DELIVERY pops then provably insert
        # nothing same-window); the dynamic window never exceeds the
        # largest link latency
        from ..net import ltcp as ltcp_mod

        max_lat = int(np.max(np.asarray(lat), initial=0))
        if self._fault_overlay is not None:
            # fault epochs can raise latencies mid-run; the wide-pop bound
            # must hold for every snapshot's tables
            max_lat = max(max_lat, self._fault_overlay.max_latency_ns())
        max_window = max(runahead, max_lat)
        stream_wide_pop = max_window < ltcp_mod.RTO_MIN
        # the longest routed path, for collect()'s lane_plane
        self._max_path_latency_ns = max_lat

        lane_pcap = np.array([h.pcap_enabled for h in cfg.hosts], dtype=bool)
        # external lanes' pcap is written host-side (the host knows the
        # payload bytes); the device captures lane-model hosts only
        lane_pcap = lane_pcap & ~ext_mask
        pcap_any = bool(lane_pcap.any())
        if pcap_any and log_capacity == 0:
            raise LaneCompatError(
                "pcap capture on the lane backend rides the device event "
                "log; log_capacity=0 disables it — use the cpu backend or "
                "enable logging"
            )
        # pcap + stream works since round 4: stream sends emit PCAP_TX
        # records through their compacted channels at departure, and both
        # backends synthesize stream bodies from sizes alone

        g_degrees = {len(row) for row in g_rows.values()}
        if len(g_degrees) > 1:
            raise LaneCompatError(
                f"gossip lanes of different degrees {sorted(g_degrees)}: the "
                "sends of a pop are one static width; use the cpu backend"
            )
        if len(g_slots) > 1:
            raise LaneCompatError(
                "gossip lanes of different bursts or messages a burst "
                f"{sorted(g_slots)}: a message's burst instant is one static "
                "table; use the cpu backend"
            )
        g_bursts, g_per_burst = g_slots.pop() if g_slots else ((), 1)
        if g_rows and (client_ids.size or server_ids or pcap_any
                       or self._flowtrace_on):
            raise LaneCompatError(
                "gossip lanes beside stream lanes, pcap capture or flowtrace "
                "are not lane-compiled yet; use the cpu backend"
            )
        self._gossip_words = -(-max(len(g_bursts) * g_per_burst, 1) // 32)

        ft_thresh, ft_all = ftr.sample_thresh(cfg.experimental.flowtrace_sample)
        self.params = lanes.LaneParams(
            n_lanes=n,
            capacity=capacity,
            pops_per_iter=cfg.experimental.tpu_events_per_round,
            log_capacity=log_capacity,
            seed=cfg.general.seed,
            stop_time=cfg.general.stop_time,
            bootstrap_end=cfg.general.bootstrap_end_time,
            runahead=runahead,
            models_present=tuple(int(x) for x in np.unique(model)),
            # fault epochs may introduce loss later in the run: the loss
            # draw must be compiled in from the start (the counter-based
            # RNG keys on send seq, so drawing on loss-free segments
            # cannot shift any stream)
            has_loss=bool(np.any(np.asarray(thresh) > 0))
            or (
                self._fault_overlay is not None and self._fault_overlay.any_loss()
            ),
            dynamic_runahead=bool(cfg.experimental.use_dynamic_runahead),
            runahead_floor=max(cfg.experimental.runahead or 0, 1),
            stream_one_to_one=one_to_one,
            stream_clients=tuple(int(c) for c in client_ids),
            stream_wide_pop=stream_wide_pop,
            pcap_any=pcap_any,
            stream_pcap=bool(
                client_ids.size
                and lane_pcap[
                    np.concatenate([client_ids,
                                    p_peer[client_ids]]).astype(np.int64)
                ].any()
            ),
            cross_capacity=cfg.experimental.tpu_cross_capacity,
            stream_tiered=tiered,
            stream_pops=cfg.experimental.tpu_stream_events_per_round,
            stream_capacity=cfg.experimental.tpu_stream_queue_capacity,
            netobs=self._netobs_on,
            flowtrace=self._flowtrace_on,
            flow_capacity=(
                cfg.experimental.flowtrace_capacity
                if self._flowtrace_on else 0
            ),
            flow_thresh=ft_thresh,
            flow_all=ft_all,
            flow_seed=cfg.general.seed,
            external_any=bool(ext_mask.any()),
            # worst case: every external lane pops a full slot row of
            # packets in one iteration; the egress buffer keeps at least
            # that much headroom so one iteration can never overflow it
            ext_per_iter=(
                int(ext_mask.sum()) * cfg.experimental.tpu_events_per_round
            ),
            egress_capacity=(
                max(1024, 4 * int(ext_mask.sum())
                    * cfg.experimental.tpu_events_per_round)
                if ext_mask.any() else 0
            ),
            inject_batch=inject_batch if ext_mask.any() else 0,
            inject_cross=capacity if ext_mask.any() else 0,
            gossip_degree=g_degrees.pop() if g_degrees else 0,
            gossip_bursts=g_bursts,
            gossip_messages=g_per_burst,
        )

        up = np.array([bucket_params(int(b)) for b in bw_up], dtype=np.int64)
        dn = np.array([bucket_params(int(b)) for b in bw_dn], dtype=np.int64)

        # int32 magnitude guards: the lane kernel's pair arithmetic is
        # exact only within these (generous) ranges — reject configs
        # beyond them instead of silently diverging
        interval = lanes.DEFAULT_INTERVAL_NS
        i32max = (1 << 31) - 1

        def _check(name, arr, limit):
            mx = int(np.max(arr)) if np.size(arr) else 0
            if mx > limit:
                raise LaneCompatError(
                    f"{name} {mx} exceeds the lane backend's int32 range "
                    f"({limit}); use the cpu backend"
                )

        if interval >= lanes.MOD_SMALL_LIMIT:
            raise LaneCompatError(
                f"bucket interval {interval} ns exceeds the chunked-mod "
                f"ceiling ({lanes.MOD_SMALL_LIMIT}); use the cpu backend"
            )
        # strictly below NEVER32: a latency equal to the sentinel would
        # read as "no sends yet" in the dynamic-runahead scalar
        _check("link latency (ns)", np.asarray(lat), i32max - 1)
        if self._fault_overlay is not None:
            _check(
                "fault-epoch link latency (ns)",
                np.asarray([self._fault_overlay.max_latency_ns()]),
                i32max - 1,
            )
        _check("runahead (ns)", np.asarray([runahead]), i32max)
        for side, b in (("up", up), ("dn", dn)):
            # the refill computes tokens + k*rate <= 2*burst + rate before
            # clamping to burst: THAT intermediate must fit int32
            _check(f"{side} bucket refill ceiling (2*burst + rate)",
                   2 * b[:, 1] + b[:, 0], i32max)
        _check("datagram size", p_size, 1 << 20)
        # one max-size packet's bucket wait must fit the int32 horizon:
        # w = ceil(bits/rate) intervals, w*interval < 2**31
        max_bits = (int(np.max(p_size, initial=0)) + 65536 + 38) * 8
        for side, b in (("up", up), ("dn", dn)):
            rates = b[:, 0][b[:, 0] > 0]
            if rates.size:
                w_max = -(-max_bits // int(rates.min()))
                if w_max * interval > i32max:
                    raise LaneCompatError(
                        f"{side} bandwidth {int(rates.min())} bits/interval is "
                        "too low for the lane backend's int32 wait horizon "
                        "(one packet would wait > 2.1 s for tokens); use the "
                        "cpu backend"
                    )

        def _kfull(b):
            rate = np.maximum(b[:, 0], 1)
            kf = b[:, 1] // rate + 1
            kfi = kf * interval
            _check("bucket full-refill horizon (ns)", kfi, i32max)
            return kf.astype(np.int32), kfi.astype(np.int32)

        up_kfull, up_kfi = _kfull(up)
        dn_kfull, dn_kfi = _kfull(dn)
        i32 = jnp.int32

        # COMPACTED stream-flow tables: [2S] endpoint rows (clients then
        # servers, flow order = ascending client lane) with everything
        # static per flow precomputed — peer, latency, loss threshold, and
        # the endpoint lane's up-bucket parameters — so the stream tier
        # touches no [N]- or [G, G]-shaped table at all.  [2]-placeholder
        # shapes when no stream models are present.
        self._s_flows = s_flows = int(client_ids.size)
        if s_flows:
            fcl = client_ids.astype(np.int32)
            fsv = p_peer[fcl].astype(np.int32)
            el_np = np.concatenate([fcl, fsv])
            peer_np = np.concatenate([fsv, fcl])
            flow_segs = np.concatenate(
                [st_segs[fcl], np.zeros(s_flows, dtype=np.int32)]
            )
            flow_mss = np.concatenate(
                [st_mss[fcl], np.zeros(s_flows, dtype=np.int32)]
            )
            flow_last = np.concatenate(
                [st_last[fcl], np.zeros(s_flows, dtype=np.int32)]
            )
            # CC follows the data sender (the client host's congestion
            # option); receiver endpoints stay CC_RENO like the scalar
            # StreamServer's default-constructed FlowState
            flow_cc = np.concatenate(
                [st_cc[fcl], np.zeros(s_flows, dtype=np.int32)]
            )
            flow_clid = np.concatenate([fcl, fcl])
        else:
            el_np = peer_np = np.zeros(2, dtype=np.int32)
            flow_segs = flow_mss = flow_last = np.zeros(2, dtype=np.int32)
            flow_cc = np.zeros(2, dtype=np.int32)
            flow_clid = np.zeros(2, dtype=np.int32)

        self._el_np = el_np  # [2S] endpoint lanes (tiered routing/collect)
        self._peer_np = peer_np  # [2S] peer lanes (fault-epoch flow tables)
        self._node_idx = node_idx  # [N] host -> dense node index
        self._g_peers_np = self._gossip_peers(g_rows, n)
        paths = self._path_tables(lat, thresh)
        # the LaneTables fields an epoch's tables decide (a faulted run's
        # program takes them as arguments)
        self._path_fields = tuple(paths)
        self.tables = lanes.LaneTables(
            node_of=jnp.asarray(node_idx, dtype=i32),
            **paths,
            up_rate=jnp.asarray(up[:, 0], dtype=i32),
            up_burst=jnp.asarray(up[:, 1], dtype=i32),
            up_kfull=jnp.asarray(up_kfull),
            up_kfi=jnp.asarray(up_kfi),
            dn_rate=jnp.asarray(dn[:, 0], dtype=i32),
            dn_burst=jnp.asarray(dn[:, 1], dtype=i32),
            dn_kfull=jnp.asarray(dn_kfull),
            dn_kfi=jnp.asarray(dn_kfi),
            model=jnp.asarray(model),
            recv_mult=jnp.asarray(recv_mult),
            p_size=jnp.asarray(p_size),
            p_int_hi=jnp.asarray(p_interval >> 31, dtype=i32),
            p_int_lo=jnp.asarray(p_interval & lanes.MASK31, dtype=i32),
            p_peer=jnp.asarray(p_peer),
            p_count=jnp.asarray(np.minimum(p_count, i32max), dtype=i32),
            p_stride=jnp.asarray(p_stride, dtype=i32),
            codel_div=jnp.asarray(np.array(codel_mod.CODEL_DIV, dtype=np.int32)),
            flow_lanes=jnp.asarray(el_np),
            flow_peers=jnp.asarray(peer_np),
            flow_clid=jnp.asarray(flow_clid),
            flow_segs=jnp.asarray(flow_segs, dtype=i32),
            flow_mss=jnp.asarray(flow_mss, dtype=i32),
            flow_last=jnp.asarray(flow_last, dtype=i32),
            flow_cc=jnp.asarray(flow_cc, dtype=i32),
            flow_up_rate=jnp.asarray(up[el_np, 0], dtype=i32),
            flow_up_burst=jnp.asarray(up[el_np, 1], dtype=i32),
            flow_up_kfull=jnp.asarray(up_kfull[el_np]),
            flow_up_kfi=jnp.asarray(up_kfi[el_np]),
            flow_pcap=jnp.asarray(lane_pcap[el_np]),
            lane_pcap=jnp.asarray(lane_pcap),
            lane_external=(
                jnp.asarray(ext_mask) if ext_mask.any() else ()
            ),
            flow_dn_rate=jnp.asarray(dn[el_np, 0], dtype=i32) if tiered else (),
            flow_dn_burst=jnp.asarray(dn[el_np, 1], dtype=i32) if tiered else (),
            flow_dn_kfull=jnp.asarray(dn_kfull[el_np]) if tiered else (),
            flow_dn_kfi=jnp.asarray(dn_kfi[el_np]) if tiered else (),
            lane_stream=(
                jnp.asarray(np.isin(np.arange(n), el_np)) if tiered else ()
            ),
            g_peers=(() if self._g_peers_np is None
                     else jnp.asarray(self._g_peers_np)),
        )
        self._local_seq0 = local_seq0
        self._model_np = model  # [N] app model per lane (collect's masks)
        self._ep_of_lane = (
            {int(l): r for r, l in enumerate(el_np)} if tiered else {}
        )
        self._dn_params = dn  # [N, 2] (rate, burst) — tier init needs bursts
        self._up_params = up
        self._interval = lanes.DEFAULT_INTERVAL_NS
        # multi-chip plane (parallel/mesh.py): attach_mesh shards the
        # lane axis over a device mesh; None = single-device placement
        self._mesh = None
        self._run_fn = None
        self._compiled = None
        # what the single-device run program takes after the state: the
        # seed's two words where the network loses packets, else nothing
        self._seed_args = ()
        # the initial state run() keeps on the device (_start_state), and
        # whether the last run started from it (lane_plane's state_reused)
        self._kept = None
        self._state_reused = 0
        # the devices the last collected state lived on (device_info)
        self._placed_devices = None
        # [window-agg] telemetry sink (step mode only; set by the facade)
        self.perf_log = None
        # obs Recorder (shadow_tpu/obs/), handed over by the facade; the
        # clock below forwards its spans there when there is one
        self.obs = None
        # the host-phase clock (obs/clock.py, always on): a run's host
        # phases, on the profiler's clock as fused/<phase>.  obs gets
        # them under its own names: device_turn is the blocking wait
        faulted = self._fault_overlay is not None
        self.clock = TurnClock(
            self, "fused",
            FUSED_PHASES + ((FAULT_PHASE,) if faulted else ()) + (RUN_PHASE,),
            notes=FUSED_NOTES, turn_phase=RUN_PHASE, journal=True,
            obs_map={
                "state_build": ("state_build", None, None),
                "dispatch": ("dispatch", None, None),
                "device_wait": ("device_turn", None, "active"),
                "collect": ("collect", None, None),
                FAULT_PHASE: ("fault_swap", None, None),
            },
        )
        # the faulted driver (_run_faulted): its ONE program a mode, the
        # AOT-compiled device one, every epoch's path leaves as placed on
        # the device (keyed by the overlay's version: a console fault
        # recompiles the snapshots), and the run's lane_plane gauges
        self._fault_fns: dict = {}
        self._fault_compiled = None
        self._fault_leaves: tuple = (None, {})
        self._fault_plane: dict = {}

    def _resolve(self, hostname: str, n: int) -> int:
        return self.dns.resolve(hostname)

    @staticmethod
    def _gossip_peers(g_rows: dict, n: int):
        """``LaneTables.g_peers`` on the host: the ``[N, D]`` peer table (a
        lane of another model keeps a row of zeros it never reads), or
        None where no lane runs gossip."""
        if not g_rows:
            return None
        peers = np.zeros((n, len(next(iter(g_rows.values())))), np.int32)
        peers[list(g_rows)] = np.stack(list(g_rows.values()))
        return peers

    def _path_tables(self, lat, thresh) -> dict:
        """The ``LaneTables`` fields one epoch's ``[G, G]`` latency and
        loss-threshold tables decide: the tables themselves, their two
        packed words by flat index where a run-time destination gathers
        its path (``flat_*``, ``[G * G]``) and their compactions for
        every STATIC destination — a flow's peer (``flow_*``, ``[2S]``)
        and a gossip lane's D mesh peers (``g_*``, ``[F, N]``, lanes
        minor) — whose path is a constant of the lane, so a send reads a
        row and gathers nothing.  ONE law for start-up and each fault
        epoch."""
        return {k: jnp.asarray(v)
                for k, v in self._path_words(lat, thresh).items()}

    def _path_words(self, lat, thresh) -> dict:
        """``_path_tables`` on the host (numpy): a faulted run places
        every epoch's in one transfer (``_epoch_leaves``)."""
        lat_np, thr_np = np.asarray(lat), np.asarray(thresh)
        nodes = np.asarray(self._node_idx)

        def words(prefix, lat, thr):
            return {
                prefix + "lat": np.asarray(lat).astype(np.int32),
                prefix + "thresh_u32": (thr & 0xFFFFFFFF).astype(np.uint32),
                prefix + "thresh_all": np.asarray(thr >= (1 << 32)),
            }

        def paths(prefix, src, dst):
            a, b = nodes[src], nodes[dst]
            return words(prefix, lat_np[a, b], thr_np[a, b])

        kw = words("", lat_np, thr_np)
        if self._s_flows:
            kw.update(paths("flow_", self._el_np, self._peer_np))
        else:  # [2]-placeholders
            kw.update(words("flow_", np.zeros(2, np.int32),
                            np.zeros(2, np.int64)))
        # on one graph node the [1, 1] lookup already folds to a scalar
        if self._g_peers_np is not None and lat_np.shape[0] > 1:
            peers = self._g_peers_np.T  # [F, N]
            kw.update(paths("g_", np.arange(peers.shape[1])[None, :], peers))
        if lanes.gathers_path(self.params, lat_np.shape[-1]):
            # bit 31 of a latency is free for the lose-everything bit:
            # __init__ rejects any latency, of any epoch, at or above
            # NEVER32, and a routed pair's latency is positive (a pair
            # without a route, -1, is one no two hosts form: RoutingInfo
            # refuses the others, and its word is never used).  A
            # loss-free program reads the word as the latency, unmasked
            assert lat_np.min() >= -1 and lat_np.max() < lanes.NEVER32
            assert self.params.has_loss or not kw["thresh_all"].any()
            kw["flat_lat"] = (
                kw["lat"] | (kw["thresh_all"].astype(np.int32) << 31)
            ).reshape(-1)
            kw["flat_thresh"] = kw["thresh_u32"].reshape(-1)
        return kw

    # -- multi-chip plane (parallel/mesh.py) -------------------------------

    def attach_mesh(self, mesh) -> None:
        """Shard this engine's data plane over ``mesh``: subsequent
        ``run()`` / ``make_hybrid_fns()`` compiles split the lane axis
        across the mesh devices under the parallel/mesh.py sharding law
        (bit-identical results at any mesh shape).  Cached programs are
        invalidated — they were compiled for the previous placement."""
        if mesh is not None and self.params.n_lanes % mesh.devices.size:
            raise LaneCompatError(
                f"n_lanes={self.params.n_lanes} not divisible by mesh "
                f"size {mesh.devices.size} (negotiate_devices picks a "
                "dividing count)"
            )
        self._mesh = mesh
        self._run_fn = None
        self._compiled = None
        self._seed_args = ()
        self._kept = None

    @property
    def mesh(self):
        return self._mesh

    def device_info(self) -> dict:
        """``{platform, kind, count}`` of the devices the lane state
        lives on (shadow_tpu/device.py): read off the final state's own
        arrays once a run has collected, and before that the placement
        the run will take (the attached mesh, else JAX's default
        device).  ``network_backend: tpu`` names the lane PROGRAM; this
        names where it ran."""
        from ..device import describe_devices

        placed = self._placed_devices
        if placed is None:
            placed = (
                self._mesh.devices.flat if self._mesh is not None
                else jax.devices()[:1]
            )
        return describe_devices(placed)

    def place_state(self, state: lanes.LaneState) -> lanes.LaneState:
        """Commit ``state`` to this engine's placement: sharded over the
        attached mesh, or unchanged when single-device."""
        if self._mesh is None:
            return state
        from .. import parallel

        return parallel.shard_state(state, self._mesh)

    def first_event_time(self) -> int:
        """Earliest initial-event epoch (NEVER when none) — the hybrid
        window loop's starting device bound."""
        t = self._init_cols[1]
        return int(t.min()) if t.size else NEVER

    def _next_event_np(self, state) -> int:
        """Host-side earliest-event readback (step-mode telemetry):
        queue rows are sorted, so column 0 is each queue's min — [N]
        lanes plus the [2S] tier block when tiered."""
        nxt = int(
            np.asarray(
                lanes.t_join(state.q_thi[:, 0], state.q_tlo[:, 0])
            ).min()
        )
        if self.params.stream_tiered:
            tq = state.stream.q
            nxt = min(nxt, int(np.asarray(lanes.t_join(
                tq[lstr_mod.TQ_THI, :, 0], tq[lstr_mod.TQ_TLO, :, 0]
            )).min()))
        return nxt

    def current_runahead(self) -> int:
        """Live window width (dynamic runahead reads the device scalar;
        static mode is the precomputed minimum) — the step driver's
        window predictor and run-control's host listing use this."""
        p = self.params
        if not p.dynamic_runahead:
            return p.runahead
        state = getattr(self, "_live_state", None)
        if state is None:
            return p.runahead
        used = int(state.min_used_lat)
        if used >= lanes.NEVER32:
            return p.runahead
        return max(used, max(p.runahead_floor, 1))

    # -- hybrid kernel ------------------------------------------------------

    def make_hybrid_fns(self, fuse_k: int, ext_slots: int):
        """The hybrid backend's jitted device entry points, built against
        this engine's params/tables: ``(turn_fn, inject_fn)``.

        ``turn_fn`` is the k-window fused call
        (:func:`lanes.make_hybrid_fused_fn`, docs/hybrid.md "k-window
        fusion law"): one dispatch covers up to ``fuse_k`` participating
        windows (the static cap; 1 = one window per dispatch) against a
        host-peeked ``ext_slots``-wide event-time schedule.

        With a mesh attached the same entry points compile SHARDED
        (parallel.make_sharded_hybrid_fns): lane state split on the host
        axis, the injection/egress boundary replicated — same transfer
        counts, same bits."""
        if self._mesh is not None:
            from .. import parallel

            return parallel.make_sharded_hybrid_fns(
                self.params, self.tables, self._mesh, fuse_k, ext_slots
            )
        return (
            lanes.make_hybrid_fused_fn(
                self.params, self.tables, fuse_k, ext_slots
            ),
            lanes.make_inject_fn(self.params, self.tables),
        )

    # -- sweep kernel (shadow_tpu/sweep drives this) -----------------------

    def make_sweep_fn(self):
        """The sweep backend's jitted vmapped entry point, built against
        this engine's STATIC params (:func:`lanes.make_sweep_fn`): the
        per-scenario tables, stop bounds, and lane states are traced
        arguments, so one compile serves every congruent variant.  The
        returned wrapper's ``.traces`` attribute is the compile probe."""
        return lanes.make_sweep_fn(self.params)

    def sweep_tables(self, snap=None) -> lanes.LaneTables:
        """This engine's device tables as ONE SCENARIO ROW of a sweep
        batch: the traced ``seed_lo``/``seed_hi`` leaves are populated
        from the config seed (core.rng ``_split_seed`` semantics — the
        exact key words the static path compiles in), and ``snap`` (a
        faults Snapshot) re-gathers the epoch's latency/loss tables."""
        tb = self.tables if snap is None else self._segment_tables(snap)
        s_lo, s_hi = _rng._split_seed(self.params.seed)
        return tb._replace(
            seed_lo=jnp.uint32(s_lo), seed_hi=jnp.uint32(s_hi)
        )

    # -- state construction ------------------------------------------------

    def initial_state(self, shardings=None) -> lanes.LaneState:
        """A FRESH initial lane state per call, built on the host and
        placed by one ``jax.device_put``: on JAX's default device, or
        straight onto ``shardings`` (``parallel.state_shardings``).
        ``run`` builds it once per engine (``_start_state``)."""
        p = self.params
        n, c = p.n_lanes, p.capacity
        q_time = np.full((n, c), NEVER, dtype=np.int64)
        q_auxh = np.zeros((n, c), dtype=np.int32)
        q_auxl = np.zeros((n, c), dtype=np.int32)
        q_size = np.zeros((n, c), dtype=np.int32)
        fill = np.zeros(n, dtype=np.int64)
        # tiered: stream endpoints' init events live in the tier queue
        c2 = p.stream_capacity
        s2 = 2 * self._s_flows
        if p.stream_tiered:
            tq_time = np.full((s2, c2), NEVER, dtype=np.int64)
            tq_auxh = np.zeros((s2, c2), dtype=np.int32)
            tq_auxl = np.zeros((s2, c2), dtype=np.int32)
            tq_size = np.zeros((s2, c2), dtype=np.int32)
            tfill = np.zeros(s2, dtype=np.int64)
        ev_lane, ev_t, ev_kind, ev_src, ev_seq, ev_size = self._init_cols
        if self._ep_of_lane:
            # tiered: stream endpoints' events route to tier rows — a
            # handful of compacted flows, the per-event loop is fine
            for lane, t, kind, src, seq, size in zip(
                ev_lane.tolist(), ev_t.tolist(), ev_kind.tolist(),
                ev_src.tolist(), ev_seq.tolist(), ev_size.tolist(),
            ):
                row = self._ep_of_lane.get(lane)
                if row is not None:
                    i = tfill[row]
                    tq_time[row, i] = t
                    tq_auxh[row, i] = (kind << lanes.AUX_KIND_SHIFT) | (
                        src << lanes.AUX_SRC_SHIFT
                    )
                    tq_auxl[row, i] = seq
                    tq_size[row, i] = size
                    tfill[row] += 1
                    continue
                i = fill[lane]
                q_time[lane, i] = t
                q_auxh[lane, i] = (kind << lanes.AUX_KIND_SHIFT) | (
                    src << lanes.AUX_SRC_SHIFT
                )
                q_auxl[lane, i] = seq
                q_size[lane, i] = size
                fill[lane] += 1
        elif ev_lane.size:
            # vectorized fill (the 100k-host startup path): stable-sort
            # events by lane and slot each into its per-lane cumcount
            # position — same per-lane event sets as the scalar loop, and
            # the per-row lexsort below normalizes slot order either way
            order = np.argsort(ev_lane, kind="stable")
            l_s = ev_lane[order]
            counts = np.bincount(l_s, minlength=n)
            starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
            pos = np.arange(l_s.size) - np.repeat(starts, counts)
            q_time[l_s, pos] = ev_t[order]
            q_auxh[l_s, pos] = (ev_kind[order] << lanes.AUX_KIND_SHIFT) | (
                ev_src[order] << lanes.AUX_SRC_SHIFT
            )
            q_auxl[l_s, pos] = ev_seq[order]
            q_size[l_s, pos] = ev_size[order]
        # the round kernel keeps queue rows sorted by the 4-word key as an
        # invariant; establish it here (aux_lo before aux_hi: np.lexsort
        # takes the PRIMARY key last)
        order = np.lexsort((q_auxl, q_auxh, q_time), axis=1)
        q_time = np.take_along_axis(q_time, order, axis=1)
        q_auxh = np.take_along_axis(q_auxh, order, axis=1)
        q_auxl = np.take_along_axis(q_auxl, order, axis=1)
        q_size = np.take_along_axis(q_size, order, axis=1)
        never = q_time == NEVER
        q_thi = np.where(never, lanes.NEVER32, q_time >> 31).astype(np.int32)
        q_tlo = np.where(never, lanes.NEVER32, q_time & lanes.MASK31).astype(
            np.int32
        )

        # no stream tier -> no stream matrices AND no payload columns: the
        # while-loop carry is assumed to pay a per-buffer cost every
        # iteration (unmeasured on the attached chip), so dead zero
        # arrays would be real wall time.
        # Flow matrices are COMPACTED: [S, F] per endpoint side
        if p.stream_tiered:
            el = self._el_np
            stream0 = lstr_mod.init_tier_state(
                self._s_flows, c2,
                dn_tokens=self._dn_params[el, 1],
                up_tokens=self._up_params[el, 1],
                interval=self._interval,
            )
            # establish the tier rows' sorted invariant + initial local
            # seq counters (one start marker consumed per endpoint)
            order = np.lexsort((tq_auxl, tq_auxh, tq_time), axis=1)
            tq_time = np.take_along_axis(tq_time, order, axis=1)
            tq_auxh = np.take_along_axis(tq_auxh, order, axis=1)
            tq_auxl = np.take_along_axis(tq_auxl, order, axis=1)
            tq_size = np.take_along_axis(tq_size, order, axis=1)
            tnever = tq_time == NEVER
            tq = np.zeros((7, s2, c2), dtype=np.int32)
            tq[lstr_mod.TQ_THI] = np.where(
                tnever, lanes.NEVER32, tq_time >> 31
            )
            tq[lstr_mod.TQ_TLO] = np.where(
                tnever, lanes.NEVER32, tq_time & lanes.MASK31
            )
            tq[lstr_mod.TQ_AUXH] = tq_auxh
            tq[lstr_mod.TQ_AUXL] = tq_auxl
            tq[lstr_mod.TQ_SIZE] = tq_size
            stream0.v[lstr_mod.TV_LOCAL_SEQ] = self._local_seq0[self._el_np]
            stream0 = stream0._replace(q=tq)
        elif p.stream_present:
            stream0 = lstr_mod.init_stream_state(self._s_flows)
        else:
            stream0 = ()

        i32 = np.int32

        def full(shape=(), fill=0, dtype=i32):
            # every leaf an array of its own: buffers placed from one host
            # array may alias (XLA:CPU places without a copy)
            return np.full(shape, fill, dtype=dtype)

        def lane(fill=0, dtype=i32):
            return full(n, fill, dtype)

        ext = p.external_any
        # bucket state: next_refill starts one interval in (grid-aligned),
        # last_depart at 0 — as pairs (hi, lo); CoDel first_above starts at
        # the UNSET sentinel (the int64 law's time-0 marker)
        host = lanes.LaneState(
            q_thi=q_thi,
            q_tlo=q_tlo,
            q_auxh=q_auxh,
            q_auxl=q_auxl,
            q_size=q_size,
            **{"q_" + w: full((n, c)) if w in p.pay_words else ()
               for w in lanes.PAY_WORDS},
            stream=stream0,
            send_seq=lane(),
            local_seq=self._local_seq0.astype(i32),
            app_draws=lane(),
            up_tokens=self._up_params[:, 1].astype(i32),
            up_nr_hi=lane(),
            up_nr_lo=lane(self._interval),
            up_ld_hi=lane(),
            up_ld_lo=lane(),
            dn_tokens=self._dn_params[:, 1].astype(i32),
            dn_nr_hi=lane(),
            dn_nr_lo=lane(self._interval),
            dn_ld_hi=lane(),
            dn_ld_lo=lane(),
            cd_fat_hi=lane(lanes.CD_UNSET),
            cd_fat_lo=lane(),
            cd_dnext_hi=lane(),
            cd_dnext_lo=lane(),
            cd_drop_count=lane(),
            cd_dropping=lane(dtype=bool),
            m_sent=lane(),
            m_peer_offset=lane(),
            n_delivered=lane(),
            n_loss=lane(),
            n_codel=lane(),
            n_queue=lane(),
            recv_bytes=lane(),
            n_sends=lane(),
            n_hops=lane(),
            log=full((max(p.log_capacity, 1), 6), dtype=np.int64),
            log_count=full(),
            log_lost=full(),
            rounds=full(),
            iters=full(),
            codel_lookup_pops=full(),
            now_we_hi=full(),
            now_we_lo=full(),
            min_used_lat=full(fill=lanes.NEVER32),
            egress=(
                full((p.egress_capacity, 6), dtype=np.int64) if ext else ()
            ),
            egress_count=full() if ext else (),
            egress_lost=full() if ext else (),
            egress_min_hi=full(fill=lanes.NEVER32) if ext else (),
            egress_min_lo=full(fill=lanes.NEVER32) if ext else (),
            nb_txb=lane() if p.netobs else (),
            nb_rxb=lane() if p.netobs else (),
            nb_thr=lane() if p.netobs else (),
            nb_shed=lane() if p.netobs else (),
            nb_hist=full(lanes.NB_HIST_BUCKETS) if p.netobs else (),
            nb_win=full() if p.netobs else (),
            fl_buf=(
                full((p.flow_capacity, ftr.FT_COLS)) if p.flowtrace else ()
            ),
            fl_count=full() if p.flowtrace else (),
            fl_lost=full() if p.flowtrace else (),
            **{
                f: full() if p.log_capacity or ext else ()
                for f in lanes._AP_SCALARS
            },
            peaks=() if p.all_passive else full(3),
            copop_wide_pops=full() if p.copop_inert else (),
            exchange_compact_iters=full() if p.sends_per_pop > 1 else (),
            exchange_slot_peak=full() if p.sends_per_pop > 1 else (),
            gossip=lanes.GossipState(
                seen=full((n, self._gossip_words)), sends=lane(),
                first=lane(), dups=lane(), last_hi=lane(), last_lo=lane(),
            ) if p.gossip_degree else (),
            gossip_age=(
                full(len(AGE_COUNTERS)) if p.gossip_degree else ()
            ),
            gossip_elided=full() if p.gossip_degree else (),
            loop_hist=() if p.all_passive else full(lanes.NB_HIST_BUCKETS),
            loop_acc=(
                () if p.all_passive else full(len(lanes.LoopAcc._fields))
            ),
        )
        # ONE transfer of the whole tree, straight onto its placement: no
        # eager device program, no whole copy on one chip before sharding
        return jax.device_put(host, shardings)

    # -- running -----------------------------------------------------------

    def run(
        self, mode: str = "device", precompile: bool = False, on_window=None,
        resume_state=None, resume_epoch: int = 0,
        disarm_stalls: bool = False,
    ) -> SimResult:
        """``mode='device'``: one fused while_loop on the accelerator;
        ``mode='step'``: one device call per round (debuggable, pausable —
        ``on_window(window_start, window_end, next_event_time)`` runs after
        every round, the run-control/heartbeat seam).
        ``precompile``: AOT-compile before starting the wall-clock timer so
        ``wall_seconds`` measures only the steady-state device program
        (with a fault schedule: the ONE program all its segments run).
        ``resume_state``/``resume_epoch``: continue from a checkpointed
        lane state (engine/checkpoint.py) — the lane pytree carries the
        whole simulation, so running it to stop_time reproduces the
        uninterrupted run's suffix exactly.  ``disarm_stalls`` skips
        injected ``backend_stall`` raises on the faulted path: the
        checkpoint-anchored failover resume must replay *through* the
        epoch that killed the first attempt.

        A run is one TURN of the engine's clock: it leaves one row (the
        phases' seconds, ``run`` their residual, ``FUSED_NOTES``) in
        ``self.clock.ring`` and in ``obs.clock.journal["fused"]``."""
        with self.clock.turn():
            self.clock.note("mode", int(mode != "device"))
            self.clock.note("segments", 1)
            return self._run(
                mode, precompile, on_window, resume_state, resume_epoch,
                disarm_stalls)

    def _run(
        self, mode: str, precompile: bool, on_window, resume_state,
        resume_epoch: int, disarm_stalls: bool,
    ) -> SimResult:
        """``run``'s body, inside the run's turn."""
        if resume_state is not None:
            if precompile:
                raise LaneCompatError(
                    "precompile is a bench affordance; it is "
                    "not supported together with checkpoint resume"
                )
            self._check_resume_log(resume_state)
        if self._fault_overlay is not None:
            return self._run_faulted(
                mode, on_window=on_window, resume_state=resume_state,
                resume_epoch=resume_epoch, disarm_stalls=disarm_stalls,
                precompile=precompile,
            )
        # with a mesh attached the state lives on its sharded placement and
        # the driver compiles under the mesh (parallel/mesh.py)
        state = self._start_state(resume_state, self._mesh)
        if mode == "device":
            # cache the program: repeat runs (bench best-of-N) must not
            # retrace/recompile
            run_fn = self._run_fn
            if run_fn is None:
                if self._mesh is not None:
                    from .. import parallel

                    run_fn = self._run_fn = parallel.make_sharded_run_fn(
                        self.params, self.tables, self._mesh
                    )
                else:
                    run_fn = self._run_fn = lanes.make_run_fn(
                        self.params, self.tables
                    )
                    if self.params.has_loss:
                        # only the loss draw reads the seed: as arguments
                        # (placed once), its words leave ONE compiled
                        # program, and one compile-cache entry, for every
                        # seed of this network
                        self._seed_args = tuple(
                            jnp.uint32(w)
                            for w in _rng._split_seed(self.params.seed)
                        )
            args = (state, *self._seed_args)
            if precompile and self._compiled is None:
                # AOT-compile so the timed run is the steady-state program
                self._compiled = run_fn.lower(*args).compile()
            if self._compiled is not None:
                run_fn = self._compiled
            t0 = wall_time.perf_counter()
            # the fused loop is one opaque device call: the call until it
            # returns, then the wait for its result (per-window spans
            # need the step driver — run-control/perf-logging select it)
            with self.clock.span("dispatch"):
                state = run_fn(*args)
            with self.clock.span("device_wait", name="device_free_run"):
                state = jax.block_until_ready(state)
            wall = wall_time.perf_counter() - t0
        else:
            if self._mesh is not None:
                from .. import parallel

                round_fn = parallel.make_sharded_round_fn(
                    self.params, self.tables, self._mesh
                )
            else:
                round_fn = lanes.make_round_fn(self.params, self.tables)
            t0 = wall_time.perf_counter()
            state = self._drive_steps(
                round_fn, state, on_window, self.params.stop_time)
            wall = wall_time.perf_counter() - t0
        with self._phase("collect"):
            result = self.collect(state, wall)
        if mode == "device" and self.obs is not None and self.obs.turns is not None:
            # the fused driver's whole run is ONE unforced dispatch: the
            # ledger's free-run baseline, with its actual free-run length
            # (the windows the dispatch covered — known at collect, no
            # extra transfer)
            self.obs.turns.turn(
                "free_run", 0, self.params.stop_time, windows=result.rounds
            )
        return result

    def run_row(self) -> Optional[dict]:
        """The last run's row of the clock, as a dict (``sim-stats.json``
        ``fused_run``: where that run's wall went — the phases' seconds,
        ``run`` their residual — and its notes); None before any run."""
        ring = self.clock.ring
        return ring[-1]._asdict() if ring else None

    def _start_state(self, resume_state, mesh):
        """The ``state_build`` phase: the state a run starts from, on its
        placement (sharded over ``mesh``, or single-device).

        The initial state is a pure function of the engine, so the first
        run builds it (``initial_state``) and KEEPS it on the device;
        every later run starts from the kept arrays — jax arrays are
        immutable and no program of this engine donates its argument, so
        nothing is built, copied or transferred.  The kept state is this
        method's own: ``attach_mesh`` drops it, a ``resume_state`` run
        neither reads nor writes it."""
        with self._phase("state_build"):
            self._state_reused = 0
            if mesh is not None:
                from .. import parallel
            if resume_state is not None:
                if mesh is None:
                    return resume_state
                return parallel.shard_state(resume_state, mesh)
            if self._kept is None:
                self._kept = self.initial_state(
                    None if mesh is None else parallel.state_shardings(mesh)
                )
            else:
                self._state_reused = 1
            return self._kept

    def _phase(self, phase: str):
        """The clock's span of a host-side phase of ``run``
        (``state_build``: ``_start_state``; ``collect``), which with
        ``dispatch`` and ``device_wait`` split a run's wall into build /
        device / collect, in every run."""
        return self.clock.span(phase)

    def _check_resume_log(self, state) -> None:
        """A checkpointed lane state carries its device log; it resumes
        only on a program built for a log of that size."""
        rows = int(np.shape(state.log)[0])
        if rows != max(self.params.log_capacity, 1):
            raise LaneCompatError(
                f"the checkpoint's device event log has {rows} rows, this "
                f"run's {max(self.params.log_capacity, 1)} (1 = log off): "
                "resume with the event_log / --event-log choice the "
                "checkpoint was written under"
            )

    def checkpoint_payload(self):
        """The live lane state as a host-side (numpy) pytree — the whole
        simulation (queues, clocks, RNG counters, flows, device log) in
        one NamedTuple, directly picklable and directly feedable back
        into ``run(resume_state=...)``.  Only meaningful from the step
        driver's ``on_window`` seam, where the handle is post-round
        (see ``_drive_steps``)."""
        state = getattr(self, "_live_state", None)
        if state is None:
            raise RuntimeError(
                "no live lane state to checkpoint (the step driver has"
                " not completed a round yet)"
            )
        return jax.device_get(state)

    def _drive_steps(
        self, round_fn, state: lanes.LaneState, on_window, stop: int,
        first_cause: str = "snapshot",
    ) -> lanes.LaneState:
        """The step driver's round loop (one device call per round) up to
        ``stop`` — shared by the plain run and every fault-epoch
        segment.  Each round is timed under the stall watchdog when
        ``faults.watchdog_timeout`` is configured.

        Ledger causes (obs/turns.py): the step driver exists exactly so
        run-control can pause at every boundary, so its window-advancing
        dispatches record as ``snapshot`` turns — except the first
        dispatch of a fault-epoch segment, which ``_run_faulted`` passes
        in as ``fault_swap``."""
        from ..faults.watchdog import RoundWatchdog

        wd = (
            RoundWatchdog(self._watchdog_timeout)
            if self._watchdog_timeout is not None
            else None
        )
        obs = self.obs
        clock = self.clock
        turns = obs.turns if obs is not None else None
        turn_cause = first_cause
        active = None
        while True:
            self._live_state = state
            if on_window is not None or self.perf_log is not None or obs is not None:
                # queue rows are sorted: column 0 is each lane's min
                lane_next = np.asarray(
                    lanes.t_join(state.q_thi[:, 0], state.q_tlo[:, 0])
                )
                start = self._next_event_np(state)
                we_pred = min(start + self.current_runahead(), stop)
                active = int((lane_next < we_pred).sum())
                if self.params.stream_tiered:
                    tq = state.stream.q
                    tier_next = np.asarray(lanes.t_join(
                        tq[lstr_mod.TQ_THI, :, 0],
                        tq[lstr_mod.TQ_TLO, :, 0],
                    ))
                    active += int((tier_next < we_pred).sum())
            with clock.span("dispatch") as call:
                state, done = round_fn(state)
            with clock.span("device_wait", active, name="device_round") as wait:
                done = bool(done)  # forces the device sync the timing needs
            # refresh the live-state handle POST-round: netobs_lines and
            # checkpoint capture both read it at on_window time, when the
            # obs accumulators already reflect this round — a stale
            # pre-round handle would desynchronize a checkpoint's lane
            # state from its obs state (one window double-counted on
            # resume)
            self._live_state = state
            if wd is not None:
                wd.observe(wait.t0 + wait.dur - call.t0)
            if obs is not None:
                m = obs.metrics
                m.count("device_turns")
                m.observe("window_active_hosts", active)
            if done:
                break
            if on_window is not None or self.perf_log is not None or obs is not None:
                window_end = int(
                    (int(state.now_we_hi) << 31) | int(state.now_we_lo)
                )
                next_ev = self._next_event_np(state)
                if turns is not None:
                    turns.turn(turn_cause, start, window_end)
                    turn_cause = "snapshot"
                if obs is not None:
                    obs.metrics.count("windows")
                    obs.metrics.observe("window_span_ns", window_end - start)
                if self.perf_log is not None:
                    self.perf_log.window_agg(
                        active, start, window_end,
                        min(next_ev, stop),
                    )
                if on_window is not None:
                    on_window(start, window_end, next_ev)
        return state

    # -- fault-epoch segmentation ------------------------------------------

    def _segment_tables(self, snap) -> lanes.LaneTables:
        """The tables of a fault epoch: the [G, G] latency/threshold
        tables plus their per-flow and per-gossip-peer compactions
        (``_path_tables``) laid over the engine's."""
        return self.tables._replace(
            **self._path_tables(snap.latency_ns, snap.loss_threshold))

    def _epoch_leaves(self, plan) -> dict:
        """``{snapshot.at: path leaves}`` for the snapshots of ``plan``,
        on the device: built once an engine (every epoch's host words,
        ``_path_words``, placed by ONE ``device_put``) and kept until the
        overlay recompiles its snapshots (a console fault).  ``None``
        keys the base tables' own leaves."""
        version, placed = self._fault_leaves
        if version != self._fault_overlay.version:
            placed = {None: {f: getattr(self.tables, f)
                             for f in self._path_fields}}
        snaps = {snap.at: snap for _s, _e, snap in plan
                 if snap is not None and snap.at not in placed}
        if snaps:
            placed.update(jax.device_put({
                at: self._path_words(snap.latency_ns, snap.loss_threshold)
                for at, snap in snaps.items()
            }))
        self._fault_leaves = (self._fault_overlay.version, placed)
        return placed

    def _run_faulted(
        self, mode: str, on_window=None, resume_state=None,
        resume_epoch: int = 0, disarm_stalls: bool = False,
        precompile: bool = False,
    ) -> SimResult:
        """Run the simulation segmented at fault epochs: each segment is
        an ordinary (fused or step-wise) run whose stop time is the next
        epoch, against that epoch's tables.  Windows therefore never
        straddle a fault — the identical clamp law the CPU engine applies
        — and the lane state (queues, buckets, RNG counters, flows)
        carries across segments untouched.

        Every segment, of every repeat, runs ONE compiled program
        (``lanes.make_run_fn(..., epochs=True)``; the step driver's is
        ``make_round_fn``'s): the epoch's path leaves, the segment's stop
        bound and the seed's words are its ARGUMENTS, the leaves placed
        on the device once an engine (``_epoch_leaves``).  The host's
        work between one segment's wait and the next segment's call is
        the ``fault_swap`` span.

        Resume (engine/checkpoint.py): segments whose end lies at or
        before ``resume_epoch`` already happened inside ``resume_state``
        and are skipped; the first live segment continues from the
        resumed state mid-segment.  Its first ledger row records as
        ``snapshot`` — the segment's ``fault_swap`` row predates the
        checkpoint and lives in the restored ledger."""
        from ..faults.watchdog import BackendStallError

        ov = self._fault_overlay
        stop = self.params.stop_time
        # segment_plan owns the boundary law (and the padded no-op rows
        # the sweep path batches over — _fault_pad lets the padded-parity
        # test drive them through this serial loop too)
        plan = ov.segment_plan(stop, pad_to=getattr(self, "_fault_pad", 0))
        resumed = resume_state is not None
        live = [seg for seg in plan
                if not (resumed and seg[1] <= resume_epoch)]
        # segments run single-device programs, mesh or none
        state = self._start_state(resume_state, None)
        fn = self._fault_fns.get(mode)
        if fn is None:
            make = lanes.make_run_fn if mode == "device" else lanes.make_round_fn
            fn = self._fault_fns[mode] = make(
                self.params, self.tables, epochs=True)
        leaves = self._epoch_leaves(plan)
        seed = tuple(
            np.uint32(w) for w in _rng._split_seed(self.params.seed))
        segments = sum(1 for seg in plan if seg[0] < seg[1])
        self.clock.note("segments", segments)
        self._fault_plane = {
            # epochs inside the horizon, the segments they cut it into,
            # the compiled programs this driver holds for them (one a
            # mode it has run), and the bytes of path tables they read
            # (each epoch's set and the base's, as placed on the device)
            "fault_epochs": segments - 1,
            "fault_segments": segments,
            "fault_programs": len(self._fault_fns),
            "fault_table_bytes": sum(
                int(a.nbytes)
                for at in dict.fromkeys(
                    [None] + [snap.at for *_, snap in plan[1:]])
                for a in leaves[at].values()),
        }

        def segment_args(seg_start, seg_end, snap):
            """What the program takes after the state for one segment (a
            stall scheduled at its start raises here)."""
            if (
                0 < seg_start < seg_end
                and not disarm_stalls
                and ov.stall_at(seg_start)
            ):
                raise BackendStallError(
                    f"injected backend stall at {seg_start} ns "
                    "(fault schedule backend_stall event)"
                )
            return (
                leaves[None if snap is None else snap.at],
                np.int32(seg_end >> 31), np.int32(seg_end & lanes.MASK31),
                *seed,
            )

        # (a resume at the stop time has no segment left to run)
        args = segment_args(*live[0]) if live else None
        if mode == "device" and live:
            if precompile and self._fault_compiled is None:
                # AOT-compile so the timed run is the steady-state program
                self._fault_compiled = fn.lower(state, *args).compile()
            if self._fault_compiled is not None:
                fn = self._fault_compiled
        t0 = wall_time.perf_counter()
        turns = self.obs.turns if self.obs is not None else None
        seg_rounds = int(np.asarray(state.rounds)) if resumed else 0
        for i, (seg_start, seg_end, _snap) in enumerate(live):
            swap_cause = (
                "snapshot"
                if seg_start == 0 or (resumed and i == 0)
                else "fault_swap"
            )
            if mode == "device":
                with self.clock.span("dispatch"):
                    state = fn(state, *args)
                with self.clock.span("device_wait", name="device_free_run"):
                    state = jax.block_until_ready(state)
            else:
                seg_args = args
                state = self._drive_steps(
                    lambda s: fn(s, *seg_args), state, on_window, seg_end,
                    first_cause=swap_cause,
                )
            last = i + 1 == len(live)
            with (contextlib.nullcontext() if last
                  else self.clock.span(FAULT_PHASE)):
                if mode == "device" and turns is not None:
                    # one fused dispatch per epoch segment; the rounds
                    # delta is its measured free-run length (a readback
                    # only the ledger asks for)
                    r = int(state.rounds)
                    turns.turn(
                        "free_run" if swap_cause == "snapshot"
                        else "fault_swap",
                        seg_start, seg_end, windows=r - seg_rounds,
                    )
                    seg_rounds = r
                if not last:
                    args = segment_args(*live[i + 1])
        wall = wall_time.perf_counter() - t0
        with self._phase("collect"):
            return self.collect(state, wall)

    def _write_pcaps(self, event_rows, pcap_rows) -> None:
        """Reconstruct per-host capture files from the device log:
        outbound = PCAP_TX records at bucket-departure time, inbound =
        DELIVERED records at delivery time — the same two capture points
        as the CPU backend (cpu_engine.send_packet / deliver), so the
        files diff byte-identical across backends."""
        from pathlib import Path as _Path

        from ..core import time as _stime
        from ..utils.pcap import PcapWriter

        # one sort per array, then per-host SLICES via searchsorted —
        # not a full-array mask per host (O(hosts x rows) otherwise)
        if pcap_rows.size:
            out_sorted = pcap_rows[np.argsort(pcap_rows[:, 1], kind="stable")]
            out_keys = out_sorted[:, 1]
        else:
            out_sorted = out_keys = np.zeros((0,), dtype=np.int64)
        delivered = (
            event_rows[event_rows[:, 5] == lanes.DELIVERED]
            if event_rows.size else event_rows
        )
        if delivered.size:
            in_sorted = delivered[np.argsort(delivered[:, 2], kind="stable")]
            in_keys = in_sorted[:, 2]
        else:
            in_sorted = in_keys = np.zeros((0,), dtype=np.int64)
        for hid, hopt in enumerate(self.cfg.hosts):
            if not hopt.pcap_enabled or self._external[hid]:
                # external (hybrid) hosts' pcap files are written by the
                # HOST side, which knows the payload bytes — rewriting
                # them here would clobber the richer capture
                continue
            # both backends write records sorted by (time, direction,
            # src, dst, seq) — PcapWriter buffers and sorts at close, so
            # the files are byte-identical even when bucket backlog makes
            # departure stamps non-monotone in processing order
            recs = []
            if out_keys.size:
                lo, hi = np.searchsorted(out_keys, [hid, hid + 1])
                for t, src, dst, seq, size, _o in out_sorted[lo:hi]:
                    recs.append((int(t), 1, int(src), int(dst), int(seq),
                                 int(size)))
            if in_keys.size:
                lo, hi = np.searchsorted(in_keys, [hid, hid + 1])
                for t, src, dst, seq, size, _o in in_sorted[lo:hi]:
                    recs.append((int(t), 0, int(src), int(dst), int(seq),
                                 int(size)))
            w = PcapWriter(
                _Path(self.cfg.general.data_directory)
                / "hosts" / hopt.hostname / "eth0.pcap",
                snaplen=hopt.pcap_capture_size,
            )
            for t, dirn, src, dst, seq, size in recs:
                w.capture(
                    _stime.sim_to_emu(t), self.ips.by_host[src],
                    self.ips.by_host[dst], size, None,
                    key=(dirn, src, dst, seq),
                )
            w.close()

    def _read_back(self, s: lanes.LaneState) -> lanes.LaneState:
        """The HOST copy of ``s`` that ``collect`` reads, fetched in ONE
        batched ``jax.device_get``: the per-lane counters, the scalars,
        the tier's counter block and the flow matrices, the netobs /
        flowtrace blocks when on.  Every other leaf is ``None`` — a read
        ``collect`` grows without listing its field here fails, it does
        not become one more blocking transfer — but the log, which stays
        on the device (``collect`` fetches its filled rows, when there
        are any)."""
        p = self.params
        fields = [
            "send_seq", "local_seq", "m_peer_offset", "n_delivered",
            "n_loss", "n_codel", "n_queue", "recv_bytes", "n_sends",
            "n_hops", "log_count", "log_lost", "rounds", "iters",
            "codel_lookup_pops",
        ]
        if not p.all_passive:
            fields.append("peaks")
        if not isinstance(s.loop_acc, tuple):
            fields += ["loop_hist", "loop_acc"]
        if p.copop_inert:
            fields.append("copop_wide_pops")
        if p.sends_per_pop > 1:
            fields += ["exchange_compact_iters", "exchange_slot_peak"]
        if p.gossip_degree:
            fields += ["gossip", "gossip_age", "gossip_elided"]
        if p.netobs:
            fields += ["nb_txb", "nb_rxb", "nb_thr", "nb_shed", "nb_hist",
                       "nb_win"]
        if p.flowtrace:
            fields += ["fl_buf", "fl_count", "fl_lost"]
        if not isinstance(s.ap_blocks, tuple):
            fields += lanes._AP_SCALARS
        want = {f: getattr(s, f) for f in fields}
        if p.stream_tiered:
            want["stream"] = s.stream._replace(q=None)
        elif p.stream_present:
            want["stream"] = s.stream
        blank = jax.tree.map(lambda leaf: None, s)
        return blank._replace(**jax.device_get(want), log=s.log)

    def collect(self, s: lanes.LaneState, wall: float) -> SimResult:
        if isinstance(s.q_thi, jax.Array):
            # where the run actually ran (device_info)
            self._placed_devices = s.q_thi.devices()
        # from here on every leaf read below is a host copy
        s = self._read_back(s)
        # int32 counter honesty: every per-lane counter is monotone, so a
        # wrap past 2**31 shows as a negative value — raise instead of
        # reporting garbage (2e9 events per lane is unreachable in any
        # realistic run)
        wrap_check = ["send_seq", "local_seq", "n_delivered", "n_sends",
                      "recv_bytes", "m_peer_offset"]
        if self.params.netobs:
            wrap_check += ["nb_txb", "nb_rxb", "nb_thr"]
        for fname in wrap_check:
            if int(getattr(s, fname).min(initial=0)) < 0:
                raise RuntimeError(
                    f"lane counter {fname} wrapped past 2**31; this run "
                    "exceeds the lane backend's int32 counter range"
                )
        # tiered stream backend: fold the [2S] tier's compact counters
        # into the lane totals (the tier owns stream endpoints' network
        # accounting)
        tv = s.stream.v if self.params.stream_tiered else None
        if tv is not None and int(tv[lstr_mod.TV_SEND_SEQ].min(initial=0)) < 0:
            raise RuntimeError(
                "tier counter send_seq wrapped past 2**31; this run "
                "exceeds the lane backend's int32 counter range"
            )

        def tier_sum(row: int) -> int:
            return int(tv[row].sum()) if tv is not None else 0

        n_tier_drops = tier_sum(lstr_mod.TV_N_QUEUE)
        n_queue_drops = int(s.n_queue.sum()) + n_tier_drops
        # the shapes this program was compiled at and, where the program
        # keeps them (lanes.LaneState.peaks: some lane's model is active),
        # how far the run filled them: a run that did not raise states its
        # headroom
        p = self.params
        shapes = {
            "queue_capacity": p.capacity,
            "cross_capacity": p.cross_cap,
            "pops_per_iter": p.pops_per_iter,
            # the [N] send channel's width: datagrams one pop may send
            "sends_per_pop": p.sends_per_pop,
            # opaque payload words a row of the [N] queues carries: 2
            # where stream events ride them, 1 for gossip's message id, 0
            "payload_words": p.payload_words,
        }
        queue_peak = cross_peak = n_cross = 0
        if not p.all_passive:
            queue_peak, cross_peak, n_cross = (int(x) for x in s.peaks)
            shapes.update(queue_peak=queue_peak, cross_peak=cross_peak)
        if n_queue_drops and self.strict_capacity:
            # name the block that overflowed, each with the option that
            # cures it: the cross block sheds before the merge sees the
            # event (lanes._merge_append's lost_pre), so no queue width
            # saves it
            tail_why = (
                f"off the tail of a lane QUEUE (it holds {p.capacity}) or "
                f"by the CROSS block (it holds {p.cross_cap}; a program of "
                "passive lanes keeps one count for both); raise "
                "experimental.tpu_lane_queue_capacity, and "
                "experimental.tpu_cross_capacity where it is set below it"
            ) if p.all_passive else (
                "off the tail of a lane QUEUE (one lane's merged row held "
                f"{queue_peak} events, the queue holds {p.capacity}); raise "
                "experimental.tpu_lane_queue_capacity"
            )
            causes = [
                (n_cross, "by the CROSS block (the exchange offered one "
                 f"lane {cross_peak} events in one iteration, the block "
                 f"holds {p.cross_cap}); raise "
                 "experimental.tpu_cross_capacity"),
                (n_queue_drops - n_cross - n_tier_drops, tail_why),
                (n_tier_drops, "off the tail of a stream-tier QUEUE (it "
                 f"holds {p.stream_capacity}); raise "
                 "experimental.tpu_stream_queue_capacity"),
            ]
            raise RuntimeError(
                f"{n_queue_drops} events dropped on capacity overflow: "
                + "; ".join(f"{k} {why}" for k, why in causes if k)
                + " (results would silently diverge from the cpu backend)"
            )
        log_count = int(s.log_count)
        log_lost = int(s.log_lost)
        path_rows, path_gathers = lanes.path_sends(p, self.tables)
        gather_tables, gather_elems = lanes.path_gather_load(p, self.tables)
        self.lane_plane = {
            **shapes,
            "lanes": self.params.n_lanes,
            "mesh_devices": (
                int(self._mesh.devices.size) if self._mesh is not None else 1
            ),
            "device_log_capacity": self.params.log_capacity,
            "device_log_records": log_count,
            # which law finds the exchange's segment bounds (static per
            # compiled program): 1 past lanes._ONEHOT_BUDGET
            "exchange_bounds_wide": int(lanes.exchange_bounds_wide(
                self.params.exchange_entries, self.params.n_lanes
            )),
            # 1 when the run started from the initial state an earlier run
            # of this engine built and kept on the device (_start_state)
            "state_reused": self._state_reused,
            # pops (of either tier; a run makes lane_iters x pops of each)
            # in which some lane took CoDel's dropping-branch table lookup
            # (lanes.codel_offer_arrays): 0 in a network with no drop
            # episode, whose program then never runs that gather
            "codel_lookup_pops": int(s.codel_lookup_pops),
            # the network this program was compiled for: nodes of the
            # [G, G] latency / loss tables, the lookahead window, the
            # longest routed path, whether the loss draw is compiled in,
            # and which co-pop law the stream lanes take (1 = wide:
            # every window ends before RTO_MIN)
            "graph_nodes": int(self.tables.lat.shape[0]),
            "window_ns": int(self.params.runahead),
            "max_path_latency_ns": self._max_path_latency_ns,
            "has_loss": int(self.params.has_loss),
            "stream_wide_pop": int(self.params.stream_wide_pop),
            # of a pop's F sends, how many read their path from per-peer
            # rows built at start-up and how many may gather it at run
            # time from a graph of more than one node (static per
            # compiled program)
            "static_path_sends": path_rows,
            "path_gather_sends": path_gathers,
            # what a gathered send reads: the [G, G] tables (1, or 3 with
            # the loss draw) and the elements an iteration gathers (pops x
            # lanes x (node_of[dst] + a word a table); 0 with no gather)
            "path_gather_tables": gather_tables,
            "path_gather_elems_per_iter": gather_elems,
        }
        if p.copop_inert:
            # pop slots (a run offers lane_iters x pops x lanes of them)
            # that only the window-inert co-pop rule consumed
            # (lanes.pop_mask): present where the program compiles it
            self.lane_plane["copop_wide_pops"] = int(s.copop_wide_pops)
        if p.sends_per_pop > 1:
            # a fan-out program's exchange (lanes._merge_append step 2):
            # the iterations (of lane_iters) whose sending slots fitted
            # ONE pass of the compacted exchange, the static budget of
            # slots a pass takes, and the most slots that sent in one
            # iteration (at or under the budget: every iteration was one
            # pass)
            self.lane_plane.update(
                exchange_compact_iters=int(s.exchange_compact_iters),
                exchange_slot_budget=p.exchange_slot_budget,
                exchange_slot_peak=int(s.exchange_slot_peak),
            )
        # a faulted run's epochs, segments, programs and table bytes
        # (_run_faulted; nothing without a schedule)
        self.lane_plane.update(self._fault_plane)
        loop_gauges, loop_hist = self._loop_ledger(s)
        self.lane_plane.update(loop_gauges)
        if self.obs is not None:
            for key, val in self.lane_plane.items():
                self.obs.metrics.gauge(key, val)
        if loop_hist:
            # the histogram whole: sim-stats.json's, no obs gauge
            self.lane_plane["loop_hist"] = loop_hist
        # the run's row (run(): one turn of the clock a run)
        note = self.clock.note
        note("rounds", int(s.rounds))
        note("lane_iters", int(s.iters))
        note("state_reused", self._state_reused)
        note("log_capacity", p.log_capacity)
        note("lanes", p.n_lanes)
        note("pops_per_iter", p.pops_per_iter)
        if p.stream_tiered:
            note("stream_pops", p.stream_pops)
            note("flows", self._s_flows)
        for key, val in loop_gauges.items():
            note(key, val)
        if log_lost:
            # surface the overflow as a metrics-registry counter BEFORE
            # raising: failed runs still flush partial obs artifacts
            # (engine/sim.py's finally), so the loss is machine-visible
            # in METRICS_*.json instead of only a crash string
            if self.obs is not None:
                self.obs.metrics.count("device_log_lost", log_lost)
                self.obs.metrics.gauge("device_log_overflowed", True)
            raise RuntimeError(
                f"device event log overflowed: the run produced {log_count} "
                f"records, the log holds {self.params.log_capacity} "
                f"({log_lost} records lost); run without the device log "
                "(Simulation(cfg, event_log=False); on the command line, "
                "drop --event-log) or build the engine with a larger "
                "log_capacity"
            )
        # the log's filled rows: the one further transfer, and only of a
        # run that kept records
        filled = min(log_count, self.params.log_capacity)
        rows = (
            np.asarray(s.log[:filled]) if filled
            else np.zeros((0, 6), dtype=np.int64)
        )
        if self.params.pcap_any:
            pcap_rows = rows[rows[:, 5] == lanes.PCAP_TX] if rows.size else rows
            rows = rows[rows[:, 5] != lanes.PCAP_TX] if rows.size else rows
            self._write_pcaps(rows, pcap_rows)
        event_log = [
            LogRecord(int(t), int(src), int(dst), int(seq), int(size), int(out))
            for t, src, dst, seq, size, out in rows
        ]
        model = self._model_np
        counters: dict[str, int] = {}

        def add(key: str, val: int) -> None:
            if val:
                counters[key] = counters.get(key, 0) + int(val)

        tgen_mask = np.isin(model, [lanes.M_TGEN_MESH, lanes.M_TGEN_CLIENT, lanes.M_TGEN_SERVER])
        add("tgen_recv_bytes", int(s.recv_bytes[tgen_mask].sum()))
        add("phold_hops", int(s.n_hops[model == lanes.M_PHOLD].sum()))
        if p.gossip_degree:
            g = s.gossip
            add("gossip_sends", int(g.sends.sum()))
            add("gossip_first", int(g.first.sum()))
            add("gossip_duplicates", int(g.dups.sum()))
            # the propagation histogram: first deliveries by age
            for key, val in zip(AGE_COUNTERS, s.gossip_age.tolist()):
                add(key, val)
            # the time the last node first saw a message: how long the
            # run's floods took to cross the mesh (0: nothing delivered)
            last = (g.last_hi.astype(np.int64) << 31) | g.last_lo
            # gossip_elided: the duplicates (of gossip_duplicates) counted
            # at their PACKET pop, whose DELIVERY row was never queued
            # (lanes.gossip_elides); the oracle has no such number
            gauges = dict(
                gossip_degree=p.gossip_degree,
                gossip_last_first_ns=int(last.max(initial=0)),
                gossip_elided=int(s.gossip_elided),
            )
            self.lane_plane.update(gauges)
            if self.obs is not None:
                for key, val in gauges.items():
                    self.obs.metrics.gauge(key, val)
        add("lane_iters", int(s.iters))
        add("lane_delivered",
            int(s.n_delivered.sum()) + tier_sum(lstr_mod.TV_N_DEL))
        add("lane_drop_loss",
            int(s.n_loss.sum()) + tier_sum(lstr_mod.TV_N_LOSS))
        add("lane_drop_codel",
            int(s.n_codel.sum()) + tier_sum(lstr_mod.TV_N_CODEL))
        add("lane_drop_queue", n_queue_drops)
        add("lane_sends",
            int(s.n_sends.sum()) + tier_sum(lstr_mod.TV_N_SENDS))

        if self.params.stream_present:
            # compacted flow matrices: every cl row is a client endpoint,
            # every sv row its server endpoint
            flows = (
                s.stream.flows if self.params.stream_tiered else s.stream
            )
            cl_m, sv_m = flows.cl, flows.sv
            done = cl_m[:, lstr_mod.C_COMPLETED] != 0
            if done.any():
                # tx/retransmit totals count at completion, like the CPU
                # _track — including zero-valued keys (counter-set parity)
                counters["stream_complete"] = int(done.sum())
                counters["stream_tx_segs"] = int(
                    cl_m[done, lstr_mod.C_TX_SEGS].sum()
                )
                counters["stream_retransmits"] = int(
                    cl_m[done, lstr_mod.C_RETRANS].sum()
                )
            add("stream_rx_bytes", int(sv_m[:, lstr_mod.C_RX_BYTES].sum()))
            add("stream_rx_segs", int(sv_m[:, lstr_mod.C_RX_SEGS].sum()))
            add(
                "stream_flows_done",
                int((sv_m[:, lstr_mod.C_COMPLETED] != 0).sum()),
            )

        # what the network cost this run, beside its shape
        for key in ("lane_drop_loss", "stream_retransmits"):
            self.lane_plane[key] = counters.get(key, 0)
            if self.obs is not None:
                self.obs.metrics.gauge(key, self.lane_plane[key])

        if self.params.netobs:
            self._netobs_data = self._netobs_collect(s, tv)
        if self.params.flowtrace:
            self._flowtrace_data = self._flowtrace_collect(s)
        if not isinstance(s.ap_blocks, tuple):
            # beside the counters, not among them: the oracle has none
            self.append_stats = {
                "append_" + f.removeprefix("ap_"): int(getattr(s, f))
                for f in lanes._AP_SCALARS
            }
            if self.obs is not None:
                for key, val in self.append_stats.items():
                    self.obs.metrics.gauge(key, val)

        return SimResult(
            sim_time_ns=self.params.stop_time,
            wall_seconds=wall,
            rounds=int(s.rounds),
            event_log=event_log,
            counters=counters,
            per_host_counters=[],
        )

    def _loop_ledger(self, s: lanes.LaneState) -> tuple[dict, dict]:
        """The loop ledger as ``collect`` reports it, from the host copy
        of ``s``: the ``loop_*`` gauges (``LOOP_GAUGES``; ``lane_plane``,
        the obs gauges, the run's row) and the histogram in
        ``NETOBS_*.json``'s ``window_hist`` form (``lane_plane
        ["loop_hist"]``: windows by the iterations they took).  Both
        empty where the program carries no ledger
        (``lanes.LaneState.loop_hist``).  The trailing window, which no
        later one folded, is folded here — as netobs folds its own."""
        if isinstance(s.loop_acc, tuple):
            return {}, {}
        p = self.params
        acc = lanes.LoopAcc(*(int(v) for v in s.loop_acc))
        by_round = np.asarray(s.loop_hist, dtype=np.int64).tolist()
        if acc.round_iters:
            by_round[netobs_mod.hist_bucket(acc.round_iters)] += 1
        round_max = max(acc.round_max, acc.round_iters)
        pct = netobs_mod.hist_percentile
        gauges = {
            # live [N] pop slots, of lane_iters x pops_per_iter x lanes
            "loop_pop_slots": acc.pop_slots,
            # [N] lanes that popped at all, summed over the iterations
            "loop_active_lanes": acc.active_lanes,
            # iterations whose exchange handed no lane a row
            "loop_iters_no_send": acc.no_send,
            # passes of the exchange: one an iteration, but for a
            # fan-out program's denser iterations
            "loop_exchange_passes": (
                acc.exch_passes if p.sends_per_pop > 1 else int(s.iters)),
            # iterations a window took: the most, and two percentiles
            # (a bucket's upper edge, at most the exact maximum)
            "loop_round_iters_max": round_max,
            "loop_round_iters_p50": pct(by_round, 0.50, round_max),
            "loop_round_iters_p95": pct(by_round, 0.95, round_max),
        }
        if p.stream_tiered:
            # live TIER pop slots, of lane_iters x stream_pops x 2 flows
            gauges["loop_tier_pop_slots"] = acc.tier_pop_slots
        return gauges, {"scheme": "log2", "round_iters": by_round}

    # -- netobs telemetry plane (obs/netobs.py) ----------------------------

    def _netobs_collect(self, s: lanes.LaneState, tv) -> dict:
        """Fold the device-resident telemetry block into the canonical
        per-host array schema (obs.netobs).  Piggybacks the collect
        readback — no extra device sync beyond the arrays already
        fetched at end-of-run."""
        from ..obs import netobs as nom

        n = self.params.n_lanes

        def fold(lane_arr, tv_row=None):
            out = np.asarray(lane_arr).astype(np.int64).copy()
            if tv is not None and tv_row is not None:
                # tier rows are per endpoint; scatter-add back to lanes
                np.add.at(out, self._el_np, tv[tv_row].astype(np.int64))
            return out

        from . import lanes_stream as lstr

        arrays = {
            "sent": fold(s.n_sends, lstr.TV_N_SENDS),
            "delivered": fold(s.n_delivered, lstr.TV_N_DEL),
            "tx_bytes": fold(s.nb_txb, lstr.TV_NB_TXB),
            "rx_bytes": fold(s.nb_rxb, lstr.TV_NB_RXB),
            "drop_loss": fold(s.n_loss, lstr.TV_N_LOSS),
            "drop_codel": fold(s.n_codel, lstr.TV_N_CODEL),
            "drop_queue": fold(s.n_queue, lstr.TV_N_QUEUE)
            - np.asarray(s.nb_shed).astype(np.int64),
            "drop_cross_shed": fold(s.nb_shed),
            "throttled": fold(s.nb_thr, lstr.TV_NB_THR),
            "retransmits": np.zeros(n, dtype=np.int64),
            "retry_giveup": np.zeros(n, dtype=np.int64),
        }
        if self.params.stream_present:
            # retransmit attribution mirrors the CPU _track: counted at
            # the CLIENT lane, for completed flows only
            flows = (
                s.stream.flows if self.params.stream_tiered else s.stream
            )
            cl_m = np.asarray(flows.cl)
            done = cl_m[:, lstr.C_COMPLETED] != 0
            cl_lanes = np.asarray(self.params.stream_clients, dtype=np.int64)
            if cl_lanes.size:
                np.add.at(
                    arrays["retransmits"], cl_lanes,
                    np.where(done, cl_m[:, lstr.C_RETRANS], 0).astype(
                        np.int64
                    ),
                )
        hist = np.asarray(s.nb_hist).astype(np.int64).copy()
        # trailing window: its occupancy was never followed by a window
        # advance, so flush it here (host-side, same bucket law)
        tail = int(s.nb_win)
        if tail > 0:
            hist[nom.hist_bucket(tail)] += 1
        return {"arrays": arrays, "window_hist": hist, "log_lost": 0}

    def netobs_snapshot(self):
        """The device telemetry snapshot of the last collected run (None
        when netobs is off or no run has completed)."""
        return self._netobs_data

    def netobs_lines(self, host: Optional[str] = None) -> list[str]:
        """Run-control ``netstats`` answer: summarize the LIVE device
        counters (step driver — ``_live_state`` is refreshed per round;
        reading it here is a snapshot-epoch fetch, not a new per-window
        sync)."""
        from ..obs import netobs as nom

        if not self.params.netobs:
            return ["netobs is not enabled (set experimental.netobs)"]
        state = getattr(self, "_live_state", None)
        if state is None:
            return ["no live device state yet (step driver only)"]
        tv = (
            np.asarray(state.stream.v)
            if self.params.stream_tiered else None
        )
        snap = self._netobs_collect(state, tv)
        names = [h.hostname for h in self.cfg.hosts]
        return nom.snapshot_lines(snap["arrays"], snap["window_hist"],
                                  names, host)

    # -- flowtrace plane (obs/flowtrace.py) --------------------------------

    def _flowtrace_collect(self, s: lanes.LaneState) -> dict:
        """Decode the device flow ring into event tuples.  The ring never
        wraps, so the kept rows are the contiguous prefix; overflow only
        bumps ``fl_lost``.  Piggybacks the collect readback — no extra
        device sync."""
        kept = min(int(s.fl_count), self.params.flow_capacity)
        rows = np.asarray(s.fl_buf)[:kept]
        return {
            "raw": ftr.rows_to_events(rows),
            "ring_lost": int(s.fl_lost),
        }

    def flowtrace_snapshot(self):
        """Decoded flow events of the last collected run (None when
        flowtrace is off or no run has completed)."""
        return self._flowtrace_data

    def flowtrace_lines(self, host: Optional[str] = None) -> list[str]:
        """Run-control ``flows`` answer from the LIVE device ring (step
        driver; snapshot-epoch fetch like netobs_lines)."""
        if not self.params.flowtrace:
            return ["flowtrace is not enabled (set experimental.flowtrace)"]
        state = getattr(self, "_live_state", None)
        if state is None:
            return ["no live device state yet (step driver only)"]
        snap = self._flowtrace_collect(state)
        events, lost = ftr.canonical_events(
            snap["raw"], self.params.flow_capacity
        )
        names = [h.hostname for h in self.cfg.hosts]
        return ftr.snapshot_lines(
            events, lost + snap["ring_lost"], names, host=host
        )
