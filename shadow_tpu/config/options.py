"""Simulation configuration: YAML + programmatic, with typed units.

User-facing parity with the reference's three-layer config system
(src/main/core/configuration.rs): the same YAML document shape —

    general:    { stop_time, seed, parallelism, bootstrap_end_time, ... }
    network:    { graph: { type: gml|1_gbit_switch, file|inline }, ... }
    experimental: { runahead, use_dynamic_runahead, ... }
    host_option_defaults: { ... }
    hosts:
      <hostname>:
        network_node_id: 0
        processes: [ { path, args, start_time, ... } ]

— parsed into plain dataclasses.  CLI overrides merge on top of the YAML
values (the reference uses the `merge` crate for this; here
:func:`ConfigOptions.apply_overrides` takes dotted keys).

TPU-specific addition: ``experimental.network_backend`` selects ``cpu``
(host reference implementation) or ``tpu`` (batched JAX lane backend), the
analog of the reference's ``use_new_tcp``-style backend switches.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Optional

import yaml

from ..core import time as stime
from . import units


class ConfigError(ValueError):
    pass


# socket buffer defaults, single-sourced for the config dataclass, the shim
# shared-memory block, and the managed-process manager
SOCKET_SEND_BUFFER_DEFAULT = 131072
SOCKET_RECV_BUFFER_DEFAULT = 174760


@dataclasses.dataclass
class GeneralOptions:
    stop_time: int = 0  # ns; required > 0
    seed: int = 1
    parallelism: int = 0  # 0 = all cores
    bootstrap_end_time: int = 0  # ns; loss-free warm-up window (worker.rs:335)
    data_directory: str = "shadow.data"
    template_directory: Optional[str] = None
    log_level: str = "info"
    heartbeat_interval: Optional[int] = stime.NANOS_PER_SEC
    progress: bool = False
    model_unblocked_syscall_latency: bool = False


@dataclasses.dataclass
class GraphOptions:
    type: str = "1_gbit_switch"  # "gml" | "1_gbit_switch"
    file_path: Optional[str] = None
    inline: Optional[str] = None


@dataclasses.dataclass
class NetworkOptions:
    graph: GraphOptions = dataclasses.field(default_factory=GraphOptions)
    use_shortest_path: bool = True


#: fields of ExperimentalOptions that no code reads: parsed so that an
#: upstream Shadow YAML loads unchanged ("reserved" in
#: docs/configuration.md).  Every other field has a reader
#: (tests/test_config.py::test_every_experimental_option_has_a_reader).
REFERENCE_PARITY_FIELDS = ("use_new_tcp", "use_worker_spinning")


@dataclasses.dataclass
class ExperimentalOptions:
    # PDES window control
    runahead: Optional[int] = stime.NANOS_PER_MILLI  # lower bound, ns
    use_dynamic_runahead: bool = False
    # scheduling (cpu backend)
    scheduler: str = "thread-per-core"  # | "thread-per-host"
    use_cpu_pinning: bool = True
    use_worker_spinning: bool = True
    # transport knobs
    use_new_tcp: bool = False
    socket_send_buffer: int = SOCKET_SEND_BUFFER_DEFAULT  # bytes
    socket_recv_buffer: int = SOCKET_RECV_BUFFER_DEFAULT
    interface_qdisc: str = "fifo"  # | "round-robin"
    # strace-style logging
    strace_logging_mode: str = "off"  # off | standard | deterministic
    # managed-process interposition backstops (the reference's seccomp
    # SIGSYS trap, shim_seccomp.c, and vDSO patching, patch_vdso.c):
    # catch raw syscalls and vDSO-direct time reads that bypass LD_PRELOAD
    use_seccomp: bool = True
    use_vdso_patching: bool = True
    # fork features: interactive run-control console (pause/step/restart at
    # window boundaries) and [window-agg]/[host-exec-agg] telemetry
    run_control: bool = False
    perf_logging: bool = False
    # observability (shadow_tpu/obs/, docs/observability.md): per-phase
    # wall metrics -> METRICS_*.json, span tracing -> Chrome-trace JSON,
    # optional JSONL event stream.  All default off; event ordering is
    # bit-identical with everything on (docs/determinism.md).  (The
    # drivers' host phases are on the profiler's clock in every run:
    # obs/clock.py)
    obs_metrics: bool = False
    obs_trace: bool = False
    obs_jsonl: bool = False
    obs_dir: Optional[str] = None  # None = general.data_directory
    # device-turn ledger (obs/turns.py): causal per-turn accounting
    # (cause classification + conservation law) and fusable-run-length
    # measurement, exported as TURNS_<backend>-seed<N>.json.  Rows derive
    # from data the host side already holds per turn — zero new
    # host<->device transfers — and are bit-identical at any hybrid
    # worker count
    obs_turns: bool = False
    # simulated-network telemetry plane (obs/netobs.py): per-host
    # sent/delivered/bytes counters, drop-cause accounting, and the
    # burst-window histogram, exported as NETOBS_<backend>-seed<N>.json.
    # Device-side the counters live in the lane kernels (zero new
    # host<->device syncs; LaneParams.netobs compiles them away when
    # off); the CPU oracle accumulates the identical counters so the
    # parity suite can diff them per host
    netobs: bool = False
    # per-flow packet-lifecycle tracing (obs/flowtrace.py): lifecycle
    # events (send / tb-wait / queue-enter / drop+cause / retransmit /
    # delivery) for deterministically-sampled flows, exported as
    # FLOWS_<backend>-seed<N>.json with a burst attribution report.
    # Device-side the events land in a bounded ring inside the lane
    # kernels (drained only at snapshot epochs / end-of-run — zero new
    # host<->device transfers; LaneParams.flowtrace compiles the plane
    # away when off); the CPU oracle emits the identical stream so the
    # parity suite can diff them event-for-event
    flowtrace: bool = False
    flowtrace_capacity: int = 65536  # device ring rows; never wraps
    flowtrace_sample: float = 1.0  # fraction of flows traced (seeded hash)
    # --- TPU-native extensions -------------------------------------------
    network_backend: str = "cpu"  # "cpu" | "tpu"
    tpu_lane_queue_capacity: int = 64  # per-host in-flight packet slots
    tpu_events_per_round: int = 8  # max pops per lane per inner step
    # cross-lane receive block width per iteration (0 = queue capacity);
    # narrower is faster when per-iteration fan-in is bounded — overflow
    # is counted and strict mode raises, exactly like queue overflow
    tpu_cross_capacity: int = 0
    # multi-chip sharded lane plane (shadow_tpu/parallel/,
    # docs/multichip.md): shard the per-host lane state over up to this
    # many devices on a 1-D ``Mesh(("hosts",))``.  0 = off
    # (single-device); the actual count is NEGOTIATED down to the largest
    # value that divides the host count and does not exceed the available
    # devices (transparent fallback — never an error).  Results are
    # bit-identical at any mesh shape.
    mesh_devices: int = 0
    # TIERED stream backend (one-to-one stream configs on a pure-lane,
    # untraced run — TpuEngine decides from the config): stream endpoints
    # run on a dedicated [2S]-row tier with their own queue block and pop
    # rate, keeping the [N]-wide machinery stream-free (docs/tpu-backend.md)
    tpu_stream_events_per_round: int = 8  # tier pops per iteration (K_s)
    tpu_stream_queue_capacity: int = 64  # tier queue width (C2)
    # HYBRID backend (backend/hybrid.py): syscall-servicing worker
    # processes for the managed hosts while their packets ride the TPU
    # lanes.  1 = serial in-process servicing; 0 = one worker per core;
    # N > 1 = exactly N spawned workers.  Results are bit-identical at
    # any worker count (tests/test_hybrid_mp.py).
    hybrid_workers: int = 1
    # injection block rows per device turn (B): staged managed-host sends
    # coalesce into blocks of this size for the host->device hop
    tpu_inject_batch: int = 512
    # k-window free-run fusion on the hybrid path (docs/hybrid.md
    # "k-window fusion law"): one device dispatch may cover up to this
    # many consecutive host-participating windows, with the covered
    # syscall rounds serviced post-hoc under the arrival-frontier
    # validation law (rollback to the validated prefix on a late staged
    # injection).  It is a depth CAP on the one turn law: at 1 every
    # dispatch covers one participating window (no rollback, and no eager
    # dispatch: there is nothing to overlap); at >= 2 the next turn is
    # also dispatched eagerly while syscall servicing runs, and adopted
    # only when its inputs match the real ones bit-exact (the
    # UNCONDITIONAL version is unsound, docs/hybrid.md).
    hybrid_fuse_k: int = 8
    # --- crash safety (engine/checkpoint.py, docs/robustness.md) ---------
    # write an on-disk checkpoint every N window-clamp boundaries
    # (0 = checkpointing off); pure-lane backends only (cpu, cpu_mp, tpu)
    checkpoint_every_windows: int = 0
    # checkpoint directory (None = <data_directory>/checkpoints)
    checkpoint_dir: Optional[str] = None
    # bounded retention: keep the newest N checkpoints of a run
    checkpoint_keep: int = 3
    # resume a run from this checkpoint file (the --resume CLI flag);
    # the resumed run is bit-identical to the uninterrupted one
    resume_from: Optional[str] = None
    # worker supervision (engine/supervisor.py): reply deadline for
    # multiprocess workers (wall seconds) — a worker that misses it is
    # diagnosed dead/hung instead of blocking the parent forever
    worker_heartbeat_s: float = 30.0
    # respawn+replay budget: consecutive failures of one worker before
    # escalating to the serial engine (0 = supervision off: a dead
    # worker raises WorkerDiedError)
    worker_restart_max: int = 2
    # hybrid device path: fused-dispatch retries (from the pre-turn
    # device checkpoint, exponential backoff) before the failure
    # escalates to the watchdog/failover boundary
    dispatch_retry_max: int = 2
    # --- fleet sweeps (shadow_tpu/sweep/, docs/sweep.md) -----------------
    # batch S scenario instances into ONE vmapped lane kernel.  With no
    # sweep_spec, sweep_size > 1 runs the seed grid general.seed ..
    # general.seed + sweep_size - 1; 0/1 = sweeps off (serial run)
    sweep_size: int = 0
    # path to a sweep-spec YAML (seeds / faults / overrides axes —
    # docs/sweep.md schema); overrides sweep_size when set
    sweep_spec: Optional[str] = None


@dataclasses.dataclass
class FaultOptions:
    """The ``faults:`` config section (shadow_tpu/faults/): a declarative
    fault schedule plus the graceful-degradation knobs.

    ``failover=None`` means auto: TPU->CPU failover is armed exactly when
    a fault schedule exists.  Set it explicitly to arm failover for real
    backend errors without scheduling any faults (``faults: {failover:
    true}``) or to make injected failures fatal (``failover: false``).
    """

    failover: Optional[bool] = None
    watchdog_timeout: Optional[float] = None  # wall seconds, tpu step driver
    events: list = dataclasses.field(default_factory=list)  # raw event dicts

    @property
    def failover_enabled(self) -> bool:
        if self.failover is not None:
            return bool(self.failover)
        return bool(self.events)

    def schedule(self):
        """Parse ``events`` into a validated FaultSchedule (raises
        shadow_tpu.faults.FaultConfigError on malformed entries)."""
        from ..faults.schedule import FaultSchedule

        return FaultSchedule.parse(self.events)


@dataclasses.dataclass
class ProcessOptions:
    path: str = ""
    args: list[str] = dataclasses.field(default_factory=list)
    environment: dict[str, str] = dataclasses.field(default_factory=dict)
    start_time: int = 0  # ns
    shutdown_time: Optional[int] = None
    shutdown_signal: str = "SIGTERM"
    expected_final_state: Any = "exited"  # {"exited": code}|"running"|{"signaled": sig}


@dataclasses.dataclass
class HostOptions:
    hostname: str = ""
    network_node_id: int = 0
    ip_addr: Optional[str] = None
    bandwidth_down: Optional[int] = None  # bits/sec; falls back to graph node
    bandwidth_up: Optional[int] = None
    processes: list[ProcessOptions] = dataclasses.field(default_factory=list)
    log_level: Optional[str] = None
    pcap_enabled: bool = False
    pcap_capture_size: int = 65535
    # TCP congestion-control algorithm for this host's flows (the
    # reference's pluggable tcp_cong.c interface: tcp_cong_reno.c and the
    # CUBIC analog here); applies to both the byte-stream stack and the
    # lane/ltcp stream tier (data-sender side)
    congestion: str = "reno"  # "reno" | "cubic"
    count: int = 1  # convenience host multiplier (hostname gets a suffix)


@dataclasses.dataclass
class ConfigOptions:
    general: GeneralOptions = dataclasses.field(default_factory=GeneralOptions)
    network: NetworkOptions = dataclasses.field(default_factory=NetworkOptions)
    experimental: ExperimentalOptions = dataclasses.field(
        default_factory=ExperimentalOptions
    )
    faults: FaultOptions = dataclasses.field(default_factory=FaultOptions)
    hosts: list[HostOptions] = dataclasses.field(default_factory=list)
    # columnar table spec (config/columnar.py ColumnarSpec), set by the
    # columnar factories only — never parsed from YAML.  When present,
    # TpuEngine adopts the per-lane tables/initial events wholesale and
    # skips its per-host model walk (the 100k-host startup path).
    columnar: Optional[Any] = None

    # -- parsing ----------------------------------------------------------

    @classmethod
    def from_yaml_file(cls, path: str | Path) -> "ConfigOptions":
        with open(path) as f:
            return cls.from_dict(yaml.safe_load(f))

    @classmethod
    def from_yaml(cls, text: str) -> "ConfigOptions":
        return cls.from_dict(yaml.safe_load(text))

    @classmethod
    def from_dict(cls, doc: dict[str, Any]) -> "ConfigOptions":
        if not isinstance(doc, dict):
            raise ConfigError("config must be a mapping")
        unknown = set(doc) - {
            "general",
            "network",
            "experimental",
            "faults",
            "host_option_defaults",
            "hosts",
        }
        if unknown:
            raise ConfigError(f"unknown top-level config keys: {sorted(unknown)}")

        gen_doc = dict(doc.get("general", {}))
        general = GeneralOptions(
            stop_time=units.parse_time(_require(gen_doc, "stop_time", "general")),
            seed=int(gen_doc.pop("seed", 1)),
            parallelism=int(gen_doc.pop("parallelism", 0)),
            bootstrap_end_time=units.parse_time(gen_doc.pop("bootstrap_end_time", 0)),
            data_directory=str(gen_doc.pop("data_directory", "shadow.data")),
            template_directory=gen_doc.pop("template_directory", None),
            log_level=str(gen_doc.pop("log_level", "info")),
            heartbeat_interval=_opt_time(gen_doc.pop("heartbeat_interval", "1s")),
            progress=bool(gen_doc.pop("progress", False)),
            model_unblocked_syscall_latency=bool(
                gen_doc.pop("model_unblocked_syscall_latency", False)
            ),
        )
        gen_doc.pop("stop_time", None)
        if gen_doc:
            raise ConfigError(f"unknown general options: {sorted(gen_doc)}")

        net_doc = dict(doc.get("network", {}))
        graph_doc = dict(net_doc.pop("graph", {"type": "1_gbit_switch"}))
        gtype = graph_doc.pop("type", "gml")
        graph = GraphOptions(type=gtype)
        if gtype == "gml":
            sources = [k for k in ("file", "inline", "path") if k in graph_doc]
            if len(sources) > 1:
                raise ConfigError(
                    f"gml graph has conflicting sources: {sources}; give one"
                )
            if "file" in graph_doc:
                fd = graph_doc.pop("file")
                graph.file_path = fd["path"] if isinstance(fd, dict) else str(fd)
            elif "inline" in graph_doc:
                graph.inline = str(graph_doc.pop("inline"))
            elif "path" in graph_doc:
                graph.file_path = str(graph_doc.pop("path"))
            else:
                raise ConfigError("gml graph needs 'file' or 'inline'")
        elif gtype != "1_gbit_switch":
            raise ConfigError(f"unknown graph type {gtype!r}")
        if graph_doc:
            raise ConfigError(f"unknown network.graph options: {sorted(graph_doc)}")
        network = NetworkOptions(
            graph=graph,
            use_shortest_path=bool(net_doc.pop("use_shortest_path", True)),
        )
        if net_doc:
            raise ConfigError(f"unknown network options: {sorted(net_doc)}")

        exp_doc = dict(doc.get("experimental", {}))
        experimental = ExperimentalOptions()
        for f in dataclasses.fields(ExperimentalOptions):
            if f.name in exp_doc:
                v = exp_doc.pop(f.name)
                if f.name == "runahead":
                    v = _opt_time(v)
                elif f.name in ("socket_send_buffer", "socket_recv_buffer"):
                    v = units.parse_bytes(v)
                setattr(experimental, f.name, v)
        if exp_doc:
            raise ConfigError(f"unknown experimental options: {sorted(exp_doc)}")

        f_doc = dict(doc.get("faults", {}) or {})
        failover = f_doc.pop("failover", None)
        wd = f_doc.pop("watchdog_timeout", None)
        faults = FaultOptions(
            failover=None if failover is None else bool(failover),
            watchdog_timeout=None if wd is None else float(wd),
            events=list(f_doc.pop("events", []) or []),
        )
        if f_doc:
            raise ConfigError(f"unknown faults options: {sorted(f_doc)}")

        defaults = dict(doc.get("host_option_defaults", {}))
        hosts: list[HostOptions] = []
        hosts_doc = doc.get("hosts", {})
        if not isinstance(hosts_doc, dict) or not hosts_doc:
            raise ConfigError("config must define at least one host")
        for name, h in sorted(hosts_doc.items()):
            merged = {**defaults, **(h or {})}
            count = int(merged.pop("count", 1))
            if count > 1 and merged.get("ip_addr") is not None:
                raise ConfigError(
                    f"host {name!r}: ip_addr cannot be combined with count > 1 "
                    "(the replicas would collide on the same IP)"
                )
            base = _parse_host(name, merged)
            if count == 1:
                hosts.append(base)
            else:
                for i in range(1, count + 1):
                    hi = dataclasses.replace(
                        base,
                        hostname=f"{name}{i}",
                        processes=[
                            dataclasses.replace(
                                p, args=list(p.args), environment=dict(p.environment)
                            )
                            for p in base.processes
                        ],
                    )
                    hosts.append(hi)
        return cls(
            general=general,
            network=network,
            experimental=experimental,
            faults=faults,
            hosts=hosts,
        )

    # -- overrides (CLI layer) -------------------------------------------

    _TIME_FIELDS = {"stop_time", "bootstrap_end_time", "runahead", "heartbeat_interval"}
    _BYTE_FIELDS = {"socket_send_buffer", "socket_recv_buffer", "pcap_capture_size"}

    def apply_overrides(self, overrides: dict[str, Any]) -> None:
        """Apply dotted-key overrides, e.g. {'general.seed': 7,
        'experimental.network_backend': 'tpu'} — the CLI merge layer.
        Values are coerced to the target field's type (CLI values arrive as
        strings)."""
        for key, value in overrides.items():
            section, _, field = key.partition(".")
            target = getattr(self, section, None)
            if target is None or not dataclasses.is_dataclass(target):
                raise ConfigError(f"unknown config option {key!r}")
            fields = {f.name: f for f in dataclasses.fields(target)}
            if field not in fields:
                raise ConfigError(f"unknown config option {key!r}")
            if value is not None:
                if field in self._TIME_FIELDS:
                    value = units.parse_time(value)
                elif field in self._BYTE_FIELDS:
                    value = units.parse_bytes(value)
                else:
                    current = getattr(target, field)
                    if isinstance(current, bool):
                        value = (
                            value
                            if isinstance(value, bool)
                            else str(value).lower() in ("1", "true", "yes", "on")
                        )
                    elif isinstance(current, int):
                        value = int(value)
                    elif isinstance(current, float):
                        value = float(value)
            setattr(target, field, value)

    def validate(self) -> None:
        if self.general.stop_time <= 0:
            raise ConfigError("general.stop_time must be > 0")
        if self.experimental.network_backend not in ("cpu", "tpu"):
            raise ConfigError("experimental.network_backend must be cpu|tpu")
        if self.experimental.scheduler not in (
            "thread-per-core",
            "thread-per-host",
        ):
            raise ConfigError(
                "experimental.scheduler must be thread-per-core|thread-per-host"
            )
        names = [h.hostname for h in self.hosts]
        if len(set(names)) != len(names):
            raise ConfigError("duplicate hostnames")
        for h in self.hosts:
            if h.congestion not in ("reno", "cubic"):
                raise ConfigError(
                    f"host {h.hostname!r}: congestion must be reno|cubic, "
                    f"got {h.congestion!r}"
                )
        if self.experimental.hybrid_fuse_k < 1:
            raise ConfigError("experimental.hybrid_fuse_k must be >= 1")
        if self.experimental.checkpoint_every_windows < 0:
            raise ConfigError(
                "experimental.checkpoint_every_windows must be >= 0"
            )
        if self.experimental.checkpoint_keep < 1:
            raise ConfigError("experimental.checkpoint_keep must be >= 1")
        if self.experimental.worker_heartbeat_s <= 0:
            raise ConfigError(
                "experimental.worker_heartbeat_s must be > 0 (wall seconds)"
            )
        if self.experimental.worker_restart_max < 0:
            raise ConfigError("experimental.worker_restart_max must be >= 0")
        if self.experimental.dispatch_retry_max < 0:
            raise ConfigError("experimental.dispatch_retry_max must be >= 0")
        if self.experimental.flowtrace_capacity < 1:
            raise ConfigError("experimental.flowtrace_capacity must be >= 1")
        if self.experimental.sweep_size < 0:
            raise ConfigError("experimental.sweep_size must be >= 0")
        if self.experimental.mesh_devices < 0:
            raise ConfigError(
                "experimental.mesh_devices must be >= 0 (0 = single-device)"
            )
        if (
            self.experimental.sweep_spec is not None
            and not str(self.experimental.sweep_spec).strip()
        ):
            raise ConfigError(
                "experimental.sweep_spec must be a spec file path (or unset)"
            )
        if not 0.0 <= self.experimental.flowtrace_sample <= 1.0:
            raise ConfigError("experimental.flowtrace_sample must be in [0, 1]")
        if self.experimental.interface_qdisc not in ("fifo", "round-robin"):
            raise ConfigError(
                "experimental.interface_qdisc must be fifo|round-robin, "
                f"got {self.experimental.interface_qdisc!r}"
            )
        if self.faults.watchdog_timeout is not None and (
            self.faults.watchdog_timeout <= 0
        ):
            raise ConfigError("faults.watchdog_timeout must be > 0 (wall seconds)")
        if self.faults.events:
            from ..faults.schedule import FaultConfigError

            try:
                sched = self.faults.schedule()
            except FaultConfigError as e:
                raise ConfigError(f"faults.events: {e}")
            for ev in sched.events:
                if ev.at < self.general.bootstrap_end_time:
                    raise ConfigError(
                        f"faults.events: {ev.kind} at {ev.at} ns lies inside "
                        "the loss-free bootstrap window "
                        f"(bootstrap_end_time={self.general.bootstrap_end_time} "
                        "ns); fault drops would be silently exempted"
                    )


def _require(doc: dict[str, Any], key: str, section: str) -> Any:
    if key not in doc:
        raise ConfigError(f"{section}.{key} is required")
    return doc[key]


def _opt_time(v: Any) -> Optional[int]:
    return None if v is None else units.parse_time(v)


def _parse_final_state(v: Any, host: str) -> Any:
    """Validate/normalize expected_final_state at parse time: "running",
    {exited: code}, or {signaled: SIG} (signal normalized like
    shutdown_signal) — a typo must fail the config, not the whole run."""
    if v in ("running", "exited"):
        return v
    if isinstance(v, dict) and len(v) == 1:
        if "exited" in v:
            return {"exited": int(v["exited"])}
        if "signaled" in v:
            return {"signaled": _parse_signal(v["signaled"], host)}
        if "running" in v:
            return "running"
    raise ConfigError(
        f"host {host!r}: expected_final_state must be 'running', "
        f"{{exited: CODE}}, or {{signaled: SIG}}; got {v!r}"
    )


def _parse_signal(v: Any, host: str) -> str:
    """Validate a signal name (or number) at parse time — a typo'd
    shutdown_signal must not silently become SIGTERM."""
    import signal as _sig

    if isinstance(v, int):
        try:
            return _sig.Signals(v).name
        except ValueError:
            raise ConfigError(f"host {host!r}: unknown signal number {v}")
    name = str(v).upper()
    if not name.startswith("SIG"):
        name = "SIG" + name
    if not hasattr(_sig, name) or not isinstance(getattr(_sig, name), _sig.Signals):
        raise ConfigError(f"host {host!r}: unknown shutdown_signal {v!r}")
    return name


def _parse_host(name: str, doc: dict[str, Any]) -> HostOptions:
    doc = dict(doc)
    procs = []
    for p in doc.pop("processes", []):
        p = dict(p)
        args = p.pop("args", [])
        if isinstance(args, str):
            args = args.split()
        procs.append(
            ProcessOptions(
                path=str(p.pop("path")),
                args=[str(a) for a in args],
                environment={str(k): str(v) for k, v in p.pop("environment", {}).items()},
                start_time=units.parse_time(p.pop("start_time", 0)),
                shutdown_time=_opt_time(p.pop("shutdown_time", None)),
                shutdown_signal=_parse_signal(p.pop("shutdown_signal", "SIGTERM"), name),
                expected_final_state=_parse_final_state(
                    p.pop("expected_final_state", {"exited": 0}), name
                ),
            )
        )
        if p:
            raise ConfigError(f"unknown process options on host {name!r}: {sorted(p)}")
    bw_down = doc.pop("bandwidth_down", None)
    bw_up = doc.pop("bandwidth_up", None)
    host = HostOptions(
        hostname=name,
        network_node_id=int(doc.pop("network_node_id", 0)),
        ip_addr=doc.pop("ip_addr", None),
        bandwidth_down=units.parse_bandwidth(bw_down) if bw_down is not None else None,
        bandwidth_up=units.parse_bandwidth(bw_up) if bw_up is not None else None,
        processes=procs,
        log_level=doc.pop("log_level", None),
        pcap_enabled=bool(doc.pop("pcap_enabled", False)),
        pcap_capture_size=units.parse_bytes(doc.pop("pcap_capture_size", 65535)),
        congestion=str(doc.pop("congestion", "reno")),
        count=1,
    )
    if doc:
        raise ConfigError(f"unknown host options on {name!r}: {sorted(doc)}")
    return host
