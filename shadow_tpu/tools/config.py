"""TypedDicts for the simulation config document.

The shadowtools.config analog: the YAML document shape as Python types,
so configs can be generated dynamically with type-checker support and fed
straight to :class:`shadow_tpu.config.options.ConfigOptions.from_dict`.

Example::

    from shadow_tpu.tools import make_config, HostDict, ProcessDict
    from shadow_tpu.config.options import ConfigOptions

    doc = make_config(
        stop_time="10s",
        hosts={
            "client": HostDict(
                network_node_id=0,
                processes=[ProcessDict(path="ping", args=["--peer", "server"])],
            ),
            "server": HostDict(network_node_id=0, processes=[ProcessDict(path="ping")]),
        },
    )
    cfg = ConfigOptions.from_dict(doc)
"""

from __future__ import annotations

from typing import Any, Optional, TypedDict


class ProcessDict(TypedDict, total=False):
    path: str
    args: list[str]
    environment: dict[str, str]
    start_time: str | int
    shutdown_time: str | int
    shutdown_signal: str
    expected_final_state: Any


class HostDict(TypedDict, total=False):
    network_node_id: int
    ip_addr: str
    bandwidth_down: str | int
    bandwidth_up: str | int
    processes: list[ProcessDict]
    log_level: str
    pcap_enabled: bool
    pcap_capture_size: str | int
    count: int


class GeneralDict(TypedDict, total=False):
    stop_time: str | int
    seed: int
    parallelism: int
    bootstrap_end_time: str | int
    data_directory: str
    log_level: str
    heartbeat_interval: Optional[str | int]
    progress: bool
    model_unblocked_syscall_latency: bool


class GraphDict(TypedDict, total=False):
    type: str  # "gml" | "1_gbit_switch"
    file: str
    inline: str


class NetworkDict(TypedDict, total=False):
    graph: GraphDict
    use_shortest_path: bool


class ExperimentalDict(TypedDict, total=False):
    runahead: str | int
    use_dynamic_runahead: bool
    scheduler: str
    use_cpu_pinning: bool
    use_worker_spinning: bool
    use_new_tcp: bool
    socket_send_buffer: str | int
    socket_recv_buffer: str | int
    interface_qdisc: str
    strace_logging_mode: str
    run_control: bool
    perf_logging: bool
    network_backend: str  # "cpu" | "tpu"
    tpu_lane_queue_capacity: int
    tpu_events_per_round: int
    tpu_cross_capacity: int


class ConfigDict(TypedDict, total=False):
    general: GeneralDict
    network: NetworkDict
    experimental: ExperimentalDict
    host_option_defaults: HostDict
    hosts: dict[str, HostDict]


def make_config(
    stop_time: str | int,
    hosts: dict[str, HostDict],
    seed: int = 1,
    general: Optional[GeneralDict] = None,
    network: Optional[NetworkDict] = None,
    experimental: Optional[ExperimentalDict] = None,
    host_option_defaults: Optional[HostDict] = None,
) -> ConfigDict:
    """Assemble a full config document from parts (stop_time and hosts are
    the only required pieces; everything else has simulator defaults)."""
    gen: GeneralDict = dict(general or {})
    gen.setdefault("stop_time", stop_time)
    gen.setdefault("seed", seed)
    doc: ConfigDict = {"general": gen, "hosts": hosts}
    if network is not None:
        doc["network"] = network
    if experimental is not None:
        doc["experimental"] = experimental
    if host_option_defaults is not None:
        doc["host_option_defaults"] = host_option_defaults
    return doc
