"""Config parsing: units, YAML document shape, overrides, validation."""

import dataclasses
import re
from pathlib import Path

import pytest

from shadow_tpu.__main__ import build_parser
from shadow_tpu.config import options, units
from shadow_tpu.config.options import ConfigError, ConfigOptions
from shadow_tpu.core import time as stime


def test_time_units():
    assert units.parse_time("10s") == 10 * stime.NANOS_PER_SEC
    assert units.parse_time("10 ms") == 10 * stime.NANOS_PER_MILLI
    assert units.parse_time("1500 us") == 1500 * stime.NANOS_PER_MICRO
    assert units.parse_time("250 ns") == 250
    assert units.parse_time("2 min") == 2 * stime.NANOS_PER_MIN
    assert units.parse_time("1h") == stime.NANOS_PER_HOUR
    assert units.parse_time(10) == 10 * stime.NANOS_PER_SEC
    assert units.parse_time("1.5s") == 1_500_000_000
    with pytest.raises(units.UnitError):
        units.parse_time("10 parsecs")


def test_bandwidth_units():
    assert units.parse_bandwidth("1 Gbit") == 10**9
    assert units.parse_bandwidth("100 Mbit") == 100 * 10**6
    assert units.parse_bandwidth("10 Mbps") == 10 * 10**6
    assert units.parse_bandwidth("1 Kibit") == 1024
    assert units.parse_bandwidth(5000) == 5000


def test_byte_units():
    assert units.parse_bytes("16 MiB") == 16 * 2**20
    assert units.parse_bytes("1500 B") == 1500
    assert units.parse_bytes("2 KB") == 2000
    assert units.parse_bytes(42) == 42


BASIC_YAML = """
general:
  stop_time: 10s
  seed: 7

network:
  graph:
    type: 1_gbit_switch

hosts:
  server:
    network_node_id: 0
    processes:
    - path: tgen-server
      args: --port 80
      start_time: 1s
      expected_final_state: running
  client: &client
    network_node_id: 0
    processes:
    - path: tgen-client
      args: [--connect, server]
      start_time: 2s
"""


def test_basic_yaml_roundtrip():
    cfg = ConfigOptions.from_yaml(BASIC_YAML)
    cfg.validate()
    assert cfg.general.stop_time == 10 * stime.NANOS_PER_SEC
    assert cfg.general.seed == 7
    assert [h.hostname for h in cfg.hosts] == ["client", "server"]
    server = cfg.hosts[1]
    assert server.processes[0].path == "tgen-server"
    assert server.processes[0].args == ["--port", "80"]
    assert server.processes[0].start_time == stime.NANOS_PER_SEC
    assert server.processes[0].expected_final_state == "running"
    assert cfg.hosts[0].processes[0].args == ["--connect", "server"]


def test_host_count_expansion():
    cfg = ConfigOptions.from_yaml(
        """
general: {stop_time: 1s}
hosts:
  peer:
    count: 3
    processes: [{path: phold}]
"""
    )
    assert [h.hostname for h in cfg.hosts] == ["peer1", "peer2", "peer3"]


def test_unknown_keys_rejected():
    with pytest.raises(ConfigError, match="unknown general"):
        ConfigOptions.from_yaml(
            "general: {stop_time: 1s, bogus: 1}\nhosts: {a: {processes: []}}"
        )
    with pytest.raises(ConfigError, match="unknown top-level"):
        ConfigOptions.from_yaml("general: {stop_time: 1s}\nhoss: {}")
    with pytest.raises(ConfigError, match="at least one host"):
        ConfigOptions.from_yaml("general: {stop_time: 1s}")


def test_overrides_and_validation():
    cfg = ConfigOptions.from_yaml(BASIC_YAML)
    cfg.apply_overrides(
        {"experimental.network_backend": "tpu", "general.stop_time": "30s"}
    )
    assert cfg.experimental.network_backend == "tpu"
    assert cfg.general.stop_time == 30 * stime.NANOS_PER_SEC
    with pytest.raises(ConfigError):
        cfg.apply_overrides({"general.nope": 1})
    cfg.experimental.network_backend = "gpu"
    with pytest.raises(ConfigError):
        cfg.validate()


def test_host_defaults_inheritance():
    cfg = ConfigOptions.from_yaml(
        """
general: {stop_time: 1s}
host_option_defaults: {pcap_enabled: true, bandwidth_up: "10 Mbit"}
hosts:
  a: {processes: [{path: phold}]}
  b: {pcap_enabled: false, processes: [{path: phold}]}
"""
    )
    a, b = cfg.hosts
    assert a.pcap_enabled and not b.pcap_enabled
    assert a.bandwidth_up == 10**7 and b.bandwidth_up == 10**7


def test_override_type_coercion():
    cfg = ConfigOptions.from_yaml(BASIC_YAML)
    cfg.apply_overrides(
        {
            "general.heartbeat_interval": "2s",
            "general.seed": "9",
            "experimental.socket_send_buffer": "16 KiB",
            "experimental.use_worker_spinning": "false",
            "experimental.runahead": None,
        }
    )
    assert cfg.general.heartbeat_interval == 2 * stime.NANOS_PER_SEC
    assert cfg.general.seed == 9
    assert cfg.experimental.socket_send_buffer == 16 * 1024
    assert cfg.experimental.use_worker_spinning is False
    assert cfg.experimental.runahead is None


def test_graph_doc_unknown_and_conflicting_keys():
    import pytest as _pytest

    with _pytest.raises(ConfigError, match="unknown network.graph"):
        ConfigOptions.from_yaml(
            """
general: {stop_time: 1s}
network: {graph: {type: 1_gbit_switch, bogus: 1}}
hosts: {a: {processes: [{path: x}]}}
"""
        )
    with _pytest.raises(ConfigError, match="conflicting sources"):
        ConfigOptions.from_yaml(
            """
general: {stop_time: 1s}
network: {graph: {type: gml, file: a.gml, inline: "graph []"}}
hosts: {a: {processes: [{path: x}]}}
"""
        )


def test_count_expansion_no_shared_mutables():
    cfg = ConfigOptions.from_yaml(
        """
general: {stop_time: 1s}
hosts:
  peer:
    count: 2
    processes: [{path: phold, args: [--x], environment: {A: "1"}}]
"""
    )
    p0, p1 = cfg.hosts[0].processes[0], cfg.hosts[1].processes[0]
    assert p0.args is not p1.args and p0.environment is not p1.environment


def test_ip_addr_with_count_rejected():
    with pytest.raises(ConfigError, match="count > 1"):
        ConfigOptions.from_yaml(
            "general: {stop_time: 1s}\n"
            "hosts: {relay: {count: 3, ip_addr: 11.0.0.5, processes: []}}"
        )


def test_mesh_devices_override_coercion():
    cfg = ConfigOptions.from_yaml(BASIC_YAML)
    cfg.apply_overrides({"experimental.mesh_devices": "4"})
    assert cfg.experimental.mesh_devices == 4
    assert type(cfg.experimental.mesh_devices) is int


@pytest.mark.parametrize("key", ["tpu_round_unroll", "tpu_mesh_shape"])
def test_removed_experimental_keys_are_refused(key):
    """PR 44 removed both: a config that still sets one fails by name, it
    is not dropped in silence, and the mesh has one spelling on the CLI."""
    with pytest.raises(ConfigError, match=key):
        ConfigOptions.from_yaml(
            BASIC_YAML.replace("general:", f"experimental: {{{key}: 2}}\ngeneral:")
        )
    cfg = ConfigOptions.from_yaml(BASIC_YAML)
    with pytest.raises(ConfigError, match=key):
        cfg.apply_overrides({f"experimental.{key}": "2"})
    with pytest.raises(SystemExit):
        build_parser().parse_args(["cfg.yaml", "--tpu-mesh-shape", "2"])


def test_every_experimental_option_has_a_reader():
    """An option nothing reads is a promise nothing keeps: every field of
    ExperimentalOptions is read (``.name``) by the package or by the sweep
    verb, or is declared parsed-for-upstream-YAML-only."""
    repo = Path(__file__).resolve().parents[1]
    declared = {"config/options.py", "tools/config.py"}
    pkg = repo / "shadow_tpu"
    readers = [repo / "scripts" / "sweep.py"] + [
        p for p in sorted(pkg.rglob("*.py"))
        if p.relative_to(pkg).as_posix() not in declared
    ]
    text = "\n".join(p.read_text() for p in readers)
    names = [f.name for f in dataclasses.fields(options.ExperimentalOptions)]
    assert len(names) == 42  # ROADMAP C4: every PR leaves it no larger
    assert set(options.REFERENCE_PARITY_FIELDS) <= set(names)
    unread = [
        n for n in names
        if n not in options.REFERENCE_PARITY_FIELDS
        and not re.search(rf"\.{n}\b", text)
    ]
    assert unread == []
    read_anyway = [
        n for n in options.REFERENCE_PARITY_FIELDS
        if re.search(rf"\.{n}\b", text)
    ]
    assert read_anyway == []  # a reserved field that gained a reader: unlist it
