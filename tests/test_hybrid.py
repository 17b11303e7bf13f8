"""Hybrid-backend parity: managed (real-binary) hosts on the TPU data
plane produce event logs bit-identical to the scalar CPU oracle.

This is the determinism contract of the reference's offload design
(BASELINE.json: syscall emulation on host CPU, packet hot path on the
device; determinism checked the way src/test/determinism/ does — run the
same config on both backends / twice and diff the canonical event logs).
"""

import subprocess
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation

pytestmark = pytest.mark.hybrid

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"


@pytest.fixture(scope="module", autouse=True)
def native_build():
    subprocess.run(
        ["make", "-C", str(REPO / "native")], check=True, capture_output=True
    )


def _mixed_config(tmp_path: Path, backend: str, count: int = 5,
                  mesh_hosts: int = 6) -> ConfigOptions:
    """Managed pingpong pair + tgen-mesh model hosts sharing one switch:
    the mesh spray crosses the managed lanes (their dn buckets and CoDel
    run on device in the hybrid), and the managed datagrams cross the
    mesh — both directions of the hybrid seam."""
    # mesh hosts sort AFTER cli/srv so the managed pair keeps 11.0.0.1/.2
    # (pingpong takes a literal IP)
    mesh = "\n".join(
        f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
"""
        for i in range(mesh_hosts)
    )
    return ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 2s, seed: 21, data_directory: {tmp_path / backend}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: {backend}}}
hosts:
  cli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.2, "9000", "{count}", "100"]
  srv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "{count}"]
{mesh}
"""
    )


def _run(cfg) -> tuple:
    sim = Simulation(cfg)
    result = sim.run()
    return result, sim.engine


def test_hybrid_managed_parity_with_cpu_oracle(tmp_path):
    """The full seam: managed hosts' deliveries ride the device egress,
    their sends ride the injection merge, model lanes run on device —
    and the event log, counters, and round count diff EQUAL against the
    all-host-side CPU oracle."""
    r_cpu, _ = _run(_mixed_config(tmp_path, "cpu"))
    r_tpu, eng = _run(_mixed_config(tmp_path, "tpu"))
    from shadow_tpu.backend.hybrid import HybridEngine

    assert isinstance(eng, HybridEngine)
    assert r_cpu.log_tuples() == r_tpu.log_tuples()
    assert not r_cpu.process_errors and not r_tpu.process_errors
    # managed-side counters agree (udp traffic, clean exits)
    for key in ("udp_tx_bytes", "udp_rx_bytes", "managed_exit_clean"):
        assert r_cpu.counters.get(key) == r_tpu.counters.get(key), key
    # model-side accounting agrees (the oracle counts per-app recv bytes;
    # the device counts them in lane counters)
    assert r_cpu.counters.get("tgen_recv_bytes") == r_tpu.counters.get(
        "tgen_recv_bytes"
    )
    assert r_cpu.rounds == r_tpu.rounds


def test_append_counters_of_a_sound_run(tmp_path):
    """The device's record appends say how often they engaged
    (``sync_stats``, beside the counters and never among them): a sound
    run writes no block for queue-overflow records, and every record of
    the device log and every egressed row went out in some block (the
    log also holds the records the host side wrote)."""
    r_tpu, eng = _run(_mixed_config(tmp_path, "tpu"))
    st = eng.sync_stats
    assert st["append_tail_blocks"] == 0
    assert 0 < st["append_blocks"] <= st["append_rows"]
    assert st["egress_rows"] > 0
    device_records = st["append_rows"] - st["egress_rows"]
    assert 0 < device_records <= len(r_tpu.event_log)
    assert not any(k.startswith("append_") for k in r_tpu.counters)


def test_hybrid_deterministic(tmp_path):
    r1, _ = _run(_mixed_config(tmp_path / "a", "tpu"))
    r2, _ = _run(_mixed_config(tmp_path / "b", "tpu"))
    assert r1.log_tuples() == r2.log_tuples()
    assert r1.counters == r2.counters


def test_hybrid_managed_tcp_parity(tmp_path):
    """Managed TCP (tcpecho) across the hybrid seam: segments ride the
    device as packets with payloads parked host-side."""

    def cfg(backend):
        return ConfigOptions.from_yaml(
            f"""
general: {{stop_time: 3s, seed: 7, data_directory: {tmp_path / ('t' + backend)}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: {backend}}}
hosts:
  ecli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [client, 11.0.0.2, "7000", "3", "600", "5"]
        start_time: 100ms
  esrv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [server, "7000", "1"]
  filler:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 100ms --size 400
        start_time: 0 s
  filler2:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 100ms --size 400
        start_time: 0 s
"""
        )

    r_cpu, _ = _run(cfg("cpu"))
    r_tpu, _ = _run(cfg("tpu"))
    assert r_cpu.log_tuples() == r_tpu.log_tuples()
    assert not r_cpu.process_errors and not r_tpu.process_errors
    assert r_cpu.rounds == r_tpu.rounds
