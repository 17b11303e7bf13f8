"""Multiprocess hybrid backend: parallel syscall servicing must be
invisible in the results.

The contract (ISSUE 7 / ROADMAP open item 1): managed hosts' syscall
plane runs across N spawned worker processes while their packets ride
the TPU lane data plane, and the event log, counters, and round count
stay bit-identical to the scalar CPU oracle — and to each other — at ANY
worker count.  This is the same parallelism-invariance law the
reference's determinism suite enforces across its thread-per-core worker
counts (src/test/determinism/), applied to the hybrid seam.

Tier-1 wall budget: the full worker matrix spawns 7 JAX-importing
processes and runs five simulations, so only the 2-worker parity check
runs in the tier-1 selection; the {1, 2, 4} matrix, the run-twice
byte-stability gate, and the relay-chain scale gate are ``slow``-marked
and run by ``make gate`` (which invokes this file without the marker
filter) and by the SHADOW_TPU_SCALE gate.
"""

import os
import subprocess
from pathlib import Path

import pytest

from shadow_tpu.backend import lanes
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


def _mixed_config(tmp_path: Path, tag: str, backend: str,
                  workers: int = 1) -> ConfigOptions:
    """Managed pingpong pair + managed tcpecho pair + tgen-mesh lane
    hosts: enough managed hosts (4) that every worker count in {1, 2, 4}
    gets a non-trivial partition, with model traffic crossing the managed
    lanes in both directions of the hybrid seam."""
    mesh = "\n".join(
        f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
"""
        for i in range(4)
    )
    return ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 2s, seed: 21, data_directory: {tmp_path / tag}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: {backend}, hybrid_workers: {workers}}}
hosts:
  cli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.4, "9000", "4", "100"]
  srv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "4"]
  ecli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [hclient, esrv, "7000", "2", "400", "5"]
        start_time: 200ms
  esrv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [server, "7000", "1"]
{mesh}
"""
    )


def _run(cfg):
    sim = Simulation(cfg)
    result = sim.run(write_data=False)
    return result, sim.engine


COUNTER_KEYS = ("udp_tx_bytes", "udp_rx_bytes", "managed_exit_clean",
                "managed_tcp_rx_bytes", "tgen_recv_bytes")


def _assert_matches(r, oracle):
    assert r.log_tuples() == oracle.log_tuples()
    assert not r.process_errors
    for key in COUNTER_KEYS:
        assert r.counters.get(key) == oracle.counters.get(key), key
    assert r.rounds == oracle.rounds


@pytest.fixture(scope="module")
def cpu_oracle(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("hybrid_mp_oracle")
    result, _ = _run(_mixed_config(tmp, "cpu", "cpu"))
    assert not result.process_errors
    return result


def test_hybrid_mp_parity_with_cpu_oracle(tmp_path, cpu_oracle):
    """Tier-1 slice: the 2-worker engine is bit-identical to the
    all-host-side CPU oracle, and the sync-cost accounting records the
    batched boundary (ONE packed scalar transfer per device turn,
    coalesced injection blocks — docs/hybrid.md)."""
    from shadow_tpu.backend.hybrid import MpHybridEngine

    r, eng = _run(_mixed_config(tmp_path, "w2", "tpu", workers=2))
    assert isinstance(eng, MpHybridEngine)
    assert eng.workers == 2
    _assert_matches(r, cpu_oracle)
    s = eng.sync_stats
    assert s["device_turns"] > 0
    assert s["scalar_reads"] == s["device_turns"]
    assert s["inject_rows"] > 0 and s["egress_rows"] > 0
    assert s["device_sync_s"] > 0 and s["syscall_service_s"] > 0
    assert s["inject_blocks"] <= s["device_turns"]
    _assert_transfer_law(eng, overflow_blocks=0)


def _assert_transfer_law(eng, overflow_blocks: int) -> None:
    """A turn crosses the boundary once each way (ISSUE 40): one
    host->device copy per fused call — the blocking dispatches less the
    adopted eager ones, plus every eager dispatch issued — and per
    overflow block; one blocking read per device turn, which brings the
    egress rows unless a turn egressed more than the head holds."""
    s = eng.sync_stats
    assert s["dispatch_retries"] == 0
    fused_calls = s["device_turns"] + s["async_dispatch_misses"]
    assert s["h2d_copies"] == fused_calls + overflow_blocks
    width = eng._lay.width
    assert width == 7 * eng.device.params.inject_batch \
        + 2 * eng._ext_slots + 2
    assert s["inject_bytes"] == 4 * width * s["h2d_copies"]
    assert s["scalar_reads"] == s["device_turns"]
    assert s["egress_head_reads"] > 0 and s["egress_reads"] == 0
    assert s["egress_bytes"] == 48 * lanes.HYB_EGRESS_HEAD * s["scalar_reads"]


def test_oversized_staging_ships_overflow_blocks(tmp_path, cpu_oracle):
    """An injection batch of TWO rows: a turn that stages n > 2 sends
    ships its first blocks through the standalone merge, in the turn
    block's own format, and the run is still the oracle's."""
    cfg = _mixed_config(tmp_path, "b2", "tpu", workers=1)
    cfg.experimental.tpu_inject_batch = 2
    r, eng = _run(cfg)
    _assert_matches(r, cpu_oracle)
    staged = [row.n_staged for row in eng.sync_stats["turn_spans"]]
    assert len(staged) == eng.clock.turns  # the ring kept every turn
    blocks = sum(-(-n // 2) for n in staged)
    overflow = blocks - sum(1 for n in staged if n)
    assert overflow > 0
    assert eng.sync_stats["inject_blocks"] == blocks
    _assert_transfer_law(eng, overflow_blocks=overflow)


@pytest.mark.slow
@pytest.mark.parametrize("workers", [1, 4])
def test_hybrid_mp_worker_matrix(tmp_path, cpu_oracle, workers):
    """The rest of the {1, 2, 4} matrix (2 is the tier-1 slice above):
    the workers=1 degenerate (serial in-process) path and the 4-worker
    engine both produce oracle-identical results."""
    from shadow_tpu.backend.hybrid import HybridEngine, MpHybridEngine

    r, eng = _run(_mixed_config(tmp_path, f"w{workers}", "tpu",
                                workers=workers))
    if workers == 1:
        assert isinstance(eng, HybridEngine)
        assert not isinstance(eng, MpHybridEngine)
    else:
        assert isinstance(eng, MpHybridEngine)
        assert eng.workers == workers
    _assert_matches(r, cpu_oracle)


@pytest.mark.slow
def test_hybrid_mp_deterministic_byte_stable(tmp_path):
    """Run-twice determinism on the multiprocess path: the canonical
    event-log artifact (the determinism-diff file) is byte-identical, and
    counters and rounds match exactly."""
    r1, _ = _run(_mixed_config(tmp_path / "a", "t1", "tpu", workers=2))
    sim2 = Simulation(_mixed_config(tmp_path / "b", "t2", "tpu", workers=2))
    r2 = sim2.run(write_data=False)
    log1 = sim2.write_event_log(r1, tmp_path / "log1.tsv")
    log2 = sim2.write_event_log(r2, tmp_path / "log2.tsv")
    assert log1.read_bytes() == log2.read_bytes()
    assert len(r1.event_log) > 50
    assert r1.counters == r2.counters
    assert r1.rounds == r2.rounds


SCALE = pytest.mark.skipif(
    not os.environ.get("SHADOW_TPU_SCALE"),
    reason="scale gate: set SHADOW_TPU_SCALE=1 to run",
)


@SCALE
def test_hybrid_gate_scenario_parity(tmp_path):
    """The SHADOW_TPU_SCALE gate exercises the full hybrid relay-chain
    shape (managed TCP chains + lane mesh, config/scenarios.py) without
    TPU time: 16 managed processes over 60 lane hosts on the CPU JAX
    platform, 2-worker syscall servicing, bit-parity vs the oracle."""
    from shadow_tpu.config.scenarios import managed_relay_chains_gate

    r_cpu, _ = _run(managed_relay_chains_gate(tmp_path / "cpu",
                                              backend="cpu"))
    r_hyb, eng = _run(managed_relay_chains_gate(tmp_path / "hyb",
                                                hybrid_workers=2))
    assert eng.workers == 2
    assert not r_cpu.process_errors and not r_hyb.process_errors
    assert r_hyb.log_tuples() == r_cpu.log_tuples()
    assert r_hyb.rounds == r_cpu.rounds
    for key in ("managed_exit_clean", "udp_rx_bytes", "tgen_recv_bytes"):
        assert r_hyb.counters.get(key) == r_cpu.counters.get(key), key
