"""Hybrid backend_stall mid-fused-run -> failover replay
(backend/hybrid.py + engine/sim.py, docs/robustness.md — the PR 13
fusion/async-dispatch machinery crossed with the PR 1 failover law).

An injected ``backend_stall`` fires while k-window fusion and
double-buffered async dispatch are in flight.  Managed (real-binary)
processes hold live OS state that cannot be snapshotted, so the hybrid
backend has no checkpoints: the failover boundary replays the whole run
on the CPU engine from t=0, where managed hosts run natively — and the
replay is bit-identical to an unfaulted CPU-only run of the same
config.  The pure-lane checkpoint-anchored variant (suffix replay with
``restart_work_saved > 0``) is pinned in tests/test_checkpoint.py.
"""

import subprocess
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.faults.watchdog import BackendStallError

pytestmark = pytest.mark.hybrid

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"


@pytest.fixture(scope="module", autouse=True)
def native_build():
    subprocess.run(
        ["make", "-C", str(REPO / "native")], check=True,
        capture_output=True,
    )


def _cfg(data_dir: Path, backend: str, workers: int = 1,
         stall: bool = False, failover: bool = True) -> ConfigOptions:
    """The fusion-suite mixed scenario (managed pingpong pair + tgen
    lane mesh): the pingpong cadence stages sends that land inside
    fused spans, so the stall interrupts genuine fused/async work."""
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
    faults = (
        "faults:\n"
        f"  failover: {str(failover).lower()}\n"
        "  events:\n    - {at: 1s, kind: backend_stall}\n"
        if stall
        else ""
    )
    return ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 2s, seed: 21, data_directory: {data_dir}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: {backend}, hybrid_workers: {workers},
                hybrid_fuse_k: 8}}
{faults}
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
{mesh}
"""
    )


@pytest.fixture(scope="module")
def cpu_ref(tmp_path_factory):
    """The unfaulted CPU-only run every failover replay must match."""
    dd = tmp_path_factory.mktemp("ref")
    return Simulation(_cfg(dd, "cpu")).run(write_data=False)


@pytest.mark.parametrize("workers", [1, 2])
def test_stall_mid_fused_run_fails_over_bit_identical(
    workers, cpu_ref, tmp_path
):
    sim = Simulation(_cfg(tmp_path, "tpu", workers=workers, stall=True))
    res = sim.run(write_data=False)
    assert sim.failovers == 1
    # hybrid holds no checkpoints (managed OS state): t=0 replay
    assert sim.restart_work_saved == 0
    assert res.log_tuples() == cpu_ref.log_tuples()


def test_stall_with_failover_disabled_raises(tmp_path):
    sim = Simulation(
        _cfg(tmp_path, "tpu", stall=True, failover=False)
    )
    with pytest.raises(BackendStallError, match="injected backend stall"):
        sim.run(write_data=False)
