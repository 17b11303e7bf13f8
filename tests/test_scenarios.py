"""The evaluation-ladder configs (BASELINE.md configs 1/2/3/5).

Small-scale gates for the factories the benchmark times at full scale: each
config must parse, run on both backends where lane-compatible, and the
managed relay-chain scenario (config #5's self-contained analog) must
carry real echo traffic through three-relay chains deterministically.
"""

import subprocess
from pathlib import Path

import pytest

from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.presets import (
    transfer_pair_config,
    udp_star_config,
)
from shadow_tpu.config.scenarios import (
    managed_chain_config,
    managed_proc_count,
)
from shadow_tpu.engine.sim import Simulation

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module", autouse=True)
def native_build():
    subprocess.run(
        ["make", "-C", str(REPO / "native")], check=True, capture_output=True
    )


def test_transfer_pair_parity():
    cfg_c = transfer_pair_config(size_bytes=300_000, sim_seconds=30,
                                 backend="cpu")
    cfg_t = transfer_pair_config(size_bytes=300_000, sim_seconds=30,
                                 backend="tpu")
    cpu = CpuEngine(cfg_c).run()
    tpu = TpuEngine(cfg_t).run(mode="step")
    assert cpu.counters["stream_complete"] == 1
    assert cpu.counters["stream_rx_bytes"] == 300_000
    assert cpu.log_tuples() == tpu.log_tuples()


def test_udp_star_parity():
    cfg_c = udp_star_config(12, sim_seconds=3, backend="cpu")
    cfg_t = udp_star_config(12, sim_seconds=3, backend="tpu")
    cpu = CpuEngine(cfg_c).run()
    tpu = TpuEngine(cfg_t).run(mode="step")
    assert cpu.counters.get("tgen_recv_bytes", 0) > 0
    assert cpu.log_tuples() == tpu.log_tuples()
    assert cpu.counters.get("tgen_recv_bytes") == tpu.counters.get(
        "tgen_recv_bytes"
    )


def _run_managed(tmp_path, tag, **kw):
    cfg = managed_chain_config(tmp_path / tag, **kw)
    result = Simulation(cfg).run()
    return cfg, result


def test_managed_chain_scenario(tmp_path):
    cfg, result = _run_managed(
        tmp_path, "m", chains=2, clients_per_chain=1, peers=4,
        sim_seconds=20, rounds=5, size=2048,
    )
    assert not result.process_errors
    assert result.counters["managed_procs"] >= managed_proc_count(2, 1)
    for c in range(2):
        out = (tmp_path / "m" / "hosts" / f"client{c}x0" /
               "tcpecho.stdout").read_text()
        assert "client done rounds=5 bytes=10240" in out, out
    # background mesh flowed
    assert result.counters.get("tgen_recv_bytes", 0) > 0


def test_managed_chain_deterministic(tmp_path):
    _, r1 = _run_managed(tmp_path, "r1", chains=1, clients_per_chain=1,
                         peers=2, sim_seconds=15, rounds=3, size=1024)
    _, r2 = _run_managed(tmp_path, "r2", chains=1, clients_per_chain=1,
                         peers=2, sim_seconds=15, rounds=3, size=1024)
    assert r1.log_tuples() == r2.log_tuples()
    assert r1.counters == r2.counters
    f = Path("hosts") / "client0x0" / "tcpecho.stdout"
    assert (tmp_path / "r1" / f).read_text() == (
        tmp_path / "r2" / f
    ).read_text()
