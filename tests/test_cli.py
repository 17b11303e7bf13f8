"""CLI and Simulation facade: end-to-end runs through the public surface,
plus the run-twice determinism diff (the reference's determinism1 test)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation

REPO = Path(__file__).resolve().parents[1]

PING_YAML = """
general: {stop_time: 2s, seed: 5, data_directory: DATADIR}
network: {graph: {type: 1_gbit_switch}}
hosts:
  cli: {network_node_id: 0, processes: [{path: ping, args: [--peer, srv, --count, "4", --interval, 250ms]}]}
  srv: {network_node_id: 0, processes: [{path: ping}]}
"""


def _write_cfg(tmp_path: Path) -> Path:
    cfg = tmp_path / "sim.yaml"
    cfg.write_text(PING_YAML.replace("DATADIR", str(tmp_path / "data")))
    return cfg


def _run_cli(args, **kw):
    return subprocess.run(
        [sys.executable, "-m", "shadow_tpu", *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
        **kw,
    )


def test_cli_end_to_end(tmp_path):
    cfg = _write_cfg(tmp_path)
    proc = _run_cli([str(cfg), "--event-log"])
    assert proc.returncode == 0, proc.stderr
    stats = json.loads((tmp_path / "data" / "sim-stats.json").read_text())
    assert stats["num_hosts"] == 2
    assert stats["packet_outcomes"]["delivered"] == 8
    assert (tmp_path / "data" / "hosts" / "cli" / "counters.json").exists()
    assert (tmp_path / "data" / "event-log.tsv").read_text().count("\n") == 9


def test_cli_stdin_and_overrides(tmp_path):
    proc = _run_cli(
        ["-", "--seed", "9", "--data-directory", str(tmp_path / "d2"), "--show-config"],
        input=PING_YAML.replace("DATADIR", str(tmp_path / "ignored")),
    )
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["general"]["seed"] == 9
    assert doc["general"]["data_directory"] == str(tmp_path / "d2")


def test_cli_config_error_exit_code(tmp_path):
    bad = tmp_path / "bad.yaml"
    bad.write_text("general: {stop_time: 1s}\nnope: {}\n")
    proc = _run_cli([str(bad)])
    assert proc.returncode == 2
    assert "config error" in proc.stderr


def test_run_twice_bit_identical(tmp_path):
    """determinism1: same config, two full runs, identical event logs."""
    yaml = PING_YAML.replace("DATADIR", str(tmp_path / "d"))
    logs = []
    for _ in range(2):
        sim = Simulation(ConfigOptions.from_yaml(yaml))
        logs.append(sim.run(write_data=False).log_tuples())
    assert logs[0] == logs[1]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_simulation_facade_backends(tmp_path, backend):
    yaml = PING_YAML.replace("DATADIR", str(tmp_path / backend))
    cfg = ConfigOptions.from_yaml(yaml)
    cfg.experimental.network_backend = backend
    result = Simulation(cfg).run()
    stats = json.loads((tmp_path / backend / "sim-stats.json").read_text())
    assert stats["backend"] == backend
    assert stats["packet_outcomes"]["delivered"] == 8
    assert result.rounds > 0


def test_simulation_tpu_mesh_devices(tmp_path):
    yaml = PING_YAML.replace("DATADIR", str(tmp_path / "mesh"))
    cfg = ConfigOptions.from_yaml(yaml)
    cfg.experimental.network_backend = "tpu"
    cfg.experimental.mesh_devices = 2
    # the request reaching the engine is the point, not the pop count: at
    # the default 8 pops a sharded run on XLA:CPU takes minutes (see
    # tests/test_multichip.py::_phold_cfg)
    cfg.experimental.tpu_events_per_round = 2
    sim = Simulation(cfg)
    result = sim.run(write_data=False)
    assert sim.engine.mesh is not None and sim.engine.mesh.devices.size == 2
    assert len(result.event_log) == 8
