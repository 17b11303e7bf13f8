"""Where a run ran is part of its result (shadow_tpu/device.py).

``network_backend: tpu`` names the lane PROGRAM; the device record beside it
names where JAX ran it.  Under test that is XLA:CPU, and every surface must
say so — the whole point being that a CPU run can no longer pass as a chip
run.  Also pinned here: the compile-cache helper's placement law and the
one-process-per-chip rule of ``dryrun_multichip``."""

import importlib.util
import json
import logging
from pathlib import Path

import jax
import pytest

from shadow_tpu import device as dev
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation

REPO = Path(__file__).resolve().parents[1]

CFG = """
general: {{stop_time: 200ms, seed: 3, heartbeat_interval: null,
          data_directory: {data}}}
experimental: {{network_backend: {backend}{extra}}}
network: {{graph: {{type: 1_gbit_switch}}}}
hosts:
  p: {{count: 4, network_node_id: 0,
      processes: [{{path: phold, args: [--messages, "2"]}}]}}
"""


def _run(tmp_path, backend, extra=""):
    data = tmp_path / backend
    cfg = ConfigOptions.from_yaml(
        CFG.format(data=data, backend=backend, extra=extra)
    )
    sim = Simulation(cfg)
    sim.run()
    return sim, json.loads((data / "sim-stats.json").read_text())


def test_describe_devices_names_platform_kind_count():
    devs = jax.devices()
    assert dev.describe_devices(devs[:1]) == {
        "platform": "cpu", "kind": devs[0].device_kind, "count": 1,
    }
    # duplicates collapse; the record is named after the lowest id
    info = dev.describe_devices([devs[3], devs[1], devs[3]])
    assert info["count"] == 2 and info["platform"] == "cpu"
    assert dev.format_device(info) == f"cpu:{devs[0].device_kind} x2"
    assert dev.format_device(None) == "none"


def test_sim_stats_and_start_line_name_the_device(tmp_path, caplog):
    with caplog.at_level(logging.INFO, logger="shadow_tpu"):
        sim, stats = _run(tmp_path, "tpu")
    assert stats["backend"] == "tpu"  # the program ...
    assert stats["device"] == {       # ... and where it ran
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 1,
    }
    assert sim.engine.device_info() == stats["device"]
    starts = [r.getMessage() for r in caplog.records
              if r.getMessage().startswith("starting simulation")]
    assert len(starts) == 1
    assert "backend=tpu" in starts[0] and "device=cpu:" in starts[0]


def test_cpu_engine_holds_no_device(tmp_path, caplog):
    with caplog.at_level(logging.INFO, logger="shadow_tpu"):
        _, stats = _run(tmp_path, "cpu")
    assert stats["backend"] == "cpu" and stats["device"] is None
    assert any("device=none" in r.getMessage() for r in caplog.records)


def test_mesh_run_counts_its_devices_and_metrics_carry_them(tmp_path):
    from shadow_tpu.config.presets import flagship_mesh_config

    cfg = flagship_mesh_config(16, queue_capacity=16, pops_per_round=2)
    cfg.general.stop_time = 50_000_000
    cfg.general.heartbeat_interval = None
    cfg.general.data_directory = str(tmp_path / "mesh")
    cfg.experimental.mesh_devices = 4
    cfg.experimental.obs_metrics = True
    sim = Simulation(cfg)
    sim.run()
    stats = json.loads((tmp_path / "mesh" / "sim-stats.json").read_text())
    assert stats["device"]["count"] == 4
    assert sim.engine.device_info() == stats["device"]
    metrics = json.loads(
        (tmp_path / "mesh" / "METRICS_tpu-seed1.json").read_text()
    )
    assert metrics["device"] == stats["device"]


def test_compile_cache_env_wins_else_fixed_in_checkout_path(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert dev.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == prev  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = dev.enable_compile_cache()
        assert path == str(REPO / ".jax_cache") == str(dev.DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert dev.enable_compile_cache() == path  # stable: no pid, no time
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dryrun_multichip_requires_existing_devices():
    graft = _load("_graft_under_test", REPO / "__graft_entry__.py")
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match="needs"):
        graft.dryrun_multichip(have * 2)
    assert len(jax.devices()) == have  # the backend was not rebuilt


def test_graft_entry_single_chip():
    graft = _load("_graft_under_test", REPO / "__graft_entry__.py")
    fn, args = graft.entry()
    out, done = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    assert not bool(done)


@pytest.mark.slow
def test_graft_dryrun_multichip():
    _load("_graft_under_test", REPO / "__graft_entry__.py").dryrun_multichip(8)
