"""Both runners end to end at tiny sizes, from configuration and traffic
files made in a temporary directory (a cell is data), and the ways
``correct`` has to come out false."""

import time
from pathlib import Path

import pytest

import run
from lib import cells
from lib import trace as trace_mod

RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")


def drive(root, name, cpu_devices, seconds=1.0, trace=False, seed=7):
    cell = cells.load_cell(name, root)
    return run.drive(cell, seed, seconds, trace, cpu_devices,
                     t_start=time.perf_counter())


@pytest.fixture
def recorded_trace(monkeypatch):
    """XLA:CPU writes no device plane, so a rehearsal of ``--trace 1``
    reads the trace recorded on the chip in place of its own."""
    monkeypatch.setattr(trace_mod, "find_xplane", lambda _d: RECORDED)


@pytest.fixture(scope="module")
def native_build():
    from runners.hybrid import build_native

    build_native(lambda _m: None)


def test_fused_mesh_cell_from_new_files_alone(tiny_root, cpu_devices):
    out = drive(tiny_root, "tiny_udp", cpu_devices)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"sim_s_per_wall_s", "setup_s"}
    assert out["metrics"]["sim_s_per_wall_s"]["unit"] == "sim_s/wall_s"
    assert out["metrics"]["sim_s_per_wall_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"  # a rehearsal says so
    assert "breakdown" not in out


def test_fused_mesh_traced_run_reports_per_layer_metrics(
        tiny_root, cpu_devices, recorded_trace):
    out = drive(tiny_root, "tiny_udp", cpu_devices, trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "trace_compile_s", "compiles_in_window", "host_share.fused",
        "iters_per_window", "device_ms_per_iter", "device_idle_share"}
    assert out["metrics"]["iters_per_window"]["value"] == 1.0
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"][0][0] == "sort.8"


def test_a_factory_configuration_on_four_devices(tiny_root, tmp_path):
    """The rows under Open questions that these runners must serve
    unchanged: a columnar configuration named by a ``factory`` field, and
    a ``chips: 4`` cell sharded with ``parallel.make_mesh`` (here on four
    virtual XLA:CPU devices) — added as new files and entries alone."""
    import json
    import shutil

    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    root = tmp_path / "r"
    shutil.copytree(tiny_root, root)
    col = {
        "name": "tgen_mesh_col", "runner": "fused_mesh",
        "parameters": {"hosts": 256, "datagram_bytes": 1428},
        "factory": "shadow_tpu.config.columnar:columnar_mesh_config",
        "factory_args": {"n_hosts": "{hosts}", "size": "{datagram_bytes}",
                         "queue_capacity": 16, "pops_per_round": 2},
        "program_options": {"tpu_cross_capacity": 8},
    }
    (root / "b" / "configs" / "tgen_mesh_col.json").write_text(json.dumps(col))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tgen_mesh_col",
                           "file": "b/configs/tgen_mesh_col.json"})
    man["workloads"].append({"name": "col_x4", "config": "tgen_mesh_col",
                             "traffic": "udp", "chips": 4})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    out = drive(root, "col_x4", jax.devices()[:4])
    assert out["correct"] is True
    assert out["device"]["count"] == 4


def test_mixed_cell_accounts_for_every_flow(tiny_root, cpu_devices):
    out = drive(tiny_root, "tiny_mixed", cpu_devices, seconds=0.5)
    assert out["correct"] is True and out["failed"] == 0


@pytest.mark.parametrize("log_capacity_of_the_broken", ["timed", "check"])
def test_a_wrong_counter_makes_correct_false(
        tiny_root, cpu_devices, monkeypatch, log_capacity_of_the_broken):
    """One program broken underneath — the lane result altered where it is
    produced (one datagram's bytes too many) — in ONLY the timed engine
    (built with ``log_capacity=0``) or ONLY the check engine (log on):
    each is held to the oracle on its own."""
    from shadow_tpu.backend.tpu_engine import TpuEngine

    collect = TpuEngine.collect
    timed = log_capacity_of_the_broken == "timed"

    def broken(self, state, wall):
        res = collect(self, state, wall)
        if (self.params.log_capacity == 0) == timed:
            res.counters["tgen_recv_bytes"] += 1428
        return res

    monkeypatch.setattr(TpuEngine, "collect", broken)
    out = drive(tiny_root, "tiny_udp", cpu_devices)
    # every repeat is wrong alike, so none is a failed repeat: it is the
    # comparison with the oracle that catches it
    assert out["correct"] is False and out["failed"] == 0


def test_a_fault_late_in_the_timed_horizon_makes_correct_false(
        tiny_root, cpu_devices, monkeypatch, tmp_path):
    """A fault the check horizon never reaches (here: the timed program
    stops one window early, as a time wrap or a lost late event would):
    the timed object's own result is compared over its whole horizon."""
    import json
    import shutil

    from shadow_tpu.backend.tpu_engine import TpuEngine

    root = tmp_path / "r"
    shutil.copytree(tiny_root, root)
    path = root / "b" / "traffic" / "udp.json"
    mix = json.loads(path.read_text())
    mix["expect_counters"] = {}  # leave the oracle alone to catch it
    path.write_text(json.dumps(mix))
    init = TpuEngine.__init__

    def short(self, cfg, *a, **kw):
        if kw.get("log_capacity") == 0:
            cfg.general.stop_time -= 10_000_000
        init(self, cfg, *a, **kw)

    monkeypatch.setattr(TpuEngine, "__init__", short)
    out = drive(root, "tiny_udp", cpu_devices)
    assert out["correct"] is False and out["failed"] == 0


def test_a_repeat_unlike_the_first_is_a_failed_repeat(
        tiny_root, cpu_devices, monkeypatch):
    from shadow_tpu.backend.tpu_engine import TpuEngine

    collect, calls = TpuEngine.collect, []

    def flaky(self, state, wall):
        res = collect(self, state, wall)
        calls.append(1)
        if len(calls) == 3:  # warm-up, repeat 1, then this one
            res.counters["lane_iters"] += 1
        return res

    monkeypatch.setattr(TpuEngine, "collect", flaky)
    out = drive(tiny_root, "tiny_udp", cpu_devices)
    assert out["correct"] is False and out["failed"] >= 1


def test_an_expected_counter_that_is_short_makes_correct_false(
        tiny_root, cpu_devices, tmp_path):
    """A flow that did not finish: the mix expects every stream complete."""
    import json
    import shutil

    root = tmp_path / "r"
    shutil.copytree(tiny_root, root)
    path = root / "b" / "traffic" / "mixed_tcp.json"
    mix = json.loads(path.read_text())
    mix["horizon_sim_s"] = 0.5  # too short for 2 MB
    path.write_text(json.dumps(mix))
    out = drive(root, "tiny_mixed", cpu_devices, seconds=0.2)
    assert out["correct"] is False


def test_hybrid_cell_end_to_end(tiny_root, cpu_devices, native_build):
    out = drive(tiny_root, "tiny_chains", cpu_devices, seconds=2.0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 11  # origin + 2 chains x 3 relays + 4 clients
    assert set(out["metrics"]) == {
        "sim_s_per_wall_s", "sim10ms_wall_p95_ms", "setup_s"}
    assert out["metrics"]["sim10ms_wall_p95_ms"]["value"] > 0


def test_hybrid_traced_run(tiny_root, cpu_devices, native_build,
                           recorded_trace):
    out = drive(tiny_root, "tiny_chains", cpu_devices, seconds=2.0, trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "trace_compile_s", "compiles_in_window", "turns_per_sim_s",
        "device_sync_ms_per_turn", "turn_wall_p95_ms",
        "syscall_service_share", "device_idle_share"}
    assert out["metrics"]["turns_per_sim_s"]["value"] > 0
    assert out["device"]["window_s"] >= 0.2  # the traced span's wall


def test_a_changed_output_file_makes_correct_false(
        tiny_root, cpu_devices, native_build, monkeypatch):
    """An answer altered where it is produced: one managed process's
    stdout gains a byte after the hybrid engine has run."""
    from shadow_tpu.backend.hybrid import MpHybridEngine

    engine_run = MpHybridEngine.run

    def broken(self, on_window=None):
        res = engine_run(self, on_window=on_window)
        outs = sorted(Path(self.cfg.general.data_directory).rglob("*.stdout"))
        assert outs
        with open(outs[0], "ab") as f:
            f.write(b"x")
        return res

    monkeypatch.setattr(MpHybridEngine, "run", broken)
    out = drive(tiny_root, "tiny_chains", cpu_devices, seconds=2.0)
    assert out["correct"] is False and out["failed"] >= 1


@pytest.mark.parametrize("name", ["tiny_udp", "tiny_mixed", "tiny_chains"])
def test_the_control_comes_out_as_not_correct(tiny_root, tmp_path, name,
                                              native_build):
    """The reference with the conservative window broken (control.py)."""
    import control

    cell = cells.load_cell(name, tiny_root)
    sound, ctl = control.control_of(cell, 3, tmp_path)
    assert sound.ok
    assert not ctl.ok and ctl.failures >= 1
