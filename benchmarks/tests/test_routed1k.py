"""Rehearsals of the routed, lossy all-TCP configuration and its traffic mix
(ISSUE 32): the manifest entries are the issue's; the built configuration is
the program's own factory at the issue's parameters, one network whatever
the run's seed; the files run ``correct`` at a rehearsal width with both new
readers reporting; each reader on a hand-made ``raw``; a wrong count makes
``correct`` false."""

import json
import time
from pathlib import Path

import jax
import pytest

import run
from conftest import BENCH, MANIFEST
from lib import cells
from lib import trace as trace_mod

RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")
CONFIG, TRAFFIC, CELL = "tgen_routed_1k", "tcp_pairs_1mib", "routed1k_tcp_loss"
READERS = ("windows_per_sim_s", "device_us_per_window")


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["horizon_sim_s"] and len(cfg["source"]) <= 200
    assert "BASELINE.md north-star config #3" in cfg["source"]
    assert MANIFEST["configs"][-1] is cfg
    w = _entry("workloads", CELL)
    assert MANIFEST["workloads"][-1] is w
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert [m["name"] for m in MANIFEST["per_layer"][-2:]] == list(READERS)
    for name in READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["layer"] == "lane kernel"
        assert m["moves"] == "sim_s_per_wall_s"
    # what the cell reports traced: its two readers and the three metrics
    # that list no cells
    assert {m["name"] for m in cells.load_cell(CELL).per_layer} == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}


def test_the_cell_is_the_programs_routed_network():
    from shadow_tpu.config.scenarios import routed_tcp_mesh_config

    cell = cells.load_cell(CELL)
    mix, p = cell.traffic, cell.params
    assert (p["hosts"], p["graph_nodes"], p["graph_seed"], p["bandwidth"]) == (
        1000, 200, 1, "1 Gbit")
    assert (p["stream_pairs"], p["stream_bytes"], p["start_spread_ms"]) == (
        500, 1 << 20, 1000)
    assert mix["horizon_sim_s"] == 5 and mix["check_ms"] % 50 == 0
    assert mix["check_ms"] >= 300
    assert mix["forbid_counters"] == ["lane_drop_queue"]
    assert "expect_counters" not in mix and "host_groups" not in mix
    assert cell.config["guarantees"] == json.loads(
        (BENCH / "configs" / "tgen_mesh_10k.json").read_text())["guarantees"]
    assert list(cell.config["reduced"]) == ["horizon_sim_s"]
    assert cell.config["control_options"]["runahead"] > 2_000_000

    want = routed_tcp_mesh_config(1000, 200, graph_seed=1,
                                  stream_bytes=1 << 20, start_spread_ms=1000,
                                  bandwidth="1 Gbit")
    built = [cells.build_config(cell, seed=seed, backend="tpu",
                                stop_ns=5 * 10**9, data_dir="d")
             for seed in (41, 2**31 + 11)]
    for got in built:
        assert got.network.graph.inline == want.network.graph.inline
        assert list(got.hosts) == list(want.hosts)
        assert got.general.stop_time == 5 * 10**9
        for key, val in cell.config["program_options"].items():
            assert getattr(got.experimental, key) == val
    assert [g.general.seed for g in built] == [42, 13]
    clients = [h for h in want.hosts
               if h.processes[0].path == "stream-client"]
    assert len(want.hosts) == 1000 and len(clients) == 500
    assert len({h.network_node_id for h in want.hosts}) > 190


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, cut to 24 hosts on 8
    nodes and flows of 60 kB over 0.5 sim-s (nothing else; the profiler is
    slow on XLA:CPU, so the traced repeat has to be short)."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg["parameters"].update(hosts=24, graph_nodes=8)
    (tmp_path / "b" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
    mix.update(horizon_sim_s=0.5, check_ms=300)
    mix["parameters"].update(stream_bytes=60_000, start_spread_ms=100)
    (tmp_path / "b" / "traffic" / f"{TRAFFIC}.json").write_text(
        json.dumps(mix))
    man = {k: MANIFEST[k] for k in ("command", "run_seconds", "end_to_end")}
    man["paths"] = ["b"]
    man["configs"] = [{**_entry("configs", CONFIG),
                       "file": f"b/configs/{CONFIG}.json"}]
    man["workloads"] = [{"name": "narrow", "config": CONFIG,
                         "traffic": TRAFFIC, "chips": 1}]
    man["end_to_end"] = [m for m in MANIFEST["end_to_end"]
                         if "workloads" not in m]
    man["per_layer"] = [
        {**m, "workloads": ["narrow"]} if m["name"] in READERS else m
        for m in MANIFEST["per_layer"]
        if "workloads" not in m or m["name"] in READERS]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path


def _drive(root, trace=False, seed=2**31 + 11):
    return run.drive(cells.load_cell("narrow", root), seed, 0.5, trace,
                     jax.devices()[:1], t_start=time.perf_counter())


def test_the_files_run_correct_with_both_readers(narrow_root, monkeypatch):
    monkeypatch.setattr(trace_mod, "find_xplane", lambda _d: RECORDED)
    out = _drive(narrow_root, trace=True)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}
    # a window opens at the next event and lasts 2 ms: at most 500 a sim-s
    assert 0 < out["metrics"]["windows_per_sim_s"]["value"] <= 500
    assert out["metrics"]["device_us_per_window"]["value"] > 0


def test_a_wrong_count_makes_correct_false(narrow_root):
    path = narrow_root / "b" / "traffic" / f"{TRAFFIC}.json"
    mix = json.loads(path.read_text())
    mix["expect_counters"] = {"stream_complete": "{stream_pairs + 1}"}
    path.write_text(json.dumps(mix))
    out = _drive(narrow_root)
    assert out["correct"] is False and out["failed"] == 0
    assert set(out["metrics"]) == {"sim_s_per_wall_s", "setup_s"}


def test_the_readers_on_a_hand_made_raw():
    raw = {"horizon_sim_s": 5.0, "rounds": [2250, 2250, 2250, 2250],
           "device_wall_s": [2.25, 2.25, 2.25, 2.25]}
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    # 9 000 windows over 4 repeats of 5 sim-s; 9 s of device program
    assert read["windows_per_sim_s"](raw) == pytest.approx(450.0)
    assert read["device_us_per_window"](raw) == pytest.approx(1000.0)
    for n in READERS:
        assert read[n]({}) is None
        assert read[n]({"rounds": [], "device_wall_s": []}) is None
    assert read["windows_per_sim_s"]({"rounds": [10]}) is None
    assert read["device_us_per_window"](
        {"rounds": [0], "device_wall_s": [1.0]}) is None
