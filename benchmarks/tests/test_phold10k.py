"""Rehearsals of the PHOLD configuration and its traffic mix (ISSUE 35): the
manifest entries are the issue's, found BY NAME; the built configuration is
the program's own factory at the issue's parameters, its shapes the
factory's law and no ``program_options``; the files run ``correct`` at a
rehearsal width with both new readers reporting; each reader on a hand-made
``raw``; a wrong ``hops_per_message`` makes ``correct`` false."""

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
CONFIG, TRAFFIC, CELL = "phold_mesh_10k", "phold_m4", "phold10k_m4"
READERS = ("hops_per_iter", "device_ns_per_hop")
SHAPE_OPTIONS = ("tpu_lane_queue_capacity", "tpu_cross_capacity",
                 "tpu_events_per_round")


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["horizon_sim_s"] and len(cfg["source"]) <= 200
    assert "Fujimoto 1990" in cfg["source"]
    assert "src/test/phold/test_phold.c" in cfg["source"]
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    units = {"hops_per_iter": ("hops/iter", "higher", "program_counter"),
             "device_ns_per_hop": ("ns", "lower", "host_clock")}
    for name in READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["layer"] == "lane kernel"
        assert m["moves"] == "sim_s_per_wall_s"
        assert (m["unit"], m["better"], m["source"]) == units[name]
        assert run.load_module("layer_metrics", name).UNIT == m["unit"]
    # what the cell reports traced: its two readers and the three metrics
    # that list no cells
    cell = cells.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}
    assert {m["name"] for m in cell.end_to_end} == {
        "sim_s_per_wall_s", "peak_hbm_mb", "setup_s"}


def test_the_cell_is_the_programs_phold_network():
    from shadow_tpu.config.scenarios import (
        phold_mesh_config, phold_shape_law,
    )

    cell = cells.load_cell(CELL)
    mix, p = cell.traffic, cell.params
    assert (p["hosts"], p["latency"], p["bandwidth"]) == (
        10000, "10 ms", "1 Gbit")
    assert (p["messages"], p["datagram_bytes"], p["hops_per_message"]) == (
        4, 256, 49)
    assert mix["horizon_sim_s"] == 0.5 and mix["check_ms"] == 50
    assert mix["forbid_counters"] == ["lane_drop_queue"]
    assert mix["expect_counters"] == {
        "phold_hops": "{hosts * messages * hops_per_message}"}
    assert cells.subst(mix["expect_counters"]["phold_hops"], p) == 1_960_000
    assert "host_groups" not in mix
    # the three shapes are the factory's: the file sets none, and says why
    assert "program_options" not in cell.config
    assert "phold_shape_law" in cell.config["program_options_why"]
    assert "program_options" not in mix
    assert cell.config["guarantees"] == json.loads(
        (BENCH / "configs" / "tgen_mesh_10k.json").read_text())["guarantees"]
    assert list(cell.config["reduced"]) == ["horizon_sim_s"]
    assert cell.config["control_options"] == {"runahead": 20_000_000}
    assert cell.config["factory_args"] == {
        "n_hosts": "{hosts}", "messages": "{messages}",
        "size": "{datagram_bytes}", "latency": "{latency}",
        "bandwidth": "{bandwidth}"}

    want = phold_mesh_config(10000, 4, 256, "10 ms", "1 Gbit")
    built = [cells.build_config(cell, seed=seed, backend="tpu",
                                stop_ns=5 * 10**8, data_dir="d")
             for seed in (41, 2**31 + 11)]
    queue, cross = phold_shape_law(10000, 4)
    for got in built:
        assert got.network.graph.inline == want.network.graph.inline
        assert list(got.hosts) == list(want.hosts)
        assert got.general.stop_time == 5 * 10**8
        assert [getattr(got.experimental, k) for k in SHAPE_OPTIONS] == [
            queue, cross, 2]
    assert [g.general.seed for g in built] == [42, 13]
    assert len(want.hosts) == 10000
    assert {(h.network_node_id, h.processes[0].path,
             tuple(h.processes[0].args)) for h in want.hosts} == {
        (0, "phold", ("--messages", "4", "--size", "256"))}
    # the check horizon's records fit the runner's fixed check log: 4 hops
    # of every message
    assert 4 * 40_000 == 160_000 <= 200_000


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, cut to 128 logical
    processes (nothing else: the horizon, the check and the counts are the
    cell's)."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg["parameters"].update(hosts=128)
    (tmp_path / "b" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
    (tmp_path / "b" / "traffic" / f"{TRAFFIC}.json").write_text(
        json.dumps(mix))
    man = {k: MANIFEST[k] for k in ("command", "run_seconds")}
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
    # 128 x 4 messages x 49 hops over the repeat's iterations; a hop is two
    # pops, an iteration offers 2 x 128 of them
    hops = out["metrics"]["hops_per_iter"]["value"]
    assert 0 < hops <= 128
    assert out["metrics"]["device_ns_per_hop"]["value"] > 0


def test_a_wrong_hops_per_message_makes_correct_false(narrow_root):
    path = narrow_root / "b" / "traffic" / f"{TRAFFIC}.json"
    mix = json.loads(path.read_text())
    mix["parameters"]["hops_per_message"] = 50
    path.write_text(json.dumps(mix))
    out = _drive(narrow_root)
    assert out["correct"] is False and out["failed"] == 0
    assert set(out["metrics"]) == {"sim_s_per_wall_s", "setup_s"}


def test_the_readers_on_a_hand_made_raw():
    raw = {"events_per_repeat": 1_960_000,
           "lane_iters": [1000, 1040, 1040, 1000],
           "device_wall_s": [0.98, 0.98, 0.98, 0.98]}
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    # 4 repeats of 1.96 M hops over 4 080 iterations; 3.92 s of device
    assert read["hops_per_iter"](raw) == pytest.approx(4 * 1_960_000 / 4080)
    assert read["device_ns_per_hop"](raw) == pytest.approx(500.0)
    # their product is the device time of an iteration
    assert (read["hops_per_iter"](raw) * read["device_ns_per_hop"](raw)
            == pytest.approx(1e9 * 3.92 / 4080))
    for n in READERS:
        assert read[n]({}) is None
        assert read[n]({"lane_iters": [], "device_wall_s": [],
                        "events_per_repeat": 5}) is None
        assert read[n]({"lane_iters": [7], "device_wall_s": [1.0],
                        "events_per_repeat": 0}) is None
    assert read["hops_per_iter"](
        {"lane_iters": [0], "events_per_repeat": 5}) is None
