"""Rehearsals of PHOLD over the wide-area graph and its traffic mix (ISSUE
48): the manifest entries are the issue's, found BY NAME; the built
configuration is the program's own PHOLD factory over its routed graph;
the traffic file's parameters are ``phold_m4``'s letter for letter and it
holds no analytic count; the files run ``correct`` at a rehearsal width
with both new readers reporting; the control (the oracle with the
conservative window broken) reads at least 1; each reader on a hand-made
``raw``."""

import json
import time
from pathlib import Path

import jax
import pytest

import control as control_mod
import run
from conftest import BENCH, MANIFEST
from lib import cells
from lib import trace as trace_mod

RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")
CONFIG, TRAFFIC, CELL = "phold_wan_10k", "phold_m4_wan", "phold10k_wan_m4"
CONTROL_CONFIG, CONTROL_TRAFFIC = "phold_mesh_10k", "phold_m4"
READERS = ("routed_hops_per_iter", "device_ns_per_routed_hop")
SHAPE_OPTIONS = ("tpu_lane_queue_capacity", "tpu_cross_capacity",
                 "tpu_events_per_round")


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def _file(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["horizon_sim_s"]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    for word in ("Fujimoto 1990", "test_phold.c", "tornettools"):
        assert word in cfg["source"]
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["source"] == _file("configs", CONFIG)["source"]
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200
    for word in ("phold10k_m4", "gossip10k_wan_slot", "idle"):
        assert word in w["why"]  # the controls and the idle-lane share
    pairs = [(x["config"], x["traffic"]) for x in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    units = {"routed_hops_per_iter": ("hops/iter", "higher",
                                      "program_counter"),
             "device_ns_per_routed_hop": ("ns", "lower", "host_clock")}
    for name in READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["layer"] == "lane kernel"
        assert m["moves"] == "sim_s_per_wall_s"
        assert (m["unit"], m["better"], m["source"]) == units[name]
        assert run.load_module("layer_metrics", name).UNIT == m["unit"]
    cell = cells.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}
    assert {m["name"] for m in cell.end_to_end} == {
        "sim_s_per_wall_s", "peak_hbm_mb", "setup_s"}


def test_the_mix_is_phold_m4_and_holds_no_analytic_count():
    mix, control = _file("traffic", TRAFFIC), _file("traffic", CONTROL_TRAFFIC)
    for key in ("messages", "datagram_bytes"):
        assert mix["parameters"][key] == control["parameters"][key]
    assert set(mix["parameters"]) == {"messages", "datagram_bytes"}
    assert mix["horizon_sim_s"] == control["horizon_sim_s"] == 0.5
    # path loss is the deployment's and final: only a queue overflow is
    # forbidden, and every count is the oracle's alone
    assert mix["forbid_counters"] == ["lane_drop_queue"]
    assert "expect_counters" not in mix
    # 187 800 - 188 433 records on six seeds at 97 ms (counts: the oracle
    # at full width, PERF.md 2), 189 916 - 190 555 at 98, inside the
    # runner's 200 000-record log
    assert mix["check_ms"] == 97
    assert "host_groups" not in mix and "program_options" not in mix


def test_the_configuration_is_phold_mesh_10k_on_the_routed_graph():
    from shadow_tpu.config.scenarios import (
        phold_mesh_config, routed_graph_gml,
    )

    cell = cells.load_cell(CELL)
    config, control = cell.config, _file("configs", CONTROL_CONFIG)
    p = cell.params
    assert (p["hosts"], p["graph_nodes"], p["graph_seed"], p["bandwidth"],
            p["messages"], p["datagram_bytes"]) == (
        10000, 200, 1, "1 Gbit", 4, 256)
    assert "latency" not in p
    assert "program_options" not in config
    assert "phold_shape_law" in config["program_options_why"]
    assert config["guarantees"] == control["guarantees"]
    assert list(config["reduced"]) == ["horizon_sim_s"]
    assert config["control_options"] == _file(
        "configs", "eth_gossip_wan_10k")["control_options"] == {
        "runahead": 8_000_000}
    assert config["factory"] == control["factory"]  # one PHOLD factory
    assert config["factory_args"] == {
        **{k: v for k, v in control["factory_args"].items()
           if k != "latency"},
        "graph_nodes": "{graph_nodes}", "graph_seed": "{graph_seed}"}

    want = phold_mesh_config(10000, 4, 256, bandwidth="1 Gbit",
                             graph_nodes=200, graph_seed=1)
    built = [cells.build_config(cell, seed=seed, backend="tpu",
                                stop_ns=5 * 10**8, data_dir="d")
             for seed in (48, 2**31 + 11)]
    gml = routed_graph_gml(200, 1, "1 Gbit")
    for got in built:
        # ONE network whatever the run's seed
        assert got.network.graph.inline == gml
        assert got.hosts == want.hosts
        assert got.general.stop_time == 5 * 10**8
        assert [getattr(got.experimental, k) for k in SHAPE_OPTIONS] == [
            42, 18, 2]
    assert [g.general.seed for g in built] == [49, 13]
    assert len(want.hosts) == 10000
    assert len({h.network_node_id for h in want.hosts}) == 200
    # host i is phold_mesh_10k's host i: same id order, same argument list
    one_switch = phold_mesh_config(10000, 4, 256, "10 ms", "1 Gbit")
    assert [h.processes[0].args for h in want.hosts] == [
        h.processes[0].args for h in one_switch.hosts]
    # the graph is tgen_routed_1k's and eth_gossip_wan_10k's, byte for byte
    wan = cells.build_config(cells.load_cell("gossip10k_wan_slot"), seed=1,
                             backend="tpu", stop_ns=10**9, data_dir="d")
    assert wan.network.graph.inline == gml


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, cut to 96 logical
    processes over 12 graph nodes and a check horizon the narrow log fills
    a tenth of (nothing else: messages, size and the horizon are the
    cell's)."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    cfg = _file("configs", CONFIG)
    cfg["parameters"].update(hosts=96, graph_nodes=12)
    (tmp_path / "b" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    mix = _file("traffic", TRAFFIC)
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


def test_the_files_run_correct_with_both_readers(narrow_root, monkeypatch):
    monkeypatch.setattr(trace_mod, "find_xplane", lambda _d: RECORDED)
    out = run.drive(cells.load_cell("narrow", narrow_root), 2**31 + 11, 0.5,
                    True, jax.devices()[:1], t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}
    # a few of the 2 x 96 pop slots of an iteration hold an event
    assert 0 < out["metrics"]["routed_hops_per_iter"]["value"] < 96
    assert out["metrics"]["device_ns_per_routed_hop"]["value"] > 0


def test_the_control_is_caught(narrow_root, tmp_path):
    """The oracle with the conservative window broken (8 ms windows over
    2 ms paths) differs from the sound oracle on some compared number, at
    the timed horizon and at the check's."""
    sound, control = control_mod.control_of(
        cells.load_cell("narrow", narrow_root), 48, tmp_path / "c")
    assert sound.ok and all(d == 0 for _w, d, _l in sound.rows)
    assert not control.ok and control.failures >= 1


def test_the_readers_on_a_hand_made_raw():
    raw = {"lane_iters": [1344, 1350, 1338], "rounds": [250, 250, 250],
           "device_wall_s": [1.45, 1.46, 1.44], "events_per_repeat": 984_172}
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    assert read["routed_hops_per_iter"](raw) == pytest.approx(
        3 * 984_172 / 4032)
    assert read["device_ns_per_routed_hop"](raw) == pytest.approx(
        1e9 * 4.35 / (3 * 984_172))
    # their product is the device time of an iteration, in ns
    assert (read["routed_hops_per_iter"](raw)
            * read["device_ns_per_routed_hop"](raw)) == pytest.approx(
        1e9 * 4.35 / 4032)
    # they are hops_per_iter's and device_ns_per_delivery's readers
    for name, other in zip(READERS, ("hops_per_iter",
                                     "device_ns_per_delivery")):
        assert read[name](raw) == run.load_module(
            "layer_metrics", other).read(raw)
    for n in READERS:
        assert read[n]({}) is None
        assert read[n]({"lane_iters": [], "device_wall_s": [],
                        "events_per_repeat": 0}) is None
    assert read["routed_hops_per_iter"](
        {"lane_iters": [0], "events_per_repeat": 5}) is None
