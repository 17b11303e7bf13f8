"""Rehearsals of the wide-area gossip configuration and its traffic mix
(ISSUE 41): the manifest entries are the issue's, found BY NAME; the built
configuration is the program's own gossip factory over its routed graph;
the traffic file's two expressions evaluate to the analytic counts and its
parameters are ``slot_3x8``'s letter for letter; the files run ``correct``
at a rehearsal width with both new readers reporting; each reader on a
hand-made ``raw``."""

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
CONFIG, TRAFFIC, CELL = ("eth_gossip_wan_10k", "slot_3x8_wan",
                         "gossip10k_wan_slot")
CONTROL_CONFIG, CONTROL_TRAFFIC = "eth_gossip_10k", "slot_3x8"
READERS = ("iters_per_round", "device_us_per_iter")
SHAPE_OPTIONS = ("tpu_lane_queue_capacity", "tpu_cross_capacity",
                 "tpu_events_per_round")
COUNTS = {"gossip_sends": 1_680_024, "gossip_first": 239_976}


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def _file(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["messages_per_slot"]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    for word in ("gossipsub-v1.0.md", "p2p-interface.md", "tornettools"):
        assert word in cfg["source"]
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["source"] == _file("configs", CONFIG)["source"]
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    units = {"iters_per_round": ("iters/round", "lower", "program_counter"),
             "device_us_per_iter": ("us", "lower", "host_clock")}
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


def test_the_mix_is_slot_3x8_and_holds_what_loss_leaves_analytic():
    mix, control = _file("traffic", TRAFFIC), _file("traffic", CONTROL_TRAFFIC)
    assert mix["parameters"] == control["parameters"]
    assert mix["horizon_sim_s"] == control["horizon_sim_s"] == 12
    # path loss is the deployment's: only a queue overflow is forbidden,
    # and the duplicate count moves with the run's loss draws
    assert mix["forbid_counters"] == ["lane_drop_queue"]
    assert sorted(mix["expect_counters"]) == sorted(COUNTS)
    for key in COUNTS:
        assert mix["expect_counters"][key] == control["expect_counters"][key]
    p = cells.load_cell(CELL).params
    assert {k: cells.subst(v, p)
            for k, v in mix["expect_counters"].items()} == COUNTS
    # the check horizon ends inside the first burst's flood: 181 454 -
    # 182 212 records on six seeds (counts: the oracle at full width,
    # PERF.md 2), 195 853 at 1 093, inside the runner's 200 000-record log
    assert mix["check_ms"] == 1092
    assert "host_groups" not in mix and "program_options" not in mix


def test_the_configuration_is_eth_gossip_10k_on_the_routed_graph():
    from shadow_tpu.config.scenarios import (
        gossip_mesh_config, routed_graph_gml,
    )

    cell = cells.load_cell(CELL)
    config, control = cell.config, _file("configs", CONTROL_CONFIG)
    p = cell.params
    assert (p["hosts"], p["degree"], p["mesh_seed"], p["graph_nodes"],
            p["graph_seed"], p["bandwidth"]) == (10000, 8, 1, 200, 1, "1 Gbit")
    assert "latency" not in p
    assert "program_options" not in config
    assert "gossip_shape_law" in config["program_options_why"]
    assert config["guarantees"] == control["guarantees"]
    assert list(config["reduced"]) == ["messages_per_slot"]
    assert config["control_options"] == {"runahead": 8_000_000}
    assert config["factory"] == control["factory"]  # one gossip factory
    # every assumption of the one-switch deployment but the switch itself,
    # then the graph's
    kept = [a for a in control["assumed"]
            if not a.startswith("one graph node")]
    assert config["assumed"][:len(kept)] == kept
    assert len(config["assumed"]) >= len(kept) + 6

    want = gossip_mesh_config(10000, 8, 1, ("1 s", "5 s", "9 s"), 8, 512,
                              bandwidth="1 Gbit", graph_nodes=200,
                              graph_seed=1)
    built = [cells.build_config(cell, seed=seed, backend="tpu",
                                stop_ns=12 * 10**9, data_dir="d")
             for seed in (41, 2**31 + 11)]
    gml = routed_graph_gml(200, 1, "1 Gbit")
    for got in built:
        # ONE network whatever the run's seed
        assert got.network.graph.inline == gml
        assert got.hosts == want.hosts
        assert [getattr(got.experimental, k) for k in SHAPE_OPTIONS] == [
            108, 16, 2]
    assert [g.general.seed for g in built] == [42, 13]
    assert len(want.hosts) == 10000
    assert len({h.network_node_id for h in want.hosts}) == 200
    # host i is eth_gossip_10k's host i: same id order, same argument list
    one_switch = gossip_mesh_config(10000, 8, 1, ("1 s", "5 s", "9 s"), 8,
                                    512, "10 ms", "1 Gbit")
    assert [h.processes[0].args for h in want.hosts] == [
        h.processes[0].args for h in one_switch.hosts]


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, cut to 96 nodes over
    12 graph nodes and a check horizon that holds the first burst whole
    (nothing else: degree, bursts, messages and the expressions are the
    cell's)."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    cfg = _file("configs", CONFIG)
    cfg["parameters"].update(hosts=96, graph_nodes=12)
    (tmp_path / "b" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    mix = _file("traffic", TRAFFIC)
    mix["check_ms"] = 1400
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
    # a 2 ms window takes a few passes of the body, never under one
    assert 1 <= out["metrics"]["iters_per_round"]["value"] < 20
    assert out["metrics"]["device_us_per_iter"]["value"] > 0


def test_the_readers_on_a_hand_made_raw():
    raw = {"lane_iters": [1400, 1404, 1396], "rounds": [220, 220, 220],
           "device_wall_s": [7.0, 7.02, 6.98]}
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    assert read["iters_per_round"](raw) == pytest.approx(4200 / 660)
    assert read["device_us_per_iter"](raw) == pytest.approx(
        1e6 * 21.0 / 4200)
    # rounds x iters_per_round x device_us_per_iter is the device time
    assert (660 * read["iters_per_round"](raw)
            * read["device_us_per_iter"](raw)) == pytest.approx(21.0e6)
    # the second is device_ms_per_iter in another unit
    ms = run.load_module("layer_metrics", "device_ms_per_iter").read(raw)
    assert read["device_us_per_iter"](raw) == pytest.approx(1e3 * ms)
    for n in READERS:
        assert read[n]({}) is None
        assert read[n]({"lane_iters": [], "rounds": [],
                        "device_wall_s": []}) is None
    assert read["iters_per_round"]({"lane_iters": [5], "rounds": [0]}) is None
    assert read["device_us_per_iter"](
        {"lane_iters": [0], "device_wall_s": [1.0]}) is None
