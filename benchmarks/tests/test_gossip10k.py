"""Rehearsals of the gossip configuration and its traffic mix (ISSUE 39):
the manifest entries are the issue's, found BY NAME; the built configuration
is the program's own factory at the issue's parameters, its shapes the
factory's law and no ``program_options``; the traffic file's expressions
evaluate to the three analytic counts; the files run ``correct`` at a
rehearsal width with both new readers reporting; each reader on a hand-made
``raw``; a wrong degree in the expected counts makes ``correct`` false."""

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
CONFIG, TRAFFIC, CELL = "eth_gossip_10k", "slot_3x8", "gossip10k_slot"
READERS = ("sends_per_iter", "device_ns_per_send")
SHAPE_OPTIONS = ("tpu_lane_queue_capacity", "tpu_cross_capacity",
                 "tpu_events_per_round")
COUNTS = {"gossip_sends": 1_680_024, "gossip_first": 239_976,
          "gossip_duplicates": 1_440_048}


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["messages_per_slot"]
    assert len(cfg["source"]) <= 200
    assert "gossipsub-v1.0.md" in cfg["source"]
    assert "p2p-interface.md" in cfg["source"]
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    units = {"sends_per_iter": ("sends/iter", "higher", "program_counter"),
             "device_ns_per_send": ("ns", "lower", "host_clock")}
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


def test_the_cell_is_the_programs_gossip_network():
    from shadow_tpu.config.scenarios import (
        gossip_mesh_config, gossip_shape_law,
    )

    cell = cells.load_cell(CELL)
    mix, p = cell.traffic, cell.params
    assert (p["hosts"], p["degree"], p["mesh_seed"], p["latency"],
            p["bandwidth"]) == (10000, 8, 1, "10 ms", "1 Gbit")
    assert (p["bursts"], p["burst_times"], p["messages"],
            p["datagram_bytes"]) == (3, ["1 s", "5 s", "9 s"], 8, 512)
    assert mix["horizon_sim_s"] == 12 and mix["check_ms"] == 1052
    assert mix["forbid_counters"] == ["lane_drop_queue", "lane_drop_loss"]
    # the traffic file's expressions are the analytic counts
    assert {k: cells.subst(v, p)
            for k, v in mix["expect_counters"].items()} == COUNTS
    assert "host_groups" not in mix
    # the three shapes are the factory's: the file sets none, and says why
    assert "program_options" not in cell.config
    assert "gossip_shape_law" in cell.config["program_options_why"]
    assert "program_options" not in mix
    assert cell.config["guarantees"] == json.loads(
        (BENCH / "configs" / "phold_mesh_10k.json").read_text())["guarantees"]
    assert list(cell.config["reduced"]) == ["messages_per_slot"]
    assert cell.config["control_options"] == {"runahead": 20_000_000}
    assert len(cell.config["assumed"]) >= 8

    want = gossip_mesh_config(10000, 8, 1, ("1 s", "5 s", "9 s"), 8, 512,
                              "10 ms", "1 Gbit")
    built = [cells.build_config(cell, seed=seed, backend="tpu",
                                stop_ns=12 * 10**9, data_dir="d")
             for seed in (41, 2**31 + 11)]
    assert gossip_shape_law(8, 8) == (108, 16)
    for got in built:
        assert got.network.graph.inline == want.network.graph.inline
        assert list(got.hosts) == list(want.hosts)
        assert got.general.stop_time == 12 * 10**9
        assert [getattr(got.experimental, k) for k in SHAPE_OPTIONS] == [
            108, 16, 2]
    assert [g.general.seed for g in built] == [42, 13]
    assert len(want.hosts) == 10000
    # one host group, one argument list for all 10 000 nodes
    assert {(h.network_node_id, h.processes[0].path,
             tuple(h.processes[0].args)) for h in want.hosts} == {
        (0, "gossip", ("--degree", "8", "--mesh-seed", "1", "--bursts",
                       "1000000000 ns,5000000000 ns,9000000000 ns",
                       "--messages", "8", "--size", "512"))}
    # the check horizon holds hops 0-4 of the first burst whole: 64 + 448 +
    # 3 136 + 21 497 + 129 409 records on this mesh (a count: the oracle at
    # full width, PERF.md 2), inside the runner's fixed check log
    assert 64 + 448 + 3136 + 21497 + 129409 == 154_554 <= 200_000


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, cut to 96 nodes and a
    check horizon that holds the first burst whole (nothing else: degree,
    bursts, messages and the expressions are the cell's)."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg["parameters"].update(hosts=96)
    (tmp_path / "b" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
    mix["check_ms"] = 1200
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
    # 24 x (8 + 95 x 7) sends over the repeat's iterations; an iteration
    # offers 2 x 96 pop slots and a send costs its receiver two of them
    sends = out["metrics"]["sends_per_iter"]["value"]
    assert 0 < sends <= 96
    assert out["metrics"]["device_ns_per_send"]["value"] > 0


def test_a_wrong_expected_count_makes_correct_false(narrow_root):
    path = narrow_root / "b" / "traffic" / f"{TRAFFIC}.json"
    mix = json.loads(path.read_text())
    mix["expect_counters"]["gossip_first"] = "{bursts * messages * hosts}"
    path.write_text(json.dumps(mix))
    out = _drive(narrow_root)
    assert out["correct"] is False and out["failed"] == 0
    assert set(out["metrics"]) == {"sim_s_per_wall_s", "setup_s"}


def test_the_readers_on_a_hand_made_raw():
    raw = {"events_per_repeat": 1_680_024,
           "lane_iters": [362, 362, 362, 362],
           "device_wall_s": [0.42, 0.42, 0.42, 0.42]}
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    # 4 repeats of 1 680 024 sends over 1 448 iterations; 1.68 s of device
    assert read["sends_per_iter"](raw) == pytest.approx(1_680_024 / 362)
    assert read["device_ns_per_send"](raw) == pytest.approx(
        1e9 * 0.42 / 1_680_024)
    # their product is the device time of an iteration
    assert (read["sends_per_iter"](raw) * read["device_ns_per_send"](raw)
            == pytest.approx(1e9 * 0.42 / 362))
    for n in READERS:
        assert read[n]({}) is None
        assert read[n]({"lane_iters": [], "device_wall_s": [],
                        "events_per_repeat": 5}) is None
        assert read[n]({"lane_iters": [7], "device_wall_s": [1.0],
                        "events_per_repeat": 0}) is None
    assert read["sends_per_iter"](
        {"lane_iters": [0], "events_per_repeat": 5}) is None
