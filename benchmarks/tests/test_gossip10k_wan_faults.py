"""Rehearsals of the faulted wide-area gossip configuration and its traffic
mix (ISSUE 45): the manifest entries are the issue's, found BY NAME; the
built configuration is ``eth_gossip_wan_10k``'s letter for letter plus the
factory's ``slot_chaos`` schedule; the mix is ``slot_3x8_wan``'s parameters
and holds no analytic count; at a rehearsal width the files run ``correct``
through the runner's ``precompile=True`` warm-up, the check horizon crosses
the schedule's first two epochs, and the oracle WITHOUT the schedule fails
the comparison; each new reader on a hand-made ``raw``."""

import json
import time
from pathlib import Path

import jax
import pytest

import run
from conftest import BENCH, MANIFEST
from lib import cells, compare
from lib import trace as trace_mod

RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")
CONFIG, TRAFFIC, CELL = ("eth_gossip_wan_faults_10k", "slot_3x8_faults",
                         "gossip10k_wan_faults_slot")
CONTROL_CONFIG, CONTROL_TRAFFIC, CONTROL_CELL = (
    "eth_gossip_wan_10k", "slot_3x8_wan", "gossip10k_wan_slot")
READERS = ("device_programs_per_repeat", "between_programs_ms")
EPOCHS_MS = [900, 1060, 2000, 4900, 7000]
MS = 1_000_000


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def _file(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["messages_per_slot"]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    for word in ("ethpandaops/attacknet", "NetworkChaos", "eth_gossip_wan_10k"):
        assert word in cfg["source"]
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["source"] == _file("configs", CONFIG)["source"]
    assert MANIFEST["configs"][-1] == cfg  # appended, nothing moved
    w = _entry("workloads", CELL)
    assert MANIFEST["workloads"][-1] == w
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    units = {"device_programs_per_repeat": "count",
             "between_programs_ms": "ms"}
    assert [m["name"] for m in MANIFEST["per_layer"][-2:]] == list(READERS)
    for name in READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["layer"] == "drivers"
        assert m["moves"] == "sim_s_per_wall_s"
        assert (m["unit"], m["better"], m["source"]) == (
            units[name], "lower", "device_trace")
        assert run.load_module("layer_metrics", name).UNIT == m["unit"]
    cell = cells.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}
    assert {m["name"] for m in cell.end_to_end} == {
        "sim_s_per_wall_s", "peak_hbm_mb", "setup_s"}


def test_the_mix_is_slot_3x8_wan_and_holds_no_analytic_count():
    mix, control = _file("traffic", TRAFFIC), _file("traffic", CONTROL_TRAFFIC)
    assert mix["parameters"] == control["parameters"]
    assert mix["horizon_sim_s"] == control["horizon_sim_s"] == 12
    assert mix["forbid_counters"] == ["lane_drop_queue"]
    assert "expect_counters" not in mix  # a partitioned node misses messages
    # the record-order check crosses the 900 and 1 060 ms epochs and ends
    # inside the first flood: 175 198 - 178 151 oracle records on six
    # seeds at full width (counts, PERF.md 2), 188 395 - 191 608 at 1 094
    assert mix["check_ms"] == 1093 >= EPOCHS_MS[1] + 1
    assert "host_groups" not in mix and "program_options" not in mix


def test_the_configuration_is_eth_gossip_wan_10k_under_the_schedule():
    from shadow_tpu.config.scenarios import gossip_mesh_config
    from shadow_tpu.faults.schedule import parse_event

    cell, control = cells.load_cell(CELL), cells.load_cell(CONTROL_CELL)
    config, calm = cell.config, control.config
    extra = {"faults": "slot_chaos", "fault_seed": 1}
    assert config["parameters"] == {**calm["parameters"], **extra}
    assert config["factory"] == calm["factory"]  # one gossip factory
    assert config["factory_args"] == {
        **calm["factory_args"], "faults": "{faults}",
        "fault_seed": "{fault_seed}"}
    assert "program_options" not in config
    assert config["control_options"] == calm["control_options"]
    assert list(config["reduced"]) == ["messages_per_slot"]
    assert config["reduced"] == calm["reduced"]
    # the control's guarantees and assumptions, then the schedule's
    n = len(calm["guarantees"])
    assert config["guarantees"][:n] == calm["guarantees"]
    assert len(config["guarantees"]) == n + 3
    assert config["assumed"][:len(calm["assumed"])] == calm["assumed"]
    told = " ".join(config["assumed"][len(calm["assumed"]):])
    for number in ("900 ms", "1 060 ms", "2 000 ms", "4 900 ms", "7 000 ms",
                   "0.05", "150 ms", "15 edges", "10 further", "0 - 49",
                   "fault_seed 1", "host_crash"):
        assert number in told
    assert any("IHAVE / IWANT" in m for m in config["known_misreadings"])
    assert any("pool the three bursts" in m
               for m in config["known_misreadings"])

    want = gossip_mesh_config(10000, 8, 1, ("1 s", "5 s", "9 s"), 8, 512,
                              bandwidth="1 Gbit", graph_nodes=200,
                              graph_seed=1, faults="slot_chaos", fault_seed=1)
    built = [cells.build_config(cell, seed=seed, backend="tpu",
                                stop_ns=12 * 10**9, data_dir="d")
             for seed in (45, 2**31 + 11)]
    quiet = cells.build_config(control, seed=45, backend="tpu",
                               stop_ns=12 * 10**9, data_dir="d")
    for got in built:
        # ONE network and ONE schedule whatever the run's seed; hosts,
        # graph and shapes are the control's
        assert got.faults.events == want.faults.events
        assert got.hosts == quiet.hosts
        assert got.network.graph.inline == quiet.network.graph.inline
        assert got.experimental == quiet.experimental
    assert quiet.faults.events == []
    events = [parse_event(e) for e in want.faults.events]
    assert sorted({e.at // MS for e in events}) == EPOCHS_MS
    assert all(e.source != e.target for e in events if e.source >= 0)
    assert [g for e in events for g in e.groups] == [
        tuple(range(50)), tuple(range(50, 200))]


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, cut to 96 nodes over
    40 graph nodes (the schedule draws 40 edges; nothing else: degree,
    bursts, messages, the schedule and the check horizon are the cell's)."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    for name in (CONFIG, CONTROL_CONFIG):
        cfg = _file("configs", name)
        cfg["parameters"].update(hosts=96, graph_nodes=40)
        (tmp_path / "b" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    (tmp_path / "b" / "traffic" / f"{TRAFFIC}.json").write_text(
        json.dumps(_file("traffic", TRAFFIC)))
    man = {k: MANIFEST[k] for k in ("command", "run_seconds")}
    man["paths"] = ["b"]
    man["configs"] = [{**_entry("configs", name),
                       "file": f"b/configs/{name}.json"}
                      for name in (CONFIG, CONTROL_CONFIG)]
    man["workloads"] = [
        {"name": "narrow", "config": CONFIG, "traffic": TRAFFIC, "chips": 1},
        {"name": "calm", "config": CONTROL_CONFIG, "traffic": TRAFFIC,
         "chips": 1}]
    man["end_to_end"] = [m for m in MANIFEST["end_to_end"]
                         if "workloads" not in m]
    man["per_layer"] = [
        {**m, "workloads": ["narrow"]} if m["name"] in READERS else m
        for m in MANIFEST["per_layer"]
        if "workloads" not in m or m["name"] in READERS]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path


def test_the_files_run_correct_through_the_runners_precompile(
        narrow_root, monkeypatch):
    monkeypatch.setattr(trace_mod, "find_xplane", lambda _d: RECORDED)
    cell = cells.load_cell("narrow", narrow_root)
    out = run.drive(cell, 2**31 + 11, 0.5, True, jax.devices()[:1],
                    t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    # the recorded trace holds programs; its gaps name none between them
    assert {"trace_compile_s", "compiles_in_window", "device_idle_share",
            "device_programs_per_repeat"} <= set(out["metrics"])
    assert out["metrics"]["device_programs_per_repeat"]["value"] >= 1


def test_the_check_horizon_crosses_two_epochs_and_the_calm_oracle_fails(
        narrow_root, tmp_path):
    """What the cell's two comparisons hold at a rehearsal width: the check
    program runs three segments (two epochs inside 1 093 ms), the timed one
    six; both equal the oracle under the schedule, and neither equals the
    oracle WITHOUT it."""
    from shadow_tpu.backend.cpu_engine import CpuEngine
    from shadow_tpu.backend.tpu_engine import TpuEngine

    cell = cells.load_cell("narrow", narrow_root)
    calm = cells.load_cell("calm", narrow_root)
    check_ns = int(cell.traffic["check_ms"] * MS)

    def build(c, backend, stop_ns):
        return cells.build_config(c, seed=7, backend=backend, stop_ns=stop_ns,
                                  data_dir=tmp_path / backend)

    for stop_ns, segments, log in ((check_ns, 3, 200_000),
                                   (12 * 10**9, 6, 0)):
        eng = TpuEngine(build(cell, "tpu", stop_ns), log_capacity=log)
        got = eng.run(mode="device", precompile=True)
        assert eng.lane_plane["fault_segments"] == segments
        assert eng.lane_plane["fault_programs"] == 1
        same, other = compare.Comparison(), compare.Comparison()
        compare.compare_results(
            same, "faulted", got, CpuEngine(build(cell, "cpu", stop_ns)).run(),
            log=bool(log))
        compare.compare_results(
            other, "calm", got, CpuEngine(build(calm, "cpu", stop_ns)).run(),
            log=bool(log))
        assert same.ok, same.lines()
        assert not other.ok and other.failures >= 1


def test_the_readers_on_a_hand_made_raw():
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    raw = {"trace": {"programs": 6, "breakdown": {"idle_gaps": [
        ["inside_program", 0.031], ["outside_first_to_last_op", 0.002],
        ["between_programs", 0.0015]]}}}
    assert read["device_programs_per_repeat"](raw) == 6
    assert read["between_programs_ms"](raw) == pytest.approx(1.5)
    # one program a repeat: counted, and no gap between programs to read
    one = {"trace": {"programs": 1, "breakdown": {"idle_gaps": [
        ["inside_program", 0.03]]}}}
    assert read["device_programs_per_repeat"](one) == 1
    assert read["between_programs_ms"](one) is None
    for n in READERS:  # an untraced run, a program that says nothing
        assert read[n]({}) is None
        assert read[n]({"trace": None}) is None
        assert read[n]({"trace": {"programs": 0, "breakdown": {}}}) is None
