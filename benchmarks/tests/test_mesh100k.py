"""Rehearsals of the wide-mesh configuration and its traffic mix (ISSUE 28):
the built configuration is the program's own columnar 100 000-host mesh;
the files run ``correct`` at a rehearsal width on one and on four (virtual)
devices with both new readers reporting; each reader on a hand-made ``raw``;
a wrong expected count makes ``correct`` false."""

import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import run
from conftest import BENCH, MANIFEST
from lib import cells
from lib import trace as trace_mod

RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")
CONFIG, TRAFFIC = "tgen_mesh_100k", "udp_500ms"
#: the same mix under the four-chip cell's name: the manifest admits a pair
#: of configuration and traffic once
TRAFFIC_X4 = "udp_500ms_x4"
READERS = ("device_ns_per_delivery", "host_ms_per_run")


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


def test_the_manifest_entries_are_the_issues():
    cfg = _entry("configs", CONFIG)
    assert cfg["reduced"] == ["horizon_sim_s"] and len(cfg["source"]) <= 200
    for name, traffic, chips in (("mesh100k_udp", TRAFFIC, 1),
                                 ("mesh100k_udp_x4", TRAFFIC_X4, 4)):
        w = _entry("workloads", name)
        assert (w["config"], w["traffic"], w["chips"]) == (
            CONFIG, traffic, chips)
        assert len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for name in READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == ["mesh100k_udp", "mesh100k_udp_x4"]
        assert m["moves"] == "sim_s_per_wall_s"
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 2)


def test_both_cells_offer_the_same_traffic():
    """The two mixes differ in ``name`` and ``what`` alone, so the four-chip
    cell reads as a ratio to its one-chip control."""
    one, four = (json.loads((BENCH / "traffic" / f"{t}.json").read_text())
                 for t in (TRAFFIC, TRAFFIC_X4))
    assert (one.pop("name"), four.pop("name")) == (TRAFFIC, TRAFFIC_X4)
    assert one.pop("what") != four.pop("what")
    assert one == four


def test_the_cell_is_the_programs_columnar_mesh():
    """``tgen_mesh_100k`` names the program's factory; what it builds must
    be ``columnar_mesh_config(100_000)`` at the cells' program options —
    tables, parameters, initial events."""
    from shadow_tpu.config.columnar import columnar_mesh_config

    want = columnar_mesh_config(100_000, queue_capacity=16, pops_per_round=2)
    want.experimental.tpu_cross_capacity = 8
    for name in ("mesh100k_udp", "mesh100k_udp_x4"):
        got = cells.build_config(cells.load_cell(name), seed=41,
                                 backend="tpu", stop_ns=10**9, data_dir="d")
        assert len(got.hosts) == 100_000
        assert got.hosts[99_999] == want.hosts[99_999]
        assert dataclasses.asdict(got.experimental) == dataclasses.asdict(
            want.experimental)
        assert got.network.graph.inline == want.network.graph.inline
        for f in dataclasses.fields(want.columnar):
            np.testing.assert_array_equal(
                getattr(got.columnar, f.name), getattr(want.columnar, f.name))
    for name in ("mesh100k_udp", "mesh100k_udp_x4"):
        mix = cells.load_cell(name)
        assert (mix.traffic["horizon_sim_s"], mix.traffic["check_ms"]) == (
            0.5, 30)
        p = mix.params
        assert p["hosts"] * p["deliveries_per_host"] * p["datagram_bytes"] == (
            6_854_400_000)


@pytest.fixture
def narrow_root(tmp_path):
    """The two new files under a root of their own, ``hosts`` cut to 256
    (nothing else), with a cell on one and a cell on four devices."""
    (tmp_path / "b" / "configs").mkdir(parents=True)
    (tmp_path / "b" / "traffic").mkdir()
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg["parameters"]["hosts"] = 256
    (tmp_path / "b" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    for t in (TRAFFIC, TRAFFIC_X4):
        (tmp_path / "b" / "traffic" / f"{t}.json").write_text(
            (BENCH / "traffic" / f"{t}.json").read_text())
    man = {k: MANIFEST[k] for k in ("command", "run_seconds", "end_to_end")}
    man["paths"] = ["b"]
    man["configs"] = [{**_entry("configs", CONFIG),
                       "file": f"b/configs/{CONFIG}.json"}]
    man["workloads"] = [
        {"name": "narrow", "config": CONFIG, "traffic": TRAFFIC, "chips": 1},
        {"name": "narrow_x4", "config": CONFIG, "traffic": TRAFFIC_X4,
         "chips": 4}]
    man["end_to_end"] = [m for m in MANIFEST["end_to_end"]
                         if "workloads" not in m]
    man["per_layer"] = [
        {**m, "workloads": ["narrow", "narrow_x4"]} if m["name"] in READERS
        else m for m in MANIFEST["per_layer"]
        if "workloads" not in m or m["name"] in READERS]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path


def _drive(root, name, devices, trace=False, seed=2**31 + 11):
    return run.drive(cells.load_cell(name, root), seed, 0.5, trace, devices,
                     t_start=time.perf_counter())


@pytest.mark.parametrize("name, chips", [("narrow", 1), ("narrow_x4", 4)])
def test_the_files_run_correct_with_both_readers(
        narrow_root, monkeypatch, name, chips):
    if len(jax.devices()) < chips:
        pytest.skip("needs four (virtual) devices")
    monkeypatch.setattr(trace_mod, "find_xplane", lambda _d: RECORDED)
    out = _drive(narrow_root, name, jax.devices()[:chips])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == chips
    assert set(out["metrics"]) == {"sim_s_per_wall_s", "setup_s"}
    out = _drive(narrow_root, name, jax.devices()[:chips], trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "trace_compile_s", "compiles_in_window", "device_idle_share",
        *READERS}
    assert out["metrics"]["device_ns_per_delivery"]["value"] > 0
    assert out["metrics"]["host_ms_per_run"]["value"] > 0


def test_a_wrong_delivery_count_makes_correct_false(narrow_root):
    path = narrow_root / "b" / "traffic" / f"{TRAFFIC}.json"
    mix = json.loads(path.read_text())
    mix["parameters"]["deliveries_per_host"] = 49
    path.write_text(json.dumps(mix))
    out = _drive(narrow_root, "narrow", jax.devices()[:1])
    assert out["correct"] is False and out["failed"] == 0


def test_the_readers_on_a_hand_made_raw():
    raw = {"call_wall_s": [1.5, 1.25, 1.25], "device_wall_s": [1.0, 1.0, 1.0],
           "events_per_repeat": 4_000_000}
    read = {n: run.load_module("layer_metrics", n).read for n in READERS}
    # 3 s of device program over 3 x 4 M deliveries; 1 s of host over 3 runs
    assert read["device_ns_per_delivery"](raw) == pytest.approx(250.0)
    assert read["host_ms_per_run"](raw) == pytest.approx(1000.0 / 3)
    for n in READERS:
        assert read[n]({}) is None
        assert read[n]({"call_wall_s": [], "device_wall_s": []}) is None
    assert read["device_ns_per_delivery"](
        {"device_wall_s": [1.0], "events_per_repeat": 0}) is None
