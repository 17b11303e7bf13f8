"""The six readers of the hybrid engine's per-turn ring
(``raw["sync_stats"]["turn_spans"]``) on a hand-made ``raw``, and in a
rehearsal of the traced hybrid cell beside the seven it printed before.
(``test_runners.py::test_hybrid_traced_run`` names those seven EXACTLY, so
it reads false since these six were appended: a ``benchmark`` issue's to
re-word; this file holds the set as it is now.)"""

import time
from collections import namedtuple
from pathlib import Path

import pytest

import run
from lib import cells, turn_spans
from lib import trace as trace_mod

BENCH = Path(__file__).resolve().parents[1]
RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")

Row = namedtuple("Row", (
    "turn", "t_start", "t_end", "inject", "peek", "dispatch", "device_wait",
    "egress_read", "egress_apply", "service_ship", "service_collect",
    "callback", "walk", "window_end_ns", "worker_exec_max_s", "dispatches"))
MS = 1_000_000


def row(i, we_ms, dispatches=1, **secs):
    """A 10 ms turn ending at sim ``we_ms``; unnamed phases take 0."""
    vals = {p: 0.0 for p in Row._fields[3:13]}
    vals.update(secs)
    return Row(turn=i, t_start=100.0 + 0.02 * i, t_end=100.01 + 0.02 * i,
               window_end_ns=we_ms * MS, dispatches=dispatches,
               worker_exec_max_s=secs.get("service_collect", 0.0) / 2, **vals)


def canned():
    """Four turns; the window (the last 0.025 sim-s of the run: rows whose
    window ends after 30 - 25 = 5 sim-ms) holds the last three."""
    rows = [
        row(0, 5, inject=9.0, walk=9.0),  # before the window: never read
        row(1, 10, inject=0.001, peek=0.0005, dispatch=0.0015,
            device_wait=0.003, walk=0.002, service_ship=0.0005,
            service_collect=0.0015),
        row(2, 20, dispatches=2, inject=0.002, egress_read=0.001,
            egress_apply=0.003, walk=0.004),
        row(3, 30, callback=0.004, walk=0.002, service_collect=0.004),
    ]
    return {"sync_stats": {"turn_spans": rows, "device_turns": 5},
            "window_sim_s": 0.025, "window_wall_s": 0.06}


def read(name, raw):
    return run.load_module("layer_metrics", name).read(raw)


def test_the_windows_rows_are_the_last_window_sim_s():
    raw = canned()
    assert [r.turn for r in turn_spans.window_rows(raw)] == [1, 2, 3]
    raw["window_sim_s"] = 1.0  # the whole run
    assert [r.turn for r in turn_spans.window_rows(raw)] == [0, 1, 2, 3]


def test_the_four_per_dispatch_readers():
    raw = canned()  # four dispatches in the window's three turns
    assert read("turn_inject_ms", raw) == pytest.approx(3.0 / 4)
    assert read("turn_dispatch_ms", raw) == pytest.approx(2.0 / 4)
    assert read("turn_egress_ms", raw) == pytest.approx(4.0 / 4)
    assert read("turn_walk_ms", raw) == pytest.approx(8.0 / 4)


def test_the_two_shares():
    raw = canned()  # three 10 ms turns in a 60 ms window
    assert read("turn_untimed_share", raw) == pytest.approx(50.0)
    # half of each collect leg is the slowest worker computing
    assert read("worker_exec_share", raw) == pytest.approx(
        100 * (0.00075 + 0.002) / (0.0005 + 0.0015 + 0.004))


NAMES = ("turn_inject_ms", "turn_dispatch_ms", "turn_egress_ms",
         "turn_walk_ms", "turn_untimed_share", "worker_exec_share")


@pytest.mark.parametrize("name", NAMES)
def test_a_raw_without_the_ring_reads_none(name):
    # the parent's program: sync_stats has no turn_spans
    assert read(name, {"sync_stats": {"device_turns": 5},
                       "window_sim_s": 0.025, "window_wall_s": 0.06}) is None
    assert read(name, {}) is None  # another runner's raw
    empty = canned()
    empty["sync_stats"]["turn_spans"] = []
    assert read(name, empty) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_entry_matches_its_reader(name):
    import json

    MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert m["unit"] == run.load_module("layer_metrics", name).UNIT
    assert m["workloads"] == ["hybrid151_chains"]
    assert (m["source"], m["moves"]) == ("program_span", "sim_s_per_wall_s")
    assert m["better"] == ("higher" if name == "worker_exec_share"
                           else "lower")
    assert (BENCH / "layer_metrics" / f"{name}.py").is_file()


def test_hybrid_traced_run_prints_the_six_beside_the_seven(
        tiny_root, cpu_devices, monkeypatch):
    from runners.hybrid import build_native

    build_native(lambda _m: None)
    # XLA:CPU writes no device plane: read the chip's recorded trace
    monkeypatch.setattr(trace_mod, "find_xplane", lambda _d: RECORDED)
    cell = cells.load_cell("tiny_chains", tiny_root)
    out = run.drive(cell, 7, 2.0, True, cpu_devices,
                    t_start=time.perf_counter())
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "trace_compile_s", "compiles_in_window", "turns_per_sim_s",
        "device_sync_ms_per_turn", "turn_wall_p95_ms",
        "syscall_service_share", "device_idle_share", *NAMES}
    got = {k: out["metrics"][k]["value"] for k in NAMES}
    assert all(v > 0 for v in got.values()), got
    assert got["turn_untimed_share"] < 100 and got["worker_exec_share"] <= 100
