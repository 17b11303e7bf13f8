"""scripts/trace_idle.py: the device's idle gaps put down to the host
phase (a ``hybrid/*`` / ``fused/*`` TraceMe of the drivers' clock) that was
running — its arithmetic on hand-made intervals, and the whole reduction on
the one recorded TPU trace the tree has (which predates the phases)."""

import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "trace_idle", REPO / "scripts" / "trace_idle.py")
ti = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ti)

MS = 1_000_000


def test_the_innermost_phase_takes_a_nested_stretch():
    phases = [(0, 100, "hybrid/walk"), (10, 30, "hybrid/peek"),
              (30, 60, "hybrid/device_wait"), (120, 150, "hybrid/walk"),
              (130, 150, "hybrid/callback")]
    assert ti.innermost(phases) == [
        (0, 10, "hybrid/walk"), (10, 30, "hybrid/peek"),
        (30, 60, "hybrid/device_wait"), (60, 100, "hybrid/walk"),
        (120, 130, "hybrid/walk"), (130, 150, "hybrid/callback")]
    assert ti.innermost([]) == []


def test_leaves_are_the_events_that_contain_no_other():
    # a while (0..100) holding two body operations, then a lone copy
    ops = [(0, 100), (10, 20), (50, 30), (120, 5)]
    assert ti.leaf_intervals(ops) == [(10, 30), (50, 80), (120, 125)]


def test_gaps_by_kind_and_their_phases():
    leaves = [(10, 30), (50, 80), (120, 125)]
    programs = [(0, 100), (118, 126)]
    gaps = ti.idle_gaps(leaves, programs, lo=0, hi=200)
    assert gaps == [(0, 10, "outside_first_to_last_op"),
                    (30, 50, "inside_program"),
                    (80, 120, "between_programs"),
                    (125, 200, "outside_first_to_last_op")]
    stretches = ti.innermost([(0, 40, "fused/dispatch"),
                              (40, 110, "fused/device_wait"),
                              (150, 200, "fused/collect")])
    by = ti.attribute(gaps, stretches)
    assert dict(by) == {
        ("outside_first_to_last_op", "fused/dispatch"): 10e-9,
        ("inside_program", "fused/dispatch"): 10e-9,
        ("inside_program", "fused/device_wait"): 10e-9,
        ("between_programs", "fused/device_wait"): 30e-9,
        ("between_programs", ti.NO_PHASE): 10e-9,
        ("outside_first_to_last_op", ti.NO_PHASE): 25e-9,
        ("outside_first_to_last_op", "fused/collect"): 50e-9}
    assert sum(by.values()) == pytest.approx(
        sum(g1 - g0 for g0, g1, _k in gaps) / 1e9)


def test_a_runs_remainder_is_the_runs_own_phase():
    """A fused run is one ``fused/run`` interval around its phases: the
    stretch between two phases is its, only what lies outside every run
    stays unattributed."""
    stretches = ti.innermost([(10, 190, "fused/run"),
                              (20, 40, "fused/dispatch"),
                              (40, 110, "fused/device_wait"),
                              (150, 180, "fused/collect")])
    assert stretches == [
        (10, 20, "fused/run"), (20, 40, "fused/dispatch"),
        (40, 110, "fused/device_wait"), (110, 150, "fused/run"),
        (150, 180, "fused/collect"), (180, 190, "fused/run")]
    by = ti.attribute([(0, 15, "outside_first_to_last_op"),
                       (100, 160, "outside_first_to_last_op")], stretches)
    assert dict(by) == {
        ("outside_first_to_last_op", ti.NO_PHASE): 10e-9,
        ("outside_first_to_last_op", "fused/run"): 45e-9,
        ("outside_first_to_last_op", "fused/device_wait"): 10e-9,
        ("outside_first_to_last_op", "fused/collect"): 10e-9}
    # a closed turn's row is no phase
    assert ti.ROW.match("fused/row") and not ti.ROW.match("fused/run")


def test_a_traced_runs_row_rides_the_trace(tmp_path):
    """A trace of one fused run (XLA:CPU: no device plane, so the
    reduction itself refuses) holds the run's interval, its phases and
    its row's stats."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from shadow_tpu.backend.tpu_engine import TpuEngine
    from shadow_tpu.config.columnar import columnar_mesh_config

    cfg = columnar_mesh_config(64, queue_capacity=16, pops_per_round=2)
    cfg.general.stop_time = 50 * MS
    eng = TpuEngine(cfg, log_capacity=0)
    eng.run(mode="device")
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.run(mode="device")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names, rows = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if ti.ROW.match(e.name):
                    rows.append(dict(e.stats))
                elif ti.PHASE.match(e.name):
                    names.append(e.name)
    assert sorted(names) == sorted(
        f"fused/{p}" for p in ("run", "state_build", "dispatch",
                               "device_wait", "collect"))
    (row,) = rows
    want = eng.run_row()
    assert row["rounds"] == res.rounds == want["rounds"]
    assert row["lane_iters"] == want["lane_iters"]
    assert row["state_reused"] == 1 and row["owner"] == want["owner"]
    assert row["collect"] == pytest.approx(want["collect"])
    with pytest.raises(SystemExit, match="no operation ran on a TPU"):
        ti.reduce(path)


@pytest.mark.parametrize("skew_us", [-1500, 0, 1700])
def test_the_device_clocks_offset_is_read_off_the_waits(skew_us):
    """Ten 3 ms programs 33 ms apart on a device clock that is ``skew``
    behind the host's; every wait ends 40-120 us after its program, a
    slice program of 20 us follows each, and every third wait adopted an
    eager dispatch (its program ended 9 ms earlier)."""
    skew = skew_us * 1e3
    programs, waits = [], []
    for i in range(10):
        end = (i * 33 + 3) * MS
        programs += [(end - 3 * MS, end), (end + 300e3, end + 320e3)]
        late = 9 * MS if i % 3 == 2 else 0
        waits.append(end + skew + 40e3 + 8e3 * i + late)
    off = ti.clock_offset(waits, programs)
    assert off == pytest.approx(skew + 40e3, abs=1e3)
    assert ti.clock_offset([], programs) == 0.0
    assert ti.clock_offset(waits, []) == 0.0


def test_a_recorded_trace_without_phases_is_all_unattributed():
    rep = ti.reduce(str(
        REPO / "benchmarks" / "tests" / "data" / "small_tpu.xplane.pb"))
    assert rep["phases_seen"] == 0
    (dev,) = rep["devices"].values()
    assert dev["offset_us"] == 0.0 and 0 < dev["idle_s"] < dev["span_s"]
    assert {p for _k, p in dev["idle_by_phase"]} == {ti.NO_PHASE}
    assert sum(dev["idle_by_phase"].values()) == pytest.approx(dev["idle_s"])
