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
