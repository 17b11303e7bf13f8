"""The trace reduction on a small trace recorded on a TPU v5e
(data/record_trace.py: three executions of a 20-iteration ``while_loop``
of a multiply-add fusion, an iota, a sort and a select fusion)."""

from pathlib import Path

import pytest

from lib import trace

RECORDED = str(Path(__file__).parent / "data" / "small_tpu.xplane.pb")


def test_union_length_merges_overlaps():
    assert trace.union_length([(0, 4), (2, 6), (10, 11)]) == 7
    assert trace.union_length([]) == 0
    assert trace.union_length([(5, 6), (0, 10)]) == 10


def test_leaf_intervals_drop_containers_by_nesting():
    events = [(0, 100, "%while"), (1, 10, "%a"), (20, 30, "%b"),
              (60, 5, "%cond"), (61, 2, "%inner"), (200, 7, "%tail")]
    names = [n for _s, _e, n in trace.leaf_intervals(events)]
    assert names == ["%a", "%b", "%inner", "%tail"]


def test_op_label():
    assert trace.op_label(
        "%sort.8 = (s32[4096]{0}, s32[4096]{0}) sort(...)") == "sort.8"
    assert trace.op_label("") == "unnamed"


def test_reduce_recorded_trace():
    out = trace.reduce_trace(RECORDED, window_s=0.0404)
    # three programs of ~125 us each; the loop body is 4 leaf operations,
    # 20 iterations, plus the copies around the loop
    assert out["programs"] == 3
    assert out["leaf_ops"] == 252
    assert out["busy_s"] == pytest.approx(3.747e-4, rel=1e-3)
    assert 0 < out["busy_s"] < out["window_s"]
    ops = dict(out["breakdown"]["device_ops"])
    assert max(ops, key=ops.get) == "sort.8"
    assert not any(k.startswith("while") for k in ops)  # containers are out
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["between_programs"] > gaps["inside_program"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10
    # busy + gaps account for the window
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(
        out["window_s"], rel=1e-6)


def test_a_trace_with_no_device_operation_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    trace.start_trace(tmp_path)
    jax.block_until_ready(jnp.arange(8) + 1)
    jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="no operation ran on a TPU"):
        trace.reduce_trace(trace.find_xplane(str(tmp_path)), 0.1)
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path / "nothing"))
