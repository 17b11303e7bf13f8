"""The clocks' arithmetic on hand-made timestamps."""

import pytest

from lib import stats

MS10 = stats.SIM_STEP_NS


def test_percentile_is_nearest_rank():
    vals = list(range(1, 221))  # 220 samples
    assert stats.percentile(vals, 95) == 209  # ceil(0.95 * 220) = 209
    assert sum(v > 209 for v in vals) == 11  # more than ten beyond it
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    assert stats.percentile([3, 1, 2], 100) == 3
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1], 0)


def test_last_window_takes_the_last_seconds_of_wall():
    # one sample per second of wall, 10 sim-ms each, for 10 s
    samples = [(100.0 + i, i * MS10) for i in range(11)]
    i_open, whole = stats.last_window(samples, 4.0)
    assert (i_open, whole) == (6, False)  # wall 106 = 110 - 4
    sim_s, wall_s = stats.window_rate(samples, i_open)
    assert wall_s == 4.0 and sim_s == pytest.approx(0.04)
    # a boundary that falls between samples opens at the next sample
    i_open, _ = stats.last_window(samples, 3.5)
    assert samples[i_open][0] == 107.0


def test_last_window_of_a_short_run_is_the_whole_run():
    samples = [(0.0, 0), (1.0, MS10), (2.0, 2 * MS10)]
    assert stats.last_window(samples, 30.0) == (0, True)
    assert stats.window_rate(samples, 0) == (pytest.approx(0.02), 2.0)
    with pytest.raises(ValueError):
        stats.last_window(samples[:1], 30.0)


def test_sim_step_walls_samples_each_10_sim_ms():
    # sim reaches 10 ms at wall 1, 20 ms at wall 3, then jumps to 50 ms at 4
    samples = [(0.0, 0), (0.5, MS10 // 2), (1.0, MS10), (2.0, MS10 + 1),
               (3.0, 2 * MS10), (4.0, 5 * MS10)]
    assert stats.sim_step_walls(samples) == [1.0, 2.0, 1.0, 0.0, 0.0]
    # a span that opens mid-run does not count the steps before it
    assert stats.sim_step_walls(samples[4:]) == [1.0, 0.0, 0.0]
    assert stats.sim_step_walls([]) == []


def test_change_walls_times_each_change_of_a_counter():
    samples = [(0.0, 5), (1.0, 5), (2.0, 6), (2.5, 6), (4.0, 8)]
    assert stats.change_walls(samples) == [2.0, 2.0, 0.0]
    assert stats.change_walls([]) == []

