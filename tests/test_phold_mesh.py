"""PHOLD at width (ISSUE 35): ``config/scenarios.py phold_mesh_config`` —
uniform random destinations and an ACTIVE lane model through the normal
path — against the CPU oracle and against an analytic invariant neither
engine can fake.

(a) the lane backend equals the oracle — whole event log, counters, rounds —
    on ``mode="device"`` and ``mode="step"``, through the facade with the
    log off, and at mesh shapes 1 / 2 / 4;
(b) the shape law: the factory's capacities are the written law's, the
    merge's row is a power of two, a run's ``queue_peak`` / ``cross_peak``
    sit under them, and a shape forced below a peak raises with a message
    that names THAT block and the option that cures it;
(c) conservation: the population is conserved and a hop is one link latency
    plus microseconds, so ``phold_hops == hosts x messages x (windows - 1)``
    and every delivery is a hop;
(d) the shapes and the peaks reach ``lane_plane``, ``sim-stats.json`` and
    the obs gauges.
"""

import functools
import json
import math

import pytest

from shadow_tpu import parallel
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config import scenarios
from shadow_tpu.config.scenarios import (
    phold_mesh_config, phold_shape_law, poisson_tail_quantile,
)
from shadow_tpu.engine.sim import Simulation

MS = 1_000_000
#: counters one backend alone keeps (as benchmarks/lib/compare.py)
BACKEND_ONLY = {"lane_iters", "lane_delivered", "lane_sends"}
SHAPES = ("queue_capacity", "cross_capacity", "pops_per_iter", "queue_peak",
          "cross_peak")
#: (hosts, messages, windows): the three small sizes of (b) and (c)
SMALL = [(64, 4, 10), (512, 4, 5), (512, 16, 5)]


def _cfg(hosts, messages, windows, backend="tpu", seed=7, **shapes):
    cfg = phold_mesh_config(hosts, messages, 256, "10 ms", "1 Gbit",
                            seed=seed)
    cfg.general.stop_time = windows * 10 * MS
    cfg.experimental.network_backend = backend
    for key, val in shapes.items():
        setattr(cfg.experimental, key, val)
    return cfg


def _shared(counters):
    return {k: v for k, v in counters.items() if k not in BACKEND_ONLY}


@functools.lru_cache(maxsize=None)
def _oracle(hosts, messages, windows):
    return CpuEngine(_cfg(hosts, messages, windows, "cpu")).run()


@functools.lru_cache(maxsize=None)
def _lane_run(hosts, messages, windows):
    """(result, lane_plane) of the fused run, log off, factory shapes."""
    eng = TpuEngine(_cfg(hosts, messages, windows), log_capacity=0)
    return eng.run(mode="device"), dict(eng.lane_plane)


def _assert_equals_oracle(res, oracle, hops):
    assert res.log_tuples() == oracle.log_tuples()
    assert len(oracle.event_log) == hops > 0
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds
    assert res.counters["phold_hops"] == hops


# -- (a) against the oracle ---------------------------------------------------


@pytest.mark.parametrize("mode", ["device", "step"])
def test_the_lane_backend_equals_the_oracle(mode):
    res = TpuEngine(_cfg(64, 4, 10)).run(mode=mode)
    _assert_equals_oracle(res, _oracle(64, 4, 10), 64 * 4 * 9)


def test_the_facade_with_the_log_off_equals_the_oracles_counters(tmp_path):
    cfg = _cfg(64, 4, 10)
    cfg.general.data_directory = str(tmp_path / "data")
    cfg.experimental.obs_metrics = True
    sim = Simulation(cfg, event_log=False)
    res = sim.run()
    oracle = _oracle(64, 4, 10)
    assert res.event_log == []
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds == 10
    # (d): the shapes and the peaks, wherever a run is read
    plane = json.loads(
        (sim.data_dir / "sim-stats.json").read_text())["lane_plane"]
    want = _lane_run(64, 4, 10)[1]
    gauges = sim.obs.finalized["report"]["gauges"]
    for key in SHAPES:
        assert plane[key] == sim.engine.lane_plane[key] == want[key]
        assert gauges[key] == want[key]
    assert plane["device_log_capacity"] == 0


def test_the_command_line_runs_the_same_network_from_a_file(tmp_path):
    """``python -m shadow_tpu`` on the factory's network written as a YAML
    file — the normal path of a user without Python (no event log kept:
    the command line's default on this backend)."""
    import subprocess
    import sys

    queue, cross = phold_shape_law(64, 4)
    path = tmp_path / "phold.yaml"
    path.write_text(f"""
general: {{stop_time: 100 ms, seed: 7, heartbeat_interval: null,
          data_directory: {tmp_path / "data"}}}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "10 ms" ]
      ]
experimental: {{network_backend: tpu, tpu_lane_queue_capacity: {queue},
               tpu_cross_capacity: {cross}, tpu_events_per_round: 2}}
hosts:
  lp: {{count: 64, network_node_id: 0,
       processes: [{{path: phold, args: --messages 4 --size 256}}]}}
""")
    done = subprocess.run(
        [sys.executable, "-m", "shadow_tpu", str(path)],
        capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    stats = json.loads((tmp_path / "data" / "sim-stats.json").read_text())
    assert stats["counters"]["phold_hops"] == 64 * 4 * 9
    assert stats["packet_outcomes"] == {"delivered": 64 * 4 * 9}
    # the factory's run, peak for peak: the same hosts, draws and shapes
    want = _lane_run(64, 4, 10)[1]
    assert {k: stats["lane_plane"][k] for k in SHAPES} == {
        k: want[k] for k in SHAPES}


@pytest.mark.multichip
@pytest.mark.parametrize("devices", [1, 2, 4])
def test_any_mesh_shape_equals_the_oracle(devices):
    eng = TpuEngine(_cfg(64, 4, 5))
    eng.attach_mesh(parallel.make_mesh(devices))
    res = eng.run(mode="device")
    _assert_equals_oracle(res, _oracle(64, 4, 5), 64 * 4 * 4)
    assert eng.lane_plane["mesh_devices"] == devices
    # the peaks are maxima over ALL lanes, whatever chip holds them
    single = _lane_run(64, 4, 5)[1]
    assert {k: eng.lane_plane[k] for k in SHAPES} == {
        k: single[k] for k in SHAPES}


# -- (b) the shape law --------------------------------------------------------


def _poisson_tail(mean, k):
    """P(X > k), summed from the far end."""
    terms = [math.exp(-mean + j * math.log(mean) - math.lgamma(j + 1))
             for j in range(k + 1, k + 400)]
    return math.fsum(reversed(terms))


@pytest.mark.parametrize("mean, p", [(2, 1e-10), (4, 2e-9), (8, 1e-10),
                                     (32, 4e-12), (2, 0.3)])
def test_the_tail_quantile_is_the_smallest_width_under_its_tail(mean, p):
    k = poisson_tail_quantile(mean, p)
    assert _poisson_tail(mean, k) < p <= _poisson_tail(mean, k - 1)


@pytest.mark.parametrize("hosts, messages",
                         [(64, 4), (512, 4), (512, 16), (10_000, 4)])
def test_the_factorys_shapes_are_the_written_law(hosts, messages):
    exp = phold_mesh_config(hosts, messages, 256, "10 ms", "1 Gbit"
                            ).experimental
    queue, cross, pops = (exp.tpu_lane_queue_capacity, exp.tpu_cross_capacity,
                          exp.tpu_events_per_round)
    assert (queue, cross) == phold_shape_law(hosts, messages)
    assert pops == 2
    # the law, restated: tails at 1 / (1 000 x lanes x windows x iterations)
    draws = 1000 * hosts * scenarios.PHOLD_LAW_WINDOWS
    iters = -(-2 * poisson_tail_quantile(messages, 1 / draws) // pops)
    p = 1 / (draws * iters)
    assert _poisson_tail(pops, cross) < p <= _poisson_tail(pops, cross - 1)
    need = poisson_tail_quantile(2 * messages, p) + scenarios.QUEUE_HEADROOM
    row = queue + 2 * pops + cross
    # the row is a power of two, the smallest that holds what is needed
    assert row & (row - 1) == 0 and queue >= need
    assert row // 2 < need + 2 * pops + cross
    if (hosts, messages) == (10_000, 4):
        # what three seeds held at this width (ISSUE 35): at least that
        assert queue >= 32 and cross >= 16 and row == 64
    # more windows, lanes or messages never narrow a shape's need
    assert phold_shape_law(hosts, messages, windows=50)[1] <= cross
    assert phold_shape_law(10 * hosts, messages)[1] >= cross


def test_the_law_refuses_nonsense():
    with pytest.raises(ValueError):
        phold_shape_law(0, 4)
    with pytest.raises(ValueError):
        phold_shape_law(64, 4, pops=0)


@pytest.mark.parametrize("hosts, messages, windows", SMALL)
def test_a_runs_peaks_sit_under_the_factorys_shapes(hosts, messages, windows):
    _res, plane = _lane_run(hosts, messages, windows)
    queue, cross = phold_shape_law(hosts, messages)
    assert (plane["queue_capacity"], plane["cross_capacity"],
            plane["pops_per_iter"]) == (queue, cross, 2)
    assert messages <= plane["queue_peak"] <= queue - scenarios.QUEUE_HEADROOM
    assert 2 <= plane["cross_peak"] <= cross


@pytest.mark.parametrize("hosts, messages, windows", SMALL)
def test_a_shape_forced_under_its_peak_raises_and_names_the_block(
        hosts, messages, windows):
    plane = _lane_run(hosts, messages, windows)[1]
    narrow = TpuEngine(_cfg(
        hosts, messages, windows,
        tpu_lane_queue_capacity=plane["queue_peak"] - 2), log_capacity=0)
    with pytest.raises(RuntimeError) as e:
        narrow.run(mode="device")
    msg = str(e.value)
    assert "off the tail of a lane QUEUE" in msg
    assert "raise experimental.tpu_lane_queue_capacity" in msg
    assert "CROSS" not in msg and "tpu_cross_capacity" not in msg
    narrow = TpuEngine(_cfg(
        hosts, messages, windows,
        tpu_cross_capacity=plane["cross_peak"] - 1), log_capacity=0)
    with pytest.raises(RuntimeError) as e:
        narrow.run(mode="device")
    msg = str(e.value)
    assert "by the CROSS block" in msg
    # (how many it was offered is the forced run's own peak: after the
    # first shed its trajectory is no longer the sound run's)
    assert f"the block holds {plane['cross_peak'] - 1})" in msg
    assert "raise experimental.tpu_cross_capacity" in msg
    assert "tpu_lane_queue_capacity" not in msg
    # not strict: both sheds are one counter, as before
    loose = TpuEngine(_cfg(
        hosts, messages, windows,
        tpu_cross_capacity=plane["cross_peak"] - 1), log_capacity=0,
        strict_capacity=False)
    assert loose.run(mode="device").counters["lane_drop_queue"] > 0


def test_an_injection_shed_is_the_queues_and_the_message_says_so():
    """The hybrid's injection merge (``lanes._inject_merge``) sheds past a
    block that is ``capacity`` wide, not ``cross_cap``: in a program that
    keeps the peaks it counts toward the QUEUE's peak and message, never
    toward the cross block's, whose option would not cure it."""
    import numpy as np

    from shadow_tpu.backend import lanes

    eng = TpuEngine(_cfg(64, 4, 5), log_capacity=0)
    p = eng.params
    assert not p.all_passive and p.cross_cap < p.capacity
    b = 2 * p.capacity  # all to lane 0: the block sheds half, the tail 4
    inj = {
        "valid": np.ones(b, dtype=bool),
        "dst": np.zeros(b, dtype=np.int32),
        "thi": np.zeros(b, dtype=np.int32),
        "tlo": np.arange(1, b + 1, dtype=np.int32),
        "auxh": np.full(b, (lanes.PACKET << lanes.AUX_KIND_SHIFT)
                        | (1 << lanes.AUX_SRC_SHIFT), dtype=np.int32),
        "auxl": np.arange(b, dtype=np.int32),
        "size": np.full(b, 256, dtype=np.int32),
    }
    s = lanes.make_inject_fn(p, eng.tables)(eng.initial_state(), inj)
    assert int(s.n_queue.sum()) == p.capacity + 4
    assert [int(x) for x in s.peaks] == [b + 4, 0, 0]
    with pytest.raises(RuntimeError) as e:
        eng.collect(s, 0.0)
    msg = str(e.value)
    assert f"{p.capacity + 4} off the tail of a lane QUEUE" in msg
    assert f"held {b + 4} events, the queue holds {p.capacity}" in msg
    assert "raise experimental.tpu_lane_queue_capacity" in msg
    assert "CROSS" not in msg and "tpu_cross_capacity" not in msg


# -- (c) conservation ---------------------------------------------------------


@pytest.mark.parametrize("hosts, messages, windows", SMALL)
def test_the_population_is_conserved(hosts, messages, windows):
    res, _plane = _lane_run(hosts, messages, windows)
    hops = hosts * messages * (windows - 1)
    assert res.counters["phold_hops"] == hops
    assert res.counters["lane_delivered"] == hops
    # every message is in flight at the stop: one more send than hops each
    assert res.counters["lane_sends"] == hops + hosts * messages
    assert "lane_drop_queue" not in res.counters
    assert res.rounds == windows
    assert _oracle(hosts, messages, windows).counters["phold_hops"] == hops


# -- (d) the gauges -----------------------------------------------------------


def test_a_program_of_passive_lanes_reports_its_shapes_and_no_peaks():
    """The peaks are three reductions an iteration, compiled only where
    some lane's model is active (``LaneParams.all_passive``): the
    permutation meshes' programs do not pay for them."""
    from shadow_tpu.config.columnar import columnar_mesh_config

    cfg = columnar_mesh_config(200, sim_seconds=1, queue_capacity=16,
                               pops_per_round=2)
    cfg.general.stop_time = 50 * MS
    cfg.experimental.tpu_cross_capacity = 8
    eng = TpuEngine(cfg, log_capacity=0)
    eng.run(mode="device")
    assert eng.params.all_passive
    assert {k: eng.lane_plane.get(k) for k in SHAPES} == {
        "queue_capacity": 16, "cross_capacity": 8, "pops_per_iter": 2,
        "queue_peak": None, "cross_peak": None}
