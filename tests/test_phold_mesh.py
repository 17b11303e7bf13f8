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
    the obs gauges;
(e) the window-inert co-pop (ISSUE 38): the predicate alone on hand-written
    rows, the iterations it saves and its engage counter, the delivery
    ties it exists for against the oracle, and that the law is static;
(f) the same model on a routed, lossy graph (ISSUE 48:
    ``phold_mesh_config(..., graph_nodes=G)``): (a) again with the run-time
    ``[G, G]`` gathers and the loss draw compiled in, the placement's and
    the default's pins, the shape law where a window is shorter than a
    hop, the laws a decaying population keeps in both engines, and the
    ``path_gather_*`` gauges;
(g) the packed words a gathered send reads by one flat index (ISSUE 49:
    ``LaneTables.flat_lat`` / ``flat_thresh``): what they unpack to,
    where they are absent, the latency guard that frees bit 31, and a
    fault schedule's epoch swap against the oracle.
"""

import dataclasses
import functools
import json
import math

import jax
import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import LaneCompatError, TpuEngine
from shadow_tpu.config import scenarios
from shadow_tpu.config.options import ConfigOptions
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


def _facade_run(hosts, messages, windows, tmp_path):
    cfg = _cfg(hosts, messages, windows)
    cfg.general.data_directory = str(tmp_path / "data")
    cfg.experimental.obs_metrics = True
    sim = Simulation(cfg, event_log=False)
    return sim, sim.run()


def test_the_facade_with_the_log_off_equals_the_oracles_counters(tmp_path):
    sim, res = _facade_run(64, 4, 10, tmp_path)
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
    # the injection rides the turn's one block (lanes.TurnBlock), whose
    # injection part is all that the standalone merge reads; this
    # engine has no external lane, so the batch is given here
    p = dataclasses.replace(p, inject_batch=b)
    block = lanes.TurnBlock(b, 0).pack(inj, (), (), lanes.NEVER32, 0)
    s = lanes.make_inject_fn(p, eng.tables)(eng.initial_state(), block)
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


def _passive_mesh():
    from shadow_tpu.config.columnar import columnar_mesh_config

    cfg = columnar_mesh_config(200, sim_seconds=1, queue_capacity=16,
                               pops_per_round=2)
    cfg.general.stop_time = 50 * MS
    cfg.experimental.tpu_cross_capacity = 8
    return cfg


def test_a_program_of_passive_lanes_reports_its_shapes_and_no_peaks():
    """The peaks are three reductions an iteration, compiled only where
    some lane's model is active (``LaneParams.all_passive``): the
    permutation meshes' programs do not pay for them."""
    eng = TpuEngine(_passive_mesh(), log_capacity=0)
    eng.run(mode="device")
    assert eng.params.all_passive
    assert {k: eng.lane_plane.get(k) for k in SHAPES} == {
        "queue_capacity": 16, "cross_capacity": 8, "pops_per_iter": 2,
        "queue_peak": None, "cross_peak": None}


# -- (e) the window-inert co-pop ----------------------------------------------

P, L, D = lanes.PACKET, lanes.LOCAL, lanes.DELIVERY
WINDOW_END = 100
#: (model, kinds, times) -> the columns popped; the window ends at 100
ROWS = [
    # a PHOLD lane co-pops the longest DELIVERY* PACKET* prefix, any times
    (lanes.M_PHOLD, [D, D], [10, 20], [1, 1]),
    (lanes.M_PHOLD, [D, P], [10, 20], [1, 1]),
    (lanes.M_PHOLD, [P, P], [10, 20], [1, 1]),
    (lanes.M_PHOLD, [P, D], [10, 20], [1, 0]),  # P's own D may tie D'
    (lanes.M_PHOLD, [P, D], [10, 10], [1, 0]),
    (lanes.M_PHOLD, [P, L], [10, 20], [1, 0]),
    (lanes.M_PHOLD, [L, P], [10, 20], [1, 0]),
    (lanes.M_PHOLD, [D, L], [10, 20], [1, 0]),
    (lanes.M_PHOLD, [L, L], [10, 10], [1, 0]),  # today's rule: first column
    (lanes.M_PHOLD, [P, P], [10, 10], [1, 1]),  # the same-instant rule
    # never across the window's end
    (lanes.M_PHOLD, [D, D], [10, 100], [1, 0]),
    (lanes.M_PHOLD, [D, P], [99, 150], [1, 0]),
    (lanes.M_PHOLD, [D, D], [100, 110], [0, 0]),
    # a passive lane pops any prefix, a stream lane (star mode, the wide
    # law) single-kind prefixes, a ping lane same-instant packets: as before
    (lanes.M_TGEN_MESH, [P, L], [10, 20], [1, 1]),
    (lanes.M_TGEN_MESH, [L, P], [10, 100], [1, 0]),
    (lanes.M_STREAM_SERVER, [D, D], [10, 20], [1, 1]),
    (lanes.M_STREAM_SERVER, [P, P], [10, 20], [1, 1]),
    (lanes.M_STREAM_SERVER, [D, P], [10, 20], [1, 0]),
    (lanes.M_STREAM_CLIENT, [P, D], [10, 20], [1, 0]),
    (lanes.M_PING_SERVER, [D, D], [10, 20], [1, 0]),
    (lanes.M_PING_SERVER, [P, P], [10, 20], [1, 0]),
    (lanes.M_PING_SERVER, [P, P], [10, 10], [1, 1]),
]
#: four pops: the prefix ends at the first DELIVERY behind a PACKET
ROWS4 = [
    (lanes.M_PHOLD, [D, D, P, P], [10, 20, 30, 40], [1, 1, 1, 1]),
    (lanes.M_PHOLD, [D, P, D, P], [10, 20, 30, 40], [1, 1, 0, 0]),
    (lanes.M_PHOLD, [P, P, D, P], [10, 20, 30, 40], [1, 1, 0, 0]),
    (lanes.M_PHOLD, [D, D, L, D], [10, 20, 30, 40], [1, 1, 0, 0]),
    (lanes.M_PHOLD, [D, P, P, P], [10, 20, 99, 100], [1, 1, 1, 0]),
    (lanes.M_TGEN_MESH, [P, L, P, L], [10, 20, 30, 40], [1, 1, 1, 1]),
]


def _pop_mask(rows):
    import numpy as np

    models = np.array([r[0] for r in rows], dtype=np.int32)
    kinds = np.array([r[1] for r in rows], dtype=np.int32)
    tlo = np.array([r[2] for r in rows], dtype=np.int32)
    p = lanes.LaneParams(
        n_lanes=len(rows), capacity=8, pops_per_iter=kinds.shape[1],
        log_capacity=0, seed=1, stop_time=10 * MS, bootstrap_end=0,
        runahead=WINDOW_END, models_present=tuple(sorted(set(models))),
        stream_wide_pop=True)
    act, wide = lanes.pop_mask(
        p, models, np.zeros_like(tlo), tlo, kinds, np.int32(0),
        np.int32(WINDOW_END))
    return np.asarray(act).astype(int).tolist(), wide


@pytest.mark.parametrize("rows", [ROWS, ROWS4], ids=["2pops", "4pops"])
def test_the_pop_predicate_on_hand_written_rows(rows, monkeypatch):
    act, wide = _pop_mask(rows)
    for (model, kinds, times, want), got in zip(rows, act):
        assert got == want, (model, kinds, times)
    # the same-instant law, which is what the model set emptied leaves:
    # only PHOLD's rows differ, and the counter counts exactly those slots
    monkeypatch.setattr(lanes, "WINDOW_INERT_MODELS", frozenset())
    old, none = _pop_mask(rows)
    assert none == ()
    for (model, kinds, times, _want), got, was in zip(rows, act, old):
        if model != lanes.M_PHOLD:
            assert got == was, (model, kinds, times)
        assert was == [int(a and b) for a, b in zip(got, was)]
        assert was[0] == got[0]
    assert int(wide) == sum(map(sum, act)) - sum(map(sum, old)) > 0


@pytest.mark.parametrize("hosts, messages, windows", SMALL)
def test_the_co_pop_saves_a_quarter_of_the_iterations(
        hosts, messages, windows, tmp_path, monkeypatch):
    sim, res = _facade_run(hosts, messages, windows, tmp_path)
    plane = sim.engine.lane_plane
    slots = res.counters["lane_iters"] * plane["pops_per_iter"] * hosts
    assert 0 < plane["copop_wide_pops"] < slots
    stats = json.loads((sim.data_dir / "sim-stats.json").read_text())
    assert stats["lane_plane"]["copop_wide_pops"] == plane["copop_wide_pops"]
    assert (sim.obs.finalized["report"]["gauges"]["copop_wide_pops"]
            == plane["copop_wide_pops"])
    assert "copop_wide_pops" not in res.counters
    # a pop sends once: the exchange is K x N rows and has no slot gauges
    assert plane["sends_per_pop"] == 1 and not {
        "exchange_compact_iters", "exchange_slot_budget",
        "exchange_slot_peak"} & set(plane)
    # the same-instant law: the same run in a third more iterations
    monkeypatch.setattr(lanes, "WINDOW_INERT_MODELS", frozenset())
    eng = TpuEngine(_cfg(hosts, messages, windows), log_capacity=0)
    old = eng.run(mode="device")
    assert "copop_wide_pops" not in eng.lane_plane
    assert _shared(old.counters) == _shared(res.counters)
    assert res.counters["lane_iters"] <= 0.75 * old.counters["lane_iters"]
    if (hosts, messages, windows) == (512, 4, 5):
        assert old.counters["lane_iters"] == 74  # ISSUE 38's count
        assert res.counters["lane_iters"] <= 55


def _slow_downlink_mesh(backend):
    """64 x 16 at 10 Mbit: a datagram takes 0.24 ms of a downlink that
    refills every millisecond, so deliveries leave the bucket in bursts
    at the SAME instant from different sources — the tie [P, D'] is
    refused for."""
    cfg = phold_mesh_config(64, 16, 256, "10 ms", "10 Mbit", seed=7)
    cfg.general.stop_time = 60 * MS
    cfg.experimental.network_backend = backend
    return cfg


def _phold_on_a_graph(backend, down, edges):
    """Twelve PHOLD hosts dealt round the graph nodes (one a ``down``
    bandwidth), over the GML ``edges``."""
    nodes = "\n".join(
        f'        node [ id {i} host_bandwidth_up "10 Mbit" '
        f'host_bandwidth_down "{bw}" ]' for i, bw in enumerate(down))
    edges = "\n".join(f"        edge [ {e} ]" for e in edges)
    hosts = "\n".join(
        f"  h{i}: {{network_node_id: {i % len(down)}, processes: "
        f"[{{path: phold, args: [--messages, '6']}}]}}" for i in range(12))
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 150 ms, seed: 11}}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
{nodes}
{edges}
      ]
experimental: {{network_backend: {backend}, tpu_events_per_round: 2}}
hosts:
{hosts}
""")


def _lossy_routed_graph(backend):
    return _phold_on_a_graph(backend, ["10 Mbit", "5 Mbit", "10 Mbit"], [
        'source 0 target 0 latency "2 ms"',
        'source 0 target 1 latency "5 ms" packet_loss 0.02',
        'source 1 target 1 latency "2 ms"',
        'source 1 target 2 latency "3 ms" packet_loss 0.01',
        'source 2 target 2 latency "2 ms"',
    ])


TIES = {"slow_downlink": _slow_downlink_mesh, "lossy_routed": _lossy_routed_graph}


@functools.lru_cache(maxsize=None)
def _ties_oracle(name):
    return CpuEngine(TIES[name]("cpu")).run()


def _assert_ties_equal_the_oracle(name, eng, res):
    oracle = _ties_oracle(name)
    assert len(oracle.event_log) > 1000
    assert res.log_tuples() == oracle.log_tuples()
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds
    # the rule engaged, and deliveries did tie in time across sources
    assert eng.lane_plane["copop_wide_pops"] > 0
    times = {}
    for t, src, dst, _seq, _size, outcome in oracle.log_tuples():
        if outcome == lanes.DELIVERED:
            times.setdefault((t, dst), set()).add(src)
    assert any(len(srcs) > 1 for srcs in times.values())


@pytest.mark.parametrize("mode", ["device", "step"])
@pytest.mark.parametrize("name", sorted(TIES))
def test_delivery_ties_equal_the_oracle(name, mode):
    eng = TpuEngine(TIES[name]("tpu"))
    _assert_ties_equal_the_oracle(name, eng, eng.run(mode=mode))


@pytest.mark.multichip
@pytest.mark.parametrize("devices", [1, 2, 4])
@pytest.mark.parametrize("name", sorted(TIES))
def test_delivery_ties_equal_the_oracle_at_any_mesh_shape(name, devices):
    eng = TpuEngine(TIES[name]("tpu"))
    eng.attach_mesh(parallel.make_mesh(devices))
    _assert_ties_equal_the_oracle(name, eng, eng.run(mode="device"))


def _lowered(cfg):
    eng = TpuEngine(cfg, log_capacity=0)
    return lanes.make_run_fn(eng.params, eng.tables).lower(
        eng.initial_state()).as_text()


def _one_to_one_streams():
    return ConfigOptions.from_yaml("""
general: {stop_time: 1s, seed: 5}
experimental: {network_backend: tpu, tpu_lane_queue_capacity: 128}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 1 latency "15 ms" ]
      ]
hosts:
  c: {network_node_id: 0, processes: [{path: stream-client, args: [--server, s, --size, 200kB]}]}
  s: {network_node_id: 1, processes: [{path: stream-server}]}
""")


@pytest.mark.parametrize("name, differs", [
    ("passive_mesh", False), ("one_to_one_streams", False), ("phold", True)])
def test_the_co_pop_law_is_static(name, differs, monkeypatch):
    """The rule is a term of the programs whose lanes run a window-inert
    model and of no other: with the model set emptied a passive mesh's
    and a one-to-one stream program's lowered text is the same text,
    PHOLD's is not."""
    make = {"passive_mesh": _passive_mesh,
            "one_to_one_streams": _one_to_one_streams,
            "phold": lambda: _cfg(64, 4, 5)}[name]
    with_rule = _lowered(make())
    monkeypatch.setattr(lanes, "WINDOW_INERT_MODELS", frozenset())
    assert (_lowered(make()) != with_rule) == differs


# -- (f) a destination picked at run time on a graph (ISSUE 48) --------------

#: the rehearsal width of ``phold10k_wan_m4``: 64 hosts over
#: ``routed_graph_gml(8, 1)``, 100 windows of 2 ms (4 of ~3 760 hops lost)
WAN = dict(hosts=64, messages=4, graph_nodes=8, stop_ms=200)


def _wan_cfg(backend="tpu", seed=7, hosts=WAN["hosts"], stop_ms=WAN["stop_ms"],
             graph_nodes=WAN["graph_nodes"], graph_seed=1, **shapes):
    cfg = phold_mesh_config(hosts, WAN["messages"], 256, bandwidth="1 Gbit",
                            seed=seed, graph_nodes=graph_nodes,
                            graph_seed=graph_seed)
    cfg.general.stop_time = stop_ms * MS
    cfg.experimental.network_backend = backend
    for key, val in shapes.items():
        setattr(cfg.experimental, key, val)
    return cfg


@functools.lru_cache(maxsize=None)
def _wan_oracle(stop_ms=WAN["stop_ms"]):
    """(result, datagrams sent, events left in the queues at the stop)."""
    eng = CpuEngine(_wan_cfg("cpu", stop_ms=stop_ms))
    res = eng.run()
    return (res, sum(h.send_seq for h in eng.hosts),
            sum(len(h.queue) for h in eng.hosts))


@functools.lru_cache(maxsize=None)
def _wan_lane_run():
    eng = TpuEngine(_wan_cfg(), log_capacity=0)
    return eng.run(mode="device"), dict(eng.lane_plane)


def _assert_equals_the_wan_oracle(res, stop_ms=WAN["stop_ms"]):
    oracle = _wan_oracle(stop_ms)[0]
    assert res.log_tuples() == oracle.log_tuples()
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds == stop_ms // 2
    lost = oracle.counters["lane_drop_loss"]
    assert lost > 0
    assert len(oracle.event_log) == oracle.counters["phold_hops"] + lost


@pytest.mark.parametrize("mode", ["device", "step"])
def test_the_routed_lossy_factory_equals_the_oracle(mode):
    eng = TpuEngine(_wan_cfg())
    _assert_equals_the_wan_oracle(eng.run(mode=mode))
    assert eng.tables.lat.shape == (8, 8) and eng.params.has_loss


def test_the_routed_facade_with_the_log_off_equals_the_oracles_counters(
        tmp_path):
    cfg = _wan_cfg()
    cfg.general.data_directory = str(tmp_path / "data")
    sim = Simulation(cfg, event_log=False)
    res = sim.run()
    oracle = _wan_oracle()[0]
    assert res.event_log == []
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds
    plane = json.loads(
        (sim.data_dir / "sim-stats.json").read_text())["lane_plane"]
    want = _wan_lane_run()[1]
    keys = SHAPES + ("graph_nodes", "has_loss", "path_gather_sends",
                     "path_gather_tables", "path_gather_elems_per_iter")
    assert {k: plane[k] for k in keys} == {k: want[k] for k in keys}


def test_the_command_line_runs_the_routed_network_from_a_file(tmp_path):
    """The factory's network as a user would write it: the graph inline,
    one document a host with its graph node, the law's shapes."""
    import subprocess
    import sys

    cfg = _wan_cfg()
    exp = cfg.experimental
    hosts = "\n".join(
        f"  {h.hostname}: {{network_node_id: {h.network_node_id}, processes: "
        "[{path: phold, args: --messages 4 --size 256}]}" for h in cfg.hosts)
    gml = scenarios.routed_graph_gml(WAN["graph_nodes"], 1, "1 Gbit")
    path = tmp_path / "phold_wan.yaml"
    path.write_text(f"""
general: {{stop_time: {WAN["stop_ms"]} ms, seed: 7, heartbeat_interval: null,
          data_directory: {tmp_path / "data"}}}
network:
  graph:
    type: gml
    inline: |
{chr(10).join("      " + line for line in gml.splitlines())}
experimental: {{network_backend: tpu,
               tpu_lane_queue_capacity: {exp.tpu_lane_queue_capacity},
               tpu_cross_capacity: {exp.tpu_cross_capacity},
               tpu_events_per_round: 2}}
hosts:
{hosts}
""")
    done = subprocess.run(
        [sys.executable, "-m", "shadow_tpu", str(path)],
        capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    stats = json.loads((tmp_path / "data" / "sim-stats.json").read_text())
    oracle = _wan_oracle()[0]
    assert stats["counters"]["phold_hops"] == oracle.counters["phold_hops"]
    assert stats["counters"]["lane_drop_loss"] == oracle.counters[
        "lane_drop_loss"]
    want = _wan_lane_run()[1]
    assert {k: stats["lane_plane"][k] for k in SHAPES} == {
        k: want[k] for k in SHAPES}
    assert stats["lane_plane"]["path_gather_sends"] == 1


@pytest.mark.multichip
@pytest.mark.parametrize("devices", [1, 2, 4])
def test_the_routed_factory_equals_the_oracle_at_any_mesh_shape(devices):
    eng = TpuEngine(_wan_cfg(stop_ms=100))
    eng.attach_mesh(parallel.make_mesh(devices))
    _assert_equals_the_wan_oracle(eng.run(mode="device"), stop_ms=100)
    assert eng.lane_plane["mesh_devices"] == devices


def test_a_decaying_population_keeps_its_laws_in_both_engines():
    """Under path loss no count is analytic, but the population's laws
    hold: a datagram is sent at the start or by a hop, every delivery is a
    hop, a lost message is never replaced, and what is alive at the stop
    is what started less what was lost."""
    start = WAN["hosts"] * WAN["messages"]
    res, _plane = _wan_lane_run()
    c = res.counters
    assert c["lane_sends"] == start + c["phold_hops"]
    assert c["phold_hops"] == c["lane_delivered"]
    assert 0 < c["lane_drop_loss"] <= start
    alive = c["lane_sends"] - c["lane_delivered"] - c["lane_drop_loss"]
    assert alive == start - c["lane_drop_loss"]
    assert "lane_drop_queue" not in c
    # the oracle's own books: per-host send counters and the event queues
    oracle, sent, queued = _wan_oracle()
    assert sent == start + oracle.counters["phold_hops"] == c["lane_sends"]
    assert oracle.counters["lane_drop_loss"] == c["lane_drop_loss"]
    assert queued == start - oracle.counters["lane_drop_loss"] == alive


def _placement(cfg):
    return [h.network_node_id for h in cfg.hosts]


def test_the_placement_follows_the_graph_seed_alone():
    a, b = _wan_cfg(seed=7), _wan_cfg(seed=8)
    assert _placement(a) == _placement(b) and a.network == b.network
    assert (a.general.seed, b.general.seed) == (7, 8)
    # two widths, one graph; the narrower placement is the wider's head
    wide = _wan_cfg(hosts=128)
    assert wide.network == a.network
    assert _placement(wide)[:64] == _placement(a)
    assert wide.network.graph.inline == scenarios.routed_graph_gml(
        8, 1, "1 Gbit")
    other = _wan_cfg(graph_seed=2)
    assert other.network != a.network and _placement(other) != _placement(a)
    # host i keeps its id: documents sort in i order
    assert [h.hostname for h in a.hosts] == [
        f"lp{i:02d}" for i in range(1, 65)]
    assert set(_placement(a)) <= set(range(8))


def test_without_a_graph_the_configuration_is_the_parents():
    """``graph_nodes=None``: the document PR 35 wrote, key for key."""
    cfg = phold_mesh_config(64, 4, 256, "10 ms", "1 Gbit", seed=3)
    queue, cross = phold_shape_law(64, 4)
    assert cfg == ConfigOptions.from_dict({
        "general": {"stop_time": "10 s", "seed": 3,
                    "heartbeat_interval": None},
        "network": {"graph": {"type": "gml", "inline": (
            "graph [\n"
            '  node [ id 0 host_bandwidth_up "1 Gbit" '
            'host_bandwidth_down "1 Gbit" ]\n'
            '  edge [ source 0 target 0 latency "10 ms" ]\n'
            "]\n")}},
        "experimental": {
            "network_backend": "tpu",
            "tpu_lane_queue_capacity": queue,
            "tpu_cross_capacity": cross,
            "tpu_events_per_round": 2,
        },
        "hosts": {"lp": {
            "count": 64, "network_node_id": 0,
            "processes": [{
                "path": "phold",
                "args": ["--messages", "4", "--size", "256"],
                "start_time": "0 s",
            }],
        }},
    })
    assert cfg == phold_mesh_config(64, 4, 256, "10 ms", "1 Gbit", seed=3,
                                    graph_nodes=None, graph_seed=5)
    assert phold_shape_law(10_000, 4) == (42, 18)


def _path_facts(cfg):
    """(window, mean hop, far hop) of the lanes as placed, in ns."""
    import numpy as np

    from shadow_tpu.net.graph import NetworkGraph

    graph = NetworkGraph.from_gml(cfg.network.graph.inline)
    lat = graph.latency_ns
    nodes = [graph.id_to_index[n] for n in _placement(cfg)]
    into = lat[nodes].mean(axis=0)  # mean path into a lane of each node
    return (graph.min_latency_ns(), float(into[nodes].mean()),
            float(into[nodes].max()))


@pytest.mark.parametrize("hosts, graph_nodes",
                         [(64, 8), (512, 8), (10_000, 200)])
def test_the_factorys_graph_shapes_are_the_written_law(hosts, graph_nodes):
    cfg = _wan_cfg(hosts=hosts, graph_nodes=graph_nodes)
    exp = cfg.experimental
    queue, cross, pops = (exp.tpu_lane_queue_capacity, exp.tpu_cross_capacity,
                          exp.tpu_events_per_round)
    window, mean_hop, far_hop = _path_facts(cfg)
    assert window == 2 * MS < mean_hop < far_hop
    windows = scenarios.PHOLD_LAW_HORIZON_NS // window
    assert windows == 5 * scenarios.PHOLD_LAW_WINDOWS
    assert (queue, cross) == phold_shape_law(
        hosts, 4, windows=windows, window_ns=window, mean_hop_ns=mean_hop,
        far_hop_ns=far_hop) and pops == 2
    # the law, restated: the fullest lane of a window is handed the tail of
    # Poisson(messages x window / mean hop); the farthest lane's queue holds
    # Poisson(messages x (far hop + window) / mean hop)
    draws = 1000 * hosts * windows
    iters = -(-2 * poisson_tail_quantile(4 * window / mean_hop, 1 / draws)
              // pops)
    p = 1 / (draws * iters)
    assert _poisson_tail(pops, cross) < p <= _poisson_tail(pops, cross - 1)
    need = poisson_tail_quantile(
        4 * (far_hop + window) / mean_hop, p) + scenarios.QUEUE_HEADROOM
    row = queue + 2 * pops + cross
    assert row & (row - 1) == 0 and queue >= need
    assert row // 2 < need + 2 * pops + cross
    if hosts == 10_000:
        # 39 + 4 + 18 = 61 columns: the one-switch cell's row of 64
        assert (need, queue, cross, iters) == (39, 42, 18, 10)
        assert mean_hop == pytest.approx(18.64e6, rel=1e-3)
        assert far_hop == pytest.approx(28.16e6, rel=1e-3)
    # a longer hop into the far lane never narrows the queue's need
    assert phold_shape_law(hosts, 4, windows=windows, window_ns=window,
                           mean_hop_ns=mean_hop,
                           far_hop_ns=2 * far_hop)[0] >= queue


def test_the_law_refuses_path_facts_out_of_order():
    for facts in (dict(window_ns=3, mean_hop_ns=2, far_hop_ns=4),
                  dict(window_ns=1, mean_hop_ns=5, far_hop_ns=4),
                  dict(window_ns=0, mean_hop_ns=1, far_hop_ns=1)):
        with pytest.raises(ValueError):
            phold_shape_law(64, 4, **facts)


def test_the_graph_laws_widths_sit_above_a_runs_peaks():
    _res, plane = _wan_lane_run()
    exp = _wan_cfg().experimental
    queue, cross = exp.tpu_lane_queue_capacity, exp.tpu_cross_capacity
    assert (plane["queue_capacity"], plane["cross_capacity"],
            plane["pops_per_iter"]) == (queue, cross, 2)
    assert 4 <= plane["queue_peak"] <= queue - scenarios.QUEUE_HEADROOM
    assert 2 <= plane["cross_peak"] <= cross


def test_a_graph_shape_forced_under_its_peak_raises_and_names_the_block():
    plane = _wan_lane_run()[1]
    narrow = TpuEngine(_wan_cfg(
        tpu_lane_queue_capacity=plane["queue_peak"] - 2), log_capacity=0)
    with pytest.raises(RuntimeError) as e:
        narrow.run(mode="device")
    msg = str(e.value)
    assert "off the tail of a lane QUEUE" in msg
    assert "raise experimental.tpu_lane_queue_capacity" in msg
    assert "CROSS" not in msg and "tpu_cross_capacity" not in msg
    narrow = TpuEngine(_wan_cfg(
        tpu_cross_capacity=plane["cross_peak"] - 1), log_capacity=0)
    with pytest.raises(RuntimeError) as e:
        narrow.run(mode="device")
    msg = str(e.value)
    assert "by the CROSS block" in msg
    assert f"the block holds {plane['cross_peak'] - 1})" in msg
    assert "raise experimental.tpu_cross_capacity" in msg
    assert "tpu_lane_queue_capacity" not in msg


def _gossip_on_the_graph():
    from shadow_tpu.config.scenarios import gossip_mesh_config

    cfg = gossip_mesh_config(64, 4, 1, ("10 ms",), 2, 512, bandwidth="1 Gbit",
                             graph_nodes=8, graph_seed=1)
    cfg.general.stop_time = 100 * MS
    return cfg


@pytest.mark.parametrize("name, want", [
    ("phold_on_the_graph", (1, 2, 2 * 64 * 3)),
    ("phold_on_one_switch", (0, 0, 0)),
    ("gossip_on_the_graph", (0, 0, 0)),
])
def test_lane_plane_states_what_a_send_gathers(name, want):
    """``path_gather_tables`` / ``path_gather_elems_per_iter``: static
    facts beside ``path_gather_sends`` in EVERY program's ``lane_plane`` —
    2 packed words and pops x lanes x 3 elements (``node_of[dst]`` and a
    word each) where PHOLD's drawn destination meets a lossy graph, nothing
    where the lookup folds (one node) or the peers' paths are rows
    (gossip)."""
    if name == "phold_on_the_graph":
        plane = _wan_lane_run()[1]
        assert plane["graph_nodes"] == 8 and plane["has_loss"] == 1
    else:
        cfg = (_cfg(64, 4, 5) if name == "phold_on_one_switch"
               else _gossip_on_the_graph())
        eng = TpuEngine(cfg, log_capacity=0)
        eng.run(mode="device")
        plane = eng.lane_plane
        assert lanes.path_gather_load(eng.params, eng.tables) == (0, 0)
        assert (plane["graph_nodes"] > 1) == (name == "gossip_on_the_graph")
    assert (plane["path_gather_sends"], plane["path_gather_tables"],
            plane["path_gather_elems_per_iter"]) == want


def test_a_loss_free_graph_gathers_one_table():
    """Without the loss draw a gathered send reads the latency alone."""
    cfg = _lossy_routed_graph("tpu")
    cfg.network.graph.inline = cfg.network.graph.inline.replace(
        " packet_loss 0.02", "").replace(" packet_loss 0.01", "")
    eng = TpuEngine(cfg, log_capacity=0)
    assert not eng.params.has_loss and eng.tables.lat.shape == (3, 3)
    pops, n = eng.params.pops_per_iter, eng.params.n_lanes
    assert lanes.path_gather_load(eng.params, eng.tables) == (
        1, pops * n * 2)


# -- (g) the packed path words (ISSUE 49) -------------------------------------

#: the largest latency the lane backend admits: NEVER32 itself is refused
MAX_LAT = lanes.NEVER32 - 1


def _edge_cases_graph(far_ns=MAX_LAT):
    """PHOLD over two graph nodes: the hop between them takes ``far_ns``
    and loses 30 %, and node 1's own switch loses everything."""
    return _phold_on_a_graph("tpu", ["10 Mbit", "10 Mbit"], [
        'source 0 target 0 latency "2 ms"',
        f'source 0 target 1 latency "{far_ns} ns" packet_loss 0.3',
        'source 1 target 1 latency "2 ms" packet_loss 1.0',
    ])


#: two epochs inside the run: a longer 0-1 hop, then a 1-2 hop that loses
#: everything (the lose-everything bit arrives with an epoch's words)
PHOLD_FAULTS = (
    {"at": "40 ms", "kind": "latency", "source": 0, "target": 1,
     "latency": "7 ms"},
    {"at": "80 ms", "kind": "loss", "source": 1, "target": 2, "loss": 1.0},
)


def _faulted_cfg(backend):
    cfg = _lossy_routed_graph(backend)
    cfg.faults.events = list(PHOLD_FAULTS)
    return cfg


@functools.lru_cache(maxsize=None)
def _faulted_oracle():
    return CpuEngine(_faulted_cfg("cpu")).run()


def _assert_unpacks_to_the_tables(tb):
    """Every pair's two words, at the flat index a send computes, are the
    ``[G, G]`` tables' three."""
    g = tb.lat.shape[-1]
    a, b = np.asarray(tb.flat_lat), np.asarray(tb.flat_thresh)
    assert (a.shape, a.dtype, b.shape, b.dtype) == (
        (g * g,), np.int32, (g * g,), np.uint32)
    src, dst = np.divmod(np.arange(g * g), g)
    assert ((a & lanes.MASK31) == np.asarray(tb.lat)[src, dst]).all()
    assert ((a < 0) == np.asarray(tb.thresh_all)[src, dst]).all()
    assert (b == np.asarray(tb.thresh_u32)[src, dst]).all()


def _epoch_arguments(eng):
    """The arguments of the program a fault schedule would run: the state,
    the epoch's path leaves, the stop pair and the seed's two words."""
    paths = {f: getattr(eng.tables, f) for f in eng._path_fields}
    text = lanes.make_run_fn(eng.params, eng.tables, epochs=True).lower(
        eng.initial_state(), paths, np.int32(0), np.int32(1), np.uint32(7),
        np.uint32(0)).as_text()
    main = text[text.index("func.func public @main("):]
    return paths, main[:main.index("->")].count("%arg")


def _case_unpack(monkeypatch):
    eng = TpuEngine(_edge_cases_graph(), log_capacity=0)
    tb = eng.tables
    lat, lost = np.asarray(tb.lat), np.asarray(tb.thresh_all)
    assert lat.max() == MAX_LAT == 2**31 - 2 and lat.min() > 0
    assert lost.any() and not lost.all() and eng.params.has_loss
    _assert_unpacks_to_the_tables(tb)
    # the largest latency keeps bit 31 clear; the pair that loses all sets it
    assert np.asarray(tb.flat_lat).max() == MAX_LAT
    assert (np.asarray(tb.flat_lat) < 0).sum() == lost.sum() == 1
    # every fault epoch's words are packed by the same law
    faulted = TpuEngine(_faulted_cfg("tpu"), log_capacity=0)
    plan = faulted._fault_overlay.segment_plan(faulted.params.stop_time)
    seen = set()
    for _start, _end, snap in plan:
        tb = faulted.tables if snap is None else faulted._segment_tables(snap)
        _assert_unpacks_to_the_tables(tb)
        seen.add(np.asarray(tb.flat_lat).tobytes())
    assert len(seen) == len(PHOLD_FAULTS) + 1


def _case_absent(make):
    def case(monkeypatch):
        eng = TpuEngine(make(), log_capacity=0)
        assert eng.tables.flat_lat == () and eng.tables.flat_thresh == ()
        assert not lanes.gathers_path(eng.params, eng.tables.lat.shape[-1])
        assert lanes.path_gather_load(eng.params, eng.tables) == (0, 0)
        # as an epoch's leaves the program is handed the [G, G] tables,
        # the flows' rows and the gossip peers' rows, and no packed word:
        # its arguments are among them (what it does not read is pruned)
        paths, n_args = _epoch_arguments(eng)
        assert not any(f.startswith("flat_") for f in paths)
        assert len(paths) == (6 if isinstance(eng.tables.g_lat, tuple) else 9)
        state = len(jax.tree.leaves(eng.initial_state()))
        assert state < n_args <= state + len(paths) + 4
    return case


def _case_never32(monkeypatch):
    def packed(*_args):
        raise AssertionError("a word was packed before the latency guard")

    monkeypatch.setattr(TpuEngine, "_path_words", packed)
    with pytest.raises(LaneCompatError, match="link latency"):
        TpuEngine(_edge_cases_graph(far_ns=lanes.NEVER32), log_capacity=0)


def _case_faulted(mode):
    def case(monkeypatch):
        oracle = _faulted_oracle()
        eng = TpuEngine(_faulted_cfg("tpu"))
        res = eng.run(mode=mode)
        assert len(oracle.event_log) > 1000
        assert res.log_tuples() == oracle.log_tuples()
        assert _shared(res.counters) == _shared(oracle.counters)
        assert res.rounds == oracle.rounds
        # both epochs were swapped in, and the last one's pair lost it all
        assert eng.lane_plane["fault_epochs"] == len(PHOLD_FAULTS)
        calm = _ties_oracle("lossy_routed").counters["lane_drop_loss"]
        assert res.counters["lane_drop_loss"] > 2 * calm > 0
        assert lanes.path_gather_load(eng.params, eng.tables) == (
            2, eng.params.pops_per_iter * eng.params.n_lanes * 3)
    return case


PACKED_WORDS = {
    "unpack": _case_unpack,
    "absent_on_one_switch": _case_absent(lambda: _cfg(64, 4, 5)),
    "absent_in_an_all_gossip_program": _case_absent(_gossip_on_the_graph),
    "absent_in_a_stream_only_program": _case_absent(_one_to_one_streams),
    "a_latency_at_never32_is_refused_first": _case_never32,
    "faulted_device": _case_faulted("device"),
    "faulted_step": _case_faulted("step"),
}


@pytest.mark.parametrize("case", sorted(PACKED_WORDS))
def test_the_packed_path_words(case, monkeypatch):
    """A gathered send's two words (``flat_lat``: the latency with the
    lose-everything bit at 31; ``flat_thresh``): equal to the ``[G, G]``
    tables pair for pair, at the largest admitted latency and on a pair
    that loses everything, in every fault epoch; ``()`` — and no argument
    of the program — where no send gathers; never packed from a latency
    the guard refuses; and carried by the epoch swap, PHOLD on the lossy
    graph under a schedule against the oracle (log, counters, rounds)."""
    PACKED_WORDS[case](monkeypatch)
