"""Ethereum-style gossip at width (ISSUE 39): ``config/scenarios.py
gossip_mesh_config`` — gossipsub's eager push over a static mesh, ONE pop
that emits up to D sends — against the CPU oracle and against counts
neither engine can fake.

(a) the lane backend equals the oracle — whole event log, counters, rounds,
    the last first-delivery — on ``mode="device"`` and ``mode="step"``, at
    mesh shapes 1 / 2, and on a small LOSSY two-node graph with slow hosts,
    where the ORDER of a pop's F sequence numbers, bucket charges and loss
    draws is what the log shows;
(b) ``gossip_mesh`` is simple, D-regular, symmetric and connected, and the
    publishers of a burst are distinct;
(c) the analytic counts: every node but the publisher receives a first copy
    once and forwards D - 1;
(d) the shape law: deterministic bounds, the merge's row a power of two, a
    run's ``queue_peak`` / ``cross_peak`` under them;
(e) ``sends_per_pop`` is a static property of the models present and 1 for
    every model of today, whose tiny programs lower to the PARENT's text.
"""

import functools
import hashlib

import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import LaneCompatError, TpuEngine
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.config.scenarios import (
    GOSSIP_POPS, gossip_flood_hops, gossip_mesh_config, gossip_shape_law,
)
from shadow_tpu.models.gossip import gossip_mesh, gossip_publishers

import test_phold_mesh as phold_tests

MS = 1_000_000
_shared = phold_tests._shared  # counters less one backend's own bookkeeping
#: (nodes, degree, messages a burst): two bursts each, at 1 s and 2 s
SMALL = [(64, 4, 3), (128, 8, 4), (96, 6, 2)]
BURSTS = ("1 s", "2 s")


def _cfg(nodes, degree, messages, backend="tpu", bursts=BURSTS, **shapes):
    cfg = gossip_mesh_config(nodes, degree, 1, bursts, messages, 512,
                             "10 ms", "1 Gbit", seed=7)
    cfg.general.stop_time = 2200 * MS
    cfg.experimental.network_backend = backend
    for key, val in shapes.items():
        setattr(cfg.experimental, key, val)
    return cfg


def _lossy_cfg(backend="tpu"):
    """Sixteen nodes on TWO graph nodes, degree 4: the edge between the
    graph nodes loses 20 % and a host sends 2 Mbit, so one pop's three or
    four 4 400-bit datagrams wait on the up bucket one after another and
    each draws its own loss: a charge or a draw out of ``k`` order is a
    different departure time or a different lost datagram in the log."""
    args = ["--degree", "4", "--mesh-seed", "3", "--bursts", "1 s,1500 ms",
            "--messages", "3", "--size", "512"]
    group = {"count": 8, "processes": [
        {"path": "gossip", "args": args, "start_time": "0 s"}]}
    return ConfigOptions.from_dict({
        "general": {"stop_time": "2500 ms", "seed": 11,
                    "heartbeat_interval": None, "bootstrap_end_time": "0 s"},
        "network": {"graph": {"type": "gml", "inline": (
            'graph [\n  directed 0\n'
            '  node [ id 0 host_bandwidth_up "2 Mbit" '
            'host_bandwidth_down "2 Mbit" ]\n'
            '  node [ id 1 host_bandwidth_up "2 Mbit" '
            'host_bandwidth_down "2 Mbit" ]\n'
            '  edge [ source 0 target 0 latency "5 ms" ]\n'
            '  edge [ source 1 target 1 latency "5 ms" ]\n'
            '  edge [ source 0 target 1 latency "8 ms" packet_loss 0.2 ]\n'
            ']\n')}},
        "experimental": {"network_backend": backend,
                         "tpu_lane_queue_capacity": 64,
                         "tpu_events_per_round": 2},
        "hosts": {"a": {**group, "network_node_id": 0},
                  "b": {**group, "network_node_id": 1}},
    })


def _oracle_run(cfg):
    eng = CpuEngine(cfg)
    res = eng.run()
    last = max(a.last_first_ns for h in eng.hosts for a in h.apps)
    return res, last


@functools.lru_cache(maxsize=None)
def _oracle(nodes, degree, messages):
    return _oracle_run(_cfg(nodes, degree, messages, "cpu"))


def _counts(nodes, degree, messages, bursts=len(BURSTS)):
    m = bursts * messages
    sends = m * (degree + (nodes - 1) * (degree - 1))
    first = m * (nodes - 1)
    return {"gossip_sends": sends, "gossip_first": first,
            "gossip_duplicates": sends - first}


def _assert_equals_oracle(eng, res, oracle, last):
    assert res.log_tuples() == oracle.log_tuples()
    assert len(oracle.event_log) > 0
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds
    assert eng.lane_plane["gossip_last_first_ns"] == last > 0


# -- (a) against the oracle ---------------------------------------------------


@pytest.mark.parametrize("mode", ["device", "step"])
def test_the_lane_backend_equals_the_oracle(mode):
    eng = TpuEngine(_cfg(64, 4, 3))
    res = eng.run(mode=mode)
    _assert_equals_oracle(eng, res, *_oracle(64, 4, 3))
    assert _shared(res.counters) == _counts(64, 4, 3)
    assert len(res.event_log) == res.counters["gossip_sends"]


@pytest.mark.parametrize("mode", ["device", "step"])
def test_the_order_of_a_pops_sends_on_a_lossy_graph(mode):
    oracle, last = _oracle_run(_lossy_cfg("cpu"))
    eng = TpuEngine(_lossy_cfg())
    res = eng.run(mode=mode)
    _assert_equals_oracle(eng, res, oracle, last)
    # the run did lose datagrams and did wait on buckets, so the order of
    # the F draws and charges was exercised: a lost datagram's record is
    # stamped at the send, a delivered one's after its wait
    assert res.counters["lane_drop_loss"] > 10
    assert eng.params.has_loss and eng.params.sends_per_pop == 4
    assert res.counters["gossip_sends"] > res.counters["lane_delivered"]


@pytest.mark.parametrize("devices", [2, 4])
def test_any_mesh_shape_equals_the_oracle(devices):
    """The lane axis sharded, at degree 2 (a ring of 16): the sharded build
    takes the fan-out in loop form (``scan_or_unroll(spmd_unroll=True)``),
    whose chain of F charges costs XLA:CPU 35x more with every send, so a
    wider mesh does not end in useful time HERE (the chip unrolls it at
    every F; 64 x 4 ran past 300 s on two virtual devices)."""
    def cfg(backend):
        c = _cfg(16, 2, 2, backend, bursts=("1 s",))
        c.general.stop_time = 1200 * MS
        return c

    eng = TpuEngine(cfg("tpu"))
    eng.attach_mesh(parallel.make_mesh(devices))
    res = eng.run(mode="device")
    _assert_equals_oracle(eng, res, *_oracle_run(cfg("cpu")))
    assert eng.lane_plane["mesh_devices"] == devices
    assert res.counters["gossip_sends"] == 2 * (2 + 15)


def test_gossip_beside_what_it_cannot_share_a_program_with_is_refused():
    cfg = _cfg(64, 4, 3)
    cfg.hosts[0].pcap_enabled = True
    with pytest.raises(LaneCompatError, match="gossip lanes beside"):
        TpuEngine(cfg)
    with pytest.raises(ValueError, match="gossip lanes beside"):
        lanes.LaneParams(
            n_lanes=8, capacity=16, pops_per_iter=2, log_capacity=0, seed=1,
            stop_time=MS, bootstrap_end=0, runahead=MS, gossip_degree=4,
            models_present=(lanes.M_GOSSIP, lanes.M_STREAM_CLIENT))


def test_a_burst_at_the_process_start_is_refused_by_both_backends():
    cfg = _cfg(64, 4, 3, bursts=("0 s",))
    with pytest.raises(LaneCompatError, match="not after the process start"):
        TpuEngine(cfg)
    cfg.experimental.network_backend = "cpu"
    with pytest.raises(ValueError, match="not after the process start"):
        CpuEngine(cfg).run()


# -- (b) the mesh and the publishers -----------------------------------------


@pytest.mark.parametrize("nodes, degree, seed", [
    (64, 4, 1), (64, 4, 2), (10, 8, 1), (1000, 8, 1), (1000, 8, 5),
    (257, 12, 3), (5, 2, 1)])
def test_the_mesh_is_simple_regular_symmetric_and_connected(
        nodes, degree, seed):
    peers = gossip_mesh(nodes, degree, seed)
    assert peers.shape == (nodes, degree) and peers.dtype == np.int32
    rows = np.arange(nodes)[:, None]
    # simple: no self-loop, no peer twice
    assert not (peers == rows).any()
    assert all(len(set(r)) == degree for r in peers.tolist())
    # symmetric: i lists j exactly when j lists i
    adj = np.zeros((nodes, nodes), dtype=bool)
    adj[rows, peers] = True
    assert (adj == adj.T).all() and (adj.sum(axis=1) == degree).all()
    # connected: a flood from node 0 reaches every node
    seen = np.zeros(nodes, dtype=bool)
    seen[0] = True
    for _ in range(nodes):
        seen |= adj[seen].any(axis=0)
    assert seen.all()
    # a pure function of its arguments
    assert (gossip_mesh.__wrapped__(nodes, degree, seed) == peers).all()


def test_the_mesh_refuses_nonsense():
    for nodes, degree in [(8, 3), (8, 8), (8, 0), (4, 6)]:
        with pytest.raises(ValueError):
            gossip_mesh(nodes, degree, 1)


def test_the_publishers_of_a_burst_are_distinct():
    pubs = gossip_publishers(100, 5, 8, 1)
    assert pubs.shape == (5, 8)
    assert all(len(set(row)) == 8 for row in pubs.tolist())
    assert (pubs != gossip_publishers(100, 5, 8, 2)).any()
    with pytest.raises(ValueError):
        gossip_publishers(4, 1, 5, 1)


# -- (c) the analytic counts ---------------------------------------------------


@functools.lru_cache(maxsize=None)
def _lane_run(nodes, degree, messages):
    eng = TpuEngine(_cfg(nodes, degree, messages), log_capacity=0)
    return eng.run(mode="device"), dict(eng.lane_plane)


@pytest.mark.parametrize("nodes, degree, messages", SMALL)
def test_every_node_receives_once_and_forwards_to_all_but_one(
        nodes, degree, messages):
    res, plane = _lane_run(nodes, degree, messages)
    assert _shared(res.counters) == _counts(nodes, degree, messages)
    # every send is delivered (zero loss, nothing shed)
    assert res.counters["lane_delivered"] == res.counters["gossip_sends"]
    assert (plane["sends_per_pop"], plane["gossip_degree"]) == (
        degree, degree)
    # the last first-delivery lies inside the second burst's flood
    assert 2000 * MS < plane["gossip_last_first_ns"] < 2200 * MS


# -- (d) the shape law -----------------------------------------------------------


@pytest.mark.parametrize("nodes, degree, messages", SMALL)
def test_a_runs_peaks_sit_under_the_laws_bounds(nodes, degree, messages):
    _res, plane = _lane_run(nodes, degree, messages)
    queue, cross = gossip_shape_law(degree, messages)
    assert (plane["queue_capacity"], plane["cross_capacity"],
            plane["pops_per_iter"]) == (queue, cross, GOSSIP_POPS)
    # the deterministic bounds themselves, not only the padded shapes
    assert plane["cross_peak"] <= degree * GOSSIP_POPS == cross
    assert plane["queue_peak"] <= degree * messages + 2 <= queue
    row = queue + 2 * GOSSIP_POPS + cross
    assert row & (row - 1) == 0


def test_the_law_counts_close_bursts_as_one_and_refuses_nonsense():
    # the deployment's own shapes: 8 x 8 arrivals + start + one publish
    # timer + headroom 8 = 82 -> a row of 128 less 4 self and 16 cross
    assert gossip_shape_law(8, 8) == (108, 16)
    assert gossip_flood_hops(10_000, 8) == 12 and gossip_flood_hops(9, 2) == 5
    far = gossip_mesh_config(64, 4, 1, ("1 s", "2 s"), 6)
    near = gossip_mesh_config(64, 4, 1, ("1 s", "1050 ms"), 6)
    assert far.experimental.tpu_lane_queue_capacity == 52
    assert near.experimental.tpu_lane_queue_capacity == 116
    with pytest.raises(ValueError):
        gossip_shape_law(0, 1)


def test_a_queue_forced_under_its_peak_raises_and_names_the_block():
    eng = TpuEngine(_cfg(64, 4, 3, tpu_lane_queue_capacity=10),
                    log_capacity=0)
    with pytest.raises(RuntimeError, match="lane QUEUE"):
        eng.run(mode="device")


# -- (e) one law, static --------------------------------------------------------

#: sha256 of the lowered text of three tiny programs AT THE PARENT COMMIT
#: (PR 38, 16f3482): where a pop sends once, this PR's ``[F, N]`` send
#: channel is the parent's ``[N]`` one, operation for operation.  A later
#: PR that changes the body changes these on purpose: recompute them with
#: ``_lowered`` on its own parent and say so.
PARENT_TEXT = {
    "phold": "c6e0b440614a00160852102498c97e9bce22d1416137ca355cd6cf506eb9fb54",
    "passive_mesh":
        "3433396c1a117ae9005927bebd3a176ccb3d142385a90ebaa6cedbee40f9d43f",
    "one_to_one_streams":
        "91569b63930e08e8d784d64179a05b58b2289c8dccf1eea7621fb458abcc461a",
}
TINY = {"phold": lambda: phold_tests._cfg(64, 4, 5),
        "passive_mesh": phold_tests._passive_mesh,
        "one_to_one_streams": phold_tests._one_to_one_streams}


@pytest.mark.parametrize("name", sorted(TINY))
def test_where_a_pop_sends_once_the_program_is_the_parents(name):
    eng = TpuEngine(TINY[name](), log_capacity=0)
    assert eng.params.sends_per_pop == 1 and eng.params.gossip_degree == 0
    assert eng.tables.g_peers == () and eng.initial_state().gossip == ()
    text = phold_tests._lowered(TINY[name]())
    assert "gossip" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[name]


def test_sends_per_pop_is_a_static_property_of_the_models_present():
    base = dict(n_lanes=8, capacity=16, pops_per_iter=2, log_capacity=0,
                seed=1, stop_time=MS, bootstrap_end=0, runahead=MS)
    for model in range(lanes.M_GOSSIP):
        p = lanes.LaneParams(**base, models_present=(model,))
        assert p.sends_per_pop == 1 and p.exchange_entries == 16
    p = lanes.LaneParams(**base, models_present=(lanes.M_GOSSIP,),
                         gossip_degree=8)
    assert p.sends_per_pop == 8 and p.exchange_entries == 2 * 8 * 8
    assert p.lanes_have_payload and p.copop_inert and not p.all_passive
    with pytest.raises(ValueError, match="gossip_degree"):
        lanes.LaneParams(**base, models_present=(lanes.M_GOSSIP,))
    # the fan-out and the bitmap test-and-set are named stages of the
    # gossip program
    eng = TpuEngine(_cfg(64, 4, 3), log_capacity=0)
    text = lanes.make_run_fn(eng.params, eng.tables).lower(
        eng.initial_state()).as_text(debug_info=True)
    assert "gossip_fanout" in text and "gossip_seen" in text
