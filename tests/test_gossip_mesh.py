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
    every model of today, whose tiny programs lower to the PARENT's text;
(f) the propagation histogram (ISSUE 41): 16 age buckets, one law in
    ``models/gossip.py`` for both backends, counters the oracle's equal;
(g) the same factory over a routed, lossy graph (``graph_nodes``): ONE
    network whatever the run's seed, the lane engine equal to the oracle
    on it — fused, step and sharded — and one program for every seed;
(h) a static destination's path is a table row (ISSUE 42): on a graph of
    more than one node a gossip lane's D peers carry ``[F, N]`` latency
    and loss-threshold rows, equal to the ``[G, G]`` tables element for
    element and rebuilt at every fault epoch; an all-gossip program
    traces no ``[G, G]`` gather, a program with a run-time destination
    beside it gathers for send 0 alone, and both equal the oracle;
(i) the exchange sorts the slots that sent (ISSUE 43): a fan-out
    program's exchange runs over its SENDING (pop, lane) slots,
    ``LaneParams.exchange_slot_budget`` of them a pass — a law of the
    shape; with the budget patched under a flood's front one run holds
    one-pass and many-pass iterations and equals the oracle, on one
    switch, on the routed lossy graph and under faults; the passes
    together hand every lane the rows and the count ONE exchange of the
    whole send channel would; and the three gauges keep their law;
(j) failure as part of the workload (ISSUE 45): a faulted run is ONE
    compiled program for all its segments and repeats — path leaves, stop
    bound and seed words its arguments, the leaves placed once an engine —
    which ``precompile`` compiles ahead; the factory's ``faults`` is a
    function of ``fault_seed`` alone and touches no self-edge; under
    partition / heal and link_up schedules on the routed graph both
    engines agree and keep the two conservation laws of a flood;
(k) a row carries the payload words its models use (ISSUE 46):
    ``LaneParams.payload_words`` is static — 1 for gossip (the message id,
    ``plo``; ``q_phi`` is ``()`` and no sort, gather or carry of the
    program holds a seventh word), 2 where stream events ride the [N]
    queues, 0 elsewhere, whose programs are the parent's text — and the
    packed carry, a checkpoint and the sharded placement hold a state of
    any word count;
(l) a duplicate the lane already knows is counted at its PACKET pop
    (ISSUE 47, ``lanes.gossip_elides``): a copy whose message's bit is
    set when its PACKET pops, delivered before the window's end, queues
    no DELIVERY row — the UNEDITED oracle keeps the event and every
    parity test above stays as it was; ``lane_plane["gossip_elided"]`` is
    over 0, at most what a counted run of the oracle says heap order
    would elide, and in no ``SimResult.counters``; each flood takes fewer
    iterations than with the predicate patched off; the window gate (slow
    hosts: without it the rounds come out short); the stale view (one
    pop an iteration elides exactly the oracle's count, two fewer); and a
    program without gossip lanes carries no such word.
"""

import functools
import hashlib
import re

import jax
import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import LaneCompatError, TpuEngine
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.config.scenarios import (
    GOSSIP_POPS, gossip_flood_hops, gossip_mesh_config, gossip_shape_law,
    routed_graph_gml, slot_chaos_events,
)
from shadow_tpu.faults.schedule import parse_event
from shadow_tpu.net.graph import NetworkGraph
from shadow_tpu.core import rng
from shadow_tpu.models.gossip import (
    AGE_COUNTERS, AGE_EDGES_MS, age_counter, gossip_mesh, gossip_publishers,
)

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
    last = max(getattr(a, "last_first_ns", 0)
               for h in eng.hosts for a in h.apps)
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


def _ages(counters):
    """The propagation histogram of a result, in bucket order."""
    return [counters.get(key, 0) for key in AGE_COUNTERS]


def _less_ages(counters):
    """The shared counters without the histogram's buckets, which must
    sum to ``gossip_first`` (every first delivery counts into one)."""
    assert sum(_ages(counters)) == counters.get("gossip_first", 0)
    return {k: v for k, v in _shared(counters).items()
            if k not in AGE_COUNTERS}


def _assert_equals_oracle(eng, res, oracle, last):
    assert res.log_tuples() == oracle.log_tuples()
    assert len(oracle.event_log) > 0
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds == oracle.rounds
    assert eng.lane_plane["gossip_last_first_ns"] == last > 0
    # the run's last first delivery belongs to the last burst: its age's
    # bucket is counted, and no later than the last non-empty one
    ages = _ages(res.counters)
    assert sum(ages) == res.counters["gossip_first"]
    bucket = AGE_COUNTERS.index(
        age_counter(last - max(eng.params.gossip_bursts)))
    assert ages[bucket] > 0
    if len(eng.params.gossip_bursts) == 1:
        # one burst: it is the last non-empty bucket
        assert not any(ages[bucket + 1:])


@pytest.fixture
def slot_budget(monkeypatch):
    """Patch the static slot budget (as tests patch ``_ONEHOT_BUDGET``), so
    that a tiny flood's front takes several passes of the exchange; None
    leaves the law's own (a tile of slots or every slot), under which a
    tiny program's every iteration is one pass.  Returns the check of the
    gauges' law."""
    def patch(slots):
        if slots is not None:
            monkeypatch.setattr(lanes.LaneParams, "exchange_slot_budget",
                                property(lambda self: slots))

        def check(eng, res):
            plane, iters = eng.lane_plane, res.counters["lane_iters"]
            assert plane["exchange_slot_budget"] == (
                eng.params.exchange_slot_budget)
            fits = plane["exchange_slot_peak"] <= plane["exchange_slot_budget"]
            assert 0 < plane["exchange_compact_iters"] <= iters
            assert (plane["exchange_compact_iters"] == iters) == fits
            assert fits == (slots is None)
        return check
    return patch


# -- (a) against the oracle ---------------------------------------------------


@pytest.mark.parametrize("mode", ["device", "step"])
def test_the_lane_backend_equals_the_oracle(mode):
    eng = TpuEngine(_cfg(64, 4, 3))
    res = eng.run(mode=mode)
    _assert_equals_oracle(eng, res, *_oracle(64, 4, 3))
    assert _less_ages(res.counters) == _counts(64, 4, 3)
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
    assert _less_ages(res.counters) == _counts(nodes, degree, messages)
    # on one switch hop h lands exactly 10 (h + 1) ms after the burst: the
    # buckets are the flood's hops, the first the publishers' own peers
    assert _ages(res.counters)[0] == len(BURSTS) * messages * degree
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

#: sha256 of the lowered text of four tiny programs AT THE PARENT COMMIT:
#: where a pop sends once, the ``[F, N]`` send channel is the ``[N]`` one,
#: operation for operation (PR 39).  A later PR that changes the body
#: changes these on purpose: recompute them with ``_lowered`` on its own
#: parent and say so.  PR 46 did, on its parent (PR 45, 635dc0e): the
#: three of PR 38's tree (16f3482) read what they read there — rows that
#: carry NO payload word (``one_to_one_streams`` is the TIERED stream
#: program: its two words live in the [2S] block) — and ``star_streams``
#: (six clients of one server, un-tiered: rows of TWO payload words, the
#: other fork of every list PR 46 folded) is new and the parent's too.
#: PR 50 changed, on purpose, the three in which some lane's model is
#: active (the loop ledger, ``lanes.LaneState.loop_hist`` / ``loop_acc``:
#: two leaves in the carry; c6e0b440..., 91569b63... and 6eb83eb2... at
#: its parent, aca1e6f): they are of PR 50's own tree.  ``passive_mesh``
#: carries no ledger and is the parent's, operation for operation.
PARENT_TEXT = {
    "phold": "748304448f76b71df02eef925ff50a08736fee727a973c566680613280b2398b",
    "passive_mesh":
        "3433396c1a117ae9005927bebd3a176ccb3d142385a90ebaa6cedbee40f9d43f",
    "one_to_one_streams":
        "b561886f11de2529d95c5d767867626ae7604d17a6d8889a041961379074a38a",
    "star_streams":
        "660843247dc0f033ca25de807175f10714217140f8352b794abf05b8549ed59d",
}


def _star_streams():
    import test_lane_parity

    return ConfigOptions.from_yaml(test_lane_parity.STREAM_STAR)


TINY = {"phold": lambda: phold_tests._cfg(64, 4, 5),
        "passive_mesh": phold_tests._passive_mesh,
        "one_to_one_streams": phold_tests._one_to_one_streams,
        "star_streams": _star_streams}
#: the payload words a row of each tiny program's [N] queues carries
TINY_WORDS = {"phold": 0, "passive_mesh": 0, "one_to_one_streams": 0,
              "star_streams": 2}


@pytest.mark.parametrize("name", sorted(TINY))
def test_where_a_pop_sends_once_the_program_is_the_parents(name):
    eng = TpuEngine(TINY[name](), log_capacity=0)
    assert eng.params.sends_per_pop == 1 and eng.params.gossip_degree == 0
    assert eng.tables.g_peers == () and eng.initial_state().gossip == ()
    # no slot budget, no counter in the carry: the exchange is K x N rows
    assert eng.params.exchange_slot_budget == 0
    assert eng.initial_state().exchange_compact_iters == ()
    assert eng.initial_state().exchange_slot_peak == ()
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
    assert p.payload_words == 1 and p.copop_inert and not p.all_passive
    with pytest.raises(ValueError, match="gossip_degree"):
        lanes.LaneParams(**base, models_present=(lanes.M_GOSSIP,))
    # the fan-out and the bitmap test-and-set are named stages of the
    # gossip program
    eng = TpuEngine(_cfg(64, 4, 3), log_capacity=0)
    text = lanes.make_run_fn(eng.params, eng.tables).lower(
        eng.initial_state()).as_text(debug_info=True)
    assert "gossip_fanout" in text and "gossip_seen" in text


# -- (f) the propagation histogram ---------------------------------------------


@pytest.mark.parametrize("age_ms, name", [
    (0, "gossip_first_le_10ms"), (10, "gossip_first_le_10ms"),
    (10.000001, "gossip_first_le_20ms"), (60, "gossip_first_le_60ms"),
    (61, "gossip_first_le_80ms"), (100, "gossip_first_le_100ms"),
    (124.5, "gossip_first_le_125ms"), (150.5, "gossip_first_le_200ms"),
    (499, "gossip_first_le_500ms"), (500, "gossip_first_le_500ms"),
    (500.000001, "gossip_first_gt_500ms"), (11_000, "gossip_first_gt_500ms")])
def test_an_age_counts_into_the_first_bucket_whose_edge_it_does_not_pass(
        age_ms, name):
    assert age_counter(round(age_ms * MS)) == name
    assert len(AGE_COUNTERS) == len(AGE_EDGES_MS) + 1 == 16
    assert list(AGE_EDGES_MS) == sorted(set(AGE_EDGES_MS))


def test_the_buckets_of_one_switch_are_the_floods_hops():
    """One switch, 10 ms a hop: a first delivery of hop h is exactly 10 (h
    + 1) ms old, so the histogram is the flood's breadth by hop, counted
    here from the mesh alone (a breadth-first walk from each publisher)."""
    res, _plane = _lane_run(64, 4, 3)
    peers = gossip_mesh(64, 4, 1)
    want = [0] * len(AGE_COUNTERS)
    for pub in gossip_publishers(64, len(BURSTS), 3, 1).reshape(-1):
        seen, front, hop = {int(pub)}, {int(pub)}, 0
        while front:
            front = {int(q) for i in front for q in peers[i]} - seen
            seen |= front
            want[AGE_COUNTERS.index(age_counter(10 * (hop + 1) * MS))] += (
                len(front))
            hop += 1
    assert _ages(res.counters) == want and sum(want) == 6 * 63


def test_lanes_of_different_bursts_are_refused():
    cfg = _cfg(64, 4, 3)
    args = cfg.hosts[5].processes[0].args
    args[args.index("--bursts") + 1] = "1000000000 ns,2100000000 ns"
    with pytest.raises(LaneCompatError, match="different bursts"):
        TpuEngine(cfg)
    with pytest.raises(ValueError, match="gossip_messages"):
        lanes.LaneParams(
            n_lanes=8, capacity=16, pops_per_iter=2, log_capacity=0, seed=1,
            stop_time=MS, bootstrap_end=0, runahead=MS, gossip_degree=4,
            gossip_messages=0, models_present=(lanes.M_GOSSIP,))


def test_the_histogram_is_a_named_stage_and_nothing_lane_sized():
    eng = TpuEngine(_cfg(64, 4, 3), log_capacity=0)
    state = eng.initial_state()
    assert state.gossip_age.shape == (16,) and state.gossip_age.dtype == (
        np.int32)
    assert eng.params.gossip_bursts == (1000 * MS, 2000 * MS)
    assert eng.params.gossip_messages == 3
    text = lanes.make_run_fn(eng.params, eng.tables).lower(
        state).as_text(debug_info=True)
    assert "gossip_age" in text


# -- (g) the same factory over a routed, lossy graph -----------------------------

#: 96 nodes over 12 graph nodes, D 4, two bursts of three
WAN = dict(nodes=96, degree=4, messages=3, graph_nodes=12)


def _wan_cfg(backend="tpu", seed=7, graph_seed=1, nodes=WAN["nodes"],
             degree=WAN["degree"], messages=WAN["messages"],
             graph_nodes=WAN["graph_nodes"], bursts=BURSTS, stop_ms=2200,
             **faults):
    cfg = gossip_mesh_config(nodes, degree, 1, bursts, messages, 512,
                             bandwidth="1 Gbit", seed=seed,
                             graph_nodes=graph_nodes, graph_seed=graph_seed,
                             **faults)
    cfg.general.stop_time = stop_ms * MS
    cfg.experimental.network_backend = backend
    return cfg


@functools.lru_cache(maxsize=None)
def _wan_oracle(seed=7):
    return _oracle_run(_wan_cfg("cpu", seed))


@pytest.mark.parametrize("budget", [None, 8], ids=["one_pass", "passes"])
@pytest.mark.parametrize("mode", ["device", "step"])
def test_on_a_routed_lossy_graph_the_lane_backend_equals_the_oracle(
        mode, budget, slot_budget):
    oracle, last = _wan_oracle()
    check_gauges = slot_budget(budget)
    eng = TpuEngine(_wan_cfg())
    res = eng.run(mode=mode)
    _assert_equals_oracle(eng, res, oracle, last)
    check_gauges(eng, res)
    plane, c = eng.lane_plane, res.counters
    assert (plane["graph_nodes"], plane["has_loss"], plane["window_ns"],
            plane["sends_per_pop"]) == (12, 1, 2 * MS, 4)
    assert plane["max_path_latency_ns"] > plane["window_ns"]
    # the deterministic shape bounds hold whatever the latency and loss
    queue, cross = gossip_shape_law(4, 3)
    assert plane["queue_peak"] <= queue and plane["cross_peak"] <= cross
    # some copies were lost, no node missed a message: the two analytic
    # counts hold under loss, and every send was a first copy, a duplicate
    # or a lost datagram
    want = _counts(96, 4, 3)
    assert c["lane_drop_loss"] > 0
    assert (c["gossip_sends"], c["gossip_first"]) == (
        want["gossip_sends"], want["gossip_first"])
    assert c["gossip_duplicates"] + c["lane_drop_loss"] == (
        c["gossip_sends"] - c["gossip_first"])
    # ages are no longer multiples of one link: several buckets fill
    assert sum(1 for count in _ages(c) if count) >= 4


def test_another_run_seed_is_another_run_of_the_same_network():
    a, b = _wan_oracle(7)[0], _wan_oracle(8)[0]
    assert a.log_tuples() != b.log_tuples()
    for key in ("gossip_sends", "gossip_first"):
        assert a.counters[key] == b.counters[key]


def test_without_a_graph_the_factory_returns_todays_configuration():
    """Field for field: ONE host group with ``count`` on one graph node,
    the self-edge's latency the lookahead (written out as PR 39 built
    it)."""
    got = gossip_mesh_config(64, 4, 1, ("1 s", "2 s"), 3, 512, "10 ms",
                             "1 Gbit", seed=7)
    want = ConfigOptions.from_dict({
        "general": {"stop_time": "12 s", "seed": 7,
                    "heartbeat_interval": None},
        "network": {"graph": {"type": "gml", "inline": (
            "graph [\n"
            '  node [ id 0 host_bandwidth_up "1 Gbit" '
            'host_bandwidth_down "1 Gbit" ]\n'
            '  edge [ source 0 target 0 latency "10 ms" ]\n'
            "]\n")}},
        "experimental": {
            "network_backend": "tpu", "tpu_lane_queue_capacity": 52,
            "tpu_cross_capacity": 8, "tpu_events_per_round": 2},
        "hosts": {"node": {
            "count": 64, "network_node_id": 0,
            "processes": [{
                "path": "gossip",
                "args": ["--degree", "4", "--mesh-seed", "1", "--bursts",
                         "1000000000 ns,2000000000 ns", "--messages", "3",
                         "--size", "512"],
                "start_time": "0 s"}]}},
    })
    assert got == want
    assert got == gossip_mesh_config(64, 4, 1, ("1 s", "2 s"), 3, 512,
                                     "10 ms", "1 Gbit", seed=7,
                                     graph_nodes=None, graph_seed=5)


def test_the_graph_and_the_placement_depend_on_graph_seed_alone():
    base = _wan_cfg(seed=7)
    assert base.network.graph.inline == routed_graph_gml(12, 1, "1 Gbit")

    def placement(cfg):
        return [h.network_node_id for h in cfg.hosts]

    # the run's seed, the mesh's seed and the traffic move nothing
    other = gossip_mesh_config(96, 4, 5, ("3 s",), 2, 256,
                               bandwidth="1 Gbit", seed=99, graph_nodes=12,
                               graph_seed=1)
    assert placement(other) == placement(base)
    assert other.network.graph.inline == base.network.graph.inline
    assert set(placement(base)) == set(range(12))  # 96 draws over 12 nodes
    # host i keeps its id (names sort in numeric order), so its mesh row
    # and publications are the one-switch deployment's
    assert [h.hostname for h in base.hosts] == [
        f"node{i:02d}" for i in range(1, 97)]
    # a wider network extends the same stream; another graph_seed is
    # another network
    wide = _wan_cfg(nodes=128, degree=4)
    assert placement(wide)[:96] == placement(base)
    moved = _wan_cfg(graph_seed=2)
    assert placement(moved) != placement(base)
    assert moved.network.graph.inline == routed_graph_gml(12, 2, "1 Gbit")
    # the shapes are the deterministic law's: latency moves none of them
    assert (base.experimental.tpu_lane_queue_capacity,
            base.experimental.tpu_cross_capacity,
            base.experimental.tpu_events_per_round) == (52, 8, 2)


def test_close_bursts_are_counted_over_the_longest_routed_path():
    from shadow_tpu.net.graph import NetworkGraph

    longest = NetworkGraph.from_gml(
        routed_graph_gml(12, 1, "1 Gbit")).max_latency_ns()
    assert longest > 10 * MS
    span_ms = gossip_flood_hops(96, 4) * longest // MS
    # 130 ms apart: two bursts on one 10 ms switch (a flood is budgeted 12
    # hops x 10 ms), ONE burst on the graph (12 hops x the longest path)
    assert 10 * gossip_flood_hops(96, 4) < 130 < span_ms
    switch = gossip_mesh_config(96, 4, 1, ("1 s", "1130 ms"), 6)
    graph = _wan_cfg(bursts=("1 s", "1130 ms"), messages=6)
    assert switch.experimental.tpu_lane_queue_capacity == 52
    assert graph.experimental.tpu_lane_queue_capacity == 116


def test_one_routed_gossip_program_serves_every_run_seed():
    texts = set()
    for seed in (1, 2**31 - 2):
        eng = TpuEngine(_wan_cfg(seed=seed), log_capacity=0)
        assert eng.params.has_loss and eng.params.sends_per_pop == 4
        fn = lanes.make_run_fn(eng.params, eng.tables)
        words = tuple(np.uint32(w) for w in rng._split_seed(seed))
        texts.add(fn.lower(eng.initial_state(), *words).as_text())
    assert len(texts) == 1
    # the engine hands the words over on its fused path
    eng.run(mode="device")
    assert [int(w) for w in eng._seed_args] == [2**31 - 2, 0]


@pytest.mark.parametrize("devices", [2, 4])
def test_any_mesh_shape_equals_the_oracle_on_the_graph(devices):
    """The sharded rehearsal at degree 2 (see
    ``test_any_mesh_shape_equals_the_oracle``): a ring of 16 over four
    graph nodes, the histogram replicated and reduced across the shards."""
    def cfg(backend):
        return _wan_cfg(backend, nodes=16, degree=2, messages=2,
                        graph_nodes=4, bursts=("1 s",), stop_ms=1400)

    eng = TpuEngine(cfg("tpu"))
    eng.attach_mesh(parallel.make_mesh(devices))
    res = eng.run(mode="device")
    _assert_equals_oracle(eng, res, *_oracle_run(cfg("cpu")))
    assert eng.lane_plane["mesh_devices"] == devices
    assert eng.lane_plane["graph_nodes"] == 4
    assert sum(_ages(res.counters)) == res.counters["gossip_first"] > 0


# -- (h) a static destination's path is a table row ------------------------------


def _rows_cfg(backend="tpu", degree=4, faults=()):
    """Eighteen gossip nodes over THREE graph nodes, beside two tgen
    clients whose datagrams go to a server each: a client's one send a
    tick takes its path from the ``[G, G]`` gather (send 0 of a mixed
    program), a gossip lane's from its rows.  Every path between graph
    nodes 1 and 2 loses everything (``thresh_all``), the others 0 / 5 /
    20 %, and a host sends 2 Mbit, so the order of a pop's charges and
    draws shows in the log.  PHOLD and tgen-mesh cannot stand here: their
    datagrams reach every host, and the oracle's gossip handler reads a
    message id off whatever it is handed."""
    args = ["--degree", str(degree), "--mesh-seed", "3", "--bursts",
            "1 s,1500 ms", "--messages", "3", "--size", "512"]
    gossip = {"count": 6, "processes": [
        {"path": "gossip", "args": args, "start_time": "0 s"}]}

    def client(server):
        return {"processes": [{
            "path": "tgen-client", "start_time": "990 ms",
            "args": ["--server", server, "--interval", "7 ms", "--size",
                     "900"]}]}

    server = {"processes": [{"path": "tgen-server", "start_time": "0 s"}]}
    node = ('  node [ id %d host_bandwidth_up "2 Mbit" '
            'host_bandwidth_down "2 Mbit" ]\n')
    cfg = {
        "general": {"stop_time": "2500 ms", "seed": 11,
                    "heartbeat_interval": None, "bootstrap_end_time": "0 s"},
        "network": {"graph": {"type": "gml", "inline": (
            "graph [\n  directed 0\n" + node % 0 + node % 1 + node % 2
            + '  edge [ source 0 target 0 latency "5 ms" ]\n'
            '  edge [ source 1 target 1 latency "5 ms" ]\n'
            '  edge [ source 2 target 2 latency "6 ms" packet_loss 0.05 ]\n'
            '  edge [ source 0 target 1 latency "8 ms" packet_loss 0.2 ]\n'
            '  edge [ source 1 target 2 latency "11 ms" packet_loss 1.0 ]\n'
            '  edge [ source 0 target 2 latency "9 ms" ]\n]\n')}},
        "experimental": {"network_backend": backend,
                         "tpu_lane_queue_capacity": 64,
                         "tpu_events_per_round": 2},
        "hosts": {"a": {**gossip, "network_node_id": 0},
                  "b": {**gossip, "network_node_id": 1},
                  "c": {**gossip, "network_node_id": 2},
                  "ka": {**client("sb"), "network_node_id": 0},
                  "kb": {**client("sa"), "network_node_id": 2},
                  "sa": {**server, "network_node_id": 0},
                  "sb": {**server, "network_node_id": 1}},
    }
    if faults:
        cfg["faults"] = {"events": list(faults)}
    return ConfigOptions.from_dict(cfg)


#: epochs that change the latency and the loss of links between a gossip
#: node and its peers, inside both floods: a longer 0-1 link, a 0-2 link
#: that loses everything, a 1-2 link that no longer does
FAULTS = (
    {"at": "1010 ms", "kind": "latency", "source": 0, "target": 1,
     "latency": "12 ms"},
    {"at": "1020 ms", "kind": "loss", "source": 0, "target": 2, "loss": 1.0},
    {"at": "1505 ms", "kind": "loss", "source": 1, "target": 2, "loss": 0.3},
    {"at": "1515 ms", "kind": "link_down", "source": 0, "target": 2},
)


def _assert_rows_are_the_tables(tb):
    """``g_*[k, n]`` is the ``[G, G]`` word at ``[node_of[n],
    node_of[g_peers[n, k]]]``, element for element."""
    node_of, peers = np.asarray(tb.node_of), np.asarray(tb.g_peers)
    src, dst = node_of[None, :], node_of[peers.T]
    for rows, table, dtype in ((tb.g_lat, tb.lat, np.int32),
                               (tb.g_thresh_u32, tb.thresh_u32, np.uint32),
                               (tb.g_thresh_all, tb.thresh_all, np.bool_)):
        rows = np.asarray(rows)
        assert rows.shape == peers.T.shape and rows.dtype == dtype
        assert (rows == np.asarray(table)[src, dst]).all()


def test_the_per_peer_rows_are_the_tables_element_for_element():
    eng = TpuEngine(_rows_cfg(), log_capacity=0)
    tb = eng.tables
    assert tb.lat.shape == (3, 3) and tb.g_lat.shape == (4, 22)
    _assert_rows_are_the_tables(tb)
    # a pair that loses everything is among them, and pairs that differ
    lost = np.asarray(tb.g_thresh_all)
    assert 0 < lost.sum() < lost.size
    assert len(np.unique(np.asarray(tb.g_lat))) > 3
    # the seeded deployment graph: rows of every width equal their tables
    wan = TpuEngine(_wan_cfg(), log_capacity=0).tables
    assert wan.g_lat.shape == (WAN["degree"], WAN["nodes"])
    _assert_rows_are_the_tables(wan)
    assert not np.asarray(wan.g_thresh_all).any()


def test_on_one_graph_node_there_are_no_rows():
    """The ``[1, 1]`` lookup already folds to a scalar: no rows, no gauge,
    and the program is the parent's (``tests/test_turn_block.py`` pins its
    lowered text)."""
    eng = TpuEngine(_cfg(64, 4, 3), log_capacity=0)
    tb = eng.tables
    assert tb.g_peers.shape == (64, 4) and tb.lat.shape == (1, 1)
    assert tb.g_lat == () and tb.g_thresh_u32 == () and tb.g_thresh_all == ()
    assert lanes.path_sends(eng.params, tb) == (0, 0)
    eng.run(mode="device")
    assert (eng.lane_plane["static_path_sends"],
            eng.lane_plane["path_gather_sends"]) == (0, 0)


def test_a_fault_epoch_rebuilds_the_rows_from_its_own_tables():
    eng = TpuEngine(_rows_cfg(faults=FAULTS), log_capacity=0)
    plan = eng._fault_overlay.segment_plan(eng.params.stop_time)
    snaps = [snap for _start, _end, snap in plan if snap is not None]
    assert len(snaps) == len(FAULTS)
    def rows(tb):
        return b"".join(np.asarray(a).tobytes() for a in (
            tb.g_lat, tb.g_thresh_u32, tb.g_thresh_all))

    seen = {rows(eng.tables)}
    for snap in snaps:
        tb = eng._segment_tables(snap)
        assert (np.asarray(tb.lat) == np.asarray(snap.latency_ns)).all()
        _assert_rows_are_the_tables(tb)
        assert (np.asarray(tb.g_peers) == np.asarray(eng.tables.g_peers)).all()
        seen.add(rows(tb))
    assert len(seen) == len(FAULTS) + 1  # every epoch moved some row


def _path_gathers(eng, *args):
    """The ``gather`` operations the run program traces under the
    ``path_lookup/path_gather`` scope (locations of the lowered text)."""
    text = lanes.make_run_fn(eng.params, eng.tables).lower(
        eng.initial_state(), *args).as_text(debug_info=True)
    assert "path_lookup/" in text and "window_gather/" in text
    scoped = set(re.findall(
        r'^(#loc\d+) = loc\("[^"]*path_lookup/path_gather/gather"', text,
        re.M))
    ops = re.findall(r'"stablehlo\.gather".* loc\((#loc\d+)\)$', text, re.M)
    assert len(ops) > len(scoped)  # the window's own gather is seen
    return sum(loc in scoped for loc in ops)


def test_an_all_gossip_program_on_a_graph_traces_no_path_gather(monkeypatch):
    """Whether the ``[G, G]`` gather is compiled follows from the models
    present and the graph's size alone: none where every send's
    destination is a mesh peer; PHOLD's drawn destination on a lossy
    graph still gathers ``node_of[dst]`` and the two packed words."""
    seed = (np.uint32(7), np.uint32(0))
    gossip = TpuEngine(_lossy_cfg(), log_capacity=0)
    assert gossip.params.has_loss and gossip.tables.lat.shape == (2, 2)
    assert lanes.path_sends(gossip.params, gossip.tables) == (4, 0)
    assert _path_gathers(gossip, *seed) == 0
    gossip.run(mode="device")
    assert (gossip.lane_plane["static_path_sends"],
            gossip.lane_plane["path_gather_sends"]) == (4, 0)

    phold = TpuEngine(phold_tests._lossy_routed_graph("tpu"), log_capacity=0)
    assert phold.params.has_loss and phold.tables.lat.shape == (3, 3)
    assert phold.tables.g_lat == ()
    assert _path_gathers(phold, *seed) == 3  # of ONE slot body (scanned)
    phold.run(mode="device")
    assert (phold.lane_plane["static_path_sends"],
            phold.lane_plane["path_gather_sends"]) == (0, 1)

    # gossip beside a model whose send gathers: in the loop form, which
    # the chip takes, send 0 alone gathers (k is a Python integer there);
    # under XLA:CPU's rolled scan the one scanned body does
    mixed = TpuEngine(_rows_cfg(), log_capacity=0)
    assert lanes.path_sends(mixed.params, mixed.tables) == (4, 1)
    assert _path_gathers(mixed, *seed) == 3
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pops = mixed.params.pops_per_iter
    assert _path_gathers(mixed, *seed) == 3 * pops
    assert _path_gathers(gossip, *seed) == 0
    assert _path_gathers(phold, *seed) == 3 * pops


def _oracle_less_tgen_sent(cfg):
    oracle, last = _oracle_run(cfg)
    # the lane backend has never counted a tgen client's sent bytes
    oracle.counters.pop("tgen_sent_bytes", None)
    return oracle, last


@pytest.mark.parametrize("mode", ["device", "step", "loop_form"])
def test_gossip_beside_a_gathered_destination_equals_the_oracle(mode):
    """One program, both sources of a path: a tgen client's send 0 gathers
    its words, a gossip lane's sends read their rows, selected lane by
    lane as ``dst`` is.  ``loop_form`` is the fan-out as the chip and a
    sharded build take it (send 0 alone gathers), at degree 2 — XLA:CPU
    pays 35x more for every unrolled send."""
    degree = 2 if mode == "loop_form" else 4
    oracle, last = _oracle_less_tgen_sent(_rows_cfg("cpu", degree))
    eng = TpuEngine(_rows_cfg(degree=degree))
    if mode == "loop_form":
        with lanes._force_unroll():
            res = eng.run(mode="device")
    else:
        res = eng.run(mode=mode)
    _assert_equals_oracle(eng, res, oracle, last)
    c = res.counters
    assert c["lane_drop_loss"] > 10 and c["tgen_recv_bytes"] > 100_000
    assert (eng.lane_plane["static_path_sends"],
            eng.lane_plane["path_gather_sends"]) == (degree, 1)
    assert set(eng.params.models_present) == {
        lanes.M_TGEN_CLIENT, lanes.M_TGEN_SERVER, lanes.M_GOSSIP}


#: a regional split under the first flood, healed before the second: graph
#: nodes 0-2 of the 12 against the rest
PARTITION_HEAL = (
    {"at": "990 ms", "kind": "partition",
     "groups": [[0, 1, 2], list(range(3, 12))]},
    {"at": "1500 ms", "kind": "heal"},
)


def _chaos(graph_nodes=WAN["graph_nodes"], fault_seed=1, **more):
    """``slot_chaos`` cut to the small graph and the two bursts at 1 s and
    2 s: lossy, slow and down links under the first flood, ``link_up``
    inside it, a partition across the second flood and its heal."""
    graph = NetworkGraph.from_gml(routed_graph_gml(graph_nodes, 1, "1 Gbit"))
    return slot_chaos_events(
        graph, fault_seed, loss_edges=3, loss=0.3, latency_edges=3,
        latency="60 ms", down_edges=3, degrade_at="990 ms",
        down_at="1015 ms", up_at="1040 ms", partition_at="1990 ms",
        heal_at="2030 ms", **more)


def _faulted(schedule, backend):
    """The faulted configuration of a case, and whether every lane runs
    gossip (so that the flood's two conservation laws are the run's)."""
    if schedule == "rows":
        return _rows_cfg(backend, faults=FAULTS), _rows_cfg(backend), False
    faults = PARTITION_HEAL if schedule == "partition_heal" else _chaos()
    return (_wan_cfg(backend, stop_ms=2600, faults=faults),
            _wan_cfg(backend, stop_ms=2600), True)


def _assert_a_flood_is_conserved(counters, degree=WAN["degree"],
                                 messages=len(BURSTS) * WAN["messages"]):
    """Whatever the faults did, in a run that ends with nothing in flight:
    a publisher sends D copies and every first receipt forwards D - 1; and
    every copy sent was a first copy, a duplicate, or lost on its path."""
    c = counters
    assert c.get("lane_drop_queue", 0) == 0
    assert c["gossip_sends"] == (
        messages * degree + (degree - 1) * c["gossip_first"])
    assert c["gossip_sends"] == (
        c["gossip_first"] + c["gossip_duplicates"] + c["lane_drop_loss"])


@pytest.mark.faults
@pytest.mark.parametrize("schedule", ["rows", "partition_heal", "link_up"])
@pytest.mark.parametrize("budget", [None, 4], ids=["one_pass", "passes"])
@pytest.mark.parametrize("mode", ["device", "step"])
def test_a_faulted_gossip_run_equals_the_oracle(mode, budget, schedule,
                                                slot_budget):
    """Every epoch's rows are that epoch's tables: a send at or after the
    epoch takes the new latency and loss, an earlier one never does —
    under latency / loss / link_down epochs beside a gathered destination
    (``rows``), under a partition and its heal, and under degraded and
    down links restored by ``link_up`` (the routed graph, all gossip)."""
    cfg, calm_cfg, all_gossip = _faulted(schedule, "cpu")
    oracle, last = _oracle_less_tgen_sent(cfg)
    calm, _last = _oracle_less_tgen_sent(calm_cfg)
    assert oracle.log_tuples() != calm.log_tuples()  # the schedule bit
    check_gauges = slot_budget(budget)
    cfg.experimental.network_backend = "tpu"
    eng = TpuEngine(cfg)
    res = eng.run(mode=mode)
    _assert_equals_oracle(eng, res, oracle, last)
    check_gauges(eng, res)
    assert res.counters["lane_drop_loss"] > calm.counters["lane_drop_loss"]
    if all_gossip:
        for counters in (oracle.counters, res.counters):
            _assert_a_flood_is_conserved(counters)
        # a partition across a whole flood keeps first copies away for
        # good; a short one the other peers' later copies make up for
        kept_away = (calm.counters["gossip_first"]
                     - res.counters["gossip_first"])
        assert kept_away > 0 if schedule == "partition_heal" else (
            kept_away >= 0)
    epochs = len({parse_event(e).at for e in cfg.faults.events})
    assert (eng.lane_plane["fault_epochs"],
            eng.lane_plane["fault_segments"],
            eng.lane_plane["fault_programs"]) == (epochs, epochs + 1, 1)


# -- (j) failure as part of the workload ----------------------------------------

FAULT_GAUGES = ("fault_epochs", "fault_segments", "fault_programs",
                "fault_table_bytes")


@pytest.mark.faults
@pytest.mark.parametrize("mode", ["device", "step"])
def test_a_faulted_run_is_one_program_for_every_segment_and_repeat(mode):
    """Five epochs, six segments, three repeats: ONE trace, the leaves
    placed once, and ``precompile`` compiles that program ahead."""
    eng = TpuEngine(_wan_cfg(stop_ms=2600, faults=_chaos()), log_capacity=0)
    first = eng.run(mode=mode, precompile=True)
    fn, placed = eng._fault_fns[mode], eng._fault_leaves[1]
    kept = {at: dict(leaves) for at, leaves in placed.items()}
    assert (eng._fault_compiled is not None) == (mode == "device")
    for _ in range(2):
        again = eng.run(mode=mode)
        assert again.counters == first.counters
        assert again.rounds == first.rounds
    assert fn.traces == 1 and eng._fault_fns == {mode: fn}
    assert eng._fault_leaves[1] is placed and len(placed) == 6
    for at, leaves in placed.items():  # the very arrays of the first run
        assert all(leaves[f] is kept[at][f] for f in leaves)
    plane = eng.lane_plane
    one_set = sum(int(getattr(eng.tables, f).nbytes)
                  for f in eng._path_fields)
    assert [plane[k] for k in FAULT_GAUGES] == [5, 6, 1, 6 * one_set]
    assert set(eng._path_fields) == {
        "lat", "thresh_u32", "thresh_all", "flow_lat", "flow_thresh_u32",
        "flow_thresh_all", "g_lat", "g_thresh_u32", "g_thresh_all"}
    assert plane["state_reused"] == 1
    assert eng.clock.phase_s["fault_swap"] > 0
    oracle, last = _oracle_run(_wan_cfg("cpu", stop_ms=2600,
                                        faults=_chaos()))
    assert _shared(again.counters) == _shared(oracle.counters)
    assert again.rounds == oracle.rounds
    # a calm engine has no such phase and no such gauge
    calm = TpuEngine(_wan_cfg(), log_capacity=0)
    calm.run(mode=mode)
    assert "fault_swap" not in calm.clock.phase_s
    assert not set(FAULT_GAUGES) & set(calm.lane_plane)


@pytest.mark.faults
def test_a_fault_added_to_the_overlay_drops_the_placed_leaves():
    """The console's ``add_event`` recompiles the snapshots: the engine
    places the new epochs' leaves, keeps its ONE program, and equals the
    oracle under the longer schedule."""
    eng = TpuEngine(_wan_cfg(stop_ms=2600, faults=PARTITION_HEAL))
    before = eng.run(mode="device")
    placed = eng._fault_leaves[1]
    late = {"at": "1995 ms", "kind": "partition",
            "groups": [[0, 1, 2, 3, 4, 5], [6, 7, 8, 9, 10, 11]]}
    eng._fault_overlay.add_event(parse_event(late))
    after = eng.run(mode="device")
    assert eng._fault_leaves[1] is not placed
    assert set(eng._fault_leaves[1]) == {
        None, 990 * MS, 1500 * MS, 1995 * MS}
    assert eng._fault_fns["device"].traces == 1
    assert eng.lane_plane["fault_epochs"] == 3
    assert after.counters["gossip_first"] < before.counters["gossip_first"]
    oracle, last = _oracle_run(_wan_cfg(
        "cpu", stop_ms=2600, faults=PARTITION_HEAL + (late,)))
    _assert_equals_oracle(eng, after, oracle, last)


def test_precompile_is_still_refused_with_a_resume():
    eng = TpuEngine(_wan_cfg(stop_ms=1100, faults=PARTITION_HEAL))
    with pytest.raises(LaneCompatError, match="checkpoint resume"):
        eng.run(mode="device", precompile=True,
                resume_state=eng.initial_state())


def _edges(events, kinds):
    return [(e["source"], e["target"]) for e in events if e["kind"] in kinds]


def test_the_factorys_schedule_is_a_function_of_the_fault_seed_alone():
    base = _wan_cfg(graph_nodes=40, faults="slot_chaos")
    events = base.faults.events
    assert [e["kind"] for e in events] == (
        ["loss"] * 15 + ["latency"] * 15 + ["link_down"] * 10
        + ["link_up"] * 40 + ["partition", "heal"])
    assert sorted({parse_event(e).at // MS for e in events}) == [
        900, 1060, 2000, 4900, 7000]
    # 40 distinct edges of the graph, none a self-edge, all restored
    graph = NetworkGraph.from_gml(routed_graph_gml(40, 1, "1 Gbit"))
    real = {(e.source, e.target) for e in graph.edges}
    hit = _edges(events, ("loss", "latency", "link_down"))
    assert len(set(hit)) == 40 and set(hit) <= real
    assert all(a != b for a, b in hit)
    assert sorted(_edges(events, ("link_up",))) == sorted(hit)
    groups = events[-2]["groups"]
    assert groups == [list(range(10)), list(range(10, 40))]
    # the run's seed, the mesh's seed, the width and the traffic move none
    other = gossip_mesh_config(128, 8, 5, ("3 s",), 2, 256,
                               bandwidth="1 Gbit", seed=99, graph_nodes=40,
                               graph_seed=1, faults="slot_chaos")
    assert other.faults.events == events
    assert _wan_cfg(graph_nodes=40, faults="slot_chaos",
                    fault_seed=2).faults.events != events
    # a list of event documents is taken as it is
    given = _wan_cfg(faults=PARTITION_HEAL)
    assert given.faults.events == list(PARTITION_HEAL)
    with pytest.raises(ValueError, match="draws 40 edges"):
        _wan_cfg(faults="slot_chaos")  # 12 graph nodes have fewer


def test_without_faults_the_factory_builds_what_it_built():
    calm, chaos = _wan_cfg(), _wan_cfg(faults=PARTITION_HEAL)
    assert calm.faults.events == [] and not calm.faults.failover_enabled
    for cfg in (calm, chaos):
        assert cfg.hosts == _wan_cfg(faults=None).hosts
        assert cfg.network.graph.inline == routed_graph_gml(12, 1, "1 Gbit")
    assert calm.experimental == _wan_cfg(faults=None, fault_seed=9).experimental
    # the shape law reads the longest routed path of ANY epoch: a slow
    # link that no route avoids stretches the span two bursts share
    slow = [{"at": "900 ms", "kind": "latency", "source": a, "target": b,
             "latency": "400 ms"}
            for a, b in sorted({(e.source, e.target) for e in
                                NetworkGraph.from_gml(
                                    routed_graph_gml(12, 1)).edges})
            if a != b]
    wide, calm = _wan_cfg(messages=8, faults=slow), _wan_cfg(messages=8)
    assert calm.experimental.tpu_lane_queue_capacity == 52
    assert wide.experimental.tpu_lane_queue_capacity == 116


# -- (i) the exchange sorts the slots that sent ---------------------------------


@pytest.mark.parametrize("lanes_n, pops, fan, want", [
    (10_000, 2, 8, 2_560),  # both gossip cells: 20 480 rows a pass
    (10_000, 4, 8, 5_120), (100_000, 2, 8, 25_088),
    (64, 2, 4, 128),  # a tiny program: never more slots than there are
])
def test_the_slot_budget_is_a_law_of_the_shape(lanes_n, pops, fan, want):
    p = lanes.LaneParams(
        n_lanes=lanes_n, capacity=16, pops_per_iter=pops, log_capacity=0,
        seed=1, stop_time=MS, bootstrap_end=0, runahead=MS,
        models_present=(lanes.M_GOSSIP,), gossip_degree=fan)
    assert p.exchange_slot_budget == want
    assert want % lanes._SLOT_TILE == 0 and want <= pops * lanes_n
    # as wide as a one-send program's exchange, to the tile
    assert p.exchange_entries == want * fan
    assert want == pops * lanes_n or (
        0 <= want * fan - pops * lanes_n < fan * lanes._SLOT_TILE)


@pytest.mark.parametrize("nodes, degree, messages", SMALL)
def test_a_flood_of_one_pass_and_many_pass_iterations_equals_the_oracle(
        nodes, degree, messages, slot_budget):
    """The budget under the flood's front: the same run holds iterations
    of one pass and iterations of several, and is the oracle's — log,
    counters, rounds, the peaks under the law's bounds."""
    check_gauges = slot_budget(nodes // 8)
    eng = TpuEngine(_cfg(nodes, degree, messages))
    res = eng.run(mode="device")
    _assert_equals_oracle(eng, res, *_oracle(nodes, degree, messages))
    assert _less_ages(res.counters) == _counts(nodes, degree, messages)
    check_gauges(eng, res)
    plane = eng.lane_plane
    assert plane["exchange_slot_peak"] > 2 * plane["exchange_slot_budget"]
    queue, cross = gossip_shape_law(degree, messages)
    assert plane["queue_peak"] <= queue and plane["cross_peak"] <= cross


@pytest.mark.parametrize("nodes, degree, messages", SMALL)
def test_under_the_laws_own_budget_every_tiny_iteration_is_one_pass(
        nodes, degree, messages):
    res, plane = _lane_run(nodes, degree, messages)
    assert plane["exchange_compact_iters"] == res.counters["lane_iters"]
    assert plane["exchange_slot_budget"] == min(GOSSIP_POPS * nodes, 128)
    assert 0 < plane["exchange_slot_peak"] <= plane["exchange_slot_budget"]
    assert not {"exchange_compact_iters", "exchange_slot_peak"} & set(
        res.counters)


_XK, _XF, _XN, _XCX, _XBUDGET = 2, 4, 24, 32, 12


def _send_channel(sending_slots, pay, seed=5):
    """A ``[K, F, N]`` send channel in which exactly the first
    ``sending_slots`` of a seeded order of the (pop, lane) slots send, one
    to F datagrams each, to seeded destinations; every word distinct, and
    of the payload words only those in ``pay`` emitted."""
    rs = np.random.RandomState(seed)
    shape = (_XK, _XF, _XN)
    sends = rs.rand(*shape) < 0.6
    sends[:, 0, :] |= ~sends.any(axis=1)  # a sending slot sends something
    order = rs.permutation(_XK * _XN)[:sending_slots]
    slot = np.zeros(_XK * _XN, dtype=bool)
    slot[order] = True
    valid = sends & slot.reshape(_XK, 1, _XN)

    def word(shape=shape):
        return np.broadcast_to(
            rs.randint(0, 1 << 20, shape), (_XK, _XF, _XN)).astype(np.int32)

    def pop_word():  # one word a slot, not F
        return word((_XK, 1, _XN))

    empty = lanes._SlotEmit(*[()] * len(lanes._SlotEmit._fields))
    return empty._replace(
        out_valid=valid, out_dst=rs.randint(0, _XN, shape).astype(np.int32),
        out_thi=word(), out_tlo=word(), out_auxh=pop_word(),
        out_auxl=np.arange(valid.size, dtype=np.int32).reshape(shape),
        out_size=pop_word(), **{"out_" + w: pop_word() for w in pay})


def _lane_rows(cnt, words):
    """Per lane, the sorted rows of its cross block's live columns."""
    cnt = np.asarray(cnt)
    block = np.stack([np.asarray(w) for w in words], axis=-1)  # [N, Cx, W]
    return [sorted(map(tuple, block[d, :cnt[d]])) for d in range(len(cnt))]


@pytest.mark.parametrize("payload_words", [1, 2])
@pytest.mark.parametrize("sending_slots, passes", [
    (0, 1), (_XBUDGET, 1), (_XBUDGET + 1, 2), (_XK * _XN, 4)],
    ids=["none", "the_budget", "one_more", "every_slot"])
def test_the_passes_hand_every_lane_what_one_full_exchange_would(
        sending_slots, passes, payload_words):
    """The compacted passes against ONE exchange of all K x F x N rows
    (the one-send law over the flattened channel): every lane's count, and
    its rows as a multiset (the row merge's key orders them) — whichever
    payload words the rows carry (ISSUE 46: gossip's one, ``plo``)."""
    pay = lanes.pay_words(payload_words)
    e = _send_channel(sending_slots, pay)
    assert isinstance(e.out_phi, tuple) == (payload_words == 1)
    flat = [np.where(e.out_valid, e.out_dst, _XN)] + [
        getattr(e, "out_" + w) for w in lanes.ROW_WORDS + pay]
    ops, start, full_cnt = lanes._sorted_exchange(
        [jax.numpy.asarray(w.reshape(-1)) for w in flat], _XN)
    full = _lane_rows(
        full_cnt, lanes._cross_block(ops, start, full_cnt, _XCX)[1])
    assert int(full_cnt.sum()) == int(e.out_valid.sum())
    assert int(full_cnt.max()) <= _XCX  # nothing shed: lost_pre is 0 both

    n_sending, ranked, table = lanes._rank_sending(e, _XBUDGET, _XN, pay)
    assert int(n_sending) == sending_slots
    assert max(-(-sending_slots // _XBUDGET), 1) == passes
    assert ranked.shape == (4 * _XBUDGET,) and table.shape == (
        _XK * _XN, 4 * _XF + 2 + payload_words)
    cnt_all = np.zeros(_XN, dtype=np.int64)
    rows = [[] for _ in range(_XN)]
    for i in range(passes):
        cols = lanes._compact_sends(ranked, table, i, _XBUDGET, _XN, _XF)
        assert len(cols) == 6 + payload_words
        assert all(c.shape == (_XBUDGET * _XF,) for c in cols)
        ops, start, cnt = lanes._sorted_exchange(cols, _XN)
        got = _lane_rows(cnt, lanes._cross_block(ops, start, cnt, _XCX)[1])
        cnt_all += np.asarray(cnt)
        rows = [a + b for a, b in zip(rows, got)]
    assert cnt_all.tolist() == np.asarray(full_cnt).tolist()
    assert [sorted(r) for r in rows] == full
    # a pass past the last sending slot holds nothing
    beyond = lanes._compact_sends(ranked, table, 3, _XBUDGET, _XN, _XF)
    if sending_slots <= 3 * _XBUDGET:
        assert (np.asarray(beyond[0]) == _XN).all()



# -- (k) a row carries the payload words its models use --------------------------


def _gossip_engines():
    return {"one_switch": lambda **kw: TpuEngine(_cfg(64, 4, 3), **kw),
            "wide": lambda **kw: TpuEngine(_cfg(128, 8, 4), **kw),
            "routed_lossy": lambda **kw: TpuEngine(_wan_cfg(), **kw),
            "beside_tgen": lambda **kw: TpuEngine(_rows_cfg(), **kw)}


@pytest.mark.parametrize("name", sorted(_gossip_engines()))
def test_a_gossip_row_carries_the_message_id_and_no_other_word(name):
    eng = _gossip_engines()[name](log_capacity=0)
    p, state = eng.params, eng.initial_state()
    assert p.payload_words == 1 and p.pay_words == ("plo",)
    assert p.row_words == ("thi", "tlo", "auxh", "auxl", "size", "plo")
    assert state.q_phi == ()
    assert state.q_plo.shape == (p.n_lanes, p.capacity)
    assert state.q_plo.dtype == np.int32
    eng.run(mode="device")
    assert eng.lane_plane["payload_words"] == 1


@pytest.mark.parametrize("name", sorted(TINY))
def test_every_other_program_carries_the_words_it_carried(name):
    """Stream events on the [N] queues keep both words (an un-tiered star);
    the tiered stream program's [N] rows, PHOLD's and the passive mesh's
    carry none."""
    eng = TpuEngine(TINY[name](), log_capacity=0)
    p, state = eng.params, eng.initial_state()
    assert p.payload_words == TINY_WORDS[name]
    assert p.stream_tiered == (name == "one_to_one_streams")
    for word in lanes.PAY_WORDS:
        col = getattr(state, "q_" + word)
        if p.payload_words:
            assert col.shape == (p.n_lanes, p.capacity)
        else:
            assert col == ()


def test_the_word_count_is_a_static_property_of_the_models_present():
    base = dict(n_lanes=8, capacity=16, pops_per_iter=2, log_capacity=0,
                seed=1, stop_time=MS, bootstrap_end=0, runahead=MS)
    for model in set(range(lanes.M_GOSSIP)) - lanes.STREAM_MODELS:
        assert lanes.LaneParams(
            **base, models_present=(model,)).payload_words == 0
    for model in lanes.STREAM_MODELS:
        p = lanes.LaneParams(**base, models_present=(model,))
        assert p.payload_words == 2 and p.pay_words == lanes.PAY_WORDS
        # the tier takes the stream events, and their words, off the [N] rows
        assert lanes.LaneParams(**base, models_present=(model,),
                                stream_tiered=True).payload_words == 0
    assert [lanes.pay_words(k) for k in range(3)] == [
        (), ("plo",), ("phi", "plo")]
    assert not hasattr(lanes.LaneParams, "lanes_have_payload")


def _stablehlo_sorts(text):
    """Operand counts of the ``stablehlo.sort`` operations of a lowered
    text, in order."""
    return [m.group(1).count("%") for m in re.finditer(
        r'"stablehlo\.sort"\(([^)]*)\)', text)]


@pytest.mark.parametrize("make", [lanes.make_run_fn, lanes.make_round_fn],
                         ids=["fused", "step"])
@pytest.mark.parametrize("name", ["one_switch", "routed_lossy"])
def test_no_sort_gather_or_carry_of_a_gossip_program_holds_a_seventh_word(
        name, make):
    """The lowered text of the run function: the row sort takes six
    operands, the exchange sort seven (the destination and a row's six
    words), the rank sort one, and the packed carry is ``[6, N, C]``."""
    eng = _gossip_engines()[name](log_capacity=0)
    p = eng.params
    text = make(p, eng.tables).lower(eng.initial_state()).as_text()
    sorts = _stablehlo_sorts(text)
    # the rank sort, a pass's exchange and row sorts and, in the step
    # driver, the row re-sort of an iteration that sent nothing
    assert sorted(sorts) == sorted(
        [1, 6, 7] + [6] * (make is lanes.make_round_fn))
    # (the fused loop's carry is the packed state; the step driver's round
    # takes the state leaf by leaf)
    assert (f"tensor<6x{p.n_lanes}x{p.capacity}xi32>" in text) == (
        make is lanes.make_run_fn)
    assert f"tensor<7x{p.n_lanes}x" not in text


def _distinct_words(eng):
    """An initial state whose every queue column holds its own numbers."""
    state = eng.initial_state()
    n, c = eng.params.n_lanes, eng.params.capacity
    cols = {"q_" + w: np.arange(n * c, dtype=np.int32).reshape(n, c) + 7 * i
            for i, w in enumerate(eng.params.row_words)}
    return state._replace(**cols)


def _word_engines():
    return {"gossip": lambda: _gossip_engines()["one_switch"](),
            "star_streams": lambda: TpuEngine(_star_streams()),
            "phold": lambda: TpuEngine(TINY["phold"]())}


@pytest.mark.parametrize("name, words", [
    ("phold", 0), ("gossip", 1), ("star_streams", 2)])
def test_the_packed_carry_holds_the_words_present_and_unpacks_to_them(
        name, words):
    eng = _word_engines()[name]()
    state = _distinct_words(eng)
    carry = lanes.pack_state(state)
    n, c = eng.params.n_lanes, eng.params.capacity
    assert carry[0].shape == (5 + words, n, c)
    back = lanes.unpack_state(carry)
    assert jax.tree.structure(back) == jax.tree.structure(state)
    assert isinstance(back.q_phi, tuple) == (words < 2)
    assert isinstance(back.q_plo, tuple) == (words < 1)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_checkpoint_of_a_gossip_state_resumes_to_the_same_run(tmp_path):
    """A lane state of one payload word through the checkpoint container
    and back into ``run(resume_state=...)``, taken in the middle of the
    first flood (message ids in flight in ``q_plo``)."""
    from shadow_tpu.engine.checkpoint import read_checkpoint, write_checkpoint

    whole = TpuEngine(_cfg(64, 4, 3)).run(mode="step")
    eng, kept = TpuEngine(_cfg(64, 4, 3)), []

    def keep(_start, end, _next):
        if not kept and end >= 1025 * MS:
            kept.append(eng.checkpoint_payload())

    eng.run(mode="step", on_window=keep)
    path = write_checkpoint(tmp_path / "gossip.ckpt", {"kind": "test"},
                            {"lane_state": kept[0]})
    state = read_checkpoint(path)[1]["lane_state"]
    assert state.q_phi == () and state.q_plo.shape == state.q_thi.shape
    live = state.q_thi != lanes.NEVER32
    assert live.any() and state.q_plo[live].max() > 0
    res = TpuEngine(_cfg(64, 4, 3)).run(
        mode="step", resume_state=state, resume_epoch=0)
    assert res.log_tuples() == whole.log_tuples()
    assert (res.counters, res.rounds) == (whole.counters, whole.rounds)


@pytest.mark.parametrize("devices", [2, 4])
def test_the_sharded_placement_holds_a_state_of_one_payload_word(devices):
    """``parallel/mesh.py`` places the leaves that exist: ``q_plo`` split
    on the lane axis with the key words, no leaf for ``q_phi``."""
    eng = _gossip_engines()["one_switch"](log_capacity=0)
    mesh = parallel.make_mesh(devices)
    state = _distinct_words(eng)
    placed = parallel.mesh.shard_state(state, mesh)
    assert placed.q_phi == ()
    for word in eng.params.row_words:
        col = getattr(placed, "q_" + word)
        assert len(col.devices()) == devices
        assert col.sharding.spec == jax.sharding.PartitionSpec(
            parallel.HOST_AXIS)
        assert np.array_equal(np.asarray(col), getattr(state, "q_" + word))
    assert jax.tree.structure(placed) == jax.tree.structure(state)
    # the sharded run function lowers over the same six-word rows
    text = parallel.make_sharded_run_fn(
        eng.params, eng.tables, mesh).lower(placed).as_text()
    assert set(_stablehlo_sorts(text)) == {1, 6, 7}


# -- (l) a known copy is counted at its PACKET pop (ISSUE 47) -------------------


def _slow_hosts_cfg(backend="tpu"):
    """64 x 4 on one switch with 500 Kbit hosts: a 512-byte datagram holds
    the down bucket for 8.8 of the window's 10 ms, so the copies behind it
    are delivered in LATER windows than their PACKET pops."""
    cfg = gossip_mesh_config(64, 4, 1, BURSTS, 3, 512, "10 ms", "500 Kbit",
                             seed=7)
    cfg.general.stop_time = 2900 * MS
    cfg.experimental.network_backend = backend
    return cfg


#: every kind of flood the parity tests above run, by name
FLOODS = {
    **{f"one_switch_{n}x{d}": functools.partial(_cfg, n, d, m)
       for n, d, m in SMALL},
    "lossy_two_nodes": lambda backend: _lossy_cfg(backend),
    "routed_lossy": lambda backend: _wan_cfg(backend),
    "partition_heal": lambda backend: _faulted("partition_heal", backend)[0],
    "slow_hosts": _slow_hosts_cfg,
}


@functools.lru_cache(maxsize=None)
def _oracle_knowing(flood):
    """The UNEDITED oracle's run of a flood with a count taken around its
    ``inbound`` (ISSUE 47's sizing): of the delivered datagrams, those
    whose message the destination had ALREADY seen when the PACKET popped
    (``known``), and of them those delivered before the window's end
    (``in_window``): what a lane that pops in heap order would elide."""
    count = {"known": 0, "in_window": 0}
    inbound = CpuEngine.inbound

    def counting(self, dst_host, ev):
        known = ev.data[1] in dst_host.apps[0].seen
        inbound(self, dst_host, ev)
        record = dst_host.log_buf[-1]
        if known and record.outcome == lanes.DELIVERED:
            count["known"] += 1
            count["in_window"] += record.time < self.window_end

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(CpuEngine, "inbound", counting)
        oracle, last = _oracle_run(FLOODS[flood]("cpu"))
    return oracle, last, count["known"], count["in_window"]


def _flood_run(flood, mode="device", pops=None):
    cfg = FLOODS[flood]("tpu")
    if pops is not None:
        cfg.experimental.tpu_events_per_round = pops
    eng = TpuEngine(cfg)
    return eng, eng.run(mode=mode)


@pytest.mark.faults
@pytest.mark.parametrize("mode", ["device", "step"])
@pytest.mark.parametrize("flood", sorted(FLOODS))
def test_a_known_copy_queues_no_delivery_and_the_flood_takes_fewer_iterations(
        flood, mode, monkeypatch):
    """The oracle keeps the event; the lane counts the duplicate at the
    PACKET pop and the run is the oracle's all the same — log, counters,
    rounds — in fewer iterations than with the elision patched off."""
    oracle, last, _known, in_window = _oracle_knowing(flood)
    eng, res = _flood_run(flood, mode)
    _assert_equals_oracle(eng, res, oracle, last)
    elided = eng.lane_plane["gossip_elided"]
    # a stale view (a co-popped [P, P'] of one message) misses an elision,
    # nothing invents one: at most what heap order would have elided
    assert 0 < elided <= in_window <= res.counters["gossip_duplicates"]
    # a gauge of the lane plane, which the comparison does not hold the
    # oracle to
    assert "gossip_elided" not in res.counters
    assert "gossip_elided" not in oracle.counters
    # today's path for every copy: the same run, more iterations
    monkeypatch.setattr(lanes, "gossip_elides",
                        lambda known, *window: known & False)
    eng_off, res_off = _flood_run(flood, mode)
    _assert_equals_oracle(eng_off, res_off, oracle, last)
    assert eng_off.lane_plane["gossip_elided"] == 0
    assert res.counters["lane_iters"] < res_off.counters["lane_iters"]
    assert eng.lane_plane["queue_peak"] <= eng_off.lane_plane["queue_peak"]


@pytest.mark.parametrize("mode", ["device", "step"])
def test_a_copy_delivered_past_the_windows_end_takes_todays_path(
        mode, monkeypatch):
    """The window gate: on slow hosts many known copies leave the down
    bucket after the window's end; they are queued as before (fewer rows
    elided than duplicates, and than known copies), and counters, log AND
    rounds are the oracle's.  Without the gate the log and the counters
    still are — the record is written at the PACKET pop either way — but a
    window that held nothing but such a delivery is never opened: the
    rounds come out short."""
    oracle, last, known, in_window = _oracle_knowing("slow_hosts")
    assert in_window < known <= oracle.counters["gossip_duplicates"]
    eng, res = _flood_run("slow_hosts", mode)
    _assert_equals_oracle(eng, res, oracle, last)
    assert 0 < eng.lane_plane["gossip_elided"] <= in_window
    monkeypatch.setattr(lanes, "gossip_elides", lambda known, *window: known)
    eng, res = _flood_run("slow_hosts", mode)
    assert eng.lane_plane["gossip_elided"] > in_window
    assert res.log_tuples() == oracle.log_tuples()
    assert _shared(res.counters) == _shared(oracle.counters)
    assert res.rounds < oracle.rounds


@pytest.mark.parametrize("flood", ["routed_lossy", "lossy_two_nodes"])
def test_a_stale_view_of_the_bitmap_misses_an_elision_and_invents_none(flood):
    """At one pop an iteration a lane pops in the oracle's heap order and
    elides exactly what the counted oracle says.  At two, a lane fed
    ``[P_a, P_b]`` — two copies of one message at two instants of one
    window — co-pops them before ``P_a``'s DELIVERY exists (the reordering
    the window-inert rule allows), so ``P_b`` finds the bit unset: the
    second copy takes today's path, fewer rows are elided, and the run is
    the oracle's both times."""
    oracle, last, _known, in_window = _oracle_knowing(flood)
    elided = {}
    for pops in (1, 2):
        eng, res = _flood_run(flood, pops=pops)
        assert eng.params.pops_per_iter == pops
        _assert_equals_oracle(eng, res, oracle, last)
        elided[pops] = eng.lane_plane["gossip_elided"]
    assert 0 < elided[2] < elided[1] == in_window


@pytest.mark.parametrize("name", sorted(TINY))
def test_a_program_without_gossip_lanes_carries_no_elision_word(name):
    eng = TpuEngine(TINY[name](), log_capacity=0)
    state = eng.initial_state()
    assert state.gossip_elided == ()
    # (the carry's last two leaves are the loop ledger's since PR 50)
    assert lanes.pack_state(state)[-3] == ()
    assert lanes.unpack_state(lanes.pack_state(state)).gossip_elided == ()
    if name == "phold":
        eng.run(mode="device")
        assert "gossip_elided" not in eng.lane_plane
    gossip = TpuEngine(_cfg(64, 4, 3), log_capacity=0).initial_state()
    assert gossip.gossip_elided.shape == () and (
        gossip.gossip_elided.dtype == np.int32)
