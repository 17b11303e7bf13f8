"""``config/scenarios.py routed_tcp_mesh_config`` (ISSUE 32): the routed,
lossy all-TCP deployment as a factory — one network per ``graph_seed``
whatever the run's seed, bit-identical to the oracle at a rehearsal size on
both drivers and through ``Simulation``, and described by ``lane_plane``."""

import json

import numpy as np
import pytest

from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.scenarios import (
    ROUTED_EDGE_LOSS, routed_graph_gml, routed_tcp_mesh_config)
from shadow_tpu.net.gml import parse_gml
from shadow_tpu.net.ltcp import RTO_MIN
from test_routed_tcp import assert_same_run


def rehearsal(graph_seed, backend, seed=1, **kw):
    cfg = routed_tcp_mesh_config(
        48, 12, graph_seed=graph_seed, stream_bytes=200_000, seed=seed, **kw)
    cfg.general.stop_time = 3 * 10**9
    cfg.experimental.network_backend = backend
    cfg.experimental.tpu_lane_queue_capacity = 128
    return cfg


def test_the_graph_is_the_issues_shape():
    g = parse_gml(routed_graph_gml(200, 1))
    assert [n["id"] for n in g["nodes"]] == list(range(200))
    assert {n["host_bandwidth_up"] for n in g["nodes"]} == {"1 Gbit"}
    selfs = [e for e in g["edges"] if e["source"] == e["target"]]
    links = [e for e in g["edges"] if e["source"] != e["target"]]
    assert len(selfs) == 200 and {e["latency"] for e in selfs} == {"2 ms"}
    # ring plus two seeded chords per node, undirected, no duplicates
    pairs = {(e["source"], e["target"]) for e in links}
    assert len(pairs) == len(links) and 500 <= len(links) <= 600
    assert all(((n, (n + 1) % 200) in pairs) or (((n + 1) % 200, n) in pairs)
               for n in range(200))
    lats = [int(e["latency"].split()[0]) for e in links]
    assert min(lats) >= 2 and max(lats) < 40
    assert np.median(lats) < 15  # log-uniform: half the edges under ~9 ms
    losses = [e.get("packet_loss", 0.0) for e in links]
    assert set(losses) == set(ROUTED_EDGE_LOSS[0])
    assert 0.4 < losses.count(0.0) / len(losses) < 0.6


def _network(cfg):
    return (cfg.network.graph.inline,
            [(h.hostname, h.network_node_id,
              [(p.path, tuple(p.args), p.start_time) for p in h.processes])
             for h in cfg.hosts])


def test_the_network_does_not_move_with_the_runs_seed():
    one = routed_tcp_mesh_config(100, 20, graph_seed=1, seed=1)
    other = routed_tcp_mesh_config(100, 20, graph_seed=1, seed=987_654_321)
    assert _network(one) == _network(other)
    assert (one.general.seed, other.general.seed) == (1, 987_654_321)
    assert _network(one) != _network(
        routed_tcp_mesh_config(100, 20, graph_seed=2, seed=1))
    # the graph alone does not move with the host count either
    assert one.network.graph.inline == routed_tcp_mesh_config(
        40, 20, graph_seed=1).network.graph.inline
    starts = [h.processes[0].start_time for h in one.hosts
              if h.processes[0].path == "stream-client"]
    assert len(starts) == 50 and len(set(starts)) > 40
    assert all(0 <= t < 10**9 and t % 10**6 == 0 for t in starts)
    with pytest.raises(ValueError):
        routed_tcp_mesh_config(7, 4)


# graph seed 4 is a network of probe (b)'s kind: before the repair the lane
# twin ran 349 rounds to the oracle's 348 on it
@pytest.mark.parametrize("graph_seed", [1, 4])
def test_the_factorys_network_is_bit_identical_on_both_drivers(graph_seed):
    cpu = CpuEngine(rehearsal(graph_seed, "cpu")).run()
    assert cpu.counters["stream_complete"] == 24
    assert cpu.counters["stream_retransmits"] > 0
    for mode in ("device", "step"):
        eng = TpuEngine(rehearsal(graph_seed, "tpu"))
        assert_same_run(cpu, eng.run(mode=mode))
    # the shape of the network and what it cost, as the program saw them
    plane = eng.lane_plane
    assert plane["graph_nodes"] == 12 and plane["window_ns"] == 2_000_000
    assert plane["has_loss"] == 1 and plane["stream_wide_pop"] == 1
    assert 2_000_000 < plane["max_path_latency_ns"] < RTO_MIN
    assert plane["stream_retransmits"] == cpu.counters["stream_retransmits"]
    assert plane["lane_drop_loss"] == sum(
        1 for r in cpu.event_log if r.outcome == 1) > 0


def test_the_factorys_network_through_simulation(tmp_path):
    from shadow_tpu.engine.sim import Simulation

    results = {}
    for backend in ("cpu", "tpu"):
        cfg = rehearsal(4, backend, seed=77)
        cfg.general.data_directory = str(tmp_path / backend)
        sim = Simulation(cfg)
        results[backend] = sim.run()
    assert_same_run(results["cpu"], results["tpu"])
    stats = json.loads((sim.data_dir / "sim-stats.json").read_text())
    plane = stats["lane_plane"]
    assert (plane["graph_nodes"], plane["window_ns"], plane["has_loss"]) == (
        12, 2_000_000, 1)
    assert plane["stream_retransmits"] == stats["counters"][
        "stream_retransmits"]
    assert plane["lane_drop_loss"] == stats["packet_outcomes"]["loss"]


def test_a_long_path_turns_the_wide_co_pop_off():
    # a path of RTO_MIN or more: a window may then hold an RTO armed
    # inside it, so stream lanes co-pop same-instant packets only and
    # every delivery is queued
    cfg = rehearsal(1, "tpu")
    assert TpuEngine(cfg).params.stream_wide_pop is True
    cfg.network.graph.inline = cfg.network.graph.inline.replace(
        'latency "2 ms"', 'latency "200 ms"', 1)  # node 0's self-edge
    eng = TpuEngine(cfg)
    assert eng.params.stream_wide_pop is False
    assert eng._max_path_latency_ns >= RTO_MIN


@pytest.mark.parametrize("log_capacity", [0, 4096])
def test_one_run_program_serves_every_seed(log_capacity):
    # where the network loses packets the seed's two words are ARGUMENTS of
    # the fused run program, not constants in it: its text — what the
    # persistent compile cache keys on — does not move with the seed
    from shadow_tpu.backend import lanes
    from shadow_tpu.core import rng

    texts = set()
    for seed in (1, 2**31 - 2):
        eng = TpuEngine(rehearsal(1, "tpu", seed=seed),
                        log_capacity=log_capacity)
        assert eng.params.has_loss and eng._seed_args == ()
        fn = lanes.make_run_fn(eng.params, eng.tables)
        words = tuple(np.uint32(w) for w in rng._split_seed(seed))
        texts.add(fn.lower(eng.initial_state(), *words).as_text())
    assert len(texts) == 1
    # called with the state alone, the same function compiles the seed in
    # (the epoch segments of a faulted run do): that text holds the seed
    assert fn.lower(eng.initial_state()).as_text() not in texts


def test_the_seed_as_an_argument_draws_what_the_constant_drew():
    from shadow_tpu.backend import lanes

    cfg = rehearsal(4, "tpu", seed=2**31 - 2)
    cfg.general.stop_time = 10**9
    eng = TpuEngine(cfg)
    first = eng.run(mode="device")  # the seed handed over
    assert [int(w) for w in eng._seed_args] == [2**31 - 2, 0]
    assert first.counters["lane_drop_loss"] > 0
    state = lanes.make_run_fn(eng.params, eng.tables)(eng.initial_state())
    baked = eng.collect(state, 0.0)  # the seed compiled in
    assert baked.log_tuples() == first.log_tuples()
    assert baked.counters == first.counters and baked.rounds == first.rounds
    # and another seed is another run
    cfg.general.seed = 5
    assert TpuEngine(cfg).run(mode="device").log_tuples() != first.log_tuples()


def test_a_network_without_loss_takes_no_seed_argument():
    from shadow_tpu.config.presets import mixed_flagship_config

    eng = TpuEngine(mixed_flagship_config(200, sim_seconds=1))
    eng.run(mode="device")
    assert not eng.params.has_loss and eng._seed_args == ()
