"""Scenario factories: managed-process scenarios (real OS binaries under
the shim), the routed, lossy all-TCP network, the PHOLD mesh and the
gossip mesh (lane models only).

The BASELINE.md evaluation ladder's config #5 is a Tor-shaped relay
topology (the reference's 500-relay chutney networks,
docs/getting_started_tor.md, src/test/tor/minimal/); this module builds
the self-contained analog from the repo's own native apps — no external
tools — so the benchmark and the scale gate measure the MANAGED path (the
workload class the reference's 6.38x was measured on,
/root/reference/MyTest/SUMMARY.md:5-9):

- an origin host running ``tcpecho server`` (epoll echo);
- ``chains`` three-relay chains (guard -> middle -> exit -> origin) of
  ``relay`` processes (poll-based TCP forwarding, the minimal Tor relay
  shape);
- per chain, ``clients_per_chain`` ``tcpecho hclient`` clients that
  resolve their guard by name and pump ``rounds`` echo round-trips of
  ``size`` bytes through the full chain;
- ``peers`` tgen-mesh model hosts keeping background datagram load on
  the same graph.
"""

from __future__ import annotations

import math
import random
from pathlib import Path

import numpy as np

from . import units
from .options import ConfigOptions

REPO = Path(__file__).resolve().parents[2]
BUILD = REPO / "native" / "build"


def managed_chain_config(
    data_dir: str | Path,
    chains: int = 8,
    clients_per_chain: int = 2,
    peers: int = 40,
    sim_seconds: int = 30,
    rounds: int = 20,
    size: int = 4096,
    gap_ms: int = 50,
    seed: int = 42,
    parallelism: int = 1,
    backend: str = "cpu",
    hybrid_workers: int = 1,
) -> ConfigOptions:
    """Relay-chain scenario config.  Managed process count =
    ``1 + 3*chains + chains*clients_per_chain``; host count adds
    ``peers`` model hosts.

    ``backend="tpu"`` selects the HYBRID engine (managed hosts' syscall
    plane on host CPU, every packet on the TPU lanes);
    ``hybrid_workers`` then picks the syscall-servicing parallelism
    (1 = serial, 0 = one worker per core, N = exactly N workers)."""
    n_clients = chains * clients_per_chain
    hosts = [
        f"""
  origin:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [server, "8080", "{n_clients}"]
        expected_final_state: {{exited: 0}}
"""
    ]
    for c in range(chains):
        hosts.append(f"""
  exit{c}:
    network_node_id: 1
    processes:
      - path: {BUILD / 'relay'}
        args: ["9000", origin, "8080"]
        start_time: 500ms
        expected_final_state: running
  middle{c}:
    network_node_id: 2
    processes:
      - path: {BUILD / 'relay'}
        args: ["9000", exit{c}, "9000"]
        start_time: 700ms
        expected_final_state: running
  guard{c}:
    network_node_id: 2
    processes:
      - path: {BUILD / 'relay'}
        args: ["9000", middle{c}, "9000"]
        start_time: 900ms
        expected_final_state: running
""")
        for k in range(clients_per_chain):
            hosts.append(f"""
  client{c}x{k}:
    network_node_id: 3
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [hclient, guard{c}, "9000", "{rounds}", "{size}", "{gap_ms}"]
        start_time: {1500 + 400 * k + 97 * c}ms
        expected_final_state: {{exited: 0}}
""")
    if peers:
        hosts.append(f"""
  peer:
    count: {peers}
    network_node_id: 1
    processes:
      - path: tgen-mesh
        args: [--interval, 50ms, --size, "600"]
        start_time: 0 s
""")
    return ConfigOptions.from_yaml(f"""
general:
  stop_time: {sim_seconds}s
  seed: {seed}
  data_directory: {data_dir}
  heartbeat_interval: null
  parallelism: {parallelism}
experimental:
  network_backend: {backend}
  hybrid_workers: {hybrid_workers}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        node [ id 2 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        node [ id 3 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 0 latency "1 ms" ]
        edge [ source 1 target 1 latency "2 ms" ]
        edge [ source 2 target 2 latency "3 ms" ]
        edge [ source 3 target 3 latency "2 ms" ]
        edge [ source 0 target 1 latency "8 ms" ]
        edge [ source 1 target 2 latency "15 ms" ]
        edge [ source 2 target 3 latency "10 ms" ]
      ]
hosts:
{''.join(hosts)}
""")


def managed_proc_count(chains: int, clients_per_chain: int) -> int:
    return 1 + 3 * chains + chains * clients_per_chain


def managed_relay_chains_large(
    data_dir: str | Path,
    chains: int = 25,
    clients_per_chain: int = 3,
    peers: int = 1000,
    sim_seconds: int = 10,
    rounds: int = 8,
    size: int = 2048,
    hybrid_workers: int = 0,
    seed: int = 42,
) -> ConfigOptions:
    """The HYBRID flagship scenario (the benchmark's `relay_chains_151`,
    ROADMAP A1; chip_smoke.py phase c): 100+ managed OS processes (default
    151 = 25 three-relay chains + 75 clients + origin) whose syscall plane
    runs across ``hybrid_workers`` processes, over 1k+ lane hosts (default
    1000 tgen peers) whose data plane — and every managed packet — rides the
    TPU lanes.  This is the workload class the reference's 6.38x headline was
    measured on, at the reference's own scale point."""
    return managed_chain_config(
        data_dir,
        chains=chains,
        clients_per_chain=clients_per_chain,
        peers=peers,
        sim_seconds=sim_seconds,
        rounds=rounds,
        size=size,
        seed=seed,
        backend="tpu",
        hybrid_workers=hybrid_workers,
    )


def managed_relay_chains_gate(
    data_dir: str | Path,
    hybrid_workers: int = 2,
    sim_seconds: int = 8,
    backend: str = "tpu",
    seed: int = 42,
) -> ConfigOptions:
    """The SHADOW_TPU_SCALE-gated small sibling of
    :func:`managed_relay_chains_large`: the same shape at 16 managed
    processes over 60 lane hosts, sized so the gate exercises the full
    hybrid seam (parallel syscall servicing included) on the CPU JAX
    platform — no TPU time needed (tests/test_hybrid_mp.py)."""
    return managed_chain_config(
        data_dir,
        chains=3,
        clients_per_chain=2,
        peers=60,
        sim_seconds=sim_seconds,
        rounds=3,
        size=1024,
        seed=seed,
        backend=backend,
        hybrid_workers=hybrid_workers,
    )


#: edge ``packet_loss`` values of :func:`routed_graph_gml` and their weights
ROUTED_EDGE_LOSS = ((0.0, 0.001, 0.005), (50, 35, 15))


def routed_graph_gml(
    graph_nodes: int, graph_seed: int, bandwidth: str = "1 Gbit"
) -> str:
    """A city-level latency/loss graph as GML text (the shape of upstream
    Shadow's tornettools graphs, which this tree does not carry): every
    node a 2 ms self-edge, a ring plus two seeded chords per node,
    undirected; edge latency log-uniform on [2, 40] ms in whole ms; edge
    loss drawn from ``ROUTED_EDGE_LOSS``.  Depends on ``graph_seed``
    alone."""
    rnd = random.Random(graph_seed)
    g = graph_nodes
    out = ["graph [", "  directed 0"]
    for n in range(g):
        out.append(
            f'  node [ id {n} host_bandwidth_up "{bandwidth}" '
            f'host_bandwidth_down "{bandwidth}" ]'
        )
        out.append(f'  edge [ source {n} target {n} latency "2 ms" ]')
    edges = set()
    for n in range(g):
        for m in ((n + 1) % g, rnd.randrange(g), rnd.randrange(g)):
            if m != n:
                edges.add((min(n, m), max(n, m)))
    values, weights = ROUTED_EDGE_LOSS
    for a, b in sorted(edges):
        lat = int(2 * 20 ** rnd.random())
        loss = rnd.choices(values, weights)[0]
        out.append(
            f'  edge [ source {a} target {b} latency "{lat} ms"'
            + (f" packet_loss {loss}" if loss else "") + " ]"
        )
    return "\n".join(out + ["]", ""])


def _one_switch_gml(latency: str, bandwidth: str) -> str:
    """One graph node: self-edge ``latency`` (the lookahead), zero loss."""
    return (
        "graph [\n"
        f'  node [ id 0 host_bandwidth_up "{bandwidth}" '
        f'host_bandwidth_down "{bandwidth}" ]\n'
        f'  edge [ source 0 target 0 latency "{latency}" ]\n'
        "]\n")


def _placed_hosts(name: str, stream: str, n_hosts: int, graph_nodes: int,
                  graph_seed: int, process: dict) -> dict:
    """``n_hosts`` host documents ``<name>00001`` ... (sorted by id), each
    running ``process`` on a graph node drawn uniformly from the stream
    ``random.Random(f"{stream}-hosts-{graph_seed}")`` — one of its own, so
    the graph does not move with the width."""
    rnd = random.Random(f"{stream}-hosts-{graph_seed}")
    return {
        f"{name}{i:0{len(str(n_hosts))}d}": {
            "network_node_id": rnd.randrange(graph_nodes),
            "processes": [process],
        }
        for i in range(1, n_hosts + 1)
    }


def routed_tcp_mesh_config(
    n_hosts: int,
    graph_nodes: int,
    graph_seed: int = 1,
    stream_bytes: int = 1 << 20,
    start_spread_ms: int = 1000,
    bandwidth: str = "1 Gbit",
    seed: int = 1,
) -> ConfigOptions:
    """The routed, lossy, all-TCP network (BASELINE.md north-star config
    #3: "1k-host tgen mesh, full TCP stack + latency/loss graph"):
    ``n_hosts // 2`` lane-TCP flows of ``stream_bytes``, client ``i`` ->
    server ``i``, hosts placed uniformly over :func:`routed_graph_gml`'s
    nodes, clients starting at whole ms uniform on [0,
    ``start_spread_ms``).  The graph goes through ``net/gml.py`` and
    ``net/graph.py``'s shortest-path routing as a user's file would.

    Graph, placement and start times depend on ``graph_seed`` alone — the
    deployment is ONE network; ``seed`` drives the loss draws.  Stop time
    (5 sim-s, nearly every flow's whole life) and backend (``tpu``) are the
    caller's to set on the result, as for any configuration."""
    if n_hosts < 2 or n_hosts % 2:
        raise ValueError("n_hosts must be a positive even number")
    gml = routed_graph_gml(graph_nodes, graph_seed, bandwidth)
    # a stream of its own, so the graph does not move with the host count
    rnd = random.Random(f"hosts-{graph_seed}")
    hosts = {}
    for i in range(n_hosts // 2):
        hosts[f"sc{i:05d}"] = {
            "network_node_id": rnd.randrange(graph_nodes),
            "processes": [{
                "path": "stream-client",
                "args": ["--server", f"ss{i:05d}", "--size", str(stream_bytes)],
                "start_time": f"{rnd.randrange(max(start_spread_ms, 1))} ms",
            }],
        }
        hosts[f"ss{i:05d}"] = {
            "network_node_id": rnd.randrange(graph_nodes),
            "processes": [{"path": "stream-server", "start_time": "0 s"}],
        }
    return ConfigOptions.from_dict({
        "general": {"stop_time": "5 s", "seed": seed,
                    "heartbeat_interval": None},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "experimental": {"network_backend": "tpu"},
        "hosts": hosts,
    })


# -- PHOLD: random destinations, an active lane model ------------------------

#: windows the PHOLD shape law budgets for on one switch: 10 sim-s at a 10 ms
#: lookahead, the horizon of this repo's presets (the stop time is the
#: caller's, set after the factory returns, and the tail moves with the LOG of
#: this number)
PHOLD_LAW_WINDOWS = 1000
#: the same horizon as simulated time: on a graph the factory budgets for
#: this many ns of windows of the graph's smallest latency
PHOLD_LAW_HORIZON_NS = PHOLD_LAW_WINDOWS * 10_000_000
#: pops an iteration of a PHOLD program: every other deployment's.  4 pops
#: take 15 % fewer iterations at 2.3x the iteration (PERF.md 6, PR 35)
PHOLD_POPS = 2
#: the engine's own headroom over a lane's queued events
#: (``TpuEngine.__init__``: capacity >= initial events + 8)
QUEUE_HEADROOM = 8


def poisson_tail_quantile(mean: float, p: float) -> int:
    """The smallest ``k`` with ``P(X > k) < p`` for ``X ~ Poisson(mean)``:
    the width that one draw overflows with probability under ``p``."""
    k = int(mean)
    while True:
        j = k + 1
        term = math.exp(-mean + j * math.log(mean) - math.lgamma(j + 1))
        tail = 0.0
        while term > tail * 1e-17:
            tail += term
            j += 1
            term *= mean / j
        if tail < p:
            return k
        k += 1


def phold_shape_law(
    n_hosts: int, messages: int, windows: int = PHOLD_LAW_WINDOWS,
    pops: int = PHOLD_POPS, window_ns: float = 1, mean_hop_ns: float = 1,
    far_hop_ns: float = 1,
) -> tuple[int, int]:
    """``(tpu_lane_queue_capacity, tpu_cross_capacity)`` for a PHOLD mesh
    of ``n_hosts`` lanes and ``messages`` messages a lane, run for
    ``windows`` lookahead windows at ``pops`` pops an iteration.  The three
    path facts enter as RATIOS and default to one switch, where a hop is
    one window: ``window_ns`` the lookahead (``NetworkGraph.min_latency_ns``),
    ``mean_hop_ns`` the mean path latency between two lanes, ``far_hop_ns``
    the mean path latency INTO the lane farthest from the rest (at most
    the longest routed path, ``max_latency_ns``).

    Destinations are uniform draws, so what a lane is handed is a Poisson
    count, and a width is a TAIL QUANTILE of it, taken so that one run
    overflows anywhere with probability about 1 / 1 000:

    - a message spends a hop to lane B in flight for the path latency into
      B and is then sent on at once, so of its time the share ``(mean
      latency into B) / (lanes x mean_hop_ns)`` is spent on the way to B:
      over the ``lanes x messages`` messages, what is IN FLIGHT to B is
      ~Poisson(messages x (mean latency into B) / mean_hop_ns) — messages
      to an average lane whatever the latencies, more to a far one — and
      all of it sits in B's queue from the iteration of its send, each a
      PACKET, then the DELIVERY it becomes;
    - a window's arrivals at one lane are ~Poisson(messages x window_ns /
      mean_hop_ns) (every lane is sent the same share); each costs two
      dependent pops (the PACKET, then the DELIVERY it inserts, whose pop
      is the send), so the fullest lane of a window sets the iterations:
      ``iters = ceil(2 q / pops)`` with ``q`` the quantile at 1 / (1 000 x
      lanes x windows).  That IS what a run takes since the lanes co-pop
      any DELIVERY* PACKET* prefix (``lanes.pop_mask``, the window-inert
      class): ``q`` arrivals are ``q / 2`` packet pairs and ``q / 2``
      delivery pairs at 2 pops; under the same-instant rule before it a
      DELIVERY popped alone and a window took ``1.5 q``;
    - a QUEUE holds what was in flight to it when the window opened plus
      what the window's sends file while it pops: ~Poisson(messages x
      (far_hop_ns + window_ns) / mean_hop_ns) at the farthest lane, at
      1 / (1 000 x lanes x windows x iters), plus the engine's headroom.
      On one switch that is ~Poisson(2 x messages): what is left of this
      window's arrivals and what the merge has already filed for the next.
      On a graph the window is a small part of a hop (2 of 18.6 ms on
      ``routed_graph_gml(200, 1)``) and the far lane's share leads (28.2
      of 18.6 ms): the longest routed path (42 ms) in its place would be
      a bound no lane's MEAN reaches, and would double the merge's row;
    - a CROSS segment holds one iteration's fan-in: every lane sends at
      most ``pops``, so ~Poisson(<= pops) (reached at start-up, when every
      lane pops ``pops`` initial messages, whatever the graph), at the
      same tail.

    Loss only thins the population, so the law ignores it.  The merge's
    row is ``capacity + 2 pops + cross`` columns and its sort pads to a
    power of two (PERF.md 4), so the columns left under that power go to
    the queue: they cost nothing.  Strict capacity is the backstop: a run
    past the tail raises and names the block."""
    if min(n_hosts, messages, windows, pops) < 1:
        raise ValueError("n_hosts, messages, windows and pops must be >= 1")
    if not 0 < window_ns <= mean_hop_ns <= far_hop_ns:
        raise ValueError(
            "need 0 < window_ns <= mean_hop_ns <= far_hop_ns: the window is "
            "the smallest path, the far lane's mean no smaller than the mean")
    draws = 1000 * n_hosts * windows
    handed = messages * window_ns / mean_hop_ns
    iters = -(-2 * poisson_tail_quantile(handed, 1 / draws) // pops)
    p = 1 / (draws * iters)
    held = messages * (far_hop_ns + window_ns) / mean_hop_ns
    queue = poisson_tail_quantile(held, p) + QUEUE_HEADROOM
    cross = poisson_tail_quantile(pops, p)
    row = queue + 2 * pops + cross
    return (1 << (row - 1).bit_length()) - 2 * pops - cross, cross


def phold_mesh_config(
    n_hosts: int,
    messages: int = 4,
    size: int = 256,
    latency: str = "10 ms",
    bandwidth: str = "1 Gbit",
    seed: int = 1,
    graph_nodes: int | None = None,
    graph_seed: int = 1,
) -> ConfigOptions:
    """PHOLD (Fujimoto 1990) as upstream Shadow ships it (the reference's
    ``src/test/phold/test_phold.c``): ``n_hosts`` hosts, each one process
    ``phold --messages <messages> --size <size>`` (``models/phold.py``:
    every received datagram answered by one to a peer drawn uniformly from
    the others), ``bandwidth`` up and down.

    Without ``graph_nodes`` the network is one graph node (ONE host group
    with ``count``): self-edge ``latency`` (the lookahead), zero loss — so
    the population of ``n_hosts x messages`` datagrams is conserved.  With
    it the network is :func:`routed_graph_gml` ``(graph_nodes, graph_seed,
    bandwidth)`` — a wide-area latency / loss graph; ``latency`` is not
    read — and host ``i`` (the same id either way, a document of its own)
    is placed on a graph node drawn uniformly from the stream
    ``random.Random(f"phold-hosts-{graph_seed}")``, of ``graph_seed`` alone
    (as :func:`gossip_mesh_config` places its nodes): the deployment is
    ONE network, ``seed`` drives the peer and loss draws.  A lost datagram
    is final there, so the population decays.

    The lane program's shapes are set here by :func:`phold_shape_law`
    (random fan-in has no hand-set safe width) — on a graph from its
    window, the placed lanes' mean path and the mean path into the
    farthest lane, over ``PHOLD_LAW_HORIZON_NS`` of windows; stop time and
    backend (``tpu``) are the caller's to set on the result."""
    process = {
        "path": "phold",
        "args": ["--messages", str(messages), "--size", str(size)],
        "start_time": "0 s",
    }
    if graph_nodes is None:
        queue, cross = phold_shape_law(n_hosts, messages)
        gml = _one_switch_gml(latency, bandwidth)
        hosts = {"lp": {"count": n_hosts, "network_node_id": 0,
                        "processes": [process]}}
    else:
        from ..net.graph import NetworkGraph

        gml = routed_graph_gml(graph_nodes, graph_seed, bandwidth)
        hosts = _placed_hosts("lp", "phold", n_hosts, graph_nodes, graph_seed,
                              process)
        # the law's path facts, over the lanes as placed
        graph = NetworkGraph.from_gml(gml)
        share = np.bincount(
            [graph.id_to_index[h["network_node_id"]] for h in hosts.values()],
            minlength=graph_nodes) / n_hosts
        into = share @ graph.latency_ns  # mean path into a lane of each node
        window = graph.min_latency_ns()
        queue, cross = phold_shape_law(
            n_hosts, messages, windows=-(-PHOLD_LAW_HORIZON_NS // window),
            window_ns=window, mean_hop_ns=float(into @ share),
            far_hop_ns=float(into[share > 0].max()))
    return ConfigOptions.from_dict({
        "general": {"stop_time": "10 s", "seed": seed,
                    "heartbeat_interval": None},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "experimental": {
            "network_backend": "tpu",
            "tpu_lane_queue_capacity": queue,
            "tpu_cross_capacity": cross,
            "tpu_events_per_round": PHOLD_POPS,
        },
        "hosts": hosts,
    })


# -- gossip: one pop, D sends, a whole-network same-instant burst ------------

#: pops an iteration of a gossip program: every other deployment's (the
#: exchange is ``pops x degree x lanes`` rows wide)
GOSSIP_POPS = 2


def gossip_flood_hops(n_hosts: int, degree: int) -> int:
    """Hops a flood is budgeted to last: twice the depth of a
    ``(degree - 1)``-ary tree over the nodes, plus two (a ring: half way
    round).  Bursts closer together than this many link latencies count as
    ONE burst in the shape law."""
    if degree <= 2:
        return n_hosts // 2 + 1
    return 2 * math.ceil(math.log(n_hosts) / math.log(degree - 1)) + 2


def gossip_shape_law(
    degree: int, concurrent: int, publications: int = 1,
    pops: int = GOSSIP_POPS,
) -> tuple[int, int]:
    """``(tpu_lane_queue_capacity, tpu_cross_capacity)`` for a gossip mesh
    of ``degree`` peers a node with at most ``concurrent`` messages in
    flight at once, some node publishing ``publications`` times in all.

    The bounds are DETERMINISTIC, not quantiles: a node is sent each
    message at most once by each of its ``degree`` peers (a peer forwards
    a message once, on its first receipt), so

    - a CROSS segment — one iteration's fan-in — holds at most ``degree x
      pops`` events: each peer pops ``pops`` events and sends this lane at
      most one datagram for each;
    - a QUEUE holds at most ``degree x concurrent`` arrivals (each a
      PACKET, then the DELIVERY it becomes), beside its own start marker
      and ``publications`` publish timers, plus the engine's headroom.

    As PHOLD's law, the merge's row ``capacity + 2 pops + cross`` pads to
    a power of two and the columns left under it go to the queue.  Strict
    capacity is the backstop: bursts closer than the law budgets (see
    :func:`gossip_flood_hops`) raise and name the block."""
    if min(degree, concurrent, pops) < 1 or publications < 0:
        raise ValueError(
            "degree, concurrent and pops must be >= 1, publications >= 0")
    cross = degree * pops
    queue = degree * concurrent + 1 + publications + QUEUE_HEADROOM
    row = queue + 2 * pops + cross
    return (1 << (row - 1).bit_length()) - 2 * pops - cross, cross


def slot_chaos_events(
    graph,
    fault_seed: int = 1,
    loss_edges: int = 15,
    loss: float = 0.05,
    latency_edges: int = 15,
    latency: str = "150 ms",
    down_edges: int = 10,
    degrade_at: str = "900 ms",
    down_at: str = "1060 ms",
    up_at: str = "2 s",
    partition_at: str = "4900 ms",
    heal_at: str = "7 s",
    region_share: int = 4,
) -> list[dict]:
    """The timed chaos schedule ``slot_chaos`` over ``graph`` (a
    ``NetworkGraph``) as ``docs/faults.md`` event documents — the kinds a
    chaos test of an Ethereum devnet injects (ethpandaops/attacknet:
    packet loss, added delay, links down, a partition, each followed by
    the check that the network recovers), timed against one slot whose
    bursts stand at 1 / 5 / 9 s:

    - ``degrade_at``: ``loss`` on ``loss_edges`` edges and ``latency`` on
      ``latency_edges`` others, just before burst 1 floods the graph;
    - ``down_at``: ``link_down`` on ``down_edges`` further edges, in the
      middle of that flood;
    - ``up_at``: ``link_up`` on all of them (clears loss, latency, down);
    - ``partition_at``: the graph nodes with the lowest ``1 /
      region_share`` of the ids against the rest, just before burst 2;
    - ``heal_at``: whole again; burst 3 is the recovery check.

    The edges are drawn WITHOUT replacement from the graph's non-self
    edges (a self-edge is never touched, so the lookahead window stays)
    by a numpy stream of ``fault_seed`` alone."""
    edges = [(e.source, e.target) for e in graph.edges
             if e.source != e.target]
    picked = loss_edges + latency_edges + down_edges
    if picked > len(edges):
        raise ValueError(
            f"slot_chaos draws {picked} edges, the graph has {len(edges)} "
            "that are no self-edge")
    order = np.random.RandomState(fault_seed).permutation(len(edges))
    drawn = [edges[i] for i in order[:picked]]
    lossy = drawn[:loss_edges]
    slow = drawn[loss_edges:loss_edges + latency_edges]
    down = drawn[loss_edges + latency_edges:]

    def link(at, kind, edge, **more):
        return {"at": at, "kind": kind, "source": edge[0], "target": edge[1],
                **more}

    ids = sorted(graph.node_ids)
    cut = max(len(ids) // region_share, 1)
    return (
        [link(degrade_at, "loss", e, loss=loss) for e in lossy]
        + [link(degrade_at, "latency", e, latency=latency) for e in slow]
        + [link(down_at, "link_down", e) for e in down]
        + [link(up_at, "link_up", e) for e in drawn]
        + [{"at": partition_at, "kind": "partition",
            "groups": [ids[:cut], ids[cut:]]},
           {"at": heal_at, "kind": "heal"}]
    )


#: the fault schedules :func:`gossip_mesh_config` knows by name
GOSSIP_FAULT_SCHEDULES = {"slot_chaos": slot_chaos_events}


def gossip_mesh_config(
    n_hosts: int,
    degree: int = 8,
    mesh_seed: int = 1,
    bursts=("1 s", "5 s", "9 s"),
    messages: int = 8,
    size: int = 512,
    latency: str = "10 ms",
    bandwidth: str = "1 Gbit",
    seed: int = 1,
    graph_nodes: int | None = None,
    graph_seed: int = 1,
    faults=None,
    fault_seed: int = 1,
) -> ConfigOptions:
    """Ethereum-style gossip (libp2p gossipsub's eager push over a static
    mesh, ``models/gossip.py``): ``n_hosts`` nodes, each ONE process
    ``gossip`` with one argument list — every node finds its row of
    ``gossip_mesh(n_hosts, degree, mesh_seed)`` and its own publications by
    its host id.  At each instant of ``bursts`` (times after the start at
    0 s) ``messages`` distinct nodes publish one ``size``-byte message
    each; ``bandwidth`` up and down.

    Without ``graph_nodes`` the network is one graph node (ONE host group
    with ``count``, no per-host document): self-edge ``latency`` (the
    lookahead), zero loss.  With it the network is
    :func:`routed_graph_gml` ``(graph_nodes, graph_seed, bandwidth)`` — a
    wide-area latency / loss graph; ``latency`` is not read — and every
    node is placed on a graph node drawn uniformly from a stream of
    ``graph_seed`` alone (as :func:`routed_tcp_mesh_config` places its
    hosts): the deployment is ONE network, ``seed`` drives the loss draws.
    Host ``i`` has the same id, mesh row and publications either way.

    ``faults`` puts the network under a fault schedule (``cfg.faults``,
    ``docs/faults.md``): the NAME of one built over the graph from a
    stream of ``fault_seed`` alone (``GOSSIP_FAULT_SCHEDULES``:
    ``"slot_chaos"``, :func:`slot_chaos_events`), or a list of event
    documents as they are.  A flood's hop is then budgeted at the longest
    routed path OF ANY EPOCH (a ``latency`` or ``link_down`` epoch
    lengthens paths).  Every event is kept whatever the stop time (an
    epoch past it is dropped by ``FaultOverlay.segment_plan``).

    The lane program's shapes are :func:`gossip_shape_law`'s; stop time
    and backend (``tpu``) are the caller's to set on the result."""
    from ..models.gossip import gossip_publishers
    from ..net.graph import NetworkGraph

    times = sorted(units.parse_time(b) for b in bursts)
    process = {
        "path": "gossip",
        "args": [
            "--degree", str(degree), "--mesh-seed", str(mesh_seed),
            "--bursts", ",".join(f"{t} ns" for t in times),
            "--messages", str(messages), "--size", str(size),
        ],
        "start_time": "0 s",
    }
    if graph_nodes is None:
        gml = _one_switch_gml(latency, bandwidth)
        hosts = {"node": {"count": n_hosts, "network_node_id": 0,
                          "processes": [process]}}
    else:
        gml = routed_graph_gml(graph_nodes, graph_seed, bandwidth)
        hosts = _placed_hosts("node", "gossip", n_hosts, graph_nodes,
                              graph_seed, process)
    # a flood's hop is budgeted at the longest routed path, of any epoch
    graph = NetworkGraph.from_gml(gml)
    hop = graph.max_latency_ns()
    events = []
    if faults is not None:
        from ..faults.overlay import FaultOverlay
        from ..faults.schedule import FaultSchedule

        events = (GOSSIP_FAULT_SCHEDULES[faults](graph, fault_seed)
                  if isinstance(faults, str) else list(faults))
        hop = FaultOverlay(
            FaultSchedule.parse(events), graph, {}, []).max_latency_ns()
    span = gossip_flood_hops(n_hosts, degree) * hop
    concurrent = messages * max(
        sum(1 for u in times if t <= u < t + span) for t in times
    )
    pubs = gossip_publishers(n_hosts, len(times), messages, mesh_seed)
    queue, cross = gossip_shape_law(
        degree, concurrent,
        publications=int(np.bincount(pubs.reshape(-1)).max()),
    )
    return ConfigOptions.from_dict({
        "general": {"stop_time": "12 s", "seed": seed,
                    "heartbeat_interval": None},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "experimental": {
            "network_backend": "tpu",
            "tpu_lane_queue_capacity": queue,
            "tpu_cross_capacity": cross,
            "tpu_events_per_round": GOSSIP_POPS,
        },
        "hosts": hosts,
        **({"faults": {"events": events}} if events else {}),
    })
