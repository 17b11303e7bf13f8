"""Canonical workload presets shared by the bench and the driver entry
points, so the program the driver compile-checks is the one the bench times
(BASELINE.md north-star configs)."""

from __future__ import annotations

from .options import ConfigOptions


def flagship_mesh_config(
    n_hosts: int,
    sim_seconds: int = 10,
    latency: str = "10 ms",
    interval: str = "10ms",
    size: int = 1428,
    queue_capacity: int | None = None,
    pops_per_round: int | None = None,
    stream_pairs: int = 0,
    stream_bytes: int = 50_000_000,
    backend: str = "tpu",
    seed: int = 1,
) -> ConfigOptions:
    """The tgen all-to-all mesh over a single switch (BASELINE config #4):
    every host sends a ``size``-byte datagram every ``interval`` to a
    round-robin peer; lookahead window = link ``latency``.

    ``stream_pairs`` > 0 makes it the MIXED TCP/UDP mesh of the north-star
    config: that many stream-client -> stream-server lane-TCP flows
    (handshake, NewReno, RTO — lanes_stream.py on device) run alongside
    the UDP mesh, each streaming ``stream_bytes``; the mesh's round-robin
    spray crosses the stream lanes, which must ignore it exactly like the
    CPU oracle does."""
    k = stream_pairs
    if 2 * k >= n_hosts:
        raise ValueError("stream_pairs must leave room for mesh hosts")
    hosts = [
        f"""
  peer:
    count: {n_hosts - 2 * k}
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval {interval} --size {size}
        start_time: 0 s
"""
    ]
    for i in range(k):
        hosts.append(
            f"""
  sc{i:05d}:
    network_node_id: 0
    processes:
      - path: stream-client
        args: --server ss{i:05d} --size {stream_bytes}
        start_time: 0 s
  ss{i:05d}:
    network_node_id: 0
    processes:
      - path: stream-server
        start_time: 0 s
"""
        )
    cfg = ConfigOptions.from_yaml(
        f"""
general:
  stop_time: {sim_seconds} s
  seed: {seed}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0  host_bandwidth_up "1 Gbit"  host_bandwidth_down "1 Gbit" ]
        edge [ source 0  target 0  latency "{latency}" ]
      ]
experimental:
  network_backend: {backend}
hosts:
{''.join(hosts)}
"""
    )
    if queue_capacity is not None:
        cfg.experimental.tpu_lane_queue_capacity = queue_capacity
    if pops_per_round is not None:
        cfg.experimental.tpu_events_per_round = pops_per_round
    return cfg


def transfer_pair_config(
    size_bytes: int = 50_000_000, sim_seconds: int = 60,
    backend: str = "tpu", seed: int = 1,
) -> ConfigOptions:
    """BASELINE config #1: a 2-host client->server transfer over one link
    (the reference's examples/docs/basic-file-transfer shape), as a
    lane-TCP stream flow."""
    return ConfigOptions.from_yaml(f"""
general:
  stop_time: {sim_seconds} s
  seed: {seed}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        node [ id 1 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 1 latency "10 ms" ]
      ]
experimental:
  network_backend: {backend}
  tpu_lane_queue_capacity: 128
hosts:
  c:
    network_node_id: 0
    processes:
      - path: stream-client
        args: --server s --size {size_bytes}
  s:
    network_node_id: 1
    processes:
      - path: stream-server
""")


def udp_star_config(
    n_hosts: int = 100,
    sim_seconds: int = 10,
    interval: str = "10ms",
    size: int = 1428,
    backend: str = "tpu",
    seed: int = 1,
) -> ConfigOptions:
    """BASELINE config #2: a UDP-only tgen star — n-1 clients send fixed
    datagrams to one server host (single switch, no TCP state).  The
    server lane's queue must hold every in-flight client datagram, so
    capacity scales with the fan-in (the clients all fire each interval)."""
    capacity = max(64, 2 * n_hosts)
    return ConfigOptions.from_yaml(f"""
general:
  stop_time: {sim_seconds} s
  seed: {seed}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "5 ms" ]
      ]
experimental:
  network_backend: {backend}
  tpu_lane_queue_capacity: {capacity}
hosts:
  srv:
    network_node_id: 0
    processes:
      - path: tgen-server
  cli:
    count: {n_hosts - 1}
    network_node_id: 0
    processes:
      - path: tgen-client
        args: --server srv --interval {interval} --size {size}
""")


def mixed_flagship_config(
    n_hosts: int, sim_seconds: int = 5, backend: str = "tpu",
    seed: int = 1,
) -> ConfigOptions:
    """The MIXED TCP/UDP mesh at its north-star tuning (the bench's and
    the probe/HLO scripts' single source of truth): 1 stream pair per 100
    hosts streaming 2 MB across the datagram mesh.

    Tuning (iteration COUNTS are platform-independent; what an
    iteration costs on the attached chip is not measured): with the
    TIERED stream backend the [N] side needs only the pure mesh's queue
    shape (capacity 16, 2 pops/iter), and the tier drains at 16
    events/iter — 623 iterations per 500 windows, against 803 at 8 and
    573 at 24 with a wider, dearer co-pop sort (docs/tpu-backend.md)."""
    cfg = flagship_mesh_config(
        n_hosts, sim_seconds=sim_seconds, queue_capacity=16,
        pops_per_round=2, stream_pairs=max(n_hosts // 100, 1),
        stream_bytes=2_000_000, backend=backend, seed=seed,
    )
    # one-to-one pairing puts stream arrivals on the split exchange, so
    # the main cross block only carries the mesh's permutation spray
    # (strict mode would raise if this ever overflowed)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.experimental.tpu_stream_events_per_round = 16
    return cfg
