"""Network topology graph, shortest-path routing, and IP assignment.

Behavior parity with the reference's ``src/main/network/graph/mod.rs``:

- GML graphs with ``node [id, host_bandwidth_up/down]`` and ``edge [source,
  target, latency, packet_loss]``; undirected graphs use each edge in both
  directions; a self-loop edge supplies the path properties between two hosts
  attached to the same node (graph/mod.rs:228-286).
- Edge latency must be > 0; packet loss must be in [0, 1].
- Path properties combine: latency adds, reliability multiplies
  (``1-(1-a)(1-b)``, graph/mod.rs:321-322); shortest paths minimize latency
  first, then loss (graph/mod.rs:301-303).
- Routing can be all-pairs shortest paths or direct-edges-only
  (graph/mod.rs:181,228).
- IPs are auto-assigned from 11.0.0.0/8 (graph/mod.rs:348).

TPU-first difference: routing resolves to **dense device-ready tables** —
``latency_ns[G,G]`` int64 and ``loss_threshold[G,G]`` int64 (u64-domain
Bernoulli thresholds, see ``core.rng.loss_threshold``) — because on the TPU
backend every per-packet (latency, loss) lookup is a gather into these
arrays.  The min latency feeds the lookahead window (runahead).
"""

from __future__ import annotations

import dataclasses
import lzma
import math
from pathlib import Path
from typing import Any, Optional

import numpy as np

from ..config import units
from ..core.rng import loss_threshold
from . import gml as gml_mod

#: Built-in one-node graph (config ``type: 1_gbit_switch``), as upstream.
ONE_GBIT_SWITCH_GML = """
graph [
  node [
    id 0
    host_bandwidth_up "1 Gbit"
    host_bandwidth_down "1 Gbit"
  ]
  edge [
    source 0
    target 0
    latency "1 ms"
  ]
]
"""

_UNREACHABLE = -1


class GraphError(ValueError):
    pass


@dataclasses.dataclass
class GraphNode:
    node_id: int
    bandwidth_up_bps: Optional[int]  # bits/sec, None if not set on the node
    bandwidth_down_bps: Optional[int]


@dataclasses.dataclass
class GraphEdge:
    source: int
    target: int
    latency_ns: int
    packet_loss: float


class NetworkGraph:
    """Parsed + validated topology with compiled routing tables."""

    def __init__(
        self,
        nodes: list[GraphNode],
        edges: list[GraphEdge],
        directed: bool,
        use_shortest_path: bool = True,
    ) -> None:
        if not nodes:
            raise GraphError("graph has no nodes")
        self.directed = directed
        self.nodes = nodes
        self.edges = edges
        # graph node ids can be sparse; map to dense indices
        self.node_ids = [n.node_id for n in nodes]
        if len(set(self.node_ids)) != len(self.node_ids):
            raise GraphError("duplicate node ids")
        self.id_to_index = {nid: i for i, nid in enumerate(self.node_ids)}
        for e in edges:
            # finiteness first: NaN slips through range comparisons (every
            # NaN comparison is False, so ``0.0 <= nan <= 1.0`` rejects it
            # only by accident of the chained form — be explicit), and an
            # inf latency would poison the shortest-path accumulation
            if isinstance(e.latency_ns, float) and not math.isfinite(e.latency_ns):
                raise GraphError(
                    f"edge {e.source}->{e.target}: latency must be a finite "
                    f"value, got {e.latency_ns!r}"
                )
            if e.latency_ns <= 0:
                raise GraphError(f"edge {e.source}->{e.target}: latency must be > 0")
            if not math.isfinite(e.packet_loss):
                raise GraphError(
                    f"edge {e.source}->{e.target}: packet_loss must be a "
                    f"finite value, got {e.packet_loss!r}"
                )
            if not (0.0 <= e.packet_loss <= 1.0):
                raise GraphError(
                    f"edge {e.source}->{e.target}: packet_loss not in [0,1]"
                )
            if e.source not in self.id_to_index or e.target not in self.id_to_index:
                raise GraphError(f"edge {e.source}->{e.target}: unknown node id")
        self._compile_routes(use_shortest_path)

    # -- construction -----------------------------------------------------

    @classmethod
    def from_gml(cls, text: str, use_shortest_path: bool = True) -> "NetworkGraph":
        g = gml_mod.parse_gml(text)
        directed = bool(g.get("directed", 0))
        nodes = []
        for n in g["nodes"]:
            if "id" not in n:
                raise GraphError("node without id")
            up = n.get("host_bandwidth_up")
            down = n.get("host_bandwidth_down")
            nodes.append(
                GraphNode(
                    node_id=int(n["id"]),
                    bandwidth_up_bps=units.parse_bandwidth(up) if up is not None else None,
                    bandwidth_down_bps=units.parse_bandwidth(down)
                    if down is not None
                    else None,
                )
            )
        edges = []
        for e in g["edges"]:
            if "source" not in e or "target" not in e:
                raise GraphError("edge without source/target")
            if "latency" not in e:
                raise GraphError("edge 'latency' was not provided")
            if not isinstance(e["latency"], str):
                # the reference requires a unit string here; a bare number is
                # ambiguous (ns? s?) and floats would truncate silently
                raise GraphError(
                    f"edge {e['source']}->{e['target']}: 'latency' must be a "
                    f"unit string like \"10 ms\", got {e['latency']!r}"
                )
            edges.append(
                GraphEdge(
                    source=int(e["source"]),
                    target=int(e["target"]),
                    latency_ns=units.parse_time(e["latency"]),
                    packet_loss=float(e.get("packet_loss", 0.0)),
                )
            )
        return cls(nodes, edges, directed, use_shortest_path)

    @classmethod
    def from_file(cls, path: str | Path, use_shortest_path: bool = True) -> "NetworkGraph":
        p = Path(path)
        raw = p.read_bytes()
        if p.suffix == ".xz" or raw[:6] == b"\xfd7zXZ\x00":
            raw = lzma.decompress(raw)
        return cls.from_gml(raw.decode(), use_shortest_path)

    @classmethod
    def one_gbit_switch(cls) -> "NetworkGraph":
        return cls.from_gml(ONE_GBIT_SWITCH_GML)

    # -- routing ----------------------------------------------------------

    def _compile_routes(self, use_shortest_path: bool) -> None:
        g = len(self.nodes)
        lat = np.full((g, g), _UNREACHABLE, dtype=np.int64)
        loss = np.zeros((g, g), dtype=np.float64)
        # direct edges (off-diagonal) and self-loops (diagonal)
        for e in self.edges:
            s, t = self.id_to_index[e.source], self.id_to_index[e.target]
            pairs = [(s, t)] if (self.directed or s == t) else [(s, t), (t, s)]
            for a, b in pairs:
                if lat[a, b] != _UNREACHABLE:
                    raise GraphError(
                        f"more than one edge connecting node {e.source} to {e.target}"
                    )
                lat[a, b] = e.latency_ns
                loss[a, b] = e.packet_loss

        if use_shortest_path and g > 1:
            lat, loss = self._all_pairs_shortest(lat, loss)

        self.latency_ns = lat
        self.packet_loss = loss
        # u64-domain thresholds for the device tables (int64 holds 2**32 fine;
        # vectorized mirror of core.rng.loss_threshold)
        self.loss_threshold = np.where(
            loss <= 0.0,
            np.int64(0),
            np.where(
                loss >= 1.0,
                np.int64(1) << 32,
                (loss * 4294967296.0).astype(np.int64),
            ),
        )

    def _all_pairs_shortest(
        self, direct_lat: np.ndarray, direct_loss: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """All-pairs shortest paths minimizing (latency, then loss).

        Lossless graphs (the overwhelmingly common case) go through scipy's
        C Dijkstra on exact integer latencies (float64 is exact below 2**53
        ns ≈ 104 days) with predecessor reconstruction, so no float error
        reaches the tables.  Graphs with lossy edges use an exact
        tuple-weight ``(latency, -log reliability)`` Dijkstra so latency
        ties genuinely break on loss — a float "epsilon" composite cannot
        represent a sub-ns perturbation at ms latencies.
        """
        if (direct_loss > 0.0).any():
            return self._all_pairs_shortest_lossy(direct_lat, direct_loss)

        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import dijkstra

        g = direct_lat.shape[0]
        rows, cols, w = [], [], []
        for i in range(g):
            for j in range(g):
                if i != j and direct_lat[i, j] != _UNREACHABLE:
                    rows.append(i)
                    cols.append(j)
                    w.append(float(direct_lat[i, j]))
        mat = csr_matrix((w, (rows, cols)), shape=(g, g))
        dist, pred = dijkstra(mat, directed=True, return_predecessors=True)

        lat = np.full((g, g), _UNREACHABLE, dtype=np.int64)
        order = np.argsort(dist, axis=1, kind="stable")
        for s in range(g):
            # accumulate exact edge latencies in increasing-distance order,
            # so predecessors are always finalized first
            for v in order[s]:
                if v == s or not np.isfinite(dist[s, v]):
                    continue
                p = pred[s, v]
                if p < 0:
                    continue
                base_lat = 0 if p == s else lat[s, p]
                lat[s, v] = base_lat + direct_lat[p, v]
        loss = np.zeros((g, g), dtype=np.float64)
        # keep self-loop (diagonal) direct properties: they model same-node
        # host-to-host paths and are not part of shortest-path routing
        np.fill_diagonal(lat, np.diag(direct_lat))
        np.fill_diagonal(loss, np.diag(direct_loss))
        return lat, loss

    def _all_pairs_shortest_lossy(
        self, direct_lat: np.ndarray, direct_loss: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Exact (latency, then loss) Dijkstra with tuple weights."""
        import heapq

        g = direct_lat.shape[0]
        adj: list[list[tuple[int, int, float]]] = [[] for _ in range(g)]
        for i in range(g):
            for j in range(g):
                if i != j and direct_lat[i, j] != _UNREACHABLE:
                    logloss = -math.log(max(1.0 - direct_loss[i, j], 1e-300))
                    adj[i].append((j, int(direct_lat[i, j]), logloss))

        lat = np.full((g, g), _UNREACHABLE, dtype=np.int64)
        loss = np.zeros((g, g), dtype=np.float64)
        for s in range(g):
            best: dict[int, tuple[int, float]] = {s: (0, 0.0)}
            done: set[int] = set()
            heap: list[tuple[int, float, int]] = [(0, 0.0, s)]
            while heap:
                d_lat, d_log, u = heapq.heappop(heap)
                if u in done:
                    continue
                done.add(u)
                for v, w_lat, w_log in adj[u]:
                    cand = (d_lat + w_lat, d_log + w_log)
                    if v not in best or cand < best[v]:
                        best[v] = cand
                        heapq.heappush(heap, (cand[0], cand[1], v))
            for v, (d_lat, d_log) in best.items():
                if v != s:
                    lat[s, v] = d_lat
                    loss[s, v] = 1.0 - math.exp(-d_log)
        np.fill_diagonal(lat, np.diag(direct_lat))
        np.fill_diagonal(loss, np.diag(direct_loss))
        return lat, loss

    def install_tables(
        self,
        latency_ns: np.ndarray,
        packet_loss: np.ndarray,
        loss_threshold: np.ndarray,
    ) -> None:
        """Swap the compiled pair tables in place — the fault-epoch seam
        (shadow_tpu/faults/overlay.py): RoutingInfo reads these arrays on
        every ``path()``, so installing a snapshot redirects all
        subsequent sends without rebuilding hosts or routing."""
        g = len(self.nodes)
        for name, arr in (
            ("latency_ns", latency_ns),
            ("packet_loss", packet_loss),
            ("loss_threshold", loss_threshold),
        ):
            if arr.shape != (g, g):
                raise GraphError(
                    f"install_tables: {name} has shape {arr.shape}, want {(g, g)}"
                )
        self.latency_ns = latency_ns
        self.packet_loss = packet_loss
        self.loss_threshold = loss_threshold

    # -- queries ----------------------------------------------------------

    def path(self, src_node_id: int, dst_node_id: int) -> tuple[int, float]:
        """(latency_ns, packet_loss) between two graph nodes; raises if the
        pair is unroutable (including a missing self-loop for same-node
        pairs, as in the reference)."""
        s = self.id_to_index[src_node_id]
        t = self.id_to_index[dst_node_id]
        l = int(self.latency_ns[s, t])
        if l == _UNREACHABLE:
            if s == t:
                raise GraphError(
                    f"node {src_node_id} hosts multiple endpoints but has no "
                    "self-loop edge to define the path between them"
                )
            raise GraphError(f"no path from node {src_node_id} to {dst_node_id}")
        return l, float(self.packet_loss[s, t])

    def _routable_latencies(self) -> np.ndarray:
        mask = self.latency_ns != _UNREACHABLE
        if not mask.any():
            raise GraphError("graph has no routable paths")
        return self.latency_ns[mask]

    def min_latency_ns(self) -> int:
        """Smallest routable latency — the conservative lookahead bound
        (graph/mod.rs:472-474, runahead.rs:14)."""
        return int(self._routable_latencies().min())

    def max_latency_ns(self) -> int:
        """Longest routable latency: what one hop of an overlay can cost."""
        return int(self._routable_latencies().max())

    def node_bandwidth(self, node_id: int) -> tuple[Optional[int], Optional[int]]:
        n = self.nodes[self.id_to_index[node_id]]
        return n.bandwidth_up_bps, n.bandwidth_down_bps


@dataclasses.dataclass
class IpAssignment:
    """Sequential auto-assignment from 11.0.0.0/8, skipping .0/.255 octets
    (mirrors graph/mod.rs:348's auto-IP block choice)."""

    _next: int = (11 << 24) + 1
    by_ip: dict[str, int] = dataclasses.field(default_factory=dict)  # ip -> host_id
    by_host: dict[int, str] = dataclasses.field(default_factory=dict)

    def assign(self, host_id: int, requested_ip: Optional[str] = None) -> str:
        if requested_ip is not None:
            if requested_ip in self.by_ip:
                raise GraphError(f"duplicate IP {requested_ip}")
            self.by_ip[requested_ip] = host_id
            self.by_host[host_id] = requested_ip
            return requested_ip
        while True:
            ip_int = self._next
            self._next += 1
            last = ip_int & 0xFF
            if last in (0, 255):
                continue
            if (ip_int >> 24) != 11:
                raise GraphError("11.0.0.0/8 exhausted")
            ip = ".".join(str((ip_int >> s) & 0xFF) for s in (24, 16, 8, 0))
            if ip in self.by_ip:
                continue
            self.by_ip[ip] = host_id
            self.by_host[host_id] = ip
            return ip

    def host_for_ip(self, ip: str) -> Optional[int]:
        return self.by_ip.get(ip)


class RoutingInfo:
    """Pairwise path lookup between *hosts* plus packet counters
    (graph/mod.rs:428-470), backed by the dense node tables.

    ``host_nodes`` maps host_id -> dense node index; the device tables are
    exactly ``latency_ns`` / ``loss_threshold`` gathered through this map.
    """

    def __init__(self, graph: NetworkGraph, host_to_node_id: dict[int, int]) -> None:
        self.graph = graph
        self.host_to_node_id = dict(host_to_node_id)
        self.host_node_index = {
            h: graph.id_to_index[nid] for h, nid in host_to_node_id.items()
        }
        self.packet_counts: dict[tuple[int, int], int] = {}
        # validate all pairs are routable up-front (reference computes paths
        # for the used node set during setup and errors early)
        from collections import Counter

        used = sorted(set(self.host_node_index.values()))
        counts = Counter(self.host_node_index.values())
        multi = {n for n, c in counts.items() if c > 1}
        for s in used:
            for t in used:
                if s == t and s not in multi:
                    continue
                if graph.latency_ns[s, t] == _UNREACHABLE:
                    raise GraphError(
                        f"hosts are assigned to nodes without a route "
                        f"({graph.node_ids[s]} -> {graph.node_ids[t]})"
                    )

    def path(self, src_host: int, dst_host: int) -> tuple[int, int]:
        """(latency_ns, loss_threshold) for a host pair; counts the packet."""
        s = self.host_node_index[src_host]
        t = self.host_node_index[dst_host]
        key = (src_host, dst_host)
        self.packet_counts[key] = self.packet_counts.get(key, 0) + 1
        return int(self.graph.latency_ns[s, t]), int(self.graph.loss_threshold[s, t])

    def min_used_latency_ns(self) -> int:
        """Min latency over node pairs actually used by hosts — the dynamic
        runahead bound (runahead.rs:60-118)."""
        used = sorted(set(self.host_node_index.values()))
        lat = self.graph.latency_ns[np.ix_(used, used)]
        mask = lat != _UNREACHABLE
        if not mask.any():
            raise GraphError("no routable path between any pair of used nodes")
        return int(lat[mask].min())

    def device_tables(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(host_node_index[N], latency_ns[G,G], loss_threshold[G,G]) ready
        to ship to the TPU backend."""
        n = max(self.host_node_index) + 1
        idx = np.zeros(n, dtype=np.int32)
        for h, i in self.host_node_index.items():
            idx[h] = i
        return idx, self.graph.latency_ns, self.graph.loss_threshold
