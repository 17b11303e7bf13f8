"""Gossip — the eager-push path of libp2p gossipsub over a static mesh.

A lane model of the protocol the Ethereum consensus layer spreads every
block, attestation and aggregate by (gossipsub v1.0 "Message processing",
v1.1; the consensus specs' p2p interface fix ``D`` = 8), as ``phold`` is
a lane model of upstream's test-phold.  Node *i* holds a static peer list
``P_i[0..D)`` and a seen set ``S_i`` of message ids:

- *publish(m)* at node *i*: ``S_i ∪= {m}``, then for ``k = 0..D-1`` in
  order ``send(P_i[k], size, payload=m)``;
- *on_delivery(t, src, m)*: a message already seen counts one
  ``gossip_duplicates`` and does nothing else; a new one joins ``S_i``,
  counts one ``gossip_first`` and one bucket of the propagation
  histogram (:func:`age_counter` of ``t`` less the message's burst
  instant), and is forwarded, in ``k`` order, to every mesh peer but the
  one it came from.

Every send is an ordinary datagram of the engine (``gossip_sends`` counts
them).  Departures from the protocol: the mesh is static (no GRAFT / PRUNE,
no heartbeat, no scoring), there is no lazy IHAVE / IWANT gossip, one
topic, no validation delay, datagrams instead of the stream transport, one
message size.

:func:`gossip_mesh` and :func:`gossip_publishers` are pure functions of
their arguments — DATA both backends build from (the CPU oracle through
this model, the lane backend as device tables); the semantics above are
written twice, here and in ``backend/lanes.py``.
"""

from __future__ import annotations

import bisect
import functools
import random

import numpy as np

from ..config import units
from .base import HostApi, parse_kv_args, register_model

#: upper edges, in ms, of the propagation histogram's buckets: a first
#: delivery counts into the first bucket whose edge its age (delivery time
#: less the message's burst instant) does not pass, or into the overflow
#: bucket.  Counters, NOT cumulative: the buckets sum to ``gossip_first``.
#: One law for both backends (the lane body compares against these)
AGE_EDGES_MS = (10, 20, 30, 40, 50, 60, 80, 100, 125, 150, 200, 250, 300,
                400, 500)
#: the same edges in the engines' unit
AGE_EDGES_NS = tuple(e * 1_000_000 for e in AGE_EDGES_MS)
#: the histogram's counter names, in bucket order
AGE_COUNTERS = tuple(f"gossip_first_le_{e}ms" for e in AGE_EDGES_MS) + (
    f"gossip_first_gt_{AGE_EDGES_MS[-1]}ms",)


def age_counter(age_ns: int) -> str:
    """The histogram counter a first delivery ``age_ns`` after its
    message's burst instant counts into."""
    return AGE_COUNTERS[bisect.bisect_left(AGE_EDGES_NS, age_ns)]


#: redraws of conflicting cycle positions before :func:`gossip_mesh` gives
#: up (a graph too small for ``degree / 2`` edge-disjoint cycles)
_REPAIR_ROUNDS = 10_000


@functools.lru_cache(maxsize=8)
def gossip_mesh(n: int, degree: int, seed: int) -> np.ndarray:
    """The static mesh: ``int32[n, degree]``, row *i* the peers of node
    *i* in forwarding order.  ``degree / 2`` seeded Hamiltonian cycles;
    cycle *c* puts a node's successor in column ``2c`` and its predecessor
    in ``2c + 1``, so the graph is symmetric and ``degree``-regular by
    construction, and CONNECTED because one Hamiltonian cycle already is.
    It is made SIMPLE (no edge twice) by redrawing: the positions of a
    cycle whose edge an earlier cycle holds are swapped with seeded random
    positions until none is left.  The result is read-only and cached (ten
    thousand model instances ask for the same table)."""
    if degree < 2 or degree % 2 or degree >= n:
        raise ValueError(
            f"gossip mesh: degree={degree} must be even, >= 2 and < n={n}")
    rng = random.Random(seed)
    peers = np.empty((n, degree), dtype=np.int32)
    taken = np.empty(0, dtype=np.int64)  # undirected edges lo * n + hi

    def keys(a, b):
        return np.minimum(a, b).astype(np.int64) * n + np.maximum(a, b)

    for c in range(degree // 2):
        perm = list(range(n))
        rng.shuffle(perm)
        perm = np.asarray(perm, dtype=np.int64)
        for _ in range(_REPAIR_ROUNDS):
            nxt = np.roll(perm, -1)
            # a 2-cycle (n == 2) cannot occur: degree < n
            bad = np.nonzero(np.isin(keys(perm, nxt), taken))[0]
            if not bad.size:
                break
            for i in bad.tolist():
                j = rng.randrange(n)
                perm[[i, j]] = perm[[j, i]]
        else:
            raise ValueError(
                f"gossip mesh: no simple {degree}-regular graph on {n} "
                f"nodes found from seed {seed}")
        nxt = np.roll(perm, -1)
        taken = np.concatenate([taken, keys(perm, nxt)])
        peers[perm, 2 * c] = nxt
        peers[nxt, 2 * c + 1] = perm
    peers.setflags(write=False)
    return peers


@functools.lru_cache(maxsize=8)
def gossip_publishers(n: int, bursts: int, messages: int,
                      seed: int) -> np.ndarray:
    """Who publishes: ``int32[bursts, messages]``, message ``b * messages
    + j`` is published by node ``[b, j]`` at burst *b*'s instant; the
    publishers of one burst are distinct (a seeded sample)."""
    if messages > n:
        raise ValueError(
            f"gossip: {messages} distinct publishers a burst of {n} nodes")
    rng = random.Random(seed ^ 0x676F7373)
    out = np.asarray(
        [rng.sample(range(n), messages) for _ in range(bursts)],
        dtype=np.int32,
    ).reshape(bursts, messages)
    out.setflags(write=False)
    return out


@register_model("gossip")
class Gossip:
    """``--degree D`` mesh peers a node, ``--mesh-seed S`` (mesh and
    publishers; never the run's seed), ``--bursts T0,T1,...`` publication
    instants (absolute sim times, after the process start), ``--messages
    M`` messages a burst, ``--size B`` datagram size in bytes (IP size
    incl. headers).  One argument list serves every node: each finds its
    own row of the mesh and its own publications by its host id."""

    def __init__(self, degree: int = 8, mesh_seed: int = 1,
                 bursts: tuple[int, ...] = (), messages: int = 1,
                 size: int = 512) -> None:
        self.degree = degree
        self.mesh_seed = mesh_seed
        self.bursts = tuple(bursts)
        self.messages = messages
        self.size = size
        self.seen: set[int] = set()
        #: time of this node's last first delivery (0: none yet)
        self.last_first_ns = 0
        self._pending: list[tuple[int, int]] = []  # (time, message id)
        self._peers: list[int] = []  # this node's row of the mesh

    @classmethod
    def from_args(cls, args: list[str]) -> "Gossip":
        kv = parse_kv_args(
            args, known={"degree", "mesh-seed", "bursts", "messages", "size"})
        bursts = tuple(
            units.parse_time(t) for t in kv.pop("bursts", "").split(",") if t
        )
        return cls(
            degree=int(kv.pop("degree", 8)),
            mesh_seed=int(kv.pop("mesh-seed", 1)),
            bursts=bursts,
            messages=int(kv.pop("messages", 1)),
            size=int(kv.pop("size", 512)),
        )

    def publications(self, host_id: int, num_hosts: int):
        """``[(time, message id), ...]`` of node ``host_id``, in message
        order (what both backends arm, one timer each)."""
        pubs = gossip_publishers(
            num_hosts, len(self.bursts), self.messages, self.mesh_seed)
        b, j = np.nonzero(pubs == host_id)
        return [(self.bursts[bi], int(bi * self.messages + ji))
                for bi, ji in zip(b.tolist(), j.tolist())]

    def _push(self, api: HostApi, m: int, but: int = -1) -> None:
        for peer in self._peers:
            if peer != but:
                api.send(peer, self.size, m)
                api.count("gossip_sends")

    def on_start(self, api: HostApi) -> None:
        self._peers = gossip_mesh(
            api.num_hosts, self.degree, self.mesh_seed)[api.host_id].tolist()
        self._pending = self.publications(api.host_id, api.num_hosts)
        for t, _m in self._pending:
            if t <= api.now:
                raise ValueError(
                    f"gossip: burst at {t} ns is not after the process "
                    f"start ({api.now} ns)")
            api.set_timer(t)

    def on_timer(self, api: HostApi, t: int) -> None:
        # one timer a publication, armed in message order: same-instant
        # timers pop in that order
        _t, m = self._pending.pop(0)
        self.seen.add(m)
        self._push(api, m)

    def on_delivery(self, api: HostApi, t: int, src: int, seq: int, size: int,
                    payload=None) -> None:
        m = payload
        if m in self.seen:
            api.count("gossip_duplicates")
            return
        self.seen.add(m)
        api.count("gossip_first")
        api.count(age_counter(t - self.bursts[m // self.messages]))
        self.last_first_ns = max(self.last_first_ns, t)
        self._push(api, m, but=src)
