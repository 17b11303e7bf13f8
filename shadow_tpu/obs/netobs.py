"""Simulated-network telemetry plane (docs/observability.md).

PR 9 instrumented the *engine* (phase walls, METRICS_*.json); this module
observes the *simulation content*: what the simulated network did.  The
reference fork ships the same layer as its host tracker / heartbeat
counters (interface.rs, utility/pcap_writer.rs) and per-window perf
logging (manager.rs / host.rs); here it is a per-host counter catalog
with **drop-cause accounting** and a **burst-window histogram**:

- per host: packets ``sent`` / ``delivered``, bytes by direction
  (``tx_bytes`` / ``rx_bytes``), drops by cause (``loss`` — the
  Bernoulli link table, ``codel`` — the CoDel law's drop decision,
  ``queue`` — lane-queue overflow, ``cross_shed`` — exchange-width shed
  (both device-only: the CPU oracle's queues are unbounded),
  ``retry_giveup`` — lTCP MAX_RTO_BACKOFFS abandonment), token-bucket
  ``throttled`` events (charges that had to wait for a refill — the
  bucket never drops, so throttle is a deferral cause, not a loss), and
  ``retransmits`` (completed stream flows, the CPU ``_track`` law);
- per run: a fixed-bucket histogram of per-window PACKET-arrival
  occupancy (bucket b = windows whose popped packet count has
  floor(log2) == b; packet-free windows are skipped) — the burst
  evidence ROADMAP open item 3 asks for.  Packets only, because wire
  arrivals are the one event class whose per-window counts are
  bit-identical across backends (LOCAL/DELIVERY decomposition differs:
  start anchors, delivery elision).

The device side accumulates the identical counters inside the lane
kernels (``backend/lanes.py``, ``LaneParams.netobs``) with **zero new
host↔device transfers**: counters stay device-resident and are fetched
only at run-control snapshot epochs and end-of-run, piggybacking the
existing collect readback.  The CPU oracle accumulates them in plain
Python through this module's :class:`NetObs`, so a parity gate can
assert device == oracle per counter per host (tests/test_telemetry.py).

The ``NETOBS_<backend>-seed<N>.json`` artifact is written through the
PR 9 Recorder lifecycle (engine/sim.py) and is **integer-only** — no
wall-clock values — so run-twice artifacts diff byte-identical (the
determinism contract of docs/determinism.md).
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Optional

import numpy as np

SCHEMA_VERSION = 1

#: must match backend.lanes.NB_HIST_BUCKETS (imported there would cycle)
HIST_BUCKETS = 24

#: the canonical per-host counter catalog, in report order
COUNTERS = (
    "sent",
    "delivered",
    "tx_bytes",
    "rx_bytes",
    "drop_loss",
    "drop_codel",
    "drop_queue",
    "drop_cross_shed",
    "throttled",
    "retransmits",
    "retry_giveup",
)

#: the drop-cause classification (docs/observability.md)
DROP_CAUSES = ("loss", "codel", "queue", "cross_shed", "retry_giveup")

TOP_TALKERS = 10
#: per-host breakdown is embedded only up to this host count (top
#: talkers and totals carry the signal at larger scales)
PER_HOST_CAP = 1024


def hist_bucket(count: int) -> int:
    """floor(log2(count)) clamped to the fixed bucket range (count >= 1).
    The identical law to the device's ``ilog2_i32`` path."""
    return min(max(int(count), 1).bit_length() - 1, HIST_BUCKETS - 1)


def hist_percentile(buckets, q: float, ceiling: Optional[int] = None) -> int:
    """The ``q``-quantile (nearest rank) of the counts a log2 histogram
    holds, read as its bucket's UPPER edge — bucket ``b`` holds counts in
    ``[2**b, 2**(b + 1))``, so ``2**(b + 1) - 1`` — clipped to ``ceiling``
    where the largest count is known (the last bucket, which takes the
    tail, has no edge of its own).  0 for an empty histogram."""
    total = sum(int(v) for v in buckets)
    if not total:
        return 0
    rank = max(1, math.ceil(q * total - 1e-9))
    seen = 0
    for b, v in enumerate(buckets):
        seen += int(v)
        if seen >= rank:
            break
    edge = (1 << (b + 1)) - 1
    return edge if ceiling is None else min(edge, int(ceiling))


def empty_arrays(n_hosts: int) -> dict[str, np.ndarray]:
    """A fresh all-zero counter-array schema."""
    return {k: np.zeros(n_hosts, dtype=np.int64) for k in COUNTERS}


class NetObs:
    """Host-side (oracle) accumulator of the per-host counters and the
    window histogram.

    Thread-safety by ownership, matching the engines' execution model:
    every array row is written only by the thread executing that host
    (sends touch the source row from the source host's thread, arrivals
    the destination row from the destination host's thread), and the
    window flush runs on the round loop after the barrier.  No locks on
    the hot path."""

    def __init__(self, n_hosts: int) -> None:
        self.n_hosts = n_hosts
        self.sent = np.zeros(n_hosts, dtype=np.int64)
        self.delivered = np.zeros(n_hosts, dtype=np.int64)
        self.tx_bytes = np.zeros(n_hosts, dtype=np.int64)
        self.rx_bytes = np.zeros(n_hosts, dtype=np.int64)
        self.drop_loss = np.zeros(n_hosts, dtype=np.int64)
        self.drop_codel = np.zeros(n_hosts, dtype=np.int64)
        # PACKET pops per host (cumulative); the round flush sums the
        # delta into the window histogram
        self.pops = np.zeros(n_hosts, dtype=np.int64)
        self.window_hist = np.zeros(HIST_BUCKETS, dtype=np.int64)
        self._pops_taken = 0

    # -- hot-path hooks (each touches one thread-owned row) ----------------

    def on_send(self, src: int, size_bytes: int) -> None:
        self.sent[src] += 1
        self.tx_bytes[src] += size_bytes

    def on_loss(self, src: int) -> None:
        self.drop_loss[src] += 1

    def on_delivered(self, dst: int, size_bytes: int) -> None:
        self.delivered[dst] += 1
        self.rx_bytes[dst] += size_bytes

    def on_codel(self, dst: int) -> None:
        self.drop_codel[dst] += 1

    # -- window flush (round loop, post-barrier) ---------------------------

    def take_round_pops(self) -> int:
        """Pops since the last take — a multiprocess worker ships this
        in its round reply so the parent can flush the global window."""
        total = int(self.pops.sum())
        delta = total - self._pops_taken
        self._pops_taken = total
        return delta

    def flush_window(self, count: Optional[int] = None) -> None:
        """Fold one finished window's event occupancy into the histogram
        (``count=None`` = this accumulator's own pop delta)."""
        if count is None:
            count = self.take_round_pops()
        if count > 0:
            self.window_hist[hist_bucket(count)] += 1

    # -- snapshot ----------------------------------------------------------

    def base_arrays(self) -> dict[str, np.ndarray]:
        """The accumulator's counters in the canonical schema (copies).
        Engine snapshots fill the remaining keys (``throttled`` from the
        token buckets, ``retransmits``/``retry_giveup`` from host
        counters, queue/shed from the device side)."""
        arrays = empty_arrays(self.n_hosts)
        arrays["sent"] = self.sent.copy()
        arrays["delivered"] = self.delivered.copy()
        arrays["tx_bytes"] = self.tx_bytes.copy()
        arrays["rx_bytes"] = self.rx_bytes.copy()
        arrays["drop_loss"] = self.drop_loss.copy()
        arrays["drop_codel"] = self.drop_codel.copy()
        return arrays


def merge_arrays(
    into: dict[str, np.ndarray], other: dict[str, np.ndarray]
) -> dict[str, np.ndarray]:
    """Elementwise-sum ``other`` into ``into`` (schema keys only)."""
    for k in COUNTERS:
        if k in other:
            into[k] = into[k] + np.asarray(other[k], dtype=np.int64)
    return into


def totals(arrays: dict[str, np.ndarray]) -> dict[str, int]:
    return {k: int(arrays[k].sum()) for k in COUNTERS}


def build_report(
    run_id: str,
    backend: str,
    seed: int,
    hostnames: list[str],
    arrays: dict[str, np.ndarray],
    window_hist,
    host_window_hist=None,
    log_lost: int = 0,
    extra: Optional[dict] = None,
) -> dict:
    """The NETOBS document (schema in docs/observability.md).  Integer
    content only, deterministic ordering — run-twice artifacts must diff
    byte-identical."""
    n = len(hostnames)
    tot = totals(arrays)
    drops = {
        "loss": tot["drop_loss"],
        "codel": tot["drop_codel"],
        "queue": tot["drop_queue"],
        "cross_shed": tot["drop_cross_shed"],
        "retry_giveup": tot["retry_giveup"],
    }
    hist = [int(v) for v in np.asarray(window_hist)]
    # top talkers: most tx bytes, then most packets, host id breaks ties
    order = sorted(
        range(n),
        key=lambda i: (
            -int(arrays["tx_bytes"][i]), -int(arrays["sent"][i]), i
        ),
    )
    talkers = [
        {
            "host": hostnames[i],
            "sent": int(arrays["sent"][i]),
            "tx_bytes": int(arrays["tx_bytes"][i]),
            "delivered": int(arrays["delivered"][i]),
            "rx_bytes": int(arrays["rx_bytes"][i]),
        }
        for i in order[:TOP_TALKERS]
        if int(arrays["sent"][i]) or int(arrays["tx_bytes"][i])
    ]
    wire_drops = (
        tot["drop_loss"] + tot["drop_codel"] + tot["drop_queue"]
        + tot["drop_cross_shed"]
    )
    doc: dict = {
        "schema": SCHEMA_VERSION,
        "run_id": run_id,
        "backend": backend,
        "seed": int(seed),
        "num_hosts": n,
        "totals": tot,
        "drops_by_cause": drops,
        "drop_total": sum(drops.values()),
        # conservation: sent == delivered + wire drops + in flight at
        # stop_time (packets whose arrival lies past the end of the run)
        "in_flight": tot["sent"] - tot["delivered"] - wire_drops,
        "log_lost": int(log_lost),
        "window_hist": {
            "scheme": "log2-packet-arrivals",
            "buckets": hist,
            "windows": sum(hist),
        },
        "top_talkers": talkers,
    }
    if host_window_hist is not None:
        hh = [int(v) for v in np.asarray(host_window_hist)]
        doc["host_window_hist"] = {
            "scheme": "log2-packet-arrivals",
            "buckets": hh,
            "windows": sum(hh),
        }
    if n <= PER_HOST_CAP:
        doc["per_host"] = {
            hostnames[i]: {k: int(arrays[k][i]) for k in COUNTERS}
            for i in range(n)
        }
    if extra:
        doc.update(extra)
    return doc


def write_report(path: str | Path, report: dict) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return path


def snapshot_lines(
    arrays: dict[str, np.ndarray],
    window_hist,
    hostnames: list[str],
    host: Optional[str] = None,
) -> list[str]:
    """Human-readable snapshot (the run-control ``netstats`` verb)."""
    tot = totals(arrays)
    lines = [
        "net totals: "
        + " ".join(f"{k}={tot[k]}" for k in (
            "sent", "delivered", "tx_bytes", "rx_bytes"))
    ]
    lines.append(
        "drops: "
        + " ".join(f"{k}={tot[k]}" for k in (
            "drop_loss", "drop_codel", "drop_queue", "drop_cross_shed",
            "retry_giveup"))
        + f" throttled={tot['throttled']} retransmits={tot['retransmits']}"
    )
    hist = [int(v) for v in np.asarray(window_hist)]
    top = max((i for i, v in enumerate(hist) if v), default=-1)
    lines.append(
        "window hist (log2 packet arrivals): "
        + (" ".join(f"b{i}={hist[i]}" for i in range(top + 1))
           if top >= 0 else "no windows yet")
    )
    if host is not None:
        if host not in hostnames:
            lines.append(f"unknown host {host!r}")
        else:
            i = hostnames.index(host)
            lines.append(
                f"{host}: "
                + " ".join(f"{k}={int(arrays[k][i])}" for k in COUNTERS)
            )
    return lines
