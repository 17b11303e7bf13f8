"""Columnar scenario construction — the 100k-host startup path.

The classic factories (presets.py) describe every host as YAML that
``ConfigOptions.from_dict`` expands into per-host ``HostOptions`` objects,
and ``TpuEngine.__init__`` then walks host-by-host, instantiating a model
object per host to fill the per-lane parameter tables.  At 10^5 hosts that
Python loop — not the device program — dominates startup (ROADMAP item 5).

This module replaces both loops with NumPy table construction:

* ``ColumnarSpec`` carries the per-lane model/parameter columns and the
  initial-event table as arrays; ``TpuEngine`` adopts them wholesale
  (``cfg.columnar``) and skips its per-host walk entirely;
* ``ColumnarHosts`` is a lazy ``Sequence[HostOptions]`` — hostname/DNS/
  bandwidth consumers (``backend.setup.build_world``, ``validate``)
  iterate materialized rows on demand, but no 100k-object list is ever
  held, and each group's ``ProcessOptions`` list is shared, so a
  columnar config remains a complete, classic-readable description of
  the same scenario (tests/test_multichip.py pins table equality
  against the classic factory).

Columnar configs are lane-only: the hybrid backend executes real process
objects host-side, which is exactly the per-host work this path deletes.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from ..models.tgen import TgenMesh
from .options import ConfigOptions, HostOptions, ProcessOptions

__all__ = ["ColumnarHosts", "ColumnarSpec", "columnar_mesh_config"]

# lanes.py model/kind constants, restated here to keep this module
# importable without JAX (tests assert they match lanes')
M_TGEN_MESH = 2
EV_LOCAL = 1


@dataclasses.dataclass(frozen=True)
class ColumnarSpec:
    """Per-lane model tables + initial events as columns.

    Model columns (all ``[n]``): ``model``/``p_size``/``p_peer``/
    ``recv_mult`` int32; ``p_interval``/``p_count``/``p_stride``/
    ``local_seq0`` int64.  Event columns (all ``[E]`` int64):
    ``(lane, t, kind, src, seq, size)`` — the exact rows the classic
    per-host walk would have appended to ``init_events``.
    """

    model: np.ndarray
    p_size: np.ndarray
    p_interval: np.ndarray
    p_peer: np.ndarray
    p_count: np.ndarray
    p_stride: np.ndarray
    recv_mult: np.ndarray
    local_seq0: np.ndarray
    ev_lane: np.ndarray
    ev_t: np.ndarray
    ev_kind: np.ndarray
    ev_src: np.ndarray
    ev_seq: np.ndarray
    ev_size: np.ndarray

    def model_columns(self, n: int):
        """The 8 per-lane columns, shape-checked against the host count
        (the order matches TpuEngine.__init__'s local table names)."""
        i32 = {"model", "p_size", "p_peer", "recv_mult"}
        cols = []
        for name in (
            "model", "p_size", "p_interval", "p_peer", "p_count",
            "p_stride", "recv_mult", "local_seq0",
        ):
            a = np.asarray(
                getattr(self, name),
                dtype=np.int32 if name in i32 else np.int64,
            )
            if a.shape != (n,):
                raise ValueError(
                    f"columnar column {name!r} has shape {a.shape}, "
                    f"config has {n} hosts"
                )
            cols.append(a)
        return tuple(cols)

    def event_columns(self):
        """The 6 initial-event columns as int64 arrays."""
        cols = tuple(
            np.asarray(getattr(self, name), dtype=np.int64)
            for name in (
                "ev_lane", "ev_t", "ev_kind", "ev_src", "ev_seq", "ev_size"
            )
        )
        e = cols[0].shape
        for name, a in zip(("ev_t", "ev_kind", "ev_src", "ev_seq",
                            "ev_size"), cols[1:]):
            if a.shape != e:
                raise ValueError(
                    f"columnar event column {name!r} has shape {a.shape}, "
                    f"ev_lane has {e}"
                )
        return cols


class ColumnarHosts(Sequence):
    """Lazy ``HostOptions`` rows for columnar configs.

    ``groups`` is a list of ``(count, prefix, node_id, processes)``; row
    ``i`` of a group materializes as ``HostOptions(hostname=f"{prefix}
    {i+1}", ...)`` on access — the same naming the classic ``count:``
    expansion produces — sharing the group's ``ProcessOptions`` list
    rather than deep-copying it per host."""

    def __init__(self, groups):
        self._groups = []
        base = 0
        for count, prefix, node_id, procs in groups:
            self._groups.append((base, int(count), prefix, node_id, procs))
            base += int(count)
        self._len = base

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._len))]
        if i < 0:
            i += self._len
        if not 0 <= i < self._len:
            raise IndexError(i)
        for base, count, prefix, node_id, procs in self._groups:
            if i < base + count:
                return HostOptions(
                    hostname=f"{prefix}{i - base + 1}",
                    network_node_id=node_id,
                    processes=procs,
                )
        raise IndexError(i)  # pragma: no cover


def columnar_mesh_config(
    n_hosts: int,
    sim_seconds: int = 10,
    latency: str = "10 ms",
    interval: str = "10ms",
    size: int = 1428,
    queue_capacity: int | None = None,
    pops_per_round: int | None = None,
    mesh_devices: int = 0,
    seed: int = 1,
) -> ConfigOptions:
    """The flagship tgen all-to-all mesh (presets.flagship_mesh_config's
    pure-UDP shape) built columnar: same hosts, same tables, same events
    — but O(1) Python objects instead of O(n_hosts).  This is the
    100k-host multi-chip scenario (the benchmark's ``tgen_mesh_100k``);
    ``mesh_devices`` presets ``experimental.mesh_devices``."""
    cfg = ConfigOptions.from_yaml(f"""
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
  network_backend: tpu
hosts:
  placeholder: {{}}
""")
    args = ["--interval", str(interval), "--size", str(size)]
    # ONE model instance parses the args — the per-host loop's source of
    # truth for interval/size/stride stays authoritative
    m = TgenMesh.from_args(list(args))
    procs = [ProcessOptions(path="tgen-mesh", args=args, start_time=0)]
    cfg.hosts = ColumnarHosts([(n_hosts, "peer", 0, procs)])

    n = n_hosts
    hid = np.arange(n, dtype=np.int64)
    cfg.columnar = ColumnarSpec(
        model=np.full(n, M_TGEN_MESH, dtype=np.int32),
        p_size=np.full(n, m.size, dtype=np.int32),
        p_interval=np.full(n, m.interval, dtype=np.int64),
        p_peer=np.zeros(n, dtype=np.int32),
        p_count=np.zeros(n, dtype=np.int64),
        p_stride=np.full(n, m.stride, dtype=np.int64),
        recv_mult=np.ones(n, dtype=np.int32),
        local_seq0=np.ones(n, dtype=np.int64),
        # one LOCAL start marker per host at t=0 (size -1 = timer driver)
        ev_lane=hid,
        ev_t=np.zeros(n, dtype=np.int64),
        ev_kind=np.full(n, EV_LOCAL, dtype=np.int64),
        ev_src=hid,
        ev_seq=np.zeros(n, dtype=np.int64),
        ev_size=np.full(n, -1, dtype=np.int64),
    )
    if queue_capacity is not None:
        cfg.experimental.tpu_lane_queue_capacity = queue_capacity
    if pops_per_round is not None:
        cfg.experimental.tpu_events_per_round = pops_per_round
    if mesh_devices:
        cfg.experimental.mesh_devices = mesh_devices
    return cfg
