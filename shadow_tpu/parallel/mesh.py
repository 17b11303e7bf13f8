"""Device-mesh sharding of host lanes — the multi-chip data plane.

The reference scales by spreading *hosts* over worker threads with work
stealing (scheduler crate, thread_per_core.rs:17-50); the cross-host packet
push is a mutex-guarded queue insert (worker.rs:603-615).  The TPU-native
equivalent: shard the lane axis of the batched simulation state over a
``jax.sharding.Mesh`` axis (``hosts``), keep the routing tables replicated,
and let XLA turn the cross-lane event exchange (the sort → rank → scatter in
``lanes._append_events``) into ICI collectives.  Host-level data parallelism
becomes SPMD data parallelism; the event exchange is the all-to-all.

Sharding law (docs/multichip.md):

* every ``[N]``- or ``[N, C]``-leading LaneState leaf (queues, bucket and
  CoDel state, per-lane counters, the netobs per-host counter block) is
  split on the lane axis — ``NamedSharding(mesh, P("hosts"))``;
* everything else replicates — scalars, the event log (one device-global
  append cursor), the compacted ``[S, F]`` stream tier, the ``[24]`` netobs
  window histogram (shard-then-reduce: per-shard partial sums all-reduce
  into the replicated array), the hybrid egress block, and the flowtrace
  ring;
* the classification is EXHAUSTIVE by construction: ``state_shardings``
  asserts every ``LaneState._fields`` entry is classified exactly once, so
  a future field cannot silently pick up the wrong sharding
  (tests/test_multichip.py plants a fake field to pin this).

Determinism: the sharded program computes the same integer arithmetic and
the same key sorts as the single-device one, so results are bit-identical
regardless of mesh shape (tests/test_parallel.py + test_multichip.py diff
the event logs and NETOBS artifacts at 1/2/4/8 devices).

Fallback semantics: ``negotiate_devices`` never raises — a request that
exceeds the available device count, or that does not divide the lane
count, steps down (with a warning) toward the largest usable mesh, and a
1-device mesh is bypassed entirely by the callers, so every existing
single-device driver keeps working unchanged on any box.
"""

from __future__ import annotations

import functools
import logging
from typing import Iterable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..backend import lanes

log = logging.getLogger("shadow_tpu.parallel")

HOST_AXIS = "hosts"
SCENARIO_AXIS = "scenarios"

# LaneState fields split on the lane axis: per-lane [N]/[N, C] arrays.
LANE_FIELDS = frozenset((
    "q_thi", "q_tlo", "q_auxh", "q_auxl", "q_size", "q_phi", "q_plo",
    "send_seq", "local_seq", "app_draws",
    "up_tokens", "up_nr_hi", "up_nr_lo", "up_ld_hi", "up_ld_lo",
    "dn_tokens", "dn_nr_hi", "dn_nr_lo", "dn_ld_hi", "dn_ld_lo",
    "cd_fat_hi", "cd_fat_lo", "cd_dnext_hi", "cd_dnext_lo",
    "cd_drop_count", "cd_dropping",
    "m_sent", "m_peer_offset", "n_delivered", "n_loss", "n_codel",
    "n_queue", "recv_bytes", "n_sends", "n_hops",
    # netobs per-host counter block (PR 10): [N] int32 counters travel
    # with their lanes; collect() gathers them for the oracle diff
    "nb_txb", "nb_rxb", "nb_thr", "nb_shed",
    # the gossip model's per-lane block (lanes.GossipState: the seen
    # bitmap, three counters, the last first-delivery): every leaf leads
    # with the lane axis
    "gossip",
))

# LaneState fields that replicate.  The stream matrices are COMPACTED per
# flow ([S, F], flow order), not per lane: S is a few hundred rows, so
# they replicate — XLA inserts the collectives for the lane-indexed
# gathers/scatters at the tier boundary.  The netobs [24] histogram and
# the hybrid egress block are device-global append targets written from
# sharded lanes: GSPMD lowers the scatter-adds as shard-then-reduce,
# which is exact for the integer counters they carry.
REPLICATED_FIELDS = frozenset((
    "log", "log_count", "log_lost", "rounds", "iters", "codel_lookup_pops",
    "now_we_hi", "now_we_lo", "min_used_lat", "stream",
    "peaks", "copop_wide_pops", "exchange_compact_iters",
    "exchange_slot_peak", "gossip_age", "gossip_elided",
    "loop_hist", "loop_acc",
    "egress", "egress_count", "egress_lost",
    "egress_min_hi", "egress_min_lo",
    "nb_hist", "nb_win",
    "fl_buf", "fl_count", "fl_lost",
    *lanes._AP_SCALARS,
))


def check_classification(fields: Optional[Iterable[str]] = None) -> None:
    """Assert LANE_FIELDS/REPLICATED_FIELDS form an exact partition of
    ``fields`` (default: the live ``LaneState._fields``).  Raises
    AssertionError naming the offending fields — a new LaneState field
    MUST be classified here before any sharded driver can run."""
    fset = set(lanes.LaneState._fields if fields is None else fields)
    both = LANE_FIELDS & REPLICATED_FIELDS
    if both:
        raise AssertionError(
            f"LaneState fields classified twice in parallel/mesh.py: "
            f"{sorted(both)}"
        )
    missing = fset - LANE_FIELDS - REPLICATED_FIELDS
    if missing:
        raise AssertionError(
            "unclassified LaneState fields (add them to LANE_FIELDS or "
            f"REPLICATED_FIELDS in parallel/mesh.py): {sorted(missing)}"
        )
    stale = (LANE_FIELDS | REPLICATED_FIELDS) - fset
    if stale:
        raise AssertionError(
            "parallel/mesh.py classifies fields LaneState no longer has: "
            f"{sorted(stale)}"
        )


def negotiate_devices(
    requested: Optional[int],
    n_lanes: int,
    available: Optional[int] = None,
) -> int:
    """The transparent-fallback law: the largest usable device count.

    Picks the biggest ``d <= min(requested, available)`` with
    ``n_lanes % d == 0`` — never raises, warns on every step-down — so a
    config asking for 8 chips runs correctly (just narrower) on a
    1-device box or with an odd host count.  ``requested`` of None/0
    means "all available"."""
    avail = len(jax.devices()) if available is None else int(available)
    want = avail if not requested or requested <= 0 else int(requested)
    d = max(1, min(want, avail, max(n_lanes, 1)))
    if d < want:
        log.warning(
            "mesh: %d device(s) requested, %d usable (available=%d, "
            "n_lanes=%d) — falling back", want, d, avail, n_lanes,
        )
    while n_lanes % d:
        d -= 1
    if d < min(want, avail) and n_lanes % min(want, avail):
        log.warning(
            "mesh: n_lanes=%d not divisible by %d device(s); using %d",
            n_lanes, min(want, avail), d,
        )
    return d


def negotiate_from_config(cfg, n_lanes: int) -> int:
    """Device count for a config: ``experimental.mesh_devices`` (0 = no
    mesh, N = shard over up to N devices), negotiated against the
    available device count and the lane count.  Returns 1 when no
    multi-device mesh applies (the callers skip attach entirely)."""
    requested = int(cfg.experimental.mesh_devices or 0)
    if requested <= 1:
        return 1
    return negotiate_devices(requested, n_lanes)


def make_mesh(n_devices: Optional[int] = None, axis: str = HOST_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def state_shardings(mesh: Mesh, axis: str = HOST_AXIS) -> lanes.LaneState:
    """A LaneState-shaped pytree of NamedShardings: per-lane arrays split
    on the lane axis, the event log and scalars replicated.  Exhaustive
    over the live field list (see check_classification)."""
    check_classification()
    lane = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    return lanes.LaneState(
        **{
            f: (repl if f in REPLICATED_FIELDS else lane)
            for f in lanes.LaneState._fields
        }
    )


def shard_state(
    s: lanes.LaneState, mesh: Mesh, axis: str = HOST_AXIS
) -> lanes.LaneState:
    n_lanes = s.q_thi.shape[0]
    if n_lanes % mesh.devices.size:
        raise ValueError(
            f"n_lanes={n_lanes} not divisible by mesh size {mesh.devices.size}"
        )
    return jax.device_put(s, state_shardings(mesh, axis))


def _spmd_entry(fn):
    """Wrap a jitted sharded entry point so ``lanes._force_unroll`` is
    live whenever it runs: jit traces on first CALL, and the traced body
    must take the unrolled slot walk (its emits stack [K, N] on the lane
    axis) — GSPMD cannot partition lax.scan's stacked-output updates on
    the lane-sharded axis under x64 (s64 index vs s32 shard-offset
    compare, rejected by the HLO verifier).  The per-flow stream walks
    keep their scan form — their stacks replicate (see
    ``lanes.scan_or_unroll``).  Post-trace calls pay one bool flip."""

    @functools.wraps(fn)
    def wrapped(*args):
        with lanes._force_unroll():
            return fn(*args)

    def lower(*args, **kwargs):
        # AOT path (precompile benches): lowering traces too
        with lanes._force_unroll():
            return fn.lower(*args, **kwargs)

    wrapped.lower = lower
    return wrapped


def make_sharded_round_fn(
    p: lanes.LaneParams, tb: lanes.LaneTables, mesh: Mesh, axis: str = HOST_AXIS
):
    """Jitted one-round advance, lane axis sharded over ``mesh`` (the
    step driver's kernel: pausable, host-visible state per boundary — no
    donation, checkpointing re-reads the input state)."""
    sh = state_shardings(mesh, axis)
    return _spmd_entry(jax.jit(
        lanes._build_round(p, tb),
        in_shardings=(sh,),
        out_shardings=(sh, NamedSharding(mesh, P())),
    ))


def make_sharded_run_fn(
    p: lanes.LaneParams, tb: lanes.LaneTables, mesh: Mesh, axis: str = HOST_AXIS
):
    """Jitted full-simulation run (while_loop over rounds), sharded.  No
    donation: the argument is the initial state its engine keeps on the
    device and starts every run from (``TpuEngine._start_state``), so a
    run holds that state and its result, as a single-device run does."""
    sh = state_shardings(mesh, axis)
    return _spmd_entry(jax.jit(
        lanes._build_full_run(p, tb), in_shardings=(sh,), out_shardings=sh
    ))


def make_sharded_hybrid_fns(
    p: lanes.LaneParams,
    tb: lanes.LaneTables,
    mesh: Mesh,
    fuse_k: int,
    ext_slots: int,
    axis: str = HOST_AXIS,
):
    """The hybrid backend's device entry points compiled under ``mesh``:
    ``(turn_fn, inject_fn)`` with the lane state sharded on the host axis
    and everything at the host<->device boundary — the turn's one block
    (``lanes.TurnBlock``), the packed readback with its egress head, and
    the (replicated) egress buffer — placed whole on every shard, so the
    one-copy-in, one-read-out law and the sync_stats byte accounting are
    unchanged by sharding (tests/test_multichip.py pins the counts).

    No donation: the fused walk's rollback re-dispatches from the
    pre-turn state, which must therefore survive the call."""
    sh = state_shardings(mesh, axis)
    repl = NamedSharding(mesh, P())

    inject_fn = _spmd_entry(jax.jit(
        lanes._build_inject(p, tb), in_shardings=(sh, repl),
        out_shardings=sh,
    ))
    turn_fn = _spmd_entry(jax.jit(
        lanes._build_hybrid_fused_run(p, tb, fuse_k, ext_slots),
        in_shardings=(sh, repl),
        out_shardings=(sh, repl),
    ))
    return turn_fn, inject_fn


def scenario_sharding(mesh: Mesh, axis: str = SCENARIO_AXIS) -> NamedSharding:
    """The sweep composition (ROADMAP item 4 × item 2): when
    hosts-per-scenario is small, shard the STACKED scenario axis instead
    of the host axis — every stacked sweep leaf (state, tables, stop
    bounds) leads with [S], so one NamedSharding broadcast over the
    pytrees splits whole scenarios across devices."""
    return NamedSharding(mesh, P(axis))
