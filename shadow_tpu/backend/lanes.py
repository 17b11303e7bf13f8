"""TPU lane backend: the batched JAX implementation of docs/SEMANTICS.md.

One **lane per simulated host**.  All per-host state lives in ``[N]`` or
``[N, C]`` device arrays; a simulation round advances every lane over the
conservative lookahead window in one XLA program, and the whole simulation
runs as a ``lax.while_loop`` over rounds without leaving the device.

Replaces the reference's packet-scheduling hot path — ``Worker::send_packet``
(worker.rs:330-404), the router CoDel queues (router/codel_queue.rs), the
relay token buckets (relay/token_bucket.rs), and the per-host event queues
(event_queue.rs) — with:

- per-lane event queues: ``[N, C]`` arrays kept key-sorted by ``lax.sort``
  (the binary heap's batched equivalent).  The event key ``(time, kind,
  src, seq)`` is RESIDENT as four order-preserving int32 words
  (``t_split``/``pack_aux_hi``): TPU has no native int64 — every i64 op
  lowers to unfusable X64 custom calls — so the whole sort/merge/pop
  pipeline stays on plain int32 lanes and only the slot arithmetic
  touches int64, through one join at the pop boundary;
- the latency/loss lookup as gathers into the dense ``[G, G]`` tables from
  ``net.graph``;
- Bernoulli loss via the counter-based threefry streams of ``core.rng``
  (bit-identical to the CPU reference);
- token bucket + CoDel as masked integer vector arithmetic (identical
  update laws to ``net.token_bucket`` / ``net.codel``);
- cross-lane packet exchange as a single-key sort by destination →
  segment bounds from a one-hot histogram matmul + cumsum (no
  data-dependent control flow) → an aligned row-gather + barrel shift
  into a lane-aligned block (the shared-memory queue push's batched
  equivalent; under a sharded mesh the exchange rides XLA collectives).
  Same-lane insertions (delivery self-inserts, timer re-arms) skip the
  exchange: they are lane-aligned blocks already;
- appends by **merge, not scatter** (TPU scatters serialize): one row sort
  of ``[old queue | same-lane inserts | cross block]`` keeps the first C
  keys per lane.

Determinism: every quantity is integer, every draw is counter-based, and
event ordering is the same ``(time, kind, src, seq)`` total order — the
event logs of this backend and the CPU reference diff equal.  Queue rows
are maintained **sorted by (time, aux) as an invariant** (established by
``TpuEngine.initial_state``, preserved by the merge — or by the explicit
re-sort on iterations that skip it), so the pop phase is a plain slice of
the first K columns.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import rng as rng_mod
from ..core import time as stime
from ..models.gossip import AGE_EDGES_NS
from ..net import codel as codel_mod
from ..net.token_bucket import DEFAULT_INTERVAL_NS, FRAME_OVERHEAD_BYTES
from ..obs import flowtrace as ftr
from . import lanes_pairs as _pairs
from . import lanes_stream as lstr

# event kinds (must match core.event.EventKind)
PACKET, LOCAL, DELIVERY = 0, 1, 2
# outcomes (must match backend.cpu_engine)
DELIVERED, DROP_LOSS, DROP_CODEL, DROP_QUEUE = 0, 1, 2, 3
# device-log record class that is NOT an event outcome: an outbound pcap
# capture at bucket-departure time (cpu_engine captures the same instant);
# collect() splits these into per-host capture files
PCAP_TX = 4

NEVER = stime.NEVER

# lane-supported app models
(M_NONE, M_PHOLD, M_TGEN_MESH, M_TGEN_CLIENT, M_TGEN_SERVER, M_PING_CLIENT,
 M_PING_SERVER, M_STREAM_CLIENT, M_STREAM_SERVER, M_GOSSIP) = range(10)

# models whose delivery handling is PASSIVE (counters only — no sends, no
# timers): their DELIVERY events are elided and applied inline at packet
# arrival, exactly like the CPU engine's passive-delivery fast path; both
# backends elide identically so event logs stay bit-identical
PASSIVE_MODELS = frozenset({M_NONE, M_TGEN_MESH, M_TGEN_CLIENT, M_TGEN_SERVER})
STREAM_MODELS = frozenset({M_STREAM_CLIENT, M_STREAM_SERVER})
# models whose sends GATHER their path: every model of the [N] send channel
# whose destination is picked at RUN time (a draw, an echo's source, a
# round-robin offset; the client models' fixed ``p_peer`` gathers too,
# PERF.md §7).  On a graph of G > 1 nodes such a send reads the pair's two
# packed words by ONE flat index, ``node_of[lane] * G + node_of[dst]``
# (``LaneTables.flat_lat`` / ``flat_thresh``).  A gossip lane's D mesh
# peers are static, and so is their path: rows of ``LaneTables.g_lat``
PATH_GATHER_MODELS = frozenset({
    M_PHOLD, M_TGEN_MESH, M_TGEN_CLIENT, M_PING_CLIENT, M_PING_SERVER})
# active DATAGRAM models whose DELIVERY handler is WINDOW-INERT, the static
# property the pop phase (pop_mask) widens an active lane's co-pop on.
# Three points, each read off _process_slot:
# (i)   it inserts no event on its own lane: the handler's one effect is a
#       send (``del_send_phold`` -> ``do_send``), which leaves through the
#       ``out_*`` channel and the exchange (phold draws its peer from the
#       other n - 1; a lone host's send to itself lands by (ii)); the only
#       self-insert of a slot is the ``ins_*`` DELIVERY of a PACKET pop,
#       and the model arms no timer;
# (ii)  everything it sends lands at or after the window's end:
#       ``arr = pair_max(dep + lat, we)``;
# (iii) it reads and writes no word a PACKET pop reads or writes: a
#       DELIVERY pop touches ``n_hops``, ``app_draws``, ``send_seq``,
#       ``n_sends``, the UP bucket (and ``n_loss``, ``min_used_lat``); a
#       PACKET pop the DOWN bucket, CoDel, ``n_delivered`` / ``n_codel``
#       (the netobs counters both add to commute).
# A model joins only with these three points argued from its handler and
# tests/test_lane_parity.py green (M_PING_SERVER, an echo, is the
# candidate); the stream models arm timers and keep their own rule.
#
# M_GOSSIP (models/gossip.py), point by point:
# (i)   its DELIVERY handler tests and sets one bit of the lane's seen
#       bitmap, bumps the lane's gossip counters and sends up to D
#       datagrams to its mesh peers, none of them itself (the mesh is
#       simple: no self-loop), all through the ``out_*`` channel; it arms
#       no timer (the publish timers are in the queue from the start);
# (ii)  every one of the D sends lands at ``pair_max(dep + lat, we)``;
# (iii) a DELIVERY pop touches the seen bitmap, the gossip counters,
#       ``send_seq``, ``n_sends``, the UP bucket, ``n_loss`` and
#       ``min_used_lat``.  A PACKET pop WRITES none of them but the
#       duplicate counter (adds commute) and READS one: the seen bitmap,
#       for the in-window duplicate elision (``gossip_elides``).  That
#       read is argued apart, below: the one reordering the rule allows
#       can only make it stale, and a stale read changes no output.
#
# In-window duplicate elision (the PACKET branch of _process_slot): where
# a gossip lane's PACKET pop passes CoDel, its message id's bit is ALREADY
# set in the lane's seen bitmap, and ``t_deliver`` is before the window's
# end (``we``: the bound pop_mask cuts every class at, the oracle's
# ``ev.time < until``), the pop counts the duplicate there and then and
# self-inserts NO DELIVERY row; everything else of the pop (down bucket,
# CoDel, ``n_delivered``, the DELIVERED record at ``t_deliver``) is what
# it was.  The oracle keeps the event; no compared output can tell:
# (1) the bitmap is monotone: bits are only set, and only by a DELIVERY
#     or a publish pop;
# (2) pops take prefixes of a key-sorted row and a cross-lane send lands
#     at or after the window's end (ii), so a bit a PACKET pop observes
#     was set by a pop whose key is below the PACKET's, hence below the
#     key of the PACKET's own DELIVERY (``t_deliver`` >= arrival, and
#     PACKET < DELIVERY at equal times): the oracle's heap pops that first
#     copy before this copy's delivery;
# (3) so the oracle's handler finds this copy a duplicate, and a
#     duplicate's handler counts ``gossip_duplicates`` and does nothing
#     else (models/gossip.py ``on_delivery``): no send, no bit, no age;
# (4) the one reordering the rule allows — [P_a, P_b] co-popped before
#     P_a's DELIVERY exists — makes P_b's view of the bitmap STALE, never
#     early: a stale view misses an elision (the copy is queued as
#     before), it cannot invent one; the ``dups`` adds commute;
# (5) the DELIVERY not queued would have popped inside this window (the
#     second gate), so the window's end, the next window's start,
#     ``rounds`` and the stop bound see nothing — without the gate the
#     log and the counters would still be the oracle's and the rounds
#     come out short (tests/test_gossip_mesh.py (l) holds both).
# Not elided: a copy whose first copy's DELIVERY is still PENDING — ties
# in ``t_deliver`` broken by (src, seq) decide WHICH copy is first there,
# and the first copy's source is observable (the peer left out of the
# forward).  ``LaneState.gossip_elided`` counts the rows not queued.
#
# What is NEW with gossip is that the ORDER of two DELIVERY pops of one
# lane is observable (the first copy of a message is the one forwarded,
# and its source the one peer left out; PHOLD's handler ignores both).
# The rule never reorders DELIVERY pops: the slot walk runs the
# co-popped columns in key order, the oracle's heap order, and the one
# shape in which a DELIVERY not yet in the row could sort below a
# co-popped one — [P, D'], P's own DELIVERY tying D' in time — is the
# shape pop_mask refuses.  tests/test_gossip_mesh.py holds the event
# log, the counters and the rounds to the oracle's with the rule on.
WINDOW_INERT_MODELS = frozenset({M_PHOLD, M_GOSSIP})

# LOCAL size marker: a non-driving process's start event on a
# multi-process lane host — anchors the window like any start, drives
# nothing (the driver's start is -1)
SZ_ANCHOR = -5

# ---- event key representation ---------------------------------------------
# TPU has no native int64 (every i64 op lowers to X64Split/Combine custom
# calls that cannot fuse, fragmenting the while body into hundreds of tiny
# kernels — what that fragmentation costs per launch is unmeasured on the
# attached chip), so the RESIDENT event key is four int32 words whose lexicographic order is
# the (time, kind, src, seq) total order:
#
#   (t_hi, t_lo)     = (time >> 31, time & 0x7FFFFFFF)  — absolute sim ns;
#                      NEVER maps to (NEVER32, NEVER32)
#   (aux_hi, aux_lo) = (kind << 29 | src << 12, seq)
#
# src < 2**17 lanes (engine-guarded); seq < 2**31 events per source (the
# engine checks the final counters — 2e9 events per lane is unreachable).
# This matches the round-1 int64 packing split at bit 32 with the 44-bit
# seq's high bits always zero, so the event TOTAL ORDER is unchanged and
# event logs stay bit-identical.
AUX_SRC_BITS = 17
AUX_SRC_SHIFT = 12
AUX_KIND_SHIFT = AUX_SRC_SHIFT + AUX_SRC_BITS
MAX_LANES = 1 << AUX_SRC_BITS
_SRC_MASK = (1 << AUX_SRC_BITS) - 1

NEVER32 = _pairs.NEVER32
MASK31 = _pairs.MASK31
MOD_SMALL_LIMIT = _pairs.MOD_SMALL_LIMIT

# netobs (obs/netobs.py): fixed bucket count of the per-window
# PACKET-arrival histogram — bucket b holds windows whose popped packet
# count has floor(log2(count)) == b, the last bucket absorbs the tail.
# Must match obs.netobs.HIST_BUCKETS (import would cycle).
NB_HIST_BUCKETS = 24

# the loop ledger (``LaneState.loop_hist`` / ``loop_acc``): what the
# loop's iterations and windows held — see ``LoopAcc``
_I32_MAX = (1 << 31) - 1

# pair arithmetic helpers (shared with the stream tier — lanes_pairs.py)
pair_lt = _pairs.pair_lt
pair_ge = _pairs.pair_ge
pair_min_lanes = _pairs.pair_min_lanes
pair_add32 = _pairs.pair_add32
pair_sub32 = _pairs.pair_sub32
pair_add_pair = _pairs.pair_add_pair
pair_max = _pairs.pair_max
pair_sel = _pairs.pair_sel
pair_sub_clamp = _pairs.pair_sub_clamp
pair_sub_pair = _pairs.pair_sub_pair
pair_abs_diff = _pairs.pair_abs_diff
pair_div_pow2 = _pairs.pair_div_pow2
pair_mul_small = _pairs.pair_mul_small
pair_mod_small = _pairs.pair_mod_small


def pack_aux_hi(kind, src):
    """The (kind, src) high word of the packed key (seq rides aux_lo)."""
    i32 = jnp.int32
    return (jnp.asarray(kind).astype(i32) << AUX_KIND_SHIFT) | (
        jnp.asarray(src).astype(i32) << AUX_SRC_SHIFT
    )


def unpack_aux_hi(aux_hi):
    kind = (aux_hi >> AUX_KIND_SHIFT).astype(jnp.int32)
    src = ((aux_hi >> AUX_SRC_SHIFT) & _SRC_MASK).astype(jnp.int32)
    return kind, src


# int32 pair arithmetic: value = hi * 2**31 + lo with lo in [0, 2**31).
# All ops fuse (plain int32 lanes), unlike emulated int64.


def t_split(t):
    """Absolute int64 ns -> (hi, lo) int32 pair; NEVER -> (NEVER32, NEVER32).
    Exact for every 0 <= t < 2**62."""
    never = t == NEVER
    hi = jnp.where(never, NEVER32, t >> 31).astype(jnp.int32)
    lo = jnp.where(never, NEVER32, t & MASK31).astype(jnp.int32)
    return hi, lo


def t_join(hi, lo):
    """Inverse of t_split (hi == NEVER32 alone marks NEVER: a real event
    cannot reach 2**62 ns)."""
    t = (hi.astype(jnp.int64) << 31) | lo.astype(jnp.int64)
    return jnp.where(hi == NEVER32, NEVER, t)


def split64(v):
    """Non-negative int64 -> (hi, lo) int32 pair (no NEVER handling)."""
    return (v >> 31).astype(jnp.int32), (v & MASK31).astype(jnp.int32)


#: a queue row's words, in the order every sort, gather and the packed
#: carry holds them: the 4-word key, the size, then as many of the payload
#: words as the models present use (``LaneParams.payload_words``, counted
#: from the back: one word is ``plo``).  ``LaneState`` holds word ``w`` as
#: ``q_<w>``
ROW_WORDS = ("thi", "tlo", "auxh", "auxl", "size")
PAY_WORDS = ("phi", "plo")


def pay_words(count: int) -> tuple:
    """The names of a row's ``count`` payload words."""
    return PAY_WORDS[len(PAY_WORDS) - count:]


class LaneState(NamedTuple):
    """The full device-resident simulation state (a pytree of arrays)."""

    # event queues [N, C]: int32 key words (see the representation note
    # above); (NEVER32, NEVER32) time pair = empty slot
    q_thi: jnp.ndarray  # int32 time hi
    q_tlo: jnp.ndarray  # int32 time lo
    q_auxh: jnp.ndarray  # int32 kind<<29 | src<<12
    q_auxl: jnp.ndarray  # int32 seq
    q_size: jnp.ndarray  # int32
    # opaque payload words (stream lanes: flags<<26|seq, ack — see
    # lanes_stream.pack_pay; gossip: the message id, in ``q_plo`` alone).
    # ``LaneParams.payload_words`` says how many a row carries: both, only
    # ``q_plo`` (``q_phi`` is ``()``), or neither (both ``()``)
    q_phi: jnp.ndarray  # int32
    q_plo: jnp.ndarray  # int32
    # per-lane counters [N] — int32 throughout (the engine checks for
    # wrap at readback: every counter is monotone, so a final negative
    # value flags > 2**31 increments)
    send_seq: jnp.ndarray  # int32
    local_seq: jnp.ndarray  # int32
    app_draws: jnp.ndarray  # int32
    # token buckets [N]: token counts int32; time-ish state as int32 pairs
    up_tokens: jnp.ndarray  # int32 bits
    up_nr_hi: jnp.ndarray  # int32 pair: next_refill
    up_nr_lo: jnp.ndarray
    up_ld_hi: jnp.ndarray  # int32 pair: last_depart
    up_ld_lo: jnp.ndarray
    dn_tokens: jnp.ndarray
    dn_nr_hi: jnp.ndarray
    dn_nr_lo: jnp.ndarray
    dn_ld_hi: jnp.ndarray
    dn_ld_lo: jnp.ndarray
    # CoDel [N]: first_above/drop_next as int32 pairs (hi == CD_UNSET
    # marks "not above" — the int64 law's time-0 sentinel)
    cd_fat_hi: jnp.ndarray
    cd_fat_lo: jnp.ndarray
    cd_dnext_hi: jnp.ndarray
    cd_dnext_lo: jnp.ndarray
    cd_drop_count: jnp.ndarray  # int32
    cd_dropping: jnp.ndarray  # bool
    # app state [N]
    m_sent: jnp.ndarray  # int32 (ping/tgen-client messages sent)
    m_peer_offset: jnp.ndarray  # int32 (tgen-mesh RR cursor)
    # stats [N] int32
    n_delivered: jnp.ndarray
    n_loss: jnp.ndarray
    n_codel: jnp.ndarray
    n_queue: jnp.ndarray
    recv_bytes: jnp.ndarray
    n_sends: jnp.ndarray
    n_hops: jnp.ndarray  # app-processed deliveries (phold hop count)
    # event log [L, 6] + count (L may be 0 = logging off)
    log: jnp.ndarray  # int64 (time, src, dst, seq, size, outcome)
    log_count: jnp.ndarray  # int32 scalar
    log_lost: jnp.ndarray  # int32 scalar: records dropped on log overflow
    # stream tier (lanes_stream.StreamState columns; () when unused)
    stream: Any
    # round bookkeeping (scalars)
    rounds: jnp.ndarray  # int32
    iters: jnp.ndarray  # int32: while-loop iterations (perf visibility)
    # int32: pops (of either tier) in which some lane took CoDel's
    # dropping-branch table lookup (codel_offer_arrays' ``looked``); read
    # into collect()'s ``lane_plane``, never into the counters
    codel_lookup_pops: jnp.ndarray
    now_we_hi: jnp.ndarray  # int32 pair: current round's window end
    now_we_lo: jnp.ndarray
    min_used_lat: jnp.ndarray  # int32 scalar: smallest latency sent over
                               # so far (NEVER32 = none; dynamic runahead)
    # hybrid-backend egress: deliveries to EXTERNAL (host-executed) lanes
    # leave the device through this buffer instead of becoming DELIVERY
    # events — [E, 6] int64 rows (t_deliver, src, dst, seq, size, 0) plus
    # count/lost and the min pending delivery time as an int32 pair (the
    # free-run guard).  () on non-hybrid runs.
    egress: Any = ()
    egress_count: Any = ()
    egress_lost: Any = ()
    egress_min_hi: Any = ()
    egress_min_lo: Any = ()
    # netobs telemetry block (LaneParams.netobs; obs/netobs.py): per-lane
    # int32 counters updated inside the already-traced kernels — bytes by
    # direction, token-bucket throttle events, cross-block sheds — plus
    # the device-resident per-window packet-arrival histogram and its
    # running window count.  () when netobs is off: the off path traces
    # ZERO extra ops (every update is behind `if p.netobs`), so the
    # compiled program is identical to a pre-netobs build.
    nb_txb: Any = ()  # [N] int32: bytes offered to the up bucket (sends)
    nb_rxb: Any = ()  # [N] int32: bytes delivered (post-CoDel)
    nb_thr: Any = ()  # [N] int32: token-bucket throttle events (up + dn)
    nb_shed: Any = ()  # [N] int32: cross-block sheds (subset of n_queue)
    nb_hist: Any = ()  # [NB_HIST_BUCKETS] int32 packet-arrival histogram
    nb_win: Any = ()  # int32 scalar: packets popped in the current window
    # flowtrace event ring (LaneParams.flowtrace; obs/flowtrace.py): a
    # bounded [FL, FT_COLS] int32 buffer of per-flow lifecycle events for
    # deterministically-sampled (src, dst) flows, drained only at
    # snapshot epochs / end-of-run.  Same zero-overhead law as nb_*:
    # () when off, every append behind `if p.flowtrace`.  The ring NEVER
    # wraps — overflow stops recording and counts into fl_lost (the
    # log_lost law), so artifacts stay byte-stable.
    fl_buf: Any = ()  # [FL, flowtrace.FT_COLS] int32 event rows
    fl_count: Any = ()  # int32 scalar: rows appended
    fl_lost: Any = ()  # int32 scalar: events dropped on ring overflow
    # engage counters of the record appends (_append_rows), summed over the
    # log's and the egress buffer's appends of the run: int32 scalars, read
    # once at collect (never in SimResult.counters — the oracle has none).
    # () when neither buffer exists, so a log-off program carries nothing.
    ap_blocks: Any = ()  # block writes
    ap_rows: Any = ()  # rows those blocks wrote
    ap_tail_blocks: Any = ()  # block writes for merge-tail overflow records
    # the run's shape peaks, int32 ``[3]`` (``PK_*``; collect()'s
    # ``lane_plane``): the most live events any lane's merged row ``[old C
    # | self | cross]`` held in any merge, overflow tail included (above
    # the capacity: the queue shed); the largest segment the exchange
    # offered any lane in any iteration (above ``cross_cap``: the cross
    # block shed, or — a fan-out iteration of several passes, each
    # holding ``cross_cap`` — may have); and how many events the cross
    # block shed in all (the part of ``n_queue`` no wider queue would
    # have saved).  Three reductions an iteration: () — nothing traced,
    # the program unchanged — where every lane's model is passive
    # (``LaneParams.all_passive``)
    peaks: Any = ()
    # int32 scalar: pop slots consumed under the window-inert co-pop rule
    # (pop_mask) that the same-instant rule would have refused; its share
    # of ``iters x pops_per_iter x n_lanes`` is how often the rule
    # engages.  Read into collect()'s ``lane_plane``, never into the
    # counters.  () — nothing traced — where no lane's model is in
    # WINDOW_INERT_MODELS (``LaneParams.copop_inert``)
    copop_wide_pops: Any = ()
    # two int32 scalars of a fan-out program's exchange (``_merge_append``
    # step 2): the iterations whose sending (pop, lane) slots fitted ONE
    # pass of the compacted exchange, and the most slots that sent in any
    # one iteration (against ``LaneParams.exchange_slot_budget``).  Read
    # into collect()'s ``lane_plane``, never into the counters.  () —
    # nothing traced — where a pop sends once (``sends_per_pop`` == 1)
    exchange_compact_iters: Any = ()
    exchange_slot_peak: Any = ()
    # gossip lanes' app state (a ``GossipState`` of per-lane arrays; ()
    # — nothing traced — where no lane runs M_GOSSIP)
    gossip: Any = ()
    # the propagation histogram, int32 ``[len(AGE_EDGES_NS) + 1]``: first
    # deliveries by age (delivery time less the message's burst instant;
    # models/gossip.py owns the edges), all lanes' together — one
    # reduction a slot, nothing lane-sized.  collect() adds the buckets to
    # the counters, which the oracle's per-host counts equal.  () where no
    # lane runs M_GOSSIP
    gossip_age: Any = ()
    # int32 scalar: DELIVERY rows a gossip lane did not queue, its PACKET
    # pop having counted the duplicate (``gossip_elides``); at most the
    # run's ``gossip_duplicates``.  Read into collect()'s ``lane_plane``,
    # never into the counters (the oracle queues every delivery).  () —
    # nothing traced — where no lane runs M_GOSSIP
    gossip_elided: Any = ()
    # the loop ledger: what the loop's iterations and windows held —
    # ``loop_hist`` int32 ``[NB_HIST_BUCKETS]``, WINDOWS by the number of
    # iterations they took (the bucket law above, ``hist_fold_index``),
    # and ``loop_acc`` int32 ``[7]``, the words of ``LoopAcc`` in its
    # order; both replicated under a mesh.  Read in collect()'s one transfer into
    # ``lane_plane`` (``loop_*``), never into the counters: the oracle has
    # no iterations.  By ``peaks``' rule: () — nothing traced, the program
    # unchanged — where every lane's model is passive
    # (``LaneParams.all_passive``: such a mesh takes one iteration a
    # window)
    loop_hist: Any = ()
    loop_acc: Any = ()


class LoopAcc(NamedTuple):
    """The words of the loop ledger's running counts, ``LaneState.
    loop_acc`` (on the device ONE int32 vector in this order — as scalars
    of the carry's scalar vector every reduction's result had to cross to
    the body's scalar unit, which cost more than the vector's own kernel,
    PERF.md §6 PR 50; the host decodes it into this tuple).  Every word
    but the first only grows, by non-negative steps, and SATURATES at
    2**31 - 1 (``_ledger_add``): a run of more than 2**31 pop slots
    (100 000 lanes x 2 pops x 10 738 iterations) reads the ceiling, never
    a wrapped count."""

    round_iters: Any  # iterations of the OPEN window so far (folded into
                      # ``loop_hist`` when the next opens; the trailing
                      # window's by ``TpuEngine.collect``)
    round_max: Any  # the most iterations a CLOSED window took
    pop_slots: Any  # live [N] pop slots, of ``iters x pops x n_lanes``
    active_lanes: Any  # [N] lanes that popped at all, summed over iters
    no_send: Any  # iterations whose exchange handed no lane a row
    exch_passes: Any  # passes of a FAN-OUT program's exchange
                      # (``_merge_append`` step 2); stays 0 where a pop
                      # sends once: a pass an iteration
    tier_pop_slots: Any  # live stream-TIER pop slots, of ``iters x
                         # stream_pops x 2 S``; stays 0 without a tier


class GossipState(NamedTuple):
    """Per-lane state of the gossip model (models/gossip.py), all int32
    and all leading with the lane axis."""

    seen: jnp.ndarray  # [N, ceil(M / 32)] bitmap of message ids seen
    sends: jnp.ndarray  # [N] datagrams pushed (publishes and forwards)
    first: jnp.ndarray  # [N] first deliveries
    dups: jnp.ndarray  # [N] duplicate deliveries
    last_hi: jnp.ndarray  # [N] pair: time of the last first delivery
    last_lo: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class LaneParams:
    """Static (compile-time) simulation parameters."""

    n_lanes: int
    capacity: int  # C
    pops_per_iter: int  # K
    log_capacity: int  # L (0 disables logging)
    seed: int
    stop_time: int
    bootstrap_end: int
    runahead: int
    bucket_interval: int = DEFAULT_INTERVAL_NS
    # models present in this simulation (static): absent models' slot logic
    # is dropped at trace time — the branchless cascade only pays for what
    # the config uses
    models_present: tuple = tuple(range(9))
    # static: any edge with packet_loss > 0?  loss-free graphs skip the
    # per-send threefry draw entirely
    has_loss: bool = True
    # dynamic runahead (runahead.rs:44-118): the window may widen to the
    # smallest latency actually used so far, never below the floor
    dynamic_runahead: bool = False
    runahead_floor: int = 1
    # cross-lane receive block width PER ITERATION (0 = the queue
    # capacity).  A lane receiving more than this many packets in one
    # iteration sheds the excess exactly like queue overflow (counted,
    # strict mode raises) — but a narrow block makes the exchange gather
    # and the merge row sort substantially cheaper, so workloads with
    # bounded per-iteration fan-in (the all-to-all mesh receives ~1) run
    # with a small value
    cross_capacity: int = 0
    # every stream server serves exactly one client: server flow rows live
    # at the server's own lane and the per-slot row gather/scatter
    # disappears (TpuEngine detects this from the config)
    stream_one_to_one: bool = False
    # static stream-client lane ids (burst-channel compaction) and the
    # wide co-pop gate: every possible lookahead window must end before
    # RTO_MIN so stream DELIVERY pops cannot insert same-window events
    stream_clients: tuple = ()
    stream_wide_pop: bool = False
    # any lane captures pcap (static): sends emit PCAP_TX records into the
    # device log at departure time
    pcap_any: bool = False
    # any STREAM endpoint lane captures (static): gates the compacted
    # pcap channels so non-capturing stream sims pay nothing for them
    stream_pcap: bool = False
    # TIERED stream backend (one-to-one configs): stream endpoints keep a
    # dedicated [2S, C2] queue block + compact network state
    # (lanes_stream.TierState under ``state.stream``), the [N] tier runs
    # the pure-mesh body with no payload columns, and deliveries at
    # stream endpoints are ELIDED (TCP law applied inline at t_deliver)
    # whenever t_deliver lands inside the current window — exact for
    # one-to-one flows, and window-law-exact via the fallback insert.
    stream_tiered: bool = False
    stream_pops: int = 8  # K_s: tier pop columns per iteration
    stream_capacity: int = 64  # C2: tier queue width
    # hybrid backend (backend/hybrid.py): some lanes are EXTERNAL — their
    # apps (real managed binaries, or any host-only model) execute on the
    # host CPU while their network dn-side (down bucket, CoDel, arrival
    # queue) stays on device.  Deliveries to external lanes leave through
    # the egress buffer; host sends enter through the injection merge.
    # netobs telemetry plane (obs/netobs.py): static — off compiles every
    # counter update away (the LaneState nb_* fields stay ())
    netobs: bool = False
    # flowtrace plane (obs/flowtrace.py): static — off compiles every
    # event append away (the LaneState fl_* fields stay ()).  Sampling is
    # the seeded-hash law shared with the CPU oracle: a flow (src, dst)
    # records iff flow_all or flow_hash < flow_thresh (uint32 compare).
    flowtrace: bool = False
    flow_capacity: int = 0  # FL (ring rows)
    flow_thresh: int = 0  # uint32 sampling threshold (flowtrace.sample_thresh)
    flow_all: bool = False  # sample == 1.0: every flow records
    flow_seed: int = 0  # sampling seed (folded into the hash)
    external_any: bool = False
    egress_capacity: int = 0  # E (rows in the egress buffer)
    ext_per_iter: int = 0  # worst-case egress appends per iteration
    inject_batch: int = 0  # B (rows per injection block)
    inject_cross: int = 0  # per-lane injection fan-in per call (0 = C)
    # mesh peers of a gossip lane (the ``[N, D]`` peer table's width; 0
    # where no lane runs M_GOSSIP)
    gossip_degree: int = 0
    # the gossip lanes' publication instants (ns) and messages a burst:
    # message ``m`` was published at ``gossip_bursts[m // gossip_messages]``,
    # the instant a first delivery's age counts from
    gossip_bursts: tuple = ()
    gossip_messages: int = 1

    @property
    def stream_present(self) -> bool:
        return bool(set(self.models_present) & STREAM_MODELS)

    @property
    def sends_per_pop(self) -> int:
        """F: the most datagrams one popped event's handler sends through
        the [N] send channel — a static property of the models present, as
        ``copop_inert`` is: a gossip pop pushes to its D mesh peers, every
        other model sends at most once.  ``_process_slot`` emits ``[F,
        N]`` sends and the exchange sorts ``pops x F x lanes`` rows."""
        return self.gossip_degree if M_GOSSIP in self.models_present else 1

    @property
    def payload_words(self) -> int:
        """How many opaque payload words a row of the [N] queues carries —
        a static property of the models present, as ``sends_per_pop`` is:
        2 where stream events ride the [N] queues (a segment's ``phi`` and
        ``plo``; the tiered backend moves those to the [2S] block, which
        always keeps its two), else 1 where gossip datagrams do (the
        message id, ``plo``: ``phi`` then exists nowhere in the program),
        else 0."""
        if self.stream_present and not self.stream_tiered:
            return 2
        return int(M_GOSSIP in self.models_present)

    @property
    def pay_words(self) -> tuple:
        """The payload words a row carries, by name."""
        return pay_words(self.payload_words)

    @property
    def row_words(self) -> tuple:
        """All the words of a row: the operand list of every row sort, in
        its order."""
        return ROW_WORDS + self.pay_words

    @property
    def emit_pay_words(self) -> tuple:
        """The payload words a slot's insert and send channels carry
        (``_SlotEmit.ins_*`` / ``out_*``; an absent one is ``()``): the
        rows' own — or, where the rows carry none, both, as zeros nobody
        reads and XLA drops: such a program's lowered text is what it has
        always been, and the tests pin it."""
        return self.pay_words or PAY_WORDS

    @property
    def all_passive(self) -> bool:
        return set(self.models_present) <= PASSIVE_MODELS

    @property
    def copop_inert(self) -> bool:
        """Some lane's model is window-inert: the pop phase compiles the
        DELIVERY* PACKET* co-pop and its engage counter."""
        return bool(set(self.models_present) & WINDOW_INERT_MODELS)

    @property
    def cross_cap(self) -> int:
        return min(self.cross_capacity, self.capacity) or self.capacity

    @property
    def exchange_entries(self) -> int:
        """Rows of the [N] exchange's flat sort: K pops of one send a
        lane, plus the compacted stream channels (slot-0 sends, RTO arms,
        bursts) where they ride it (``_merge_append`` holds it to the
        traced shape) — or, where a pop fans out, the F sends of
        ``exchange_slot_budget`` sending slots a pass."""
        if self.sends_per_pop > 1:
            return self.exchange_slot_budget * self.sends_per_pop
        m = self.pops_per_iter * self.n_lanes
        if (self.stream_present and not self.stream_tiered
                and not self.stream_one_to_one):
            m += self.pops_per_iter * len(self.stream_clients) * (
                4 + lstr.ltcp.PUMP_BURST
            )
        return m

    @property
    def exchange_slot_budget(self) -> int:
        """S_b: the sending (pop, lane) slots ONE pass of a fan-out
        program's exchange takes (``_merge_append`` step 2).  A shape
        law, no option: the compacted exchange, ``S_b x F`` rows, is as
        wide as a one-send program's ``K x N``, the slots rounded up to
        ``_SLOT_TILE`` (2 560 slots = 20 480 rows at K = 2, F = 8 and
        10 000 lanes, where the send channel has 160 000), and never more
        slots than there are.  0 where a pop sends once: that exchange is
        ``K x N`` rows already."""
        if self.sends_per_pop == 1:
            return 0
        slots = self.pops_per_iter * self.n_lanes
        tiles = -(-slots // (self.sends_per_pop * _SLOT_TILE))
        return min(slots, tiles * _SLOT_TILE)

    def __post_init__(self) -> None:
        if self.n_lanes > MAX_LANES:
            raise ValueError(
                f"n_lanes={self.n_lanes} exceeds the packed-key limit {MAX_LANES}"
            )
        if self.cross_capacity < 0:
            raise ValueError(
                f"cross_capacity={self.cross_capacity} must be >= 0"
            )
        if self.flowtrace and self.stream_tiered:
            # flowtrace instruments the [N] untiered path only; engines
            # drop the tier (an equivalent, faster execution strategy)
            # when tracing so event streams stay bit-identical
            raise ValueError("flowtrace requires stream_tiered=False")
        if self.flowtrace and self.flow_capacity <= 0:
            raise ValueError(
                f"flowtrace requires flow_capacity > 0 (got {self.flow_capacity})"
            )
        if M_GOSSIP in self.models_present:
            if self.gossip_degree < 1 or self.gossip_messages < 1:
                raise ValueError(
                    "gossip lanes need gossip_degree and gossip_messages "
                    ">= 1")
            # the payload words are the stream tier's or the message id's,
            # and the pcap / flowtrace channels carry one send a pop
            if self.stream_present or self.pcap_any or self.flowtrace:
                raise ValueError(
                    "gossip lanes beside stream lanes, pcap capture or "
                    "flowtrace are not lane-compiled"
                )


class LaneTables(NamedTuple):
    """Device-resident per-lane constants (not mutated by the sim).
    Everything on the hot path is int32 (the engine validates magnitudes
    and raises LaneCompatError out of range — see TpuEngine).

    A path's three words — latency, loss threshold, lose-everything — are
    held in the layout each kind of send reads: the ``[G, G]`` tables
    (every program; on one graph node the lookup folds), rows per static
    destination (``flow_*``, ``g_*``: nothing gathered), and for a
    destination picked at run time two packed ``[G * G]`` words gathered
    by one flat index (``flat_lat``, ``flat_thresh``; ``path_gather_tables``
    counts them).  ``TpuEngine._path_words`` builds all of them from one
    epoch's tables."""

    node_of: jnp.ndarray  # [N] int32: lane -> graph node index
    lat: jnp.ndarray  # [G, G] int32 latency ns (< 2**31 enforced)
    # loss thresholds, u64 domain split for pure-int32 compares (the u64
    # compare was the hot loop's last X64 custom call): u32 draw < thresh
    # == thresh_all | (draw < thresh_u32)
    thresh_u32: jnp.ndarray  # [G, G] uint32: thresh & 0xFFFFFFFF
    thresh_all: jnp.ndarray  # [G, G] bool: thresh == 2**32 (loss = 1.0)
    up_rate: jnp.ndarray  # [N] int32 bits/interval
    up_burst: jnp.ndarray  # [N] int32
    up_kfull: jnp.ndarray  # [N] int32: intervals that certainly fill burst
    up_kfi: jnp.ndarray  # [N] int32: up_kfull * interval ns
    dn_rate: jnp.ndarray
    dn_burst: jnp.ndarray
    dn_kfull: jnp.ndarray
    dn_kfi: jnp.ndarray
    model: jnp.ndarray  # [N] int32 model id
    recv_mult: jnp.ndarray  # [N] int32: counting apps per lane
    p_size: jnp.ndarray  # [N] int32 datagram size
    p_int_hi: jnp.ndarray  # [N] int32 pair: timer interval ns
    p_int_lo: jnp.ndarray
    p_peer: jnp.ndarray  # [N] int32 fixed peer (client models)
    p_count: jnp.ndarray  # [N] int32 message budget (ping client)
    p_stride: jnp.ndarray  # [N] int32 (tgen-mesh)
    codel_div: jnp.ndarray  # [1025] int32
    # COMPACTED stream-flow tables [2S] (S flows; rows 0..S-1 = client
    # endpoints, S..2S-1 = server endpoints — lanes_stream.endpoint_cols).
    # All static per flow, so the stream tier runs on [2S] rows instead
    # of [N] lanes and its sends need no latency/loss gathers at all.
    # Shapes are [2] placeholder when no stream models are present.
    flow_lanes: jnp.ndarray  # [2S] int32: endpoint's own lane
    flow_peers: jnp.ndarray  # [2S] int32: endpoint's peer lane
    flow_clid: jnp.ndarray  # [2S] int32: the flow's CLIENT lane
    flow_lat: jnp.ndarray  # [2S] int32: latency lane -> peer
    flow_thresh_u32: jnp.ndarray  # [2S] uint32 loss threshold
    flow_thresh_all: jnp.ndarray  # [2S] bool
    flow_segs: jnp.ndarray  # [2S] int32 (zeros on the server half)
    flow_mss: jnp.ndarray  # [2S] int32
    flow_last: jnp.ndarray  # [2S] int32
    flow_cc: jnp.ndarray  # [2S] int32 CC algorithm (ltcp.CC_RENO/CC_CUBIC)
    flow_up_rate: jnp.ndarray  # [2S] int32: the endpoint lane's up bucket
    flow_up_burst: jnp.ndarray  # [2S] int32
    flow_up_kfull: jnp.ndarray  # [2S] int32
    flow_up_kfi: jnp.ndarray  # [2S] int32
    flow_pcap: jnp.ndarray  # [2S] bool: the endpoint lane captures pcap
    lane_pcap: jnp.ndarray  # [N] bool: host captures pcap
    # hybrid backend: [N] bool — lane is EXTERNAL (host-executed host);
    # () on non-hybrid runs
    lane_external: Any = ()
    # tiered backend: the endpoint lane's DOWN bucket (arrivals at stream
    # endpoints are processed by the [2S] tier) — () otherwise
    flow_dn_rate: Any = ()
    flow_dn_burst: Any = ()
    flow_dn_kfull: Any = ()
    flow_dn_kfi: Any = ()
    # [N] bool: lane is a stream endpoint (tiered: its [N] queue row is
    # dead and cross traffic to it diverts into the tier block)
    lane_stream: Any = ()
    # sweep backend (shadow_tpu/sweep): the master seed as a pair of
    # uint32 SCALARS carried as traced table leaves, so a vmapped batch
    # gives every scenario its own seed under one compile.  () on the
    # serial path, where the static LaneParams.seed is baked in instead;
    # the threefry key inputs are identical either way (core.rng
    # _split_seed semantics), so the two forms are bit-identical.
    seed_lo: Any = ()
    seed_hi: Any = ()
    # [N, D] int32: a gossip lane's mesh peers in forwarding order
    # (models/gossip.py gossip_mesh; rows of other lanes are zeros); ()
    # where no lane runs M_GOSSIP
    g_peers: Any = ()
    # [F, N], lanes minor: the path from a gossip lane to its k-th mesh
    # peer — ``lat`` / ``thresh_u32`` / ``thresh_all`` at ``[node_of[n],
    # node_of[g_peers[n, k]]]``, constants of the pair as a flow's
    # ``flow_lat`` is (TpuEngine._path_tables builds both, per fault epoch
    # too); () where no lane runs M_GOSSIP and on a graph of one node,
    # where the [1, 1] lookup already folds to a scalar
    g_lat: Any = ()
    g_thresh_u32: Any = ()
    g_thresh_all: Any = ()
    # [G * G], row-major over (source node, destination node): the words a
    # send whose destination is picked at RUN time gathers, by the ONE
    # index ``node_of[lane] * G + node_of[dst]`` (a one-word index into a
    # 1-D table; the [G, G] tables take a two-word one).  ``flat_lat`` is
    # ``lat | (thresh_all << 31)``: bit 31 is free, a routed pair's
    # latency is positive and the engine rejects any at or above NEVER32 =
    # 2**31 - 1, so ``word & MASK31`` is the latency and ``word < 0`` the
    # pair that loses everything (a pair without a route, -1 in ``lat``,
    # is one no two hosts form: its word is never used); ``flat_thresh``
    # is ``thresh_u32``.  Built by
    # ``TpuEngine._path_words`` with the tables above, per fault epoch too;
    # () where no send gathers (``gathers_path``), and there the program
    # takes no such argument
    flat_lat: Any = ()  # int32
    flat_thresh: Any = ()  # uint32


def gathers_path(p: LaneParams, graph_nodes: int) -> bool:
    """Whether the program ``p`` compiles to on a graph of ``graph_nodes``
    nodes has a send that gathers its path: a destination picked at run
    time (a ``PATH_GATHER_MODELS`` lane) on more than one node.  On one
    node the [1, 1] lookup folds and nothing is gathered."""
    return graph_nodes > 1 and bool(
        set(p.models_present) & PATH_GATHER_MODELS)


def path_sends(p: LaneParams, tb: LaneTables) -> tuple[int, int]:
    """``(static_path_sends, path_gather_sends)``, two static facts of the
    program ``p`` and ``tb`` compile to (``lane_plane`` gauges): of a pop's
    ``sends_per_pop`` sends, how many read their path's latency and loss
    threshold from per-peer rows (``tb.g_lat``: the gossip lanes' F on a
    graph of more than one node, else 0), and how many have a destination
    picked at run time on such a graph, whose path is gathered from the
    packed ``flat_*`` words (send 0 of a ``PATH_GATHER_MODELS`` lane; on
    one node the lookup folds and nothing is gathered)."""
    rows = 0 if isinstance(tb.g_lat, tuple) else p.sends_per_pop
    return rows, int(gathers_path(p, tb.lat.shape[-1]))


def path_gather_load(p: LaneParams, tb: LaneTables) -> tuple[int, int]:
    """``(path_gather_tables, path_gather_elems_per_iter)``, static too:
    the packed words a gathered send reads beside ``node_of[dst]``
    (``flat_lat``: the latency and the lose-everything bit; with the loss
    draw compiled in ``flat_thresh`` as well) and the elements one
    iteration gathers in the ``path_gather`` scope — ``node_of[dst]`` and
    each word for every lane at every pop, whether the slot holds an event
    or not.  ``(0, 0)`` where no send gathers."""
    if not path_sends(p, tb)[1]:
        return 0, 0
    tables = 2 if p.has_loss else 1
    return tables, p.pops_per_iter * p.n_lanes * (1 + tables)


# --------------------------------------------------------------------------
# vectorized component laws (identical arithmetic to net/token_bucket.py and
# net/codel.py — see docs/SEMANTICS.md), on int32 pairs
# --------------------------------------------------------------------------


def bucket_charge_vec(
    tokens, nr_hi, nr_lo, ld_hi, ld_lo, rate, burst, k_full, kfi,
    t_hi, t_lo, bits, active, interval
):
    """Masked PAIR-arithmetic form of TokenBucket.charge; returns
    (tokens', nr_hi', nr_lo', ld_hi', ld_lo', dep_hi, dep_lo, waited).
    ``waited`` is the THROTTLE mask (active, rate-limited, and tokens
    short after the refill — the instant the scalar law counts as a
    throttle event, netobs' token-bucket cause).  Identical update law to
    net/token_bucket.py, with the elapsed-interval count computed
    exactly:

    - within the k_full horizon (``kfi = k_full * interval`` ns, where
      ``k_full`` intervals always refill to burst) the elapsed count comes
      from an int32 clamped pair difference — exact because the clamp only
      saturates beyond the horizon;
    - beyond it the refill saturates at burst and next_refill realigns to
      the first grid point past t (``next_refill ≡ 0 (mod interval)`` is
      an invariant: the initial value is ``interval`` and every update
      adds multiples of ``interval``), which needs one int64 mod — the
      only int64 in the law besides the depart-wait product.

    FIFO law: the charge clock is ``max(t, last_depart)`` so departures
    are monotone per lane."""
    unlimited = rate == 0
    act = active & ~unlimited
    t_hi, t_lo = pair_max(t_hi, t_lo, ld_hi, ld_lo)

    do_refill = act & pair_ge(t_hi, t_lo, nr_hi, nr_lo)
    diff = pair_sub_clamp(t_hi, t_lo, nr_hi, nr_lo, kfi)  # int32, exact < kfi
    full = diff >= kfi
    k = jnp.where(do_refill, jnp.minimum(diff // interval + 1, k_full), 0)
    tokens = jnp.where(
        do_refill, jnp.minimum(burst, tokens + k * rate), tokens
    )
    # next_refill': nr + k_true*interval == first grid point past t.
    # Non-saturated: nr + k*interval (k == k_true).  Saturated: realign
    # from t's grid phase directly — chunked int32 mod (the int64 ``%``
    # was the hot loop's last X64 custom call)
    part_hi, part_lo = pair_add32(nr_hi, nr_lo, k * interval)
    tmod = pair_mod_small(t_hi, t_lo, interval)
    g_hi, g_lo = pair_add32(*pair_sub32(t_hi, t_lo, tmod), interval)
    nr_hi = jnp.where(do_refill, jnp.where(full, g_hi, part_hi), nr_hi)
    nr_lo = jnp.where(do_refill, jnp.where(full, g_lo, part_lo), nr_lo)

    have = tokens >= bits
    wait_lane = act & ~have
    need = jnp.maximum(bits - tokens, 1)
    w = jnp.where(wait_lane, -(-need // jnp.maximum(rate, 1)), 1)
    # depart = next_refill' + (w-1)*interval.  The engine guarantees
    # w*interval < 2**31 (minimum-rate guard: one max-size packet's wait
    # never exceeds the int32 horizon), so the products stay int32 — an
    # int64 product here made XLA:CPU's while-loop execution pathological
    dep_hi, dep_lo = pair_add32(nr_hi, nr_lo, (w - 1) * interval)
    dep_hi, dep_lo = pair_sel(wait_lane, dep_hi, dep_lo, t_hi, t_lo)
    # token math caps w at the burst horizon (identical result: beyond it
    # the refill saturates at burst before subtracting)
    w_r = jnp.minimum(w, burst // jnp.maximum(rate, 1) + 1)
    new_tokens = jnp.where(
        have,
        tokens - bits,
        jnp.maximum(0, jnp.minimum(burst, tokens + w_r * rate) - bits),
    )
    tokens = jnp.where(act, new_tokens, tokens)
    nr2_hi, nr2_lo = pair_add32(nr_hi, nr_lo, w * interval)
    nr_hi = jnp.where(wait_lane, nr2_hi, nr_hi)
    nr_lo = jnp.where(wait_lane, nr2_lo, nr_lo)
    ld_hi = jnp.where(act, dep_hi, ld_hi)
    ld_lo = jnp.where(act, dep_lo, ld_lo)
    return tokens, nr_hi, nr_lo, ld_hi, ld_lo, dep_hi, dep_lo, wait_lane


def bucket_charge_chained_vec(
    tokens, nr_hi, nr_lo, ld_hi, ld_lo, rate, burst, bits, active, interval,
    t_hi, t_lo
):
    """One charge of an INTRA-INSTANT chain, for every unit after the
    first: all burst units share the stimulus time t, so once unit 1 has
    charged, every later unit's charge clock is ``max(t, last_depart) =
    last_depart`` and the refill branch provably cannot fire — after a
    no-wait charge ``last_depart = t_eff < next_refill`` (the full law
    leaves ``next_refill`` strictly past the charge clock), and after a
    wait ``last_depart = next_refill' - interval < next_refill'``.  The
    law therefore reduces to the wait machinery: ~5x fewer ops than
    ``bucket_charge_vec`` and none of the grid-realignment mod chains.
    Identical update law to the full form under that precondition (the
    stream parity suite diffs the result against the scalar oracle).
    ``t`` is still needed for the no-wait departure stamp: on UNLIMITED
    lanes (rate == 0) ``last_depart`` never advances, so the stamp is
    ``max(t, last_depart)`` exactly as in the full law."""
    unlimited = rate == 0
    act = active & ~unlimited
    have = tokens >= bits
    wait_lane = act & ~have
    need = jnp.maximum(bits - tokens, 1)
    w = jnp.where(wait_lane, -(-need // jnp.maximum(rate, 1)), 1)
    te_hi, te_lo = pair_max(t_hi, t_lo, ld_hi, ld_lo)
    dep_hi, dep_lo = pair_add32(nr_hi, nr_lo, (w - 1) * interval)
    dep_hi, dep_lo = pair_sel(wait_lane, dep_hi, dep_lo, te_hi, te_lo)
    w_r = jnp.minimum(w, burst // jnp.maximum(rate, 1) + 1)
    new_tokens = jnp.where(
        have,
        tokens - bits,
        jnp.maximum(0, jnp.minimum(burst, tokens + w_r * rate) - bits),
    )
    tokens = jnp.where(act, new_tokens, tokens)
    nr2_hi, nr2_lo = pair_add32(nr_hi, nr_lo, w * interval)
    nr_hi = jnp.where(wait_lane, nr2_hi, nr_hi)
    nr_lo = jnp.where(wait_lane, nr2_lo, nr_lo)
    ld_hi = jnp.where(act, dep_hi, ld_hi)
    ld_lo = jnp.where(act, dep_lo, ld_lo)
    return tokens, nr_hi, nr_lo, ld_hi, ld_lo, dep_hi, dep_lo, wait_lane


# CoDel "first_above" unset sentinel: the int64 law used time 0; with pair
# state the sentinel is a hi word no real time can reach
CD_UNSET = -(1 << 31) + 1


@jax.named_scope("codel_offer")
def codel_offer_arrays(
    fat_hi, fat_lo, dn_hi, dn_lo, dcount, dropping,
    td_hi, td_lo, sojourn, active, codel_div,
):
    """Masked PAIR form of CoDel.offer on explicit state arrays; returns
    ``(fat_hi', fat_lo', dnext_hi', dnext_lo', dcount', dropping', drop,
    looked)``.  ``sojourn`` is an int32 clamped difference — exact for
    every compare in the law (values past the clamp are far above TARGET
    either way).  Shape-generic: the [N] lane tier and the [2S] stream
    tier share it.

    The control-law interval ``codel_div[k]`` costs the chip a serial
    per-element gather (~6.6 ns an element, PERF.md §6 PR 36), so it is
    fetched only where it can be kept.  Entering an episode, ``k`` is 1 or
    2: a select of two constants.  Inside one, ``k`` is general but the
    entry is kept only on lanes with ``drop_in_dropping``: ``looked`` (a
    scalar: some lane has it in this pop) guards the gather with a
    ``lax.cond`` whose operands are the index and the table alone — no
    lane state crosses it.  A quiet network never takes the branch.
    Guarded at every length, no rule and no option (the readings that
    decided it: PERF.md §6 PR 36)."""
    unset = fat_hi == CD_UNSET
    below = sojourn < codel_mod.TARGET_NS
    ent_hi, ent_lo = pair_add32(td_hi, td_lo, codel_mod.INTERVAL_NS)
    fatn_hi = jnp.where(below, CD_UNSET, jnp.where(unset, ent_hi, fat_hi))
    fatn_lo = jnp.where(below, 0, jnp.where(unset, ent_lo, fat_lo))
    ok_to_drop = (
        active & ~below & ~unset & pair_ge(td_hi, td_lo, fat_hi, fat_lo)
    )

    # dropping state machine
    drop_in_dropping = (
        active & dropping & ok_to_drop & pair_ge(td_hi, td_lo, dn_hi, dn_lo)
    )
    dcount_d = dcount + drop_in_dropping.astype(dcount.dtype)
    div_idx_d = jnp.minimum(dcount_d, codel_mod.DIV_TABLE_SIZE - 1)
    looked = jnp.any(drop_in_dropping)
    div_d = lax.cond(
        looked,
        lambda table, idx: table[idx],
        lambda table, idx: jnp.zeros_like(idx),
        codel_div, div_idx_d,
    )
    dnd_hi, dnd_lo = pair_add32(dn_hi, dn_lo, div_d)
    dnd_hi = jnp.where(drop_in_dropping, dnd_hi, dn_hi)
    dnd_lo = jnp.where(drop_in_dropping, dnd_lo, dn_lo)

    # enter conditions: t_del - dnext < INTERVAL  |  t_del - fat_new >= INTERVAL
    dni_hi, dni_lo = pair_add32(dn_hi, dn_lo, codel_mod.INTERVAL_NS)
    fni_hi, fni_lo = pair_add32(fatn_hi, fatn_lo, codel_mod.INTERVAL_NS)
    enter = (
        active
        & ~dropping
        & ok_to_drop
        & (
            pair_lt(td_hi, td_lo, dni_hi, dni_lo)
            | pair_ge(td_hi, td_lo, fni_hi, fni_lo)
        )
    )
    recent = pair_lt(td_hi, td_lo, dni_hi, dni_lo)
    resume = (dcount > 2) & recent
    dcount_e = jnp.where(resume, 2, 1).astype(dcount.dtype)
    div_e = jnp.where(
        resume,
        jnp.int32(codel_mod.CODEL_DIV[2]),
        jnp.int32(codel_mod.CODEL_DIV[1]),
    )
    dne_hi, dne_lo = pair_add32(td_hi, td_lo, div_e)

    drop = drop_in_dropping | enter
    fat_out_hi = jnp.where(active, fatn_hi, fat_hi)
    fat_out_lo = jnp.where(active, fatn_lo, fat_lo)
    dropping_out = jnp.where(active, (dropping & ok_to_drop) | enter, dropping)
    dcount_out = jnp.where(
        enter, dcount_e, jnp.where(drop_in_dropping, dcount_d, dcount)
    )
    dn_out_hi = jnp.where(enter, dne_hi, dnd_hi)
    dn_out_lo = jnp.where(enter, dne_lo, dnd_lo)
    return (fat_out_hi, fat_out_lo, dn_out_hi, dn_out_lo, dcount_out,
            dropping_out, drop, looked)


def codel_offer_vec(state, td_hi, td_lo, sojourn, active, codel_div):
    """LaneState wrapper of :func:`codel_offer_arrays`."""
    (fat_hi, fat_lo, dn_hi, dn_lo, dcount, dropping, drop,
     looked) = codel_offer_arrays(
        state.cd_fat_hi, state.cd_fat_lo, state.cd_dnext_hi,
        state.cd_dnext_lo, state.cd_drop_count, state.cd_dropping,
        td_hi, td_lo, sojourn, active, codel_div,
    )
    state = state._replace(
        cd_fat_hi=fat_hi,
        cd_fat_lo=fat_lo,
        cd_dnext_hi=dn_hi,
        cd_dnext_lo=dn_lo,
        cd_drop_count=dcount,
        cd_dropping=dropping,
        codel_lookup_pops=state.codel_lookup_pops + looked,
    )
    return state, drop


def rand_u32_lane(seed, stream, counter32):
    """threefry draw with an int32 counter (c1 = 0): bit-identical to
    core.rng.rand_u32 for counters < 2**32, with no int64 in the path.

    ``seed`` is either a Python int (static — split here, compiled into
    the kernel) or a ``(lo, hi)`` pair of uint32 scalars (traced — the
    sweep path threads per-scenario seeds through LaneTables so one
    trace serves every seed).  Both forms feed threefry the same key
    words, so they are bit-identical."""
    u32 = jnp.uint32
    if isinstance(seed, tuple):
        s_lo, s_hi = seed
    else:
        s_lo, s_hi = rng_mod._split_seed(seed)
    k0 = jnp.asarray(s_lo, dtype=u32)
    k1 = (
        jnp.asarray(stream, dtype=u32) ^ jnp.asarray(s_hi, dtype=u32)
    ).astype(u32)
    c0 = counter32.astype(u32)
    c1 = jnp.zeros_like(c0)
    return rng_mod.threefry2x32(k0, k1, c0, c1, jnp)[0]


def _seed_keys(p: "LaneParams", tb: "LaneTables"):
    """The seed argument for rand_u32_lane under this trace: the traced
    per-scenario (lo, hi) pair from the tables when the sweep path
    populated it, else the static LaneParams seed."""
    if not isinstance(tb.seed_lo, tuple):
        return (tb.seed_lo, tb.seed_hi)
    return p.seed


# --------------------------------------------------------------------------
# the round kernel
# --------------------------------------------------------------------------


# Set (via _force_unroll) while a sharded kernel is being traced:
# GSPMD cannot partition lax.scan's stacked-output update when the
# stacked axis is lane-sharded and x64 indices are live (the partitioner
# emits an s64-vs-s32 offset compare the HLO verifier rejects), so the
# multi-chip build must take the Python-loop form even on XLA:CPU — but
# ONLY at the call sites whose stacked outputs carry the lane axis
# (spmd_unroll=True below): the stream-tier walks stack per-flow [S]
# rows, which replicate under the mesh and partition fine as scans, and
# unrolling their heavy bodies made the sharded mixed-kernel compile
# pathological (tens of GB of XLA working set).
_SPMD_UNROLL = False


class _force_unroll:
    """Context manager forcing scan_or_unroll into its Python-loop form.

    The sharded drivers (parallel/mesh.py) wrap their jitted entry points
    with this: jit traces lazily on first call, so the flag must be live
    around the CALL, not around jax.jit."""

    def __enter__(self):
        global _SPMD_UNROLL
        self._old = _SPMD_UNROLL
        _SPMD_UNROLL = True

    def __exit__(self, *exc):
        global _SPMD_UNROLL
        _SPMD_UNROLL = self._old


def scan_or_unroll(step, carry, xs, length: int, spmd_unroll: bool = False):
    """``lax.scan`` on XLA:CPU (whose per-op thunk dispatch makes unrolled
    bodies pathological) — but a plain Python loop with ONE final stack on
    the accelerator: scan materializes its stacked outputs via a
    dynamic-update-slice per step even when fully unrolled, and each DUS
    ends an XLA fusion, fragmenting the loop into one kernel launch per
    step (a fusion count, not a timing: ~300 fusions per mixed-mesh
    iteration; its cost is unmeasured on the attached chip).
    The Python-loop form leaves pure elementwise chains that fuse — and
    for lane-axis stacked outputs is the only form GSPMD partitions
    (``spmd_unroll=True`` marks those sites; see _SPMD_UNROLL above);
    both forms run the same integer ops in the same order, so they are
    bit-identical.
    """
    if jax.default_backend() == "cpu" and not (_SPMD_UNROLL and spmd_unroll):
        return lax.scan(step, carry, xs, length=length)
    outs = []
    for j in range(length):
        xj = None if xs is None else jax.tree.map(lambda a: a[j], xs)
        carry, o = step(carry, xj)
        outs.append(o)
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *outs)
    return carry, stacked


def _q_cols(s: LaneState, words) -> list:
    """The queue's columns ``[N, C]`` for a list of row words."""
    return [getattr(s, "q_" + w) for w in words]


def _q_replace(s: LaneState, words, cols) -> LaneState:
    """``s`` with those columns as the queue's, word by word."""
    return s._replace(**{"q_" + w: col for w, col in zip(words, cols)})


def _sort_rows(words, cols) -> dict:
    """ONE row sort by the 4-word key — the split form of the (time, kind,
    src, seq) total order; empty slots (NEVER pair) end at the back —
    that carries every word of the row (``LaneParams.row_words``: the
    operand list) through the permutation.  The sorted columns, by word."""
    return dict(zip(words, lax.sort(
        tuple(cols), dimension=1, num_keys=4, is_stable=False)))


def _sort_queues(s: LaneState, words) -> LaneState:
    """Key-sort every lane's queue: restores the sorted-row invariant on
    iterations that pop events but skip the merge (see ``iter_body``)."""
    return _q_replace(
        s, words, _sort_rows(words, _q_cols(s, words)).values())


class _SlotEmit(NamedTuple):
    """What one pop-slot step emits (all [N]).  Every event key — time
    included — is already (hi, lo) int32 words; the only int64 left is
    the log-record channel (int64 log rows, built only when logging)."""

    # same-lane insert channel 1: DELIVERY self-insert (packet pops)
    ins_valid: jnp.ndarray  # bool
    ins_thi: jnp.ndarray  # int32 pair
    ins_tlo: jnp.ndarray
    ins_auxh: jnp.ndarray  # int32
    ins_auxl: jnp.ndarray  # int32
    ins_size: jnp.ndarray  # int32
    ins_phi: Any  # int32 payload words (``phi`` () where it is not
    ins_plo: jnp.ndarray  # one of ``LaneParams.emit_pay_words``)
    # same-lane insert channel 2: timer re-arm / stream pump (LOCAL)
    arm_valid: jnp.ndarray
    arm_thi: jnp.ndarray
    arm_tlo: jnp.ndarray
    arm_auxh: jnp.ndarray
    arm_auxl: jnp.ndarray
    arm_size: jnp.ndarray  # int32 (0 timer, -2 pump)
    arm_plo: jnp.ndarray  # int32 (stream flow id; phi is always 0)
    # cross-lane channel: outbound packets
    out_valid: jnp.ndarray
    out_dst: jnp.ndarray  # int32
    out_thi: jnp.ndarray
    out_tlo: jnp.ndarray
    out_auxh: jnp.ndarray
    out_auxl: jnp.ndarray
    out_size: jnp.ndarray
    out_phi: Any  # int32 payload words (``phi`` () as ``ins_phi`` is)
    out_plo: jnp.ndarray
    # COMPACTED stream channels (endpoint rows; () when no stream tier).
    # Destinations/aux words come from the static flow tables, so only
    # the dynamic fields travel here.
    # slot-0 control sends [2S]
    se_valid: Any
    se_thi: Any  # arrival pair
    se_tlo: Any
    se_seq: Any  # engine send seq
    se_size: Any
    se_phi: Any
    se_plo: Any
    # stream RTO arms [2S] (LOCAL self-inserts, size SZ_RTO)
    sa_valid: Any
    sa_thi: Any
    sa_tlo: Any
    sa_auxl: Any  # local seq
    # burst data segments [PUMP_BURST, S] (client rows; dst = server lane)
    bo_valid: Any
    bo_thi: Any
    bo_tlo: Any
    bo_auxl: Any  # engine send seq
    bo_size: Any
    bo_phi: Any
    bo_plo: Any
    # stream loss records ([2S] slot-0 / [PUMP_BURST, S] burst; () unless
    # logging+stream)
    srec_valid: Any
    srec_time: Any
    srec_seq: Any
    srec_size: Any
    brec_valid: Any
    brec_time: Any
    brec_seq: Any
    brec_size: Any
    # stream outbound pcap captures at bucket DEPARTURE, pre-loss ([2S]
    # slot-0 / [PUMP_BURST, S] burst; () unless pcap+stream)
    spc_valid: Any
    spc_time: Any
    spc_seq: Any
    spc_size: Any
    bpc_valid: Any
    bpc_time: Any
    bpc_seq: Any
    bpc_size: Any
    # outbound pcap channel (int64; () unless pcap_any)
    pc_valid: Any
    pc_time: Any
    pc_dst: Any
    pc_seq: Any
    pc_size: Any
    # log record channel (int64; zeros when logging is off)
    rec_valid: jnp.ndarray
    rec_time: jnp.ndarray
    rec_src: jnp.ndarray
    rec_dst: jnp.ndarray
    rec_seq: jnp.ndarray
    rec_size: jnp.ndarray
    rec_outcome: jnp.ndarray
    # flowtrace channel: dict of per-slot lifecycle observations
    # (obs/flowtrace.py event sources; () unless p.flowtrace).  Dicts are
    # pytrees, so scan stacking handles the bundle like any other leaf.
    ft: Any = ()


def gossip_elides(known, td_hi, td_lo, we_hi, we_lo):
    """Where a gossip lane's PACKET pop counts its datagram's duplicate
    itself and queues no DELIVERY row: the message's bit is ALREADY set
    in the lane's seen bitmap (``known``) and the delivery time falls
    before the window's end, the bound ``pop_mask`` cuts every class at.
    Why no compared output can tell is argued at WINDOW_INERT_MODELS."""
    return known & pair_lt(td_hi, td_lo, we_hi, we_lo)


def _process_slot(
    p: LaneParams, tb: LaneTables, s: LaneState, slot, we_hi, we_lo
) -> tuple[LaneState, _SlotEmit]:
    """Process one popped queue column (all lanes, masked by kind).
    Every time is an (hi, lo) int32 pair; see the representation note at
    module top."""
    n = p.n_lanes
    mp = set(p.models_present)
    lanes = jnp.arange(n, dtype=jnp.int32)
    thi, tlo = slot["thi"], slot["tlo"]
    kind, src, seq = slot["kind"], slot["src"], slot["seq"]  # int32
    size = slot["size"]
    phi, plo = slot["phi"], slot["plo"]
    active = slot["act"]
    false_n = jnp.zeros(n, dtype=bool)

    i64 = jnp.int64
    i32 = jnp.int32
    sp = p.stream_present
    # the only int64 left is the log-record channel (edge work)
    t64 = t_join(thi, tlo) if p.log_capacity else None

    # ---- PACKET pops: down bucket + CoDel -> DELIVERY self-insert --------
    is_pkt = active & (kind == PACKET)
    bits = (size + FRAME_OVERHEAD_BYTES) * 8  # int32: size <= 64 KiB
    (dn_tokens, dn_nr_hi, dn_nr_lo, dn_ld_hi, dn_ld_lo, td_hi, td_lo,
     dn_wait) = (
        bucket_charge_vec(
            s.dn_tokens, s.dn_nr_hi, s.dn_nr_lo, s.dn_ld_hi, s.dn_ld_lo,
            tb.dn_rate, tb.dn_burst, tb.dn_kfull, tb.dn_kfi,
            thi, tlo, bits, is_pkt, p.bucket_interval,
        )
    )
    s = s._replace(
        dn_tokens=dn_tokens, dn_nr_hi=dn_nr_hi, dn_nr_lo=dn_nr_lo,
        dn_ld_hi=dn_ld_hi, dn_ld_lo=dn_ld_lo,
    )
    if p.netobs:
        s = s._replace(nb_thr=s.nb_thr + dn_wait)
    # sojourn only feeds compares against TARGET/INTERVAL: the clamp at
    # NEVER32 is exact for every branch of the law
    sojourn = pair_sub_clamp(td_hi, td_lo, thi, tlo, NEVER32)
    s, codel_drop = codel_offer_vec(s, td_hi, td_lo, sojourn, is_pkt,
                                    tb.codel_div)
    deliver = is_pkt & ~codel_drop
    s = s._replace(
        n_codel=s.n_codel + (is_pkt & codel_drop),
        n_delivered=s.n_delivered + deliver,
    )
    if p.netobs:
        s = s._replace(nb_rxb=s.nb_rxb + jnp.where(deliver, size, 0))

    # passive lanes consume the delivery inline (counters only); active
    # lanes get a DELIVERY self-insert keyed by the packet's (src, seq).
    # EXTERNAL lanes (hybrid backend) consume neither: their delivery
    # leaves the device through the egress buffer — the host side queues
    # it as a DELIVERY event (or applies the same passive elision the
    # oracle would) at the identical t_deliver.
    model = tb.model
    passive = false_n
    for _m in sorted(PASSIVE_MODELS & mp):
        passive = passive | (model == _m)
    if p.external_any:
        ext_lane = tb.lane_external
        # CoDel-dropped packets egress too (outcome column) so the host
        # can unpark their payloads — only DELIVERED rows become host
        # events (and only they feed the free-run guard's egress_min)
        s = _append_egress(
            p, s, is_pkt & ext_lane, deliver, td_hi, td_lo, src, lanes,
            seq, size,
        )
        passive = passive & ~ext_lane
    # every counting app on the host adds the size (the CPU oracle
    # dispatches each delivery to every app): recv_mult is the per-lane
    # app count — 1 on single-process lanes, 0 on empty ones
    inline_del = deliver & passive
    s = s._replace(
        recv_bytes=s.recv_bytes
        + jnp.where(inline_del, size * tb.recv_mult, 0)
    )
    all_passive = mp <= PASSIVE_MODELS
    ins_valid = false_n if all_passive else (deliver & ~passive)
    if p.external_any and not all_passive:
        ins_valid = ins_valid & ~ext_lane
    ins_thi, ins_tlo = td_hi, td_lo
    ins_auxh = pack_aux_hi(jnp.full(n, DELIVERY, dtype=i32), src)
    ins_auxl = seq
    ins_size = size
    ins_phi, ins_plo = phi, plo

    # packet outcome log record
    pk_rec_valid = is_pkt
    pk_rec_outcome = jnp.where(codel_drop, DROP_CODEL, DELIVERED).astype(i32)

    # ---- DELIVERY pops: app on_delivery (non-passive models only; the
    # passive ones were consumed inline at packet arrival above) ----------
    is_del = active & (kind == DELIVERY)
    # phold: send to a random peer; ping server: echo back to src
    del_send_phold = (is_del & (model == M_PHOLD)) if M_PHOLD in mp else false_n
    del_send_echo = (
        (is_del & (model == M_PING_SERVER)) if M_PING_SERVER in mp else false_n
    )
    if M_PHOLD in mp:
        s = s._replace(n_hops=s.n_hops + (is_del & (model == M_PHOLD)))

    # ---- LOCAL pops (start markers / timers / phold initial messages) ----
    # size == -1 marks a process-start event: it anchors the first window at
    # start_time exactly like the CPU engine's start task, and arms the
    # model's first timer without sending.
    is_loc = active & (kind == LOCAL)
    is_start = is_loc & (size == -1)
    # negative sizes are markers (start -1, stream pump/rto -2/-3,
    # multi-process start anchors -5), never timer ticks
    is_timer = is_loc & (size >= 0)
    loc_send_phold = (is_timer & (model == M_PHOLD)) if M_PHOLD in mp else false_n
    mesh_tick = (
        (is_timer & (model == M_TGEN_MESH) & (n > 1))
        if M_TGEN_MESH in mp
        else false_n
    )
    client_tick = (
        (is_timer & (model == M_TGEN_CLIENT)) if M_TGEN_CLIENT in mp else false_n
    )
    ping_tick = (
        (is_timer & (model == M_PING_CLIENT) & (s.m_sent < tb.p_count))
        if M_PING_CLIENT in mp
        else false_n
    )

    # ---- stream tier (COMPACTED lane-TCP on [2S] endpoint rows) ----------
    # The flow matrices are resident per ENDPOINT (rows 0..S-1 = clients,
    # S..2S-1 = servers, flow order — lanes_stream.endpoint_cols), so the
    # whole TCP law runs on a few hundred rows instead of every lane: at
    # bench scale this removed ~96% of the stream tier's tile work per
    # slot.  The popped slot columns reach the endpoints through ONE
    # [N, 9]-row gather; sends/arms leave through compacted channels that
    # ride the exchange sort (see _merge_append), and per-lane counters
    # and the up-bucket state round-trip through one row gather + one
    # masked row scatter (at most one active endpoint per lane per slot,
    # so the scatter is write-unique).
    if sp:
        s2 = int(tb.flow_lanes.shape[0])  # 2S
        s_flows = s2 // 2
        el = tb.flow_lanes
        false_e = jnp.zeros(s2, dtype=bool)
        pm = jnp.stack(
            [thi, tlo, kind, src, size, phi, plo,
             active.astype(i32)], axis=1
        )
        pe = pm[el]  # [2S, 8] row gather
        ethi, etlo = pe[:, 0], pe[:, 1]
        ekind, esrc = pe[:, 2], pe[:, 3]
        esize = pe[:, 4]
        ephi, eplo = pe[:, 5], pe[:, 6]
        eact = pe[:, 7].astype(bool)
        is_cl_e = jnp.arange(s2, dtype=i32) < s_flows
        flags_in, sseq_in, sack_in = lstr.unpack_pay(ephi, eplo)
        e_loc = eact & (ekind == LOCAL)
        stim_open = e_loc & (esize == -1) & is_cl_e
        # RTO locals carry the flow's client lane in the payload word:
        # that also picks WHICH flow of a shared server lane owns it
        stim_rto = e_loc & (esize == lstr.SZ_RTO) & (eplo == tb.flow_clid)
        # zero payload words mark a foreign (non-ltcp) datagram delivered
        # to a stream lane in a mixed workload: every real segment carries
        # flags != 0.  The CPU oracle ignores those via its isinstance
        # check (tcpflow.StreamServer.on_delivery) — mirror it exactly.
        # Server endpoints answer only their own client's segments (the
        # scalar law keys server flows by src); client endpoints keep the
        # oracle's isinstance-only check
        stim_seg = (
            eact & (ekind == DELIVERY) & ((ephi | eplo) != 0)
            & (is_cl_e | (esrc == tb.flow_clid))
        )
        stream_stim = stim_open | stim_rto | stim_seg
        f = lstr.endpoint_cols(
            s.stream, tb.flow_segs, tb.flow_mss, tb.flow_last, tb.flow_cc
        )
        f1, em1 = lstr.open_flow_vec(f, ethi, etlo, stim_open)
        f = lstr._merge_cols(f, f1, stim_open)
        f3, em3 = lstr.on_rto_vec(f, ethi, etlo, stim_rto)
        f = lstr._merge_cols(f, f3, stim_rto)
        f4, em4 = lstr.on_segment_vec(
            f, ethi, etlo, stim_seg, flags_in, sseq_in, sack_in, esize
        )
        f = lstr._merge_cols(f, f4, stim_seg)
        sem = lstr._merge_emit(
            lstr._merge_emit(em1, em3, stim_rto), em4, stim_seg
        )
        # completion latches (counted once, like the CPU _track)
        f = f._replace(
            completed=f.completed | (sem.completed_now & stream_stim)
        )
        # the transmission-opportunity epilogue: every stimulus ends with
        # a burst of up to PUMP_BURST window-permitted data segments
        # (scalar _pump_units) — the law that removed pump LOCAL events
        f, sem, st_burst = lstr.pump_epilogue_vec(
            f, ethi, etlo, stream_stim, sem
        )
        s = s._replace(stream=lstr.endpoint_split(f))
        st_send = sem.send_valid & stream_stim
        st_rto = sem.rto_valid & stream_stim

    # ---- gossip (models/gossip.py): a publish timer's message id rides
    # the LOCAL's size word, a datagram's the payload word; a message new
    # to the lane (or published by it) is pushed to the mesh peers through
    # the send channel below, a known one counts a duplicate ---------------
    if M_GOSSIP in mp:
        gs = s.gossip
        g_lane = model == M_GOSSIP
        g_pub = is_timer & g_lane
        g_del = is_del & g_lane
        # a publish and a DELIVERY pop test AND set the message's bit; a
        # datagram CoDel lets through only tests it, for the elision below
        g_sets = g_pub | g_del
        g_pkt = deliver & g_lane
        g_mid = jnp.where(g_pub, size, plo)
        with jax.named_scope("gossip_seen"):
            # one word of the lane's bitmap row, picked by compare (the
            # row is ceil(M / 32) words: no gather)
            g_hit = (
                jnp.arange(gs.seen.shape[1], dtype=i32)[None, :]
                == (g_mid >> 5)[:, None]
            ) & (g_sets | g_pkt)[:, None]
            g_bit = jnp.where(g_hit, (jnp.int32(1) << (g_mid & 31))[:, None], 0)
            g_known = jnp.any((gs.seen & g_bit) != 0, axis=1)
            g_seen = gs.seen | jnp.where(g_sets[:, None], g_bit, 0)
        # in-window duplicate elision (argued at WINDOW_INERT_MODELS): a
        # copy the lane already knows, delivered before the window's end,
        # is counted here and queues no DELIVERY row
        g_elide = g_pkt & gossip_elides(g_known, td_hi, td_lo, we_hi, we_lo)
        ins_valid = ins_valid & ~g_elide
        s = s._replace(
            gossip_elided=s.gossip_elided + g_elide.sum(dtype=i32))
        g_first = g_del & ~g_known
        g_push = g_pub | g_first
        gl_hi, gl_lo = pair_max(gs.last_hi, gs.last_lo, thi, tlo)
        with jax.named_scope("gossip_age"):
            # the message's burst instant and the age's bucket, both
            # picked by compares against static tables (no gather); the
            # age is clamped one past the last edge: the overflow bucket
            b_hi = b_lo = jnp.zeros(n, dtype=i32)
            for b, t_b in enumerate(p.gossip_bursts):
                m_b = b * p.gossip_messages
                of_b = (g_mid >= m_b) & (g_mid < m_b + p.gossip_messages)
                b_hi = jnp.where(of_b, i32(t_b >> 31), b_hi)
                b_lo = jnp.where(of_b, i32(t_b & MASK31), b_lo)
            age = pair_sub_clamp(thi, tlo, b_hi, b_lo, AGE_EDGES_NS[-1] + 1)
            bucket = sum((age > e).astype(i32) for e in AGE_EDGES_NS)
            g_age = s.gossip_age + jnp.sum(
                (jnp.arange(len(AGE_EDGES_NS) + 1, dtype=i32)[:, None]
                 == bucket[None, :]) & g_first[None, :],
                axis=1, dtype=i32)
        s = s._replace(gossip_age=g_age, gossip=gs._replace(
            seen=g_seen,
            first=gs.first + g_first,
            dups=gs.dups + ((g_del & g_known) | g_elide),
            last_hi=jnp.where(g_first, gl_hi, gs.last_hi),
            last_lo=jnp.where(g_first, gl_lo, gs.last_lo),
        ))

    # ---- unified send channel (≤F sends per lane per slot, F =
    # ``sends_per_pop``; stream lanes send through the compacted channels
    # below, not this one) -------------------------------------------------
    send_phold = del_send_phold | loc_send_phold
    do_send = (
        send_phold | del_send_echo | mesh_tick | client_tick | ping_tick
    )

    # phold peer draw (consumes an app draw only where it happens; traced
    # only when phold lanes exist — the threefry is ~50 ops per slot)
    if M_PHOLD in mp:
        with jax.named_scope("phold_draw"):
            draw = rand_u32_lane(
                _seed_keys(p, tb),
                (lanes.astype(jnp.uint32) | jnp.uint32(rng_mod.APP_STREAM)),
                s.app_draws,
            )
            r = rng_mod.u32_below(draw, max(n - 1, 1), xp=jnp).astype(i32)
            phold_dst = jnp.where(n == 1, lanes, (lanes + 1 + r) % n)
        s = s._replace(app_draws=s.app_draws + send_phold)
    else:
        phold_dst = lanes

    # tgen-mesh round-robin peer
    if M_TGEN_MESH in mp:
        mesh_off = s.m_peer_offset % max(n - 1, 1)
        mesh_dst = (lanes + 1 + mesh_off) % n
        s = s._replace(
            m_peer_offset=s.m_peer_offset + jnp.where(mesh_tick, tb.p_stride, 0)
        )
    else:
        mesh_dst = lanes
    s = s._replace(m_sent=s.m_sent + (client_tick | ping_tick))

    dst = jnp.where(
        send_phold,
        phold_dst,
        jnp.where(
            del_send_echo,
            src,
            jnp.where(mesh_tick, mesh_dst, tb.p_peer),
        ),
    ).astype(i32)
    out_size = jnp.where(del_send_echo, size, tb.p_size).astype(i32)
    out_phi = out_plo = jnp.zeros(n, dtype=i32)
    if M_GOSSIP in mp:
        out_plo = jnp.where(g_push, g_mid, 0)
    if "phi" not in p.emit_pay_words:
        # the rows carry ``plo`` alone: ``phi`` rides no channel
        ins_phi = out_phi = ()

    def one_send(s, do_send, dst, rows=None, gathers=True):
        """One datagram a lane, as the oracle's ``send_packet``: the next
        sequence number, the up bucket's charge, the path's latency and
        its own loss draw — in the order of the calls.  The path's three
        words are a constant of the pair where the destination is static
        (``rows``: the ``(use, lat, thresh_u32, thresh_all)`` of a gossip
        lane's k-th peer) and gathered where it is picked at run time
        (``gathers``, static), as ``dst`` itself is: by one flat index
        from the packed ``tb.flat_*`` words, unpacked here to the same
        three."""

        # per-send sequence numbers
        snd_seq = s.send_seq
        s = s._replace(send_seq=s.send_seq + do_send, n_sends=s.n_sends + do_send)

        # up bucket
        out_bits = (out_size + FRAME_OVERHEAD_BYTES) * 8
        (up_tokens, up_nr_hi, up_nr_lo, up_ld_hi, up_ld_lo, dep_hi, dep_lo,
         up_wait) = (
            bucket_charge_vec(
                s.up_tokens, s.up_nr_hi, s.up_nr_lo, s.up_ld_hi, s.up_ld_lo,
                tb.up_rate, tb.up_burst, tb.up_kfull, tb.up_kfi,
                thi, tlo, out_bits, do_send, p.bucket_interval,
            )
        )
        s = s._replace(
            up_tokens=up_tokens, up_nr_hi=up_nr_hi, up_nr_lo=up_nr_lo,
            up_ld_hi=up_ld_hi, up_ld_lo=up_ld_lo,
        )
        if p.netobs:
            s = s._replace(
                nb_thr=s.nb_thr + up_wait,
                nb_txb=s.nb_txb + jnp.where(do_send, out_size, 0),
            )

        # loss (bootstrap window is loss-free; loss-free graphs skip the draw)
        with jax.named_scope("path_lookup"):
            # the run-time gathers apart from the draw and the compares
            # (``path_gather_load``): node_of[dst], then each packed word
            # at the pair's flat index.  On one graph node there are no
            # packed words: the [1, 1] lookup folds
            def gather(table, at):
                with jax.named_scope("path_gather"):
                    return table[at]

            words = None  # indexed as ``rows``: (-, lat, thresh_u32, all)
            if gathers:
                my_node = tb.node_of
                dst_node = gather(tb.node_of, dst)
                if not isinstance(tb.flat_lat, tuple):
                    flat = my_node * tb.lat.shape[-1] + dst_node
                    w_lat = gather(tb.flat_lat, flat)
                    words = (None, w_lat)  # loss-free: bit 31 is clear
                    if p.has_loss:
                        words = (None, w_lat & MASK31,
                                 gather(tb.flat_thresh, flat), w_lat < 0)

            def path_word(table, i):
                if not gathers:
                    return rows[i]
                word = (gather(table, (my_node, dst_node)) if words is None
                        else words[i])
                return word if rows is None else jnp.where(
                    rows[0], rows[i], word)

            lat = path_word(tb.lat, 1)  # int32
            if p.has_loss:
                u = rand_u32_lane(
                    _seed_keys(p, tb),
                    (lanes.astype(jnp.uint32) | jnp.uint32(rng_mod.LOSS_STREAM)),
                    snd_seq,
                )
                bs_hi, bs_lo = p.bootstrap_end >> 31, p.bootstrap_end & MASK31
                past_bootstrap = pair_ge(thi, tlo, bs_hi, bs_lo)
                lost = do_send & past_bootstrap & (
                    path_word(tb.thresh_all, 3)
                    | (u < path_word(tb.thresh_u32, 2))
                )
                s = s._replace(n_loss=s.n_loss + lost)
            else:
                lost = false_n

        if p.dynamic_runahead:
            # the smallest path latency of this slot's sends (the CPU law
            # records EVERY send, before the loss draw — mirror exactly)
            s = s._replace(
                min_used_lat=jnp.minimum(
                    s.min_used_lat, jnp.min(jnp.where(do_send, lat, NEVER32))
                )
            )
        arr_hi, arr_lo = pair_max(*pair_add32(dep_hi, dep_lo, lat), we_hi, we_lo)
        out_valid = do_send & ~lost
        out_auxh = pack_aux_hi(jnp.full(n, PACKET, dtype=i32), lanes)
        out_auxl = snd_seq

        # outbound pcap capture at DEPARTURE (pre-loss, like the CPU path)
        if p.pcap_any:
            pc_valid = do_send & tb.lane_pcap
            pc_time = t_join(dep_hi, dep_lo)
            pc_dst = dst.astype(i64)
            pc_seq = snd_seq.astype(i64)
            pc_size = out_size.astype(i64)
        else:
            pc_valid = pc_time = pc_dst = pc_seq = pc_size = ()
        return s, (
            do_send, dst, snd_seq, dep_hi, dep_lo, lost, arr_hi, arr_lo,
            out_valid, out_auxh, out_auxl,
            pc_valid, pc_time, pc_dst, pc_seq, pc_size,
        )

    # the handler's sends keep the oracle's order: F calls, state threaded
    # through (sequence numbers, bucket charges and loss draws in k order);
    # send 0 is every other model's one send.  [N] where a pop sends once
    # (the program every other model compiles to), [F, N] where it fans out
    n_f = p.sends_per_pop
    if n_f == 1:
        s, sent = one_send(s, do_send, dst)
    else:
        chain = ("send_seq", "n_sends", "up_tokens", "up_nr_hi", "up_nr_lo",
                 "up_ld_hi", "up_ld_lo", "n_loss", "min_used_lat", "nb_thr",
                 "nb_txb")

        # peer k's path is a constant of the lane (``tb.g_lat``; () on one
        # graph node, where the lookup folds and the trace is the
        # parent's).  Another model's send 0 still gathers, its words
        # selected by ``g_k`` as its ``dst`` is; sends k >= 1 are gossip's
        # alone, which the loop form sees (``k`` is a Python integer there,
        # and traced under the rolled scan, where every k gathers: right,
        # only not free)
        with_rows = not isinstance(tb.g_lat, tuple)
        mixed = bool(set(mp) & PATH_GATHER_MODELS)
        fan_xs = (jnp.arange(n_f) == 0, tb.g_peers.T)
        if with_rows:
            fan_xs += ((tb.g_lat, tb.g_thresh_u32, tb.g_thresh_all),
                       np.arange(n_f))

        def fan_step(carry, x):
            # gossipsub forwards to the mesh less the peer the message
            # came from; a publish goes to all of it
            first_k, peer_k, *path = x
            g_k = g_push & (g_pub | (peer_k != src))
            static = {}
            if with_rows:
                path_k, k = path
                static = dict(rows=(g_k, *path_k), gathers=mixed and (
                    not jax.core.is_concrete(k) or k == 0))
            st, o = one_send(
                s._replace(**dict(zip(chain, carry[0]))),
                (do_send & first_k) | g_k, jnp.where(g_k, peer_k, dst),
                **static,
            )
            return (tuple(getattr(st, f) for f in chain),
                    carry[1] + g_k), o

        # a rolled scan on XLA:CPU, where the unrolled chain of charges
        # costs 35x more with every send (1.6 ms a slot at F = 4, 2 s at
        # 6); the accelerator takes the loop form, as the stream burst does
        with jax.named_scope("gossip_fanout"):
            (chained, g_sends), sent = scan_or_unroll(
                fan_step,
                (tuple(getattr(s, f) for f in chain), s.gossip.sends),
                fan_xs, n_f, spmd_unroll=True,
            )
        s = s._replace(**dict(zip(chain, chained)),
                       gossip=s.gossip._replace(sends=g_sends))
    (do_send, dst, snd_seq, dep_hi, dep_lo, lost, arr_hi, arr_lo,
     out_valid, out_auxh, out_auxl,
     pc_valid, pc_time, pc_dst, pc_seq, pc_size) = sent
    if n_f > 1:
        out_size, out_phi, out_plo = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_f, n)),
            (out_size, out_phi, out_plo),
        )

    # ---- compacted stream send/arm channels ([2S] and [B, S]) ------------
    # Slot-0 control send, then the burst's data segments, charging the
    # endpoint lane's up bucket and drawing losses IN ORDER exactly like
    # the CPU driver's per-api.send sequence; engine send seqs rank
    # slot-0 first, then the burst prefix.  Per-lane counters and bucket
    # state round-trip through one row gather + one write-unique scatter.
    if sp:
        lane_cols = [s.up_tokens, s.up_nr_hi, s.up_nr_lo, s.up_ld_hi,
                     s.up_ld_lo, s.send_seq, s.local_seq, s.n_sends,
                     s.n_loss]
        if p.netobs:
            # the netobs counters round-trip through the same gather /
            # write-unique scatter as the send bookkeeping
            lane_cols += [s.nb_txb, s.nb_thr]
        lane_mat = jnp.stack(lane_cols, axis=1)
        lm = lane_mat[el]  # [2S, 9(+2)] row gather
        g_tok, g_nrh, g_nrl = lm[:, 0], lm[:, 1], lm[:, 2]
        g_ldh, g_ldl = lm[:, 3], lm[:, 4]
        g_sseq, g_lseq = lm[:, 5], lm[:, 6]
        g_nsend, g_nloss = lm[:, 7], lm[:, 8]
        if p.netobs:
            g_txb, g_thr = lm[:, 9], lm[:, 10]

        # slot-0 control send
        se_size = sem.send_size
        se_bits = (se_size + FRAME_OVERHEAD_BYTES) * 8
        (g_tok, g_nrh, g_nrl, g_ldh, g_ldl, se_dep_hi, se_dep_lo,
         se_wait) = (
            bucket_charge_vec(
                g_tok, g_nrh, g_nrl, g_ldh, g_ldl,
                tb.flow_up_rate, tb.flow_up_burst, tb.flow_up_kfull,
                tb.flow_up_kfi, ethi, etlo, se_bits, st_send,
                p.bucket_interval,
            )
        )
        se_seq = g_sseq
        g_sseq = g_sseq + st_send
        g_nsend = g_nsend + st_send
        if p.netobs:
            g_txb = g_txb + jnp.where(st_send, se_size, 0)
            g_thr = g_thr + se_wait
        if p.has_loss:
            bs_hi2, bs_lo2 = p.bootstrap_end >> 31, p.bootstrap_end & MASK31
            e_past_bs = pair_ge(ethi, etlo, bs_hi2, bs_lo2)
            eu = rand_u32_lane(
                _seed_keys(p, tb),
                (el.astype(jnp.uint32) | jnp.uint32(rng_mod.LOSS_STREAM)),
                se_seq,
            )
            se_lost = st_send & e_past_bs & (
                tb.flow_thresh_all | (eu < tb.flow_thresh_u32)
            )
            g_nloss = g_nloss + se_lost
        else:
            se_lost = false_e
        if p.dynamic_runahead:
            s = s._replace(min_used_lat=jnp.minimum(
                s.min_used_lat,
                jnp.min(jnp.where(st_send, tb.flow_lat, NEVER32)),
            ))
        se_thi, se_tlo = pair_max(
            *pair_add32(se_dep_hi, se_dep_lo, tb.flow_lat), we_hi, we_lo
        )
        se_valid = st_send & ~se_lost
        se_phi, se_plo = lstr.pack_pay(
            sem.send_flags, sem.send_seq, sem.send_ack
        )

        # RTO arm channel (LOCAL self-insert at the endpoint lane)
        sa_valid = st_rto
        sa_thi, sa_tlo = sem.rto_thi, sem.rto_tlo
        sa_auxl = g_lseq
        g_lseq = g_lseq + sa_valid

        # burst chain on the CLIENT half only (the law's role gate makes
        # server rows' bursts empty)
        cl_sl = slice(0, s_flows)
        b_lat_c = tb.flow_lat[cl_sl]
        cthi, ctlo = ethi[cl_sl], etlo[cl_sl]
        false_c = jnp.zeros(s_flows, dtype=bool)
        if p.has_loss:
            b_thresh_u32 = tb.flow_thresh_u32[cl_sl]
            b_thresh_all = tb.flow_thresh_all[cl_sl]
            c_past_bs = e_past_bs[cl_sl]
        cl_lanes_u32 = el[cl_sl].astype(jnp.uint32)

        def bstep_body(carry, cols, first: bool):
            (tok, nrh, nrl, ldh, ldl, nloss, mul, sent_before,
             btxb, bthr) = carry
            bm, bflags, bunit, back, bsize = cols
            bbits = (bsize + FRAME_OVERHEAD_BYTES) * 8
            if first:
                # only unit 1 can see a pending refill; later units'
                # charge clock is last_depart, provably short of
                # next_refill, so they take the reduced chained law
                tok, nrh, nrl, ldh, ldl, bdep_hi, bdep_lo, bwait = (
                    bucket_charge_vec(
                        tok, nrh, nrl, ldh, ldl,
                        tb.flow_up_rate[cl_sl], tb.flow_up_burst[cl_sl],
                        tb.flow_up_kfull[cl_sl], tb.flow_up_kfi[cl_sl],
                        cthi, ctlo, bbits, bm, p.bucket_interval,
                    )
                )
            else:
                tok, nrh, nrl, ldh, ldl, bdep_hi, bdep_lo, bwait = (
                    bucket_charge_chained_vec(
                        tok, nrh, nrl, ldh, ldl, tb.flow_up_rate[cl_sl],
                        tb.flow_up_burst[cl_sl], bbits, bm,
                        p.bucket_interval, cthi, ctlo,
                    )
                )
            if p.netobs:
                btxb = btxb + jnp.where(bm, bsize, 0)
                bthr = bthr + bwait
            bseq = se_seq[cl_sl] + sent_before
            if p.has_loss:
                bu = rand_u32_lane(
                    _seed_keys(p, tb),
                    (cl_lanes_u32 | jnp.uint32(rng_mod.LOSS_STREAM)),
                    bseq,
                )
                blost = bm & c_past_bs & (
                    b_thresh_all | (bu < b_thresh_u32)
                )
                nloss = nloss + blost
            else:
                blost = false_c
            if p.dynamic_runahead:
                mul = jnp.minimum(
                    mul, jnp.min(jnp.where(bm, b_lat_c, NEVER32))
                )
            barr_hi, barr_lo = pair_max(
                *pair_add32(bdep_hi, bdep_lo, b_lat_c), we_hi, we_lo
            )
            bphi, bplo = lstr.pack_pay(bflags, bunit, back)
            outs = (
                bm & ~blost, barr_hi, barr_lo, bseq, bsize, bphi, bplo,
                blost, bdep_hi, bdep_lo,
            )
            return (tok, nrh, nrl, ldh, ldl, nloss, mul,
                    sent_before + bm, btxb, bthr), outs

        zero_c = jnp.zeros(s_flows, dtype=i32)
        carry0 = (
            g_tok[cl_sl], g_nrh[cl_sl], g_nrl[cl_sl], g_ldh[cl_sl],
            g_ldl[cl_sl], g_nloss[cl_sl], s.min_used_lat,
            st_send[cl_sl].astype(i32), zero_c, zero_c,
        )
        # the burst chain consumes the first five columns; the sixth
        # (retransmit marker) is a flowtrace-only channel read below
        st_burst_c = jax.tree.map(lambda a: a[:, cl_sl], tuple(st_burst[:5]))
        first_cols = jax.tree.map(lambda a: a[0], st_burst_c)
        rest_cols = jax.tree.map(lambda a: a[1:], st_burst_c)
        carry, out0 = bstep_body(carry0, first_cols, True)
        n_rest = st_burst_c[0].shape[0] - 1
        if n_rest:
            carry, bouts_rest = scan_or_unroll(
                lambda c, x: bstep_body(c, x, False), carry, rest_cols,
                n_rest,
            )
            bouts = jax.tree.map(
                lambda a0, ar: jnp.concatenate([a0[None], ar]),
                out0, bouts_rest,
            )
        else:
            bouts = jax.tree.map(lambda a0: a0[None], out0)
        (tok_c, nrh_c, nrl_c, ldh_c, ldl_c, nloss_c, mul, sent_after,
         btxb_c, bthr_c) = carry
        if p.dynamic_runahead:
            s = s._replace(min_used_lat=mul)
        sv_sl = slice(s_flows, s2)
        g_tok = jnp.concatenate([tok_c, g_tok[sv_sl]])
        g_nrh = jnp.concatenate([nrh_c, g_nrh[sv_sl]])
        g_nrl = jnp.concatenate([nrl_c, g_nrl[sv_sl]])
        g_ldh = jnp.concatenate([ldh_c, g_ldh[sv_sl]])
        g_ldl = jnp.concatenate([ldl_c, g_ldl[sv_sl]])
        g_nloss = jnp.concatenate([nloss_c, g_nloss[sv_sl]])
        burst_total = sent_after - st_send[cl_sl].astype(i32)
        g_sseq = g_sseq + jnp.concatenate(
            [burst_total, jnp.zeros(s_flows, dtype=i32)]
        )
        g_nsend = g_nsend + jnp.concatenate(
            [burst_total, jnp.zeros(s_flows, dtype=i32)]
        )
        if p.netobs:
            g_txb = g_txb + jnp.concatenate([btxb_c, zero_c])
            g_thr = g_thr + jnp.concatenate([bthr_c, zero_c])

        # write-back: one masked row scatter (at most one endpoint of a
        # lane is stimulated per slot, so indices are write-unique)
        row_cols = [g_tok, g_nrh, g_nrl, g_ldh, g_ldl, g_sseq, g_lseq,
                    g_nsend, g_nloss]
        if p.netobs:
            row_cols += [g_txb, g_thr]
        new_rows = jnp.stack(row_cols, axis=1)
        sc_idx = jnp.where(stream_stim, el, jnp.int32(n))
        lane_mat = lane_mat.at[sc_idx].set(new_rows, mode="drop")
        s = s._replace(
            up_tokens=lane_mat[:, 0], up_nr_hi=lane_mat[:, 1],
            up_nr_lo=lane_mat[:, 2], up_ld_hi=lane_mat[:, 3],
            up_ld_lo=lane_mat[:, 4], send_seq=lane_mat[:, 5],
            local_seq=lane_mat[:, 6], n_sends=lane_mat[:, 7],
            n_loss=lane_mat[:, 8],
        )
        if p.netobs:
            s = s._replace(nb_txb=lane_mat[:, 9], nb_thr=lane_mat[:, 10])

        (bo_valid, bo_thi, bo_tlo, bo_auxl, bo_size, bo_phi, bo_plo,
         blost_all, bdep_hi_all, bdep_lo_all) = bouts  # [B, S] each
        if p.stream_pcap and p.log_capacity:
            # outbound captures at bucket departure, PRE-loss (the CPU
            # path's capture point); stream payloads synthesize from
            # sizes alone on both backends, so (time, seq, size) + the
            # static flow tables reproduce the files byte-identically
            spc_valid = st_send & tb.flow_pcap
            spc_time = t_join(se_dep_hi, se_dep_lo)
            spc_seq = se_seq.astype(i64)
            spc_size = se_size.astype(i64)
            bpc_valid = (bo_valid | blost_all) & tb.flow_pcap[cl_sl][None, :]
            bpc_time = t_join(bdep_hi_all, bdep_lo_all)
            bpc_seq = bo_auxl.astype(i64)
            bpc_size = bo_size.astype(i64)
        else:
            spc_valid = spc_time = spc_seq = spc_size = ()
            bpc_valid = bpc_time = bpc_seq = bpc_size = ()
        if p.log_capacity:
            et64 = t_join(ethi, etlo)
            srec_valid = se_lost
            srec_time = et64
            srec_seq = se_seq.astype(i64)
            srec_size = se_size.astype(i64)
            bb = bo_valid.shape[0]
            brec_valid = blost_all
            brec_time = jnp.broadcast_to(et64[cl_sl][None, :],
                                         (bb, s_flows))
            brec_seq = bo_auxl.astype(i64)
            brec_size = bo_size.astype(i64)
        else:
            srec_valid = srec_time = srec_seq = srec_size = ()
            brec_valid = brec_time = brec_seq = brec_size = ()
    else:
        se_valid = se_thi = se_tlo = se_phi = se_plo = ()
        se_seq = se_size = ()
        sa_valid = sa_thi = sa_tlo = sa_auxl = ()
        bo_valid = bo_thi = bo_tlo = bo_auxl = bo_size = bo_phi = bo_plo = ()
        srec_valid = srec_time = srec_seq = srec_size = ()
        brec_valid = brec_time = brec_seq = brec_size = ()
        spc_valid = spc_time = spc_seq = spc_size = ()
        bpc_valid = bpc_time = bpc_seq = bpc_size = ()

    # ---- local arm channels ---------------------------------------------
    has_timer = (
        (model == M_TGEN_MESH) | (model == M_TGEN_CLIENT) | (model == M_PING_CLIENT)
    )
    rearm_timer = (
        (is_start & has_timer)
        | mesh_tick
        | client_tick
        | ping_tick
        | (is_timer & (model == M_TGEN_MESH) & (n == 1))
    )
    rearm = rearm_timer
    ti_hi, ti_lo = pair_add_pair(thi, tlo, tb.p_int_hi, tb.p_int_lo)
    arm_thi, arm_tlo = ti_hi, ti_lo
    arm_size = jnp.zeros(n, dtype=i32)
    arm_plo = jnp.zeros(n, dtype=i32)
    arm_auxh = pack_aux_hi(jnp.full(n, LOCAL, dtype=i32), lanes)
    arm_auxl = s.local_seq
    s = s._replace(local_seq=s.local_seq + rearm)
    # (stream RTO arms ride the compacted sa_* channel above: stream
    # lanes never take this generic timer re-arm, so their local_seq is
    # consumed only through the gathered counters)

    # ---- flowtrace channel (obs/flowtrace.py): raw lifecycle observations
    # for this slot, reduced to events post-scan (_build_iter).  Stamps
    # follow the oracle laws exactly: send/loss at stimulus t, TB wait at
    # bucket departure, queue-enter/delivery/codel at arrival time.
    if p.flowtrace:
        ft = {
            # generic [N] sends (lane -> dst)
            "sd_valid": do_send, "sd_dst": dst, "sd_seq": snd_seq,
            "sd_size": out_size, "sd_thi": thi, "sd_tlo": tlo,
            "sd_dhi": dep_hi, "sd_dlo": dep_lo, "sd_lost": lost,
            "sd_ahi": arr_hi, "sd_alo": arr_lo,
            # generic [N] packet arrivals (src -> lane)
            "ar_valid": is_pkt, "ar_src": src, "ar_seq": seq,
            "ar_size": size, "ar_thi": thi, "ar_tlo": tlo,
            "ar_dhi": td_hi, "ar_dlo": td_lo, "ar_drop": codel_drop,
        }
        if sp:
            ft.update({
                # stream slot-0 control sends [2S] (endpoint -> peer)
                "ss_valid": st_send, "ss_retx": sem.send_retx & st_send,
                "ss_seq": se_seq, "ss_size": se_size,
                "ss_thi": ethi, "ss_tlo": etlo,
                "ss_dhi": se_dep_hi, "ss_dlo": se_dep_lo,
                "ss_lost": se_lost, "ss_ahi": se_thi, "ss_alo": se_tlo,
                # stream burst data segments [B, S] (client -> server)
                "bs_valid": bo_valid | blost_all,
                "bs_retx": st_burst[5][:, cl_sl],
                "bs_seq": bo_auxl, "bs_size": bo_size,
                "bs_thi": jnp.broadcast_to(cthi[None, :], bo_valid.shape),
                "bs_tlo": jnp.broadcast_to(ctlo[None, :], bo_valid.shape),
                "bs_dhi": bdep_hi_all, "bs_dlo": bdep_lo_all,
                "bs_lost": blost_all, "bs_ahi": bo_thi, "bs_alo": bo_tlo,
            })
    else:
        ft = ()

    # ---- log record (≤1 per send channel row: packet outcome, or send
    # loss; a PACKET pop's one record rides row 0 of a fan-out) ------------
    pk_rows = pk_rec_valid if n_f == 1 else (
        pk_rec_valid[None, :] & (jnp.arange(n_f) == 0)[:, None]
    )
    rec_valid = pk_rows | lost
    if p.log_capacity:
        rec_time = jnp.where(pk_rows, t_join(td_hi, td_lo), t64)
        rec_src = jnp.where(pk_rows, src, lanes).astype(i64)
        rec_dst = jnp.where(pk_rows, lanes, dst).astype(i64)
        rec_seq = jnp.where(pk_rows, seq, snd_seq).astype(i64)
        rec_size = jnp.where(pk_rows, size, out_size).astype(i64)
        rec_outcome = jnp.where(pk_rows, pk_rec_outcome, DROP_LOSS).astype(i64)
    else:
        z64 = jnp.zeros(rec_valid.shape, dtype=i64)
        rec_time = rec_src = rec_dst = rec_seq = rec_size = rec_outcome = z64

    emit = _SlotEmit(
        ins_valid, ins_thi, ins_tlo, ins_auxh, ins_auxl, ins_size, ins_phi,
        ins_plo,
        rearm, arm_thi, arm_tlo, arm_auxh, arm_auxl, arm_size, arm_plo,
        out_valid, dst, arr_hi, arr_lo, out_auxh, out_auxl, out_size,
        out_phi, out_plo,
        se_valid, se_thi, se_tlo, se_seq, se_size, se_phi, se_plo,
        sa_valid, sa_thi, sa_tlo, sa_auxl,
        bo_valid, bo_thi, bo_tlo, bo_auxl, bo_size, bo_phi, bo_plo,
        srec_valid, srec_time, srec_seq, srec_size,
        brec_valid, brec_time, brec_seq, brec_size,
        spc_valid, spc_time, spc_seq, spc_size,
        bpc_valid, bpc_time, bpc_seq, bpc_size,
        pc_valid, pc_time, pc_dst, pc_seq, pc_size,
        rec_valid, rec_time, rec_src, rec_dst, rec_seq, rec_size, rec_outcome,
        ft,
    )
    return s, emit


def _window_gather(arrs, start, c):
    """Gather the contiguous windows ``arr[start[n] : start[n]+c]`` for all
    lanes — but as one *aligned row* gather plus a barrel shift, because TPU
    per-element gathers serialize (~20ns/elem) while row gathers and static
    rolls vectorize.  ``arrs`` is a list of flat [m] arrays sharing ``start``;
    entries past m are garbage the caller must mask (segment counts do).
    Arrays are processed in same-dtype groups at their NATIVE width — the
    barrel passes are memory-bound, so int32 operands move half the bytes.

    The layout rule (ISSUE 33): **a gathered row must be at least a tile
    wide, and lanes go minor exactly once.**  The chip stores an array in
    (8, 128) tiles, so a gather whose rows are ``v`` = 8 wide writes a
    buffer padded sixteen-fold, and putting the lane axis minor afterwards
    took two relayout copies of it (82 MB at 10 000 lanes, 820 MB at
    100 000: a fifth to a third of a mesh program's device time).  So the
    table holds ALL operands and BOTH aligned rows of a window in one row —
    ``tab2[q] = [arr_0[qv : qv+2v], arr_1[qv : qv+2v], ...]``, A*2v wide —
    the gather takes one index per lane, and ONE 2-D transpose puts the
    lanes minor, the layout the barrel shift and the merge run in.  One
    law at every width; a row wider than a tile simply spans tiles."""
    m = arrs[0].shape[0]
    # the barrel shift decomposes the offset over bits, so the row width
    # must be a power of two >= c (c itself is any user-chosen capacity)
    v = 1 << max(c - 1, 1).bit_length()
    pad = (-m) % v
    nrow = (m + pad) // v
    q = jnp.clip(start // v, 0, nrow - 1)  # [N]: one index per lane
    sh = (start % v).astype(jnp.int32)

    def gather_group(group):
        a = len(group)
        tab = jnp.stack(group)  # [A, m], uniform dtype
        tab = jnp.pad(tab, ((0, 0), (0, pad))).reshape(a, nrow, v)
        # a window reaches into the next aligned row; past the last row
        # that is the last row again (entries past m: garbage)
        nxt = jnp.concatenate([tab[:, 1:], tab[:, -1:]], axis=1)
        tab2 = jnp.concatenate([tab, nxt], axis=2)  # [A, nrow, 2v]
        tab2 = tab2.transpose(1, 0, 2).reshape(nrow, a * 2 * v)
        block = tab2[q].T.reshape(a, 2 * v, -1)  # [A, 2v, N]: lanes minor
        block = block.transpose(0, 2, 1)  # [A, N, 2v] (no data moves)
        b = v >> 1
        while b:
            rolled = jnp.concatenate([block[:, :, b:], block[:, :, :b]], axis=2)
            block = jnp.where(((sh & b) != 0)[None, :, None], rolled, block)
            b >>= 1
        return [block[i, :, :c] for i in range(a)]

    # group by dtype, preserving caller order in the result
    by_dtype: dict = {}
    for i, a in enumerate(arrs):
        by_dtype.setdefault(a.dtype, []).append((i, a))
    out = [None] * len(arrs)
    with jax.named_scope("window_gather"):
        for _dt, items in by_dtype.items():
            gathered = gather_group([a for _i, a in items])
            for (i, _a), g in zip(items, gathered):
                out[i] = g
    return out


def _cross_block(ops, start, cnt, cx):
    """Each lane's slice ``[start, start + cnt)`` of destination-sorted
    columns as a lane-aligned block: ``in_seg`` [L, Cx] and every operand's
    window masked to it — the two time words (``ops[:2]``) to NEVER, the
    rest to 0.  A lane with more than ``cx`` entries keeps the first cx."""
    in_seg = jnp.arange(cx, dtype=jnp.int32)[None, :] < cnt[:, None]
    words = [
        jnp.where(in_seg, w, NEVER32 if i < 2 else 0).astype(jnp.int32)
        for i, w in enumerate(_window_gather(ops, start, cx))
    ]
    return in_seg, words


#: the exchange's segment bounds come from ONE one-hot histogram matmul
#: while its operands — entries x (ceil((n + 1) / 128) + 128) one-hot
#: columns — stay inside this many elements (128 MiB of f32); wider
#: exchanges accumulate the histogram over chunks of the entries.  With K
#: pops per iteration an [N]-wide exchange has K * n entries, and the first
#: wide program has 38 837 lanes at K = 2 and 16 384 at K = 8.  A shape the
#: code observes, not an option: tests patch it
_ONEHOT_BUDGET = 1 << 25


def exchange_bounds_wide(m_entries: int, n: int) -> bool:
    """Whether an exchange of ``m_entries`` sends over ``n`` lanes is past
    the one-hot budget and finds its bounds chunk by chunk (static per
    compiled program: the ``exchange_bounds_wide`` gauge)."""
    return m_entries * (-(-(n + 1) // 128) + 128) > _ONEHOT_BUDGET


def _bounds_by_onehot(dst, n):
    """``(start, cnt)`` [n] of each lane's slice of the destination-sorted
    exchange, from the pre-sort column ``dst`` [m] (an invalid send has
    ``dst == n``; a histogram is order-free).  NOT jnp.searchsorted — its
    binary search lowers to a lax.while_loop of per-element gathers inside
    the hot body (18 steps of 100 001 gathers at 100k lanes: 12.0 ms on a
    v5e).  The counts are a one-hot HISTOGRAM as a single MXU matmul: dst
    decomposes as (dst >> 7, dst & 127) and counts[q, r] = sum_m
    oh_q[m, q] * oh_r[m, r] — exact in f32 (counts < 2**24) — then one
    small 2D cumsum gives the exclusive prefix (= segment starts) with no
    data-dependent control flow.  The one-hot operands are
    [m, ceil((n+1)/128)] and [m, 128]: ~6 MB at 10k lanes and K = 2, but
    m * n / 128 grows with the square of the width (728 MB at 100k), hence
    _ONEHOT_BUDGET and ``_bounds_by_onehot_chunked``."""
    dq = -(-(n + 1) // 128)
    oh_q = (
        (dst[:, None] >> 7)
        == jnp.arange(dq, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32)
    oh_r = (
        (dst[:, None] & 127)
        == jnp.arange(128, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32)
    counts_grid = lax.dot_general(
        oh_q, oh_r, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # [dq, 128]
    row_cum = jnp.cumsum(counts_grid, axis=1)
    row_tot = row_cum[:, -1]
    row_off = jnp.cumsum(row_tot) - row_tot  # exclusive row offsets
    start_grid = row_cum - counts_grid + row_off[:, None]
    return start_grid.reshape(-1)[:n], counts_grid.reshape(-1)[:n]


#: sends per step of the wide law's histogram: 8 192 x (782 + 128) bf16
#: one-hot elements at 100 000 lanes, 15 MB of temporaries
_ONEHOT_CHUNK = 8192


def _bounds_by_onehot_chunked(dst, n):
    """The same ``(start, cnt)`` as ``_bounds_by_onehot`` where its one-hot
    operands would not fit ``_ONEHOT_BUDGET``: the histogram matmul
    accumulated over chunks of ``_ONEHOT_CHUNK`` sends in one ``fori_loop``
    — a slice of the column, two compares and an MXU pass per step; no
    gather, no scatter, no search.  The operands are 0 / 1 in bf16 (exact)
    and the sums f32 (exact below 2**24).  The low 7 bits are encoded
    ``<=`` rather than ``==``, so a row of the grid is already its own
    running sum and ``upto[q, r]`` counts the sends to lanes ``128 q ..
    128 q + r``: what is left after the loop is the offset of each row, a
    cumsum over ``ceil((n + 1) / 128)`` row totals.  m * n multiply-adds,
    as the one-shot law: 0.53 ms at (m, n) = (200 000, 100 000) on a v5e
    and 1.9 ms at m = 800 000, where the searchsorted it replaced took
    12.0 and 13.4 (PERF.md §6, PR 29, which also says why not a sort: the
    chip's peak of memory counts a program's CODE, and a second sort is
    1.5 MB of it)."""
    m = dst.shape[0]
    dq = -(-(n + 1) // 128)
    steps = -(-m // _ONEHOT_CHUNK)
    # padding rows fall in grid row dq: they match no q
    dst = jnp.pad(
        dst, (0, steps * _ONEHOT_CHUNK - m), constant_values=dq * 128
    )
    qs = jnp.arange(dq, dtype=jnp.int32)[:, None]
    rs = jnp.arange(128, dtype=jnp.int32)[None, :]

    def add_chunk(i, upto):
        d = lax.dynamic_slice(dst, (i * _ONEHOT_CHUNK,), (_ONEHOT_CHUNK,))
        oh_q = (qs == (d >> 7)[None, :]).astype(jnp.bfloat16)  # [dq, chunk]
        le_r = ((d & 127)[:, None] <= rs).astype(jnp.bfloat16)  # [chunk, 128]
        return upto + jnp.dot(oh_q, le_r, preferred_element_type=jnp.float32)

    upto = lax.fori_loop(
        0, steps, add_chunk, jnp.zeros((dq, 128), dtype=jnp.float32)
    ).astype(jnp.int32)
    row_tot = upto[:, -1]
    upto = upto + (jnp.cumsum(row_tot) - row_tot)[:, None]
    # upto.reshape(-1)[d] = sends to lanes 0..d = start[d + 1]
    bounds = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), upto.reshape(-1)[:n]]
    )
    return bounds[:n], bounds[1:] - bounds[:n]


#: a fan-out program's slot budget is a whole number of these many (pop,
#: lane) slots (``LaneParams.exchange_slot_budget``): the compacted
#: columns' minor dimension fills the chip's 128-wide tiles.  A shape the
#: code observes, not an option (tests patch the budget itself, as they
#: patch ``_ONEHOT_BUDGET``)
_SLOT_TILE = 128


def _sorted_exchange(flat_ops, n):
    """The exchange proper over flat columns of any length ``[m]``: ONE
    single-key sort by destination (``flat_ops[0]``; an invalid send has
    ``dst == n``), then each lane's slice bounds of the sorted columns by
    the law the static shape picks (``exchange_bounds_wide``; both give
    the same integers).  Returns the sorted columns less ``dst``, and
    ``start`` / ``cnt`` ``[n]``."""
    # the sort need not be stable: within a destination's segment the real
    # entries carry the 4-word event key, a TOTAL order (ties impossible
    # between distinct events), and the merge sort below re-orders by that
    # key anyway.  Unstable drops XLA's hidden iota tiebreaker operand
    # from every compare-exchange stage.  The one observable: when a
    # segment overflows cross_cap, WHICH entries are shed is no longer
    # emission order but the sort network's choice — still deterministic
    # for a given compiled program, and strict mode (the default) raises
    # on any shed; non-strict overflow was already documented as
    # non-parity (see tpu_engine.py's strict_capacity note).
    with jax.named_scope("exchange_sort"):
        sorted_ops = lax.sort(
            tuple(flat_ops), dimension=0, num_keys=1, is_stable=False
        )
    with jax.named_scope("exchange_bounds"):
        if exchange_bounds_wide(flat_ops[0].shape[0], n):
            start, cnt = _bounds_by_onehot_chunked(flat_ops[0], n)
        else:
            start, cnt = _bounds_by_onehot(flat_ops[0], n)
    return list(sorted_ops[1:]), start, cnt


def _rank_sending(emits: _SlotEmit, budget, n, pay):
    """What a fan-out iteration's exchange reads, built once an iteration
    from the ``[K, F, N]`` send channel: how many (pop, lane) slots put a
    datagram into the exchange (some ``out_valid[k, :, lane]`` set); their
    slot ids ranked to the front by ONE single-operand sort of the ``K x
    N`` slot ids (a slot that did not send is keyed past them all), padded
    to whole passes of ``budget``; and the slots' words as a ``[K x N, 4 F
    + 2 + P]`` table of ONE row a slot — its F destinations (``n`` where
    the send is lost or unused), arrival pairs and sequence numbers, and
    the words of its pop (``auxh``, ``size`` and the P payload words
    ``pay`` the rows carry: one a slot, not F)."""
    k, f, lanes_n = emits.out_valid.shape
    slots = k * lanes_n
    with jax.named_scope("exchange_compact"):
        sending = jnp.any(emits.out_valid, axis=1).reshape(-1)
        ranked = lax.sort(
            jnp.where(sending, jnp.arange(slots, dtype=jnp.int32),
                      jnp.int32(slots)),
            dimension=0, is_stable=False,
        )
        ranked = jnp.pad(ranked, (0, (-slots) % budget),
                         constant_values=slots)
        dst = jnp.where(emits.out_valid, emits.out_dst, jnp.int32(n))
        table = jnp.concatenate(
            [dst, emits.out_thi, emits.out_tlo, emits.out_auxl]
            + [getattr(emits, "out_" + w)[:, :1]
               for w in ("auxh", "size", *pay)],
            axis=1,
        )  # [K, 4F + 2 + P, N]
        table = table.transpose(0, 2, 1).reshape(slots, -1)
    return sending.sum(dtype=jnp.int32), ranked, table


def _compact_sends(ranked, table, i, budget, n, f):
    """Pass ``i`` of a fan-out iteration's exchange: the flat columns
    ``_sorted_exchange`` takes (``dst``, then a row's words: the arrival
    pair, ``auxh``, ``auxl``, ``size`` and the payload words the table
    holds — seven columns at one payload word) over the ``i``-th
    ``budget`` of the ranked sending slots — ``budget x F`` rows where the
    send channel has ``K x F x N``.  A slot's words are fetched as ONE row
    of the table and one 2-D transpose puts the slots minor again, where
    every word's ``[F, budget]`` block is the flat column as it lies:
    ``_window_gather``'s layout rule (a gathered row at least a tile wide,
    the long axis minor once), and never an element gather out of an
    ``[N]``-minor send word (6.7–11.2 ns an element on a v5e, PERF.md §6
    PR 41).  Rows past the last sending slot carry ``dst == n`` (the
    table's own for a lost or unused send)."""
    slots, width = table.shape
    with jax.named_scope("exchange_compact"):
        pick = lax.dynamic_slice(ranked, (i * budget,), (budget,))
        block = table[jnp.minimum(pick, slots - 1)].T  # [4F + 2 + P, budget]

        def per_send(w):
            return block[w * f:(w + 1) * f]  # [F, budget]

        def per_pop(w):
            return jnp.broadcast_to(block[4 * f + w][None, :], (f, budget))

        # a slot past the last sending one reads SOME row: only its
        # destinations have to say so
        cols = [
            jnp.where((pick < slots)[None, :], per_send(0), jnp.int32(n)),
            per_send(1), per_send(2),  # arrival pair
            per_pop(0), per_send(3),  # auxh, auxl
            # size, the payload words
            *(per_pop(w) for w in range(1, width - 4 * f)),
        ]
        return [c.reshape(-1) for c in cols]


def _row_fill(mthi):
    """Live events per row of a merged block ``[rows, C + ...]`` (an empty
    slot's time is the NEVER pair): what ``queue_peak`` is the maximum
    of."""
    return (mthi != NEVER32).sum(axis=1, dtype=jnp.int32)


def _merge_append(p: LaneParams, tb: LaneTables, s: LaneState,
                  emits: _SlotEmit, divert: bool = False):
    """Append all generated events by **merge**, not scatter (TPU scatters
    serialize; sorts and gathers vectorize):

    1. same-lane channels (delivery self-inserts, timer re-arms) are already
       lane-aligned ``[N, 2K]`` blocks (``[N, K]`` when every model is
       passive) — invalid entries get time=NEVER;
    2. outbound packets take one single-key sort by destination (unstable —
       the event key is re-sorted below); each lane's slice bounds come from
       a one-hot histogram matmul + 2D cumsum (``_bounds_by_onehot``: one
       MXU pass over operands that grow with lanes squared) or, past
       ``_ONEHOT_BUDGET`` — 38 837 lanes at K = 2, 16 384 at K = 8 — from
       the same histogram accumulated over chunks of the sends
       (``_bounds_by_onehot_chunked``: the same multiply-adds, a few MB of
       operands at any width); the slices are gathered into a lane-aligned
       ``[N, Cx]`` block (``Cx = cross_cap``; ``_window_gather``: tile-wide
       rows, lanes minor once) — the batched equivalent of the reference's
       cross-host queue push (worker.rs:603-615); stream-endpoint lanes'
       slices reach the tier by the same gather over ``[2S]`` lanes.
       **Where a pop fans out** (``p.sends_per_pop`` > 1, static) the
       exchange runs over the (pop, lane) slots that SENT, not over every
       slot that could have: the sending slots are ranked to the front
       (``_rank_sending``: one single-operand sort of the K x N slot ids)
       and taken ``p.exchange_slot_budget`` a pass (S_b = ceil(K N / F)
       rounded up to ``_SLOT_TILE``: a static shape law, the compacted
       exchange as wide as a one-send program's), each slot's F sends
       fetched as ONE table row (``_compact_sends``), then the same sort,
       bounds and cross block over ``S_b x F`` rows and step 3.  One
       ``lax.while_loop`` of ceil(sending / S_b) passes, at least one:
       the merge is associative, so a flood's front costs a few passes
       and every other iteration one.  The carry counts the one-pass
       iterations and keeps the most slots one iteration sent
       (``lane_plane``: ``exchange_compact_iters``, ``exchange_slot_budget``,
       ``exchange_slot_peak``; absent where a pop sends once, whose
       program is untouched);
    3. one row-sort of ``[old C | self | cross Cx]`` by the 4-word key
       keeps the first C per lane — the queue's sorted invariant is
       maintained, so the pop phase needs no sort at all.

    The whole pipeline runs on the resident int32 key words; the only
    conversions left are the emit-time splits at entry (slot times are
    int64 scalars-per-lane) and the log joins at exit (logging only).

    Events pushed past column C are capacity overflow: counted per lane
    (the engine raises in strict mode) and logged as DROP_QUEUE; the merge
    keeps the *earliest* C keys, so overflow sheds the latest events.
    Returns (state, overflow log-record dict).
    """
    n, c = p.n_lanes, p.capacity
    sp = p.stream_present
    pay = p.pay_words  # the payload words the rows carry

    # -- same-lane block [N, 2K] (3K with the stream RTO channel; K when
    # every model is passive — the DELIVERY self-insert channel is then
    # statically dead and its always-NEVER columns are dropped) ----------
    chans = ("arm",) if p.all_passive else ("ins", "arm")

    def part(chan, w):
        if (chan, w) == ("arm", "phi"):  # a timer's phi is always 0
            return jnp.zeros_like(emits.arm_plo.T)
        return getattr(emits, f"{chan}_{w}").T

    parts = {w: [part(chan, w) for chan in chans]
             for w in ("valid", *ROW_WORDS, *p.emit_pay_words)}
    self_valid = jnp.concatenate(parts.pop("valid"), axis=1)
    self_cols = {}
    for w, cols in parts.items():
        self_cols[w] = jnp.concatenate(cols, axis=1)
        if w in ("thi", "tlo"):  # an unused entry's time is NEVER
            self_cols[w] = jnp.where(self_valid, self_cols[w], NEVER32)

    # -- cross-lane block [N, Cx] via sort-by-dst + histogram bounds -------
    # one-to-one stream configs take the SPLIT exchange: every stream
    # channel entry's destination is static (each lane has one flow, one
    # role), so stream events skip the flat sort entirely and merge
    # through a tiny [2S, C+W] row sort below (_merge_stream_rows); the
    # big exchange then carries only the [N]-wide model sends, with
    # all-zero payloads.  Star-shaped configs (several clients per
    # server) keep the combined exchange: their per-lane fan-in is not
    # static.  Which path an event rides is unobservable — placement is
    # by the keyed merge either way.
    split_se = sp and p.stream_one_to_one
    flat_pay = () if split_se else pay  # what the flat exchange carries
    cx = p.cross_cap
    self_words = [self_cols[w] for w in p.row_words]

    if p.sends_per_pop > 1:
        # a pop that fans out made the send channel F times wider and left
        # it as empty as it was (a gossip slot sends only on a publish or a
        # FIRST delivery: 1–3 % of its rows are real), so the exchange runs
        # over the slots that SENT, ``exchange_slot_budget`` of them a
        # pass: as wide as a one-send program's.  Nearly every iteration
        # is one pass; a denser one (a flood's front) takes ceil(sending /
        # budget), each through the cross block and the row merge — the
        # merge is associative, the rows' 4-word key a total order, so
        # every lane's queue is what one exchange of all K x F x N rows
        # would leave, and strict capacity raises on any shed.  (Not a
        # ``lax.cond`` beside the full exchange: a second sort of as many
        # operands is 4 MB of code, PERF.md §6 PR 43.)
        assert flat_pay and not divert  # gossip rides no stream tier
        budget = p.exchange_slot_budget
        n_sending, ranked, table = _rank_sending(emits, budget, n, pay)
        passes = jnp.maximum((n_sending + (budget - 1)) // budget, 1)

        def one_pass(carry):
            i, st, cnt_all = carry
            gather_ops, start, cnt = _sorted_exchange(
                _compact_sends(ranked, table, i, budget, n,
                               p.sends_per_pop), n)
            words = _cross_block(gather_ops, start, cnt, cx)[1]
            # the same-lane block rides the first pass
            own = [jnp.where(i == 0, w, NEVER32) for w in self_words[:2]]
            cnt_all = cnt_all + cnt
            st = _merge_rows(p, st, own + self_words[2:], words, cnt_all,
                             jnp.maximum(cnt - cx, 0))
            return i + 1, st, cnt_all

        _, s, _ = lax.while_loop(
            lambda carry: carry[0] < passes, one_pass,
            (jnp.int32(0), s, jnp.zeros(n, dtype=jnp.int32)),
        )
        s = _ledger_add(s, no_send=n_sending == 0, exch_passes=passes)
        return s._replace(
            exchange_compact_iters=s.exchange_compact_iters + (passes == 1),
            exchange_slot_peak=jnp.maximum(s.exchange_slot_peak, n_sending),
        )
    valid = emits.out_valid.reshape(-1)
    dst = jnp.where(valid, emits.out_dst.reshape(-1), jnp.int32(n))
    out_thi = emits.out_thi.reshape(-1)
    out_tlo = emits.out_tlo.reshape(-1)
    flat_ops = [dst, out_thi, out_tlo, emits.out_auxh.reshape(-1),
                emits.out_auxl.reshape(-1), emits.out_size.reshape(-1)]
    flat_ops += [getattr(emits, "out_" + w).reshape(-1) for w in flat_pay]
    if sp and not split_se:
        # the COMPACTED stream channels join the exchange here: slot-0
        # control sends (dst = peer lane), burst data segments (dst =
        # server lane), and RTO self-arms (dst = OWN lane, kind LOCAL) —
        # a few thousand extra sort entries against static flow tables
        # instead of [N]-wide channels.  All placement is by the keyed
        # merge sort, so which channel an event rides is unobservable.
        kk, s2 = emits.se_valid.shape
        s_flows = s2 // 2
        bb = emits.bo_valid.shape[1]

        def bc2(table):  # [2S] static -> [K*2S] flat
            return jnp.broadcast_to(table[None, :], (kk, s2)).reshape(-1)

        def bcb(table):  # [S] static -> [K*B*S] flat
            return jnp.broadcast_to(
                table[None, None, :], (kk, bb, s_flows)
            ).reshape(-1)

        se_v = emits.se_valid.reshape(-1)
        sa_v = emits.sa_valid.reshape(-1)
        bo_v = emits.bo_valid.reshape(-1)
        pkt_auxh_e = pack_aux_hi(
            jnp.full(s2, PACKET, dtype=jnp.int32), tb.flow_lanes
        )
        loc_auxh_e = pack_aux_hi(
            jnp.full(s2, LOCAL, dtype=jnp.int32), tb.flow_lanes
        )
        bo_auxh_c = pack_aux_hi(
            jnp.full(s_flows, PACKET, dtype=jnp.int32),
            tb.flow_lanes[:s_flows],
        )
        extras = [
            # dst
            jnp.concatenate([
                jnp.where(se_v, bc2(tb.flow_peers), jnp.int32(n)),
                jnp.where(sa_v, bc2(tb.flow_lanes), jnp.int32(n)),
                jnp.where(bo_v, bcb(tb.flow_peers[:s_flows]), jnp.int32(n)),
            ]),
            # thi / tlo
            jnp.concatenate([
                emits.se_thi.reshape(-1), emits.sa_thi.reshape(-1),
                emits.bo_thi.reshape(-1),
            ]),
            jnp.concatenate([
                emits.se_tlo.reshape(-1), emits.sa_tlo.reshape(-1),
                emits.bo_tlo.reshape(-1),
            ]),
            # auxh / auxl
            jnp.concatenate([
                bc2(pkt_auxh_e), bc2(loc_auxh_e), bcb(bo_auxh_c),
            ]),
            jnp.concatenate([
                emits.se_seq.reshape(-1), emits.sa_auxl.reshape(-1),
                emits.bo_auxl.reshape(-1),
            ]),
            # size (RTO arms carry the SZ_RTO marker)
            jnp.concatenate([
                emits.se_size.reshape(-1),
                jnp.full(kk * s2, lstr.SZ_RTO, dtype=jnp.int32),
                emits.bo_size.reshape(-1),
            ]),
            # phi / plo (arms carry the flow's client lane in plo)
            jnp.concatenate([
                emits.se_phi.reshape(-1),
                jnp.zeros(kk * s2, dtype=jnp.int32),
                emits.bo_phi.reshape(-1),
            ]),
            jnp.concatenate([
                emits.se_plo.reshape(-1), bc2(tb.flow_clid),
                emits.bo_plo.reshape(-1),
            ]),
        ]
        flat_ops = [
            jnp.concatenate([a, b]) for a, b in zip(flat_ops, extras)
        ]
    assert flat_ops[0].shape[0] == p.exchange_entries
    gather_ops, start, cnt = _sorted_exchange(flat_ops, n)
    # the loop ledger: an iteration whose exchange handed no lane a row
    # (``cross_peak``'s reduction, read once more) still pays its sort and
    # its row merge
    if _has_ledger(s):
        s = _ledger_add(s, no_send=cnt.max() == 0)
    _in_seg, words = _cross_block(gather_ops, start, cnt, cx)
    cross = dict(zip(ROW_WORDS + flat_pay, words))
    for w in pay[len(flat_pay):]:
        # split exchange: the [N] channel never carries payloads
        cross[w] = jnp.zeros((n, cx), dtype=jnp.int32)
    # receivers of more than Cx events in one iteration lose the tail
    # before the merge even sees it; count those drops too
    lost_pre = jnp.maximum(cnt - cx, 0)

    # tiered stream backend: entries destined to stream-endpoint lanes
    # divert into the [2S] tier merge (their [N] queue rows are dead) —
    # the endpoint lanes' windows by a gather of their own, then NEVER-mask
    # those lanes out of the [N] merge below.  NOT ``cross_thi[el]``: rows
    # picked out of the [N, Cx] block are Cx-wide row gathers (the padded
    # rows _window_gather's layout rule forbids), and XLA then lays the
    # whole barrel shift above them rows-major (the mixed 10k mesh's
    # iteration: 1.05 -> 0.81 ms on a v5e, PERF.md §6, PR 33)
    tier_cross = None
    if divert:
        el = tb.flow_lanes
        t_valid, t_words = _cross_block(
            gather_ops[:len(ROW_WORDS)], start[el], cnt[el], cx
        )
        tier_cross = dict(zip(ROW_WORDS, t_words), valid=t_valid)
        keep = ~tb.lane_stream[:, None]
        cross["thi"] = jnp.where(keep, cross["thi"], NEVER32)
        cross["tlo"] = jnp.where(keep, cross["tlo"], NEVER32)

    s = _merge_rows(p, s, self_words, [cross[w] for w in p.row_words], cnt,
                    lost_pre)
    if split_se:
        s = _merge_stream_rows(p, tb, s, emits)
    return (s, tier_cross) if divert else s


def _merge_rows(p: LaneParams, s: LaneState, self_words, cross_words, cnt,
                lost_pre):
    """``_merge_append`` step 3 over one cross block: the row sort of
    ``[old C | self | cross Cx]`` (the words of ``p.row_words`` each: five
    and the payload words the rows carry) and everything that reads the
    merged row.  ``cnt`` is what ``cross_peak`` reads, ``lost_pre`` what
    the block shed before the merge."""
    n, c = p.n_lanes, p.capacity
    words = p.row_words
    # -- merge [N, C + self + Cx], keep first C ---------------------------
    # queue state is ALREADY the int32 4-word key: no conversions at all
    with jax.named_scope("row_merge"):
        merged = _sort_rows(words, [
            jnp.concatenate(cols, axis=1)
            for cols in zip(_q_cols(s, words), self_words, cross_words)])
    mthi, mtlo, mh, ml, ms = (merged[w] for w in ROW_WORDS)
    tail_mask = mthi[:, c:] != NEVER32
    s = _q_replace(s, ROW_WORDS, [merged[w][:, :c] for w in ROW_WORDS])
    s = s._replace(
        n_queue=s.n_queue + tail_mask.sum(axis=1, dtype=jnp.int32)
        + lost_pre,
    )
    if not p.all_passive:
        # the run's shape peaks: three reductions an iteration (~50 KB of
        # code and ~1.5 % of the compiler's cycles in the 10 000-lane
        # mesh, PERF.md 6, PR 35), so a program of passive lanes alone —
        # fixed peers, the shapes its configuration was measured at —
        # carries none and reports the shapes only
        s = s._replace(peaks=jnp.stack([
            jnp.maximum(s.peaks[PK_QUEUE], _row_fill(mthi).max()),
            jnp.maximum(s.peaks[PK_CROSS], cnt.max()),
            s.peaks[PK_CROSS_SHED] + lost_pre.sum(dtype=jnp.int32),
        ]))
    if p.netobs:
        # cross-block sheds stay inside n_queue (the strict-mode total)
        # but carry their own cause counter so the netobs drop classification
        # can split queue overflow from exchange-width shed
        s = s._replace(nb_shed=s.nb_shed + lost_pre)
    s = _q_replace(s, p.pay_words, [merged[w][:, :c] for w in p.pay_words])
    if p.flowtrace:
        # queue-overflow drops for sampled flows, from the merge tail's
        # pair times directly (no int64 re-split).  PACKET rows only: the
        # oracle's heap is unbounded, so these are dead in parity runs
        # (strict mode raises on any shed).  Cross-block sheds (lost_pre)
        # lose entry identity in the window gather and stay count-only —
        # the netobs nb_shed counter covers them (CAUSE_CROSS_SHED is
        # reserved for the oracle-side accounting).
        fq_kind, fq_src = unpack_aux_hi(mh[:, c:])
        fq_rows = jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32)[:, None], tail_mask.shape
        )
        fq_valid = (
            tail_mask & (fq_kind == PACKET)
            & _flow_sampled(p, fq_src, fq_rows)
        )
        s = _append_flow(p, s, _flow_group(
            fq_valid, mthi[:, c:], mtlo[:, c:], ftr.FT_DROP, fq_src,
            fq_rows, ml[:, c:], ms[:, c:], ftr.CAUSE_QUEUE,
        ))

    # overflow log records from the merge tail (pre-gather losses surface
    # only in n_queue; both paths raise in strict mode).  With the log on
    # (the hybrid turn's TIMED program, the mesh cells' check program) the
    # whole [N, 2K + Cx] tail is offered every iteration and a sound run
    # writes none of it, so the columns stay int32 and only written rows
    # become int64 (_append_rows).  Log off (the mesh cells' timed program):
    # nothing is traced
    if p.log_capacity:
        s = _append_log(p, s, _tail_records(
            tail_mask, mthi[:, c:], mtlo[:, c:], mh[:, c:], ml[:, c:],
            ms[:, c:], jnp.arange(n, dtype=jnp.int32),
        ), tail=True)
    return s


def _tail_records(tail_mask, thi, tlo, auxh, auxl, size, lane_ids):
    """DROP_QUEUE log records for a merge tail ``[rows, T]`` (what a row
    sort pushed past the queue's capacity), flat in row-major order;
    ``lane_ids`` names each row's lane."""
    _kind, o_src = unpack_aux_hi(auxh)
    return {
        "valid": tail_mask.reshape(-1),
        "time": (thi.reshape(-1), tlo.reshape(-1)),
        "src": o_src.reshape(-1),
        "dst": jnp.broadcast_to(lane_ids[:, None], tail_mask.shape
                                ).reshape(-1),
        "seq": auxl.reshape(-1),
        "size": size.reshape(-1),
        "outcome": DROP_QUEUE,
    }


def _merge_stream_rows(p: LaneParams, tb: LaneTables, s: LaneState,
                       emits: _SlotEmit):
    """Split-exchange merge of the compacted stream channels, for
    one-to-one configs: every channel entry's destination LANE is static
    (client row s receives its server's control sends + its own arms;
    server row s receives its client's control sends + bursts + its own
    arms), so the candidate block is pure reshaping — no flat sort, no
    histogram, no window gather — and one [2S, C + W] row sort merges it
    into the stream lanes' queue rows (gathered and scattered back by the
    static ``flow_lanes`` indices).

    Two-stage overflow note: events shed by the MAIN merge cannot be
    revived here; strict mode (the default) raises on any shed either
    way, and non-strict overflow is documented non-parity."""
    n, c = p.n_lanes, p.capacity
    kk, s2 = emits.se_valid.shape
    s_flows = s2 // 2
    bb = emits.bo_valid.shape[1]
    el = tb.flow_lanes  # [2S] unique in one-to-one mode

    never_kb = jnp.full((s_flows, kk * bb), NEVER32, dtype=jnp.int32)
    zero_kb = jnp.zeros((s_flows, kk * bb), dtype=jnp.int32)

    def chan(arr_se, arr_sa, arr_bo, pad_cl):
        """Build the [2S, W] candidate block (W = K + K + K*B): client
        rows take the SERVER half of se (their peer's sends), the CLIENT
        half of sa (their own arms), and padding; server rows take the
        client half of se, the server half of sa, and the bursts."""
        se_cl = arr_se[:, s_flows:].T  # [S, K]
        se_sv = arr_se[:, :s_flows].T
        sa_cl = arr_sa[:, :s_flows].T
        sa_sv = arr_sa[:, s_flows:].T
        bo_sv = jnp.moveaxis(arr_bo, 2, 0).reshape(s_flows, kk * bb)
        cl_rows = jnp.concatenate([se_cl, sa_cl, pad_cl], axis=1)
        sv_rows = jnp.concatenate([se_sv, sa_sv, bo_sv], axis=1)
        return jnp.concatenate([cl_rows, sv_rows], axis=0)  # [2S, W]

    v = chan(emits.se_valid, emits.sa_valid, emits.bo_valid,
             jnp.zeros((s_flows, kk * bb), dtype=bool))
    cthi = chan(emits.se_thi, emits.sa_thi, emits.bo_thi, never_kb)
    ctlo = chan(emits.se_tlo, emits.sa_tlo, emits.bo_tlo, never_kb)
    cauxl = chan(emits.se_seq, emits.sa_auxl, emits.bo_auxl, zero_kb)
    csize = chan(
        emits.se_size,
        jnp.full((kk, s2), lstr.SZ_RTO, dtype=jnp.int32),
        emits.bo_size, zero_kb,
    )
    cphi = chan(emits.se_phi, jnp.zeros((kk, s2), dtype=jnp.int32),
                emits.bo_phi, zero_kb)
    cplo = chan(
        emits.se_plo,
        jnp.broadcast_to(tb.flow_clid[None, :], (kk, s2)),
        emits.bo_plo, zero_kb,
    )
    # aux-hi words are fully static per position: se entries are PACKETs
    # from the peer lane, sa entries LOCALs from the own lane, bursts
    # PACKETs from the client lane
    pk = jnp.full(s2, PACKET, dtype=jnp.int32)
    lc = jnp.full(s2, LOCAL, dtype=jnp.int32)
    se_auxh = pack_aux_hi(pk, el)  # indexed by SENDER endpoint
    sa_auxh = pack_aux_hi(lc, el)
    bo_auxh_c = pack_aux_hi(pk[:s_flows], el[:s_flows])
    cauxh = chan(
        jnp.broadcast_to(se_auxh[None, :], (kk, s2)),
        jnp.broadcast_to(sa_auxh[None, :], (kk, s2)),
        jnp.broadcast_to(bo_auxh_c[None, None, :], (kk, bb, s_flows)),
        zero_kb,
    )
    cthi = jnp.where(v, cthi, NEVER32)
    ctlo = jnp.where(v, ctlo, NEVER32)

    # gather the stream lanes' queue rows, merge, keep first C, scatter
    q_rows = [a[el] for a in (s.q_thi, s.q_tlo, s.q_auxh, s.q_auxl,
                              s.q_size, s.q_phi, s.q_plo)]
    mthi, mtlo, mh, ml, ms, mphi, mplo = lax.sort(
        (
            jnp.concatenate([q_rows[0], cthi], axis=1),
            jnp.concatenate([q_rows[1], ctlo], axis=1),
            jnp.concatenate([q_rows[2], cauxh], axis=1),
            jnp.concatenate([q_rows[3], cauxl], axis=1),
            jnp.concatenate([q_rows[4], csize], axis=1),
            jnp.concatenate([q_rows[5], cphi], axis=1),
            jnp.concatenate([q_rows[6], cplo], axis=1),
        ),
        dimension=1, num_keys=4, is_stable=False,
    )
    tail_mask = mthi[:, c:] != NEVER32
    s = s._replace(
        q_thi=s.q_thi.at[el].set(mthi[:, :c]),
        q_tlo=s.q_tlo.at[el].set(mtlo[:, :c]),
        q_auxh=s.q_auxh.at[el].set(mh[:, :c]),
        q_auxl=s.q_auxl.at[el].set(ml[:, :c]),
        q_size=s.q_size.at[el].set(ms[:, :c]),
        q_phi=s.q_phi.at[el].set(mphi[:, :c]),
        q_plo=s.q_plo.at[el].set(mplo[:, :c]),
        n_queue=s.n_queue.at[el].add(
            tail_mask.sum(axis=1, dtype=jnp.int32)
        ),
    )
    if p.flowtrace:
        # queue-overflow drops at the stream lanes (same law as the main
        # merge tail in _merge_append — PACKET rows only, sampled flows)
        fq_kind, fq_src = unpack_aux_hi(mh[:, c:])
        fq_rows = jnp.broadcast_to(el[:, None], tail_mask.shape)
        fq_valid = (
            tail_mask & (fq_kind == PACKET)
            & _flow_sampled(p, fq_src, fq_rows)
        )
        s = _append_flow(p, s, _flow_group(
            fq_valid, mthi[:, c:], mtlo[:, c:], ftr.FT_DROP, fq_src,
            fq_rows, ml[:, c:], ms[:, c:], ftr.CAUSE_QUEUE,
        ))
    if p.log_capacity == 0:
        return s
    return _append_log(p, s, _tail_records(
        tail_mask, mthi[:, c:], mtlo[:, c:], mh[:, c:], ml[:, c:],
        ms[:, c:], el,
    ), tail=True)


# rows per block write of an append (see _append_rows).  A block costs the
# chip ~9 us plus ~0.03 us per row (TPU v5e, PERF.md PR 27), so 256 keeps a
# sparse append (the hybrid turn: tens of records among 9 208 candidates)
# near the floor while a dense one (the mesh check program: ~10 000 of
# 20 000) takes ~40 trips and still a third of the old all-candidate
# scatter's time.
_APPEND_BLOCK = 256


@jax.named_scope("record_append")
def _append_rows(buf, count, valid, rows_at):
    """Append the valid candidates of a flat batch to ``buf`` at ``count``,
    in flat order: the j-th valid candidate lands on row ``count + j``, rows
    past the buffer's end are lost, everything else in ``buf`` stays as it
    was (the cumsum-position law the log, the egress buffer and the flow
    ring share, bit for bit).

    The cost follows the rows WRITTEN, not the ``M`` candidates offered: the
    valid rows go out in contiguous blocks of ``R = min(_APPEND_BLOCK, M,
    capacity)`` rows — a rank search in ``cumsum(valid)`` names each block's
    source candidates, ``rows_at(pick)`` builds the ``[R, cols]`` block from
    them (``pick(col)`` gathers a flat ``[M]`` column at those R sources, so
    wide columns are built on R rows, not on M), and one
    ``dynamic_update_slice`` writes it.  Zero trips when nothing is valid,
    one when sparse, ``ceil(kept / R)`` when dense: one code path, and the
    trip count is the only thing that adapts.  A block that would run past
    the buffer's end is slid back (``dynamic_update_slice`` would clamp its
    start anyway) and keeps the rows it overlaps.

    Returns ``(buf, n_valid, n_kept, blocks)`` as int32 scalars."""
    i32 = jnp.int32
    cap, m = buf.shape[0], valid.shape[0]
    r = min(_APPEND_BLOCK, m, cap)
    csum = jnp.cumsum(valid.astype(i32))
    n = csum[-1]
    n_kept = jnp.clip(cap - count, 0, n)
    blocks = (n_kept + (r - 1)) // r
    row_i = jnp.arange(r, dtype=i32)

    def write_block(b, out):
        start = jnp.minimum(count + b * r, cap - r)
        rank = start + row_i - count  # of the candidate each row would hold
        mine = (rank >= b * r) & (rank < n)
        src = jnp.minimum(
            jnp.searchsorted(csum, rank + 1, side="left",
                             method="compare_all").astype(i32),
            m - 1,
        )
        rows = rows_at(lambda col: col[src])
        at = (start, i32(0))
        old = lax.dynamic_slice(out, at, (r, out.shape[1]))
        return lax.dynamic_update_slice(
            out, jnp.where(mine[:, None], rows, old), at
        )

    buf = lax.fori_loop(i32(0), blocks, write_block, buf)
    return buf, n, n_kept, blocks


def _append_log(p: LaneParams, s: LaneState, recs, tail: bool = False
                ) -> LaneState:
    """Append valid records to the device event log (if enabled).

    ``recs`` holds flat ``[M]`` columns: ``valid``, ``time`` (int64, or an
    ``(hi, lo)`` int32 pair joined on the written rows only), and ``src``,
    ``dst``, ``seq``, ``size``, ``outcome`` as arrays of any integer type
    or scalars.  ``tail`` marks the queue-overflow records of a merge
    tail, which no sound run has, for the engage counters."""
    if p.log_capacity == 0:
        return s
    i64 = jnp.int64

    def rows_at(pick):
        t = recs["time"]
        cols = [t_join(pick(t[0]), pick(t[1])) if isinstance(t, tuple)
                else pick(t)]
        for key in ("src", "dst", "seq", "size", "outcome"):
            c = recs[key]
            cols.append(pick(c).astype(i64) if jnp.ndim(c)
                        else jnp.full(cols[0].shape, c, dtype=i64))
        return jnp.stack(cols, axis=1)

    log, n, n_kept, blocks = _append_rows(
        s.log, s.log_count, recs["valid"], rows_at
    )
    s = s._replace(
        log=log,
        log_count=s.log_count + n,
        log_lost=s.log_lost + (n - n_kept),
        ap_blocks=s.ap_blocks + blocks,
        ap_rows=s.ap_rows + n_kept,
    )
    if tail:
        s = s._replace(ap_tail_blocks=s.ap_tail_blocks + blocks)
    return s


def flow_hash_lane(src, dst, seed: int):
    """Device twin of ``obs.flowtrace.flow_hash`` (fid = 0): the same u32
    mix + murmur3 fmix32, on ``jnp.uint32`` lanes — bit-identical to the
    Python ints for any int32 host indices, so device and oracle sample
    the same flows with no coordination."""
    u32 = jnp.uint32
    h = (
        src.astype(u32) * u32(2654435761)
        + dst.astype(u32) * u32(2246822519)
        + u32((seed * 668265263) & 0xFFFFFFFF)
    )
    h = h ^ (h >> 16)
    h = h * u32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * u32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _flow_sampled(p: LaneParams, src, dst):
    """[shape-of-src] bool: the (src, dst) flow records flowtrace events
    (the static all-pass / none fast paths trace zero hash ops)."""
    if p.flow_all:
        return jnp.ones(jnp.broadcast_shapes(src.shape, dst.shape),
                        dtype=bool)
    if p.flow_thresh == 0:
        return jnp.zeros(jnp.broadcast_shapes(src.shape, dst.shape),
                         dtype=bool)
    return flow_hash_lane(src, dst, p.flow_seed) < jnp.uint32(p.flow_thresh)


def _append_flow(p: LaneParams, s: LaneState, rows) -> LaneState:
    """Append sampled lifecycle events to the flowtrace ring — the
    ``_append_log`` law on ``[FL, FT_COLS]`` int32 rows: contiguous
    cumsum positions, never wrap, overflow counts into ``fl_lost``.
    ``rows`` is a dict of flat int32/bool columns (valid, t_hi, t_lo,
    kind, src, dst, seq, size, aux); the window stamp broadcasts from
    the state's current pair."""
    if not p.flowtrace:
        return s
    i32 = jnp.int32

    def rows_at(pick):
        c = [pick(rows[k]).astype(i32) for k in
             ("t_hi", "t_lo", "kind", "src", "dst", "seq", "size", "aux")]
        we = [jnp.broadcast_to(w, c[0].shape).astype(i32)
              for w in (s.now_we_hi, s.now_we_lo)]
        return jnp.stack(c[:2] + we + c[2:], axis=1)

    fl_buf, n, n_kept, _blocks = _append_rows(
        s.fl_buf, s.fl_count, rows["valid"], rows_at
    )
    return s._replace(
        fl_buf=fl_buf,
        fl_count=s.fl_count + n,
        fl_lost=s.fl_lost + (n - n_kept),
    )


def _flow_group(valid, t_hi, t_lo, kind, src, dst, seq, size, aux):
    """One flattened flowtrace event group (scalar kind/aux broadcast)."""
    shape = valid.shape
    i32 = jnp.int32

    def col(v):
        a = jnp.asarray(v, dtype=i32)
        return jnp.broadcast_to(a, shape).reshape(-1)

    return {
        "valid": valid.reshape(-1),
        "t_hi": col(t_hi), "t_lo": col(t_lo),
        "kind": col(kind), "src": col(src), "dst": col(dst),
        "seq": col(seq), "size": col(size), "aux": col(aux),
    }


def _concat_flow_groups(groups):
    return {
        k: jnp.concatenate([g[k] for g in groups]) for k in groups[0]
    }


def _ft_dead(p: LaneParams):
    """Zeros flowtrace channel matching the live ``ft`` dict built by
    ``_process_slot`` (lax.cond branches must return identical pytrees)."""
    if not p.flowtrace:
        return ()
    n = p.n_lanes
    nb = jnp.zeros(n, dtype=bool)
    z32 = jnp.zeros(n, dtype=jnp.int32)
    ft = {
        "sd_valid": nb, "sd_dst": z32, "sd_seq": z32, "sd_size": z32,
        "sd_thi": z32, "sd_tlo": z32, "sd_dhi": z32, "sd_dlo": z32,
        "sd_lost": nb, "sd_ahi": z32, "sd_alo": z32,
        "ar_valid": nb, "ar_src": z32, "ar_seq": z32, "ar_size": z32,
        "ar_thi": z32, "ar_tlo": z32, "ar_dhi": z32, "ar_dlo": z32,
        "ar_drop": nb,
    }
    if p.stream_present:
        from ..net import ltcp as _ltcp

        s2 = 2 * len(p.stream_clients)
        eb = jnp.zeros(s2, dtype=bool)
        ei = jnp.zeros(s2, dtype=jnp.int32)
        bshape = (_ltcp.PUMP_BURST, s2 // 2)
        bb = jnp.zeros(bshape, dtype=bool)
        bi = jnp.zeros(bshape, dtype=jnp.int32)
        ft.update({
            "ss_valid": eb, "ss_retx": eb, "ss_seq": ei, "ss_size": ei,
            "ss_thi": ei, "ss_tlo": ei, "ss_dhi": ei, "ss_dlo": ei,
            "ss_lost": eb, "ss_ahi": ei, "ss_alo": ei,
            "bs_valid": bb, "bs_retx": bb, "bs_seq": bi, "bs_size": bi,
            "bs_thi": bi, "bs_tlo": bi, "bs_dhi": bi, "bs_dlo": bi,
            "bs_lost": bb, "bs_ahi": bi, "bs_alo": bi,
        })
    return ft


def _append_egress(p: LaneParams, s: LaneState, valid, delivered,
                   td_hi, td_lo, src, dst, seq, size) -> LaneState:
    """Append packet outcomes at EXTERNAL lanes to the egress buffer
    (hybrid backend): int64 rows (t_deliver, src, dst, seq, size,
    outcome).  DELIVERED rows become host-side DELIVERY events and feed
    the running min pending delivery time (the device free-run guard —
    the loop must not advance a window past an unserviced host delivery);
    DROP_CODEL rows only release the host's parked payload."""
    i64 = jnp.int64

    def rows_at(pick):
        return jnp.stack(
            [
                t_join(pick(td_hi), pick(td_lo)),
                pick(src).astype(i64),
                pick(dst).astype(i64),
                pick(seq).astype(i64),
                pick(size).astype(i64),
                jnp.where(pick(delivered), DELIVERED, DROP_CODEL).astype(i64),
            ],
            axis=1,
        )

    egress, n, n_kept, blocks = _append_rows(
        s.egress, s.egress_count, valid, rows_at
    )
    live = valid & delivered
    mh, ml = pair_min_lanes(
        jnp.where(live, td_hi, NEVER32), jnp.where(live, td_lo, NEVER32)
    )
    is_lt = pair_lt(mh, ml, s.egress_min_hi, s.egress_min_lo)
    return s._replace(
        egress=egress,
        egress_count=s.egress_count + n,
        egress_lost=s.egress_lost + (n - n_kept),
        egress_min_hi=jnp.where(is_lt, mh, s.egress_min_hi),
        egress_min_lo=jnp.where(is_lt, ml, s.egress_min_lo),
        ap_blocks=s.ap_blocks + blocks,
        ap_rows=s.ap_rows + n_kept,
    )


@jax.named_scope("window_min")
def _queue_min(p: LaneParams, s: LaneState):
    """Scalar pair: the earliest event over ALL queues ([N] lanes, plus
    the [2S] tier block when the tiered stream backend is live)."""
    mh, ml = pair_min_lanes(s.q_thi[:, 0], s.q_tlo[:, 0])
    if p.stream_tiered:
        th, tl = pair_min_lanes(
            s.stream.q[lstr.TQ_THI, :, 0], s.stream.q[lstr.TQ_TLO, :, 0]
        )
        sel = pair_lt(th, tl, mh, ml)
        mh = jnp.where(sel, th, mh)
        ml = jnp.where(sel, tl, ml)
    return mh, ml


def ilog2_i32(x):
    """floor(log2(x)) for int32 x >= 1, branch-free (0 for x <= 1)."""
    x = jnp.asarray(x, dtype=jnp.int32)
    r = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        ge = x >= (1 << shift)
        x = jnp.where(ge, x >> shift, x)
        r = r + jnp.where(ge, shift, 0)
    return r


def hist_fold_index(count, enable):
    """THE bucket law of the repo's log2 histograms (``obs.netobs.
    hist_bucket`` is its host form): ``(do, idx)`` for folding one
    ``count`` — bucket ``floor(log2(count))``, the last of the
    ``NB_HIST_BUCKETS`` taking the tail; a zero count, or ``enable``
    false, folds nowhere: ``idx`` is then one past the row, which an
    ``.at[idx].add(1, mode="drop")`` drops."""
    do = enable & (count > 0)
    bucket = jnp.minimum(ilog2_i32(count), NB_HIST_BUCKETS - 1)
    return do, jnp.where(do, bucket, NB_HIST_BUCKETS)


def _flush_hist(p: LaneParams, s: LaneState, enable) -> LaneState:
    """Fold the running window occupancy (packet arrivals) into the [B]
    histogram and reset it — called exactly when a NEW window begins
    (and once more at collect, host-side, for the trailing window).
    Packet-free windows leave ``nb_win == 0`` and are skipped — on both
    backends identically, so the histogram stays bit-comparable."""
    do, idx = hist_fold_index(s.nb_win, enable)
    return s._replace(
        nb_hist=s.nb_hist.at[idx].add(1, mode="drop"),
        nb_win=jnp.where(do, 0, s.nb_win),
    )


def _has_ledger(s: LaneState) -> bool:
    """Whether this program carries the loop ledger: the state's own leaf
    says (``TpuEngine.initial_state`` decides, by ``LaneParams.
    all_passive`` of the WHOLE program — a tiered program's [N] pass runs
    under a params view without the stream models)."""
    return not isinstance(s.loop_acc, tuple)


def _ledger_add(s: LaneState, **steps) -> LaneState:
    """``loop_acc[word] += step`` for non-negative int32 scalar steps by
    ``LoopAcc`` word, saturating at 2**31 - 1.  Where the program carries
    no ledger it adds nothing, but a step computed for it is traced all
    the same (and a loop body's text keeps dead operations): a site whose
    step is a reduction asks ``_has_ledger`` first."""
    if not _has_ledger(s):
        return s
    with jax.named_scope("loop_ledger"):
        step = jnp.stack([jnp.asarray(steps.get(word, 0), dtype=jnp.int32)
                          for word in LoopAcc._fields])
        return s._replace(
            loop_acc=jnp.minimum(s.loop_acc, _I32_MAX - step) + step)


def _ledger_open_window(s: LaneState, fresh) -> LaneState:
    """Where ``fresh`` (a new window opens): fold the iterations the
    finished window took into ``loop_hist``, keep the most any took, and
    start the count again — at the instant ``_flush_hist`` folds netobs's
    window, in every loop form.  The trailing window's count stays in the
    ``round_iters`` word for ``TpuEngine.collect``.  The fold is one
    elementwise add of a one-hot row and the two words' update a select
    on the word's index (scatters into a few words are kernels of their
    own on the chip; ``idx`` past the row, ``hist_fold_index``'s
    "nowhere", matches no bucket)."""
    if not _has_ledger(s):
        return s
    with jax.named_scope("loop_ledger"):
        acc = s.loop_acc
        word = np.arange(len(LoopAcc._fields))
        at_iters, at_max = (word == LoopAcc._fields.index(w)
                            for w in ("round_iters", "round_max"))
        took = acc[LoopAcc._fields.index("round_iters")]
        do, idx = hist_fold_index(took, fresh)
        acc = jnp.where(do & at_iters, 0, acc)
        acc = jnp.where(do & at_max, jnp.maximum(acc, took), acc)
        hit = np.arange(NB_HIST_BUCKETS) == idx
        return s._replace(
            loop_hist=s.loop_hist + hit.astype(jnp.int32), loop_acc=acc)


def _stream_tier_iter(p: LaneParams, tb: LaneTables, s: LaneState,
                      we_hi, we_lo, tier_cross) -> LaneState:
    """One iteration of the TIERED stream backend: pop ≤K_s events per
    endpoint row from the [2S, C2] tier queue, process them (dn bucket +
    CoDel + the TCP law, all on compact [2S] state), and merge the
    emissions — control sends and bursts land at the STATIC peer row,
    RTO arms and delivery fallbacks at the own row, and ``tier_cross``
    carries the mesh spray the [N] exchange diverted to stream lanes.

    Delivery elision: a delivered packet whose t_deliver lands INSIDE the
    current window, BEFORE the row's earliest queued LOCAL and with no
    DELIVERY queued (or being queued this iteration) behind it, applies
    the law inline at t_deliver instead of self-inserting a DELIVERY
    event.  Exact for one-to-one flows: nothing the oracle's heap would
    pop between the packet and its delivery touches the flow (the gate
    above), every flow-relevant delivery at a row shares one src (its
    single peer), dn departures are FIFO (inline order = the oracle's
    delivery order), and the law's send/arm emissions touch state
    disjoint from later pops' dn charges.  Every other delivery falls
    back to a real DELIVERY insert, which keeps the WINDOW-LAW sequence
    bit-identical too (a pending delivery bounds the next window on both
    backends; an RTO that the oracle pops before a same-instant delivery
    re-arms or fires exactly as there).  A LOCAL may lead a co-popped
    prefix but never interrupt one, and a LOCAL tied with the row's head
    is rotated in front of it first (see below)."""
    ts = s.stream
    q, v = ts.q, ts.v
    k = p.stream_pops
    s2 = q.shape[1]
    s_flows = s2 // 2
    c2 = p.stream_capacity
    i32 = jnp.int32
    i64 = jnp.int64
    el = tb.flow_lanes
    is_cl_e = jnp.arange(s2, dtype=i32) < s_flows
    false_e = jnp.zeros(s2, dtype=bool)
    false_c = jnp.zeros(s_flows, dtype=bool)
    cl_sl = slice(0, s_flows)

    if p.stream_wide_pop:
        # ---- a LOCAL tied with the row's head leads it -------------------
        # The oracle's heap pops same-instant PACKETs, then the LOCAL, then
        # the packets' DELIVERYs.  A packet pop touches only the dn bucket
        # and CoDel, the LOCAL only the flow, so they commute: handling the
        # LOCAL FIRST and the packets' deliveries inline after it is the
        # same order of everything that does not.  (Arrivals are bumped to
        # window ends and an RTO fires a whole RTO_MIN after the ACK that
        # armed it, so on a graph whose latencies are multiples of the
        # window such ties are the rule, not the exception.)  Only this
        # function sees the rotated rows: the merge below re-sorts them.
        cols = jnp.arange(c2, dtype=i32)[None, :]
        loc_all = (q[lstr.TQ_THI] != NEVER32) & (
            (q[lstr.TQ_AUXH] >> AUX_KIND_SHIFT) == LOCAL)
        first_loc = jnp.argmax(loc_all, axis=1).astype(i32)[:, None]
        at_loc = loc_all & (cols == first_loc)  # one-hot, or all False

        def at_first_loc(plane):
            return jnp.sum(jnp.where(at_loc, plane, 0), axis=1, keepdims=True,
                           dtype=plane.dtype)

        lead = (
            (first_loc > 0) & jnp.any(loc_all, axis=1, keepdims=True)
            & (at_first_loc(q[lstr.TQ_THI]) == q[lstr.TQ_THI, :, :1])
            & (at_first_loc(q[lstr.TQ_TLO]) == q[lstr.TQ_TLO, :, :1])
        )

        def rotate(plane):
            shifted = jnp.concatenate([plane[:, :1], plane[:, :-1]], axis=1)
            return jnp.where(
                lead & (cols == 0), at_first_loc(plane),
                jnp.where(lead & (cols <= first_loc), shifted, plane))

        q = jnp.stack([rotate(q[i]) for i in range(q.shape[0])])
        ts = ts._replace(q=q)

    # ---- pop prefix ------------------------------------------------------
    thi_b = q[lstr.TQ_THI, :, :k]
    tlo_b = q[lstr.TQ_TLO, :, :k]
    kind_cols = q[lstr.TQ_AUXH, :, :k] >> AUX_KIND_SHIFT
    first_col = (jnp.arange(k) == 0)[None, :]
    if p.stream_wide_pop:
        # any within-window prefix that no LOCAL interrupts: a LOCAL may
        # LEAD one (it is handled first, and what it emits — sends, an RTO
        # arm at now + rto — lands past every possible window, which the
        # engine guarantees ends before RTO_MIN), never follow a packet
        # whose delivery might have to wait for it (the elision gate)
        prefix = jnp.cumprod(
            (kind_cols != LOCAL) | first_col, axis=1).astype(bool)
    else:
        same_t = (thi_b == thi_b[:, :1]) & (tlo_b == tlo_b[:, :1])
        pkt_prefix = jnp.cumprod(kind_cols == PACKET, axis=1).astype(bool)
        prefix = same_t & pkt_prefix
    allowed = prefix | first_col
    act_b = allowed & pair_lt(thi_b, tlo_b, we_hi, we_lo)
    # the loop ledger: the tier's live pop slots (its work is here, not
    # in the [N] lanes)
    if _has_ledger(s):
        s = _ledger_add(s, tier_pop_slots=act_b.sum(dtype=i32))
    if p.netobs:
        # tier PACKET pops join the window occupancy count ([N] pops are
        # added by iter_body; wire arrivals are the one event class whose
        # per-window counts are bit-identical across backends)
        s = s._replace(
            nb_win=s.nb_win
            + (act_b & (kind_cols == PACKET)).sum(dtype=i32)
        )
    q = q.at[lstr.TQ_THI, :, :k].set(jnp.where(act_b, NEVER32, thi_b))
    q = q.at[lstr.TQ_TLO, :, :k].set(jnp.where(act_b, NEVER32, tlo_b))
    if p.stream_wide_pop:
        # what an elided delivery may not overtake (see tier_slot's gate):
        # the row's earliest queued LOCAL behind the leading column, and
        # any DELIVERY still queued behind the popped column
        live_q = ts.q[lstr.TQ_THI] != NEVER32
        kind_q = ts.q[lstr.TQ_AUXH] >> AUX_KIND_SHIFT
        loc_q = live_q & (kind_q == LOCAL) & (jnp.arange(c2) > 0)[None, :]
        loc_hi = jnp.min(
            jnp.where(loc_q, ts.q[lstr.TQ_THI], NEVER32), axis=1)
        loc_lo = jnp.min(
            jnp.where(loc_q & (ts.q[lstr.TQ_THI] == loc_hi[:, None]),
                      ts.q[lstr.TQ_TLO], NEVER32), axis=1)
        del_q = (live_q & (kind_q == DELIVERY)).astype(i32)
        del_behind = (
            del_q.sum(axis=1, keepdims=True) - jnp.cumsum(del_q[:, :k], axis=1)
        ) > 0  # [2S, K]

    f = lstr.endpoint_cols(
        ts.flows, tb.flow_segs, tb.flow_mss, tb.flow_last, tb.flow_cc
    )
    mul = s.min_used_lat
    log_on = bool(p.log_capacity)
    bs_hi, bs_lo = p.bootstrap_end >> 31, p.bootstrap_end & MASK31

    # slots run through scan_or_unroll: ONE law copy under XLA:CPU's
    # rolled scan (K inlined law bodies made CPU compile explode), a
    # fusable Python loop on the accelerator
    xs = {
        "thi": thi_b.T,
        "tlo": tlo_b.T,
        "auxh": jnp.moveaxis(ts.q[lstr.TQ_AUXH, :, :k], 1, 0),
        "auxl": jnp.moveaxis(ts.q[lstr.TQ_AUXL, :, :k], 1, 0),
        "size": jnp.moveaxis(ts.q[lstr.TQ_SIZE, :, :k], 1, 0),
        "phi": jnp.moveaxis(ts.q[lstr.TQ_PHI, :, :k], 1, 0),
        "plo": jnp.moveaxis(ts.q[lstr.TQ_PLO, :, :k], 1, 0),
        "act": act_b.T,
    }
    if p.stream_wide_pop:
        xs["del_behind"] = del_behind.T

    def tier_slot(carry, x):
        f, v, mul, held = carry
        thi, tlo = x["thi"], x["tlo"]
        auxh, auxl, size = x["auxh"], x["auxl"], x["size"]
        phi, plo = x["phi"], x["plo"]
        act = x["act"]
        kind, src = unpack_aux_hi(auxh)

        # -- PACKET: dn bucket + CoDel on compact rows ---------------------
        is_pkt = act & (kind == PACKET)
        bits = (size + FRAME_OVERHEAD_BYTES) * 8
        (dn_tok, dn_nrh, dn_nrl, dn_ldh, dn_ldl, td_hi, td_lo, dn_wait) = (
            bucket_charge_vec(
                v[lstr.TV_DN_TOK], v[lstr.TV_DN_NRH], v[lstr.TV_DN_NRL],
                v[lstr.TV_DN_LDH], v[lstr.TV_DN_LDL],
                tb.flow_dn_rate, tb.flow_dn_burst, tb.flow_dn_kfull,
                tb.flow_dn_kfi, thi, tlo, bits, is_pkt, p.bucket_interval,
            )
        )
        sojourn = pair_sub_clamp(td_hi, td_lo, thi, tlo, NEVER32)
        (cd_fh, cd_fl, cd_dh, cd_dl, cd_cnt, cd_drop_state, codel_drop,
         cd_looked) = (
            codel_offer_arrays(
                v[lstr.TV_CD_FATH], v[lstr.TV_CD_FATL], v[lstr.TV_CD_DNH],
                v[lstr.TV_CD_DNL], v[lstr.TV_CD_CNT],
                v[lstr.TV_CD_DROP].astype(bool),
                td_hi, td_lo, sojourn, is_pkt, tb.codel_div,
            )
        )
        deliver = is_pkt & ~codel_drop
        v = v.at[lstr.TV_DN_TOK].set(dn_tok)
        v = v.at[lstr.TV_DN_NRH].set(dn_nrh)
        v = v.at[lstr.TV_DN_NRL].set(dn_nrl)
        v = v.at[lstr.TV_DN_LDH].set(dn_ldh)
        v = v.at[lstr.TV_DN_LDL].set(dn_ldl)
        v = v.at[lstr.TV_CD_FATH].set(cd_fh)
        v = v.at[lstr.TV_CD_FATL].set(cd_fl)
        v = v.at[lstr.TV_CD_DNH].set(cd_dh)
        v = v.at[lstr.TV_CD_DNL].set(cd_dl)
        v = v.at[lstr.TV_CD_CNT].set(cd_cnt)
        v = v.at[lstr.TV_CD_DROP].set(cd_drop_state.astype(i32))
        v = v.at[lstr.TV_N_DEL].add(deliver)
        v = v.at[lstr.TV_N_CODEL].add(is_pkt & codel_drop)
        if p.netobs:
            v = v.at[lstr.TV_NB_RXB].add(jnp.where(deliver, size, 0))
            v = v.at[lstr.TV_NB_THR].add(dn_wait)

        # -- delivery elision gate ----------------------------------------
        # elide only under the wide-pop guarantee (window < RTO_MIN): no
        # LOCAL armed in THIS window can sort below an in-window t_deliver.
        # One armed in an earlier window can (an RTO that fires at or
        # before t_deliver: LOCAL sorts below DELIVERY on a tie), and so
        # can a fallback DELIVERY of an earlier packet still queued behind
        # this one (the dn bucket held it past its window); the oracle
        # handles either first, so such a delivery takes the exact queued
        # path.  So does every delivery on huge-latency graphs.
        if p.stream_wide_pop:
            del_now = (
                deliver & pair_lt(td_hi, td_lo, we_hi, we_lo)
                & pair_lt(td_hi, td_lo, loc_hi, loc_lo)
                & ~x["del_behind"] & ~held
            )
            # dn departures are FIFO: once a row queues one delivery, the
            # deliveries of this iteration's later slots queue behind it
            held = held | (deliver & ~del_now)
        else:
            del_now = false_e
        ins_valid = deliver & ~del_now  # fallback DELIVERY self-insert
        is_del = act & (kind == DELIVERY)

        # stimulus time: the delivery time either way
        sh = jnp.where(del_now, td_hi, thi)
        sl = jnp.where(del_now, td_lo, tlo)
        flags_in, sseq_in, sack_in = lstr.unpack_pay(phi, plo)
        seg_stim = (
            (del_now | is_del) & ((phi | plo) != 0)
            & (is_cl_e | (src == tb.flow_clid))
        )
        is_loc = act & (kind == LOCAL)
        stim_open = is_loc & (size == -1) & is_cl_e
        stim_rto = is_loc & (size == lstr.SZ_RTO) & (plo == tb.flow_clid)

        f1, em1 = lstr.open_flow_vec(f, sh, sl, stim_open)
        f = lstr._merge_cols(f, f1, stim_open)
        f3, em3 = lstr.on_rto_vec(f, sh, sl, stim_rto)
        f = lstr._merge_cols(f, f3, stim_rto)
        f4, em4 = lstr.on_segment_vec(
            f, sh, sl, seg_stim, flags_in, sseq_in, sack_in, size
        )
        f = lstr._merge_cols(f, f4, seg_stim)
        sem = lstr._merge_emit(
            lstr._merge_emit(em1, em3, stim_rto), em4, seg_stim
        )
        stream_stim = stim_open | stim_rto | seg_stim
        f = f._replace(
            completed=f.completed | (sem.completed_now & stream_stim)
        )
        f, sem, st_burst = lstr.pump_epilogue_vec(f, sh, sl, stream_stim, sem)
        st_send = sem.send_valid & stream_stim
        st_rto = sem.rto_valid & stream_stim

        # -- slot-0 control send (up bucket, loss, arrival) ---------------
        se_size = sem.send_size
        se_bits = (se_size + FRAME_OVERHEAD_BYTES) * 8
        (up_tok, up_nrh, up_nrl, up_ldh, up_ldl, se_dep_hi, se_dep_lo,
         se_wait) = (
            bucket_charge_vec(
                v[lstr.TV_UP_TOK], v[lstr.TV_UP_NRH], v[lstr.TV_UP_NRL],
                v[lstr.TV_UP_LDH], v[lstr.TV_UP_LDL],
                tb.flow_up_rate, tb.flow_up_burst, tb.flow_up_kfull,
                tb.flow_up_kfi, sh, sl, se_bits, st_send,
                p.bucket_interval,
            )
        )
        se_seq = v[lstr.TV_SEND_SEQ]
        if p.has_loss:
            with jax.named_scope("path_lookup"):
                e_past_bs = pair_ge(sh, sl, bs_hi, bs_lo)
                eu = rand_u32_lane(
                    _seed_keys(p, tb),
                    (el.astype(jnp.uint32) | jnp.uint32(rng_mod.LOSS_STREAM)),
                    se_seq,
                )
                se_lost = st_send & e_past_bs & (
                    tb.flow_thresh_all | (eu < tb.flow_thresh_u32)
                )
        else:
            se_lost = false_e
        if p.dynamic_runahead:
            mul = jnp.minimum(
                mul, jnp.min(jnp.where(st_send, tb.flow_lat, NEVER32))
            )
        se_thi, se_tlo = pair_max(
            *pair_add32(se_dep_hi, se_dep_lo, tb.flow_lat), we_hi, we_lo
        )
        se_valid = st_send & ~se_lost
        se_phi, se_plo = lstr.pack_pay(
            sem.send_flags, sem.send_seq, sem.send_ack
        )

        # -- RTO arm (LOCAL self-insert at the own row) --------------------
        sa_valid = st_rto
        sa_thi, sa_tlo = sem.rto_thi, sem.rto_tlo
        sa_auxl = v[lstr.TV_LOCAL_SEQ]

        # -- burst chain (client half), charging compact up-bucket rows ----
        cthi, ctlo = sh[cl_sl], sl[cl_sl]
        b_lat_c = tb.flow_lat[cl_sl]
        cl_lanes_u32 = el[cl_sl].astype(jnp.uint32)

        def bstep(carry, cols, first: bool):
            (tok, nrh, nrl, ldh, ldl, nloss, mu, sent_before,
             btxb, bthr) = carry
            bm, bflags, bunit, back, bsize, *bdraw = cols
            bbits = (bsize + FRAME_OVERHEAD_BYTES) * 8
            if first:
                tok, nrh, nrl, ldh, ldl, bdep_hi, bdep_lo, bwait = (
                    bucket_charge_vec(
                        tok, nrh, nrl, ldh, ldl,
                        tb.flow_up_rate[cl_sl], tb.flow_up_burst[cl_sl],
                        tb.flow_up_kfull[cl_sl], tb.flow_up_kfi[cl_sl],
                        cthi, ctlo, bbits, bm, p.bucket_interval,
                    )
                )
            else:
                tok, nrh, nrl, ldh, ldl, bdep_hi, bdep_lo, bwait = (
                    bucket_charge_chained_vec(
                        tok, nrh, nrl, ldh, ldl, tb.flow_up_rate[cl_sl],
                        tb.flow_up_burst[cl_sl], bbits, bm,
                        p.bucket_interval, cthi, ctlo,
                    )
                )
            if p.netobs:
                btxb = btxb + jnp.where(bm, bsize, 0)
                bthr = bthr + bwait
            bseq = se_seq[cl_sl] + sent_before
            if p.has_loss:
                blost = bm & bdraw[0]
                nloss = nloss + blost
            else:
                blost = false_c
            if p.dynamic_runahead:
                mu = jnp.minimum(
                    mu, jnp.min(jnp.where(bm, b_lat_c, NEVER32))
                )
            barr_hi, barr_lo = pair_max(
                *pair_add32(bdep_hi, bdep_lo, b_lat_c), we_hi, we_lo
            )
            bphi, bplo = lstr.pack_pay(bflags, bunit, back)
            outs = (
                bm & ~blost, barr_hi, barr_lo, bseq, bsize, bphi, bplo,
                blost, bdep_hi, bdep_lo,
            )
            return (tok, nrh, nrl, ldh, ldl, nloss, mu,
                    sent_before + bm, btxb, bthr), outs

        up_nloss = v[lstr.TV_N_LOSS] + se_lost
        zero_cc = jnp.zeros(s_flows, dtype=i32)
        carry0 = (
            up_tok[cl_sl], up_nrh[cl_sl], up_nrl[cl_sl], up_ldh[cl_sl],
            up_ldl[cl_sl], up_nloss[cl_sl], mul,
            st_send[cl_sl].astype(i32), zero_cc, zero_cc,
        )
        # first five burst columns only (the sixth is the flowtrace
        # retransmit marker; flowtrace forbids the tier — see LaneParams)
        st_burst_c = jax.tree.map(lambda a: a[:, cl_sl], tuple(st_burst[:5]))
        if p.has_loss:
            # burst validity is a PREFIX (pump_epilogue_vec), so valid
            # unit j's send seq is the control send's plus j: the whole
            # burst's loss draws are ONE [B, S] threefry outside the
            # bucket chain instead of one per unit inside it
            with jax.named_scope("path_lookup"):
                b_units = jnp.arange(st_burst_c[0].shape[0], dtype=i32)
                bseq_all = (
                    se_seq[cl_sl] + st_send[cl_sl].astype(i32)
                )[None, :] + b_units[:, None]
                bu_all = rand_u32_lane(
                    _seed_keys(p, tb),
                    (cl_lanes_u32 | jnp.uint32(rng_mod.LOSS_STREAM))[None, :],
                    bseq_all,
                )
                bdraw_all = e_past_bs[cl_sl][None, :] & (
                    tb.flow_thresh_all[cl_sl][None, :]
                    | (bu_all < tb.flow_thresh_u32[cl_sl][None, :])
                )
            st_burst_c = st_burst_c + (bdraw_all,)
        first_cols = jax.tree.map(lambda a: a[0], st_burst_c)
        rest_cols = jax.tree.map(lambda a: a[1:], st_burst_c)
        carry, out0 = bstep(carry0, first_cols, True)
        n_rest = st_burst_c[0].shape[0] - 1
        if n_rest:
            carry, bouts_rest = scan_or_unroll(
                lambda c_, x: bstep(c_, x, False), carry, rest_cols, n_rest
            )
            bouts = jax.tree.map(
                lambda a0, ar: jnp.concatenate([a0[None], ar]),
                out0, bouts_rest,
            )
        else:
            bouts = jax.tree.map(lambda a0: a0[None], out0)
        (tok_c, nrh_c, nrl_c, ldh_c, ldl_c, nloss_c, mul, sent_after,
         btxb_c, bthr_c) = carry
        burst_total = sent_after - st_send[cl_sl].astype(i32)
        pad_c = jnp.zeros(s_flows, dtype=i32)

        v = v.at[lstr.TV_UP_TOK].set(
            jnp.concatenate([tok_c, up_tok[s_flows:]]))
        v = v.at[lstr.TV_UP_NRH].set(
            jnp.concatenate([nrh_c, up_nrh[s_flows:]]))
        v = v.at[lstr.TV_UP_NRL].set(
            jnp.concatenate([nrl_c, up_nrl[s_flows:]]))
        v = v.at[lstr.TV_UP_LDH].set(
            jnp.concatenate([ldh_c, up_ldh[s_flows:]]))
        v = v.at[lstr.TV_UP_LDL].set(
            jnp.concatenate([ldl_c, up_ldl[s_flows:]]))
        v = v.at[lstr.TV_N_LOSS].set(
            jnp.concatenate([nloss_c, up_nloss[s_flows:]]))
        v = v.at[lstr.TV_SEND_SEQ].add(
            st_send + jnp.concatenate([burst_total, pad_c]))
        v = v.at[lstr.TV_N_SENDS].add(
            st_send + jnp.concatenate([burst_total, pad_c]))
        v = v.at[lstr.TV_LOCAL_SEQ].add(sa_valid)
        if p.netobs:
            v = v.at[lstr.TV_NB_TXB].add(
                jnp.where(st_send, se_size, 0)
                + jnp.concatenate([btxb_c, pad_c]))
            v = v.at[lstr.TV_NB_THR].add(
                se_wait + jnp.concatenate([bthr_c, pad_c]))

        (bo_valid, bo_thi, bo_tlo, bo_auxl, bo_size, bo_phi, bo_plo,
         blost_all, bdep_hi_all, bdep_lo_all) = bouts

        out = {
            "ins_valid": ins_valid, "ins_thi": td_hi, "ins_tlo": td_lo,
            "ins_auxh": pack_aux_hi(jnp.full(s2, DELIVERY, dtype=i32), src),
            "ins_auxl": auxl, "ins_size": size, "ins_phi": phi,
            "ins_plo": plo,
            "se_valid": se_valid, "se_thi": se_thi, "se_tlo": se_tlo,
            "se_seq": se_seq, "se_size": se_size, "se_phi": se_phi,
            "se_plo": se_plo,
            "sa_valid": sa_valid, "sa_thi": sa_thi, "sa_tlo": sa_tlo,
            "sa_auxl": sa_auxl,
            "bo_valid": bo_valid, "bo_thi": bo_thi, "bo_tlo": bo_tlo,
            "bo_auxl": bo_auxl, "bo_size": bo_size, "bo_phi": bo_phi,
            "bo_plo": bo_plo,
            "cd_looked": cd_looked,
        }
        if log_on:
            t64d = t_join(td_hi, td_lo)
            out["rec_valid"] = is_pkt
            out["rec_time"] = t64d
            out["rec_src"] = src.astype(i64)
            out["rec_dst"] = el.astype(i64)
            out["rec_seq"] = auxl.astype(i64)
            out["rec_size"] = size.astype(i64)
            out["rec_outcome"] = jnp.where(
                codel_drop, DROP_CODEL, DELIVERED
            ).astype(i64)
            st64 = t_join(sh, sl)
            out["srec_valid"] = se_lost
            out["srec_time"] = st64
            out["srec_seq"] = se_seq.astype(i64)
            out["srec_size"] = se_size.astype(i64)
            out["brec_valid"] = blost_all
            out["brec_time"] = jnp.broadcast_to(
                st64[cl_sl][None, :], blost_all.shape
            )
            out["brec_seq"] = bo_auxl.astype(i64)
            out["brec_size"] = bo_size.astype(i64)
            if p.stream_pcap:
                out["spc_valid"] = st_send & tb.flow_pcap
                out["spc_time"] = t_join(se_dep_hi, se_dep_lo)
                out["spc_seq"] = se_seq.astype(i64)
                out["spc_size"] = se_size.astype(i64)
                out["bpc_valid"] = (
                    (bo_valid | blost_all) & tb.flow_pcap[cl_sl][None, :]
                )
                out["bpc_time"] = t_join(bdep_hi_all, bdep_lo_all)
                out["bpc_seq"] = bo_auxl.astype(i64)
                out["bpc_size"] = bo_size.astype(i64)
        return (f, v, mul, held), out

    (f, v, mul, _held), outs = scan_or_unroll(
        tier_slot, (f, v, mul, false_e), xs, k
    )
    ts = ts._replace(flows=lstr.endpoint_split(f), v=v)
    s = s._replace(
        min_used_lat=mul,
        codel_lookup_pops=s.codel_lookup_pops
        + outs["cd_looked"].sum(dtype=i32),
    )

    # ---- merge: queue + all slot channels + diverted mesh cross ----------
    def stack(key):  # [K, 2S] -> [2S, K]
        return jnp.moveaxis(outs[key], 0, 1)

    # se channels swap halves (emitter-indexed -> receiver-indexed: client
    # row r receives its server's sends and vice versa)
    def swap(a):
        return jnp.concatenate([a[s_flows:], a[:s_flows]], axis=0)

    kk = k
    bb = int(outs["bo_valid"].shape[1])
    never_kb = jnp.full((s_flows, kk * bb), NEVER32, dtype=i32)
    zero_kb = jnp.zeros((s_flows, kk * bb), dtype=i32)

    def bo_block(key, pad):
        # [K, B, S] -> [S, K*B] on the server half, pad on the client half
        arr = outs["bo_" + key]
        sv_rows = jnp.moveaxis(arr, 2, 0).reshape(s_flows, kk * bb)
        return jnp.concatenate([pad, sv_rows], axis=0)  # [2S, K*B]

    se_v = swap(stack("se_valid"))
    cand_valid = [stack("ins_valid"), stack("sa_valid"), se_v]
    cand_thi = [stack("ins_thi"), stack("sa_thi"), swap(stack("se_thi"))]
    cand_tlo = [stack("ins_tlo"), stack("sa_tlo"), swap(stack("se_tlo"))]
    # aux-hi: ins carries the packet's (DELIVERY, src); arms are LOCAL from
    # the own lane; se are PACKETs from the peer lane
    loc_auxh = pack_aux_hi(jnp.full(s2, LOCAL, dtype=i32), el)
    pkt_from_peer = pack_aux_hi(
        jnp.full(s2, PACKET, dtype=i32), tb.flow_peers
    )
    cand_auxh = [
        stack("ins_auxh"),
        jnp.broadcast_to(loc_auxh[:, None], (s2, kk)),
        jnp.broadcast_to(pkt_from_peer[:, None], (s2, kk)),
    ]
    cand_auxl = [stack("ins_auxl"), stack("sa_auxl"), swap(stack("se_seq"))]
    cand_size = [
        stack("ins_size"),
        jnp.full((s2, kk), lstr.SZ_RTO, dtype=i32),
        swap(stack("se_size")),
    ]
    cand_phi = [stack("ins_phi"), jnp.zeros((s2, kk), dtype=i32),
                swap(stack("se_phi"))]
    cand_plo = [stack("ins_plo"),
                jnp.broadcast_to(tb.flow_clid[:, None], (s2, kk)),
                swap(stack("se_plo"))]

    bo_v = bo_block("valid", jnp.zeros((s_flows, kk * bb), dtype=bool))
    cand_valid.append(bo_v)
    cand_thi.append(bo_block("thi", never_kb))
    cand_tlo.append(bo_block("tlo", never_kb))
    bo_auxh_c = pack_aux_hi(
        jnp.full(s_flows, PACKET, dtype=i32), el[:s_flows]
    )
    cand_auxh.append(
        jnp.concatenate([
            jnp.zeros((s_flows, kk * bb), dtype=i32),
            jnp.broadcast_to(bo_auxh_c[:, None], (s_flows, kk * bb)),
        ], axis=0)
    )
    cand_auxl.append(bo_block("auxl", zero_kb))
    cand_size.append(bo_block("size", zero_kb))
    cand_phi.append(bo_block("phi", zero_kb))
    cand_plo.append(bo_block("plo", zero_kb))

    if tier_cross is not None:
        cand_valid.append(tier_cross["valid"])
        cand_thi.append(tier_cross["thi"])
        cand_tlo.append(tier_cross["tlo"])
        cand_auxh.append(tier_cross["auxh"])
        cand_auxl.append(tier_cross["auxl"])
        cand_size.append(tier_cross["size"])
        cand_phi.append(jnp.zeros_like(tier_cross["auxl"]))
        cand_plo.append(jnp.zeros_like(tier_cross["auxl"]))

    cv = jnp.concatenate(cand_valid, axis=1)
    cthi = jnp.where(cv, jnp.concatenate(cand_thi, axis=1), NEVER32)
    ctlo = jnp.where(cv, jnp.concatenate(cand_tlo, axis=1), NEVER32)
    cauxh = jnp.concatenate(cand_auxh, axis=1)
    cauxl = jnp.concatenate(cand_auxl, axis=1)
    csize = jnp.concatenate(cand_size, axis=1)
    cphi = jnp.concatenate(cand_phi, axis=1)
    cplo = jnp.concatenate(cand_plo, axis=1)

    with jax.named_scope("tier_row_sort"):
        mthi, mtlo, mh, ml, ms, mphi, mplo = lax.sort(
            (
                jnp.concatenate([q[lstr.TQ_THI], cthi], axis=1),
                jnp.concatenate([q[lstr.TQ_TLO], ctlo], axis=1),
                jnp.concatenate([q[lstr.TQ_AUXH], cauxh], axis=1),
                jnp.concatenate([q[lstr.TQ_AUXL], cauxl], axis=1),
                jnp.concatenate([q[lstr.TQ_SIZE], csize], axis=1),
                jnp.concatenate([q[lstr.TQ_PHI], cphi], axis=1),
                jnp.concatenate([q[lstr.TQ_PLO], cplo], axis=1),
            ),
            dimension=1, num_keys=4, is_stable=False,
        )
    tail_mask = mthi[:, c2:] != NEVER32
    v = v.at[lstr.TV_N_QUEUE].add(tail_mask.sum(axis=1, dtype=i32))
    q = jnp.stack([
        mthi[:, :c2], mtlo[:, :c2], mh[:, :c2], ml[:, :c2], ms[:, :c2],
        mphi[:, :c2], mplo[:, :c2],
    ])
    s = s._replace(stream=ts._replace(q=q, v=v))

    # ---- log appends (log on: the hybrid turn, the mesh cells' check) ----
    if log_on:
        el64 = el.astype(i64)
        pe64 = tb.flow_peers.astype(i64)
        el64_k = jnp.broadcast_to(el64[None, :], (kk, s2)).reshape(-1)
        pe64_k = jnp.broadcast_to(pe64[None, :], (kk, s2)).reshape(-1)
        s = _append_log(p, s, {
            "valid": outs["rec_valid"].reshape(-1),
            "time": outs["rec_time"].reshape(-1),
            "src": outs["rec_src"].reshape(-1),
            "dst": outs["rec_dst"].reshape(-1),
            "seq": outs["rec_seq"].reshape(-1),
            "size": outs["rec_size"].reshape(-1),
            "outcome": outs["rec_outcome"].reshape(-1),
        })
        s = _append_log(p, s, {
            "valid": outs["srec_valid"].reshape(-1),
            "time": outs["srec_time"].reshape(-1),
            "src": el64_k, "dst": pe64_k,
            "seq": outs["srec_seq"].reshape(-1),
            "size": outs["srec_size"].reshape(-1),
            "outcome": DROP_LOSS,
        })
        shape_b = outs["brec_valid"].shape  # [K, B, S]
        el64_b = jnp.broadcast_to(
            el64[:s_flows][None, None, :], shape_b).reshape(-1)
        pe64_b = jnp.broadcast_to(
            pe64[:s_flows][None, None, :], shape_b).reshape(-1)
        s = _append_log(p, s, {
            "valid": outs["brec_valid"].reshape(-1),
            "time": outs["brec_time"].reshape(-1),
            "src": el64_b, "dst": pe64_b,
            "seq": outs["brec_seq"].reshape(-1),
            "size": outs["brec_size"].reshape(-1),
            "outcome": DROP_LOSS,
        })
        if p.stream_pcap:
            s = _append_log(p, s, {
                "valid": outs["spc_valid"].reshape(-1),
                "time": outs["spc_time"].reshape(-1),
                "src": el64_k, "dst": pe64_k,
                "seq": outs["spc_seq"].reshape(-1),
                "size": outs["spc_size"].reshape(-1),
                "outcome": PCAP_TX,
            })
            s = _append_log(p, s, {
                "valid": outs["bpc_valid"].reshape(-1),
                "time": outs["bpc_time"].reshape(-1),
                "src": el64_b, "dst": pe64_b,
                "seq": outs["bpc_seq"].reshape(-1),
                "size": outs["bpc_size"].reshape(-1),
                "outcome": PCAP_TX,
            })
        # queue-overflow records
        s = _append_log(p, s, _tail_records(
            tail_mask, mthi[:, c2:], mtlo[:, c2:], mh[:, c2:],
            ml[:, c2:], ms[:, c2:], el,
        ), tail=True)
    return s


def pop_mask(p: LaneParams, model, thi, tlo, kind_cols, we_hi, we_lo):
    """The pop phase's mask over the first K columns of the sorted queue
    rows: ``(act, wide)`` — ``act[n, j]``: lane ``n`` pops column ``j``
    this iteration; ``wide``: how many of them only the window-inert rule
    admits (``()`` where the program does not compile it).

    The per-lane pop-safety class is STATIC (a property each model
    declares, read off ``p.models_present``):

    - passive lanes (``PASSIVE_MODELS``) co-pop ANY prefix — their packet
      handling (inline counters, dst-side bucket/CoDel) and timer ticks
      (src-side bucket, cross-window sends) touch disjoint state and
      commute, so heap-order interleaving cannot be observed;
    - active lanes (phold/ping/stream) may generate same-window events
      (pump arms, DELIVERY inserts) that the CPU heap pops before later
      queue entries, so they co-pop only same-instant PACKET prefixes (a
      packet pop generates nothing that sorts before a same-time PACKET),
      or their first column alone;
    - window-inert lanes (``WINDOW_INERT_MODELS``: active datagram models
      whose DELIVERY pop puts nothing back inside the window) also co-pop
      the longest prefix of the form DELIVERY* PACKET*, at any times;
    - stream lanes have their own wide rule where ``stream_wide_pop``.

    Every class is cut at the window's end."""
    k = thi.shape[1]
    mp_r = set(p.models_present)
    same_t = (thi == thi[:, :1]) & (tlo == tlo[:, :1])
    pkt_prefix = jnp.cumprod(kind_cols == PACKET, axis=1).astype(bool)
    first_col = (jnp.arange(k) == 0)[None, :]
    passive_lane = jnp.zeros(p.n_lanes, dtype=bool)
    for _mid in sorted(PASSIVE_MODELS & mp_r):
        passive_lane = passive_lane | (model == _mid)
    allowed = passive_lane[:, None] | (same_t & (pkt_prefix | first_col))
    if p.stream_present and p.stream_wide_pop:
        # Stream lanes may co-pop WITHIN-WINDOW queue prefixes beyond
        # the same-instant rule (distinct times included):
        # - PACKET pops touch only per-lane network state (dn bucket,
        #   CoDel) and insert DELIVERYs whose relative order the merge
        #   preserves; they COMMUTE with DELIVERY pops (which touch
        #   only flow state), so the CPU heap's interleaving of an
        #   inserted DELIVERY between two queued events is
        #   unobservable;
        # - DELIVERY pops emit sends that arrive >= window end and RTO
        #   arms at now + rto >= now + RTO_MIN, which the engine
        #   guarantees lies beyond every possible window
        #   (stream_wide_pop is set only then) — and the burst law
        #   queues no same-instant pump events at all;
        # - a DELIVERY inserted by an in-prefix PACKET lands at the
        #   bucket's FIFO departure time, >= every queued delivery
        #   time, so it never overtakes a co-popped event — EXCEPT on
        #   an exact tie, where (src, seq) breaks order.  In
        #   one-to-one mode every flow-state-relevant delivery at a
        #   lane shares one src (its single peer; foreign datagrams
        #   are no-ops), making ties benign: MIXED packet/delivery
        #   prefixes are safe.  In star mode ties across clients are
        #   real, so prefixes stay single-kind.
        # - LOCAL-interrupted prefixes fall back to slot 0.
        stream_lane = (model == M_STREAM_CLIENT) | (
            model == M_STREAM_SERVER
        )
        if p.stream_one_to_one:
            stream_prefix = jnp.cumprod(
                kind_cols != LOCAL, axis=1
            ).astype(bool)
        else:
            stream_prefix = pkt_prefix | jnp.cumprod(
                kind_cols == DELIVERY, axis=1
            ).astype(bool)
        allowed = allowed | (stream_lane[:, None] & stream_prefix)
    in_window = pair_lt(thi, tlo, we_hi, we_lo)
    if not p.copop_inert:
        return allowed & in_window, ()
    # The window-inert class (points (i)-(iii) are argued at
    # WINDOW_INERT_MODELS).  Why each shape of DELIVERY* PACKET* is what
    # the oracle's heap does, the slot walk running the columns in key order:
    # - [D, D]: a DELIVERY pop inserts nothing on its lane (i) and what it
    #   sends lands at or after the window's end (ii), so nothing can
    #   sort between the two;
    # - [D, P]: the same, then the packet;
    # - [P, P'] at distinct instants: the heap may pop P's own DELIVERY
    #   (inserted at its dn-bucket departure) BEFORE P'; the two touch
    #   disjoint words (iii) — a gossip P' does READ the bitmap P's
    #   DELIVERY would have set, and a stale read only keeps a row queued
    #   (WINDOW_INERT_MODELS, the elision's point (4)) — so the
    #   interleaving is unobservable, and
    #   that DELIVERY is in the sorted queue before any LATER DELIVERY
    #   is popped: dn departures are FIFO, so it sorts at or after every
    #   DELIVERY already queued, and ties fall to the row sort's
    #   (src, seq);
    # - [P, D'] stays REFUSED: P's DELIVERY can tie D' in time and sort
    #   BELOW it by (src, seq) — sources are random, the tie is real —
    #   and a co-popped D' would then run first.  (At EQUAL times
    #   PACKET < LOCAL < DELIVERY, so a D' behind P in the row is never
    #   earlier than P: only the tie with P's OWN delivery refuses it.)
    # - LOCAL keeps the same-instant rule (first column only): a phold
    #   LOCAL is an initial message's send, a start anchors the window.
    narrow = allowed
    inert_lane = jnp.zeros(p.n_lanes, dtype=bool)
    for _mid in sorted(WINDOW_INERT_MODELS & mp_r):
        inert_lane = inert_lane | (model == _mid)

    def run_and(cols):
        # a running AND over the K columns, unrolled so that it fuses with
        # the compares around it: two jnp.cumprod here cost the
        # 10 000-lane program 2.6x the generated code this form does
        out = [cols[:, 0]]
        for j in range(1, k):
            out.append(out[-1] & cols[:, j])
        return jnp.stack(out, axis=1)

    del_prefix = run_and(kind_cols == DELIVERY)
    dp_prefix = run_and(del_prefix | (kind_cols == PACKET))
    act = (allowed | (inert_lane[:, None] & dp_prefix)) & in_window
    return act, (act & ~narrow).sum(dtype=jnp.int32)


def _build_iter(p: LaneParams, tb: LaneTables, pure_dataflow: bool = False):
    """Build the raw one-ITERATION advance (pop ≤K, process, merge) against
    the window already in ``state.now_we_hi/lo``.  The step driver wraps
    it in a per-round while (window fixed across iterations); the fused
    full run folds the window advance into a single flat loop.

    ``pure_dataflow=True`` (the fused device run) removes every
    ``lax.cond`` skip path in favour of unconditional masked work, on
    the assumption that a device-side branch costs more than the work it
    skips.  The price of a branch was read on the chip (PERF.md §6
    PR 36: a few microseconds for a scalar predicate reduced from an
    ``[N]`` mask plus one ``lax.cond`` in this body), so the assumption
    holds only for work cheaper than that: ``codel_offer_arrays`` guards
    its table gather with one, and the masked skips here stand untested
    one by one.  The step driver keeps the skips — on CPU they pay.

    TIERED mode: the [N] machinery runs with a derived params view whose
    model set excludes the stream models (the whole stream slot body,
    payload columns, and 7-operand merge vanish from the [N] tier); the
    [2S] stream tier runs as its own pop/process/merge pass per
    iteration (``_stream_tier_iter``), fed the diverted cross rows."""

    tiered = p.stream_tiered
    if tiered:
        p_lane = dataclasses.replace(
            p,
            models_present=tuple(
                m for m in p.models_present if m not in STREAM_MODELS
            ),
            stream_tiered=False,
            stream_clients=(),
            stream_pcap=False,
        )
    else:
        p_lane = p

    k = p.pops_per_iter

    def iter_body(s: LaneState) -> LaneState:
        # queue rows are kept sorted by the 4-word key — the pop is a slice
        we_hi, we_lo = s.now_we_hi, s.now_we_lo
        thi = s.q_thi[:, :k]
        tlo = s.q_tlo[:, :k]
        kind_cols = s.q_auxh[:, :k] >> AUX_KIND_SHIFT
        act, wide = pop_mask(p_lane, tb.model, thi, tlo, kind_cols,
                             we_hi, we_lo)
        if p_lane.copop_inert:
            s = s._replace(copop_wide_pops=s.copop_wide_pops + wide)
        if _has_ledger(s):
            # the loop ledger: one more iteration of the open window, its
            # live pop slots, and the lanes that popped at all
            with jax.named_scope("loop_ledger"):
                slots = act.sum(dtype=jnp.int32)
                popping = act.any(axis=1).sum(dtype=jnp.int32)
            s = _ledger_add(s, round_iters=1, pop_slots=slots,
                            active_lanes=popping)
        kcol, srccol = unpack_aux_hi(s.q_auxh[:, :k])
        popped = {
            "thi": thi,
            "tlo": tlo,
            "kind": kcol,
            "src": srccol,
            "seq": s.q_auxl[:, :k],
            "size": s.q_size[:, :k],
            # a payload word no model present uses has no column at all
            # (dead carry costs per-iteration wall time); slots still see
            # a zeros operand for it, which XLA folds
            **{w: getattr(s, "q_" + w)[:, :k] if w in p_lane.pay_words
               else jnp.zeros((p.n_lanes, k), dtype=jnp.int32)
               for w in PAY_WORDS},
            "act": act,
        }
        consumed = popped["act"]
        s = s._replace(
            q_thi=s.q_thi.at[:, :k].set(jnp.where(consumed, NEVER32, thi)),
            q_tlo=s.q_tlo.at[:, :k].set(jnp.where(consumed, NEVER32, tlo)),
        )
        if p.netobs:
            # PACKET pops this iteration join the running window
            # occupancy (flushed into nb_hist when the window advances —
            # the burst-window evidence of docs/observability.md).
            # Packets only: wire arrivals are bit-identical across
            # backends, while LOCAL/DELIVERY decomposition is not (start
            # anchors, delivery elision)
            s = s._replace(
                nb_win=s.nb_win
                + (consumed & (kind_cols == PACKET)).sum(dtype=jnp.int32)
            )

        # the stream tier's slot body is large: inlining it per slot blows
        # up XLA:CPU compile time, so slot-level conds stay there.  On the
        # accelerator the body is inlined and masked instead, on the same
        # cond-vs-mask assumption as pure_dataflow above (a slot-level
        # cond here is not yet read against the branch's price, PERF.md
        # §6 PR 36); compile tolerates the inlined body
        slot_dataflow = pure_dataflow and (
            not p_lane.stream_present or jax.default_backend() != "cpu"
        )

        def scan_body(carry, slot_cols):
            st = carry
            if slot_dataflow:
                # _process_slot is fully masked by `act`: unconditional
                # masked work beats a control decision on the device
                return _process_slot(p_lane, tb, st, slot_cols, we_hi, we_lo)

            def live(st_):
                return _process_slot(p_lane, tb, st_, slot_cols, we_hi, we_lo)

            def dead(st_):
                nb = jnp.zeros(p.n_lanes, dtype=bool)
                z64 = jnp.zeros(p.n_lanes, dtype=jnp.int64)
                z32 = jnp.zeros(p.n_lanes, dtype=jnp.int32)
                if p_lane.stream_present:
                    from ..net import ltcp as _ltcp

                    s2 = 2 * len(p_lane.stream_clients)
                    eb = jnp.zeros(s2, dtype=bool)
                    ei = jnp.zeros(s2, dtype=jnp.int32)
                    se = (eb, ei, ei, ei, ei, ei, ei)
                    sa = (eb, ei, ei, ei)
                    bshape = (_ltcp.PUMP_BURST, s2 // 2)
                    bo_b = jnp.zeros(bshape, dtype=bool)
                    bo_i = jnp.zeros(bshape, dtype=jnp.int32)
                    bo = (bo_b, bo_i, bo_i, bo_i, bo_i, bo_i, bo_i)
                    if p.log_capacity:
                        e64 = jnp.zeros(s2, dtype=jnp.int64)
                        b64 = jnp.zeros(bshape, dtype=jnp.int64)
                        srec = (eb, e64, e64, e64)
                        brec = (bo_b, b64, b64, b64)
                        if p_lane.stream_pcap:
                            spc = (eb, e64, e64, e64)
                            bpc = (bo_b, b64, b64, b64)
                        else:
                            spc = ((),) * 4
                            bpc = ((),) * 4
                    else:
                        srec = ((), (), (), ())
                        brec = ((), (), (), ())
                        spc = ((),) * 4
                        bpc = ((),) * 4
                else:
                    se = ((),) * 7
                    sa = ((),) * 4
                    bo = ((),) * 7
                    srec = ((),) * 4
                    brec = ((),) * 4
                    spc = ((),) * 4
                    bpc = ((),) * 4
                if p.pcap_any:
                    pc = (nb, z64, z64, z64, z64)
                else:
                    pc = ((), (), (), (), ())
                # the send and record channels are [F, N] under a fan-out
                n_f = p_lane.sends_per_pop
                fan = (n_f, p.n_lanes) if n_f > 1 else (p.n_lanes,)
                fb = jnp.zeros(fan, dtype=bool)
                f32 = jnp.zeros(fan, dtype=jnp.int32)
                f64 = jnp.zeros(fan, dtype=jnp.int64)
                emit = _SlotEmit(
                    nb, z32, z32, z32, z32, z32, z32, z32,
                    nb, z32, z32, z32, z32, z32, z32,
                    fb, f32, f32, f32, f32, f32, f32, f32, f32,
                    *se, *sa, *bo, *srec, *brec, *spc, *bpc,
                    *pc,
                    fb, f64, f64, f64, f64, f64, f64,
                    _ft_dead(p_lane),
                )
                if "phi" not in p_lane.emit_pay_words:
                    emit = emit._replace(ins_phi=(), out_phi=())
                return st_, emit

            return lax.cond(jnp.any(slot_cols["act"]), live, dead, st)

        slots = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), popped)  # [K, N]
        # On the accelerator, a Python loop over slots leaves fusable
        # chains (scan's stacked outputs fragment fusion into one launch
        # per step); on CPU the rolled scan keeps the HLO small — K
        # duplicated slot bodies under XLA:CPU's per-op thunk dispatch
        # made tiny parity runs hundreds of times slower.
        # spmd_unroll: emits stack [K, N] on the lane axis — the one walk
        # the sharded build must take in loop form
        with jax.named_scope("slot_walk"):
            s, emits = scan_or_unroll(
                scan_body, s, slots, k, spmd_unroll=True
            )

        if tiered:
            # unconditional merge (the tier needs the diverted cross rows
            # every iteration), then the [2S] stream tier's own pass
            s, tier_cross = _merge_append(p_lane, tb, s, emits, divert=True)
            s = _stream_tier_iter(p, tb, s, we_hi, we_lo, tier_cross)
        elif pure_dataflow:
            # always merge: a merge whose insert channels are all empty
            # reduces to the row re-sort that restores the sorted
            # invariant, so one unconditional path replaces the cond
            s = _merge_append(p, tb, s, emits)
        else:
            # the merge (exchange + wide row sort) is the expensive step;
            # iterations that generated nothing only need the invariant
            # restored after the consumed->NEVER holes
            any_new = (
                jnp.any(emits.ins_valid)
                | jnp.any(emits.arm_valid)
                | jnp.any(emits.out_valid)
            )
            if p_lane.stream_present:
                any_new = (
                    any_new
                    | jnp.any(emits.se_valid)
                    | jnp.any(emits.sa_valid)
                    | jnp.any(emits.bo_valid)
                )

            def do_merge(st: LaneState) -> LaneState:
                return _merge_append(p, tb, st, emits)

            def do_sort(st: LaneState) -> LaneState:
                st = _sort_queues(st, p_lane.row_words)
                if p_lane.sends_per_pop > 1:
                    # no slot sent: the iteration fits one pass (of none)
                    st = st._replace(
                        exchange_compact_iters=st.exchange_compact_iters + 1)
                # the ledger's books of a merge not run, as the
                # unconditional one keeps them
                return _ledger_add(
                    st, no_send=1,
                    exch_passes=int(p_lane.sends_per_pop > 1))

            s = lax.cond(any_new, do_merge, do_sort, s)

        per_slot = {
            "valid": emits.rec_valid.reshape(-1),
            "time": emits.rec_time.reshape(-1),
            "src": emits.rec_src.reshape(-1),
            "dst": emits.rec_dst.reshape(-1),
            "seq": emits.rec_seq.reshape(-1),
            "size": emits.rec_size.reshape(-1),
            "outcome": emits.rec_outcome.reshape(-1),
        }
        s = _append_log(p, s, per_slot)
        if p.pcap_any and p.log_capacity:
            kk = emits.pc_valid.shape[0]
            lanes64 = jnp.broadcast_to(
                jnp.arange(p.n_lanes, dtype=jnp.int64)[None, :],
                (kk, p.n_lanes),
            )
            s = _append_log(p, s, {
                "valid": emits.pc_valid.reshape(-1),
                "time": emits.pc_time.reshape(-1),
                "src": lanes64.reshape(-1),
                "dst": emits.pc_dst.reshape(-1),
                "seq": emits.pc_seq.reshape(-1),
                "size": emits.pc_size.reshape(-1),
                "outcome": PCAP_TX,
            })
        if p_lane.stream_present and p_lane.stream_pcap and p.log_capacity:
            # stream outbound pcap captures (PCAP_TX at departure)
            kk, s2 = emits.spc_valid.shape
            s_flows = s2 // 2
            el64 = tb.flow_lanes.astype(jnp.int64)
            pe64 = tb.flow_peers.astype(jnp.int64)
            s = _append_log(p, s, {
                "valid": emits.spc_valid.reshape(-1),
                "time": emits.spc_time.reshape(-1),
                "src": jnp.broadcast_to(el64[None, :], (kk, s2)).reshape(-1),
                "dst": jnp.broadcast_to(pe64[None, :], (kk, s2)).reshape(-1),
                "seq": emits.spc_seq.reshape(-1),
                "size": emits.spc_size.reshape(-1),
                "outcome": PCAP_TX,
            })
            kk, bb, _ss = emits.bpc_valid.shape
            shape_b = (kk, bb, s_flows)
            s = _append_log(p, s, {
                "valid": emits.bpc_valid.reshape(-1),
                "time": emits.bpc_time.reshape(-1),
                "src": jnp.broadcast_to(
                    el64[:s_flows][None, None, :], shape_b).reshape(-1),
                "dst": jnp.broadcast_to(
                    pe64[:s_flows][None, None, :], shape_b).reshape(-1),
                "seq": emits.bpc_seq.reshape(-1),
                "size": emits.bpc_size.reshape(-1),
                "outcome": PCAP_TX,
            })
        if p_lane.stream_present and p.log_capacity:
            # stream loss records (DROP_LOSS at the send instant): slot-0
            # control sends [K, 2S] and burst data segments [K, B, S],
            # with lanes/peers from the static flow tables
            kk, s2 = emits.srec_valid.shape
            s_flows = s2 // 2
            el64 = tb.flow_lanes.astype(jnp.int64)
            pe64 = tb.flow_peers.astype(jnp.int64)
            s = _append_log(p, s, {
                "valid": emits.srec_valid.reshape(-1),
                "time": emits.srec_time.reshape(-1),
                "src": jnp.broadcast_to(el64[None, :], (kk, s2)).reshape(-1),
                "dst": jnp.broadcast_to(pe64[None, :], (kk, s2)).reshape(-1),
                "seq": emits.srec_seq.reshape(-1),
                "size": emits.srec_size.reshape(-1),
                "outcome": DROP_LOSS,
            })
            kk, bb, _ss = emits.brec_valid.shape
            shape_b = (kk, bb, s_flows)
            s = _append_log(p, s, {
                "valid": emits.brec_valid.reshape(-1),
                "time": emits.brec_time.reshape(-1),
                "src": jnp.broadcast_to(
                    el64[:s_flows][None, None, :], shape_b).reshape(-1),
                "dst": jnp.broadcast_to(
                    pe64[:s_flows][None, None, :], shape_b).reshape(-1),
                "seq": emits.brec_seq.reshape(-1),
                "size": emits.brec_size.reshape(-1),
                "outcome": DROP_LOSS,
            })
        if p.flowtrace:
            # reduce the per-slot flowtrace observations to lifecycle
            # events and append once (obs/flowtrace.py stamp laws: send /
            # loss at stimulus t, TB wait at bucket departure, queue-enter
            # at arrival, delivery / codel at the dn departure)
            ftc = emits.ft
            lanes_i = jnp.arange(p.n_lanes, dtype=jnp.int32)
            kk = ftc["sd_valid"].shape[0]
            lanes_k = jnp.broadcast_to(lanes_i[None, :], (kk, p.n_lanes))
            sd_smp = _flow_sampled(p, lanes_k, ftc["sd_dst"])
            ar_smp = _flow_sampled(p, ftc["ar_src"], lanes_k)
            sd_wait = (
                (ftc["sd_dhi"] != ftc["sd_thi"])
                | (ftc["sd_dlo"] != ftc["sd_tlo"])
            )
            ar_wait = (
                (ftc["ar_dhi"] != ftc["ar_thi"])
                | (ftc["ar_dlo"] != ftc["ar_tlo"])
            )
            groups = [
                # generic sends (lane -> dst): SEND at stimulus t, UP-side
                # TB wait at departure (lost sends charge the bucket too),
                # loss drop at stimulus t, queue-enter at arrival
                _flow_group(
                    ftc["sd_valid"] & sd_smp, ftc["sd_thi"], ftc["sd_tlo"],
                    ftr.FT_SEND, lanes_k, ftc["sd_dst"], ftc["sd_seq"],
                    ftc["sd_size"], 0),
                _flow_group(
                    ftc["sd_valid"] & sd_wait & sd_smp,
                    ftc["sd_dhi"], ftc["sd_dlo"], ftr.FT_TB_WAIT, lanes_k,
                    ftc["sd_dst"], ftc["sd_seq"], ftc["sd_size"],
                    ftr.TB_UP),
                _flow_group(
                    ftc["sd_lost"] & sd_smp, ftc["sd_thi"], ftc["sd_tlo"],
                    ftr.FT_DROP, lanes_k, ftc["sd_dst"], ftc["sd_seq"],
                    ftc["sd_size"], ftr.CAUSE_LOSS),
                _flow_group(
                    ftc["sd_valid"] & ~ftc["sd_lost"] & sd_smp,
                    ftc["sd_ahi"], ftc["sd_alo"], ftr.FT_QUEUE_ENTER,
                    lanes_k, ftc["sd_dst"], ftc["sd_seq"], ftc["sd_size"],
                    0),
                # packet arrivals (src -> lane): DN-side TB wait, codel
                # drop or delivery — all at the dn bucket departure
                _flow_group(
                    ftc["ar_valid"] & ar_wait & ar_smp,
                    ftc["ar_dhi"], ftc["ar_dlo"], ftr.FT_TB_WAIT,
                    ftc["ar_src"], lanes_k, ftc["ar_seq"], ftc["ar_size"],
                    ftr.TB_DN),
                _flow_group(
                    ftc["ar_valid"] & ftc["ar_drop"] & ar_smp,
                    ftc["ar_dhi"], ftc["ar_dlo"], ftr.FT_DROP,
                    ftc["ar_src"], lanes_k, ftc["ar_seq"], ftc["ar_size"],
                    ftr.CAUSE_CODEL),
                _flow_group(
                    ftc["ar_valid"] & ~ftc["ar_drop"] & ar_smp,
                    ftc["ar_dhi"], ftc["ar_dlo"], ftr.FT_DELIVERY,
                    ftc["ar_src"], lanes_k, ftc["ar_seq"], ftc["ar_size"],
                    0),
            ]
            if p_lane.stream_present:
                kk2, s2 = ftc["ss_valid"].shape
                s_f = s2 // 2
                el_k = jnp.broadcast_to(
                    tb.flow_lanes[None, :], (kk2, s2))
                pe_k = jnp.broadcast_to(
                    tb.flow_peers[None, :], (kk2, s2))
                ss_smp = _flow_sampled(p, el_k, pe_k)
                ss_kind = jnp.where(
                    ftc["ss_retx"], ftr.FT_RETRANSMIT, ftr.FT_SEND)
                ss_wait = (
                    (ftc["ss_dhi"] != ftc["ss_thi"])
                    | (ftc["ss_dlo"] != ftc["ss_tlo"])
                )
                bs_shape = ftc["bs_valid"].shape
                el_b = jnp.broadcast_to(
                    tb.flow_lanes[:s_f][None, None, :], bs_shape)
                pe_b = jnp.broadcast_to(
                    tb.flow_peers[:s_f][None, None, :], bs_shape)
                bs_smp = _flow_sampled(p, el_b, pe_b)
                bs_kind = jnp.where(
                    ftc["bs_retx"], ftr.FT_RETRANSMIT, ftr.FT_SEND)
                bs_wait = (
                    (ftc["bs_dhi"] != ftc["bs_thi"])
                    | (ftc["bs_dlo"] != ftc["bs_tlo"])
                )
                groups += [
                    # stream slot-0 control sends (endpoint -> peer)
                    _flow_group(
                        ftc["ss_valid"] & ss_smp, ftc["ss_thi"],
                        ftc["ss_tlo"], ss_kind, el_k, pe_k, ftc["ss_seq"],
                        ftc["ss_size"], 0),
                    _flow_group(
                        ftc["ss_valid"] & ss_wait & ss_smp,
                        ftc["ss_dhi"], ftc["ss_dlo"], ftr.FT_TB_WAIT,
                        el_k, pe_k, ftc["ss_seq"], ftc["ss_size"],
                        ftr.TB_UP),
                    _flow_group(
                        ftc["ss_lost"] & ss_smp, ftc["ss_thi"],
                        ftc["ss_tlo"], ftr.FT_DROP, el_k, pe_k,
                        ftc["ss_seq"], ftc["ss_size"], ftr.CAUSE_LOSS),
                    _flow_group(
                        ftc["ss_valid"] & ~ftc["ss_lost"] & ss_smp,
                        ftc["ss_ahi"], ftc["ss_alo"], ftr.FT_QUEUE_ENTER,
                        el_k, pe_k, ftc["ss_seq"], ftc["ss_size"], 0),
                    # burst data segments (client -> server)
                    _flow_group(
                        ftc["bs_valid"] & bs_smp, ftc["bs_thi"],
                        ftc["bs_tlo"], bs_kind, el_b, pe_b, ftc["bs_seq"],
                        ftc["bs_size"], 0),
                    _flow_group(
                        ftc["bs_valid"] & bs_wait & bs_smp,
                        ftc["bs_dhi"], ftc["bs_dlo"], ftr.FT_TB_WAIT,
                        el_b, pe_b, ftc["bs_seq"], ftc["bs_size"],
                        ftr.TB_UP),
                    _flow_group(
                        ftc["bs_lost"] & bs_smp, ftc["bs_thi"],
                        ftc["bs_tlo"], ftr.FT_DROP, el_b, pe_b,
                        ftc["bs_seq"], ftc["bs_size"], ftr.CAUSE_LOSS),
                    _flow_group(
                        ftc["bs_valid"] & ~ftc["bs_lost"] & bs_smp,
                        ftc["bs_ahi"], ftc["bs_alo"], ftr.FT_QUEUE_ENTER,
                        el_b, pe_b, ftc["bs_seq"], ftc["bs_size"], 0),
                ]
            s = _append_flow(p, s, _concat_flow_groups(groups))
        return s._replace(iters=s.iters + 1)

    return iter_body


def _effective_runahead(p: LaneParams, s: LaneState):
    """Static: the precomputed min possible latency.  Dynamic: the min
    latency of paths used so far, never below the floor (identical law to
    CpuEngine.current_runahead / the reference's runahead.rs:44-57)."""
    if not p.dynamic_runahead:
        return p.runahead
    return jnp.where(
        s.min_used_lat == NEVER32,
        jnp.int32(p.runahead),
        jnp.maximum(s.min_used_lat, jnp.int32(max(p.runahead_floor, 1))),
    )


def _build_round(p: LaneParams, tb: LaneTables, stop=None):
    """Build the raw (un-jitted) one-round advance: state -> (state, done)
    for the STEP driver.  Preserves the pre-round state when the
    simulation already finished (a full-state ``where``); the fused full
    run uses ``_build_iter`` directly instead.  ``stop`` is an optional
    traced int64 scalar in place of the static ``p.stop_time`` (a
    fault-epoch segment's bound: ``make_round_fn(..., epochs=True)``)."""
    iter_body = _build_iter(p, tb)
    if stop is None:
        stop = p.stop_time

    def round_fn(s: LaneState) -> tuple[LaneState, jnp.ndarray]:
        # rows sorted: col 0 is each queue's min; lexicographic pair min
        start = t_join(*_queue_min(p, s))
        done = start >= stop
        if p.netobs:
            # a live round IS a new window: flush the previous round's
            # occupancy (the trailing window flushes at collect)
            s = _flush_hist(p, s, ~done)
        s = _ledger_open_window(s, ~done)
        window_end = jnp.minimum(
            start + _effective_runahead(p, s), stop
        )
        we_hi, we_lo = t_split(window_end)
        s = s._replace(now_we_hi=we_hi, now_we_lo=we_lo)

        def cond(st: LaneState):
            mh, ml = _queue_min(p, st)
            return pair_lt(mh, ml, st.now_we_hi, st.now_we_lo)

        def body(st: LaneState):
            return iter_body(st)

        s2 = lax.while_loop(cond, body, s)
        s2 = s2._replace(rounds=s2.rounds + 1)
        # keep the pre-round state when already done
        s2 = jax.tree.map(lambda a, b: jnp.where(done, a, b), s, s2)
        return s2, done

    return round_fn


def make_round_fn(p: LaneParams, tb: LaneTables, epochs: bool = False):
    """Jitted one-round advance: state -> (state, done).  Step-wise driver
    for debugging, parity tests, and run-control pauses.  ``epochs``: the
    fault-epoch form, ``round_fn(state, paths, stop_hi, stop_lo, seed_lo,
    seed_hi)`` (see :func:`make_run_fn`)."""
    if not epochs:
        return jax.jit(_build_round(p, tb))
    return _epoch_fn(
        tb, lambda t, hi, lo: _build_round(p, t, stop=t_join(hi, lo)))


# -- while-carry packing -----------------------------------------------------
# The fused run assumes a per-BUFFER cost on every while iteration (a
# ~32-leaf LaneState carry vs a handful of stacked arrays; unmeasured on
# the attached chip), so it packs the carry into a handful of stacked arrays at the loop
# boundary.  Slicing them apart inside the body fuses into the consumers;
# restacking is one concatenate per group.

_I32_N_FIELDS = (
    "send_seq", "local_seq", "app_draws",
    "up_tokens", "up_nr_hi", "up_nr_lo", "up_ld_hi", "up_ld_lo",
    "dn_tokens", "dn_nr_hi", "dn_nr_lo", "dn_ld_hi", "dn_ld_lo",
    "cd_fat_hi", "cd_fat_lo", "cd_dnext_hi", "cd_dnext_lo",
    "cd_drop_count",
    "m_sent", "m_peer_offset",
    "n_delivered", "n_loss", "n_codel", "n_queue", "recv_bytes",
    "n_sends", "n_hops",
)
_SCALAR_FIELDS = ("log_count", "log_lost", "rounds", "iters",
                  "codel_lookup_pops", "now_we_hi", "now_we_lo",
                  "min_used_lat")
# hybrid-backend scalar extension (present only when egress is live)
_EG_SCALARS = ("egress_count", "egress_lost", "egress_min_hi",
               "egress_min_lo")
# netobs extension (present only when LaneParams.netobs): [N] counters
# ride the c32 stack after cd_dropping, the window count rides the
# scalar vector, and the [B] histogram is its own carry leaf
_NB_N_FIELDS = ("nb_txb", "nb_rxb", "nb_thr", "nb_shed")
_NB_SCALARS = ("nb_win",)
# flowtrace extension (present only when LaneParams.flowtrace): the ring
# cursor/lost ride the scalar vector, the [FL, F] ring is its own leaf
_FL_SCALARS = ("fl_count", "fl_lost")
# append engage counters (present when the log or the egress buffer is)
_AP_SCALARS = ("ap_blocks", "ap_rows", "ap_tail_blocks")
# rows of LaneState.peaks
PK_QUEUE, PK_CROSS, PK_CROSS_SHED = range(3)


def pack_state(s: LaneState):
    # the row's words present: five and the payload columns that exist
    q = jnp.stack([col for col in _q_cols(s, ROW_WORDS + PAY_WORDS)
                   if not isinstance(col, tuple)])
    has_nb = not isinstance(s.nb_txb, tuple)
    nb_fields = _NB_N_FIELDS if has_nb else ()
    c32 = jnp.stack(
        [getattr(s, f) for f in _I32_N_FIELDS]
        + [s.cd_dropping.astype(jnp.int32)]
        + [getattr(s, f) for f in nb_fields]
    )
    sc_fields = _scalar_fields(
        has_eg=not isinstance(s.egress, tuple), has_nb=has_nb,
        has_fl=not isinstance(s.fl_buf, tuple),
        has_ap=not isinstance(s.ap_blocks, tuple),
    )
    sc = jnp.stack(
        [jnp.asarray(getattr(s, f), dtype=jnp.int32) for f in sc_fields]
    )
    return (q, c32, sc, s.log, s.stream, s.egress, s.nb_hist, s.fl_buf,
            s.peaks, s.copop_wide_pops, s.exchange_compact_iters,
            s.exchange_slot_peak, s.gossip, s.gossip_age, s.gossip_elided,
            s.loop_hist, s.loop_acc)


def _scalar_fields(has_eg: bool, has_nb: bool, has_fl: bool, has_ap: bool):
    """The packed scalar vector's layout for the optional blocks live."""
    return (
        _SCALAR_FIELDS
        + (_EG_SCALARS if has_eg else ())
        + (_NB_SCALARS if has_nb else ())
        + (_FL_SCALARS if has_fl else ())
        + (_AP_SCALARS if has_ap else ())
    )


def unpack_state(carry) -> LaneState:
    (q, c32, sc, log, stream, egress, nb_hist, fl_buf, peaks,
     copop_wide_pops, exchange_compact_iters, exchange_slot_peak, gossip,
     gossip_age, gossip_elided, loop_hist, loop_acc) = carry
    words = ROW_WORDS + pay_words(q.shape[0] - len(ROW_WORDS))
    # the optional blocks' own carry leaves say which are live; the append
    # counters have none, so the scalar count left over tells
    has_eg = not isinstance(egress, tuple)
    has_nb = not isinstance(nb_hist, tuple)
    has_fl = not isinstance(fl_buf, tuple)
    sc_fields = _scalar_fields(has_eg, has_nb, has_fl, has_ap=False)
    if sc.shape[0] != len(sc_fields):
        sc_fields += _AP_SCALARS
    kw = {f: c32[i] for i, f in enumerate(_I32_N_FIELDS)}
    n_base = len(_I32_N_FIELDS) + 1  # + cd_dropping
    if has_nb:
        kw.update({
            f: c32[n_base + i] for i, f in enumerate(_NB_N_FIELDS)
        })
    kw.update({f: sc[i] for i, f in enumerate(sc_fields)})
    kw.update({"q_" + w: () for w in PAY_WORDS})
    kw.update({"q_" + w: q[i] for i, w in enumerate(words)})
    return LaneState(
        stream=stream,
        cd_dropping=c32[len(_I32_N_FIELDS)].astype(bool),
        log=log, egress=egress, nb_hist=nb_hist, fl_buf=fl_buf,
        peaks=peaks, copop_wide_pops=copop_wide_pops,
        exchange_compact_iters=exchange_compact_iters,
        exchange_slot_peak=exchange_slot_peak, gossip=gossip,
        gossip_age=gossip_age, gossip_elided=gossip_elided,
        loop_hist=loop_hist, loop_acc=loop_acc, **kw,
    )


def _build_full_run(p: LaneParams, tb: LaneTables, dynamic_stop=None):
    """Raw (un-jitted) full-simulation run, entirely on-device.

    ONE flat ``lax.while_loop`` whose body both advances the window (only
    when the previous window is exhausted — the identical window sequence
    of the nested per-round form, so arrival bumps and event logs stay
    bit-identical) and pops/processes/merges one iteration of events, over
    the PACKED carry (see pack_state).  Shared by the single-device and
    sharded drivers.

    ``dynamic_stop`` is an optional traced ``(stop_hi, stop_lo)`` int32
    pair that replaces the static ``p.stop_time`` split — the sweep path
    threads per-scenario (and per-fault-segment) stop times through it
    so one trace serves every segment bound."""
    iter_fn = _build_iter(p, tb, pure_dataflow=True)

    if dynamic_stop is None:
        stop_hi, stop_lo = p.stop_time >> 31, p.stop_time & MASK31
    else:
        stop_hi, stop_lo = dynamic_stop

    def full_run(s: LaneState) -> LaneState:
        def cond(carry):
            mh, ml = _queue_min(p, unpack_state(carry))
            return pair_lt(mh, ml, stop_hi, stop_lo)

        def step(st: LaneState):
            mn_hi, mn_lo = _queue_min(p, st)
            live = pair_lt(mn_hi, mn_lo, stop_hi, stop_lo)
            fresh = pair_ge(mn_hi, mn_lo, st.now_we_hi, st.now_we_lo) & live
            if p.netobs:
                # window advance: flush the finished window's occupancy
                st = _flush_hist(p, st, fresh)
            st = _ledger_open_window(st, fresh)
            # clamp before adding runahead: min_next may be the NEVER pair
            # on a no-op trailing step
            c_hi, c_lo = pair_sel(
                pair_lt(mn_hi, mn_lo, stop_hi, stop_lo),
                mn_hi, mn_lo, stop_hi, stop_lo,
            )
            c_hi, c_lo = pair_add32(c_hi, c_lo, _effective_runahead(p, st))
            c_hi, c_lo = pair_sel(
                pair_lt(c_hi, c_lo, stop_hi, stop_lo),
                c_hi, c_lo, stop_hi, stop_lo,
            )
            st = st._replace(
                now_we_hi=jnp.where(fresh, c_hi, st.now_we_hi),
                now_we_lo=jnp.where(fresh, c_lo, st.now_we_lo),
                rounds=st.rounds + fresh.astype(st.rounds.dtype),
            )
            return iter_fn(st)

        def body(carry):
            return pack_state(step(unpack_state(carry)))

        return unpack_state(lax.while_loop(cond, body, pack_state(s)))

    return full_run


def make_run_fn(p: LaneParams, tb: LaneTables, epochs: bool = False):
    """Jitted full-simulation run — the bench hot path (one device call per
    simulation): ``run_fn(state)``, or ``run_fn(state, seed_lo, seed_hi)``
    with the master seed's two uint32 words as ARGUMENTS of the program
    (the sweep path's traced ``LaneTables`` leaves) in place of constants
    in it.  The seed words are the only thing a program whose network
    loses packets holds of ``general.seed``, so handed over they leave one
    compiled program — and one entry of the persistent compile cache — for
    every seed; the draws are bit-identical either way (``_seed_keys``).

    ``epochs``: the serial form of the sweep's law (``make_sweep_fn``
    without the ``vmap``) for a run segmented at fault epochs:
    ``run_fn(state, paths, stop_hi, stop_lo, seed_lo, seed_hi)`` runs
    ``state`` up to the traced bound against ``tb`` with the ``paths``
    leaves (a dict of the ``LaneTables`` fields one epoch's tables decide:
    what ``TpuEngine._path_tables`` builds) in place of ``tb``'s — ONE
    program for every segment, epoch and seed of a schedule.  Its
    ``.traces`` counts the traces, as ``make_sweep_fn``'s."""
    if epochs:
        return _epoch_fn(
            tb, lambda t, hi, lo: _build_full_run(p, t, dynamic_stop=(hi, lo)))

    def full_run(s: LaneState, *seed) -> LaneState:
        t = tb._replace(seed_lo=seed[0], seed_hi=seed[1]) if seed else tb
        return _build_full_run(p, t)(s)

    return jax.jit(full_run)


def _epoch_fn(tb: LaneTables, build):
    """The jitted fault-epoch form of a driver: ``build(tables, stop_hi,
    stop_lo)`` gives the raw function of the state, the tables being
    ``tb`` with the traced path leaves and seed words laid over it."""

    def epoch_run(s: LaneState, paths, stop_hi, stop_lo, seed_lo, seed_hi):
        t = tb._replace(**paths, seed_lo=seed_lo, seed_hi=seed_hi)
        return build(t, stop_hi, stop_lo)(s)

    return _jit_counting_traces(epoch_run)


def _jit_counting_traces(fn):
    """``jax.jit(fn)`` behind a wrapper whose ``.traces`` counts how often
    ``fn`` was traced — the compile probe a one-compile assertion reads —
    and whose ``.lower`` is the jitted function's (the AOT path)."""

    @functools.wraps(fn)
    def counted(*args):
        wrapper.traces += 1
        return fn(*args)

    jitted = jax.jit(counted)

    def wrapper(*args):
        return jitted(*args)

    wrapper.traces = 0
    wrapper.lower = jitted.lower
    return wrapper


def make_sweep_fn(p: LaneParams):
    """Jitted VMAPPED full-simulation run over a leading scenario axis
    (shadow_tpu/sweep): S whole simulations as one compiled kernel.

    The per-scenario arguments are all TRACED — the whole LaneTables
    pytree (per-scenario latency/loss/rate tables and the seed_lo/
    seed_hi leaves), the (stop_hi, stop_lo) pair, and the LaneState —
    so one XLA compile serves every seed, fault segment, and stop bound
    whose array shapes match (the sweep variant compiler enforces that
    congruence).  Under vmap the while_loop batching rule runs the body
    while ANY scenario's cond holds and per-element selects the old
    carry where it does not: finished scenarios are preserved exactly
    (including iters), which is what makes the batched run bit-identical
    per scenario to S serial runs — a per-scenario done mask, not a
    global barrier.

    The returned wrapper counts traces in ``.traces`` — the compile
    probe the one-compile acceptance assertion reads."""

    def run_one(tb: LaneTables, stop_hi, stop_lo, s: LaneState):
        return _build_full_run(p, tb, dynamic_stop=(stop_hi, stop_lo))(s)

    # .lower: the AOT path (tests/test_chip_compile.py)
    return _jit_counting_traces(jax.vmap(run_one))


# --------------------------------------------------------------------------
# hybrid backend device entry points (backend/hybrid.py drives these)
# --------------------------------------------------------------------------


def _inject_merge(p: LaneParams, tb: LaneTables, s: LaneState, inj):
    """Merge a host-staged injection block into the lane queues.

    ``inj`` is a dict of [B] arrays (valid, dst, thi, tlo, auxh, auxl,
    size): PACKET arrival events computed host-side (external hosts' up
    bucket + loss + latency already applied — cpu_engine.send_packet's
    law).  Runs ONCE per device call (outside the while loop), so a plain
    ``searchsorted`` for the segment bounds is fine here — the histogram
    matmul only matters inside the hot body.  Overflow past the per-lane
    fan-in or queue capacity is counted in ``n_queue`` (strict mode raises
    host-side, same as cross overflow)."""
    n, c = p.n_lanes, p.capacity
    valid = inj["valid"]
    dst = jnp.where(valid, inj["dst"], jnp.int32(n))
    thi = jnp.where(valid, inj["thi"], NEVER32)
    tlo = jnp.where(valid, inj["tlo"], NEVER32)
    dst_s, thi_s, tlo_s, auxh_s, auxl_s, size_s = lax.sort(
        (dst, thi, tlo, inj["auxh"], inj["auxl"], inj["size"]),
        dimension=0, num_keys=1, is_stable=False,
    )
    bounds = jnp.searchsorted(
        dst_s, jnp.arange(n + 1, dtype=dst_s.dtype), side="left"
    ).astype(jnp.int32)
    start, cnt = bounds[:n], bounds[1:] - bounds[:n]
    cxi = min(p.inject_cross or c, c)
    _in_seg, cross_words = _cross_block(
        [thi_s, tlo_s, auxh_s, auxl_s, size_s], start, cnt, cxi)
    lost_pre = jnp.maximum(cnt - cxi, 0)

    cols = [jnp.concatenate([q, new], axis=1)
            for q, new in zip(_q_cols(s, ROW_WORDS), cross_words)]
    # a host-staged event carries no payload: zeros beside the rows' own
    zpad = jnp.zeros((n, cxi), dtype=jnp.int32)
    cols += [jnp.concatenate([q, zpad], axis=1)
             for q in _q_cols(s, p.pay_words)]
    merged = _sort_rows(p.row_words, cols)
    mthi = merged["thi"]
    s = _q_replace(s, p.pay_words, [merged[w][:, :c] for w in p.pay_words])
    tail = (mthi[:, c:] != NEVER32).sum(axis=1, dtype=jnp.int32)
    if p.netobs:
        s = s._replace(nb_shed=s.nb_shed + lost_pre)
    s = _q_replace(s, ROW_WORDS, [merged[w][:, :c] for w in ROW_WORDS])
    s = s._replace(n_queue=s.n_queue + tail + lost_pre)
    if not p.all_passive:
        # the injection block is ``capacity`` wide (``cxi``), not
        # ``cross_cap``: what it sheds is cured by the QUEUE's option, so
        # ``lost_pre`` counts toward the queue's peak — the most events one
        # lane was handed — and never toward PK_CROSS_SHED, whose cure
        # (tpu_cross_capacity) would not save it
        s = s._replace(peaks=s.peaks.at[PK_QUEUE].max(
            (_row_fill(mthi) + lost_pre).max()))
    return s


# indices into the packed scalar vector make_hybrid_fused_fn returns: ONE
# int64 transfer per device turn carries every host-side decision input
# (lane_min, completed window end, dynamic-runahead fold, egress
# fill/overflow), then the consumed-window count and the per-window ends
HYB_LANE_MIN = 0
HYB_DEV_WE = 1
HYB_MIN_USED = 2
HYB_EGRESS_COUNT = 3
HYB_EGRESS_LOST = 4
HYB_K_DONE = 5
HYB_WE_BASE = 6
# ... and, behind the ``k_cap`` window ends, the first HYB_EGRESS_HEAD rows
# of the egress buffer, flattened: the turn's deliveries ride the one
# readback, and a second read fetches only what lies past the head.  The
# smallest power of two that covers a turn's egress count in >= 99 % of
# the turns of ``hybrid151_chains`` (PERF.md section 3 has the counts)
HYB_EGRESS_HEAD = 128


def hyb_egress_rows(scalars, k_cap: int):
    """The egress head, ``[rows, 6]``, of a fused call's packed vector."""
    return scalars[HYB_WE_BASE + k_cap:].reshape(-1, 6)


class TurnBlock(NamedTuple):
    """The layout of the ONE ``int32[width]`` block a hybrid turn carries
    from host to device, known here and nowhere else: the seven injection
    columns of ``inject_batch`` rows each (``valid`` as 0/1 words) lead
    it, so their offsets depend on ``inject_batch`` alone; then the
    peeked schedule's ``hi`` and ``lo`` words (``ext_slots`` each), then
    ``ext_used`` and ``k_eff``.  The host fills a numpy block through
    ``columns`` / ``schedule`` (views) and the two word offsets; the
    device entry points slice the same block back with ``unpack`` /
    ``injection``."""

    inject_batch: int
    ext_slots: int

    COLUMNS = ("valid", "dst", "thi", "tlo", "auxh", "auxl", "size")

    @property
    def used_at(self) -> int:
        return 7 * self.inject_batch + 2 * self.ext_slots

    @property
    def k_at(self) -> int:
        return self.used_at + 1

    @property
    def width(self) -> int:
        return self.used_at + 2

    def columns(self, block) -> dict:
        """The injection columns as slices of ``block`` (views of a numpy
        block), all int32."""
        b = self.inject_batch
        return {
            name: block[i * b:(i + 1) * b]
            for i, name in enumerate(self.COLUMNS)
        }

    def schedule(self, block):
        """The schedule's (hi, lo) words as slices of ``block``."""
        o, e = 7 * self.inject_batch, self.ext_slots
        return block[o:o + e], block[o + e:o + 2 * e]

    def empty(self) -> np.ndarray:
        """A host block that injects nothing and schedules nothing."""
        block = np.zeros(self.width, dtype=np.int32)
        self.clear(block)
        hi, lo = self.schedule(block)
        hi[:] = lo[:] = block[self.used_at] = NEVER32
        return block

    def clear(self, block) -> None:
        """Empty a host block's injection part."""
        cols = self.columns(block)
        cols["valid"][:] = 0
        cols["thi"][:] = cols["tlo"][:] = NEVER32

    def pack(self, inj, ext_hi, ext_lo, ext_used, k_eff) -> np.ndarray:
        """A fresh host block from the values ``unpack`` gives back."""
        block = self.empty()
        for name, col in self.columns(block).items():
            col[:] = inj[name]
        hi, lo = self.schedule(block)
        hi[:], lo[:] = ext_hi, ext_lo
        block[self.used_at], block[self.k_at] = ext_used, k_eff
        return block

    def injection(self, block) -> dict:
        """The ``inj`` dict ``_inject_merge`` takes."""
        inj = self.columns(block)
        inj["valid"] = inj["valid"] != 0
        return inj

    def unpack(self, block):
        """(inj, ext_thi, ext_tlo, ext_used, k_eff) of ``block``."""
        hi, lo = self.schedule(block)
        return (
            self.injection(block), hi, lo,
            block[self.used_at], block[self.k_at],
        )


def _build_inject(p: LaneParams, tb: LaneTables):
    """The standalone injection merge (used when the host stages more
    than one batch worth of sends between device turns): it takes the
    turn's block (``TurnBlock``) and reads its injection part alone."""
    lay = TurnBlock(p.inject_batch, 0)  # the columns lead the block

    def inject(s: LaneState, turn_in):
        return _inject_merge(p, tb, s, lay.injection(turn_in))

    return inject


def make_inject_fn(p: LaneParams, tb: LaneTables):
    """Jitted ``_build_inject``."""
    return jax.jit(_build_inject(p, tb))


def _build_hybrid_fused_run(p: LaneParams, tb: LaneTables, k_cap: int,
                            ext_slots: int):
    """Device half of the hybrid backend, the k-window FUSED call
    (docs/hybrid.md "k-window fusion law"): merge the injection block,
    then free-run the window loop under the EXTERNAL bound.

    The window law is ``start = min(lane_min, ext_bound)`` where
    ``ext_bound = min(ext_min, egress_min)`` — ``ext_min`` is the host
    side's next managed event and ``egress_min`` the earliest delivery
    already egressed this call (a pending host event the host hasn't seen
    yet).  The loop free-runs across windows the host has no events in
    (the conservative-PDES contract: identical window sequence to the
    scalar oracle) and COMPLETES every window the host participates in
    (``ext_bound < now_we``), consuming up to ``k_eff`` such windows from
    a host-provided schedule of peeked next-event times and recording
    each consumed window's end for the post-hoc host round servicing
    (the arrival-frontier validation law lives host-side in
    backend/hybrid.py; a misprediction rolls back by re-running this
    kernel from the pre-dispatch state with ``k_eff`` = the validated
    prefix, which reproduces the prefix bit-identically).  Also returns
    early when the egress buffer runs low on headroom.

    ``turn_in`` is the turn's ONE host-to-device block (``TurnBlock``):
    the injection columns, the schedule, ``ext_used`` and ``k_eff``.  Its
    schedule ([ext_slots] int32 hi/lo pairs, ascending) carries the
    host side's next distinct event times; the LAST slot is the
    **horizon** — the first external time the schedule does NOT cover
    (NEVER when the schedule is exhaustive).  Participation at or past
    the horizon ends the dispatch without consuming, so the device never
    free-runs past an external event it was not told about.  Between
    consumed windows the ``egress_min`` free-run guard is RE-ARMED as the
    min pending DELIVERED egress time at or past the consumed frontier —
    a popped fold of the running min, so an unserviced host delivery
    keeps bounding the window law exactly as the oracle's DELIVERY event
    would.

    Returns (state, scalars[6 + k_cap + 6 * HYB_EGRESS_HEAD] int64): the
    HYB_* slots, the consumed-window count (HYB_K_DONE), the consumed
    window ends (HYB_WE_BASE + i) and the egress buffer's first
    HYB_EGRESS_HEAD rows (the buffer holds at least 1 024), so that the
    host's one blocking read brings the turn's deliveries.  With
    ``k_eff = 1`` the call returns after the FIRST window with external
    participation: one device call per host sync (``hybrid_fuse_k: 1``,
    and every one-window rollback rebuild)."""
    iter_fn = _build_iter(p, tb, pure_dataflow=True)
    stop_hi, stop_lo = p.stop_time >> 31, p.stop_time & MASK31
    room_floor = p.egress_capacity - p.ext_per_iter
    eg_idx = jnp.arange(p.egress_capacity, dtype=jnp.int32)
    never64 = (NEVER32 << 31) | NEVER32  # the (NEVER32, NEVER32) pair
    lay = TurnBlock(p.inject_batch, ext_slots)

    def ext_bound(st, ext_hi, ext_lo):
        lt = pair_lt(ext_hi, ext_lo, st.egress_min_hi, st.egress_min_lo)
        return (
            jnp.where(lt, ext_hi, st.egress_min_hi),
            jnp.where(lt, ext_lo, st.egress_min_lo),
        )

    def egress_refold(st, thr_hi, thr_lo):
        """Min pending DELIVERED egress time >= the consumed frontier:
        rows below it were applied host-side with their windows."""
        t = st.egress[:, 0]
        thr = t_join(thr_hi, thr_lo)
        live = (
            (eg_idx < st.egress_count)
            & (st.egress[:, 5] == DELIVERED)
            & (t >= thr)
        )
        tmin = jnp.min(jnp.where(live, t, jnp.int64(never64)))
        return (tmin >> 31).astype(jnp.int32), (
            tmin & MASK31
        ).astype(jnp.int32)

    def fused_run(s: LaneState, turn_in):
        inj, ext_thi, ext_tlo, ext_used, k_eff = lay.unpack(turn_in)
        if p.dynamic_runahead:
            s = s._replace(
                min_used_lat=jnp.minimum(s.min_used_lat, ext_used)
            )
        # previous call's egress was consumed by the host
        s = s._replace(
            egress_count=jnp.int32(0), egress_lost=jnp.int32(0),
            egress_min_hi=jnp.int32(NEVER32),
            egress_min_lo=jnp.int32(NEVER32),
        )
        s = _inject_merge(p, tb, s, inj)
        horizon_hi, horizon_lo = ext_thi[ext_slots - 1], ext_tlo[ext_slots - 1]

        def inner(pk, ptr):
            """One fused segment: the window loop up to and including
            the next window the host participates in, bounded by the
            current schedule slot."""
            e_hi = ext_thi[jnp.minimum(ptr, ext_slots - 1)]
            e_lo = ext_tlo[jnp.minimum(ptr, ext_slots - 1)]

            def cond(carry):
                st = unpack_state(carry)
                mh, ml = _queue_min(p, st)
                in_window = pair_lt(mh, ml, st.now_we_hi, st.now_we_lo)
                bh, bl = ext_bound(st, e_hi, e_lo)
                host_in_cur = pair_lt(bh, bl, st.now_we_hi, st.now_we_lo)
                nsh, nsl = pair_sel(pair_lt(mh, ml, bh, bl), mh, ml, bh, bl)
                fresh_ok = (~host_in_cur) & pair_lt(nsh, nsl, stop_hi, stop_lo)
                room = st.egress_count < room_floor
                return room & (in_window | fresh_ok)

            def body(carry):
                st = unpack_state(carry)
                mn_hi, mn_lo = _queue_min(p, st)
                bh, bl = ext_bound(st, e_hi, e_lo)
                mn_hi, mn_lo = pair_sel(
                    pair_lt(mn_hi, mn_lo, bh, bl), mn_hi, mn_lo, bh, bl
                )
                live = pair_lt(mn_hi, mn_lo, stop_hi, stop_lo)
                fresh = pair_ge(mn_hi, mn_lo, st.now_we_hi, st.now_we_lo) & live
                if p.netobs:
                    st = _flush_hist(p, st, fresh)
                st = _ledger_open_window(st, fresh)
                c_hi, c_lo = pair_sel(live, mn_hi, mn_lo, stop_hi, stop_lo)
                c_hi, c_lo = pair_add32(c_hi, c_lo, _effective_runahead(p, st))
                c_hi, c_lo = pair_sel(
                    pair_lt(c_hi, c_lo, stop_hi, stop_lo),
                    c_hi, c_lo, stop_hi, stop_lo,
                )
                st = st._replace(
                    now_we_hi=jnp.where(fresh, c_hi, st.now_we_hi),
                    now_we_lo=jnp.where(fresh, c_lo, st.now_we_lo),
                    rounds=st.rounds + fresh.astype(st.rounds.dtype),
                )
                return pack_state(iter_fn(st))

            pk2 = lax.while_loop(cond, body, pk)
            return pk2, e_hi, e_lo

        def seg_cond(carry):
            _pk, _ptr, _kd, _we, run = carry
            return run

        def seg_body(carry):
            pk, ptr, kd, we_arr, _run = carry
            pk, e_hi, e_lo = inner(pk, ptr)
            st = unpack_state(pk)
            mh, ml = _queue_min(p, st)
            in_window = pair_lt(mh, ml, st.now_we_hi, st.now_we_lo)
            room = st.egress_count < room_floor
            bh, bl = ext_bound(st, e_hi, e_lo)
            host_in_cur = pair_lt(bh, bl, st.now_we_hi, st.now_we_lo)
            # a consumable participation lies strictly below the horizon:
            # at or past it the host's schedule ran out — return instead
            below_h = pair_lt(bh, bl, horizon_hi, horizon_lo)
            consume = host_in_cur & room & (~in_window) & below_h
            we64 = t_join(st.now_we_hi, st.now_we_lo)
            we_arr = jnp.where(
                consume,
                we_arr.at[jnp.minimum(kd, k_cap - 1)].set(we64),
                we_arr,
            )
            kd2 = kd + consume.astype(jnp.int32)
            # advance the schedule pointer past times the consumed window
            # covered (its round will execute them host-side)
            done_t = pair_lt(ext_thi, ext_tlo, st.now_we_hi, st.now_we_lo)
            ptr2 = jnp.where(
                consume, jnp.sum(done_t, dtype=jnp.int32), ptr
            )
            # re-arm the free-run guard for the next segment
            ref_hi, ref_lo = egress_refold(st, st.now_we_hi, st.now_we_lo)
            st2 = st._replace(
                egress_min_hi=jnp.where(consume, ref_hi, st.egress_min_hi),
                egress_min_lo=jnp.where(consume, ref_lo, st.egress_min_lo),
            )
            run2 = consume & (kd2 < k_eff)
            return (pack_state(st2), ptr2, kd2, we_arr, run2)

        carry = (
            pack_state(s), jnp.int32(0), jnp.int32(0),
            jnp.zeros((k_cap,), dtype=jnp.int64), jnp.bool_(True),
        )
        pk, _ptr, kd, we_arr, _run = lax.while_loop(
            seg_cond, seg_body, carry
        )
        s = unpack_state(pk)
        lane_min = t_join(*_queue_min(p, s))
        scalars = jnp.concatenate([
            jnp.stack([
                lane_min,
                t_join(s.now_we_hi, s.now_we_lo),
                (s.min_used_lat if p.dynamic_runahead
                 else jnp.int32(NEVER32)).astype(jnp.int64),
                s.egress_count.astype(jnp.int64),
                s.egress_lost.astype(jnp.int64),
                kd.astype(jnp.int64),
            ]),
            we_arr,
            s.egress[:HYB_EGRESS_HEAD].reshape(-1),
        ])
        return s, scalars

    return fused_run


def make_hybrid_fused_fn(p: LaneParams, tb: LaneTables, k_cap: int,
                         ext_slots: int):
    """Jitted k-window fused hybrid device call: (state, turn_block) ->
    (state, scalars int64) — the HYB_* slots, HYB_K_DONE, the consumed
    window ends at HYB_WE_BASE + i and the egress head.  ``k_cap`` and
    ``ext_slots`` are static (array widths); ``k_eff`` is a word of the
    block, so varying the per-dispatch fusion depth never recompiles."""
    return jax.jit(_build_hybrid_fused_run(p, tb, k_cap, ext_slots))
