"""Vectorized lane-TCP: the stream tier on device, in pure int32 lanes.

The masked-vector twin of the scalar law in :mod:`shadow_tpu.net.ltcp`
(SURVEY §7 hard part (e): "TCP state machine vectorization").  One flow per
stream-client lane; flow state lives in two ``[N, F]`` int32 matrices —
``cl`` (client endpoints, indexed by client lane) and ``sv`` (server
endpoints, indexed by the client lane in the general case, by the SERVER
lane when the config pairs every server with exactly one client).

**Representation.** TPU has no native int64 (every i64 op lowers to
unfusable X64 custom calls whose per-launch overhead dominated the mixed
bench), so every column is int32: sequence state, congestion control, and
counters are plain int32 (engine-guarded magnitudes), and the six
time-valued fields (srtt, rttvar, rto, rtt_ts, rto_deadline, rto_evt) are
(hi, lo) int32 pairs in the same split encoding as the event keys
(``lanes.t_split``).  ``now`` enters as a pair; no int64 exists anywhere in
the law.  The arithmetic is exactly the scalar law's — pair add/sub/mul-by-
small-constant/div-by-power-of-two reproduce the integer results bit for
bit (the CPU oracle these lanes are diffed against).

**Wire payloads** pack ``flags(4) | seq(26)`` into one int32 queue word and
``ack`` into a second (engine guard: seq units < 2**26); pump/RTO local
events are marked by size -2/-3 and carry the flow id in the low payload
word.

**Indexing.**  The general (star) case gathers/scatters server rows at the
flow index — one row-gather + one row-scatter per endpoint matrix per slot
(rows vectorize where per-element access serializes).  When every stream
server serves exactly ONE client (``one_to_one``), server rows live at the
server's own lane and the gather/scatter disappear entirely: slot access
is a masked elementwise select.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..net import ltcp
from . import lanes_pairs as lp

# size-field markers for stream LOCAL events
SZ_PUMP = -2
SZ_RTO = -3

# payload packing: word0 = flags(4) << 26 | seq(26); word1 = ack
PAY_SEQ_BITS = 26
PAY_SEQ_MASK = (1 << PAY_SEQ_BITS) - 1

NEVER32 = lp.NEVER32

# RTO constants as static pair splits (python ints at trace time)
_RTO_INIT_P = (ltcp.RTO_INIT >> 31, ltcp.RTO_INIT & lp.MASK31)
_RTO_MIN_P = (ltcp.RTO_MIN >> 31, ltcp.RTO_MIN & lp.MASK31)
_RTO_MAX_P = (ltcp.RTO_MAX >> 31, ltcp.RTO_MAX & lp.MASK31)
_GRAN_P = (0, 1_000_000)  # RFC 6298 1 ms granularity floor


def pack_pay(flags, seq, ack):
    """(flags, seq, ack) -> (word0, word1) int32 pair."""
    i32 = jnp.int32
    w0 = (jnp.asarray(flags).astype(i32) << PAY_SEQ_BITS) | jnp.asarray(
        seq
    ).astype(i32)
    return w0, jnp.asarray(ack).astype(i32)


def unpack_pay(w0, w1):
    flags = w0 >> PAY_SEQ_BITS
    seq = w0 & PAY_SEQ_MASK
    return flags, seq, w1


# -- column layout of the per-endpoint [N, F] int32 matrix -------------------
(C_STATE, C_SND_UNA, C_SND_NXT, C_RCV_NXT, C_CWND, C_SSTHRESH, C_DUP_ACKS,
 C_IN_REC, C_RECOVER, C_MAX_SENT, C_RTT_SEQ,
 C_SRTT_HI, C_SRTT_LO, C_RTTVAR_HI, C_RTTVAR_LO, C_RTO_HI, C_RTO_LO,
 C_RTT_TS_HI, C_RTT_TS_LO, C_RTODL_HI, C_RTODL_LO, C_RTOEV_HI, C_RTOEV_LO,
 C_TX_SEGS, C_RETRANS, C_COMPLETED, C_RX_SEGS, C_RX_BYTES,
 C_WMAX, C_ORIGIN, C_EPOCH_HI, C_EPOCH_LO, C_KQ) = range(33)
N_COLS = 33


class StreamState(NamedTuple):
    """Two [N, F] int32 matrices: client endpoints (indexed by client lane)
    and server endpoints (indexed by client lane, or by server lane in
    one-to-one mode)."""

    cl: jnp.ndarray
    sv: jnp.ndarray


def _fresh_matrix(n: int) -> np.ndarray:
    m = np.zeros((n, N_COLS), dtype=np.int32)
    m[:, C_CWND] = ltcp.INIT_CWND_FP
    m[:, C_SSTHRESH] = ltcp.INIT_SSTHRESH_FP
    m[:, C_SRTT_HI] = -1
    m[:, C_RTO_HI] = _RTO_INIT_P[0]
    m[:, C_RTO_LO] = _RTO_INIT_P[1]
    m[:, C_RTT_SEQ] = -1
    for col in (C_RTODL_HI, C_RTODL_LO, C_RTOEV_HI, C_RTOEV_LO,
                C_EPOCH_HI, C_EPOCH_LO):
        m[:, col] = NEVER32
    return m


def init_stream_state(n: int) -> StreamState:
    """Fresh endpoint matrices, on the HOST (numpy): the engine places the
    whole initial state in one transfer (``TpuEngine.initial_state``).
    Transfer-shape tables are static and live in LaneTables, not here."""
    return StreamState(cl=_fresh_matrix(n), sv=_fresh_matrix(n))


class FlowCols(NamedTuple):
    """One endpoint's FlowState as [N] int32 columns (+ static shape)."""

    state: jnp.ndarray
    snd_una: jnp.ndarray
    snd_nxt: jnp.ndarray
    rcv_nxt: jnp.ndarray
    cwnd_fp: jnp.ndarray
    ssthresh_fp: jnp.ndarray
    dup_acks: jnp.ndarray
    in_rec: jnp.ndarray  # bool
    recover: jnp.ndarray
    max_sent: jnp.ndarray
    rtt_seq: jnp.ndarray
    srtt_hi: jnp.ndarray  # pair (hi < 0 = no sample yet)
    srtt_lo: jnp.ndarray
    rttvar_hi: jnp.ndarray
    rttvar_lo: jnp.ndarray
    rto_hi: jnp.ndarray
    rto_lo: jnp.ndarray
    rtt_ts_hi: jnp.ndarray
    rtt_ts_lo: jnp.ndarray
    rtodl_hi: jnp.ndarray  # NEVER32 = unarmed
    rtodl_lo: jnp.ndarray
    rtoev_hi: jnp.ndarray
    rtoev_lo: jnp.ndarray
    tx_segs: jnp.ndarray
    retransmits: jnp.ndarray
    completed: jnp.ndarray  # bool
    rx_segs: jnp.ndarray
    rx_bytes: jnp.ndarray
    # CUBIC state (inert under CC_RENO)
    w_max_fp: jnp.ndarray
    cub_origin_fp: jnp.ndarray
    cub_epoch_hi: jnp.ndarray  # pair (NEVER32 = no epoch yet)
    cub_epoch_lo: jnp.ndarray
    cub_k_q: jnp.ndarray
    role: jnp.ndarray  # SENDER / RECEIVER
    segs: jnp.ndarray  # transfer shape (client flows; 0 for server role)
    mss: jnp.ndarray
    last_bytes: jnp.ndarray
    cc: jnp.ndarray  # static per flow: ltcp.CC_RENO / CC_CUBIC


_MATRIX_FIELDS = (
    ("state", C_STATE), ("snd_una", C_SND_UNA), ("snd_nxt", C_SND_NXT),
    ("rcv_nxt", C_RCV_NXT), ("cwnd_fp", C_CWND), ("ssthresh_fp", C_SSTHRESH),
    ("dup_acks", C_DUP_ACKS), ("recover", C_RECOVER),
    ("max_sent", C_MAX_SENT), ("rtt_seq", C_RTT_SEQ),
    ("srtt_hi", C_SRTT_HI), ("srtt_lo", C_SRTT_LO),
    ("rttvar_hi", C_RTTVAR_HI), ("rttvar_lo", C_RTTVAR_LO),
    ("rto_hi", C_RTO_HI), ("rto_lo", C_RTO_LO),
    ("rtt_ts_hi", C_RTT_TS_HI), ("rtt_ts_lo", C_RTT_TS_LO),
    ("rtodl_hi", C_RTODL_HI), ("rtodl_lo", C_RTODL_LO),
    ("rtoev_hi", C_RTOEV_HI), ("rtoev_lo", C_RTOEV_LO),
    ("tx_segs", C_TX_SEGS), ("retransmits", C_RETRANS),
    ("rx_segs", C_RX_SEGS), ("rx_bytes", C_RX_BYTES),
    ("w_max_fp", C_WMAX), ("cub_origin_fp", C_ORIGIN),
    ("cub_epoch_hi", C_EPOCH_HI), ("cub_epoch_lo", C_EPOCH_LO),
    ("cub_k_q", C_KQ),
)
_BOOL_FIELDS = (("in_rec", C_IN_REC), ("completed", C_COMPLETED))


class StreamEmit(NamedTuple):
    """What one stream stimulus emits (all [N], masked by validity).
    The control/slot-0 send channel; data bursts ride the epilogue's
    separate channel (pump_epilogue_vec).  There is no pump-arm channel:
    with PUMP_BURST == RWND_SEGS the epilogue always exhausts the window,
    so the scalar law's ``arm_pump`` can never fire (asserted below)."""

    send_valid: jnp.ndarray
    send_flags: jnp.ndarray
    send_seq: jnp.ndarray
    send_ack: jnp.ndarray
    send_size: jnp.ndarray  # wire size
    send_retx: jnp.ndarray  # the send is a retransmission (flowtrace)
    rto_valid: jnp.ndarray  # arm an RTO LOCAL
    rto_thi: jnp.ndarray  # pair: RTO event time
    rto_tlo: jnp.ndarray
    completed_now: jnp.ndarray  # flow reached DONE on this stimulus


# the no-pump-events invariant the wide co-pop rule in lanes.py rests on
assert ltcp.PUMP_BURST >= ltcp.RWND_SEGS


# --------------------------------------------------------------------------
# law helpers (pair twins of ltcp.py's helpers)
# --------------------------------------------------------------------------


def _seg_wire_size(f: FlowCols, unit):
    is_data = (unit >= 1) & (unit <= f.segs)
    payload = jnp.where(unit == f.segs, f.last_bytes, f.mss)
    return jnp.where(is_data, ltcp.HDR_BYTES + payload, ltcp.HDR_BYTES).astype(
        jnp.int32
    )


def _seg_flags(f: FlowCols, unit):
    syn = jnp.where(
        f.role == ltcp.SENDER, ltcp.F_SYN, ltcp.F_SYN | ltcp.F_ACK
    )
    data = ltcp.F_DATA | ltcp.F_ACK
    fin = ltcp.F_FIN | ltcp.F_ACK
    is_data = (f.role == ltcp.SENDER) & (unit >= 1) & (unit <= f.segs)
    return jnp.where(
        unit == 0, syn, jnp.where(is_data, data, fin)
    ).astype(jnp.int32)


def _flight(f: FlowCols):
    return f.snd_nxt - f.snd_una


def _icbrt32_vec(x):
    """Vector twin of ltcp.icbrt32 — the identical 11-iteration bitwise
    floor-cbrt, unrolled.  ``b << s`` may wrap int32 in lanes where the
    take branch is false; those lanes discard the value (when taken,
    b << s <= x < 2**31, so no wrap)."""
    y = jnp.zeros_like(x)
    for s in range(30, -1, -3):
        y = y + y
        b = 3 * y * (y + 1) + 1
        take = (x >> s) >= b
        x = jnp.where(take, x - (b << s), x)
        y = jnp.where(take, y + 1, y)
    return y


def _cc_on_loss(f: FlowCols, m) -> FlowCols:
    """ltcp.cc_on_loss under mask ``m``: per-algorithm ssthresh; CUBIC
    records W_max (fast convergence) and resets its epoch."""
    cub = m & (f.cc == ltcp.CC_CUBIC)
    ren = m & ~cub
    # flight <= MAX window segs (law invariant): the product fits int32
    fl_fp = jnp.minimum(_flight(f), 1 << 15) * ltcp.FP
    new_wmax = jnp.where(
        f.cwnd_fp < f.w_max_fp,
        (f.cwnd_fp * ltcp.CUBIC_FC_MUL) >> 10,
        f.cwnd_fp,
    )
    return f._replace(
        w_max_fp=jnp.where(cub, new_wmax, f.w_max_fp),
        cub_epoch_hi=jnp.where(cub, NEVER32, f.cub_epoch_hi),
        cub_epoch_lo=jnp.where(cub, NEVER32, f.cub_epoch_lo),
        ssthresh_fp=jnp.where(
            cub,
            jnp.maximum(
                (f.cwnd_fp * ltcp.CUBIC_BETA_MUL) >> 10, ltcp.MIN_SSTHRESH_FP
            ),
            jnp.where(
                ren,
                jnp.maximum(fl_fp // 2, ltcp.MIN_SSTHRESH_FP),
                f.ssthresh_fp,
            ),
        ),
    )


def _cc_grow_ca(f: FlowCols, nh, nl, m) -> FlowCols:
    """ltcp.cc_grow_ca under mask ``m`` (congestion-avoidance growth for
    one new ACK); no MAX_CWND clamp here — the caller clamps, exactly
    like the scalar flow."""
    cub = m & (f.cc == ltcp.CC_CUBIC)
    # epoch start on the first CA ACK after a loss (or ever)
    start = cub & (f.cub_epoch_hi == NEVER32)
    below = f.cwnd_fp < f.w_max_fp
    k_new = jnp.where(
        below,
        4 * _icbrt32_vec((f.w_max_fp - f.cwnd_fp) * ltcp.CUBIC_K_MUL),
        0,
    )
    f = f._replace(
        cub_epoch_hi=jnp.where(start, nh, f.cub_epoch_hi),
        cub_epoch_lo=jnp.where(start, nl, f.cub_epoch_lo),
        cub_origin_fp=jnp.where(
            start, jnp.where(below, f.w_max_fp, f.cwnd_fp), f.cub_origin_fp
        ),
        cub_k_q=jnp.where(start, k_new, f.cub_k_q),
    )
    # d_q = min((now - epoch) >> 20, D_MAX) on pairs: value = hi*2**31+lo,
    # so >> 20 is hi*2**11 + (lo >> 20); hi is pre-clamped so the shift
    # cannot wrap (any clamped case is >= D_MAX anyway)
    dh, dl = lp.pair_sub_pair(nh, nl, f.cub_epoch_hi, f.cub_epoch_lo)
    d_q = jnp.minimum(
        jnp.minimum(dh, 1 << 19) * (1 << 11) + (dl >> 20), ltcp.CUBIC_D_MAX
    )
    offs = d_q - f.cub_k_q
    neg = offs < 0
    offs = jnp.minimum(jnp.abs(offs), ltcp.CUBIC_D_MAX)
    delta_fp = (
        ((((offs * offs) >> 10) * offs) >> 10) * ltcp.CUBIC_C_MUL
    ) >> 10
    target_fp = jnp.where(
        neg, f.cub_origin_fp - delta_fp, f.cub_origin_fp + delta_fp
    )
    cwnd_safe = jnp.maximum(f.cwnd_fp, 1)
    cub_grow = jnp.where(
        target_fp > f.cwnd_fp,
        jnp.maximum(1, (target_fp - f.cwnd_fp) * ltcp.FP // cwnd_safe),
        jnp.maximum(1, (ltcp.FP * ltcp.FP) // (100 * cwnd_safe)),
    )
    ren_grow = jnp.maximum(1, (ltcp.FP * ltcp.FP) // cwnd_safe)
    return f._replace(
        cwnd_fp=jnp.where(
            m, f.cwnd_fp + jnp.where(cub, cub_grow, ren_grow), f.cwnd_fp
        )
    )


# NOTE: the scalar law's per-unit send gate (ltcp._can_send_new) has no
# vector twin here — pump_epilogue_vec's closed form derives the whole
# burst length from the gate's components at once (can0/lim_w/lim_fin);
# change the gate THERE when the scalar law changes.


def _rtt_sample(f: FlowCols, nh, nl, m) -> FlowCols:
    """RFC 6298 update where mask ``m`` — identical integer results to the
    scalar law, on pairs."""
    # r = max(now - rtt_ts, 0)
    nonneg = lp.pair_ge(nh, nl, f.rtt_ts_hi, f.rtt_ts_lo)
    rh, rl = lp.pair_sub_pair(nh, nl, f.rtt_ts_hi, f.rtt_ts_lo)
    rh = jnp.where(nonneg, rh, 0)
    rl = jnp.where(nonneg, rl, 0)
    first = f.srtt_hi < 0
    # srtt' = first ? r : (7*srtt + r) // 8
    s7h, s7l = lp.pair_mul_small(f.srtt_hi, f.srtt_lo, 7)
    sh, sl = lp.pair_div_pow2(*lp.pair_add_pair(s7h, s7l, rh, rl), 3)
    srtt1h = jnp.where(first, rh, sh)
    srtt1l = jnp.where(first, rl, sl)
    # delta = |srtt - r| (PRE-update srtt, as in the scalar law)
    dh, dl = lp.pair_abs_diff(f.srtt_hi, f.srtt_lo, rh, rl)
    # rttvar' = first ? r // 2 : (3*rttvar + delta) // 4
    v3h, v3l = lp.pair_mul_small(f.rttvar_hi, f.rttvar_lo, 3)
    vh, vl = lp.pair_div_pow2(*lp.pair_add_pair(v3h, v3l, dh, dl), 2)
    r2h, r2l = lp.pair_div_pow2(rh, rl, 1)
    var1h = jnp.where(first, r2h, vh)
    var1l = jnp.where(first, r2l, vl)
    # rto' = clip(srtt' + max(4*rttvar', 1 ms), RTO_MIN, RTO_MAX)
    v4h, v4l = lp.pair_mul_small(var1h, var1l, 4)
    v4h, v4l = lp.pair_max(v4h, v4l, _GRAN_P[0], _GRAN_P[1])
    toh, tol = lp.pair_add_pair(srtt1h, srtt1l, v4h, v4l)
    below = lp.pair_lt(toh, tol, _RTO_MIN_P[0], _RTO_MIN_P[1])
    toh = jnp.where(below, _RTO_MIN_P[0], toh)
    tol = jnp.where(below, _RTO_MIN_P[1], tol)
    above = lp.pair_lt(_RTO_MAX_P[0], _RTO_MAX_P[1], toh, tol)
    toh = jnp.where(above, _RTO_MAX_P[0], toh)
    tol = jnp.where(above, _RTO_MAX_P[1], tol)
    return f._replace(
        srtt_hi=jnp.where(m, srtt1h, f.srtt_hi),
        srtt_lo=jnp.where(m, srtt1l, f.srtt_lo),
        rttvar_hi=jnp.where(m, var1h, f.rttvar_hi),
        rttvar_lo=jnp.where(m, var1l, f.rttvar_lo),
        rto_hi=jnp.where(m, toh, f.rto_hi),
        rto_lo=jnp.where(m, tol, f.rto_lo),
    )


def _restart_rto(f: FlowCols, nh, nl, m, em_rto_valid, em_rto_thi,
                 em_rto_tlo):
    """(Re)start the retransmission timer where ``m``; returns (f, valid,
    thi, tlo) with the dedup law of ltcp._restart_rto."""
    dlh, dll = lp.pair_add_pair(nh, nl, f.rto_hi, f.rto_lo)
    arm = m & (
        (f.rtoev_hi == NEVER32)
        | lp.pair_lt(dlh, dll, f.rtoev_hi, f.rtoev_lo)
    )
    f = f._replace(
        rtodl_hi=jnp.where(m, dlh, f.rtodl_hi),
        rtodl_lo=jnp.where(m, dll, f.rtodl_lo),
        rtoev_hi=jnp.where(arm, dlh, f.rtoev_hi),
        rtoev_lo=jnp.where(arm, dll, f.rtoev_lo),
    )
    return (
        f,
        em_rto_valid | arm,
        jnp.where(arm, dlh, em_rto_thi),
        jnp.where(arm, dll, em_rto_tlo),
    )


def _emit_unit(f: FlowCols, unit, m, retransmit, em):
    """Send the segment for ``unit`` where ``m`` (≤1 send per stimulus, so
    the channel is a plain overwrite under the mask)."""
    send_flags = _seg_flags(f, unit)
    send_size = _seg_wire_size(f, unit)
    f = f._replace(
        tx_segs=f.tx_segs + m,
        retransmits=f.retransmits + (m & retransmit),
        rtt_seq=jnp.where(
            m & retransmit & (f.rtt_seq >= 0) & (unit <= f.rtt_seq),
            -1,
            jnp.where(m & ~retransmit & (f.rtt_seq < 0), unit, f.rtt_seq),
        ),
        max_sent=jnp.where(m & (unit + 1 > f.max_sent), unit + 1, f.max_sent),
    )
    em = em._replace(
        send_valid=em.send_valid | m,
        send_flags=jnp.where(m, send_flags, em.send_flags),
        send_seq=jnp.where(m, unit, em.send_seq),
        send_ack=jnp.where(m, f.rcv_nxt, em.send_ack),
        send_size=jnp.where(m, send_size, em.send_size),
        send_retx=jnp.where(m, retransmit, em.send_retx),
    )
    return f, em


def _empty_emit(n: int) -> StreamEmit:
    i32 = jnp.int32
    zb = jnp.zeros(n, dtype=bool)
    z32 = jnp.zeros(n, dtype=i32)
    return StreamEmit(
        send_valid=zb,
        send_flags=z32,
        send_seq=z32,
        send_ack=z32,
        send_size=z32,
        send_retx=zb,
        rto_valid=zb,
        rto_thi=z32,
        rto_tlo=z32,
        completed_now=zb,
    )


def _pull_back(f: FlowCols, nh, nl, m, em):
    """Go-back-N loss response where ``m`` (the epilogue pump re-streams
    the rest)."""
    f = f._replace(
        snd_nxt=jnp.where(m, f.snd_una + 1, f.snd_nxt),
        state=jnp.where(
            m & (f.role == ltcp.SENDER) & (f.state == ltcp.FIN_WAIT),
            ltcp.ESTAB,
            f.state,
        ),
    )
    f, em = _emit_unit(f, f.snd_una, m, jnp.asarray(True), em)
    f, rv, rth, rtl = _restart_rto(f, nh, nl, m, em.rto_valid, em.rto_thi,
                                   em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    return f, em


def pump_epilogue_vec(f: FlowCols, nh, nl, m, em):
    """The transmission-opportunity epilogue (scalar ``_pump_units``):
    transmit up to PUMP_BURST window-permitted units.  Runs ONCE per
    stimulus, after the handler's primary effects.  Returns
    ``(f, em, burst)`` where ``burst`` is a ``(valid, flags, seq, ack,
    size, retx)`` tuple of stacked [PUMP_BURST, N] arrays whose validity
    is a PREFIX along axis 0 (emissions stop when the window exhausts) —
    the engine's send-sequence ranking relies on that.  ``retx`` marks
    the retransmit prefix (units below the entry ``max_sent``) for the
    flowtrace plane; when flowtrace is off nothing consumes it and XLA
    folds the comparison away.

    CLOSED FORM — not a loop.  The scalar law's per-unit loop is exactly
    derivable because nothing the gate depends on changes mid-burst
    (cwnd, snd_una, role are fixed; state flips to FIN_WAIT only at the
    final sendable unit; snd_nxt is affine in the unit index), so:

    - the burst length is ``B = clip(min(window_room, fin_room), 0,
      PUMP_BURST)`` with units ``u0 .. u0+B-1``;
    - retransmit units are the prefix below the entry ``max_sent``
      (``nR = clip(max_sent - u0, 0, B)``), so the retransmit counter
      adds ``nR`` and the fresh-sample bookkeeping reduces to: a clear
      happens iff a retransmit unit exists at or below ``rtt_seq``
      (only the FIRST unit can satisfy ``unit <= rtt_seq``: units grow),
      and the first FRESH unit samples iff ``rtt_seq`` was negative or
      just cleared;
    - the per-step ``_restart_rto`` is idempotent across the burst (the
      deadline ``now + rto`` is constant and the dedup law arms at most
      once), so one call under ``m & (B > 0)`` is exact.

    Per-unit wire fields (flags/size/ack) depend only on the unit index
    and static shape columns, so they broadcast to [PUMP_BURST, N] with
    no sequential dependency at all — this removed ~PUMP_BURST
    dependent fusion blocks per slot from the mixed-mesh iteration."""
    i32 = jnp.int32
    b_max = ltcp.PUMP_BURST
    u0 = f.snd_nxt
    cwnd_segs = f.cwnd_fp // ltcp.FP
    can0 = m & (f.role == ltcp.SENDER) & (f.state == ltcp.ESTAB)
    lim_w = jnp.minimum(cwnd_segs, ltcp.RWND_SEGS) - (u0 - f.snd_una)
    lim_fin = f.segs + 2 - u0
    b_cnt = jnp.where(
        can0, jnp.clip(jnp.minimum(lim_w, lim_fin), 0, b_max), 0
    ).astype(i32)
    sent_any = b_cnt > 0

    ks = jnp.arange(b_max, dtype=i32)[:, None]  # [B, 1]
    units = u0[None, :] + ks  # [B, N]
    valid = ks < b_cnt[None, :]  # prefix along axis 0
    flags = _seg_flags(f, units)  # broadcasts: shape cols are [N]
    sizes = _seg_wire_size(f, units)
    acks = jnp.broadcast_to(f.rcv_nxt[None, :], units.shape)

    n_re = jnp.clip(f.max_sent - u0, 0, b_cnt)  # retransmit prefix length
    cleared = (n_re > 0) & (f.rtt_seq >= 0) & (u0 <= f.rtt_seq)
    fresh_exists = b_cnt > n_re
    take_ts = fresh_exists & ((f.rtt_seq < 0) | cleared)
    new_rtt_seq = jnp.where(
        take_ts, u0 + n_re, jnp.where(cleared, -1, f.rtt_seq)
    )
    f = f._replace(
        rtt_ts_hi=jnp.where(take_ts, nh, f.rtt_ts_hi),
        rtt_ts_lo=jnp.where(take_ts, nl, f.rtt_ts_lo),
        rtt_seq=new_rtt_seq,
        tx_segs=f.tx_segs + b_cnt,
        retransmits=f.retransmits + n_re,
        max_sent=jnp.where(
            sent_any, jnp.maximum(f.max_sent, u0 + b_cnt), f.max_sent
        ),
        snd_nxt=u0 + b_cnt,
        state=jnp.where(
            sent_any & (u0 + b_cnt == f.segs + 2), ltcp.FIN_WAIT, f.state
        ),
    )
    f, rv, rth, rtl = _restart_rto(f, nh, nl, m & sent_any, em.rto_valid,
                                   em.rto_thi, em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    retx = ks < n_re[None, :]  # retransmit prefix (flowtrace channel)
    return f, em, (valid, flags, units, acks, sizes, retx)


# --------------------------------------------------------------------------
# stimulus handlers (pair twins of ltcp.open_flow / on_pump / on_rto_event
# / on_segment); each applies under an activity mask ``m``
# --------------------------------------------------------------------------


def open_flow_vec(f: FlowCols, nh, nl, m) -> tuple[FlowCols, StreamEmit]:
    em = _empty_emit(f.state.shape[0])
    f = f._replace(
        state=jnp.where(m, ltcp.SYN_SENT, f.state),
        snd_nxt=jnp.where(m, 1, f.snd_nxt),
    )
    f, em = _emit_unit(f, jnp.zeros_like(f.snd_nxt), m, jnp.asarray(False), em)
    f = f._replace(
        rtt_ts_hi=jnp.where(m, nh, f.rtt_ts_hi),
        rtt_ts_lo=jnp.where(m, nl, f.rtt_ts_lo),
    )
    f, rv, rth, rtl = _restart_rto(f, nh, nl, m, em.rto_valid, em.rto_thi,
                                   em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    return f, em


def on_rto_vec(f: FlowCols, nh, nl, m) -> tuple[FlowCols, StreamEmit]:
    em = _empty_emit(f.state.shape[0])
    # ownership law: only the event at time rto_evt speaks for the timer
    m = m & (nh == f.rtoev_hi) & (nl == f.rtoev_lo)
    f = f._replace(
        rtoev_hi=jnp.where(m, NEVER32, f.rtoev_hi),
        rtoev_lo=jnp.where(m, NEVER32, f.rtoev_lo),
    )
    lapse = (f.rtodl_hi == NEVER32) | (_flight(f) <= 0)
    m = m & ~lapse
    # deadline moved later: re-arm there
    rearm = m & lp.pair_lt(nh, nl, f.rtodl_hi, f.rtodl_lo)
    f = f._replace(
        rtoev_hi=jnp.where(rearm, f.rtodl_hi, f.rtoev_hi),
        rtoev_lo=jnp.where(rearm, f.rtodl_lo, f.rtoev_lo),
    )
    em = em._replace(
        rto_valid=em.rto_valid | rearm,
        rto_thi=jnp.where(rearm, f.rtodl_hi, em.rto_thi),
        rto_tlo=jnp.where(rearm, f.rtodl_lo, em.rto_tlo),
    )
    fire = m & ~rearm
    r2h, r2l = lp.pair_mul_small(f.rto_hi, f.rto_lo, 2)
    over = lp.pair_lt(_RTO_MAX_P[0], _RTO_MAX_P[1], r2h, r2l)
    r2h = jnp.where(over, _RTO_MAX_P[0], r2h)
    r2l = jnp.where(over, _RTO_MAX_P[1], r2l)
    f = _cc_on_loss(f, fire)
    f = f._replace(
        cwnd_fp=jnp.where(fire, ltcp.FP, f.cwnd_fp),
        dup_acks=jnp.where(fire, 0, f.dup_acks),
        in_rec=jnp.where(fire, False, f.in_rec),
        rto_hi=jnp.where(fire, r2h, f.rto_hi),
        rto_lo=jnp.where(fire, r2l, f.rto_lo),
    )
    f, em = _pull_back(f, nh, nl, fire, em)
    return f, em


def on_segment_vec(
    f: FlowCols, nh, nl, m, flags, seq, ack, size
) -> tuple[FlowCols, StreamEmit]:
    """Vector twin of ltcp.on_segment.  The scalar function is a sequence
    of early returns; here each return path is a disjoint mask and state
    updates compose under them in the same order."""
    n = f.state.shape[0]
    em = _empty_emit(n)
    i32 = jnp.int32

    is_syn = (flags & ltcp.F_SYN) != 0
    is_ack = (flags & ltcp.F_ACK) != 0
    is_fin = (flags & ltcp.F_FIN) != 0
    is_data = (flags & ltcp.F_DATA) != 0

    # ---- DONE: dup FIN from peer that missed our final ACK ---------------
    done0 = m & (f.state == ltcp.DONE)
    reack = done0 & (f.role == ltcp.SENDER) & is_fin
    em = em._replace(
        send_valid=em.send_valid | reack,
        send_flags=jnp.where(reack, ltcp.F_ACK, em.send_flags),
        send_seq=jnp.where(reack, f.snd_nxt, em.send_seq),
        send_ack=jnp.where(reack, f.rcv_nxt, em.send_ack),
        send_size=jnp.where(reack, ltcp.HDR_BYTES, em.send_size).astype(i32),
    )
    m = m & ~done0

    # ---- passive open ----------------------------------------------------
    po = m & (f.role == ltcp.RECEIVER) & (f.state == ltcp.CLOSED)
    po_ok = po & is_syn & ~is_ack
    f = f._replace(
        state=jnp.where(po_ok, ltcp.SYN_RCVD, f.state),
        rcv_nxt=jnp.where(po_ok, 1, f.rcv_nxt),
        snd_nxt=jnp.where(po_ok, 1, f.snd_nxt),
    )
    f, em = _emit_unit(f, jnp.zeros(n, dtype=i32), po_ok, jnp.asarray(False),
                       em)
    f = f._replace(
        rtt_ts_hi=jnp.where(po_ok, nh, f.rtt_ts_hi),
        rtt_ts_lo=jnp.where(po_ok, nl, f.rtt_ts_lo),
    )
    f, rv, rth, rtl = _restart_rto(f, nh, nl, po_ok, em.rto_valid, em.rto_thi,
                                   em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    m = m & ~po  # both the handled SYN and the ignored non-SYN return

    # retransmitted SYN into SYN_RCVD: resend the SYN-ACK
    rsyn = (
        m & (f.role == ltcp.RECEIVER) & (f.state == ltcp.SYN_RCVD)
        & is_syn & ~is_ack
    )
    f, em = _emit_unit(f, jnp.zeros(n, dtype=i32), rsyn, jnp.asarray(True), em)
    f, rv, rth, rtl = _restart_rto(f, nh, nl, rsyn, em.rto_valid, em.rto_thi,
                                   em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    m = m & ~rsyn

    # ---- ACK processing ---------------------------------------------------
    new_ack = m & is_ack & (ack > f.snd_una)
    # acked <= the max historical flight (law invariant ~ RWND); the clamp
    # keeps acked*FP inside int32 with identical results (cwnd saturates
    # at MAX_CWND_FP far below the clamp)
    acked = jnp.minimum(ack - f.snd_una, 1 << 15)
    pre_snd_una = f.snd_una  # the dup test is an elif on the PRE-ack value
    pre_in_rec = f.in_rec  # branch on the PRE-ack recovery flag
    was_syn_sent = new_ack & (f.state == ltcp.SYN_SENT)
    was_syn_rcvd = new_ack & (f.state == ltcp.SYN_RCVD)
    f = f._replace(snd_una=jnp.where(new_ack, ack, f.snd_una))
    clamp = new_ack & (f.snd_nxt < f.snd_una)
    f = f._replace(snd_nxt=jnp.where(clamp, f.snd_una, f.snd_nxt))
    f = f._replace(
        state=jnp.where(was_syn_sent | was_syn_rcvd, ltcp.ESTAB, f.state),
        # the SYN-ACK consumed the peer's unit 0
        rcv_nxt=jnp.where(was_syn_sent, 1, f.rcv_nxt),
    )

    # full-ack recovery exit / slow start / congestion avoidance
    full_ack = new_ack & pre_in_rec & (ack >= f.recover)
    f = f._replace(
        cwnd_fp=jnp.where(full_ack, f.ssthresh_fp, f.cwnd_fp),
        in_rec=jnp.where(full_ack, False, f.in_rec),
        dup_acks=jnp.where(full_ack, 0, f.dup_acks),
    )
    growth = new_ack & ~pre_in_rec
    ss = growth & (f.cwnd_fp < f.ssthresh_fp)
    ca = growth & ~ss
    f = f._replace(
        dup_acks=jnp.where(growth, 0, f.dup_acks),
        cwnd_fp=jnp.where(ss, f.cwnd_fp + acked * ltcp.FP, f.cwnd_fp),
    )
    f = _cc_grow_ca(f, nh, nl, ca)
    f = f._replace(
        cwnd_fp=jnp.where(
            growth, jnp.minimum(f.cwnd_fp, ltcp.MAX_CWND_FP), f.cwnd_fp
        )
    )
    rtt_m = new_ack & (f.rtt_seq >= 0) & (ack > f.rtt_seq)
    f = _rtt_sample(f, nh, nl, rtt_m)
    f = f._replace(rtt_seq=jnp.where(rtt_m, -1, f.rtt_seq))
    has_flight = _flight(f) > 0
    f, rv, rth, rtl = _restart_rto(f, nh, nl, new_ack & has_flight,
                                   em.rto_valid, em.rto_thi, em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    no_flight = new_ack & ~has_flight
    f = f._replace(
        rtodl_hi=jnp.where(no_flight, NEVER32, f.rtodl_hi),
        rtodl_lo=jnp.where(no_flight, NEVER32, f.rtodl_lo),
    )

    # pure duplicate ACK
    dup = (
        m
        & is_ack
        & (ack == pre_snd_una)
        & ~new_ack
        & (_flight(f) > 0)
        & ~(is_data | is_syn | is_fin)
    )
    infl = dup & f.in_rec
    f = f._replace(cwnd_fp=jnp.where(infl, f.cwnd_fp + ltcp.FP, f.cwnd_fp))
    count = dup & ~f.in_rec
    f = f._replace(dup_acks=jnp.where(count, f.dup_acks + 1, f.dup_acks))
    fr = count & (f.dup_acks == ltcp.DUP_THRESH)
    f = f._replace(
        in_rec=jnp.where(fr, True, f.in_rec),
        recover=jnp.where(fr, f.snd_nxt, f.recover),
    )
    f = _cc_on_loss(f, fr)
    f = f._replace(
        cwnd_fp=jnp.where(
            fr, f.ssthresh_fp + ltcp.DUP_THRESH * ltcp.FP, f.cwnd_fp
        )
    )
    f, em = _pull_back(f, nh, nl, fr, em)

    # ---- sender-side teardown / window-opened pump ------------------------
    snd = m & (f.role == ltcp.SENDER)
    fin_done = snd & is_fin & (f.snd_una == f.segs + 2)
    f = f._replace(rcv_nxt=jnp.where(fin_done, 2, f.rcv_nxt))
    em = em._replace(
        send_valid=em.send_valid | fin_done,
        send_flags=jnp.where(fin_done, ltcp.F_ACK, em.send_flags),
        send_seq=jnp.where(fin_done, f.snd_nxt, em.send_seq),
        send_ack=jnp.where(fin_done, f.rcv_nxt, em.send_ack),
        send_size=jnp.where(fin_done, ltcp.HDR_BYTES, em.send_size).astype(i32),
        completed_now=em.completed_now | fin_done,
    )
    f = f._replace(
        state=jnp.where(fin_done, ltcp.DONE, f.state),
        rtodl_hi=jnp.where(fin_done, NEVER32, f.rtodl_hi),
        rtodl_lo=jnp.where(fin_done, NEVER32, f.rtodl_lo),
    )
    # a window opened by this ACK is streamed by the epilogue pump
    # (pump_epilogue_vec, run once per stimulus by the slot driver)
    # sender path returns here in the scalar law
    m = m & ~snd

    # ---- receiver-side data path ------------------------------------------
    stray = (
        m
        & ((f.state == ltcp.SYN_RCVD) | (f.state == ltcp.ESTAB))
        & is_syn
        & is_ack
    )
    m = m & ~stray
    est = m & ((f.state == ltcp.ESTAB) | (f.state == ltcp.SYN_RCVD))
    data_seg = est & is_data
    in_order = data_seg & (seq == f.rcv_nxt)
    f = f._replace(
        rcv_nxt=jnp.where(in_order, f.rcv_nxt + 1, f.rcv_nxt),
        rx_segs=f.rx_segs + in_order,
        rx_bytes=f.rx_bytes + jnp.where(in_order, size - ltcp.HDR_BYTES, 0),
    )
    # ACK everything (advance or duplicate)
    em = em._replace(
        send_valid=em.send_valid | data_seg,
        send_flags=jnp.where(data_seg, ltcp.F_ACK, em.send_flags),
        send_seq=jnp.where(data_seg, f.snd_nxt, em.send_seq),
        send_ack=jnp.where(data_seg, f.rcv_nxt, em.send_ack),
        send_size=jnp.where(data_seg, ltcp.HDR_BYTES, em.send_size).astype(i32),
    )
    fin_seg = est & ~is_data & is_fin
    fin_in_order = fin_seg & (seq == f.rcv_nxt)
    unit = f.snd_nxt
    fresh_ts = fin_in_order & (f.rtt_seq < 0)
    f = f._replace(
        rcv_nxt=jnp.where(fin_in_order, f.rcv_nxt + 1, f.rcv_nxt),
        snd_nxt=jnp.where(fin_in_order, f.snd_nxt + 1, f.snd_nxt),
        rtt_ts_hi=jnp.where(fresh_ts, nh, f.rtt_ts_hi),
        rtt_ts_lo=jnp.where(fresh_ts, nl, f.rtt_ts_lo),
    )
    f, em = _emit_unit(f, unit, fin_in_order, jnp.asarray(False), em)
    f = f._replace(state=jnp.where(fin_in_order, ltcp.LAST_ACK, f.state))
    f, rv, rth, rtl = _restart_rto(f, nh, nl, fin_in_order, em.rto_valid,
                                   em.rto_thi, em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)
    fin_ooo = fin_seg & ~fin_in_order
    em = em._replace(
        send_valid=em.send_valid | fin_ooo,
        send_flags=jnp.where(fin_ooo, ltcp.F_ACK, em.send_flags),
        send_seq=jnp.where(fin_ooo, f.snd_nxt, em.send_seq),
        send_ack=jnp.where(fin_ooo, f.rcv_nxt, em.send_ack),
        send_size=jnp.where(fin_ooo, ltcp.HDR_BYTES, em.send_size).astype(i32),
    )

    # LAST_ACK (elif in the scalar law: a flow the est branch just moved
    # to LAST_ACK is NOT re-examined this stimulus)
    la = m & ~est & (f.state == ltcp.LAST_ACK)
    la_done = la & (f.snd_una >= 2)
    f = f._replace(
        state=jnp.where(la_done, ltcp.DONE, f.state),
        rtodl_hi=jnp.where(la_done, NEVER32, f.rtodl_hi),
        rtodl_lo=jnp.where(la_done, NEVER32, f.rtodl_lo),
    )
    em = em._replace(completed_now=em.completed_now | la_done)
    la_stale = la & ~la_done & (is_data | is_fin) & (seq < f.rcv_nxt)
    f, em = _emit_unit(f, f.snd_una, la_stale, jnp.asarray(True), em)
    f, rv, rth, rtl = _restart_rto(f, nh, nl, la_stale, em.rto_valid,
                                   em.rto_thi, em.rto_tlo)
    em = em._replace(rto_valid=rv, rto_thi=rth, rto_tlo=rtl)

    return f, em


def _merge_cols(a: FlowCols, b: FlowCols, m) -> FlowCols:
    return FlowCols(*[
        jnp.where(m, fb, fa) if fa is not fb else fa
        for fa, fb in zip(a, b)
    ])


def _merge_emit(a: StreamEmit, b: StreamEmit, m) -> StreamEmit:
    return StreamEmit(*[
        jnp.where(m, fb, fa) if fa is not fb else fa for fa, fb in zip(a, b)
    ])


def endpoint_cols(st: StreamState, flow_segs, flow_mss, flow_last, flow_cc):
    """The COMPACTED [2S] FlowCols view of the flow matrices: rows
    0..S-1 are the S client endpoints, rows S..2S-1 the matching server
    endpoints (flow slot order).  No per-lane gather/scatter exists any
    more — the endpoint axis IS the resident layout, so building the
    view is a concatenate plus column slices, and writing back is a
    split.  ``flow_*`` are the [2S] static transfer-shape tables (zeros
    on the server half: its units 0/1 are control segments, like the
    scalar receiver)."""
    s_flows = st.cl.shape[0]
    src = jnp.concatenate([st.cl, st.sv], axis=0)  # [2S, F]
    vals = {name: src[:, col] for name, col in _MATRIX_FIELDS}
    for name, col in _BOOL_FIELDS:
        vals[name] = src[:, col] != 0
    role = jnp.concatenate([
        jnp.full(s_flows, ltcp.SENDER, dtype=jnp.int32),
        jnp.full(s_flows, ltcp.RECEIVER, dtype=jnp.int32),
    ])
    vals["role"] = role
    vals["segs"] = flow_segs
    vals["mss"] = flow_mss
    vals["last_bytes"] = flow_last
    vals["cc"] = flow_cc
    return FlowCols(**vals)


def _to_rows(f: FlowCols) -> jnp.ndarray:
    """FlowCols -> [2S, F] matrix rows (column order of the layout)."""
    cols = [None] * N_COLS
    for name, col in _MATRIX_FIELDS:
        cols[col] = getattr(f, name)
    for name, col in _BOOL_FIELDS:
        cols[col] = getattr(f, name).astype(jnp.int32)
    return jnp.stack(cols, axis=1)


def endpoint_split(f: FlowCols) -> StreamState:
    """Inverse of endpoint_cols: [2S] FlowCols -> (cl, sv) matrices."""
    rows = _to_rows(f)
    s_flows = rows.shape[0] // 2
    return StreamState(cl=rows[:s_flows], sv=rows[s_flows:])


# --------------------------------------------------------------------------
# the TIERED stream backend (one-to-one configs): stream endpoints own a
# dedicated [2S, C2] event-queue block plus COMPACT per-endpoint network
# state, so the [N]-wide lane machinery carries no stream work at all.
# Sound only in one-to-one mode: each endpoint lane hosts exactly one flow,
# so its dn/up buckets, CoDel state, and per-host counters are in
# bijection with endpoint rows.
# --------------------------------------------------------------------------

# row indices of the packed [TV_COUNT, 2S] int32 tier vector matrix.
# The trailing TV_NB_* rows are the netobs telemetry block (tx/rx bytes
# and token-bucket throttle events per endpoint, docs/observability.md):
# always allocated (the packed matrix keeps the while carry flat) but
# written only when LaneParams.netobs is on — off, they stay the zeros
# they were initialized to and XLA carries them untouched.
(TV_DN_TOK, TV_DN_NRH, TV_DN_NRL, TV_DN_LDH, TV_DN_LDL,
 TV_CD_FATH, TV_CD_FATL, TV_CD_DNH, TV_CD_DNL, TV_CD_CNT, TV_CD_DROP,
 TV_UP_TOK, TV_UP_NRH, TV_UP_NRL, TV_UP_LDH, TV_UP_LDL,
 TV_SEND_SEQ, TV_LOCAL_SEQ, TV_N_SENDS, TV_N_LOSS, TV_N_DEL, TV_N_CODEL,
 TV_N_QUEUE, TV_NB_TXB, TV_NB_RXB, TV_NB_THR) = range(26)
TV_COUNT = 26


class TierState(NamedTuple):
    """Device state of the tiered stream backend, packed into THREE
    arrays so the while-loop carry stays flat (the carry-packing
    assumption of lanes.py "while-carry packing": a per-buffer cost every
    iteration, unmeasured on the attached chip):

    - ``flows``: the [S, F] endpoint law matrices (StreamState);
    - ``q``: [7, 2S, C2] int32 — the endpoints' event queues as stacked
      key/payload planes (thi, tlo, auxh, auxl, size, phi, plo), each
      row kept sorted by the 4-word key exactly like the [N] queues;
    - ``v``: [TV_COUNT, 2S] int32 — buckets, CoDel, and counters (the
      TV_* rows above)."""

    flows: StreamState
    q: jnp.ndarray
    v: jnp.ndarray


(TQ_THI, TQ_TLO, TQ_AUXH, TQ_AUXL, TQ_SIZE, TQ_PHI, TQ_PLO) = range(7)


def init_tier_state(
    s_flows: int,
    capacity: int,
    dn_tokens,
    up_tokens,
    interval: int,
) -> TierState:
    """Fresh tier state, on the HOST (numpy, like ``init_stream_state``).
    ``dn_tokens``/``up_tokens`` are the [2S] initial bucket fills
    (= burst) of each endpoint's lane; time-state starts at the same
    values LaneState uses (next_refill = one interval in, CoDel
    first_above = unset sentinel)."""
    i32 = np.int32
    s2 = 2 * s_flows
    q = np.zeros((7, s2, capacity), dtype=i32)
    q[TQ_THI] = NEVER32
    q[TQ_TLO] = NEVER32
    v = np.zeros((TV_COUNT, s2), dtype=i32)
    v[TV_DN_TOK] = np.asarray(dn_tokens).astype(i32)
    v[TV_UP_TOK] = np.asarray(up_tokens).astype(i32)
    v[TV_DN_NRL] = interval
    v[TV_UP_NRL] = interval
    # CD_UNSET mirrors lanes.CD_UNSET (module split avoids the import cycle)
    v[TV_CD_FATH] = -(1 << 31) + 1
    return TierState(flows=init_stream_state(s_flows), q=q, v=v)
