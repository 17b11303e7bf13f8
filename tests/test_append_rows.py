"""The record-append law (``lanes._append_rows``) against its plain form.

The device event log, the hybrid egress buffer and the flow ring are all
appended to by one law: the j-th valid candidate of a flat batch lands on
row ``count + j``, rows past the buffer's end are counted lost, nothing else
in the buffer moves.  The parent wrote it as cumsum positions plus one row
scatter of every candidate; ``_append_rows`` writes blocks of the valid rows
only.  Here the law is written out in NumPy, candidate by candidate, and the
three append sites of the hybrid turn program are held to it bit for bit —
buffer, count and lost — at every density and buffer position that changes
the block walk.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.backend import lanes

R = lanes._APPEND_BLOCK
CAP = 2000
N_TAIL, T_TAIL = 12, 80  # merge tail [N, 2K + Cx]
K_SLOT, N_SLOT = 8, 100  # per-slot records [K, N]
N_EGRESS = 300  # one slot's packet outcomes [N]

DENSITIES = ("none", "one", "sparse", "block", "block_plus_1", "all")
COUNTS = {"empty": 0, "mid": 777, "near_end": CAP - 100, "past_end": CAP + 7}


def reference_append(buf, count, valid, rows):
    """The law, one candidate at a time."""
    buf = buf.copy()
    pos, lost = int(count), 0
    for i in np.flatnonzero(valid):
        if pos < len(buf):
            buf[pos] = rows[i]
        else:
            lost += 1
        pos += 1
    return buf, pos, lost


def _mask(shape, density, rng, prefix_rows=False):
    m = int(np.prod(shape))
    n = {"none": 0, "one": 1, "sparse": max(m // 20, 2), "block": R,
         "block_plus_1": R + 1, "all": m}[density]
    assert n <= m
    if not prefix_rows:
        flat = np.zeros(m, dtype=bool)
        flat[rng.choice(m, size=n, replace=False)] = True
        return flat.reshape(shape)
    # a merge tail is sorted: each lane's valid entries are a prefix
    rows, width = shape
    per = np.zeros(rows, dtype=np.int64)
    for _ in range(n):
        per[rng.choice(np.flatnonzero(per < width))] += 1
    return np.arange(width)[None, :] < per[:, None]


def _blank_state(**fields):
    s = lanes.LaneState._make([()] * len(lanes.LaneState._fields))
    zero = {f: jnp.int32(0) for f in lanes._AP_SCALARS}
    return s._replace(**zero, **fields)


def _params(**kw):
    return lanes.LaneParams(
        n_lanes=1, capacity=1, pops_per_iter=1, log_capacity=CAP, seed=0,
        stop_time=1, bootstrap_end=0, runahead=1, **kw)


def _i32(rng, shape, hi=1 << 20):
    return rng.integers(0, hi, size=shape, dtype=np.int32)


@jax.jit
def _tail_site(log, count, mask, thi, tlo, auxh, auxl, size):
    s = _blank_state(log=log, log_count=count, log_lost=jnp.int32(0))
    p = _params()
    recs = lanes._tail_records(
        mask, thi, tlo, auxh, auxl, size,
        jnp.arange(N_TAIL, dtype=jnp.int32))
    return lanes._append_log(p, s, recs, tail=True)


@jax.jit
def _slot_site(log, count, mask, cols):
    s = _blank_state(log=log, log_count=count, log_lost=jnp.int32(0))
    recs = {k: v.reshape(-1) for k, v in cols.items()}
    recs["valid"] = mask.reshape(-1)
    return lanes._append_log(_params(), s, recs)


@jax.jit
def _egress_site(egress, count, mask, delivered, td_hi, td_lo, src, seq, size):
    s = _blank_state(
        egress=egress, egress_count=count, egress_lost=jnp.int32(0),
        egress_min_hi=jnp.int32(lanes.NEVER32),
        egress_min_lo=jnp.int32(lanes.NEVER32))
    return lanes._append_egress(
        _params(egress_capacity=CAP), s, mask, delivered, td_hi, td_lo, src,
        jnp.arange(N_EGRESS, dtype=jnp.int32), seq, size)


def _t_join(hi, lo):
    return (hi.astype(np.int64) << 31) | lo.astype(np.int64)


def _case_tail(density, count, rng):
    shape = (N_TAIL, T_TAIL)
    mask = _mask(shape, density, rng, prefix_rows=True)
    thi, tlo, auxl, size = (_i32(rng, shape) for _ in range(4))
    src = _i32(rng, shape, hi=1 << 12)
    auxh = np.asarray(lanes.pack_aux_hi(
        jnp.full(shape, lanes.PACKET, dtype=jnp.int32), jnp.asarray(src)))
    dst = np.broadcast_to(np.arange(N_TAIL)[:, None], shape)
    rows = np.stack(
        [_t_join(thi, tlo), src, dst, auxl, size,
         np.full(shape, lanes.DROP_QUEUE)], axis=-1
    ).reshape(-1, 6).astype(np.int64)
    buf = rng.integers(-9, 9, size=(CAP, 6)).astype(np.int64)
    s = _tail_site(buf, np.int32(count), mask, thi, tlo, auxh, auxl, size)
    return (buf, mask.reshape(-1), rows), (s.log, s.log_count, s.log_lost), s


def _case_slot(density, count, rng):
    shape = (K_SLOT, N_SLOT)
    mask = _mask(shape, density, rng)
    cols = {k: rng.integers(0, 1 << 40, size=shape).astype(np.int64)
            for k in ("time", "src", "dst", "seq", "size", "outcome")}
    rows = np.stack(
        [cols[k] for k in ("time", "src", "dst", "seq", "size", "outcome")],
        axis=-1).reshape(-1, 6)
    buf = rng.integers(-9, 9, size=(CAP, 6)).astype(np.int64)
    s = _slot_site(buf, np.int32(count), mask, cols)
    return (buf, mask.reshape(-1), rows), (s.log, s.log_count, s.log_lost), s


def _case_egress(density, count, rng):
    mask = _mask((N_EGRESS,), density, rng)
    delivered = rng.random(N_EGRESS) < 0.7
    td_hi, td_lo, src, seq, size = (_i32(rng, N_EGRESS) for _ in range(5))
    rows = np.stack(
        [_t_join(td_hi, td_lo), src, np.arange(N_EGRESS), seq, size,
         np.where(delivered, lanes.DELIVERED, lanes.DROP_CODEL)], axis=-1
    ).astype(np.int64)
    buf = rng.integers(-9, 9, size=(CAP, 6)).astype(np.int64)
    s = _egress_site(buf, np.int32(count), mask, delivered, td_hi, td_lo,
                     src, seq, size)
    return (buf, mask, rows), (s.egress, s.egress_count, s.egress_lost), s


SITES = {"tail": _case_tail, "slot": _case_slot, "egress": _case_egress}


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("site", SITES)
def test_append_equals_the_plain_law(site, density, count):
    rng = np.random.default_rng(list(repr((site, density, count)).encode()))
    (buf, mask, rows), (got_buf, got_count, got_lost), s = SITES[site](
        density, COUNTS[count], rng)
    want_buf, want_count, want_lost = reference_append(
        buf, COUNTS[count], mask, rows)
    assert np.array_equal(np.asarray(got_buf), want_buf)
    assert int(got_count) == want_count
    assert int(got_lost) == want_lost
    # the engage counters: blocks follow the rows that landed
    kept = int(mask.sum()) - want_lost
    assert int(s.ap_rows) == kept
    assert int(s.ap_blocks) == -(-kept // R)
    assert int(s.ap_tail_blocks) == (int(s.ap_blocks) if site == "tail" else 0)


@pytest.mark.parametrize("cap", [1, 7, R - 1, R, R + 1])
def test_buffer_smaller_than_a_block(cap):
    """The block shrinks to the buffer (and to the batch), and an append
    that starts inside the last block keeps the rows already there."""
    rng = np.random.default_rng(cap)
    m = 3 * R
    mask = rng.random(m) < 0.5
    rows = rng.integers(0, 1 << 40, size=(m, 2)).astype(np.int64)
    buf = rng.integers(-9, 9, size=(cap, 2)).astype(np.int64)
    for count in (0, cap // 2, cap):
        got_buf, n, n_kept, _blocks = jax.jit(
            lambda b, c, v, r: lanes._append_rows(
                b, c, v, lambda pick: jnp.stack(
                    [pick(r[:, 0]), pick(r[:, 1])], axis=1))
        )(buf, np.int32(count), mask, rows)
        want_buf, want_count, want_lost = reference_append(
            buf, count, mask, rows)
        assert np.array_equal(np.asarray(got_buf), want_buf)
        assert count + int(n) == want_count
        assert int(n) - int(n_kept) == want_lost
