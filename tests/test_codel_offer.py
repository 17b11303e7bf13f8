"""``lanes.codel_offer_arrays`` against the scalar law, word for word.

The vectorized CoDel fetches its control-law interval ``CODEL_DIV[k]``
without an unconditional per-element table gather (PERF.md §6 PR 36):
entering an episode it selects between two constants, inside one it
gathers under a ``lax.cond`` that runs only in a pop in which some lane
keeps the entry.  Every case here holds all of that to ``net/codel.py
CoDel.offer`` lane by lane, on seeded random states, at a length below
and one above the table's.
"""

import jax
import numpy as np
import pytest

from shadow_tpu.backend import lanes
from shadow_tpu.net import codel

NARROW = 257  # a stream tier's width: shorter than the table
WIDE = 2 * codel.DIV_TABLE_SIZE  # a lane tier's: longer


def _lane_states(kind: str, n: int, seed: int) -> dict[str, np.ndarray]:
    """Seeded int64 lane states that put ``kind``'s branch of the law to
    work: times straddle 2**31 ns so both words of every pair matter."""
    rng = np.random.default_rng(seed)
    td = rng.integers(1_500_000_000, 3_000_000_000, n)
    st = {
        "td": td,
        "active": rng.random(n) < 0.8,
        # first_above: unset (0), due, or not yet due
        "fat": np.where(
            rng.random(n) < 0.2, 0,
            td + rng.integers(-300_000_000, 50_000_000, n)),
        "dnext": td + rng.integers(-250_000_000, 150_000_000, n),
        "sojourn": np.where(
            rng.random(n) < 0.15,
            rng.integers(0, codel.TARGET_NS, n),
            rng.integers(codel.TARGET_NS, 40 * codel.TARGET_NS, n)),
    }
    if kind == "quiet":  # no lane is in an episode: the guard's zero branch
        st["dropping"] = np.zeros(n, dtype=bool)
        st["dcount"] = rng.integers(0, 8, n)
    elif kind == "episode":  # counts over the table and past its clamp
        st["dropping"] = rng.random(n) < 0.7
        st["dcount"] = np.where(
            rng.random(n) < 0.5,
            rng.integers(0, codel.DIV_TABLE_SIZE, n),
            rng.integers(codel.DIV_TABLE_SIZE - 3, 5_000, n))
    elif kind == "enter":  # outside an episode, counts around the 2 rule
        st["dropping"] = np.zeros(n, dtype=bool)
        st["dcount"] = rng.choice([0, 1, 2, 3, 4, 900, 2_000], n)
        st["sojourn"] = rng.integers(
            codel.TARGET_NS, 40 * codel.TARGET_NS, n)
    elif kind == "one_lane":  # a single lane of the whole width drops
        st["dropping"] = np.zeros(n, dtype=bool)
        st["dcount"] = rng.integers(0, 8, n)
        i = int(rng.integers(n))
        st["dropping"][i] = st["active"][i] = True
        st["dcount"][i] = 777
        st["fat"][i] = st["dnext"][i] = td[i] - 1
        st["sojourn"][i] = 2 * codel.TARGET_NS
    elif kind == "inactive":  # nothing popped: every word stays
        st["active"] = np.zeros(n, dtype=bool)
        st["dropping"] = rng.random(n) < 0.5
        st["dcount"] = rng.integers(0, 2_000, n)
    else:
        raise ValueError(kind)
    return st


def _scalar(st):
    """``CoDel.offer`` on every active lane; inactive lanes keep state."""
    out = {k: [] for k in ("fat", "dnext", "dcount", "dropping", "drop",
                           "looked")}
    for i in range(len(st["td"])):
        c = codel.CoDel(
            first_above_time=int(st["fat"][i]), drop_next=int(st["dnext"][i]),
            drop_count=int(st["dcount"][i]), dropping=bool(st["dropping"][i]))
        drop = False
        if st["active"][i]:
            drop = c.offer(int(st["td"][i]), int(st["sojourn"][i]))
        out["fat"].append(c.first_above_time)
        out["dnext"].append(c.drop_next)
        out["dcount"].append(c.drop_count)
        out["dropping"].append(c.dropping)
        out["drop"].append(drop)
        # the dropping branch's lookup: a drop that was already dropping
        out["looked"].append(drop and bool(st["dropping"][i]))
    return {k: np.asarray(v) for k, v in out.items()}


def _pair(t):
    return (t >> 31).astype(np.int32), (t & lanes.MASK31).astype(np.int32)


def _vector_args(st):
    fat_hi, fat_lo = _pair(st["fat"])
    unset = st["fat"] == 0
    fat_hi = np.where(unset, lanes.CD_UNSET, fat_hi).astype(np.int32)
    fat_lo = np.where(unset, 0, fat_lo).astype(np.int32)
    dn_hi, dn_lo = _pair(st["dnext"])
    td_hi, td_lo = _pair(st["td"])
    return (
        fat_hi, fat_lo, dn_hi, dn_lo, st["dcount"].astype(np.int32),
        st["dropping"], td_hi, td_lo, st["sojourn"].astype(np.int32),
        st["active"], np.asarray(codel.CODEL_DIV, dtype=np.int32),
    )


def _vector(st):
    fh, fl, dh, dl, cnt, dropping, drop, looked = (
        np.asarray(x)
        for x in jax.jit(lanes.codel_offer_arrays)(*_vector_args(st)))
    join = lambda hi, lo: (hi.astype(np.int64) << 31) | lo  # noqa: E731
    return {
        "fat": np.where(fh == lanes.CD_UNSET, 0, join(fh, fl)),
        "dnext": join(dh, dl), "dcount": cnt, "dropping": dropping,
        "drop": drop, "looked": looked,
    }


@pytest.mark.parametrize("n", [NARROW, WIDE])
@pytest.mark.parametrize(
    "kind", ["quiet", "episode", "enter", "one_lane", "inactive"])
def test_every_word_equals_the_scalar_law(kind, n):
    st = _lane_states(kind, n, seed=36_000 + n + len(kind))
    want, got = _scalar(st), _vector(st)
    for word in ("fat", "dnext", "dcount", "dropping", "drop"):
        np.testing.assert_array_equal(got[word], want[word], err_msg=word)
    assert bool(got["looked"]) == bool(want["looked"].any())
    # the case reaches the branch it is named for
    took = want["looked"]
    entered = want["drop"] & ~st["dropping"]
    if kind == "quiet":
        assert not took.any() and want["drop"].any()
    elif kind == "episode":
        counts = want["dcount"][took]
        assert (counts < 10).any() and (counts > 1_024).any()
        assert ((counts > 10) & (counts < 1_024)).any()
    elif kind == "enter":
        assert {1, 2} <= set(want["dcount"][entered].tolist())
    elif kind == "one_lane":
        assert took.sum() == 1 and want["dcount"][took][0] == 778
    else:
        assert not want["drop"].any()
        for word in ("fat", "dnext", "dcount", "dropping"):
            np.testing.assert_array_equal(got[word], st[word])


def test_a_batched_predicate_keeps_every_word():
    """Under ``vmap`` (the sweep plane) the guard's predicate is batched
    and the conditional becomes a select: both branches run, same words."""
    sts = [_lane_states(k, WIDE, seed=36_100 + i)
           for i, k in enumerate(("quiet", "episode", "enter"))]
    args = [np.stack(col) for col in zip(*(_vector_args(s)[:-1] for s in sts))]
    table = _vector_args(sts[0])[-1]
    outs = jax.jit(jax.vmap(
        lambda *a: lanes.codel_offer_arrays(*a, table)))(*args)
    for b, st in enumerate(sts):
        one = lanes.codel_offer_arrays(*_vector_args(st))
        for got, want in zip(outs, one):
            np.testing.assert_array_equal(np.asarray(got)[b],
                                          np.asarray(want))


def _gathers(jaxpr, in_cond=False):
    """One entry a gather, nested jaxprs (``pjit``, ``cond`` branches)
    included: whether it sits inside a ``cond`` branch."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            found.append(in_cond)
        inner = in_cond or eqn.primitive.name == "cond"
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _gathers(sub, inner)
    return found


@pytest.mark.parametrize("n", [NARROW, WIDE])
def test_one_table_gather_and_it_is_guarded(n):
    st = _lane_states("episode", n, seed=1)
    jaxpr = jax.make_jaxpr(lanes.codel_offer_arrays)(*_vector_args(st))
    assert _gathers(jaxpr.jaxpr) == [True]
