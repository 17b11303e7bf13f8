"""int32 pair arithmetic for the lane kernels.

TPU has no native int64: every i64 op lowers to X64Split/Combine custom
calls that cannot fuse, fragmenting the while body into tiny kernels (the
per-launch cost is unmeasured on the attached chip).  All resident lane
state therefore uses (hi, lo) int32 pairs with value = hi * 2**31 + lo,
lo in [0, 2**31); (NEVER32, NEVER32) encodes the NEVER sentinel for
time-valued pairs.  Every helper here is exact within its documented
range and compiles to plain fusable int32 lanes.
"""

from __future__ import annotations

import jax.numpy as jnp

NEVER32 = 0x7FFFFFFF  # plain int: no device array at import time
MASK31 = 0x7FFFFFFF


def pair_lt(ahi, alo, bhi, blo):
    return (ahi < bhi) | ((ahi == bhi) & (alo < blo))


def pair_ge(ahi, alo, bhi, blo):
    return ~pair_lt(ahi, alo, bhi, blo)


def pair_min_lanes(hi, lo):
    """Lexicographic min over all elements of an (hi, lo) pair array."""
    mh = jnp.min(hi)
    ml = jnp.min(jnp.where(hi == mh, lo, NEVER32))
    return mh, ml


def pair_add32(hi, lo, x):
    """pair + x for 0 <= x < 2**31 (x int32 scalar or [N])."""
    t = lo + x  # may wrap into the sign bit: that IS the carry
    return hi + (t < 0).astype(jnp.int32), t & MASK31


def pair_sub32(hi, lo, x):
    """pair - x for 0 <= x < 2**31; caller guarantees pair >= x.
    t < 0 means the true low word is t + 2**31, whose int32 bit pattern
    is t & MASK31 (adding 2**31 just clears the sign bit mod 2**32)."""
    t = lo - x
    return hi - (t < 0).astype(jnp.int32), t & MASK31


def pair_add_pair(ahi, alo, bhi, blo):
    t = alo + blo
    return ahi + bhi + (t < 0).astype(jnp.int32), t & MASK31


def pair_max(ahi, alo, bhi, blo):
    a_wins = pair_ge(ahi, alo, bhi, blo)
    return jnp.where(a_wins, ahi, bhi), jnp.where(a_wins, alo, blo)


def pair_sel(c, ahi, alo, bhi, blo):
    return jnp.where(c, ahi, bhi), jnp.where(c, alo, blo)


def pair_sub_clamp(ahi, alo, bhi, blo, lim):
    """max(0, min(a - b, lim)) as int32 — exact whenever the true
    difference lies in [0, lim] (lim < 2**31)."""
    d = ahi - bhi
    raw = alo - blo  # in (-2**31, 2**31)
    ge = pair_ge(ahi, alo, bhi, blo)
    # d == 1 with raw < 0: value = 2**31 + raw = (raw + 1) + MASK31,
    # which cannot overflow because raw + 1 <= 0
    return jnp.where(
        ~ge,
        0,
        jnp.where(
            d == 0,
            jnp.minimum(raw, lim),
            jnp.where(
                (d == 1) & (raw < 0),
                jnp.minimum((raw + 1) + MASK31, lim),
                lim,
            ),
        ),
    )


def pair_sub_pair(ahi, alo, bhi, blo):
    """a - b as a pair, valid when a >= b (callers mask the a < b case)."""
    t = alo - blo
    borrow = (t < 0).astype(jnp.int32)
    return ahi - bhi - borrow, t & MASK31


def pair_abs_diff(ahi, alo, bhi, blo):
    """|a - b| as a pair (both subtractions computed, the valid one kept)."""
    ge = pair_ge(ahi, alo, bhi, blo)
    d1h, d1l = pair_sub_pair(ahi, alo, bhi, blo)
    d2h, d2l = pair_sub_pair(bhi, blo, ahi, alo)
    return pair_sel(ge, d1h, d1l, d2h, d2l)


def pair_div_pow2(hi, lo, k: int):
    """(hi, lo) >> k for static 1 <= k <= 30 (non-negative pairs)."""
    mask = (1 << k) - 1
    return hi >> k, ((hi & mask) << (31 - k)) + (lo >> k)


def pair_mul_small(hi, lo, c: int):
    """pair * c for a small static 1 <= c <= 7; caller guarantees the
    product fits the pair range (hi * c < 2**31).  Decomposes lo so every
    int32 intermediate stays in range: lo = lh*2**16 + ll, and
    lh*c = q*2**15 + s gives lo*c = q*2**31 + s*2**16 + ll*c.  The final
    sum can reach 2**31 + 65535*c, one carry past the low word: the int32
    wrap IS that carry (sign bit set), recovered exactly like
    pair_add32."""
    if not 1 <= c <= 7:
        raise ValueError(f"pair_mul_small: c={c} out of range")
    lh = lo >> 16
    ll = lo & 0xFFFF
    mid = lh * c
    q = mid >> 15
    s = mid & 0x7FFF
    t = (s << 16) + ll * c
    return hi * c + q + (t < 0).astype(jnp.int32), t & MASK31


# engine-guarded ceiling for pair_mod_small's modulus: every intermediate
# of the chunked reduction must fit int32 (see the derivation below)
MOD_SMALL_LIMIT = 1 << 22


def pair_mod_small(hi, lo, m: int):
    """``(hi * 2**31 + lo) % m`` for a STATIC modulus ``m < 2**22``, in pure
    int32 lanes — the X64-emulated int64 ``%`` breaks fusion and was the
    last custom call in the passive hot loop.

    Reduction: ``v % m = ((hi % m) * (2**31 % m) + lo % m) % m``; the
    product is folded 8 bits at a time with the STATIC chunks of
    ``M = 2**31 % m``, so every intermediate is ``< m*256 + m*255 < 2**31``
    when ``m < 2**22``."""
    if m >= MOD_SMALL_LIMIT:
        raise ValueError(f"pair_mod_small: modulus {m} >= {MOD_SMALL_LIMIT}")
    big_m = (1 << 31) % m
    a = hi % m
    r = jnp.zeros_like(a)
    for shift in (24, 16, 8, 0):
        chunk = (big_m >> shift) & 0xFF
        r = ((r << 8) + a * chunk) % m
    return (r + lo % m) % m
