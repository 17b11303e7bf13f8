"""Counter-based deterministic RNG (Threefry-2x32).

The reference gives every host its own ``Xoshiro256PlusPlus`` stream seeded
from the master seed (sim_config.rs:50-51, host.rs:658).  A stateful
sequential generator cannot be replayed out-of-order, which is exactly what a
batched TPU backend needs to do — so we use a *counter-based* generator
instead: Threefry-2x32 (the same cipher JAX's PRNG is built on), keyed by
``(master_seed, stream)`` and indexed by a 64-bit counter.

One implementation, written against the array-API surface shared by ``numpy``
and ``jax.numpy``, is used by both the CPU reference backend and the TPU lane
backend; the bit-identical outputs are what make cross-backend deterministic
replay possible (the property the reference gates with its determinism tests,
src/test/determinism/CMakeLists.txt:1-45).  The CPU backend's one-at-a-time
draws take the same cipher on Python ints (``rand_u32_scalar``).

Stream-id conventions (keep in one place so backends can't disagree):

- ``stream = host_id | LOSS_STREAM``   : per-packet Bernoulli loss decisions
- ``stream = host_id | APP_STREAM``    : application-model draws (phold peer
  picks, payload sizes, think times)
- ``stream = host_id | PORT_STREAM``   : ephemeral port allocation
- counter = the per-host monotonically increasing draw sequence number for
  that stream (each stream counts independently).
"""

from __future__ import annotations

from typing import Any, Tuple

import numpy as np

# High bits or'd into the stream id to separate draw purposes.
LOSS_STREAM = 1 << 30
APP_STREAM = 2 << 30
PORT_STREAM = 3 << 30

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def _rotl(x: Any, d: int, xp: Any) -> Any:
    u32 = xp.uint32
    return ((x << u32(d)) | (x >> u32(32 - d))).astype(u32)


def threefry2x32(k0: Any, k1: Any, c0: Any, c1: Any, xp: Any = np) -> Tuple[Any, Any]:
    """Threefry-2x32, 20 rounds.  All inputs uint32 arrays (or scalars);
    returns two uint32 arrays of the broadcast shape."""
    if xp is np:
        # Wrapping u32 arithmetic is the point; silence numpy's scalar
        # overflow warning (arrays wrap silently, 0-d scalars warn).
        with np.errstate(over="ignore"):
            return _threefry2x32_impl(k0, k1, c0, c1, xp)
    return _threefry2x32_impl(k0, k1, c0, c1, xp)


def _threefry2x32_impl(k0: Any, k1: Any, c0: Any, c1: Any, xp: Any) -> Tuple[Any, Any]:
    u32 = xp.uint32
    ks0 = xp.asarray(k0, dtype=u32)
    ks1 = xp.asarray(k1, dtype=u32)
    ks2 = (ks0 ^ ks1 ^ u32(_PARITY)).astype(u32)
    x0 = (xp.asarray(c0, dtype=u32) + ks0).astype(u32)
    x1 = (xp.asarray(c1, dtype=u32) + ks1).astype(u32)

    schedule = (
        (_ROTATIONS[0], ks1, ks2),
        (_ROTATIONS[1], ks2, ks0),
        (_ROTATIONS[0], ks0, ks1),
        (_ROTATIONS[1], ks1, ks2),
        (_ROTATIONS[0], ks2, ks0),
    )
    for i, (rots, add0, add1) in enumerate(schedule):
        for r in rots:
            x0 = (x0 + x1).astype(u32)
            x1 = _rotl(x1, r, xp)
            x1 = (x1 ^ x0).astype(u32)
        x0 = (x0 + add0).astype(u32)
        x1 = (x1 + add1 + u32(i + 1)).astype(u32)
    return x0, x1


def _split_seed(seed: int) -> Tuple[int, int]:
    seed &= (1 << 64) - 1
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def rand_u32(seed: int, stream: Any, counter: Any, xp: Any = np) -> Any:
    """One uniform uint32 per (stream, counter) pair; shapes broadcast."""
    return rand_u32_pair(seed, stream, counter, xp)[0]


def rand_u32_scalar(seed: int, stream: int, counter: int) -> int:
    """``int(rand_u32(seed, stream, counter))`` for ONE draw, on Python
    ints: the same cipher, word for word (tests/test_rng_scalar.py holds the two
    equal), without numpy's 0-d scalar arithmetic, which costs about 30
    times as much per draw.  The CPU oracle draws once per packet on a
    lossy path, so there the difference is most of its run time."""
    m = 0xFFFFFFFF
    s_lo, s_hi = _split_seed(seed)
    counter = int(counter) & ((1 << 64) - 1)
    ks = (s_lo, (int(stream) ^ s_hi) & m)
    ks += (ks[0] ^ ks[1] ^ _PARITY,)
    x0 = ((counter & m) + ks[0]) & m
    x1 = ((counter >> 32) + ks[1]) & m
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = (x0 + x1) & m
            x1 = (((x1 << r) & m) | (x1 >> (32 - r))) ^ x0
        x0 = (x0 + ks[(i + 1) % 3]) & m
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & m
    return x0


def rand_u32_pair(seed: int, stream: Any, counter: Any, xp: Any = np) -> Tuple[Any, Any]:
    s_lo, s_hi = _split_seed(seed)
    u32 = xp.uint32
    k0 = u32(s_lo)
    k1 = (xp.asarray(stream, dtype=u32) ^ u32(s_hi)).astype(u32)
    counter = xp.asarray(counter)
    c0 = counter.astype(xp.uint64).astype(u32)
    c1 = (counter.astype(xp.uint64) >> xp.uint64(32)).astype(u32)
    return threefry2x32(k0, k1, c0, c1, xp)


def u32_below(u: Any, n: Any, xp: Any = np) -> Any:
    """Map a uniform uint32 to ``[0, n)`` by the multiply-shift trick.

    Slightly biased for huge ``n`` but branch-free and bit-identical across
    backends, which is what matters here.
    """
    u64 = xp.uint64
    return ((xp.asarray(u, dtype=u64) * xp.asarray(n, dtype=u64)) >> u64(32)).astype(
        xp.uint32
    )


def loss_threshold(packet_loss: float) -> int:
    """Convert a loss probability to the Bernoulli drop threshold:
    drop iff ``uint64(rand_u32) < threshold``.

    The comparison domain is **u64**, not u32: ``packet_loss=1.0`` maps to
    ``2**32``, which must always drop and is unrepresentable in u32 (it would
    wrap to "never drop").  Backends store loss tables in int64/uint64 lanes
    and widen the draw before comparing.
    """
    if packet_loss <= 0.0:
        return 0
    if packet_loss >= 1.0:
        return 1 << 32  # > any u32 draw: always drop
    return int(packet_loss * 4294967296.0)


def host_seed(master_seed: int, host_id: int) -> int:
    """Per-host 64-bit sub-seed (analog of ``seed ^ hostname_hash``,
    sim_config.rs:242) — used for host-local sequential draws on the CPU
    path where a cheap stateful stream is handy."""
    x = (master_seed ^ (host_id * 0x9E3779B97F4A7C15)) & ((1 << 64) - 1)
    # splitmix64 finalizer
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9 & ((1 << 64) - 1)
    x = (x ^ (x >> 27)) * 0x94D049BB133111EB & ((1 << 64) - 1)
    return x ^ (x >> 31)
