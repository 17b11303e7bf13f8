"""Core determinism primitives: time, event order, queue, RNG parity."""

import numpy as np
import pytest

from shadow_tpu.core import time as stime
from shadow_tpu.core.event import Event, EventKind
from shadow_tpu.core.event_queue import EventQueue
from shadow_tpu.core import rng


def test_time_conversions():
    assert stime.from_secs(3) == 3 * stime.NANOS_PER_SEC
    assert stime.from_millis(10) == 10 * stime.NANOS_PER_MILLI
    assert stime.sim_to_emu(0) == stime.SIM_START_EMU
    assert stime.emu_to_sim(stime.sim_to_emu(123)) == 123
    assert stime.sim_to_emu(stime.NEVER) == stime.NEVER
    assert stime.fmt(1_500_000_000) == "1.500000000s"


def test_event_total_order():
    # time > kind > src_host > seq, exactly the reference's order
    # (core/work/event.rs:84-130).
    a = Event(10, EventKind.PACKET, src_host=5, seq=9)
    b = Event(10, EventKind.LOCAL, src_host=0, seq=0)
    c = Event(10, EventKind.PACKET, src_host=6, seq=0)
    d = Event(11, EventKind.PACKET, src_host=0, seq=0)
    e = Event(10, EventKind.PACKET, src_host=5, seq=10)
    order = sorted([d, c, b, e, a])
    assert order == [a, e, c, b, d]


def test_event_queue_pops_in_order_and_until():
    q = EventQueue()
    evs = [
        Event(30, EventKind.LOCAL, 0, 1),
        Event(10, EventKind.PACKET, 2, 0),
        Event(10, EventKind.PACKET, 1, 4),
        Event(20, EventKind.LOCAL, 0, 0),
    ]
    for ev in evs:
        q.push(ev)
    assert q.next_time() == 10
    popped = list(q.pop_until(25))
    assert [e.key() for e in popped] == [
        (10, 0, 1, 4),
        (10, 0, 2, 0),
        (20, 1, 0, 0),
    ]
    assert q.next_time() == 30
    assert len(q) == 1
    q2 = EventQueue()
    assert q2.next_time() == stime.NEVER


def test_threefry_matches_jax_reference():
    # Our generic implementation must match JAX's own threefry2x32 bit-for-bit
    # so jax.random keys and ours share one cipher.
    import jax.numpy as jnp
    from jax.extend import random as jprng

    k = (np.uint32(0x13198A2E), np.uint32(0x03707344))
    counts = np.arange(16, dtype=np.uint32)
    expected = np.asarray(
        jprng.threefry_2x32(jnp.asarray(np.stack(k)), jnp.asarray(counts))
    )
    # jax packs a count vector as (first half -> c0, second half -> c1)
    x0, x1 = rng.threefry2x32(k[0], k[1], counts[:8], counts[8:], xp=np)
    got = np.concatenate([x0, x1])
    np.testing.assert_array_equal(got, expected)


def test_rng_numpy_jax_parity():
    import jax.numpy as jnp

    seed = 0xDEADBEEF_12345678
    streams = np.arange(64, dtype=np.uint32)
    counters = (np.arange(64, dtype=np.uint64) * np.uint64(977)) + np.uint64(2**33)
    a = rng.rand_u32(seed, streams, counters, xp=np)
    b = np.asarray(rng.rand_u32(seed, jnp.asarray(streams), jnp.asarray(counters), xp=jnp))
    np.testing.assert_array_equal(a, b)
    # distinct streams give distinct draws
    assert len(np.unique(a)) == len(a)


def test_u32_below_parity_and_range():
    import jax.numpy as jnp

    u = rng.rand_u32(42, np.uint32(7), np.arange(1000, dtype=np.uint64), xp=np)
    n = 10
    got_np = rng.u32_below(u, n, xp=np)
    got_jnp = np.asarray(rng.u32_below(jnp.asarray(u), n, xp=jnp))
    np.testing.assert_array_equal(got_np, got_jnp)
    assert got_np.max() < n and got_np.min() >= 0
    # roughly uniform
    counts = np.bincount(got_np, minlength=n)
    assert counts.min() > 50


def test_loss_threshold_edges():
    assert rng.loss_threshold(0.0) == 0
    assert rng.loss_threshold(1.0) == 1 << 32
    t = rng.loss_threshold(0.25)
    assert abs(t / 2**32 - 0.25) < 1e-9


def test_host_seed_spread():
    seeds = {rng.host_seed(1, h) for h in range(1000)}
    assert len(seeds) == 1000
