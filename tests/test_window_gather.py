"""The exchange's window gather (``lanes._window_gather``, ISSUE 33).

Lane ``n`` receives the contiguous window ``arr[start[n] : start[n] + c]``
of every flat operand; entries past the operand's end are garbage the
caller masks by its segment counts.  The law under test is that contract,
against plain numpy slices — not the layout in which the rows are gathered
(a row at least a tile wide, lanes minor once: docs/tpu-backend.md), which
``tests/test_chip_compile.py`` holds in the compiled text.

Cases: window widths on and off a power of two, one operand / the
exchange's five / the payload exchange's seven (mixed dtypes, so the dtype
grouping keeps the caller's order), operand lengths that are no multiple of
the row width, and starts at 0, at the last entry and past the end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.backend import lanes

I32, I64 = np.int32, np.int64
#: operand dtypes by count: one, the exchange's five, the payload
#: exchange's seven with a second dtype threaded through
DTYPES = {
    1: (I32,),
    5: (I32,) * 5,
    7: (I32, I64, I32, I32, I64, I32, I64),
}
#: operand length by lane count: never a multiple of a row width
LENGTH = {1: 17, 129: 2_003, 1_000: 20_003}


def _starts(rng, n: int, m: int) -> np.ndarray:
    """[n] window starts: random, with the edges pinned — 0, the last
    entry, the end itself and past it (a lane with an empty segment)."""
    start = rng.integers(0, m, size=n)
    edges = [m - 1, 0, m, m + 5, max(m - 16, 0)]
    for i, e in enumerate(edges[:n]):
        start[(i * 37) % n] = e
    return start.astype(np.int32)


@pytest.mark.parametrize("n", sorted(LENGTH))
@pytest.mark.parametrize("a", sorted(DTYPES))
@pytest.mark.parametrize("c", [1, 3, 8, 16])
def test_window_gather_equals_numpy_slices(c, a, n):
    m = LENGTH[n]
    rng = np.random.default_rng(1000 * c + 10 * a + n)
    arrs = [
        rng.integers(-(2**31), 2**31 - 1, size=m).astype(dt)
        for dt in DTYPES[a]
    ]
    start = _starts(rng, n, m)
    got = jax.jit(lambda xs, s: lanes._window_gather(xs, s, c))(
        [jnp.asarray(x) for x in arrs], jnp.asarray(start)
    )
    assert len(got) == a
    idx = start[:, None].astype(np.int64) + np.arange(c)[None, :]  # [n, c]
    live = idx < m  # past the end is garbage the caller masks
    assert live[start < m, 0].all() and not live[start >= m].any()
    for arr, g in zip(arrs, got):
        g = np.asarray(g)
        assert g.shape == (n, c) and g.dtype == arr.dtype
        want = arr[np.minimum(idx, m - 1)]
        np.testing.assert_array_equal(
            np.where(live, g, 0), np.where(live, want, 0)
        )


def test_window_gather_whole_operand_in_order():
    """Disjoint windows laid end to end give the operand back: nothing is
    skipped or repeated at a row boundary."""
    c, m = 8, 4_099
    arr = np.arange(m, dtype=I32) * 7 - 3
    start = np.arange(0, m, c, dtype=I32)
    (g,) = lanes._window_gather([jnp.asarray(arr)], jnp.asarray(start), c)
    flat = np.asarray(g).reshape(-1)[:m]
    np.testing.assert_array_equal(flat, arr)
