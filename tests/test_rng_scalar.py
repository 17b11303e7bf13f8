"""``core/rng.py rand_u32_scalar``: the CPU oracle's one-draw form of the
cipher is ``rand_u32`` word for word."""

import random

import numpy as np
import pytest

from shadow_tpu.core import rng

EDGE_SEEDS = [0, 1, 2**31 - 1, 2**32 - 1, 2**32, 2**63, 2**64 - 1]
EDGE_COUNTERS = [0, 1, 2**31, 2**32 - 1, 2**32, 2**63 - 1]


@pytest.mark.parametrize("seed", EDGE_SEEDS)
def test_scalar_draw_equals_the_array_form_at_the_edges(seed):
    for stream in (0, 7 | rng.LOSS_STREAM, 9 | rng.APP_STREAM, 2**32 - 1):
        for counter in EDGE_COUNTERS:
            assert rng.rand_u32_scalar(seed, stream, counter) == int(
                rng.rand_u32(seed, stream, counter))


@pytest.mark.parametrize("case", range(4))
def test_scalar_draw_equals_the_array_form_on_random_inputs(case):
    rnd = random.Random(case)
    seed = rnd.getrandbits(64 if case % 2 else 31)
    streams = np.array([rnd.getrandbits(32) for _ in range(500)], np.uint32)
    counters = np.array([rnd.getrandbits(40) for _ in range(500)], np.uint64)
    want = rng.rand_u32(seed, streams, counters)
    got = [rng.rand_u32_scalar(seed, int(s), int(c))
           for s, c in zip(streams, counters)]
    assert got == want.tolist()
    # numpy integers are taken as they come from a host's counters
    assert rng.rand_u32_scalar(seed, streams[0], counters[0]) == got[0]
