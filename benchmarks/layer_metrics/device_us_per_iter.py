"""Device-program wall per loop iteration, in microseconds: 1e6 x
sum(``device_wall_s``) over sum(``lane_iters``), over the window's repeats
— what one pass of the body costs with the ``[G, G]`` path tables and the
loss draw compiled in (16 lookups and draws an iteration at D = 8, two
pops).

This is ``device_ms_per_iter``'s reader in another unit and under a name
of its own: a ``model_config`` PR may not append its cell to that metric's
``workloads`` (PERF.md 7)."""

import runpy
from pathlib import Path

UNIT = "us"

_ms_per_iter = runpy.run_path(
    str(Path(__file__).with_name("device_ms_per_iter.py")))["read"]


def read(raw: dict):
    ms = _ms_per_iter(raw)
    return None if ms is None else 1e3 * ms
