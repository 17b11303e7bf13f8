"""Committed events (PHOLD hops over the routed graph) per loop iteration:
repeats x ``events_per_repeat`` over sum(``lane_iters``) — an exact count
of how much of an iteration is work.  A lane is handed ~0.43 arrivals in a
2 ms window (4 messages a lane, a mean path of 18.6 ms), so most pop slots
of an iteration are empty: the one-switch control, one hop a window, reads
2 816.

``events_per_repeat`` = ``lane_delivered`` = ``phold_hops`` (a lost
datagram is neither).  This is ``hops_per_iter``'s reader under the cell's
own name: a ``model_config`` PR may not append its cell to that metric's
``workloads`` (PERF.md 7)."""

import runpy
from pathlib import Path

UNIT = "hops/iter"

read = runpy.run_path(
    str(Path(__file__).with_name("hops_per_iter.py")))["read"]
