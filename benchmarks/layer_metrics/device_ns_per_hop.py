"""Device-program wall per committed event: 1e9 x sum(device_wall_s) over
(repeats x ``events_per_repeat``) — the PDES field's figure, committed
events per second, inverted.  Its product with ``hops_per_iter`` is the
device time of one iteration.

A hop IS a delivery here (``events_per_repeat`` = ``lane_delivered`` =
``phold_hops``), so this is ``device_ns_per_delivery``'s reader under the
field's name: a ``model_config`` PR may not append its cell to that
metric's ``workloads`` (PERF.md 7)."""

import runpy
from pathlib import Path

UNIT = "ns"

read = runpy.run_path(
    str(Path(__file__).with_name("device_ns_per_delivery.py")))["read"]
