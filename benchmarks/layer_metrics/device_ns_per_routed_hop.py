"""Device-program wall per committed event on the routed graph: 1e9 x
sum(device_wall_s) over (repeats x ``events_per_repeat``).  Its product
with ``routed_hops_per_iter`` is the device time of one iteration — the
body with the run-time ``[G, G]`` path gathers and the loss draw compiled
in — to set beside the one-switch control's (``hops_per_iter`` x
``device_ns_per_hop``).

A hop IS a delivery here (``events_per_repeat`` = ``lane_delivered`` =
``phold_hops``), so this is ``device_ns_per_delivery``'s reader under the
cell's own name, as ``device_ns_per_hop`` is: a ``model_config`` PR may
not append its cell to that metric's ``workloads`` (PERF.md 7)."""

import runpy
from pathlib import Path

UNIT = "ns"

read = runpy.run_path(
    str(Path(__file__).with_name("device_ns_per_delivery.py")))["read"]
