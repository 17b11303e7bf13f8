"""Device-program wall per datagram sent: 1e9 x sum(device_wall_s) over
(repeats x ``events_per_repeat``).  Its product with ``sends_per_iter`` is
the device time of one iteration.

A send IS a delivery here (``events_per_repeat`` = ``lane_delivered`` =
``gossip_sends``: zero loss, nothing shed), so this is
``device_ns_per_delivery``'s reader under the field's name, as
``device_ns_per_hop`` is: a ``model_config`` PR may not append its cell to
that metric's ``workloads`` (PERF.md 7)."""

import runpy
from pathlib import Path

UNIT = "ns"

read = runpy.run_path(
    str(Path(__file__).with_name("device_ns_per_delivery.py")))["read"]
