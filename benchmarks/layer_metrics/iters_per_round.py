"""Loop iterations per lookahead window: sum(``lane_iters``) over
sum(``rounds``), over the window's repeats — an exact count of how many
passes of the body one 2 ms window of the routed gossip network takes
(the fullest lane of a window sets it; most lanes idle in each).

``rounds`` x ``iters_per_round`` x ``device_us_per_iter`` is a repeat's
device time.  This is ``iters_per_window``'s reader under a name of its
own: a ``model_config`` PR may not append its cell to that metric's
``workloads`` (PERF.md 7)."""

import runpy
from pathlib import Path

UNIT = "iters/round"

read = runpy.run_path(
    str(Path(__file__).with_name("iters_per_window.py")))["read"]
