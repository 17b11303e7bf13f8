"""Datagrams sent per loop iteration: repeats x ``events_per_repeat`` over
sum(``lane_iters``) — an exact count of how much of an iteration is work.
In a gossip cell every send is delivered (zero loss, nothing shed), so
``events_per_repeat`` = ``lane_delivered`` = ``gossip_sends``, and this is
``hops_per_iter``'s reader under the field's name.  A node offers ``pops``
slots an iteration and a send costs its receiver two of them (the PACKET,
then the DELIVERY it inserts, whose pop is the handler: up to D - 1
forwards, or one duplicate counted), so the fullest node of a window — up
to D copies of every message in flight — sets how many iterations the
window takes."""

import runpy
from pathlib import Path

UNIT = "sends/iter"

read = runpy.run_path(
    str(Path(__file__).with_name("hops_per_iter.py")))["read"]
