"""Committed events (PHOLD hops) per loop iteration: repeats x
``events_per_repeat`` over sum(``lane_iters``) — an exact count of how much
of an iteration is work.  Every lane offers ``pops`` slots an iteration and
a hop takes two of them (the PACKET, then the DELIVERY it inserts, whose
pop is the send), so hops x 2 / (pops x lanes) of the pop slots hold an
event; the fullest lane of a window sets how many iterations it takes."""

UNIT = "hops/iter"


def read(raw: dict):
    iters, events = raw.get("lane_iters"), raw.get("events_per_repeat")
    if not iters or not events or not sum(iters):
        return None
    return len(iters) * events / sum(iters)
