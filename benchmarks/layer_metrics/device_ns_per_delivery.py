"""Device-program wall per delivered event: sum(SimResult.wall_seconds) over
(repeats x events_per_repeat), over the window's repeats — the lane kernel's
cost in the one unit comparable across widths and chip counts."""

UNIT = "ns"


def read(raw: dict):
    dev, events = raw.get("device_wall_s"), raw.get("events_per_repeat")
    if not dev or not events:
        return None
    return 1e9 * sum(dev) / (len(dev) * events)
