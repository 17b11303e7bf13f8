"""Share of a traced steady span in which no leaf operation ran on the
device: 1 - busy / span, from the profiler's trace (``lib/trace.py``)."""

UNIT = "%"


def read(raw: dict):
    tr = raw.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
