"""Device programs the traced repeat executed: the events of the trace's
``XLA Modules`` line (``raw["trace"]["programs"]``, ``lib/trace.py``).

A calm fused repeat is ONE program; a run segmented at fault epochs is one
call of its one compiled program per segment (six for five epochs inside
the horizon), and would read 1 with the epoch swap inside the device
loop.  What it costs the device is ``between_programs_ms``."""

UNIT = "count"


def read(raw: dict):
    tr = raw.get("trace")
    if not tr or not tr.get("programs"):
        return None
    return int(tr["programs"])
