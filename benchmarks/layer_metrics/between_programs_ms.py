"""Device idle time BETWEEN programs in the traced repeat, in ms: the
``between_programs`` entry of the trace's idle gaps (``lib/trace.py``
``_gaps``: from the last operation of one program to the first of the
next) — what the host's epoch swaps cost the device in a repeat of a run
segmented at fault epochs.  A repeat of one program has no such gap, and
the reader then finds nothing to read."""

UNIT = "ms"


def read(raw: dict):
    tr = raw.get("trace")
    if not tr:
        return None
    gaps = dict(tr.get("breakdown", {}).get("idle_gaps", []))
    if "between_programs" not in gaps:
        return None
    return 1e3 * gaps["between_programs"]
