"""Host wall per fused run inside the device program's CALL until it
returns (the program's own ``fused/dispatch`` span: argument handling and
the enqueue; summed over a faulted run's segments), a mean over the
window's repeats, from the run journal."""

UNIT = "ms"


def read(raw: dict):
    from lib.run_journal import phase_ms

    return phase_ms(raw, "dispatch")
