"""Host wall per faulted run between one segment's wait and the next
segment's call (the program's own ``fused/fault_swap`` span: the stall
check, the next epoch's placed leaves and stop pair), summed over the
run's swaps, a mean over the window's repeats, from the run journal.  Only
an engine with a fault schedule has the span."""

UNIT = "ms"


def read(raw: dict):
    from lib.run_journal import phase_ms

    return phase_ms(raw, "fault_swap")
