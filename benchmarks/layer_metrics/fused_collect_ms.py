"""Host wall per fused run in ``TpuEngine.collect`` (the program's own
``fused/collect`` span: the ONE batched read-back and the counters), a
mean over the window's repeats, from the run journal."""

UNIT = "ms"


def read(raw: dict):
    from lib.run_journal import phase_ms

    return phase_ms(raw, "collect")
