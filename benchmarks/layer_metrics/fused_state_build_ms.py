"""Host wall per fused run spent on the state the run starts from (the
program's own ``fused/state_build`` span, ``TpuEngine._start_state``: an
engine's first run builds and places it, every later one is handed the
kept device arrays), a mean over the window's repeats, from the run
journal."""

UNIT = "ms"


def read(raw: dict):
    from lib.run_journal import phase_ms

    return phase_ms(raw, "state_build")
