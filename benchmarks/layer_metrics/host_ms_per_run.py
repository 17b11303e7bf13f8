"""Host wall per fused run outside the device program: (sum(call wall) -
sum(SimResult.wall_seconds)) / repeats — the wall of the program's
``state_build`` and ``collect`` phases (backend/tpu_engine.py), read off the
host clock around the call because a reader sees only the runner's ``raw``."""

UNIT = "ms"


def read(raw: dict):
    call, dev = raw.get("call_wall_s"), raw.get("device_wall_s")
    if not call or not dev:
        return None
    return 1e3 * (sum(call) - sum(dev)) / len(call)
