"""Share of a repeat's call wall the host spends outside the device program:
1 - sum(SimResult.wall_seconds) / sum(call wall) — state build and
``collect`` on the fused driver."""

UNIT = "%"


def read(raw: dict):
    call, dev = raw.get("call_wall_s"), raw.get("device_wall_s")
    if not call or not dev:
        return None
    return 100.0 * (1.0 - sum(dev) / sum(call))
