"""Host wall blocked on the device's readback per turn, inside the window:
the window's ``device_sync_s`` over its ``device_turns``."""

UNIT = "ms"


def read(raw: dict):
    turns = raw.get("window_device_turns")
    if not turns:
        return None
    return 1e3 * raw["window_device_sync_s"] / turns
