"""Device-program wall per loop iteration: sum(SimResult.wall_seconds) over
sum(lane_iters), over the window's repeats (each spans seconds)."""

UNIT = "ms"


def read(raw: dict):
    dev, iters = raw.get("device_wall_s"), raw.get("lane_iters")
    if not dev or not iters or not sum(iters):
        return None
    return 1e3 * sum(dev) / sum(iters)
