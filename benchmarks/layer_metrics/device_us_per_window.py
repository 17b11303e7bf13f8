"""Device-program wall per lookahead window: sum(SimResult.wall_seconds)
over sum(rounds), over the window's repeats — what one window of the
routed network costs the lane kernel, whatever its iterations."""

UNIT = "us"


def read(raw: dict):
    dev, rounds = raw.get("device_wall_s"), raw.get("rounds")
    if not dev or not rounds or not sum(rounds):
        return None
    return 1e6 * sum(dev) / sum(rounds)
