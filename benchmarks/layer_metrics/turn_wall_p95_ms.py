"""95th percentile of the wall between successive changes of
``device_turns``, sampled in ``on_window``, inside the window."""

UNIT = "ms"


def read(raw: dict):
    walls = raw.get("turn_walls_s")
    if not walls:
        return None
    from lib.stats import percentile

    return 1e3 * percentile(walls, 95)
