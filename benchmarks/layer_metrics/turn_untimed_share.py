"""Share of the window's wall that lies outside every turn: the window
loop's top and the host-only windows.  100 x (1 - sum of the window's
turns' walls / the window's wall)."""

UNIT = "%"


def read(raw: dict):
    from lib.turn_spans import window_rows

    rows, wall = window_rows(raw), raw.get("window_wall_s")
    if not rows or not wall:
        return None
    return 100.0 * (1.0 - sum(r.t_end - r.t_start for r in rows) / wall)
