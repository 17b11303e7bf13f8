"""Share of the stream TIER's pop slots the loop offered that held an
event: ``loop_tier_pop_slots`` / (``lane_iters`` x ``stream_pops`` x 2 x
``flows``), the window's last repeat, counted inside the program (the loop
ledger).  ``None`` where the program has no tier."""

UNIT = "%"


def read(raw: dict):
    from lib.run_journal import share

    value = share(raw, "loop_tier_pop_slots", "lane_iters", "stream_pops",
                  "flows")
    return None if value is None else value / 2.0
