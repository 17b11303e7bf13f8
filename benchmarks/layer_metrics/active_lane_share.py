"""Share of the lanes that popped at least one event, a mean over the
iterations: ``loop_active_lanes`` / (``lane_iters`` x ``lanes``), the
window's last repeat, counted inside the program (the loop ledger)."""

UNIT = "%"


def read(raw: dict):
    from lib.run_journal import share

    return share(raw, "loop_active_lanes", "lane_iters", "lanes")
