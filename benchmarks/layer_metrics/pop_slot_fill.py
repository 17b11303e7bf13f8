"""Share of the [N] pop slots the loop offered that held an event:
``loop_pop_slots`` / (``lane_iters`` x ``pops_per_iter`` x ``lanes``), the
window's last repeat, counted inside the program (the loop ledger)."""

UNIT = "%"


def read(raw: dict):
    from lib.run_journal import share

    return share(raw, "loop_pop_slots", "lane_iters", "pops_per_iter",
                 "lanes")
