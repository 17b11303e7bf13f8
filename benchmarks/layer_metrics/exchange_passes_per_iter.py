"""Passes of the compacted exchange an iteration of a fan-out program
took (1 but for a flood's front, ``lanes._merge_append`` step 2):
``loop_exchange_passes`` / ``lane_iters``, the window's last repeat,
counted inside the program (the loop ledger)."""

UNIT = "passes/iter"


def read(raw: dict):
    from lib.run_journal import ratio

    return ratio(raw, "loop_exchange_passes", "lane_iters")
