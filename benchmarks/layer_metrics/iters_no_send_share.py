"""Share of the iterations in which no (pop, lane) slot put a datagram
into the exchange — and which paid the exchange's sort and the row merge
all the same: ``loop_iters_no_send`` / ``lane_iters``, the window's last
repeat, counted inside the program (the loop ledger)."""

UNIT = "%"


def read(raw: dict):
    from lib.run_journal import share

    return share(raw, "loop_iters_no_send", "lane_iters")
