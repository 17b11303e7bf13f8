"""95th percentile of the iterations a lookahead window took (the upper
edge of its log2 bucket, at most the exact maximum), beside the mean that
``iters_per_window`` / ``iters_per_round`` report: ``loop_round_iters_p95``
of the window's last repeat, counted inside the program (the loop
ledger)."""

UNIT = "iters/window"


def read(raw: dict):
    from lib.run_journal import ratio

    return ratio(raw, "loop_round_iters_p95")
