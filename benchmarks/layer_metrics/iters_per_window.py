"""Loop iterations per lookahead window: ``counters["lane_iters"]`` over
``rounds`` (an exact count; above 1 means surplus iterations, ROADMAP A3)."""

UNIT = "iters/window"


def read(raw: dict):
    iters, rounds = raw.get("lane_iters"), raw.get("rounds")
    if not iters or not rounds or not sum(rounds):
        return None
    return sum(iters) / sum(rounds)
