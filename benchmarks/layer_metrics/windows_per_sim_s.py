"""Lookahead windows per simulated second: sum(rounds) over (repeats x
horizon_sim_s), an exact count.  A window opens at the next pending event
and is one smallest used path latency long, so a routed graph whose
self-edges are far below its median path runs many (500 a sim-s at 2 ms
if no window is empty) — each costs at least one loop iteration."""

UNIT = "windows/sim_s"


def read(raw: dict):
    rounds, horizon = raw.get("rounds"), raw.get("horizon_sim_s")
    if not rounds or not horizon:
        return None
    return sum(rounds) / (len(rounds) * horizon)
