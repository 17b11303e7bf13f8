"""Blocking device turns per simulated second over the whole run:
``sync_stats["device_turns"]`` over the horizon (an exact count while the
window law is unchanged)."""

UNIT = "turns/sim_s"


def read(raw: dict):
    sync, horizon = raw.get("sync_stats"), raw.get("horizon_sim_s")
    if not sync or not horizon:
        return None
    return sync["device_turns"] / horizon
