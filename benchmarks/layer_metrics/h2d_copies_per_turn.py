"""Host-to-device array copies of the hybrid turn path per device turn:
``sync_stats["h2d_copies"]`` / ``sync_stats["device_turns"]`` over the run
(law: 1 + the async dispatch's misses and the overflow blocks, per turn)."""

UNIT = "count"


def read(raw: dict):
    stats = raw.get("sync_stats") or {}
    turns = stats.get("device_turns")
    if not turns or "h2d_copies" not in stats:
        return None
    return stats["h2d_copies"] / turns
