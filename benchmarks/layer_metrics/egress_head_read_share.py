"""Share of the hybrid turns' egress drains served whole by the head
that rides the turn's one packed read-back (no second device read):
``sync_stats["egress_head_reads"]`` / (``egress_head_reads`` +
``egress_reads``, the tail reads) over the run."""

UNIT = "%"


def read(raw: dict):
    stats = raw.get("sync_stats") or {}
    head, tail = stats.get("egress_head_reads"), stats.get("egress_reads")
    if head is None or tail is None or not head + tail:
        return None
    return 100.0 * head / (head + tail)
