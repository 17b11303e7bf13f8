"""Host wall per device dispatch spent on egressed deliveries: the slice's
read back to the host (``egress_read``) and the per-row application that
routes each to its worker (``egress_apply``), inside the window."""

UNIT = "ms"


def read(raw: dict):
    from lib.turn_spans import ms_per_dispatch

    return ms_per_dispatch(raw, ("egress_read", "egress_apply"))
