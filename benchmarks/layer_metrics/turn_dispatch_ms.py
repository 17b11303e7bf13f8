"""Host wall per device dispatch spent getting a fused call under way: the
peeked external schedule and its encoding (``peek``) and the call itself
until it RETURNS (``dispatch``: the jit dispatch of the whole lane-state
pytree, the eager one too) — not the wait, which is
``device_sync_ms_per_turn``.  Inside the window."""

UNIT = "ms"


def read(raw: dict):
    from lib.turn_spans import ms_per_dispatch

    return ms_per_dispatch(raw, ("peek", "dispatch"))
