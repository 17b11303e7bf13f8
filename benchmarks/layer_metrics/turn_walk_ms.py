"""Host wall per device dispatch that is the turn's own residual
(``walk``: scalar decode, the validation walk's Python, ledger calls,
rollback bookkeeping — a turn's wall less every named phase), inside the
window."""

UNIT = "ms"


def read(raw: dict):
    from lib.turn_spans import ms_per_dispatch

    return ms_per_dispatch(raw, ("walk",))
