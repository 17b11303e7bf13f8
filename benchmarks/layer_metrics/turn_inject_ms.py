"""Host wall per device dispatch spent packing staged sends into injection
blocks and copying them to the device (the turn's ``inject`` phase:
``HybridEngine._build_inj``), inside the window."""

UNIT = "ms"


def read(raw: dict):
    from lib.turn_spans import ms_per_dispatch

    return ms_per_dispatch(raw, ("inject",))
