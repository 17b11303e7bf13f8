"""Backend-compile events (cache reads included) that ended inside the
window.  Should be 0: every shape is warmed up in set-up."""

UNIT = "count"


def read(raw: dict):
    secs = raw.get("compile_secs_in_window")
    return None if secs is None else len(secs)
