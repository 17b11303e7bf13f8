"""Seconds of tracing, lowering and compiling (or reading the compile cache)
before the window: every ``/jax/core/compile/*`` duration that ended before
it opened (``lib/probe.py``)."""

UNIT = "s"


def read(raw: dict):
    return raw.get("trace_compile_s")
