"""Application models (the simulated workloads).

The reference runs real Linux binaries under syscall interposition; the
built-in *models* here are the TPU-friendly first tier: each model is a
small state machine over the host API below, restricted enough that the TPU
lane backend can run the identical logic vectorized on-device (one lane per
host).  Real-binary execution via the native shim plugs into the same engine
as a host-resident app (later milestone).

A model reacts to three stimuli, always at a definite simulation time:

- ``on_start(api)``        — process start (config ``start_time``)
- ``on_timer(api, t)``     — a timer it armed fired
- ``on_delivery(api, t, src, seq, size, payload=None)`` — a datagram arrived

and acts through the :class:`HostApi`: ``send``, ``set_timer``,
``rand_u32`` (deterministic APP_STREAM draws), and counters.
"""

from __future__ import annotations

from typing import Any, Callable, Protocol


class HostApi(Protocol):
    """What a model may do to its host (both backends provide this)."""

    host_id: int
    num_hosts: int

    def send(self, dst: int, size_bytes: int, payload: object = None) -> int:
        """Send a datagram (IP size incl. 28 header bytes) at current time;
        returns its per-host sequence number.  ``payload`` is opaque cargo
        handed to the receiver's ``on_delivery(..., payload=)``: it never
        affects event ordering or the event log, which record sizes only
        (``gossip`` rides its message id here; on the lane backend the
        payload is one int32 word of the queue row)."""

    def set_timer(self, t_abs_ns: int) -> None:
        """Arm a timer local event at absolute sim time."""

    def set_timer_relative(self, delta_ns: int) -> None:
        """Arm a timer ``delta_ns`` after the current time."""

    def schedule_at(self, t_abs_ns: int, fn) -> None:
        """Queue an exact-time local event calling ``fn(host)`` (may land
        at the current instant; pops in event-key order)."""

    def resolve(self, hostname: str) -> int:
        """DNS: hostname -> host id (also accepts a numeric id string)."""

    def rand_u32(self) -> int:
        """Next deterministic app-stream draw (u32)."""

    def count(self, key: str, n: int = 1) -> None:
        """Bump a named per-host counter (merged into sim stats)."""


class AppModel(Protocol):
    def on_start(self, api: HostApi) -> None: ...

    def on_timer(self, api: HostApi, t: int) -> None: ...

    def on_delivery(self, api: HostApi, t: int, src: int, seq: int, size: int, payload=None) -> None: ...


_REGISTRY: dict[str, Callable[..., AppModel]] = {}


def register_model(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        return cls

    return deco


def create_model(
    path: str, args: list[str], environment: dict | None = None
) -> AppModel:
    """Instantiate an app from a process ``path`` + ``args`` (config-
    compatible with the reference's process entries).  A registered model
    name selects the built-in (lane-compilable) tier; an executable path
    selects the native-shim tier — a real Linux binary run under syscall
    interposition, as the reference does for every process."""
    if path in _REGISTRY:
        return _REGISTRY[path].from_args(args)  # type: ignore[attr-defined]
    import os

    if os.path.isfile(path) and os.access(path, os.X_OK):
        from ..native.process import ManagedApp

        return ManagedApp([path, *args], environment)
    raise ValueError(
        f"unknown app model {path!r}: neither a built-in model "
        f"({sorted(_REGISTRY)}) nor an executable file"
    )


def parse_kv_args(args: list[str], known: set[str] | None = None) -> dict[str, str]:
    """Parse ``--key value`` / ``--key=value`` model args.  When ``known``
    is given, unknown keys are rejected (typos must not silently fall back
    to defaults)."""
    out: dict[str, str] = {}
    i = 0
    while i < len(args):
        a = args[i]
        if not a.startswith("--"):
            raise ValueError(f"model args must be --key value pairs, got {a!r}")
        if "=" in a:
            k, _, v = a[2:].partition("=")
            out[k] = v
            i += 1
        else:
            if i + 1 >= len(args):
                raise ValueError(f"missing value for model arg {a!r}")
            out[a[2:]] = args[i + 1]
            i += 2
    if known is not None:
        unknown = set(out) - known
        if unknown:
            raise ValueError(
                f"unknown model args {sorted('--' + k for k in unknown)} "
                f"(known: {sorted('--' + k for k in known)})"
            )
    return out
