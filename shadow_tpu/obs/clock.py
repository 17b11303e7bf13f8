"""The host-phase clock: one timing law for both drivers.

A driver's host work is cut into named **phases**; every phase is a span
of ONE clock::

    with clock.span("egress_read", count):
        ...

A span takes one ``perf_counter`` pair and, while a profiler session is
open, enters one ``jax.profiler.TraceAnnotation("<prefix>/<phase>")``
(otherwise one flag test), so the same interval sits on the profiler's
clock beside the device's operations.  On exit the duration goes, from
that one pair, to

- the cumulative ``phase_s[phase]`` (always on, as ``sync_stats`` is),
- the open **turn**'s row, when there is one,
- the owner's obs ``Recorder``, when it has one, under the phase
  vocabulary obs documents (``obs_map``; docs/observability.md).

Spans nest.  What a span books is its SELF time — its duration less what
its children cover — so the phases of a turn tile the turn exactly and
sum to its wall, and obs never counts a second twice.  A turn
(``with clock.turn():``) is itself a span, of ``turn_phase``: that phase
is the turn's residual.  A closed turn leaves one row in ``ring`` (the
last ``RING_TURNS`` turns): its index, its ``perf_counter`` bounds, the
seconds of every phase and the notes the driver set (``note`` / ``add``).

A clock built with ``journal=True`` also leaves every closed row in the
module's ``journal`` — one ``deque(maxlen=RING_TURNS)`` a clock prefix —
where a reader finds it after the clock's owner is gone (the fused
driver's: one turn a run, and a benchmark reader sees no engine).  Such
a row ends with ``owner``, a process-wide serial the clock took at
construction, which tells one engine's runs from another's.  Under a
profiler session such a clock also hands the closed row to the trace, as
the stats of an empty ``<prefix>/row`` span.

No lock (a driver's loop is one thread), no option, no environment
variable: the clock is on in every run.  It runs cold, once every few
milliseconds, between a driver's device calls, so a span is ONE object
per phase, used again every time (a phase never nests inside itself),
and its exit books everything in one function (PERF.md §6, PR 37: what a
span costs in a loop and in a run).  This is the one module that imports
``TraceAnnotation``.
"""

from __future__ import annotations

import itertools
import time as wall_time
from collections import defaultdict, deque, namedtuple
from typing import Optional

from jax.profiler import TraceAnnotation

RING_TURNS = 8192  # rows kept: the last turns of a run

#: ``journal[prefix]``: the last ``RING_TURNS`` rows closed by the clocks
#: of that prefix built with ``journal=True``, in closing order
journal: dict = defaultdict(lambda: deque(maxlen=RING_TURNS))
_owners = itertools.count()

_perf_counter = wall_time.perf_counter


class _Span:
    """A phase's span: entered and left again and again.  After its exit
    ``t0`` and ``dur`` hold the pair it took, until it is entered next."""

    __slots__ = ("_clock", "phase", "detail", "name", "t0", "dur",
                 "_ann_name", "_col", "_sum", "_fwd", "_child_s", "_parent",
                 "_ann")

    def __init__(self, clock: "TurnClock", phase: str) -> None:
        self._clock = clock
        self.phase = phase
        self.detail = self.name = None
        self._ann_name = f"{clock.prefix}/{phase}"
        self._col = clock.phases.index(phase)
        self._sum = clock._sums.get(phase)
        self._fwd = clock._obs_map.get(phase)
        self._parent = self._ann = None

    def __enter__(self) -> "_Span":
        c = self._clock
        self._child_s = 0.0
        self._parent = c._open
        c._open = self
        if c.annotating():
            self._ann = ann = c.annotate(self._ann_name)
            ann.__enter__()
        self.t0 = _perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _perf_counter()
        self._close(t1)

    def _close(self, t1: float) -> None:
        """The one emission point: totals, the turn's row, obs."""
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        c = self._clock
        dur = self.dur = t1 - self.t0
        c._open = parent = self._parent
        if parent is not None:
            parent._child_s += dur
        self_s = dur - self._child_s
        tot = c.phase_s
        tot[self.phase] += self_s
        if self._sum is not None:
            key, members = self._sum
            acc = 0.0
            for m in members:
                acc += tot[m]
            c._stats[key] = acc
        t = c._turn
        if t is not None:
            t.secs[self._col] += self_s
        obs = c._owner.obs
        if obs is not None and self._fwd is not None:
            obs_phase, obs_name, key = self._fwd
            detail = self.detail
            if key is None or detail is None:
                obs.record(obs_phase, self.name or obs_name, self.t0, self_s)
            elif key != "rows" or detail:
                obs.record(obs_phase, self.name or obs_name, self.t0, self_s,
                           **{key: detail})


class _Turn(_Span):
    __slots__ = ("secs", "notes")

    def __enter__(self) -> "_Turn":
        c = self._clock
        if c._turn is not None:
            raise RuntimeError("a turn is already open on this clock")
        self.secs = [0.0] * len(c.phases)
        self.notes = [0] * len(c.notes)
        c._turn = self
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        t1 = _perf_counter()
        self._close(t1)  # books the turn's own residual into its row
        c = self._clock
        c._turn = None
        row = c.Row(c.turns, self.t0, t1, *self.secs, *self.notes, *c._tail)
        c.ring.append(row)
        if c._tail:
            journal[c.prefix].append(row)
            if c.annotating():
                # the row itself on the profiler's clock, as the stats of
                # an empty ``<prefix>/row`` span: what a trace's reader
                # prints beside the gaps (scripts/trace_idle.py)
                with c.annotate(f"{c.prefix}/row", **row._asdict()):
                    pass
        c.turns += 1


class TurnClock:
    """``owner`` is the engine: its ``obs`` attribute is read at every
    span's close (engines are handed their Recorder after construction).

    ``phases``: the phase names, one column each.  ``notes``: the names
    of the per-turn values the driver sets.  ``turn_phase``: the phase a
    turn's self time books to (None: this clock opens no turn).
    ``obs_map``: ``{phase: (obs_phase, obs_name, detail_key)}`` — the ONE
    place that says under which documented obs phase a span is recorded
    and what its ``detail`` is called there; a phase not in the map is
    not forwarded, and a span that carried no rows (``detail_key ==
    "rows"``, detail 0) is not either (no tracer-capacity burn).
    ``totals``: ``(stats, {key: (phase, ...)})`` keeps ``stats[key]`` the
    sum of those phases' cumulative seconds — the names accepted readers
    divide by (``device_sync_s``, ``syscall_service_s``).  ``journal``:
    every closed row goes to the module's ``journal[prefix]`` too, with
    the clock's ``owner`` serial as its last field."""

    def __init__(
        self,
        owner,
        prefix: str,
        phases: tuple,
        notes: tuple = (),
        turn_phase: Optional[str] = None,
        obs_map: Optional[dict] = None,
        totals: Optional[tuple] = None,
        journal: bool = False,
    ) -> None:
        self._owner = owner
        self.prefix = prefix
        self.phases = tuple(phases)
        self.notes = tuple(notes)
        self._note_col = {n: i for i, n in enumerate(self.notes)}
        self._obs_map = dict(obs_map or {})
        self.phase_s: dict = {p: 0.0 for p in self.phases}
        self._stats, sums = totals if totals is not None else (None, {})
        # phase -> (stats key, the phases it sums)
        self._sums = {
            p: (key, members) for key, members in sums.items()
            for p in members
        }
        # what a span enters while a profiler session is open; a test
        # puts a stub here with ``use_annotator``
        self.annotate = TraceAnnotation
        self.annotating = TraceAnnotation.is_enabled
        # a journal row's last field: which clock (so which engine) left it
        self._tail = (next(_owners),) if journal else ()
        self.Row = namedtuple(
            "TurnRow", ("turn", "t_start", "t_end") + self.phases + self.notes
            + (("owner",) if journal else ())
        )
        self.ring: deque = deque(maxlen=RING_TURNS)
        self.turns = 0  # turns closed so far: the next turn's index
        self._open: Optional[_Span] = None
        self._turn: Optional[_Turn] = None
        self._spans = {p: _Span(self, p) for p in self.phases}
        self._turn_span = (
            _Turn(self, turn_phase) if turn_phase is not None else None
        )

    def use_annotator(self, annotate) -> None:
        """Hand every span's name to ``annotate`` (a callable returning a
        context manager), session or none: what a test reads."""
        self.annotate = annotate
        self.annotating = lambda: True

    # -- spans -------------------------------------------------------------

    def span(self, phase: str, detail=None, name: Optional[str] = None):
        """THE span of ``phase`` (one object, entered again each time).
        ``detail`` is the one value obs shows with it (rows, window end:
        ``obs_map`` names it) and may be set on the span inside the
        block, where it is only known there; ``name`` overrides the obs
        span's name."""
        sp = self._spans[phase]
        sp.detail = detail
        sp.name = name
        return sp

    def turn(self):
        """Open a turn: the span of ``turn_phase``, which leaves a row."""
        return self._turn_span

    def note(self, name: str, value) -> None:
        """Set the open turn's note ``name`` (nothing outside a turn)."""
        t = self._turn
        if t is not None:
            t.notes[self._note_col[name]] = value

    def add(self, name: str, value) -> None:
        """Add to the open turn's note ``name`` (nothing outside a turn)."""
        t = self._turn
        if t is not None:
            t.notes[self._note_col[name]] += value
