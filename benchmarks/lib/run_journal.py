"""The window's rows of the fused driver's journal
(``shadow_tpu.obs.clock.journal["fused"]``: one row a ``TpuEngine.run``,
left by the engine's host-phase clock where a reader finds it after the
engine is gone; a row names its phases' seconds — ``state_build``,
``dispatch``, ``device_wait``, ``collect``, ``fault_swap`` on an engine
with a fault schedule, ``run`` their residual — its ``perf_counter``
bounds, the run's ``rounds`` and ``lane_iters``, the static shapes
``lanes``, ``pops_per_iter``, ``stream_pops``, ``flows``, the loop
ledger's ``loop_*`` counts and ``owner``, its engine's serial).

The ``fused_mesh`` runner makes, on ONE engine, a warm-up run, the
window's repeats and — traced — one more; then a second engine runs the
check program.  So the window's rows are those of the owner whose rows,
after its first, carry exactly ``raw["lane_iters"]`` and ``raw["rounds"]``
in order; the traced repeat, when there is one, is the row after them.
Anything else — a repeat that raised, a program without the journal, a
runner that repeats nothing — gives ``None``, and so does every reader
built on this.

Host phases are means over the window's (untraced) repeats; ledger counts
are the window's last repeat's (they are equal in every repeat, or the
runner counts the repeat as failed).
"""

from __future__ import annotations

from collections import namedtuple
from typing import Optional, Sequence

#: the window's repeats' rows, and the traced repeat's (or None)
WindowRuns = namedtuple("WindowRuns", ("rows", "traced"))

_said = False


def fused_journal() -> Optional[Sequence]:
    """The fused clock's rows of this process, oldest first."""
    try:
        from shadow_tpu.obs import clock
    except ImportError:
        return None
    journal = getattr(clock, "journal", None)
    return list(journal["fused"]) if journal is not None else None


def match_window(rows: Sequence, raw: dict) -> Optional[WindowRuns]:
    """The rows of ``rows`` that are the window ``raw`` describes."""
    iters, rounds = raw.get("lane_iters"), raw.get("rounds")
    if not rows or not iters or not rounds or len(iters) != len(rounds):
        return None
    by_owner: dict = {}
    for r in rows:
        by_owner.setdefault(r.owner, []).append(r)
    n = len(iters)
    for mine in by_owner.values():
        window, rest = mine[1:1 + n], mine[1 + n:]
        if (len(window) == n and len(rest) <= 1
                and [r.lane_iters for r in window] == list(iters)
                and [r.rounds for r in window] == list(rounds)):
            return WindowRuns(window, rest[0] if rest else None)
    return None


def window_runs(raw: dict) -> Optional[WindowRuns]:
    rows = fused_journal()
    found = match_window(rows, raw) if rows else None
    if found is not None:
        _say_once(found, raw)
    return found


def phase_names(row) -> list:
    """A row's phase columns: between its bounds and its first note."""
    fields = row._fields
    return list(fields[fields.index("t_end") + 1:fields.index("mode")])


def _say_once(found: WindowRuns, raw: dict) -> None:
    """One earlier line of the run: the phases tile a repeat's call."""
    global _said
    if _said:
        return
    _said = True
    n = len(found.rows)
    parts = {p: 1e3 * sum(getattr(r, p) for r in found.rows) / n
             for p in phase_names(found.rows[0])}
    call = 1e3 * sum(raw.get("call_wall_s") or [0.0]) / n
    total = sum(parts.values())
    print("[bench journal] a repeat's host phases, ms (mean of "
          f"{n}): " + ", ".join(f"{p} {v:.3f}" for p, v in parts.items())
          + f"; sum {total:.3f} against the call's wall {call:.3f}"
          + (f" ({100.0 * (total - call) / call:+.3f} %)" if call else ""),
          flush=True)


def phase_ms(raw: dict, phase: str) -> Optional[float]:
    """Milliseconds of ``phase`` per repeat, over the window's repeats."""
    found = window_runs(raw)
    if found is None or not hasattr(found.rows[0], phase):
        return None
    return 1e3 * sum(getattr(r, phase) for r in found.rows) / len(found.rows)


def last_run(raw: dict):
    """The window's last repeat's row, where its program carries the loop
    ledger (every window takes an iteration, so a program that carries
    none is the one whose largest window reads 0: ``None``)."""
    found = window_runs(raw)
    if found is None or not getattr(found.rows[-1], "loop_round_iters_max",
                                    0):
        return None
    return found.rows[-1]


def share(raw: dict, count: str, *offered: str) -> Optional[float]:
    """100 x the last repeat's ``count`` over the product of its
    ``offered`` notes (``None`` where that product is 0)."""
    value = ratio(raw, count, *offered)
    return None if value is None else 100.0 * value


def ratio(raw: dict, count: str, *offered: str) -> Optional[float]:
    """The last repeat's ``count`` over the product of its ``offered``
    notes; with none offered, the count itself."""
    row = last_run(raw)
    if row is None:
        return None
    den = 1
    for name in offered:
        den *= getattr(row, name, 0)
    return getattr(row, count) / den if den else None
