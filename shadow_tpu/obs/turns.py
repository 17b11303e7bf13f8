"""Device-turn ledger: causal turn accounting + fusion-headroom evidence.

PR 9 measured *how long* the hybrid path's blocking device turns take
(``device_turn`` = 92.8% of wall on ``managed_relay_chains_large`` on
CPU-JAX; the share on the attached chip is not measured); this module
records *why each turn exists* and *how many
consecutive windows could legally have been fused into one dispatch* —
the instrument ROADMAP open item 1 (k-window device free-run,
speculative pipelining) designs against, the same way PR 10's
burst-window histogram instruments item 3.

One :class:`TurnLedger` per run (owned by the obs
:class:`~shadow_tpu.obs.recorder.Recorder`, slot pattern: ``None`` = off
= zero calls).  A **row** is one blocking device dispatch on the device
backends — hybrid ``hybrid_fn`` call, tpu step-driver round, or the tpu
fused driver's whole free-run — and one window round on the CPU oracle,
where the "device" is hypothetical and the ledger answers *what a
device run of this config could legally have fused*.

The **turn-cause classification** — one primary cause per row, decided in
priority order ``fault_swap`` > ``egress_drain`` > ``injection`` >
``host_window`` > ``snapshot``/``free_run``:

- ``fault_swap``   — first dispatch against a freshly swapped fault
  table (epoch-segmented tpu runs; CPU windows where the fault runtime
  installed a snapshot);
- ``egress_drain`` — mid-window resumption after the device paused on
  low egress-buffer headroom (hybrid only; always empty-injection);
- ``injection``    — the dispatch carried a non-empty injection block
  (managed-host sends staged since the previous turn; on the CPU oracle:
  the window staged >= 1 managed, non-loopback, surviving send);
- ``host_window``  — a managed host participates in the turn's completed
  window (the conservative clamp forces the device to return there);
- ``snapshot``     — a run-control snapshot epoch: the pausable tpu step
  driver dispatches one device call per round exactly so the console can
  pause/inspect at every boundary;
- ``free_run``     — nothing forced the dispatch to block: the device
  free-ran to drain/stop with no managed participation (the tpu fused
  driver's whole run is one such row — the comparison baseline).

The **conservation law** ``turns == sum(cause_counts.values())`` holds
by construction and is asserted on every exported artifact
(``make turns-smoke``).

The **fusable-run accounting** is the headroom instrument.  A row is
*fusable* iff its injection block was **provably empty** — nothing from
the host side had to enter the device before the dispatch ran.  The
conservative window law's only hard dependency chain is
``device(W) -> host(W) -> device(W+1)`` *through the injection*
(docs/hybrid.md): a dispatch whose injection is empty could have been
absorbed into its predecessor's free-run by a law able to prove that
emptiness — item 1(a) extended by the provably-empty-injection
condition of item 1(b), and every window such a dispatch covers has no
managed participation the device had to stop for.  Maximal runs of
consecutive fusable rows accumulate into a log2 run-length histogram
plus deterministic percentiles; an injecting turn closes the current
run.  Run lengths count the rows' ``windows`` (1 per dispatch on
hybrid/step, the measured free-run length on the fused driver), so the
CPU oracle's histogram reads directly as *the legal free-run length
distribution of this scenario* — the dispatch-collapse item 1 would
realize.

Two headroom estimates close the loop (``summary()`` keys):

- ``kfusion_headroom`` = turns / (turns - fusable turns): the ceiling
  of the fusable-run collapse — every empty-injection dispatch merges
  into its predecessor;
- ``kfusion_headroom_freerun`` = turns / (turns - strict free turns):
  the narrower, provable-without-any-host-knowledge 1(a) collapse —
  only rows with NO managed participation at all (``egress_drain`` /
  ``free_run`` causes) merge.

Determinism contract: the ledger stores **integers only** (causes are
fixed strings, times are sim-ns, participants are host ids) and never
feeds a value back into the simulation, so ``TURNS_<run_id>.json`` diffs
byte-identical run-twice and bit-identical across hybrid worker counts
(tests/test_turns.py).  Rows derive exclusively from data the host side
already holds per turn — recording adds **zero host<->device
transfers** (the hybrid ``sync_stats`` transfer counts are asserted
unchanged with the ledger on).
"""

from __future__ import annotations

import json
import logging
from pathlib import Path
from typing import Optional

log = logging.getLogger(__name__)

from .netobs import HIST_BUCKETS as _NETOBS_HIST_BUCKETS
from .netobs import hist_bucket as _hist_bucket

SCHEMA_VERSION = 1

#: the turn-cause classification, in report order (docs/observability.md).
#: ``rollback`` (PR 13) marks a fused-prefix rebuild dispatch: a k-window
#: fused turn whose speculation failed validation re-ran its validated
#: prefix from the checkpoint — the dispatch is real (counted by the
#: conservation law) but covers no windows the primary row did not
#: already account for (``windows=0``)
CAUSES = (
    "host_window",
    "injection",
    "egress_drain",
    "snapshot",
    "fault_swap",
    "free_run",
    "rollback",
)

#: causes carrying NO managed participation at all — the strict 1(a)
#: free-run rows (fusable without even proving injection emptiness)
STRICT_FREE_CAUSES = ("egress_drain", "free_run")

#: log2 run-length histogram width (bucket b = runs of [2^b, 2^(b+1))
#: windows) — the netobs burst-window histogram's scheme, reused so the
#: two bucketing laws can never drift apart
RUN_HIST_BUCKETS = _NETOBS_HIST_BUCKETS

#: per-turn rows kept verbatim; past this the rows list stops growing
#: (aggregates keep counting) and ``rows_dropped`` records the loss
DEFAULT_CAPACITY = 1 << 18

#: deterministic percentile sample: the FIRST N run lengths (the same
#: bounded-sample law as obs.metrics)
SAMPLE_CAP = 65536


def run_bucket(length: int) -> int:
    """floor(log2(length)) clamped to the histogram range (length >= 1)
    — the identical law to the netobs window histogram."""
    return _hist_bucket(length)


class TurnLedger:
    """Single-threaded by ownership: every engine records turns from its
    round/window loop (the controller thread), never from workers —
    worker processes ship participant sets over the round pipes and the
    parent records.  No locks needed."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY) -> None:
        self.capacity = capacity
        # rows: [cause, t_start, t_end, windows, inject_rows,
        #        egress_rows, [participant host ids...]]
        self.rows: list[list] = []
        self.rows_dropped = 0
        self.turns = 0
        self.cause_counts: dict[str, int] = {c: 0 for c in CAUSES}
        self.host_rounds = 0
        self.inject_rows_total = 0
        self.egress_rows_total = 0
        self.empty_injection_turns = 0
        # rows with no managed participation at all (strict 1(a) rows);
        # attach_participants retro-corrects the most recent PRIMARY row
        # (egress_drain resumptions cover participation-free partial
        # windows and stay strict regardless)
        self.strict_free_turns = 0
        self._last_primary_idx: Optional[int] = None
        self._last_primary_strict = False
        # host id -> number of turns whose completed window it
        # participated in
        self.participation: dict[int, int] = {}
        # fusable-run accounting (closed runs of empty-injection rows)
        self.run_hist = [0] * RUN_HIST_BUCKETS
        self.run_count = 0
        self.run_windows_total = 0
        self.run_max = 0
        self._run_sample: list[int] = []
        self._open_run = 0
        self._finished = False
        # realized-fusion accounting (PR 13): windows_covered_total is
        # the unfused turn count the rows imply (every non-rollback row
        # counts max(windows, 1)); fused rows are dispatches that
        # covered >= 2 validated windows
        self.windows_covered_total = 0
        self.fused_turns = 0
        self.fused_windows_total = 0

    # -- recording ---------------------------------------------------------

    def turn(
        self,
        cause: str,
        t_start: int,
        t_end: int,
        windows: int = 1,
        inject_rows: int = 0,
        egress_rows: int = 0,
        participants: tuple = (),
    ) -> None:
        """Record one blocking device dispatch (or oracle window)."""
        if cause not in self.cause_counts:
            raise ValueError(f"unknown turn cause {cause!r}")
        self.turns += 1
        self.cause_counts[cause] += 1
        self.inject_rows_total += inject_rows
        self.egress_rows_total += egress_rows
        if cause != "rollback":
            # rollback rebuilds re-run windows their primary row already
            # covers: they count as turns (conservation) but neither as
            # fusable evidence nor toward the implied-unfused total
            self.windows_covered_total += max(int(windows), 1)
            if int(windows) >= 2:
                self.fused_turns += 1
                self.fused_windows_total += int(windows)
        if inject_rows == 0 and cause != "rollback":
            self.empty_injection_turns += 1
        for hid in participants:
            self.participation[int(hid)] = (
                self.participation.get(int(hid), 0) + 1
            )
        stored = len(self.rows) < self.capacity
        if stored:
            self.rows.append([
                cause, int(t_start), int(t_end), int(windows),
                int(inject_rows), int(egress_rows),
                [int(h) for h in participants],
            ])
        else:
            self.rows_dropped += 1
        if cause in STRICT_FREE_CAUSES and not participants:
            self.strict_free_turns += 1
            strict = True
        else:
            strict = False
        if cause not in ("egress_drain", "rollback"):
            # a turn's PRIMARY row (resumptions and rollback rebuilds
            # are never primary): attach_participants retro-corrects
            # this one
            self._last_primary_idx = len(self.rows) - 1 if stored else None
            self._last_primary_strict = strict
        if inject_rows == 0:
            # fusable: nothing from the host entered the device before
            # this dispatch — a fusion law proving that emptiness could
            # have absorbed it into the previous dispatch
            self._open_run += max(int(windows), 0)
        else:
            self._close_run()

    def attach_participants(self, participants) -> None:
        """Amend the most recent turn's PRIMARY row with the managed
        hosts that participated in its completed window (the
        multiprocess hybrid engine learns the set from the worker round
        replies, *after* the turn rows are recorded; egress-drain
        resumption and rollback rows cover participation-free or
        re-run windows and are never amended).  A fused turn attaches
        once per covered round: the row accumulates the sorted union.
        Participation retro-corrects the strict free-turn count; the
        fusable (empty-injection) run is unaffected — participation
        alone does not force an injection."""
        participants = tuple(int(h) for h in participants)
        if not participants:
            return
        for hid in participants:
            self.participation[hid] = self.participation.get(hid, 0) + 1
        if self._last_primary_idx is not None:
            row = self.rows[self._last_primary_idx]
            row[6] = sorted(set(row[6]) | set(participants))
        if self._last_primary_strict:
            self.strict_free_turns -= 1
            self._last_primary_strict = False

    def host_round(self) -> None:
        """A host-only window (no device dispatch) ran.  Bookkeeping
        only: if it staged sends, the NEXT dispatch's injection cause
        closes the fusable run; if not, the device free-run could have
        continued straight through it."""
        self.host_rounds += 1

    def _close_run(self) -> None:
        n = self._open_run
        if n <= 0:
            return
        self._open_run = 0
        self.run_hist[run_bucket(n)] += 1
        self.run_count += 1
        self.run_windows_total += n
        if n > self.run_max:
            self.run_max = n
        if len(self._run_sample) < SAMPLE_CAP:
            self._run_sample.append(n)

    def finish(self) -> None:
        """Close the trailing fusable run (idempotent; called by the
        Recorder at finalize, before export)."""
        if not self._finished:
            self._finished = True
            self._close_run()

    # -- read side ---------------------------------------------------------

    def fusable_percentiles(self) -> dict[str, int]:
        s = sorted(self._run_sample)  # one sort serves all quantiles

        def pct(q: float) -> int:
            if not s:
                return 0
            return s[min(int(q * len(s)), len(s) - 1)]

        return {
            "p50": pct(0.50),
            "p90": pct(0.90),
            "p99": pct(0.99),
            "max": self.run_max,
        }

    def kfusion_headroom(self) -> float:
        """Turn-collapse ceiling of the fusable-run law (ROADMAP item
        1a+1b): every empty-injection dispatch merges into its
        predecessor once injection emptiness is provable."""
        if not self.turns:
            return 1.0
        return round(
            self.turns / max(self.turns - self.empty_injection_turns, 1), 4
        )

    def kfusion_headroom_freerun(self) -> float:
        """Conservative, strict-1(a) collapse: only rows with no managed
        participation at all merge into their predecessor's dispatch."""
        if not self.turns:
            return 1.0
        return round(
            self.turns / max(self.turns - self.strict_free_turns, 1), 4
        )

    def turns_saved(self) -> int:
        """Blocking dispatches the realized fusion eliminated, NET of
        rollback rebuilds: the unfused law would have spent one dispatch
        per covered window (``windows_covered_total``); the fused run
        spent ``turns`` (rebuilds included).  0 on unfused runs."""
        return self.windows_covered_total - self.turns

    def achieved_fusion(self) -> float:
        """The realized turn collapse: implied unfused turns per actual
        dispatch — the achieved counterpart of the kfusion_headroom
        predictions (1.0 when fusion is off or ineffective)."""
        if not self.turns:
            return 1.0
        return round(self.windows_covered_total / self.turns, 4)

    def summary(self) -> dict:
        """Aggregates only (live-safe: includes the open run without
        closing it) — what the ``turns`` verb reads."""
        pct = self.fusable_percentiles()
        return {
            "turns": self.turns,
            "cause_counts": dict(self.cause_counts),
            "host_rounds": self.host_rounds,
            "inject_rows_total": self.inject_rows_total,
            "egress_rows_total": self.egress_rows_total,
            "empty_injection_turns": self.empty_injection_turns,
            "strict_free_turns": self.strict_free_turns,
            "fusable_runs": self.run_count + (1 if self._open_run else 0),
            "fusable_windows_total": (
                self.run_windows_total + self._open_run
            ),
            "fusable_run_p50": pct["p50"],
            "fusable_run_p90": pct["p90"],
            "fusable_run_p99": pct["p99"],
            "fusable_run_max": max(self.run_max, self._open_run),
            "kfusion_headroom": self.kfusion_headroom(),
            "kfusion_headroom_freerun": self.kfusion_headroom_freerun(),
            "fused_turns": self.fused_turns,
            "fused_windows_total": self.fused_windows_total,
            "implied_unfused_turns": self.windows_covered_total,
            "turns_saved": self.turns_saved(),
            "achieved_fusion": self.achieved_fusion(),
            "rollbacks": self.cause_counts["rollback"],
        }

    def report(self, run_id: str) -> dict:
        """The TURNS document (schema in docs/observability.md).
        Integer-only content, deterministic ordering — run-twice
        artifacts must diff byte-identical."""
        self.finish()
        assert self.turns == sum(self.cause_counts.values()), (
            "turn-cause conservation violated"
        )
        pct = self.fusable_percentiles()
        return {
            "schema": SCHEMA_VERSION,
            "run_id": run_id,
            "turns": self.turns,
            "cause_counts": dict(self.cause_counts),
            "host_rounds": self.host_rounds,
            "inject_rows_total": self.inject_rows_total,
            "egress_rows_total": self.egress_rows_total,
            "empty_injection_turns": self.empty_injection_turns,
            "strict_free_turns": self.strict_free_turns,
            "participation": {
                str(hid): n for hid, n in sorted(self.participation.items())
            },
            "fusable": {
                "scheme": "log2-run-windows",
                "buckets": list(self.run_hist),
                "runs": self.run_count,
                "windows_total": self.run_windows_total,
                "p50": pct["p50"],
                "p90": pct["p90"],
                "p99": pct["p99"],
                "max": self.run_max,
            },
            "kfusion_headroom": self.kfusion_headroom(),
            "kfusion_headroom_freerun": self.kfusion_headroom_freerun(),
            "fused": {
                "turns": self.fused_turns,
                "windows_total": self.fused_windows_total,
                "implied_unfused_turns": self.windows_covered_total,
                "turns_saved": self.turns_saved(),
                "achieved_fusion": self.achieved_fusion(),
                "rollbacks": self.cause_counts["rollback"],
            },
            "rows_dropped": self.rows_dropped,
            "rows": [list(r) for r in self.rows],
        }

    def snapshot_lines(self) -> list[str]:
        """Human-readable snapshot (the run-control ``turns`` verb)."""
        s = self.summary()
        lines = [
            f"turns: {s['turns']} "
            + " ".join(
                f"{c}={s['cause_counts'][c]}"
                for c in CAUSES
                if s["cause_counts"][c]
            ),
            f"host_rounds={s['host_rounds']} "
            f"inject_rows={s['inject_rows_total']} "
            f"egress_rows={s['egress_rows_total']} "
            f"empty_injection_turns={s['empty_injection_turns']}",
            f"fusable runs: {s['fusable_runs']} covering "
            f"{s['fusable_windows_total']} window(s), "
            f"p50={s['fusable_run_p50']} p99={s['fusable_run_p99']} "
            f"max={s['fusable_run_max']}",
            f"k-fusion headroom: {s['kfusion_headroom']}x speculative "
            f"(empty injection), {s['kfusion_headroom_freerun']}x "
            "provable (free-run)",
            f"fused runs: {s['fused_turns']} dispatch(es) covering "
            f"{s['fused_windows_total']} window(s), "
            f"{s['turns_saved']} turn(s) saved, "
            f"{s['rollbacks']} rollback(s); achieved "
            f"{s['achieved_fusion']}x collapse",
        ]
        if not s["turns"]:
            return ["no device turns recorded yet"]
        return lines


def write_report(path: str | Path, report: dict) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return path


def load_report(path: str | Path) -> dict:
    return json.loads(Path(path).read_text())


def check_fusion_accounting(
    ledger: "TurnLedger", sync_stats: dict,
    warn_fraction: Optional[float] = None,
) -> None:
    """The fused-turn conservation cross-check (ISSUE 13 satellite),
    run by the hybrid engines at end of run when the ledger is on:

    1. HARD: the engine's independently-counted ``turns_saved`` must
       agree with the ledger aggregates, and ``turns`` plus that engine
       count must equal the unfused turn count recomputed from the
       cause rows themselves — the aggregate ``turns + turns_saved ==
       implied`` identity holds by construction (``turns_saved`` IS
       ``windows_covered - turns``), so the engine counter and the
       per-row recompute are the two independent sides that can
       actually catch a mis-recorded dispatch;
    2. SOFT: the achieved collapse should reach ``warn_fraction`` of the
       ledger's REMAINING free-run headroom prediction — if fusion
       silently disengages, rows revert to the unfused pattern, the
       remaining headroom climbs while achieved collapses to 1.0, and
       this warns (never fails)."""
    saved = ledger.turns_saved()
    engine_saved = sync_stats.get("turns_saved", 0)
    if engine_saved != saved:
        raise AssertionError(
            "fused-turn accounting drift: engine counted "
            f"turns_saved={engine_saved} but the ledger aggregates "
            f"imply {saved}"
        )
    if not ledger.rows_dropped:
        # recompute the implied-unfused total from the rows themselves —
        # independent of both the aggregate counters and the engine's
        # turns_saved, so a dispatch recorded with a drifted
        # windows/cause value cannot self-consistently hide
        implied_rows = sum(
            max(r[3], 1) for r in ledger.rows if r[0] != "rollback"
        )
        if ledger.turns + engine_saved != implied_rows:
            raise AssertionError(
                f"fused-turn conservation violated: turns="
                f"{ledger.turns} + engine turns_saved={engine_saved} "
                f"!= {implied_rows} unfused turns implied by the rows"
            )
    if warn_fraction:
        predicted = ledger.kfusion_headroom_freerun()
        achieved = ledger.achieved_fusion()
        if achieved < warn_fraction * predicted:
            log.warning(
                "k-window fusion underperforming: achieved %.2fx "
                "collapse vs %.2fx remaining free-run headroom "
                "(floor fraction %.2f) — check hybrid_fuse_k and the "
                "scenario's external lookahead",
                achieved, predicted, warn_fraction,
            )


def check_conservation(report: dict) -> Optional[str]:
    """Validate the conservation law on an exported artifact; returns an
    error string or None (``make turns-smoke``, tests)."""
    total = sum(report.get("cause_counts", {}).values())
    if report.get("turns") != total:
        return (
            f"turns={report.get('turns')} != sum(cause_counts)={total}"
        )
    rows = report.get("rows", [])
    if len(rows) + report.get("rows_dropped", 0) != report.get("turns"):
        return (
            f"rows({len(rows)}) + dropped({report.get('rows_dropped')}) "
            f"!= turns({report.get('turns')})"
        )
    return None
