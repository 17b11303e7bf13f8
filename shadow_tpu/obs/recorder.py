"""The per-run obs facade: phase spans feeding metrics AND the tracer.

A phase span is the unit of wall attribution::

    with obs.phase("device_turn", window_end=we):
        ...

On exit the measured duration lands in the metrics registry's per-phase
wall totals and — when tracing is on — as one Chrome-trace complete
event, from the *same* ``perf_counter`` pair, so the trace's summed span
wall per phase and the METRICS report's ``phase_wall_s`` agree by
construction (the acceptance cross-check in tests/test_obs.py).

The engine-facing phase vocabulary (docs/observability.md).  The two
device drivers do not call the Recorder themselves: every host phase of
theirs is a span of the host-phase clock (:mod:`.clock`), which hands the
pair it took to ``record`` under these names —

- ``window_compute``  — host-side window execution + barrier (cpu; the
  parent's collect wall on cpu_mp, which IS the workers' execution);
- ``dispatch``        — a device call until it RETURNS (tpu device and
  step mode, hybrid: the jit dispatch of the whole lane-state pytree);
- ``device_turn``     — the blocking wait for that call's result: the
  packed-scalar readback (hybrid, ``sync_stats.device_sync_s``), the
  round's ``done`` flag (step driver), ``block_until_ready`` of the whole
  fused run (device mode);
- ``state_build`` / ``collect`` — the fused driver's two host phases;
- ``injection``       — staged-send block packing + H2D dispatch
  (hybrid; the transfer itself overlaps the next device call under JAX
  async dispatch);
- ``egress``          — egress-slice D2H read (hybrid; deliveries are
  applied per validated window, in ``egress_apply``);
- ``peek``            — the fused dispatch's external-event schedule and
  its encoding (hybrid);
- ``syscall_service`` — managed hosts' syscall-plane round, barrier
  included (hybrid; on the multiprocess engine this is the collect leg
  of the round — the barrier wait that holds the workers' execution);
- ``worker_pipe``     — the pipe ship (broadcast) leg of a multiprocess
  round (cpu_mp, hybrid mp); disjoint from the collect-leg phase, so
  phase walls tile the round without double-counting;
- ``callback``        — the caller's ``on_window`` hook inside a hybrid
  turn (a sampler, the run-control console): never the engine's time;
- ``walk``            — a hybrid turn's own residual (scalar decode, the
  validation walk's Python, ledger calls, rollback bookkeeping): a
  LENGTH drawn from the turn's start, not an interval;
- ``fault_swap``      — fault-table epoch application at a window
  boundary (cpu backend).
"""

from __future__ import annotations

import time as wall_time
from pathlib import Path
from typing import Optional

from .metrics import MetricsRegistry
from .tracer import Tracer

PHASES = (
    "window_compute",
    "dispatch",
    "device_turn",
    "state_build",
    "collect",
    "injection",
    "egress",
    "egress_apply",
    "peek",
    "syscall_service",
    "worker_pipe",
    "callback",
    "walk",
    "fault_swap",
)


class _PhaseSpan:
    __slots__ = ("_rec", "phase", "name", "args", "_t0")

    def __init__(
        self, rec: "Recorder", phase: str, name: Optional[str], args: dict
    ) -> None:
        self._rec = rec
        self.phase = phase
        self.name = name or phase
        self.args = args

    def __enter__(self) -> "_PhaseSpan":
        self._t0 = wall_time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t0 = self._t0
        dur = wall_time.perf_counter() - t0
        self._rec._record(self.phase, self.name, t0, dur, self.args)


class Recorder:
    """Owns one run's metrics registry and (optionally) tracer.

    Engines carry ``self.obs: Optional[Recorder] = None`` and guard every
    hook with ``if obs is not None`` — disabled means zero overhead, the
    same contract as ``perf_log``."""

    def __init__(
        self,
        run_id: str = "run",
        out_dir: Optional[str | Path] = None,
        trace: bool = False,
        jsonl: bool = False,
        trace_capacity: Optional[int] = None,
        turns: bool = False,
    ) -> None:
        self.run_id = run_id
        self.out_dir = Path(out_dir) if out_dir is not None else None
        jsonl_path = (
            self.out_dir / f"metrics_{run_id}.jsonl"
            if (jsonl and self.out_dir is not None)
            else None
        )
        self.metrics = MetricsRegistry(run_id=run_id, jsonl_path=jsonl_path)
        self.tracer: Optional[Tracer] = None
        if trace:
            self.tracer = (
                Tracer() if trace_capacity is None else Tracer(trace_capacity)
            )
        # device-turn ledger (obs/turns.py): causal turn accounting +
        # fusion-headroom measurement; None = off = zero engine calls
        self.turns: Optional["TurnLedger"] = None
        if turns:
            from .turns import TurnLedger

            self.turns = TurnLedger()
        self.finalized: Optional[dict] = None
        # queued JSON artifacts (name -> payload), written at finalize —
        # the subsystem-report seam (sweep/report.py's SWEEP_* files ride
        # the same lifecycle as METRICS_*/TURNS_*)
        self.artifacts: dict = {}

    # -- span API ----------------------------------------------------------

    def add_artifact(self, name: str, payload: dict) -> None:
        """Queue a JSON artifact for finalize: written into ``out_dir``
        as ``<name>.json`` (deterministically serialized — sorted keys,
        fixed separators) alongside the METRICS report."""
        self.artifacts[name] = payload

    def phase(self, phase: str, name: Optional[str] = None, **args):
        return _PhaseSpan(self, phase, name, args)

    def record(
        self,
        phase: str,
        name: Optional[str],
        t0: float,
        dur_s: float,
        **args,
    ) -> None:
        """Record an already-measured span (``t0`` from
        ``wall_time.perf_counter()``): the hook for code that timed the
        block anyway (sync_stats, watchdogs) — one clock pair, no second
        measurement."""
        self._record(phase, name or phase, t0, dur_s, args)

    def _record(
        self, phase: str, name: str, t0: float, dur_s: float, args: dict
    ) -> None:
        m = self.metrics
        m.phase_add(phase, dur_s)
        if m.jsonl_path is not None:
            rec = {"ev": "span", "phase": phase, "name": name,
                   "ts_s": t0 - m._t0, "dur_s": dur_s}
            if args:
                rec["args"] = args
            m.stream(rec)
        if self.tracer is not None:
            self.tracer.complete(name, phase, t0, dur_s, args or None)

    def mark(self, name: str, **args) -> None:
        """Instant marker: trace instant event + JSONL record."""
        if self.tracer is not None:
            self.tracer.instant(name, "mark", args or None)
        self.metrics.stream({"ev": "mark", "name": name, **args})

    # -- checkpoint state (engine/checkpoint.py) ---------------------------

    def checkpoint_state(self) -> dict:
        """The resumable observability state: metrics accumulators plus
        the device-turn ledger (plain-data, picklable).  Trace spans are
        wall-clock artifacts and deliberately excluded — a resumed run's
        trace covers the resumed segment only.  The ledger is deep-copied
        so the checkpoint is a true snapshot even when the payload is
        held in memory while the live ledger keeps accumulating."""
        import copy

        return {
            "metrics": self.metrics.checkpoint_state(),
            "turns": copy.deepcopy(self.turns),
        }

    def restore_checkpoint_state(self, st: dict) -> None:
        self.metrics.restore_checkpoint_state(st.get("metrics", {}))
        if st.get("turns") is not None and self.turns is not None:
            self.turns = st["turns"]

    def reset_for_replay(self) -> None:
        """Zero the accumulators for a from-t=0 replay (serial
        escalation, checkpoint-less failover): the replay re-earns every
        count, so the abandoned prefix must not linger."""
        self.metrics.reset_accumulators()
        if self.turns is not None:
            from .turns import TurnLedger

            self.turns = TurnLedger()

    # -- finalize ----------------------------------------------------------

    def finalize(self, extra: Optional[dict] = None) -> dict:
        """Write the run artifacts (``METRICS_<run_id>.json`` and, when
        tracing, ``trace_<run_id>.json``) into ``out_dir`` and return
        ``{"report": ..., "metrics_path": ..., "trace_path": ...}``.
        Idempotent per recorder: the second call returns the first
        result."""
        if self.finalized is not None:
            return self.finalized
        out: dict = {}
        report_extra = dict(extra or {})
        if self.tracer is not None:
            report_extra.setdefault("trace_spans", self.tracer.span_count())
            report_extra.setdefault("trace_dropped", self.tracer.dropped)
        if self.turns is not None:
            # the METRICS report carries the ledger aggregates; the
            # per-turn rows live in the TURNS artifact written below
            self.turns.finish()  # close the trailing fusable run first
            report_extra.setdefault("device_turn_ledger", self.turns.summary())
        if self.out_dir is not None:
            self.out_dir.mkdir(parents=True, exist_ok=True)
            if self.tracer is not None:
                out["trace_path"] = str(
                    self.tracer.export(
                        self.out_dir / f"trace_{self.run_id}.json",
                        extra={"run_id": self.run_id},
                    )
                )
            if self.turns is not None:
                from .turns import write_report as _write_turns

                out["turns_path"] = str(
                    _write_turns(
                        self.out_dir / f"TURNS_{self.run_id}.json",
                        self.turns.report(self.run_id),
                    )
                )
            out["metrics_path"] = str(
                self.metrics.write_report(
                    self.out_dir / f"METRICS_{self.run_id}.json",
                    extra=report_extra,
                )
            )
            if self.artifacts:
                import json as _json

                paths = []
                for aname in sorted(self.artifacts):
                    p = self.out_dir / f"{aname}.json"
                    p.write_text(
                        _json.dumps(
                            self.artifacts[aname], sort_keys=True,
                            indent=2, separators=(",", ": "),
                        )
                        + "\n"
                    )
                    paths.append(str(p))
                out["artifact_paths"] = paths
        out["report"] = self.metrics.report(extra=report_extra)
        self.metrics.close()
        self.finalized = out
        return out
