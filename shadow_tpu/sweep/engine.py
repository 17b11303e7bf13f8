"""Batched sweep driver: S lane states stacked on a leading scenario
axis, run through ONE jitted vmapped kernel (``lanes.make_sweep_fn``).

Batching law (docs/sweep.md): every per-scenario quantity — the device
tables (latency/loss/rate gathers and the traced seed pair), the stop
bound, and the whole LaneState — is a traced argument, so one XLA
compile serves all S variants.  Under vmap the while_loop batching rule
advances while ANY scenario is live and per-element re-selects the old
carry for finished ones, so each scenario sees exactly its serial
trajectory (a per-scenario done mask, not a global barrier) and the
batched run is bit-identical per scenario to S serial runs.

Fault schedules batch by SEGMENTS: every variant's epoch plan is padded
to the longest plan's length with trailing zero-length no-op rows
(``FaultOverlay.segment_plan``), and the batch runs E sequential
batched calls — each against that segment's per-scenario tables and
stop bounds — through the same compiled kernel.
"""

from __future__ import annotations

import dataclasses
import time as wall_time
from typing import Optional

import jax
import jax.numpy as jnp

from .. import parallel
from ..backend import lanes
from ..backend.cpu_engine import CpuEngine, SimResult
from ..backend.tpu_engine import TpuEngine
from .variants import SweepVariant, check_congruence


class SweepEngine:
    """Runs the S variants of a sweep as one vmapped lane program.

    ``backend='tpu'`` (the sweep path proper) drives the batched lane
    kernel; ``backend='cpu'`` runs the scalar CPU oracle serially per
    variant behind the same API — the cross-backend parity arm of the
    sweep correctness law."""

    def __init__(
        self,
        variants: list[SweepVariant],
        log_capacity: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> None:
        if not variants:
            raise ValueError("sweep needs at least one variant")
        self.variants = variants
        self.backend = (
            backend
            if backend is not None
            else variants[0].cfg.experimental.network_backend
        )
        self._log_capacity = log_capacity
        self._fn = None
        self.engines: list = []
        if self.backend == "cpu":
            return
        self.engines = [
            TpuEngine(v.cfg, log_capacity=log_capacity) for v in variants
        ]
        check_congruence(self.engines)
        # has_loss normalization: one variant with loss makes the whole
        # batch trace the loss draw.  Bit-safe for loss-free scenarios —
        # draws are threefry counters keyed on the send sequence, never
        # consumed from a positional stream, so extra draws with an
        # all-pass threshold change no downstream value (the same law
        # that keeps seed parity across backends; see tpu_engine).
        any_loss = any(e.params.has_loss for e in self.engines)
        for e in self.engines:
            e.params = dataclasses.replace(e.params, has_loss=any_loss)

    @property
    def size(self) -> int:
        return len(self.variants)

    @property
    def traces(self) -> int:
        """Compile probe: how many times the batched kernel traced (the
        one-compile acceptance assertion reads this after run())."""
        return self._fn.traces if self._fn is not None else 0

    # -- plans -------------------------------------------------------------

    def _segment_plans(self):
        """Per-variant epoch plans, padded to one common length E with
        trailing zero-length no-op rows (the padded-epoch
        representation — docs/sweep.md)."""
        stop = self.engines[0].params.stop_time
        plans = []
        for eng in self.engines:
            ov = eng._fault_overlay
            plans.append(
                [(0, stop, None)]
                if ov is None
                else ov.segment_plan(stop)
            )
        depth = max(len(p) for p in plans)
        for p in plans:
            last = p[-1][2]
            while len(p) < depth:
                p.append((stop, stop, last))
        return plans, depth

    # -- running -----------------------------------------------------------

    def run(self) -> list[SimResult]:
        """Run all S scenarios; returns one SimResult per variant, in
        variant order.  ``wall_seconds`` on every result is the WHOLE
        batch's wall time (the per-scenario rate is not individually
        meaningful; scenarios_per_hour divides by S at the report
        layer)."""
        if self.backend == "cpu":
            return self._run_cpu_serial()
        engines = self.engines
        states = [eng.initial_state() for eng in engines]
        plans, depth = self._segment_plans()
        if self._fn is None:
            self._fn = engines[0].make_sweep_fn()
        fn = self._fn
        # sweep x mesh composition (docs/multichip.md): when the config
        # asks for a mesh, shard the STACKED scenario axis — whole
        # scenarios per device — instead of the (small) per-scenario host
        # axis.  Every batched argument leads with [S], so committing the
        # inputs to one NamedSharding is the entire change: the shardings
        # propagate through the same jitted vmapped kernel, keeping the
        # one-compile law (tests/test_sweep.py asserts traces == 1).
        smesh = None
        n_dev = parallel.negotiate_from_config(
            engines[0].cfg, len(engines)
        )
        if n_dev > 1:
            smesh = parallel.make_mesh(n_dev, axis=parallel.SCENARIO_AXIS)
            ssh = parallel.scenario_sharding(smesh)
        t0 = wall_time.perf_counter()
        state_b = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        if smesh is not None:
            state_b = jax.device_put(state_b, ssh)
        for seg in range(depth):
            tbs = [
                eng.sweep_tables(plans[i][seg][2])
                for i, eng in enumerate(engines)
            ]
            tb_b = jax.tree.map(lambda *xs: jnp.stack(xs), *tbs)
            ends = [plans[i][seg][1] for i in range(len(engines))]
            stop_hi = jnp.asarray([t >> 31 for t in ends], dtype=jnp.int32)
            stop_lo = jnp.asarray(
                [t & ((1 << 31) - 1) for t in ends], dtype=jnp.int32
            )
            if smesh is not None:
                tb_b, stop_hi, stop_lo = jax.device_put(
                    (tb_b, stop_hi, stop_lo), ssh
                )
                with lanes._force_unroll():
                    state_b = fn(tb_b, stop_hi, stop_lo, state_b)
            else:
                state_b = fn(tb_b, stop_hi, stop_lo, state_b)
        state_b = jax.block_until_ready(state_b)
        wall = wall_time.perf_counter() - t0
        results = []
        for i, eng in enumerate(engines):
            s_i = jax.tree.map(lambda a: a[i], state_b)
            results.append(eng.collect(s_i, wall))
        return results

    def _run_cpu_serial(self) -> list[SimResult]:
        """The scalar CPU oracle, one variant at a time — same API, no
        batching (the parity arm, not the throughput lever)."""
        t0 = wall_time.perf_counter()
        results = []
        self.engines = []
        for v in self.variants:
            eng = CpuEngine(v.cfg)
            self.engines.append(eng)
            results.append(eng.run())
        self._cpu_wall = wall_time.perf_counter() - t0
        return results
