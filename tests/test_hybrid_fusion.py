"""k-window free-run fusion + double-buffered async dispatch
(backend/hybrid.py, docs/hybrid.md "k-window fusion law" — ISSUE 13).

The contracts under test:

1. **Pure scheduling change** — at the default depth cap (8, with the
   eager dispatch it brings), the event log, rounds, and workload
   counters are bit-identical to a cap of 1 (``hybrid_fuse_k=1``: one
   participating window per dispatch), and every cap in {1, 2, 8} is
   held to the CPU oracle directly.  The single intentional exception
   is the ``lane_iters`` diagnostic: a fused dispatch visits absorbed
   ext-only windows with no-op device iterations a one-window dispatch
   never ran, so the iteration *count* (not any event, log byte, or
   netobs counter) legitimately differs.
2. **Degenerate law** — ``hybrid_fuse_k=1`` is the SAME turn law and
   kernel at a cap of 1: the walk accepts its one window
   unconditionally, so no fused rows, no rollbacks, no eager dispatch,
   ``turns_saved == 0``, and (at the SHADOW_TPU_SCALE gate) the pinned
   651-turn gate-scale count.
3. **Late injection falls back** — the pingpong cadence stages sends
   whose arrivals land inside fused spans, forcing validation failures:
   rollback rebuilds and discarded eager dispatches both occur, and the
   results stay oracle-bit-identical (the blocking-path fallback).
4. **Ledger accounting** — ``turns == sum(cause_counts)`` with
   ``free_run``/``rollback`` rows present, ``turns == device_turns``,
   ``turns + turns_saved == implied_unfused``, and the covered-windows
   invariant across the cap-8 and cap-1 runs.

Worker-count invariance and oracle bit-parity with fusion ON ride the
existing suite (tests/test_hybrid_mp.py, tests/test_turns.py — fusion is
the default there); this file pins the fusion-specific laws.
"""

import os
import subprocess
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import TurnLedger
from shadow_tpu.obs import turns as tmod

pytestmark = pytest.mark.hybrid

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"

SCALE = pytest.mark.skipif(
    not os.environ.get("SHADOW_TPU_SCALE"),
    reason="scale gate: set SHADOW_TPU_SCALE=1 to run",
)


@pytest.fixture(scope="module", autouse=True)
def native_build():
    subprocess.run(
        ["make", "-C", str(REPO / "native")], check=True,
        capture_output=True,
    )


def _cfg(data_dir: Path, workers: int = 1, fuse_k: int = 8) -> ConfigOptions:
    """The test_hybrid_mp mixed scenario (managed pingpong + tcpecho
    pairs over a tgen lane mesh): pingpong's per-round request/response
    cadence stages sends whose arrivals land one window out — the
    forced late-injection workload that exercises rollback and eager-
    dispatch misses alongside clean fused spans."""
    mesh = "\n".join(
        f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
"""
        for i in range(4)
    )
    return ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 2s, seed: 21, data_directory: {data_dir}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu, hybrid_workers: {workers},
                hybrid_fuse_k: {fuse_k}, obs_turns: true}}
hosts:
  cli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.4, "9000", "4", "100"]
  srv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "4"]
  ecli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [hclient, esrv, "7000", "2", "400", "5"]
        start_time: 200ms
  esrv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [server, "7000", "1"]
{mesh}
"""
    )


def _congested_cfg(data_dir: Path, fuse_k: int = 8) -> ConfigOptions:
    """Bulk echo traffic into a 10 Mbit node queues deliveries in the
    device down-buckets, pushing their ``t_deliver`` past the fused
    window they were generated in, while the short-latency pingpong
    cadence keeps forcing rollbacks — the combination that loses
    validated-prefix deliveries if a rollback discards the unapplied
    egress rows instead of re-reading them from the rebuild."""
    bulk = "\n".join(
        f"""
  bcli{i}:
    network_node_id: 0
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [hclient, bsrv{i}, "{7000 + i}", "6", "8192", "0"]
        start_time: {100 + 40 * i}ms
  bsrv{i}:
    network_node_id: 1
    processes:
      - path: {BUILD / 'tcpecho'}
        args: [server, "{7000 + i}", "1"]
"""
        for i in range(3)
    )
    return ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 2s, seed: 7, data_directory: {data_dir}, heartbeat_interval: null}}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        node [ id 1 host_bandwidth_up "10 Mbit" host_bandwidth_down "10 Mbit" ]
        edge [ source 0 target 0 latency "100 us" ]
        edge [ source 1 target 1 latency "100 us" ]
        edge [ source 0 target 1 latency "300 us" ]
      ]
experimental: {{network_backend: tpu, hybrid_fuse_k: {fuse_k},
                obs_turns: true}}
hosts:
  acli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.2, "9000", "4", "100"]
  asrv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "4"]
{bulk}
"""
    )


def _run(cfg):
    sim = Simulation(cfg)
    result = sim.run(write_data=False)
    assert not result.process_errors, result.process_errors
    return result, sim.engine, sim.obs.turns


@pytest.fixture(scope="module")
def fused(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fusion_on")
    return _run(_cfg(tmp / "d"))


@pytest.fixture(scope="module")
def unfused(tmp_path_factory):
    """The same law at a depth cap of 1: one window per dispatch."""
    tmp = tmp_path_factory.mktemp("fusion_off")
    return _run(_cfg(tmp / "d", fuse_k=1))


@pytest.fixture(scope="module")
def congested(tmp_path_factory):
    """The congested scenario at a depth cap, run once per cap."""
    tmp = tmp_path_factory.mktemp("congested")
    runs = {}

    def at(fuse_k: int):
        if fuse_k not in runs:
            runs[fuse_k] = _run(_congested_cfg(tmp / f"k{fuse_k}", fuse_k))
        return runs[fuse_k]

    return at


@pytest.fixture(scope="module")
def congested_oracle(tmp_path_factory):
    cfg = _congested_cfg(tmp_path_factory.mktemp("congested_cpu") / "d")
    cfg.experimental.network_backend = "cpu"
    return _run(cfg)[0]


def _counters_mod_iters(r):
    # lane_iters counts device iterations: the fused schedule runs no-op
    # iterations for absorbed windows the one-window law never visited —
    # a diagnostic of work scheduling, not an observable output
    return {k: v for k, v in r.counters.items() if k != "lane_iters"}


class TestPureSchedulingChange:
    def test_fusion_engages(self, fused):
        _r, eng, _led = fused
        s = eng.sync_stats
        assert s["fused_dispatches"] > 0
        assert s["fused_windows"] > s["fused_dispatches"]
        assert s["turns_saved"] > 0
        assert s["device_turns"] < s["fused_windows"] + s["fuse_rollbacks"]

    def test_bit_parity_with_unfused_law(self, fused, unfused):
        rf, _ef, _lf = fused
        ru, _eu, _lu = unfused
        assert rf.log_tuples() == ru.log_tuples()
        assert rf.rounds == ru.rounds
        assert _counters_mod_iters(rf) == _counters_mod_iters(ru)
        assert rf.per_host_counters == ru.per_host_counters

    def test_late_injection_falls_back_to_blocking(self, fused):
        """The pingpong cadence forces mispredictions: rollback rebuilds
        and discarded eager dispatches both happen, and (per the parity
        test above) the results are unchanged — the async/fused paths
        degrade to the blocking law instead of corrupting it."""
        _r, eng, led = fused
        s = eng.sync_stats
        assert s["fuse_rollbacks"] > 0
        assert led.cause_counts["rollback"] == s["fuse_rollbacks"]
        # the eager double-buffer resolved BOTH ways at least once
        assert s["async_dispatch_hits"] > 0
        assert s["async_dispatch_misses"] > 0

    def test_run_twice_byte_identical_with_fusion_and_async(
        self, tmp_path, fused
    ):
        rf, ef, _led = fused
        r2, e2, _led2 = _run(_cfg(tmp_path / "d"))
        assert r2.log_tuples() == rf.log_tuples()
        assert r2.counters == rf.counters
        # the counts; the walls (floats, phase_s, the turn ring) differ
        assert {k: v for k, v in e2.sync_stats.items()
                if isinstance(v, int)} == \
               {k: v for k, v in ef.sync_stats.items()
                if isinstance(v, int)}


class TestRollbackEgressParity:
    def test_congested_rollback_bit_parity(self, congested):
        """Validated-prefix deliveries whose down-bucket queueing delays
        ``t_deliver`` past the last validated window end must survive a
        rollback (re-read from the rebuild's egress buffer) — without
        that, the fused law silently drops them and diverges from the
        ``hybrid_fuse_k=1`` cap under congestion."""
        rf, ef, _lf = congested(8)
        ru, eu, _lu = congested(1)
        # the scenario is only probative while it actually rolls back
        assert ef.sync_stats["fuse_rollbacks"] > 0
        assert eu.sync_stats["fuse_rollbacks"] == 0
        assert rf.log_tuples() == ru.log_tuples()
        assert rf.rounds == ru.rounds
        assert _counters_mod_iters(rf) == _counters_mod_iters(ru)
        assert rf.per_host_counters == ru.per_host_counters

    @pytest.mark.parametrize("fuse_k", [1, 2, 8])
    def test_depth_cap_equals_cpu_oracle(
        self, fuse_k, congested, congested_oracle
    ):
        """Every depth cap against ``network_backend: cpu`` itself, not
        against another cap: log, rounds, counters (less the lane
        plane's own ``lane_*`` bookkeeping, which the oracle has no
        device to count) and per-host counters."""
        r, eng, _led = congested(fuse_k)
        assert (eng.sync_stats["fuse_rollbacks"] > 0) == (fuse_k > 1)
        assert r.log_tuples() == congested_oracle.log_tuples()
        assert r.rounds == congested_oracle.rounds
        assert {
            k: v for k, v in r.counters.items() if not k.startswith("lane_")
        } == congested_oracle.counters
        assert r.per_host_counters == congested_oracle.per_host_counters


class TestOneBlockEveryPath:
    def test_eager_and_rebuild_blocks_equal_a_fresh_pack(
        self, tmp_path, monkeypatch, fused
    ):
        """The turn's block reaches the device by three routes (ISSUE
        40): the primary dispatch ships the turn's staging array, an
        ADOPTED eager dispatch shipped its own array a turn earlier, and
        a rollback's REBUILD re-ships the turn's array with the depth
        word patched.  On every dispatch of a run, a block packed afresh
        from the turn's HOST values and dispatched from the same base
        state must give the same packed read-back — scalars and egress
        head — and the same state, leaf for leaf."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from shadow_tpu.backend import lanes
        from shadow_tpu.backend.hybrid import NEVER, HybridEngine

        real = HybridEngine._dispatch_fused
        seen = {"primary": 0, "adopted": 0, "rebuild": 0}
        rolled = [0]

        def checked(self, state, fused_fn, ext, used_enc, n_staged, k_eff):
            st, lay = self.sync_stats, self._lay
            hits = st["async_dispatch_hits"]
            state2, sc, wait = real(
                self, state, fused_fn, ext, used_enc, n_staged, k_eff)
            if st["fuse_rollbacks"] > rolled[0]:
                rolled[0] = st["fuse_rollbacks"]
                kind = "rebuild"
            else:
                kind = ("adopted" if st["async_dispatch_hits"] > hits
                        else "primary")
            seen[kind] += 1
            t = np.asarray(ext, dtype=np.int64)
            hi = np.where(t >= NEVER, lanes.NEVER32, t >> 31)
            lo = np.where(t >= NEVER, lanes.NEVER32, t & lanes.MASK31)
            inj = lay.injection(self._turn_np)
            assert int(inj["valid"].sum()) == min(
                n_staged, lay.inject_batch)
            ref_state, ref_sc = fused_fn(state, jnp.array(
                lay.pack(inj, hi, lo, used_enc, k_eff)))
            assert np.array_equal(sc, np.asarray(ref_sc)), kind
            for a, b in zip(jax.tree.leaves(state2),
                            jax.tree.leaves(ref_state)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), kind
            count = int(sc[lanes.HYB_EGRESS_COUNT])
            head = lanes.hyb_egress_rows(sc, self._fuse_k)
            assert np.array_equal(
                head[:count], np.asarray(state2.egress[:count])), kind
            return state2, sc, wait

        monkeypatch.setattr(HybridEngine, "_dispatch_fused", checked)
        r, eng, _led = _run(_cfg(tmp_path / "d"))
        assert min(seen.values()) > 0, seen
        s = eng.sync_stats
        assert seen["adopted"] == s["async_dispatch_hits"]
        assert seen["rebuild"] == s["fuse_rollbacks"]
        assert sum(seen.values()) == s["device_turns"]
        assert r.log_tuples() == fused[0].log_tuples()
        assert r.counters == fused[0].counters


class TestDegenerateLaw:
    def test_fuse1_has_no_fusion_artifacts(self, unfused):
        _r, eng, led = unfused
        s = eng.sync_stats
        assert s["fused_dispatches"] == 0
        assert s["fused_windows"] == 0
        assert s["turns_saved"] == 0
        assert s["fuse_rollbacks"] == 0
        assert s["async_dispatch_hits"] == 0
        assert s["async_dispatch_misses"] == 0
        assert led.cause_counts["rollback"] == 0
        assert all(row[3] == 1 for row in led.rows)  # every row: 1 window
        assert led.turns_saved() == 0

    @pytest.mark.parametrize("fuse_k", [1, 8])
    def test_failed_dispatch_is_retried(
        self, fuse_k, tmp_path, monkeypatch, fused, unfused
    ):
        """The dispatch retry law (docs/robustness.md) at both ends of
        the cap: a device runtime error on a blocking dispatch is
        re-dispatched from the pre-turn state, and the run's results are
        those of a run that never failed."""
        from shadow_tpu.backend.tpu_engine import TpuEngine

        real = TpuEngine.make_hybrid_fns
        calls = []

        def flaky(self, k, slots):
            turn_fn, inject_fn = real(self, k, slots)

            def turn(*args):
                calls.append(1)
                if len(calls) == 1:  # the first dispatch is never eager
                    raise RuntimeError("injected device runtime error")
                return turn_fn(*args)

            return turn, inject_fn

        monkeypatch.setattr(TpuEngine, "make_hybrid_fns", flaky)
        r, eng, _led = _run(_cfg(tmp_path / "d", fuse_k=fuse_k))
        assert eng.sync_stats["dispatch_retries"] == 1
        ref = (unfused if fuse_k == 1 else fused)[0]
        assert r.log_tuples() == ref.log_tuples()
        assert r.rounds == ref.rounds
        assert r.counters == ref.counters

    def test_fused_turn_count_drops(self, fused, unfused):
        _rf, ef, _lf = fused
        _ru, eu, _lu = unfused
        assert ef.sync_stats["device_turns"] < eu.sync_stats["device_turns"]


class TestLedgerAccounting:
    def test_conservation_with_free_run_rows(self, fused):
        _r, eng, led = fused
        rep = led.report("t")
        assert tmod.check_conservation(rep) is None
        assert rep["cause_counts"]["free_run"] > 0
        assert rep["turns"] == eng.sync_stats["device_turns"]
        fus = rep["fused"]
        assert rep["turns"] + fus["turns_saved"] == (
            fus["implied_unfused_turns"]
        )
        assert fus["turns_saved"] == eng.sync_stats["turns_saved"]
        # the engine-level cross-check (run at end-of-run too) agrees
        tmod.check_fusion_accounting(led, eng.sync_stats, 0.5)

    def test_covered_windows_invariant(self, fused, unfused):
        """The fusion changes how many dispatches carry the windows,
        never which windows run: covered participating windows plus
        remaining host-only rounds is invariant across the two laws."""
        _rf, _ef, lf = fused
        _ru, _eu, lu = unfused
        assert (
            lf.windows_covered_total + lf.host_rounds
            == lu.windows_covered_total + lu.host_rounds
        )

    def test_snapshot_lines_report_fused_stats(self, fused):
        _r, _eng, led = fused
        text = "\n".join(led.snapshot_lines())
        assert "fused runs:" in text
        assert "turn(s) saved" in text and "rollback(s)" in text


class TestLedgerUnitLaws:
    def test_fused_row_accounting(self):
        led = TurnLedger()
        led.turn("injection", 0, 10, inject_rows=2)          # 1 window
        led.turn("free_run", 10, 50, windows=4)              # fused
        led.turn("rollback", 10, 50, windows=0)              # rebuild
        led.finish()
        assert led.turns == 3 == sum(led.cause_counts.values())
        assert led.windows_covered_total == 5
        assert led.fused_turns == 1
        assert led.fused_windows_total == 4
        assert led.turns_saved() == 2  # 5 implied - 3 dispatches
        assert led.achieved_fusion() == round(5 / 3, 4)
        # rollback rows are neither fusable evidence nor primary
        assert led.empty_injection_turns == 1  # the free_run row only
        s = led.summary()
        assert s["rollbacks"] == 1 and s["turns_saved"] == 2

    def test_check_fusion_accounting_detects_drift(self):
        led = TurnLedger()
        led.turn("free_run", 0, 10, windows=3)
        tmod.check_fusion_accounting(led, {"turns_saved": 2})
        with pytest.raises(AssertionError):
            tmod.check_fusion_accounting(led, {"turns_saved": 1})

    def test_fuse_knob_validation(self):
        cfg = _cfg(Path("/tmp/x"), fuse_k=0)
        with pytest.raises(Exception):
            cfg.validate()


@SCALE
class TestGateScale:
    def test_fuse1_reproduces_pr7_pinned_turns(self, tmp_path):
        """The degenerate law at the gate scale: the exact 651 blocking
        turns PR 7/PR 11 pinned for managed_relay_chains_gate at 4 sim-s
        (make turns-smoke history)."""
        from shadow_tpu.config.scenarios import managed_relay_chains_gate

        cfg = managed_relay_chains_gate(
            tmp_path / "d", hybrid_workers=2, sim_seconds=4
        )
        cfg.experimental.hybrid_fuse_k = 1
        sim = Simulation(cfg)
        r = sim.run(write_data=False)
        assert not r.process_errors
        assert sim.engine.sync_stats["device_turns"] == 651

    def test_fused_gate_meets_2x_bar(self, tmp_path):
        from shadow_tpu.config.scenarios import managed_relay_chains_gate

        cfg = managed_relay_chains_gate(
            tmp_path / "d", hybrid_workers=2, sim_seconds=4
        )
        sim = Simulation(cfg)
        r = sim.run(write_data=False)
        assert not r.process_errors
        assert sim.engine.sync_stats["device_turns"] * 2 <= 651
