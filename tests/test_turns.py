"""Device-turn ledger (shadow_tpu/obs/turns.py, docs/observability.md).

The contracts under test:

1. **Ledger unit laws** — cause conservation, the fusable-run
   (empty-injection) accounting, strict free-turn retro-correction on
   participant attachment, capacity bounding, deterministic percentiles.
2. **Byte-identical artifacts** — ``TURNS_*.json`` diffs byte-identical
   run-twice on cpu, cpu_mp (workers 2), and hybrid; the cpu_mp rows
   equal the serial engine's.
3. **Worker-count invariance** — the hybrid ledger (causes, rows,
   participants) is bit-identical at workers {1, 2, 4}.
4. **Zero new transfers** — the hybrid ``sync_stats`` transfer counts
   are unchanged with the ledger on.
5. **Zero overhead off** — with ``obs=None`` a hybrid round makes zero
   tracer/metrics/ledger calls (the slot pattern PRs 9-11 rely on).
6. **Conservation on faults** — ``turns == sum(cause_counts)`` holds on
   a faulted scenario, with ``fault_swap`` attributed.
"""

import io
import json
import subprocess
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.run_control import RunControl
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import Recorder, TurnLedger
from shadow_tpu.obs import turns as tmod

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"


# ---------------------------------------------------------------------------
# 1. ledger unit laws
# ---------------------------------------------------------------------------


class TestLedgerUnit:
    def test_conservation_and_totals(self):
        led = TurnLedger()
        led.turn("injection", 0, 10, inject_rows=3, egress_rows=2)
        led.turn("host_window", 10, 20, participants=(1, 4))
        led.turn("free_run", 20, 30)
        led.host_round()
        rep = led.report("t")
        assert rep["turns"] == 3 == sum(rep["cause_counts"].values())
        assert rep["inject_rows_total"] == 3
        assert rep["egress_rows_total"] == 2
        assert rep["host_rounds"] == 1
        assert rep["participation"] == {"1": 1, "4": 1}
        assert tmod.check_conservation(rep) is None

    def test_unknown_cause_rejected(self):
        with pytest.raises(ValueError):
            TurnLedger().turn("bogus", 0, 1)

    def test_fusable_runs_are_empty_injection_runs(self):
        led = TurnLedger()
        # run of 3 empty-injection turns, broken by an injecting turn,
        # then a run of 1
        led.turn("host_window", 0, 1)
        led.turn("host_window", 1, 2)
        led.turn("egress_drain", 2, 3)
        led.turn("injection", 3, 4, inject_rows=5)
        led.turn("free_run", 4, 5)
        led.finish()
        assert led.run_count == 2
        assert led.run_windows_total == 4
        assert sorted(led._run_sample) == [1, 3]
        assert led.run_max == 3
        s = led.summary()
        assert s["empty_injection_turns"] == 4
        assert s["fusable_run_p50"] == 3  # pct law: s[min(int(q*n), n-1)]
        assert s["fusable_run_max"] == 3
        # headroom: 5 turns, 4 empty-injection => 5/1
        assert s["kfusion_headroom"] == 5.0
        # strict: egress_drain + free_run only => 5/3
        assert s["strict_free_turns"] == 2
        assert s["kfusion_headroom_freerun"] == round(5 / 3, 4)

    def test_run_length_counts_windows(self):
        # the fused driver's one dispatch covering N windows is one run
        # of length N (its actual free-run length)
        led = TurnLedger()
        led.turn("free_run", 0, 100, windows=17)
        led.finish()
        assert led.run_windows_total == 17
        assert led.run_hist[tmod.run_bucket(17)] == 1

    def test_attach_participants_corrects_strict_count(self):
        led = TurnLedger()
        led.turn("free_run", 0, 1)
        assert led.strict_free_turns == 1
        led.attach_participants((2, 7))
        assert led.strict_free_turns == 0
        assert led.rows[-1][6] == [2, 7]
        assert led.participation == {2: 1, 7: 1}
        # the empty-injection run survives participation
        led.finish()
        assert led.run_windows_total == 1

    def test_attach_amends_primary_row_not_drain_resumptions(self):
        # a hybrid turn that paused TWICE on egress headroom records
        # [host_window, egress_drain, egress_drain]; the participants
        # belong to the turn's completed window -> the PRIMARY row, and
        # the drain rows (participation-free partial windows) stay
        # strict — no over-correction, no misattribution
        led = TurnLedger()
        led.turn("host_window", 0, 5)
        led.turn("egress_drain", 0, 5)
        led.turn("egress_drain", 0, 5)
        assert led.strict_free_turns == 2
        led.attach_participants((3,))
        assert led.strict_free_turns == 2  # drains untouched
        assert led.rows[0][6] == [3]       # primary row amended
        assert led.rows[1][6] == [] and led.rows[2][6] == []
        # primary was host_window (never strict): count unchanged, and a
        # strict primary IS corrected
        led.turn("free_run", 5, 6)
        assert led.strict_free_turns == 3
        led.attach_participants((4,))
        assert led.strict_free_turns == 2

    def test_capacity_bound(self):
        led = TurnLedger(capacity=2)
        for i in range(5):
            led.turn("snapshot", i, i + 1)
        rep = led.report("t")
        assert len(rep["rows"]) == 2 and rep["rows_dropped"] == 3
        assert rep["turns"] == 5  # aggregates keep counting
        assert tmod.check_conservation(rep) is None

    def test_check_conservation_catches_drift(self):
        led = TurnLedger()
        led.turn("free_run", 0, 1)
        rep = led.report("t")
        bad = dict(rep)
        bad["turns"] = 2
        assert tmod.check_conservation(bad) is not None

    def test_snapshot_lines(self):
        led = TurnLedger()
        assert led.snapshot_lines() == ["no device turns recorded yet"]
        led.turn("injection", 0, 1, inject_rows=2)
        lines = "\n".join(led.snapshot_lines())
        assert "injection=1" in lines and "k-fusion headroom" in lines


# ---------------------------------------------------------------------------
# 2. byte-identical artifacts: cpu + cpu_mp
# ---------------------------------------------------------------------------


def _ping_cfg(data_dir, backend: str = "cpu") -> ConfigOptions:
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 7, data_directory: {data_dir},
           heartbeat_interval: null}}
experimental: {{network_backend: {backend}, obs_turns: true}}
hosts:
  a: {{processes: [{{path: ping, args: --peer b --count 5 --interval 100ms}}]}}
  b: {{processes: [{{path: ping}}]}}
  c: {{processes: [{{path: ping, args: --peer d --count 5 --interval 100ms}}]}}
  d: {{processes: [{{path: ping}}]}}
""")


def _turns_doc(sim: Simulation) -> tuple[dict, bytes]:
    path = Path(sim.obs.finalized["turns_path"])
    raw = path.read_bytes()
    return json.loads(raw), raw


class TestTurnsDeterminism:
    def test_cpu_run_twice_byte_identical(self, tmp_path):
        raws = []
        for tag in ("r1", "r2"):
            sim = Simulation(_ping_cfg(tmp_path / tag))
            sim.run(write_data=False)
            doc, raw = _turns_doc(sim)
            raws.append(raw)
        assert raws[0] == raws[1]
        assert tmod.check_conservation(json.loads(raws[0])) is None

    def test_cpu_oracle_rows_are_free_run_baseline(self, tmp_path):
        # a pure-model config has no managed hosts: every oracle window
        # is a legal free-run, and the whole run is ONE fusable run —
        # exactly what the tpu fused driver achieves in one dispatch
        sim = Simulation(_ping_cfg(tmp_path / "d"))
        r = sim.run(write_data=False)
        doc, _ = _turns_doc(sim)
        assert doc["cause_counts"]["free_run"] == doc["turns"] == r.rounds
        assert doc["fusable"]["runs"] == 1
        assert doc["fusable"]["windows_total"] == r.rounds

    def test_cpu_mp_run_twice_and_serial_parity(self, tmp_path):
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        raws = []
        for tag in ("m1", "m2"):
            eng = MpCpuEngine(_ping_cfg(tmp_path / tag), workers=2)
            eng.obs = Recorder(
                run_id="cpu-seed7", out_dir=tmp_path / tag, turns=True
            )
            eng.run()
            fin = eng.obs.finalize()
            raws.append(Path(fin["turns_path"]).read_bytes())
        assert raws[0] == raws[1]
        sim = Simulation(_ping_cfg(tmp_path / "ser"))
        sim.run(write_data=False)
        ser, _ = _turns_doc(sim)
        mp_doc = json.loads(raws[0])
        assert mp_doc["rows"] == ser["rows"]
        assert mp_doc["cause_counts"] == ser["cause_counts"]

    def test_tpu_fused_driver_records_free_run_baseline(self, tmp_path):
        sim = Simulation(_ping_cfg(tmp_path / "t", backend="tpu"))
        r = sim.run(write_data=False)
        doc, _ = _turns_doc(sim)
        # one unforced dispatch covering the whole run
        assert doc["turns"] == 1
        assert doc["cause_counts"]["free_run"] == 1
        assert doc["rows"][0][3] == r.rounds  # windows = measured length
        assert doc["fusable"]["windows_total"] == r.rounds


# ---------------------------------------------------------------------------
# 3+4. hybrid: worker-count invariance, run-twice, transfer counts
# ---------------------------------------------------------------------------


def _hybrid_cfg(data_dir, workers: int = 2, turns: bool = True):
    mesh = "\n".join(f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
""" for i in range(4))
    extra = ", obs_turns: true" if turns else ""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 21, data_directory: {data_dir},
           heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu, hybrid_workers: {workers}{extra}}}
hosts:
  cli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.2, "9000", "3", "100"]
  srv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "3"]
{mesh}
""")


TRANSFER_KEYS = ("device_turns", "scalar_reads", "h2d_copies",
                 "inject_blocks", "inject_rows", "inject_bytes",
                 "egress_head_reads", "egress_reads", "egress_rows",
                 "egress_bytes")


@pytest.mark.hybrid
class TestTurnsHybrid:
    @pytest.fixture(scope="class", autouse=True)
    def native_build(self):
        subprocess.run(
            ["make", "-C", str(REPO / "native")],
            check=True, capture_output=True,
        )

    @pytest.fixture(scope="class")
    def w2(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("turns_w2")
        sim = Simulation(_hybrid_cfg(tmp / "d", workers=2))
        sim.run(write_data=False)
        doc, raw = _turns_doc(sim)
        return doc, raw, dict(sim.engine.sync_stats)

    def test_run_twice_byte_identical(self, tmp_path, w2):
        sim = Simulation(_hybrid_cfg(tmp_path / "d", workers=2))
        sim.run(write_data=False)
        _, raw = _turns_doc(sim)
        assert raw == w2[1]

    def test_serial_vs_mp_turn_cause_parity(self, tmp_path, w2):
        sim = Simulation(_hybrid_cfg(tmp_path / "d", workers=1))
        sim.run(write_data=False)
        doc, raw = _turns_doc(sim)
        assert raw == w2[1]  # bit-identical ledger, causes included
        assert doc["cause_counts"] == w2[0]["cause_counts"]

    @pytest.mark.slow
    def test_mp_worker4_turn_cause_parity(self, tmp_path, w2):
        sim = Simulation(_hybrid_cfg(tmp_path / "d", workers=4))
        sim.run(write_data=False)
        _, raw = _turns_doc(sim)
        assert raw == w2[1]

    def test_ledger_matches_sync_stats_and_conserves(self, w2):
        doc, _, sync = w2
        assert tmod.check_conservation(doc) is None
        assert doc["turns"] == sync["device_turns"]
        assert doc["inject_rows_total"] == sync["inject_rows"]
        assert doc["egress_rows_total"] == sync["egress_rows"]
        assert doc["cause_counts"]["host_window"] > 0
        assert doc["cause_counts"]["injection"] > 0
        assert doc["participation"]  # managed hosts participated

    def test_transfer_counts_unchanged_with_ledger_on(self, tmp_path, w2):
        # the acceptance contract: ledger rows derive from host-held
        # values — zero new host<->device transfers in instrumented runs
        sim = Simulation(_hybrid_cfg(tmp_path / "off", workers=2,
                                     turns=False))
        sim.run(write_data=False)
        off = sim.engine.sync_stats
        for key in TRANSFER_KEYS:
            assert w2[2][key] == off[key], key

    def test_trace_flow_events_link_turns_to_service_spans(self, tmp_path):
        cfg = _hybrid_cfg(tmp_path / "d", workers=1)
        cfg.experimental.obs_trace = True
        sim = Simulation(cfg)
        sim.run(write_data=False)
        doc = json.loads(
            Path(sim.obs.finalized["trace_path"]).read_text()
        )
        starts = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
        ends = [e for e in doc["traceEvents"] if e.get("ph") == "f"]
        assert starts and len(starts) == len(ends)
        assert {e["id"] for e in starts} == {e["id"] for e in ends}
        for e in starts + ends:
            assert e["cat"] == "turn_flow"
        # every flow finish binds to its enclosing device_turn slice
        assert all(e.get("bp") == "e" for e in ends)


# ---------------------------------------------------------------------------
# 5. zero overhead when disabled (the slot pattern of PRs 9-11)
# ---------------------------------------------------------------------------


@pytest.mark.hybrid
class TestZeroOverheadOff:
    @pytest.fixture(scope="class", autouse=True)
    def native_build(self):
        subprocess.run(
            ["make", "-C", str(REPO / "native")],
            check=True, capture_output=True,
        )

    def test_hybrid_round_makes_zero_obs_calls(self, tmp_path, monkeypatch):
        # with obs=None the engine must never touch the tracer, metrics
        # registry, or turn ledger — any call through these entry points
        # fails the run
        from shadow_tpu.obs.metrics import MetricsRegistry
        from shadow_tpu.obs.tracer import Tracer

        def boom(*a, **k):  # pragma: no cover - the assertion itself
            raise AssertionError("obs call with obs disabled")

        for cls, names in (
            (MetricsRegistry, ("count", "observe", "phase_add", "gauge",
                               "stream")),
            (Tracer, ("complete", "instant", "flow")),
            (TurnLedger, ("turn", "host_round", "attach_participants")),
        ):
            for name in names:
                monkeypatch.setattr(cls, name, boom)
        sim = Simulation(_hybrid_cfg(tmp_path / "d", workers=1,
                                     turns=False))
        result = sim.run(write_data=False)
        assert sim.obs is None
        assert result.rounds > 0


# ---------------------------------------------------------------------------
# 6. conservation on a faulted scenario
# ---------------------------------------------------------------------------


class TestFaultedConservation:
    def test_cpu_faulted_scenario_conserves_with_fault_swap(self, tmp_path):
        cfg = ConfigOptions.from_yaml(f"""
general: {{stop_time: 2s, seed: 13, data_directory: {tmp_path / 'd'},
           heartbeat_interval: null}}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_up "4 Mbit" host_bandwidth_down "1 Mbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.05 ]
      ]
experimental: {{network_backend: cpu, obs_turns: true}}
faults:
  events:
    - {{kind: loss, at: 500ms, source: 0, target: 0, loss: 0.3}}
hosts:
  srv: {{network_node_id: 0, processes: [{{path: tgen-server}}]}}
  cli:
    count: 3
    network_node_id: 0
    processes:
      - path: tgen-client
        args: --server srv --interval 5ms --size 1300
""")
        sim = Simulation(cfg)
        sim.run(write_data=False)
        doc, _ = _turns_doc(sim)
        assert tmod.check_conservation(doc) is None
        assert doc["cause_counts"]["fault_swap"] >= 1
        assert doc["turns"] == sum(doc["cause_counts"].values())


# ---------------------------------------------------------------------------
# run-control verbs: `turns` + the stats/netobs fold
# ---------------------------------------------------------------------------


class TestRunControlVerbs:
    def test_turns_without_ledger_reports_disabled(self):
        out = io.StringIO()
        rc = RunControl(out=out)
        rc.set_obs(Recorder(run_id="t"))  # metrics only, no ledger
        rc._apply("turns")
        assert "turn ledger is not enabled" in out.getvalue()

    def test_turns_prints_snapshot(self):
        out = io.StringIO()
        rc = RunControl(out=out)
        rec = Recorder(run_id="t", turns=True)
        rec.turns.turn("host_window", 0, 10, participants=(3,))
        rc.set_obs(rec)
        rc._apply("turns")
        text = out.getvalue()
        assert "[run-control] turns:" in text
        assert "host_window=1" in text and "k-fusion headroom" in text

    def test_turns_verb_live_at_pause(self, tmp_path):
        out = io.StringIO()
        rc = RunControl(out=out, poll_interval=0.01, max_wait=10)
        rc.feed("p", "turns", "c")
        sim = Simulation(_ping_cfg(tmp_path / "d"), run_control=rc)
        sim.run(write_data=False)
        assert "[run-control] turns:" in out.getvalue()
        assert "fusable runs" in out.getvalue()

    def test_stats_folds_net_totals(self):
        # satellite: one verb gives phase walls + network totals
        out = io.StringIO()
        rc = RunControl(out=out)
        rec = Recorder(run_id="t")
        rec.metrics.phase_add("window_compute", 0.5)
        rc.set_obs(rec)
        rc.set_netobs_sink(
            lambda host: ["net totals: sent=42 delivered=40", "drops: 2"]
        )
        rc._apply("stats")
        text = out.getvalue()
        assert "phase walls:" in text
        assert "net totals: sent=42" in text and "drops: 2" in text

    def test_stats_without_netobs_keeps_old_shape(self):
        out = io.StringIO()
        rc = RunControl(out=out)
        rec = Recorder(run_id="t")
        rec.metrics.count("windows", 3)
        rc.set_obs(rec)
        rc._apply("stats")
        assert "windows=3" in out.getvalue()
        assert "net totals" not in out.getvalue()


# ---------------------------------------------------------------------------
# CLI flag
# ---------------------------------------------------------------------------


class TestCliFlag:
    def test_obs_turns_flag_parses(self):
        from shadow_tpu.__main__ import build_parser

        ns = build_parser().parse_args(["cfg.yaml", "--obs-turns"])
        assert ns.obs_turns
