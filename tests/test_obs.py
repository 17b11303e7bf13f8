"""Observability subsystem (shadow_tpu/obs/, docs/observability.md).

Four contracts under test:

1. **Golden perf-log line formats** — the docstring promise that
   ``[window-agg]`` / ``[host-exec-agg]`` / ``[hybrid-agg]`` lines are
   fork-parseable is pinned here character for character, and every
   emission rides ONE locked ``emit()`` (whole lines, never interleaved,
   worker-process lines forwarded to the parent sink).
2. **Tracer/metrics correctness** — Chrome-trace export shape, METRICS
   report schema, and the span-sum ↔ ``phase_wall_s`` cross-check (both
   sides are fed from the same clock pair, so they agree exactly).
3. **Determinism with obs fully enabled** — run-twice shadow logs are
   bit-identical on the cpu, cpu_mp (workers 2), and hybrid backends
   with tracing + metrics + perf logging all on.
4. **Zero overhead when disabled** — engines default to ``obs=None``
   and no obs module is touched.
"""

import io
import json
import subprocess
import threading
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.run_control import (
    BufferedPerfLog,
    PerfLog,
    RunControl,
)
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import MetricsRegistry, Recorder, Tracer

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"

SYNC_STATS = {
    "device_turns": 3,
    "device_sync_s": 0.25,
    "syscall_service_s": 0.125,
    "scalar_reads": 3,
    "h2d_copies": 4,
    "inject_blocks": 1,
    "inject_rows": 7,
    "inject_bytes": 57568,
    "egress_head_reads": 2,
    "egress_reads": 1,
    "egress_rows": 139,
    "egress_bytes": 18816,
}


# ---------------------------------------------------------------------------
# 1. golden perf-log formats + the locked emit path
# ---------------------------------------------------------------------------


class TestPerfLogGoldenFormats:
    def test_window_agg_format(self):
        out = io.StringIO()
        PerfLog(out=out).window_agg(3, 1000, 2000, 1500)
        assert out.getvalue() == (
            "[window-agg] active_hosts_in_window=3 "
            "window_start_ns=1000 window_end_ns=2000 next_event_ns=1500\n"
        )

    def test_host_exec_agg_format(self):
        out = io.StringIO()
        pl = PerfLog(out=out)
        pl.HOST_EXEC_LOG_EVERY = 2  # instance override: emit on call 2
        pl.host_exec("alpha", 10, 500)
        assert out.getvalue() == ""  # below the every-N threshold
        pl.host_exec("beta", 30, 700)
        assert out.getvalue() == (
            "[host-exec-agg] calls=2 total_ns=40 last_ns=30 "
            "host=beta window_end_abs_ns=700\n"
        )

    def test_hybrid_agg_format(self):
        out = io.StringIO()
        PerfLog(out=out).hybrid_agg("device", 102000000, SYNC_STATS)
        assert out.getvalue() == (
            "[hybrid-agg] kind=device window_end_ns=102000000 "
            "device_turns=3 device_sync_ns=250000000 "
            "syscall_service_ns=125000000 scalar_reads=3 h2d_copies=4 "
            "inject_blocks=1 inject_rows=7 inject_bytes=57568 "
            "egress_head_reads=2 egress_reads=1 egress_rows=139 "
            "egress_bytes=18816\n"
        )

    def test_emit_is_atomic_under_threads(self):
        # the satellite bug: window_agg/hybrid_agg used to print without
        # the lock, so concurrent emitters could interleave fragments.
        # Hammer emit from threads and require every line intact.
        out = io.StringIO()
        pl = PerfLog(out=out)

        def hammer(tag):
            for i in range(200):
                pl.window_agg(tag, i, i + 1, i + 2)

        threads = [
            threading.Thread(target=hammer, args=(t,)) for t in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lines = out.getvalue().splitlines()
        assert len(lines) == 800
        for line in lines:
            assert line.startswith("[window-agg] active_hosts_in_window=")
            assert line.count("window_start_ns=") == 1

    def test_buffered_perf_log_forwards_through_emit_many(self):
        # the worker side buffers; the parent's locked sink prints —
        # exactly the pipe-forwarding round trip, minus the pipe
        wpl = BufferedPerfLog()
        wpl.window_agg(1, 0, 100, 50)
        wpl.hybrid_agg("host", 100, SYNC_STATS)
        lines = wpl.drain()
        assert len(lines) == 2 and wpl.drain() == []
        out = io.StringIO()
        PerfLog(out=out).emit_many(lines)
        got = out.getvalue().splitlines()
        assert got[0] == PerfLog.format_window_agg(1, 0, 100, 50)
        assert got[1] == PerfLog.format_hybrid_agg("host", 100, SYNC_STATS)


# ---------------------------------------------------------------------------
# 2. tracer / metrics / recorder units
# ---------------------------------------------------------------------------


class TestTracer:
    def test_export_shape(self, tmp_path):
        tr = Tracer()
        tr.complete("w", "window_compute", tr.t0, 0.002, {"we": 5})
        tr.instant("mark", "mark")
        doc = json.loads(tr.export(tmp_path / "t.json").read_text())
        assert doc["displayTimeUnit"] == "ms"
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(spans) == 1
        ev = spans[0]
        assert ev["name"] == "w" and ev["cat"] == "window_compute"
        assert ev["pid"] == 1 and isinstance(ev["tid"], int)
        assert ev["dur"] == pytest.approx(2000.0)
        assert ev["args"] == {"we": 5}
        # thread-name metadata rows for Perfetto
        assert any(e.get("ph") == "M" for e in doc["traceEvents"])

    def test_capacity_bound(self):
        tr = Tracer(capacity=3)
        for i in range(5):
            tr.complete(f"s{i}", "c", tr.t0, 0.001)
        assert tr.span_count() == 3 and tr.dropped == 2

    def test_disable_toggle(self):
        tr = Tracer()
        tr.enabled = False
        tr.complete("s", "c", tr.t0, 0.001)
        assert tr.span_count() == 0


class TestMetricsRegistry:
    def test_counters_gauges_histograms(self):
        m = MetricsRegistry(run_id="t")
        m.count("windows")
        m.count("windows", 2)
        m.gauge("workers", 4)
        for v in (1, 2, 3, 4, 100):
            m.observe("active", v)
        rep = m.report()
        assert rep["counters"] == {"windows": 3}
        assert rep["gauges"] == {"workers": 4}
        h = rep["histograms"]["active"]
        assert h["count"] == 5 and h["min"] == 1 and h["max"] == 100
        assert h["mean"] == pytest.approx(22.0)
        assert h["p50"] == 3

    def test_phase_walls_and_report_schema(self):
        m = MetricsRegistry(run_id="t")
        m.phase_add("device_turn", 0.5)
        m.phase_add("device_turn", 0.25)
        m.phase_add("egress", 0.125)
        rep = m.report(extra={"backend": "tpu"})
        assert rep["phase_wall_s"] == {
            "device_turn": 0.75, "egress": 0.125,
        }
        assert rep["phases"]["device_turn"]["spans"] == 2
        assert rep["phase_wall_total_s"] == pytest.approx(0.875)
        assert rep["backend"] == "tpu"
        assert rep["schema"] == 1

    def test_timer_observes(self):
        m = MetricsRegistry(run_id="t")
        with m.timer("block"):
            pass
        assert m.report()["histograms"]["block"]["count"] == 1

    def test_jsonl_stream(self, tmp_path):
        m = MetricsRegistry(run_id="t", jsonl_path=tmp_path / "m.jsonl")
        m.stream({"ev": "mark", "name": "x"})
        m.close()
        lines = (tmp_path / "m.jsonl").read_text().splitlines()
        assert [json.loads(l)["ev"] for l in lines] == ["mark"]


class TestRecorder:
    def test_phase_span_feeds_metrics_and_trace(self, tmp_path):
        rec = Recorder(run_id="t", out_dir=tmp_path, trace=True)
        with rec.phase("window_compute", window_end=7):
            pass
        rec.record("egress", None, rec.tracer.t0, 0.5, rows=3)
        fin = rec.finalize(extra={"backend": "cpu"})
        rep = json.loads(Path(fin["metrics_path"]).read_text())
        assert set(rep["phase_wall_s"]) == {"window_compute", "egress"}
        assert rep["phase_wall_s"]["egress"] == pytest.approx(0.5)
        doc = json.loads(Path(fin["trace_path"]).read_text())
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        # the cross-check: per-phase span sums equal the report totals
        summed = {}
        for e in spans:
            summed[e["cat"]] = summed.get(e["cat"], 0.0) + e["dur"] / 1e6
        for phase, wall in rep["phase_wall_s"].items():
            assert summed[phase] == pytest.approx(wall, abs=1e-9)
        # finalize is idempotent
        assert rec.finalize() is fin

    def test_engines_default_obs_none(self):
        # the zero-overhead contract: nothing enables obs implicitly
        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        cfg = _ping_cfg("/tmp/obs-none", obs="")
        assert CpuEngine(cfg).obs is None
        assert MpCpuEngine(cfg, workers=2).obs is None
        sim = Simulation(cfg)
        assert sim.obs is None  # set per run(); obs_* all default off


# ---------------------------------------------------------------------------
# 3. run-twice determinism with obs fully enabled
# ---------------------------------------------------------------------------

OBS_ALL = (
    "obs_metrics: true, obs_trace: true, obs_jsonl: true, "
    "perf_logging: true"
)


def _ping_cfg(data_dir, obs: str = OBS_ALL, backend: str = "cpu",
              workers: int = 1) -> ConfigOptions:
    extra = f", {obs}" if obs else ""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 7, data_directory: {data_dir},
           heartbeat_interval: null}}
experimental: {{network_backend: {backend}{extra}}}
hosts:
  a: {{processes: [{{path: ping, args: --peer b --count 5 --interval 100ms}}]}}
  b: {{processes: [{{path: ping}}]}}
  c: {{processes: [{{path: ping, args: --peer d --count 5 --interval 100ms}}]}}
  d: {{processes: [{{path: ping}}]}}
""")


def _hybrid_cfg(data_dir) -> ConfigOptions:
    mesh = "\n".join(f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
""" for i in range(4))
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 21, data_directory: {data_dir},
           heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu, {OBS_ALL}}}
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


class TestObsDeterminism:
    def test_cpu_run_twice_byte_identical(self, tmp_path):
        results = []
        for tag in ("r1", "r2"):
            sim = Simulation(_ping_cfg(tmp_path / tag))
            results.append(sim.run(write_data=False))
        r1, r2 = results
        assert r1.log_tuples() == r2.log_tuples()
        assert r1.counters == r2.counters

    def test_cpu_mp_run_twice_byte_identical(self, tmp_path):
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        logs = []
        for tag in ("r1", "r2"):
            eng = MpCpuEngine(_ping_cfg(tmp_path / tag), workers=2)
            eng.obs = Recorder(run_id=tag, trace=True)
            eng.perf_log = PerfLog(out=io.StringIO())
            logs.append(eng.run())
        assert logs[0].log_tuples() == logs[1].log_tuples()
        assert logs[0].counters == logs[1].counters

    def test_obs_on_equals_obs_off(self, tmp_path):
        # obs must never feed back into the simulation: the obs-on log
        # diffs EQUAL against a plain run of the same config
        on = Simulation(_ping_cfg(tmp_path / "on")).run(write_data=False)
        off = Simulation(
            _ping_cfg(tmp_path / "off", obs="")
        ).run(write_data=False)
        assert on.log_tuples() == off.log_tuples()
        assert on.counters == off.counters


@pytest.mark.hybrid
class TestObsHybrid:
    @pytest.fixture(scope="class", autouse=True)
    def native_build(self):
        subprocess.run(
            ["make", "-C", str(REPO / "native")],
            check=True, capture_output=True,
        )

    def test_hybrid_run_twice_identical_with_artifacts(self, tmp_path):
        runs = []
        for tag in ("r1", "r2"):
            sim = Simulation(_hybrid_cfg(tmp_path / tag))
            runs.append((sim.run(), sim))
        (r1, s1), (r2, s2) = runs
        assert r1.log_tuples() == r2.log_tuples()
        assert r1.counters == r2.counters
        # the acceptance cross-check: the trace's device-turn, injection,
        # egress, and syscall-service span sums match the METRICS report
        fin = s1.obs.finalized
        rep = json.loads(Path(fin["metrics_path"]).read_text())
        assert {"device_turn", "injection", "egress",
                "syscall_service"} <= set(rep["phase_wall_s"])
        assert "hybrid_sync" in rep
        doc = json.loads(Path(fin["trace_path"]).read_text())
        summed: dict[str, float] = {}
        for e in doc["traceEvents"]:
            if e.get("ph") == "X":
                summed[e["cat"]] = summed.get(e["cat"], 0.0) + e["dur"] / 1e6
        for phase, wall in rep["phase_wall_s"].items():
            assert summed[phase] == pytest.approx(wall, abs=1e-6), phase


# ---------------------------------------------------------------------------
# 4. worker perf-line forwarding (cpu_mp) — end to end over real pipes
# ---------------------------------------------------------------------------


class TestWorkerPerfForwarding:
    def test_mp_cpu_forwards_host_exec_lines(self, tmp_path):
        # 1ms ping cadence => ~1000 rounds; each worker owns 2 of 4
        # hosts, so its host_exec call count crosses the 1000-line
        # threshold and at least one [host-exec-agg] line must ride the
        # pipe to the parent sink
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        cfg = ConfigOptions.from_yaml(f"""
general: {{stop_time: 600ms, seed: 5, data_directory: {tmp_path / 'd'},
           heartbeat_interval: null}}
experimental: {{perf_logging: true}}
hosts:
  a: {{processes: [{{path: ping, args: --peer b --count 550 --interval 1ms}}]}}
  b: {{processes: [{{path: ping}}]}}
  c: {{processes: [{{path: ping, args: --peer d --count 550 --interval 1ms}}]}}
  d: {{processes: [{{path: ping}}]}}
""")
        eng = MpCpuEngine(cfg, workers=2)
        out = io.StringIO()
        eng.perf_log = PerfLog(out=out)
        eng.run()
        lines = out.getvalue().splitlines()
        agg = [l for l in lines if l.startswith("[host-exec-agg]")]
        assert agg, "no worker perf lines were forwarded to the parent"
        for line in agg:
            assert " host=" in line and " window_end_abs_ns=" in line


# ---------------------------------------------------------------------------
# run-control stats / trace verbs
# ---------------------------------------------------------------------------


class TestRunControlObsVerbs:
    def test_stats_without_obs_reports_disabled(self):
        out = io.StringIO()
        rc = RunControl(out=out)
        rc._apply("stats")
        assert "obs is not enabled" in out.getvalue()

    def test_stats_prints_snapshot(self):
        out = io.StringIO()
        rc = RunControl(out=out)
        rec = Recorder(run_id="t")
        rec.metrics.count("windows", 3)
        rec.metrics.phase_add("window_compute", 0.5)
        rc.set_obs(rec)
        rc._apply("stats")
        text = out.getvalue()
        assert "windows=3" in text and "window_compute" in text

    def test_trace_status_toggle_and_dump(self, tmp_path):
        out = io.StringIO()
        rc = RunControl(out=out)
        rec = Recorder(run_id="t", out_dir=tmp_path, trace=True)
        with rec.phase("window_compute"):
            pass
        rc.set_obs(rec)
        rc._apply("trace")
        assert "1 span(s) recorded" in out.getvalue()
        rc._apply("trace off")
        assert not rec.tracer.enabled
        rc._apply("trace on")
        assert rec.tracer.enabled
        path = tmp_path / "dump.json"
        rc._apply(f"trace dump {path}")
        assert "trace written" in out.getvalue()
        assert json.loads(path.read_text())["traceEvents"]

    def test_trace_without_tracer_reports_disabled(self):
        out = io.StringIO()
        rc = RunControl(out=out)
        rc.set_obs(Recorder(run_id="t"))  # metrics only
        rc._apply("trace")
        assert "tracing is not enabled" in out.getvalue()

    def test_stats_verb_live_at_pause(self, tmp_path):
        # scripted console: pause, ask for stats, resume — the verb
        # answers from the live recorder mid-run
        out = io.StringIO()
        rc = RunControl(out=out, poll_interval=0.01, max_wait=10)
        rc.feed("p", "stats", "c")
        sim = Simulation(_ping_cfg(tmp_path / "d"), run_control=rc)
        sim.run(write_data=False)
        assert "[run-control] stats:" in out.getvalue()
        assert "phase walls:" in out.getvalue()


# ---------------------------------------------------------------------------
# CLI flags
# ---------------------------------------------------------------------------


class TestCliFlags:
    def test_obs_flags_map_to_overrides(self):
        from shadow_tpu.__main__ import build_parser, parse_overrides

        ns = build_parser().parse_args(
            ["cfg.yaml", "--obs-metrics", "--obs-trace"]
        )
        assert ns.obs_metrics and ns.obs_trace
        # parse_overrides only carries dotted keys; the main() shim adds
        # the experimental.* overrides — mirror it here
        overrides = parse_overrides(ns)
        assert "experimental.obs_metrics" not in overrides  # added by main
