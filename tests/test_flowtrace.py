"""Per-flow packet-lifecycle tracing (obs/flowtrace.py,
docs/observability.md).

Contracts under test:

1. **Device ↔ oracle event parity** — the canonical flowtrace event
   stream (send, token-bucket wait, queue-enter, drop-with-cause,
   retransmit, delivery; each stamped with sim-time/window/src/dst/
   seq/size) bit-identical between the TPU/lane path and the CPU oracle
   on a drop-heavy scenario, a lossy stream-flow scenario (retransmit
   coverage), and the mixed flagship mesh, on fused and step drivers.
2. **Run-twice / worker-count determinism** — byte-identical
   ``FLOWS_*.json`` on the cpu backend; the cpu_mp engine's merged
   stream equals the serial oracle at any worker count.
3. **Sampling determinism** — the device flow hash equals the Python
   hash bit-for-bit, so device and oracle select the same flows; a
   sampled run's stream is a strict subset and still bit-identical.
4. **Ring-overflow law** — a full device ring stops recording (never
   wraps), counts the excess into ``events_lost``, and the kept+lost
   total conserves against the oracle; the loss surfaces as the
   ``flow_events_lost`` metrics counter.
5. **Zero overhead when off** — engines default flowtrace-off with no
   state allocated; the LaneParams guards pin the untiered-only law.
6. **Console ``flows`` verb** — run-control answers live at a paused
   boundary; ``stats`` folds the one-line summary.
7. **Hybrid** — byte-identical run-twice FLOWS artifacts, the merged
   host/device split covers the stream, worker-count invariance, and
   ZERO new host↔device transfers (sync_stats unchanged vs off).
"""

import copy
import json
import subprocess
from pathlib import Path

import numpy as np
import pytest

from shadow_tpu.config.options import ConfigError, ConfigOptions
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import flowtrace as ftr

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"


# ---------------------------------------------------------------------------
# configs (the netobs scenario family, flowtrace plane on)
# ---------------------------------------------------------------------------


def _drop_heavy_cfg(data_dir="/tmp/flowtrace-droppy", seed=11,
                    backend="cpu", stop="1500ms",
                    sample=1.0) -> ConfigOptions:
    """Loss on the link + oversubscribed buckets: loss drops, codel
    drops, and token-bucket waits all nonzero."""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: {stop}, seed: {seed}, data_directory: {data_dir},
           heartbeat_interval: null}}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_up "2 Mbit" host_bandwidth_down "1 Mbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.05 ]
      ]
experimental: {{network_backend: {backend}, flowtrace: true,
               flowtrace_sample: {sample},
               tpu_lane_queue_capacity: 2048}}
hosts:
  srv:
    network_node_id: 0
    processes: [{{path: tgen-server}}]
  cli:
    count: 6
    network_node_id: 0
    processes:
      - path: tgen-client
        args: --server srv --interval 5ms --size 1400
""")


def _lossy_stream_cfg(data_dir="/tmp/flowtrace-stream",
                      backend="tpu") -> ConfigOptions:
    """Two-host lane-TCP transfer over a lossy link: the retransmit
    lifecycle stage (FT_RETRANSMIT joins on the NEW wire seq)."""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 6s, seed: 5, data_directory: {data_dir},
           heartbeat_interval: null, bootstrap_end_time: 100ms}}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 1 latency "10 ms" packet_loss 0.02 ]
      ]
experimental: {{network_backend: {backend}, flowtrace: true,
               tpu_lane_queue_capacity: 128}}
hosts:
  c:
    network_node_id: 0
    processes:
      - path: stream-client
        args: --server s --size 400000
  s:
    network_node_id: 1
    processes:
      - path: stream-server
""")


def _phold_cfg(data_dir="/tmp/flowtrace-phold", backend="tpu",
               capacity=65536) -> ConfigOptions:
    """Small phold ring: cheap lane program for overflow/artifact tests."""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 3, data_directory: {data_dir},
           heartbeat_interval: null}}
experimental: {{network_backend: {backend}, flowtrace: true,
               flowtrace_capacity: {capacity}}}
hosts:
  n:
    count: 8
    processes: [{{path: phold, args: --messages 3 --size 600}}]
""")


def _canon(snap, capacity=1 << 20):
    ev, lost = ftr.canonical_events(snap["raw"], capacity)
    return ev, lost + snap["ring_lost"]


def _streams(cfg_tpu, mode="device"):
    """(cpu events, tpu events) for the same config, with the log
    parity precondition asserted and no event loss on either side."""
    from shadow_tpu.backend.cpu_engine import CpuEngine
    from shadow_tpu.backend.tpu_engine import TpuEngine

    cfg_cpu = copy.deepcopy(cfg_tpu)
    cfg_cpu.experimental.network_backend = "cpu"
    ce = CpuEngine(cfg_cpu)
    r1 = ce.run()
    te = TpuEngine(cfg_tpu)
    r2 = te.run(mode=mode)
    assert r1.log_tuples() == r2.log_tuples()
    ec, lc = _canon(ce.flowtrace_snapshot())
    et, lt = _canon(te.flowtrace_snapshot())
    assert lc == 0 and lt == 0  # parity is asserted at zero loss only
    return ec, et


# ---------------------------------------------------------------------------
# 1. device <-> oracle event parity
# ---------------------------------------------------------------------------


class TestDeviceOracleParity:
    def test_drop_heavy_parity_fused(self):
        ec, et = _streams(_drop_heavy_cfg(backend="tpu"))
        assert ec == et
        kinds = {e[2] for e in ec}
        # the scenario exercises the lifecycle: sends, bucket waits,
        # queue entries, loss AND codel drops, deliveries
        assert {ftr.FT_SEND, ftr.FT_TB_WAIT, ftr.FT_QUEUE_ENTER,
                ftr.FT_DROP, ftr.FT_DELIVERY} <= kinds
        causes = {e[7] for e in ec if e[2] == ftr.FT_DROP}
        assert {ftr.CAUSE_LOSS, ftr.CAUSE_CODEL} <= causes

    def test_drop_heavy_parity_step_driver(self):
        ec, et = _streams(
            _drop_heavy_cfg(backend="tpu", seed=12, stop="600ms"),
            mode="step",
        )
        assert ec == et

    def test_lossy_stream_parity_retransmits(self):
        ec, et = _streams(_lossy_stream_cfg(backend="tpu"))
        assert ec == et
        retx = [e for e in ec if e[2] == ftr.FT_RETRANSMIT]
        assert retx  # the lossy link forced retries
        # every retransmit is a full wire packet: its (src, dst, seq)
        # either delivers or drops downstream, same as a first send
        seqs = {(e[3], e[4], e[5]) for e in ec
                if e[2] in (ftr.FT_DELIVERY, ftr.FT_DROP)}
        assert any((e[3], e[4], e[5]) in seqs for e in retx)

    def test_mixed_mesh_parity_tier_fallback(self):
        from shadow_tpu.backend.tpu_engine import TpuEngine
        from shadow_tpu.config.presets import mixed_flagship_config

        cfg = mixed_flagship_config(40, sim_seconds=1)
        # 400 ms: the flow's handshake and slow start, and 40 windows of
        # the mesh.  The flat path sorts the whole queue width every
        # iteration, so a horizon that needs a narrower queue is what
        # keeps this test far from its wall limit (1 s needed 4096
        # columns and 261 s; this shape takes ~25 s alone)
        cfg.general.stop_time = 400_000_000
        cfg.experimental.flowtrace = True
        # flowtrace instruments the untiered path only: the engine falls
        # back (equivalent execution) — queue headroom for the flat path
        cfg.experimental.tpu_lane_queue_capacity = 1024
        assert TpuEngine(cfg).params.stream_tiered is False
        ec, et = _streams(cfg)
        assert ec == et
        names = [h.hostname[:2] for h in cfg.hosts]
        classes = {(names[e[3]], names[e[4]]) for e in ec}
        # the stream pair's segments and ACKs, and the mesh's datagrams
        assert {("sc", "ss"), ("ss", "sc"), ("pe", "pe")} <= classes

    def test_sampled_subset_parity(self):
        full, _ = _streams(_drop_heavy_cfg(backend="tpu"))
        ec, et = _streams(_drop_heavy_cfg(backend="tpu", sample=0.5))
        assert ec == et
        assert 0 < len(ec) < len(full)
        # the sampled stream is exactly the full stream restricted to
        # the selected pairs (no event mutation, pure flow selection)
        pairs = {(e[3], e[4]) for e in ec}
        assert ec == [e for e in full if (e[3], e[4]) in pairs]


# ---------------------------------------------------------------------------
# 2. run-twice byte-identical FLOWS artifacts; worker invariance
# ---------------------------------------------------------------------------


class TestFlowsDeterminism:
    def test_cpu_flows_artifact_byte_identical(self, tmp_path):
        blobs = []
        for tag in ("r1", "r2"):
            sim = Simulation(_drop_heavy_cfg(tmp_path / tag))
            sim.run(write_data=False)
            arts = sorted((tmp_path / tag).glob("FLOWS_*.json"))
            assert len(arts) == 1
            blobs.append(arts[0].read_bytes())
        assert blobs[0] == blobs[1]
        rep = json.loads(blobs[0])
        assert rep["schema"] == ftr.SCHEMA_VERSION
        assert rep["events_lost"] == 0
        assert rep["num_events"] == len(rep["events"])
        assert rep["events_by_kind"]["drop"] > 0
        assert rep["num_flows"] == len(rep["flows"])
        # per-flow conservation: sends == delivered + drops + in flight
        for fl in rep["flows"].values():
            assert fl["sends"] >= fl["delivered"] + sum(fl["drops"].values())
        # burst attribution names flow classes per occupancy bucket
        buckets = rep["burst_attribution"]["buckets"]
        assert buckets and all(b["top_classes"] for b in buckets)

    def test_cpu_mp_worker_invariance(self, tmp_path):
        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        names = [h.hostname for h in _drop_heavy_cfg(tmp_path / "n").hosts]

        def report(snap):
            ev, lost = _canon(snap, 65536)
            return json.dumps(
                ftr.build_report("t", "cpu", 11, names, ev, lost, 0,
                                 True, 65536),
                sort_keys=True,
            )

        ser = CpuEngine(_drop_heavy_cfg(tmp_path / "ser"))
        ser.run()
        rs = report(ser.flowtrace_snapshot())
        for w in (2, 4):
            eng = MpCpuEngine(_drop_heavy_cfg(tmp_path / f"w{w}"),
                              workers=w)
            eng.run()
            snap = eng.flowtrace_snapshot()
            assert snap is not None
            assert report(snap) == rs, f"workers={w}"

    def test_tpu_flows_artifact_via_facade(self, tmp_path):
        sim = Simulation(_phold_cfg(tmp_path / "r1", capacity=131072))
        sim.run(write_data=False)
        arts = sorted((tmp_path / "r1").glob("FLOWS_*.json"))
        assert len(arts) == 1
        rep = json.loads(arts[0].read_text())
        assert rep["backend"] == "tpu"
        assert rep["num_events"] > 0
        counters = sim.obs.metrics.counters()
        assert counters["flow_events"] == rep["num_events"]
        assert counters.get("flow_events_lost", 0) == 0


# ---------------------------------------------------------------------------
# 3. sampling determinism (device hash == python hash)
# ---------------------------------------------------------------------------


class TestSamplingDeterminism:
    def test_device_hash_matches_python(self):
        import jax.numpy as jnp

        from shadow_tpu.backend import lanes

        n = 24
        for seed in (0, 1, 11, 12345):
            py = np.array(
                [[ftr.flow_hash(s, d, 0, seed) for d in range(n)]
                 for s in range(n)],
                dtype=np.uint32,
            )
            dev = np.asarray(lanes.flow_hash_lane(
                jnp.asarray(np.repeat(np.arange(n, dtype=np.int32), n)),
                jnp.asarray(np.tile(np.arange(n, dtype=np.int32), n)),
                jnp.int32(seed),
            )).astype(np.uint32).reshape(n, n)
            assert np.array_equal(py, dev), f"seed={seed}"

    def test_sample_thresh_edges(self):
        assert ftr.sample_thresh(1.0) == (0, True)   # all flows record
        assert ftr.sample_thresh(0.0) == (0, False)  # none record
        thresh, all_pass = ftr.sample_thresh(0.5)
        assert not all_pass and 0 < thresh < (1 << 32)

    def test_sampled_selection_is_seed_stable(self):
        ft1 = ftr.FlowTrace(16, seed=7, sample=0.5, capacity=64)
        ft2 = ftr.FlowTrace(16, seed=7, sample=0.5, capacity=64)
        sel1 = {(s, d) for s in range(16) for d in range(16)
                if ft1.sampled(s, d)}
        assert sel1 == {(s, d) for s in range(16) for d in range(16)
                        if ft2.sampled(s, d)}
        assert 0 < len(sel1) < 256
        # a different seed picks a different subset
        ft3 = ftr.FlowTrace(16, seed=8, sample=0.5, capacity=64)
        assert sel1 != {(s, d) for s in range(16) for d in range(16)
                        if ft3.sampled(s, d)}


# ---------------------------------------------------------------------------
# 4. ring-overflow law
# ---------------------------------------------------------------------------


class TestOverflowLaw:
    def test_device_ring_never_wraps_and_conserves(self):
        import copy as _copy

        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.tpu_engine import TpuEngine

        cfg = _phold_cfg("/tmp/flowtrace-ovf", capacity=32)
        te = TpuEngine(cfg)
        te.run(mode="device")
        snap = te.flowtrace_snapshot()
        # full ring: exactly `capacity` rows kept, the rest counted
        assert len(snap["raw"]) == 32
        assert snap["ring_lost"] > 0
        cfg_c = _copy.deepcopy(cfg)
        cfg_c.experimental.network_backend = "cpu"
        ce = CpuEngine(cfg_c)
        ce.run()
        total = len(ce.flowtrace_snapshot()["raw"])
        # conservation: device kept + lost == the oracle's full stream
        assert len(snap["raw"]) + snap["ring_lost"] == total
        # the oracle's canonical truncation mirrors the law
        ev, lost = ftr.canonical_events(ce.flowtrace_snapshot()["raw"], 32)
        assert len(ev) == 32 and lost == total - 32

    def test_overflow_surfaces_as_metric(self, tmp_path):
        sim = Simulation(_phold_cfg(tmp_path / "ovf", capacity=32))
        sim.run(write_data=False)
        counters = sim.obs.metrics.counters()
        assert counters["flow_events_lost"] > 0
        rep = json.loads(
            next((tmp_path / "ovf").glob("FLOWS_*.json")).read_text()
        )
        assert rep["events_lost"] == counters["flow_events_lost"]
        assert rep["num_events"] <= 32


# ---------------------------------------------------------------------------
# 5. off = zero overhead; config + LaneParams guards
# ---------------------------------------------------------------------------


class TestOffPathAndGuards:
    def test_engines_default_flowtrace_off(self):
        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.tpu_engine import TpuEngine

        cfg = _drop_heavy_cfg("/tmp/flowtrace-off")
        cfg.experimental.flowtrace = False
        assert CpuEngine(cfg).flowtrace is None
        te = TpuEngine(cfg)
        assert te.params.flowtrace is False
        state = te.initial_state()
        # the whole plane compiles away: no ring, no cursor, no counter
        assert state.fl_buf == () and state.fl_count == ()
        assert state.fl_lost == ()
        assert te.flowtrace_snapshot() is None

    def test_config_validation(self):
        cfg = _drop_heavy_cfg("/tmp/flowtrace-val")
        cfg.experimental.flowtrace_capacity = 0
        with pytest.raises(ConfigError, match="flowtrace_capacity"):
            cfg.validate()
        cfg = _drop_heavy_cfg("/tmp/flowtrace-val")
        cfg.experimental.flowtrace_sample = 1.5
        with pytest.raises(ConfigError, match="flowtrace_sample"):
            cfg.validate()

    def test_laneparams_untiered_only_guard(self):
        from shadow_tpu.backend import lanes

        base = dict(
            n_lanes=2, capacity=8, pops_per_iter=2, log_capacity=0,
            seed=1, stop_time=1000, bootstrap_end=0, runahead=100,
        )
        with pytest.raises(ValueError, match="stream_tiered"):
            lanes.LaneParams(
                **base, flowtrace=True, flow_capacity=16,
                stream_tiered=True,
            )
        with pytest.raises(ValueError, match="flow_capacity"):
            lanes.LaneParams(**base, flowtrace=True, flow_capacity=0)


# ---------------------------------------------------------------------------
# 6. console `flows` verb (run-control)
# ---------------------------------------------------------------------------


class TestFlowsVerb:
    def test_flows_verb_not_enabled(self):
        import io

        from shadow_tpu.engine.run_control import RunControl

        out = io.StringIO()
        rc = RunControl(out=out)
        rc._apply("flows")
        assert "flowtrace is not enabled" in out.getvalue()

    def test_flows_verb_with_sink(self):
        import io

        from shadow_tpu.engine.run_control import RunControl

        events = [
            (1000, 10_000_000, ftr.FT_SEND, 0, 1, 5, 1400, 0),
            (2000, 10_000_000, ftr.FT_DELIVERY, 0, 1, 5, 1400, 0),
        ]
        out = io.StringIO()
        rc = RunControl(out=out)
        rc.set_flows_sink(
            lambda host: ftr.snapshot_lines(events, 0, ["a", "b"], host)
        )
        rc._apply("flows")
        text = out.getvalue()
        assert "events=2" in text
        assert "a->b" in text

    def test_flows_live_at_pause_and_stats_fold(self, tmp_path):
        import io

        from shadow_tpu.engine.run_control import RunControl

        out = io.StringIO()
        rc = RunControl(out=out, poll_interval=0.01, max_wait=10)
        rc.feed("p", "flows", "stats", "c")
        sim = Simulation(_drop_heavy_cfg(tmp_path / "d"), run_control=rc)
        sim.run(write_data=False)
        text = out.getvalue()
        assert "[run-control] flows:" in text
        # `stats` folds the one-line flow summary next to the metrics
        assert "flows: sampled_pairs=" in text


# ---------------------------------------------------------------------------
# 7. hybrid: determinism + zero new syncs (native binaries required)
# ---------------------------------------------------------------------------


def _hybrid_cfg(data_dir, ft=True) -> ConfigOptions:
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
experimental: {{network_backend: tpu, flowtrace: {str(ft).lower()}}}
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


@pytest.mark.hybrid
class TestFlowsHybrid:
    @pytest.fixture(scope="class", autouse=True)
    def native_build(self):
        subprocess.run(
            ["make", "-C", str(REPO / "native")],
            check=True, capture_output=True,
        )

    def test_hybrid_flows_byte_identical_and_sync_invariant(
        self, tmp_path
    ):
        blobs, syncs = [], []
        for tag in ("r1", "r2"):
            sim = Simulation(_hybrid_cfg(tmp_path / tag))
            sim.run(write_data=False)
            arts = sorted((tmp_path / tag).glob("FLOWS_*.json"))
            assert len(arts) == 1
            blobs.append(arts[0].read_bytes())
            syncs.append(dict(sim.engine.sync_stats))
        assert blobs[0] == blobs[1]
        rep = json.loads(blobs[0])
        # the split covers the stream: host-emitted sends (managed +
        # loopback) join device-emitted arrivals in one canonical order
        assert rep["events_by_kind"]["send"] > 0
        assert rep["events_by_kind"]["delivery"] > 0
        assert rep["num_flows"] > 0

        # zero new per-window host syncs: the flowtrace-OFF run moves
        # exactly the same transfers (the ring drains at collect only)
        cfg_off = _hybrid_cfg(tmp_path / "off", ft=False)
        sim_off = Simulation(cfg_off)
        sim_off.run(write_data=False)
        off = sim_off.engine.sync_stats
        for key in ("scalar_reads", "h2d_copies", "inject_blocks",
                    "egress_reads", "device_turns"):
            assert off[key] == syncs[0][key] == syncs[1][key], key

    def test_hybrid_worker_invariance(self, tmp_path):
        blobs = {}
        for hw in (1, 2):
            cfg = _hybrid_cfg(tmp_path / f"hw{hw}")
            cfg.experimental.hybrid_workers = hw
            sim = Simulation(cfg)
            sim.run(write_data=False)
            arts = sorted((tmp_path / f"hw{hw}").glob("FLOWS_*.json"))
            assert len(arts) == 1
            blobs[hw] = arts[0].read_bytes()
        assert blobs[1] == blobs[2]
