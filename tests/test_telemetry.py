"""Simulated-network telemetry plane (obs/netobs.py, docs/observability.md).

Contracts under test:

1. **Device ↔ oracle counter parity** — every netobs counter (packets,
   bytes, drops by cause, throttles, retransmits) and the burst-window
   histogram bit-identical between the TPU/lane path and the CPU oracle
   on a drop-heavy scenario (link loss + CoDel pressure) and on a lossy
   stream-flow scenario, on both the fused and step drivers.
2. **Run-twice determinism** — byte-identical ``NETOBS_*.json`` on the
   cpu, cpu_mp (workers 2), and hybrid backends.
3. **pcap ↔ netobs cross-check** — for a two-host TCP scenario the sum
   of pcap records written by utils/pcap.py equals the netobs
   sent/delivered counters for those hosts (the two capture layers tie).
4. **log_lost surfacing** — a device event-log overflow lands in the
   metrics registry before the run fails.
5. **Zero overhead / zero new syncs when off and on** — engines default
   netobs-off with no state allocated, and the hybrid backend's
   host↔device transfer counts are unchanged with netobs on.
"""

import copy
import json
import struct
import subprocess
from pathlib import Path

import numpy as np
import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import netobs as nom

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------


def _drop_heavy_cfg(data_dir="/tmp/netobs-droppy", seed=11,
                    backend="cpu", stop="1500ms") -> ConfigOptions:
    """Loss on the link + oversubscribed buckets: every drop cause the
    oracle can produce (loss, codel) plus heavy throttle pressure."""
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
experimental: {{network_backend: {backend}, netobs: true,
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


def _lossy_stream_cfg(data_dir="/tmp/netobs-stream", backend="tpu",
                      pcap: bool = False) -> ConfigOptions:
    """Two-host lane-TCP transfer over a lossy link: retransmit and
    stream-counter coverage (client c -> server s)."""
    pcap_line = "pcap_enabled: true" if pcap else "pcap_enabled: false"
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
experimental: {{network_backend: {backend}, netobs: true,
               tpu_lane_queue_capacity: 128}}
hosts:
  c:
    network_node_id: 0
    {pcap_line}
    processes:
      - path: stream-client
        args: --server s --size 400000
  s:
    network_node_id: 1
    {pcap_line}
    processes:
      - path: stream-server
""")


def _phold_cfg(data_dir="/tmp/netobs-phold", backend="tpu") -> ConfigOptions:
    """Small phold ring: a cheap-to-compile lane program for the step
    driver and overflow tests."""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 3, data_directory: {data_dir},
           heartbeat_interval: null}}
experimental: {{network_backend: {backend}, netobs: true}}
hosts:
  n:
    count: 8
    processes: [{{path: phold, args: --messages 3 --size 600}}]
""")


def _snapshots(cfg_tpu, mode="device"):
    """(cpu snapshot, tpu snapshot) for the same config, with the log
    parity precondition asserted."""
    from shadow_tpu.backend.cpu_engine import CpuEngine
    from shadow_tpu.backend.tpu_engine import TpuEngine

    cfg_cpu = copy.deepcopy(cfg_tpu)
    cfg_cpu.experimental.network_backend = "cpu"
    ce = CpuEngine(cfg_cpu)
    r1 = ce.run()
    te = TpuEngine(cfg_tpu)
    r2 = te.run(mode=mode)
    assert r1.log_tuples() == r2.log_tuples()
    return ce.netobs_snapshot(), te.netobs_snapshot()


def _assert_snap_equal(sc, st):
    for k in nom.COUNTERS:
        assert np.array_equal(sc["arrays"][k], st["arrays"][k]), (
            k, sc["arrays"][k], st["arrays"][k]
        )
    assert np.array_equal(sc["window_hist"], st["window_hist"]), (
        sc["window_hist"], st["window_hist"]
    )


# ---------------------------------------------------------------------------
# 1. device <-> oracle parity
# ---------------------------------------------------------------------------


class TestDeviceOracleParity:
    def test_drop_heavy_parity_fused(self):
        sc, st = _snapshots(_drop_heavy_cfg(backend="tpu"))
        _assert_snap_equal(sc, st)
        # the scenario actually exercises the classification: loss AND codel
        # drops AND bucket throttles are all nonzero
        tot = nom.totals(sc["arrays"])
        assert tot["drop_loss"] > 0
        assert tot["drop_codel"] > 0
        assert tot["throttled"] > 0
        assert sc["window_hist"].sum() > 0

    def test_drop_heavy_parity_step_driver(self):
        # the step driver's per-round histogram flush path (10 ms
        # windows keep the per-round device-call count small)
        sc, st = _snapshots(
            _drop_heavy_cfg(backend="tpu", seed=12, stop="600ms"),
            mode="step",
        )
        _assert_snap_equal(sc, st)

    def test_lossy_stream_parity_retransmits_and_device_determinism(self):
        # ONE compiled device program serves both checks: parity vs the
        # oracle, and run-twice determinism of the device-side snapshot
        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.tpu_engine import TpuEngine

        cfg_tpu = _lossy_stream_cfg(backend="tpu")
        cfg_cpu = copy.deepcopy(cfg_tpu)
        cfg_cpu.experimental.network_backend = "cpu"
        ce = CpuEngine(cfg_cpu)
        r1 = ce.run()
        te = TpuEngine(cfg_tpu)
        r2 = te.run(mode="device")
        assert r1.log_tuples() == r2.log_tuples()
        sc, st = ce.netobs_snapshot(), te.netobs_snapshot()
        _assert_snap_equal(sc, st)
        tot = nom.totals(sc["arrays"])
        assert tot["retransmits"] > 0  # the lossy link forced retries
        assert tot["tx_bytes"] > 400_000  # payload + control + retrans

        # second device run (cached program): the NETOBS report must be
        # byte-identical run-twice on the lane backend too
        def report(snap):
            return json.dumps(
                nom.build_report(
                    "t", "tpu", 5, ["c", "s"], snap["arrays"],
                    snap["window_hist"],
                ),
                sort_keys=True,
            )

        te.run(mode="device")
        assert report(te.netobs_snapshot()) == report(st)

    def test_mixed_mesh_parity_tiered(self):
        from shadow_tpu.config.presets import mixed_flagship_config

        cfg = mixed_flagship_config(40, sim_seconds=1)
        cfg.experimental.netobs = True
        sc, st = _snapshots(cfg)
        _assert_snap_equal(sc, st)


# ---------------------------------------------------------------------------
# 2. run-twice byte-identical NETOBS artifacts
# ---------------------------------------------------------------------------


class TestNetobsDeterminism:
    def test_cpu_netobs_artifact_byte_identical(self, tmp_path):
        blobs = []
        for tag in ("r1", "r2"):
            sim = Simulation(_drop_heavy_cfg(tmp_path / tag))
            sim.run(write_data=False)
            arts = sorted((tmp_path / tag).glob("NETOBS_*.json"))
            assert len(arts) == 1
            blobs.append(arts[0].read_bytes())
        assert blobs[0] == blobs[1]
        rep = json.loads(blobs[0])
        assert rep["schema"] == nom.SCHEMA_VERSION
        assert rep["drops_by_cause"]["loss"] > 0
        assert rep["drops_by_cause"]["codel"] > 0
        assert sum(rep["window_hist"]["buckets"]) == (
            rep["window_hist"]["windows"]
        )
        # conservation: sent == delivered + wire drops + in flight
        tot = rep["totals"]
        assert tot["sent"] == (
            tot["delivered"] + tot["drop_loss"] + tot["drop_codel"]
            + tot["drop_queue"] + tot["drop_cross_shed"]
            + rep["in_flight"]
        )

    def test_cpu_mp_netobs_byte_identical_and_serial_equal(self, tmp_path):
        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        def report(snap):
            return json.dumps(
                nom.build_report(
                    "t", "cpu", 11, [f"h{i}" for i in range(7)],
                    snap["arrays"], snap["window_hist"],
                ),
                sort_keys=True,
            )

        reps = []
        for tag in ("r1", "r2"):
            eng = MpCpuEngine(_drop_heavy_cfg(tmp_path / tag), workers=2)
            eng.run()
            snap = eng.netobs_snapshot()
            assert snap is not None
            reps.append(report(snap))
        assert reps[0] == reps[1]
        # and the parallel plane equals the serial oracle exactly
        ser = CpuEngine(_drop_heavy_cfg(tmp_path / "ser"))
        ser.run()
        assert report(ser.netobs_snapshot()) == reps[0]

    def test_tpu_netobs_artifact_via_facade(self, tmp_path):
        # the facade writes the NETOBS artifact for the lane backend too
        # (run-twice determinism of the device plane is pinned by the
        # cached-program check in the stream parity test)
        sim = Simulation(_phold_cfg(tmp_path / "r1"))
        sim.run(write_data=False)
        arts = sorted((tmp_path / "r1").glob("NETOBS_*.json"))
        assert len(arts) == 1
        rep = json.loads(arts[0].read_text())
        assert rep["backend"] == "tpu"
        assert rep["totals"]["sent"] > 0
        assert rep["window_hist"]["windows"] > 0


# ---------------------------------------------------------------------------
# 3. pcap <-> netobs cross-check (two-host TCP)
# ---------------------------------------------------------------------------


def _count_pcap_records(path: Path) -> int:
    """Count records in a pcap file (24-byte global header, then
    16-byte record headers with incl_len)."""
    data = path.read_bytes()
    assert len(data) >= 24, "truncated pcap header"
    off, n = 24, 0
    while off < len(data):
        (_ts, _us, incl, _orig) = struct.unpack(">IIII", data[off:off + 16])
        off += 16 + incl
        n += 1
    return n


class TestPcapCrossCheck:
    def test_two_host_tcp_pcap_matches_netobs(self, tmp_path):
        from shadow_tpu.backend.cpu_engine import CpuEngine

        cfg = _lossy_stream_cfg(tmp_path, backend="cpu", pcap=True)
        eng = CpuEngine(cfg)
        eng.run()
        snap = eng.netobs_snapshot()
        arrays = snap["arrays"]
        names = [h.hostname for h in cfg.hosts]
        for hid, name in enumerate(names):
            pcap = tmp_path / "hosts" / name / "eth0.pcap"
            assert pcap.exists(), f"no capture for {name}"
            recs = _count_pcap_records(pcap)
            # outbound records are captured per SEND (pre-loss), inbound
            # per DELIVERY — exactly the netobs sent/delivered counters
            expect = int(arrays["sent"][hid] + arrays["delivered"][hid])
            assert recs == expect, (
                f"{name}: {recs} pcap records != sent+delivered {expect}"
            )
            assert recs > 0


# ---------------------------------------------------------------------------
# 4. log_lost surfacing (device log overflow -> metrics registry)
# ---------------------------------------------------------------------------


class TestLogLostSurfacing:
    def test_overflow_counts_into_metrics_before_raising(self):
        from shadow_tpu.backend.tpu_engine import TpuEngine
        from shadow_tpu.obs import Recorder

        cfg = _phold_cfg("/tmp/netobs-loglost")
        eng = TpuEngine(cfg, log_capacity=8)  # guaranteed overflow
        eng.obs = Recorder(run_id="loglost")
        with pytest.raises(RuntimeError, match="event log overflowed"):
            eng.run(mode="device")
        counters = eng.obs.metrics.counters()
        assert counters.get("device_log_lost", 0) > 0


# ---------------------------------------------------------------------------
# 5. off = zero overhead; unit laws
# ---------------------------------------------------------------------------


class TestOffPathAndUnits:
    def test_engines_default_netobs_off(self):
        from shadow_tpu.backend.cpu_engine import CpuEngine
        from shadow_tpu.backend.tpu_engine import TpuEngine

        cfg = _drop_heavy_cfg("/tmp/netobs-off")
        cfg.experimental.netobs = False
        assert CpuEngine(cfg).netobs is None
        te = TpuEngine(cfg)
        assert te.params.netobs is False
        state = te.initial_state()
        assert state.nb_txb == () and state.nb_hist == ()
        assert te.netobs_snapshot() is None

    def test_hist_bucket_law(self):
        assert nom.hist_bucket(1) == 0
        assert nom.hist_bucket(2) == 1
        assert nom.hist_bucket(3) == 1
        assert nom.hist_bucket(4) == 2
        assert nom.hist_bucket(1023) == 9
        assert nom.hist_bucket(1024) == 10
        assert nom.hist_bucket(1 << 40) == nom.HIST_BUCKETS - 1

    def test_device_ilog2_matches_oracle_bucket(self):
        import jax.numpy as jnp

        from shadow_tpu.backend import lanes

        vals = [1, 2, 3, 4, 7, 8, 1023, 1024, (1 << 23) - 1, 1 << 23,
                (1 << 30)]
        dev = np.asarray(
            jnp.minimum(
                lanes.ilog2_i32(jnp.asarray(vals, dtype=jnp.int32)),
                lanes.NB_HIST_BUCKETS - 1,
            )
        )
        assert list(dev) == [nom.hist_bucket(v) for v in vals]
        assert lanes.NB_HIST_BUCKETS == nom.HIST_BUCKETS

    def test_report_schema_and_determinism(self):
        arrays = nom.empty_arrays(3)
        arrays["sent"][:] = [5, 0, 2]
        arrays["tx_bytes"][:] = [500, 0, 900]
        arrays["drop_loss"][:] = [1, 0, 0]
        hist = np.zeros(nom.HIST_BUCKETS, dtype=np.int64)
        hist[2] = 4
        r1 = nom.build_report("r", "cpu", 1, ["a", "b", "c"], arrays,
                              hist)
        r2 = nom.build_report("r", "cpu", 1, ["a", "b", "c"], arrays,
                              hist)
        assert json.dumps(r1, sort_keys=True) == json.dumps(
            r2, sort_keys=True
        )
        # top talker order: tx_bytes first, host id breaks ties
        assert [t["host"] for t in r1["top_talkers"]] == ["c", "a"]
        assert r1["drops_by_cause"]["loss"] == 1
        assert r1["window_hist"]["windows"] == 4
        assert r1["per_host"]["a"]["sent"] == 5

    def test_netstats_verb(self):
        import io

        from shadow_tpu.engine.run_control import RunControl

        out = io.StringIO()
        rc = RunControl(out=out)
        rc._apply("netstats")
        assert "netobs is not enabled" in out.getvalue()

        arrays = nom.empty_arrays(2)
        arrays["sent"][:] = [3, 1]
        hist = np.zeros(nom.HIST_BUCKETS, dtype=np.int64)
        rc.set_netobs_sink(
            lambda host: nom.snapshot_lines(arrays, hist, ["a", "b"],
                                            host)
        )
        rc._apply("netstats a")
        text = out.getvalue()
        assert "net totals: sent=4" in text
        assert "a: sent=3" in text

    def test_netstats_live_at_pause(self, tmp_path):
        import io

        from shadow_tpu.engine.run_control import RunControl

        out = io.StringIO()
        rc = RunControl(out=out, poll_interval=0.01, max_wait=10)
        rc.feed("p", "netstats", "c")
        sim = Simulation(_drop_heavy_cfg(tmp_path / "d"), run_control=rc)
        sim.run(write_data=False)
        assert "[run-control] netstats:" in out.getvalue()
        assert "net totals:" in out.getvalue()


# ---------------------------------------------------------------------------
# hybrid: determinism + zero new syncs (native binaries required)
# ---------------------------------------------------------------------------


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
experimental: {{network_backend: tpu, netobs: true}}
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
class TestNetobsHybrid:
    @pytest.fixture(scope="class", autouse=True)
    def native_build(self):
        subprocess.run(
            ["make", "-C", str(REPO / "native")],
            check=True, capture_output=True,
        )

    def test_hybrid_netobs_byte_identical_and_sync_invariant(
        self, tmp_path
    ):
        blobs, syncs = [], []
        for tag in ("r1", "r2"):
            sim = Simulation(_hybrid_cfg(tmp_path / tag))
            sim.run(write_data=False)
            arts = sorted((tmp_path / tag).glob("NETOBS_*.json"))
            assert len(arts) == 1
            blobs.append(arts[0].read_bytes())
            syncs.append(dict(sim.engine.sync_stats))
        assert blobs[0] == blobs[1]
        rep = json.loads(blobs[0])
        # the device-plane histogram (all packet arrivals pop on the
        # lane plane on this backend) plus both halves' counters merged
        assert rep["window_hist"]["windows"] > 0
        assert rep["totals"]["sent"] > 0
        assert rep["totals"]["delivered"] > 0

        # zero new per-window host syncs: the netobs-OFF run of the same
        # config moves exactly the same number of transfers across the
        # boundary (counters ride existing readbacks only)
        cfg_off = _hybrid_cfg(tmp_path / "off")
        cfg_off.experimental.netobs = False
        sim_off = Simulation(cfg_off)
        sim_off.run(write_data=False)
        off = sim_off.engine.sync_stats
        for key in ("scalar_reads", "h2d_copies", "inject_blocks",
                    "egress_reads", "device_turns"):
            assert off[key] == syncs[0][key] == syncs[1][key], key
