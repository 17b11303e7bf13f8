"""TPU lane backend vs CPU reference: bit-identical event logs.

This is the determinism gate the reference enforces with its determinism
test suite (src/test/determinism/CMakeLists.txt) — here applied *across
backends*: the batched JAX lane engine must produce exactly the event log
of the scalar Python engine for every supported workload.
"""

import pytest

from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import LaneCompatError, TpuEngine
from shadow_tpu.config.options import ConfigOptions


def both_runs(yaml: str, mode: str = "step"):
    """Both backends' results and the TPU engine's collect-time gauges."""
    cpu = CpuEngine(ConfigOptions.from_yaml(yaml)).run()
    eng = TpuEngine(ConfigOptions.from_yaml(yaml))
    tpu = eng.run(mode=mode)
    return cpu, tpu, eng.lane_plane


def both_logs(yaml: str, mode: str = "step"):
    return both_runs(yaml, mode)[:2]


PHOLD_SMALL = """
general: {stop_time: 500ms, seed: 7}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        edge [ source 0 target 0 latency "2 ms" ]
        edge [ source 0 target 1 latency "5 ms" ]
        edge [ source 1 target 1 latency "2 ms" ]
      ]
hosts:
  a: {network_node_id: 0, processes: [{path: phold, args: [--messages, "3"]}]}
  b: {network_node_id: 1, processes: [{path: phold, args: [--messages, "3"]}]}
  c: {network_node_id: 1, processes: [{path: phold, args: [--messages, "2"]}]}
"""


def test_phold_parity():
    cpu, tpu = both_logs(PHOLD_SMALL)
    assert len(cpu.event_log) > 50
    assert cpu.log_tuples() == tpu.log_tuples()


def test_phold_parity_device_mode():
    cpu, tpu = both_logs(PHOLD_SMALL, mode="device")
    assert cpu.log_tuples() == tpu.log_tuples()


TGEN_PAIR = """
general: {stop_time: 300ms, seed: 3}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "10 Mbit" host_bandwidth_down "10 Mbit" ]
        node [ id 1 host_bandwidth_up "10 Mbit" host_bandwidth_down "10 Mbit" ]
        edge [ source 0 target 1 latency "10 ms" packet_loss 0.2 ]
      ]
hosts:
  tx: {network_node_id: 0, processes: [{path: tgen-client, args: [--server, rx, --interval, 5ms, --size, "600"]}]}
  rx: {network_node_id: 1, processes: [{path: tgen-server}]}
"""


def test_tgen_lossy_parity():
    cpu, tpu, plane = both_runs(TGEN_PAIR)
    assert len(cpu.event_log) > 30
    assert any(r.outcome == 1 for r in cpu.event_log)  # some loss happened
    assert cpu.log_tuples() == tpu.log_tuples()
    assert cpu.counters["tgen_recv_bytes"] == tpu.counters["tgen_recv_bytes"]
    # a quiet downlink: no lane ever in a CoDel drop episode, so no pop
    # took the control law's table lookup
    assert plane["codel_lookup_pops"] == 0


TGEN_FAULTED = TGEN_PAIR + """
faults:
  events:
    - {at: 50ms, kind: latency, source: 0, target: 1, latency: "25 ms"}
    - {at: 100ms, kind: link_down, source: 0, target: 1}
    - {at: 200ms, kind: link_up, source: 0, target: 1}
"""


@pytest.mark.faults
@pytest.mark.parametrize("mode", ["step", "device"])
def test_fault_schedule_parity(mode):
    """Fault epochs re-upload the device gather tables mid-run; the CPU
    engine mutates its routing in place at the same window-clamp epochs —
    delivered-event ordering must stay bit-identical (docs/faults.md)."""
    cpu, tpu = both_logs(TGEN_FAULTED, mode=mode)
    assert len(cpu.event_log) > 20
    # the schedule actually bit: a latency shift and a dark window
    assert any(r.outcome == 1 for r in cpu.event_log)
    assert cpu.log_tuples() == tpu.log_tuples()
    assert cpu.counters["tgen_recv_bytes"] == tpu.counters["tgen_recv_bytes"]


MESH = """
general: {stop_time: 200ms, seed: 11}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 0 latency "3 ms" ]
      ]
hosts:
  m: {count: 5, network_node_id: 0, processes: [{path: tgen-mesh, args: [--interval, 7ms, --size, "400"]}]}
"""


def test_tgen_mesh_parity():
    cpu, tpu = both_logs(MESH)
    assert len(cpu.event_log) > 50
    assert cpu.log_tuples() == tpu.log_tuples()


FAR_TIMER = """
general: {stop_time: 12s, seed: 9}
network: {graph: {type: 1_gbit_switch}}
hosts:
  cli: {network_node_id: 0, processes: [{path: ping, args: [--peer, srv, --count, "2", --interval, 5s]}]}
  srv: {network_node_id: 0, processes: [{path: ping}]}
"""


MESH_NARROW_CROSS = MESH.replace(
    "hosts:", "experimental: {tpu_cross_capacity: 4}\nhosts:"
)


def test_narrow_cross_block_parity():
    """tpu_cross_capacity narrows the per-iteration receive block below the
    queue capacity (the bench's configuration); logs stay bit-identical
    when fan-in fits, and strict mode still raises when it doesn't."""
    cpu, tpu = both_logs(MESH_NARROW_CROSS, mode="device")
    assert cpu.log_tuples() == tpu.log_tuples()


def test_negative_cross_capacity_rejected():
    cfg = ConfigOptions.from_yaml(
        MESH.replace("hosts:", "experimental: {tpu_cross_capacity: -1}\nhosts:")
    )
    with pytest.raises(LaneCompatError):
        TpuEngine(cfg)


def test_far_future_events_parity():
    """Events queued >2.1 s past the window (a 5 s timer here; RTO backoff
    and staggered starts hit the same path) exercise the high word of the
    int32 time split — ordering and logs must stay exact, not saturate."""
    cpu, tpu = both_logs(FAR_TIMER, mode="device")
    assert len(cpu.event_log) >= 4  # two pings + echoes
    assert cpu.log_tuples() == tpu.log_tuples()


PING = """
general: {stop_time: 2s, seed: 5}
network: {graph: {type: 1_gbit_switch}}
hosts:
  cli: {network_node_id: 0, processes: [{path: ping, args: [--peer, srv, --count, "4", --interval, 250ms]}]}
  srv: {network_node_id: 0, processes: [{path: ping}]}
"""


def test_ping_parity():
    cpu, tpu = both_logs(PING)
    assert len(cpu.event_log) == 8  # 4 requests + 4 echoes
    assert cpu.log_tuples() == tpu.log_tuples()


BOTTLENECK = """
general: {stop_time: 400ms, seed: 9}
experimental: {tpu_lane_queue_capacity: 1024}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "20 Mbit" host_bandwidth_down "2 Mbit" ]
        edge [ source 0 target 0 latency "1 ms" ]
      ]
hosts:
  blast: {network_node_id: 0, processes: [{path: tgen-client, args: [--server, sink, --interval, 1ms, --size, "1200"]}]}
  sink: {network_node_id: 0}
"""


def test_codel_bottleneck_parity():
    # saturated downlink: token-bucket queueing + CoDel drops on both backends
    cpu, tpu, plane = both_runs(BOTTLENECK)
    assert any(r.outcome == 2 for r in cpu.event_log)  # codel drops happened
    assert cpu.log_tuples() == tpu.log_tuples()
    # drops inside an episode take the table lookup, and the counter of the
    # pops that did is not among the counters held to the oracle's
    assert 0 < plane["codel_lookup_pops"] <= (
        tpu.counters["lane_iters"] * plane["pops_per_iter"])
    assert "codel_lookup_pops" not in tpu.counters


def test_bootstrap_parity():
    yaml = TGEN_PAIR.replace(
        "general: {stop_time: 300ms, seed: 3}",
        "general: {stop_time: 300ms, seed: 3, bootstrap_end_time: 150ms}",
    )
    cpu, tpu = both_logs(yaml)
    assert cpu.log_tuples() == tpu.log_tuples()


def test_lane_compat_gate():
    # multi-process is lane-compiled only for tgen-trio combinations
    # with a single timer driver; everything else names the cpu backend
    with pytest.raises(LaneCompatError, match="tgen mesh/client/server"):
        TpuEngine(
            ConfigOptions.from_yaml(
                "general: {stop_time: 1s}\n"
                "hosts: {a: {processes: [{path: phold}, {path: phold}]}}"
            )
        )
    with pytest.raises(LaneCompatError, match="at most one timer-driving"):
        TpuEngine(
            ConfigOptions.from_yaml(
                "general: {stop_time: 1s}\n"
                "hosts:\n"
                "  a:\n"
                "    processes:\n"
                "      - {path: tgen-mesh, args: [--interval, 10ms]}\n"
                "      - {path: tgen-mesh, args: [--interval, 20ms]}\n"
            )
        )


MULTIPROC = """
general: {stop_time: 2s, seed: 13}
network: {graph: {type: 1_gbit_switch}}
hosts:
  duplex0:
    network_node_id: 0
    processes:
      - {path: tgen-client, args: [--server, duplex1, --interval, 40ms, --size, "700"]}
      - {path: tgen-server}
  duplex1:
    network_node_id: 0
    processes:
      - {path: tgen-server}
      - {path: tgen-client, args: [--server, duplex0, --interval, 55ms, --size, "500"]}
  sinks:
    network_node_id: 0
    processes:
      - {path: tgen-server}
      - {path: tgen-server}
  mesh0:
    network_node_id: 0
    processes:
      - {path: tgen-mesh, args: [--interval, 30ms, --size, "300"]}
      - {path: tgen-server}
"""


def test_multi_process_host_parity():
    """Multi-process lane hosts (tgen-trio combos, one driver max):
    logs bit-identical and counters equal — including the per-app
    delivery multiplication the CPU oracle performs."""
    cpu, tpu = both_logs(MULTIPROC, mode="device")
    assert len(cpu.event_log) > 50
    assert cpu.log_tuples() == tpu.log_tuples()
    assert cpu.counters.get("tgen_recv_bytes") == \
        tpu.counters.get("tgen_recv_bytes")


def test_phold_hops_counter_parity():
    cpu, tpu = both_logs(PHOLD_SMALL)
    assert cpu.counters["phold_hops"] == tpu.counters["phold_hops"]


SINK_DRAIN = """
general: {stop_time: 100ms, seed: 2}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "1 ms" ]
      ]
hosts:
  c: {count: 30, network_node_id: 0, processes: [{path: tgen-client, args: [--server, sink, --interval, 20ms, --size, "200"]}]}
  sink: {network_node_id: 0}
"""


def test_passive_sink_drain_parity():
    # regression: a lane popping >K passive DELIVERY events in one window
    # used to skip the merge AND the re-sort, wedging the device while_loop
    cpu, tpu = both_logs(SINK_DRAIN)
    assert len(cpu.event_log) > 100
    assert cpu.log_tuples() == tpu.log_tuples()


def test_non_power_of_two_capacity_parity():
    # regression: the barrel-shift gather assumed power-of-two capacities
    yaml = MESH.replace(
        "general: {stop_time: 200ms, seed: 11}",
        "general: {stop_time: 200ms, seed: 11}\n"
        "experimental: {tpu_lane_queue_capacity: 23}",
    )
    cpu, tpu = both_logs(yaml)
    assert cpu.log_tuples() == tpu.log_tuples()


def test_overflow_raises_loudly():
    # 40 synchronized senders blast one sink: the sink lane receives a
    # >capacity burst in a single window and must raise, not diverge
    yaml = """
general: {stop_time: 100ms, seed: 2}
experimental: {tpu_lane_queue_capacity: 9}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "1 ms" ]
      ]
hosts:
  c: {count: 40, network_node_id: 0, processes: [{path: tgen-client, args: [--server, sink, --interval, 5ms, --size, "300"]}]}
  sink: {network_node_id: 0}
"""
    from shadow_tpu.backend.tpu_engine import TpuEngine as TE

    with pytest.raises(RuntimeError, match="capacity overflow"):
        TE(ConfigOptions.from_yaml(yaml)).run(mode="step")


STREAM_PAIR = """
general: {stop_time: 30s, seed: 5}
experimental: {tpu_lane_queue_capacity: 128}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 1 latency "15 ms" ]
      ]
hosts:
  c: {network_node_id: 0, processes: [{path: stream-client, args: [--server, s, --size, 200kB]}]}
  s: {network_node_id: 1, processes: [{path: stream-server}]}
"""


def test_stream_tcp_parity():
    # the vectorized lane-TCP vs the scalar ltcp law: full handshake,
    # slow start, teardown — bit-identical wire traffic
    cpu, tpu = both_logs(STREAM_PAIR)
    assert cpu.counters["stream_complete"] == 1
    assert cpu.counters["stream_rx_bytes"] == 200_000
    assert cpu.log_tuples() == tpu.log_tuples()
    for k in ("stream_complete", "stream_rx_bytes", "stream_rx_segs",
              "stream_tx_segs", "stream_flows_done", "stream_retransmits"):
        assert cpu.counters.get(k) == tpu.counters.get(k), k


def test_stream_tcp_lossy_parity():
    yaml = STREAM_PAIR.replace('latency "15 ms"', 'latency "15 ms" packet_loss 0.03')
    cpu, tpu = both_logs(yaml)
    assert cpu.counters["stream_complete"] == 1
    assert cpu.counters["stream_retransmits"] > 0  # recovery exercised
    assert cpu.log_tuples() == tpu.log_tuples()
    assert cpu.counters.get("stream_retransmits") == tpu.counters.get("stream_retransmits")


STREAM_STAR = """
general: {stop_time: 60s, seed: 9}
experimental: {tpu_lane_queue_capacity: 512}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 0 latency "5 ms" packet_loss 0.01 ]
      ]
hosts:
  c: {count: 6, network_node_id: 0, processes: [{path: stream-client, args: [--server, srv, --size, 80kB]}]}
  srv: {network_node_id: 0, processes: [{path: stream-server}]}
"""


def test_stream_star_parity():
    # 6 concurrent flows into one server lane: exercises the per-flow
    # gather/scatter and multi-flow RTO/pump interleaving
    cpu, tpu = both_logs(STREAM_STAR)
    assert cpu.counters["stream_complete"] == 6
    assert cpu.counters["stream_rx_bytes"] == 6 * 80_000
    assert cpu.log_tuples() == tpu.log_tuples()
    assert cpu.counters.get("stream_flows_done") == tpu.counters.get("stream_flows_done")


def test_stream_device_mode_parity():
    cpu, tpu = both_logs(STREAM_PAIR, mode="device")
    assert cpu.log_tuples() == tpu.log_tuples()


def test_vector_law_keeps_ack_rto_arm_through_opened_pump():
    # regression: an ACK that shrinks the RTO (arming a new owner event)
    # AND opens the send window used to lose the arm when the inline pump's
    # emit was merged wholesale — leaving rto_evt naming an event that was
    # never queued (a dead retransmission timer)
    import jax
    import jax.numpy as jnp

    from shadow_tpu.backend import lanes_stream as lstr
    from shadow_tpu.net import ltcp

    def p(v):  # ns value -> (hi, lo) int32 split
        return v >> 31, v & ((1 << 31) - 1)

    segs = jnp.array([50], dtype=jnp.int32)
    mss = jnp.array([1448], dtype=jnp.int32)
    last = jnp.array([1448], dtype=jnp.int32)
    st = lstr.init_stream_state(1)  # host-side (numpy) matrices
    cl = jnp.asarray(st.cl)
    for col, val in (
        (lstr.C_STATE, ltcp.ESTAB), (lstr.C_SND_UNA, 5), (lstr.C_SND_NXT, 10),
        (lstr.C_RCV_NXT, 1), (lstr.C_MAX_SENT, 10),
        (lstr.C_CWND, 20 * ltcp.FP),
        (lstr.C_SRTT_HI, -1),  # first RTT sample -> RTO collapses to 200ms
        (lstr.C_SRTT_LO, 0), (lstr.C_RTTVAR_HI, 0), (lstr.C_RTTVAR_LO, 0),
        (lstr.C_RTO_HI, p(900_000_000)[0]), (lstr.C_RTO_LO, p(900_000_000)[1]),
        (lstr.C_RTT_SEQ, 5),
        (lstr.C_RTT_TS_HI, p(970_000_000)[0]),
        (lstr.C_RTT_TS_LO, p(970_000_000)[1]),
        (lstr.C_RTODL_HI, p(1_900_000_000)[0]),
        (lstr.C_RTODL_LO, p(1_900_000_000)[1]),
        (lstr.C_RTOEV_HI, p(1_900_000_000)[0]),
        (lstr.C_RTOEV_LO, p(1_900_000_000)[1]),
    ):
        cl = cl.at[0, col].set(val)
    st = st._replace(cl=cl)
    z1 = jnp.zeros(1, dtype=jnp.int32)
    f = lstr.endpoint_cols(
        st,
        jnp.concatenate([segs, z1]),
        jnp.concatenate([mss, z1]),
        jnp.concatenate([last, z1]),
        jnp.zeros(2, dtype=jnp.int32),  # flow_cc: reno
    )  # [2S]=2 rows: row 0 = the client endpoint, row 1 = its server
    now = 1_000_000_000
    nh = jnp.full(2, p(now)[0], dtype=jnp.int32)
    nl = jnp.full(2, p(now)[1], dtype=jnp.int32)
    # mirror the scalar law on the identical state
    fs = ltcp.FlowState(role=ltcp.SENDER, segs=50, mss=1448, last_bytes=1448,
                        state=ltcp.ESTAB, snd_una=5, snd_nxt=10, rcv_nxt=1,
                        max_sent=10, cwnd_fp=20 * ltcp.FP, srtt=-1,
                        rttvar=0, rto=900_000_000, rtt_seq=5,
                        rtt_ts=970_000_000, rto_deadline=1_900_000_000,
                        rto_evt=1_900_000_000)
    em_ref = ltcp.on_segment(fs, now, ltcp.F_ACK, 0, 6)
    m = jnp.array([True, False])
    f2, em = lstr.on_segment_vec(
        f, nh, nl, m, jnp.full(2, ltcp.F_ACK, dtype=jnp.int32),
        jnp.zeros(2, dtype=jnp.int32), jnp.full(2, 6, dtype=jnp.int32),
        jnp.full(2, ltcp.HDR_BYTES, dtype=jnp.int32),
    )
    # the slot driver runs the transmission-opportunity epilogue after
    # every stimulus — mirror it (the scalar wrapper does the same)
    f2, em, burst = lstr.pump_epilogue_vec(f2, nh, nl, m, em)
    assert em_ref.arm_rto is not None  # the scenario arms a shrunk owner
    assert bool(em.rto_valid[0])
    rto_t = (int(em.rto_thi[0]) << 31) | int(em.rto_tlo[0])
    assert rto_t == em_ref.arm_rto
    evt = (int(f2.rtoev_hi[0]) << 31) | int(f2.rtoev_lo[0])
    assert evt == fs.rto_evt
    # the epilogue pumped the same units the scalar law emitted
    n_burst = int(burst[0].sum())
    assert n_burst == len(em_ref.sends)
    assert [int(x) for x in jnp.stack([b for b in burst[2]])[
        jnp.stack([b for b in burst[0]])]] == [sd[1] for sd in em_ref.sends]


def test_mixed_mesh_stream_parity():
    """BASELINE config #4's shape in miniature: a UDP tgen mesh whose
    round-robin spray crosses lane-TCP stream pairs.  Stream lanes must
    ignore the foreign datagrams exactly like the CPU oracle's isinstance
    gate, and the logs must still diff equal."""
    from shadow_tpu.config.presets import flagship_mesh_config

    from shadow_tpu.backend.cpu_engine import CpuEngine as _Cpu

    cfg = flagship_mesh_config(
        12, sim_seconds=2, stream_pairs=2, stream_bytes=200_000,
        queue_capacity=96, pops_per_round=4,
    )
    import copy

    cpu_cfg = copy.deepcopy(cfg)
    cpu_cfg.experimental.network_backend = "cpu"
    cpu = _Cpu(cpu_cfg).run()
    tpu = TpuEngine(cfg).run(mode="device")
    assert cpu.log_tuples() == tpu.log_tuples()
    assert len(cpu.event_log) > 100
    # the stream tier really ran: segments crossed alongside the mesh
    assert tpu.counters.get("stream_rx_bytes", 0) > 0


def test_dynamic_runahead_parity():
    """use_dynamic_runahead on DEVICE (round-1 review item: it was
    cpu-only): the window widens to the smallest latency actually used —
    while only the slow path carries traffic the windows are wide, and
    the first fast-path send narrows them.  Bit-identical logs against
    the CPU oracle prove the identical law (runahead.rs:44-57)."""
    yaml = """
general: {stop_time: 2s, seed: 13}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        edge [ source 0 target 0 latency "2 ms" ]
        edge [ source 0 target 1 latency "40 ms" ]
        edge [ source 1 target 1 latency "2 ms" ]
      ]
experimental: {use_dynamic_runahead: true}
hosts:
  a: {network_node_id: 0, processes: [{path: tgen-client, args: "--server b --interval 30ms --size 600"}]}
  b: {network_node_id: 1, processes: [{path: tgen-server}]}
  c: {network_node_id: 1, processes: [{path: ping, args: "--peer d --count 5 --interval 100ms"}]}
  d: {network_node_id: 1, processes: [{path: ping}]}
"""
    cpu, tpu = both_logs(yaml, mode="device")
    assert cpu.log_tuples() == tpu.log_tuples()
    assert len(cpu.event_log) > 40


def test_pair_arithmetic_exact():
    """Property check of the int32 pair helpers against Python bignums —
    including the mul carry case where (s<<16) + ll*c wraps past 2**31
    (srtt ≈ 306.8 ms once corrupted RTO timing silently)."""
    import random

    import numpy as np

    from shadow_tpu.backend import lanes_pairs as lp

    rng = random.Random(7)
    cases = [(0, 306839551, 7), (0, 1431699455, 3)]
    for _ in range(20_000):
        c = rng.randint(1, 7)
        v = rng.randint(0, ((1 << 31) // c - 1) << 31 | lp.MASK31)
        cases.append((v >> 31, v & lp.MASK31, c))
    his = np.array([a for a, _b, _c in cases], dtype=np.int32)
    los = np.array([b for _a, b, _c in cases], dtype=np.int32)
    for cval in range(1, 8):
        mask = np.array([c == cval for _a, _b, c in cases])
        if not mask.any():
            continue
        h, l = lp.pair_mul_small(his[mask], los[mask], cval)
        h = np.asarray(h, dtype=np.int64)
        l = np.asarray(l, dtype=np.int64)
        exp = (
            his[mask].astype(np.int64) * (1 << 31) + los[mask].astype(np.int64)
        ) * cval
        got = h * (1 << 31) + l
        assert (got == exp).all() and (l >= 0).all() and (l < 1 << 31).all()
    # div / mod / sub round-trips on the same corpus
    vs = his.astype(np.int64) * (1 << 31) + los.astype(np.int64)
    for k in (1, 2, 3, 8, 30):
        dh, dl = lp.pair_div_pow2(his, los, k)
        got = np.asarray(dh, np.int64) * (1 << 31) + np.asarray(dl, np.int64)
        assert (got == vs >> k).all()
    for m in (3, 1_000_000, (1 << 22) - 1):
        got = np.asarray(lp.pair_mod_small(his, los, m), np.int64)
        assert (got == vs % m).all()
