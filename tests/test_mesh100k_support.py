"""The wide columnar mesh on the NORMAL path (ISSUE 28): the ``Simulation``
facade and the CLI, on one device and on a virtual mesh, at small sizes.

The law under test: *a run keeps a device event log only when something
will read it.*  ``Simulation(cfg, event_log=False)`` (the CLI without
``--event-log``) runs the lane program with no device log, so a mesh whose
records outnumber the log's fixed 200 000 rows finishes; its counters,
rounds and ``sim-stats.json`` equal the CPU oracle's; with the log on the
same run still raises, and the message names the remedy.  ``TpuEngine.run``
splits its wall into ``state_build`` / ``device_turn`` / ``collect`` phases
and gauges the shape of the run.

Wherever a mesh is attached the configuration keeps 2 pops per round: at
the default 8 a sharded run on XLA:CPU does not end in useful time
(ROADMAP C2).
"""

import copy
import json

import jax
import pytest

from shadow_tpu.backend import tpu_engine
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.config.columnar import columnar_mesh_config
from shadow_tpu.config.options import ConfigError, ConfigOptions
from shadow_tpu.engine.sim import Simulation, device_log_readers
from shadow_tpu.obs import Recorder

MS = 1_000_000
#: lane-engine bookkeeping the oracle does not keep (and one it alone keeps);
#: ``lane_drop_loss`` / ``lane_drop_codel`` are the oracle's too since PR 32
OWN = {"lane_iters", "lane_delivered", "lane_sends", "lane_drop_queue",
       "tgen_sent_bytes"}
#: the network ``lane_plane`` reports for these meshes (ISSUE 32): one graph
#: node, the 10 ms link as window and longest path, no loss draw compiled
#: in, no stream lanes
#: ... and (ISSUE 35) the shapes ``_mesh_cfg`` compiles at (no peaks: a
#: program of passive lanes compiles none)
#: ... and (ISSUE 36) no pop took CoDel's table lookup: 1 Gbit downlinks
#: ... and (ISSUE 39) the [N] send channel is one send wide: no gossip lane
#: ... and (ISSUE 42) no send reads per-peer path rows, and on one graph
#: node none gathers its path either (the [1, 1] lookup folds)
#: ... and (ISSUE 46) a row of its queues carries no payload word
#: ... and (ISSUE 48) so it reads no [G, G] table and gathers no element
ONE_SWITCH = {"graph_nodes": 1, "window_ns": 10 * MS,
              "max_path_latency_ns": 10 * MS, "has_loss": 0,
              "stream_wide_pop": 1, "lane_drop_loss": 0,
              "stream_retransmits": 0, "codel_lookup_pops": 0,
              "queue_capacity": 16, "cross_capacity": 8, "pops_per_iter": 2,
              "sends_per_pop": 1, "payload_words": 0,
              "static_path_sends": 0, "path_gather_sends": 0,
              "path_gather_tables": 0, "path_gather_elems_per_iter": 0}


def _mesh_cfg(tmp_path, hosts=2_000, stop_ms=1_100, mesh_devices=0):
    """The cells' configuration at a rehearsal width: 2 000 hosts x 1.1
    sim-s make 216 000 records, more than the device log's 200 000."""
    cfg = columnar_mesh_config(hosts, queue_capacity=16, pops_per_round=2,
                               mesh_devices=mesh_devices)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.general.stop_time = stop_ms * MS
    cfg.general.data_directory = str(tmp_path / f"d{mesh_devices}")
    cfg.general.heartbeat_interval = None
    return cfg


def _oracle(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.experimental.network_backend = "cpu"
    cfg.experimental.mesh_devices = 0
    return CpuEngine(cfg).run()


def _shared(counters):
    return {k: v for k, v in counters.items() if k not in OWN}


@pytest.fixture(scope="module")
def wide_oracle(tmp_path_factory):
    return _oracle(_mesh_cfg(tmp_path_factory.mktemp("oracle")))


# -- (a), (b): the log's law through the facade ------------------------------


def test_facade_without_the_log_runs_past_the_logs_capacity(
        tmp_path, wide_oracle):
    sim = Simulation(_mesh_cfg(tmp_path), event_log=False)
    res = sim.run()
    assert len(wide_oracle.event_log) == 216_000 > 200_000
    assert res.event_log == []
    assert res.rounds == wide_oracle.rounds
    assert _shared(res.counters) == _shared(wide_oracle.counters)
    assert res.counters["lane_delivered"] == 216_000
    stats = json.loads((sim.data_dir / "sim-stats.json").read_text())
    assert stats["packet_outcomes"] == {"delivered": 216_000}
    assert stats["lane_plane"] == {
        "lanes": 2_000, "mesh_devices": 1, "device_log_capacity": 0,
        "device_log_records": 0, "exchange_bounds_wide": 0,
        "state_reused": 0, **ONE_SWITCH}


def test_facade_with_the_log_still_raises_and_names_the_remedy(tmp_path):
    with pytest.raises(RuntimeError) as e:
        Simulation(_mesh_cfg(tmp_path)).run(write_data=False)
    msg = str(e.value)
    assert "event log overflowed" in msg
    assert "produced 216000 records" in msg and "holds 200000" in msg
    assert "event_log=False" in msg and "--event-log" in msg


# -- (c): packet_outcomes from the lane counters ------------------------------

_DROPPY = """
general: {{stop_time: 1500ms, seed: 11, data_directory: {data},
           heartbeat_interval: null}}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_up "2 Mbit" host_bandwidth_down "1 Mbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.05 ]
      ]
experimental: {{network_backend: {backend}, tpu_lane_queue_capacity: {cap}}}
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
"""

_STREAM = """
general: {{stop_time: 3s, seed: 5, data_directory: {data},
           heartbeat_interval: null}}
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
experimental: {{network_backend: {backend}, tpu_lane_queue_capacity: {cap}}}
hosts:
  c:
    network_node_id: 0
    processes: [{{path: stream-client, args: [--server, s, --size, "1 MB"]}}]
  s:
    network_node_id: 1
    processes: [{{path: stream-server}}]
"""


def _outcomes(tmp_path, yaml, tag, backend, cap=2048, **kw):
    cfg = ConfigOptions.from_yaml(yaml.format(
        data=tmp_path / tag, backend=backend, cap=cap))
    sim = Simulation(cfg, **kw)
    sim.run()
    stats = json.loads((sim.data_dir / "sim-stats.json").read_text())
    return stats["packet_outcomes"], stats["lane_plane"]


@pytest.mark.parametrize("yaml, shows", [
    (_DROPPY, {"delivered", "loss", "codel"}),
    (_STREAM, {"delivered", "loss"}),
], ids=["datagrams_loss_codel", "lane_tcp_loss"])
def test_packet_outcomes_from_counters_equal_the_logs(tmp_path, yaml, shows):
    """Log off, ``packet_outcomes`` comes from the lane engine's totals; it
    equals the walk over the log-on tpu run's records and over the CPU
    oracle's, on traffic that shows every outcome a finished run can."""
    oracle, plane = _outcomes(tmp_path, yaml, "cpu", "cpu")
    assert plane is None and set(oracle) >= shows
    logged, plane = _outcomes(tmp_path, yaml, "on", "tpu")
    assert plane["device_log_capacity"] == 200_000
    assert plane["device_log_records"] == sum(
        v for k, v in logged.items() if k != "retry_drop")
    counted, plane = _outcomes(tmp_path, yaml, "off", "tpu", event_log=False)
    assert plane["device_log_capacity"] == 0
    assert counted == logged == oracle


@pytest.mark.parametrize("event_log", [True, False])
def test_a_queue_drop_never_reaches_packet_outcomes(tmp_path, event_log):
    """The fourth outcome, ``queue``: the oracle's queues are unbounded,
    and strict capacity makes a lane-queue overflow a raise in BOTH forms,
    so no finished facade run reports one."""
    cfg = ConfigOptions.from_yaml(_DROPPY.format(
        data=tmp_path / "q", backend="tpu", cap=12))
    with pytest.raises(RuntimeError, match="off the tail of a lane QUEUE"):
        Simulation(cfg, event_log=event_log).run(write_data=False)


# -- (d): a contradiction is refused before the run ---------------------------


def test_event_log_off_with_pcap_is_a_config_error(tmp_path):
    cfg = ConfigOptions.from_yaml(
        _STREAM.format(data=tmp_path / "p", backend="tpu", cap=128)
        .replace("  c:\n", "  c:\n    pcap_enabled: true\n"))
    assert device_log_readers(cfg) == ["pcap capture (host c)"]
    with pytest.raises(ConfigError, match="pcap capture"):
        Simulation(cfg, event_log=False)
    Simulation(cfg)  # the default keeps the log: nothing to refuse
    cfg.experimental.network_backend = "cpu"
    assert device_log_readers(cfg) == []  # the cpu engine's own capture
    Simulation(cfg, event_log=False)


# -- (e): the facade on a virtual mesh ----------------------------------------


def test_facade_on_four_devices_equals_one(tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    runs = {}
    for d in (0, 4):
        sim = Simulation(
            _mesh_cfg(tmp_path, hosts=256, stop_ms=300, mesh_devices=d),
            event_log=False)
        runs[d] = (sim.run(), sim)
    (one, _), (four, sim4) = runs[0], runs[4]
    assert sim4.engine.mesh.devices.size == 4
    assert sim4.engine.lane_plane["mesh_devices"] == 4
    assert sim4.engine.device_info()["count"] == 4
    assert (four.rounds, four.counters) == (one.rounds, one.counters)
    oracle = _oracle(_mesh_cfg(tmp_path, hosts=256, stop_ms=300))
    assert four.rounds == oracle.rounds
    assert _shared(four.counters) == _shared(oracle.counters)


# -- (f): the CLI passes its flag ---------------------------------------------

_PING = """
general: {{stop_time: 2s, seed: 5, data_directory: {data}}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu}}
hosts:
  cli: {{network_node_id: 0, processes: [{{path: ping, args: [--peer, srv, --count, "4", --interval, 250ms]}}]}}
  srv: {{network_node_id: 0, {pcap}processes: [{{path: ping}}]}}
"""


@pytest.mark.parametrize("flags, pcap, capacity", [
    ([], "", 0),
    (["--event-log"], "", 200_000),
    ([], "pcap_enabled: true, ", 200_000),  # pcap rides the log: kept
], ids=["no_flag", "event_log", "pcap_keeps_it"])
def test_cli_keeps_the_device_log_only_when_it_is_read(
        tmp_path, monkeypatch, flags, pcap, capacity):
    from shadow_tpu.__main__ import main

    built = []
    lane_engine = Simulation._lane_engine

    def spy(self, **kw):
        built.append(lane_engine(self, **kw))
        return built[-1]

    monkeypatch.setattr(Simulation, "_lane_engine", spy)
    path = tmp_path / "sim.yaml"
    path.write_text(_PING.format(data=tmp_path / "data", pcap=pcap))
    assert main([str(path), *flags]) == 0
    assert [e.params.log_capacity for e in built] == [capacity]
    stats = json.loads((tmp_path / "data" / "sim-stats.json").read_text())
    assert stats["packet_outcomes"] == {"delivered": 8}
    assert stats["lane_plane"]["device_log_capacity"] == capacity
    assert (tmp_path / "data" / "event-log.tsv").exists() == bool(flags)


# -- (g): the phases and gauges of a run --------------------------------------


@pytest.mark.parametrize("mode", ["device", "step"])
@pytest.mark.parametrize("event_log", [True, False], ids=["log", "nolog"])
def test_a_run_is_split_into_build_device_collect(tmp_path, mode, event_log):
    cfg = _mesh_cfg(tmp_path, hosts=64, stop_ms=100)
    cfg.experimental.obs_metrics = True
    cfg.experimental.perf_logging = mode == "step"  # selects the step driver
    sim = Simulation(cfg, event_log=event_log)
    res = sim.run()
    report = sim.obs.finalized["report"]
    spans = {k: v["spans"] for k, v in report["phases"].items()}
    assert spans["state_build"] == spans["collect"] == 1
    # one span for the fused call; one per round on the step driver
    assert spans["device_turn"] == (1 if mode == "device" else res.rounds + 1)
    assert all(report["phase_wall_s"][k] > 0 for k in spans)
    records = 64 * 8 if event_log else 0  # 10 windows: 8 deliveries a host
    assert {k: report["gauges"][k] for k in sim.engine.lane_plane} == {
        "lanes": 64, "mesh_devices": 1,
        "device_log_capacity": 200_000 if event_log else 0,
        "device_log_records": records, "exchange_bounds_wide": 0,
        "state_reused": 0, **ONE_SWITCH}
    assert len(res.event_log) == records


@pytest.mark.parametrize("mode", ["device", "step"])
def test_two_runs_of_one_engine_are_two_spans_of_each_host_phase(
        tmp_path, mode):
    eng = tpu_engine.TpuEngine(
        _mesh_cfg(tmp_path, hosts=64, stop_ms=100), log_capacity=0)
    eng.obs = Recorder()
    first, second = eng.run(mode=mode), eng.run(mode=mode)
    report = eng.obs.finalize()["report"]
    spans = {k: v["spans"] for k, v in report["phases"].items()}
    assert spans["state_build"] == spans["collect"] == 2
    assert all(report["phase_wall_s"][k] > 0 for k in spans)
    assert report["gauges"]["state_reused"] == 1
    assert (second.rounds, second.counters) == (first.rounds, first.counters)


def test_resume_refuses_a_state_of_another_log_capacity(tmp_path):
    """A checkpointed lane state carries its device log: written with the
    log on, it does not resume on a log-off program."""
    cfg = _mesh_cfg(tmp_path, hosts=64, stop_ms=100)
    state = tpu_engine.TpuEngine(cfg).initial_state()
    eng = tpu_engine.TpuEngine(cfg, log_capacity=0)
    with pytest.raises(tpu_engine.LaneCompatError, match="--event-log"):
        eng.run(mode="step", resume_state=state, resume_epoch=0)
