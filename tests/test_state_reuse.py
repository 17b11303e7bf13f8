"""A fused run's host phases move nothing lane-sized (ISSUE 31).

The law under test, two halves of ``TpuEngine.run``:

* ``state_build``: the initial lane state is a pure function of the engine,
  so the FIRST run builds it (``initial_state``, one ``jax.device_put`` of a
  host tree straight onto its placement) and keeps it on the device; every
  later run is handed the kept arrays themselves (no program of the engine
  donates its argument).  ``attach_mesh`` drops it; a ``resume_state`` run
  neither reads nor writes it; ``initial_state()`` still returns a fresh
  state per call.
* ``collect``: every device value it reads comes back in ONE batched
  ``jax.device_get`` (plus the log's filled rows when a run kept records),
  and it reads nothing else: every leaf it did not fetch is ``None``.
  Counters, raises and gauges are what they were.

Wherever a mesh is attached the configuration keeps 2 pops per round (at
the default 8 a sharded run on XLA:CPU does not end in useful time).
"""

import json

import jax
import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend import lanes_stream as lstr
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.columnar import columnar_mesh_config
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.config.presets import flagship_mesh_config
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import Recorder

MS = 1_000_000


def _plain_cfg(hosts=64, stop_ms=100):
    cfg = columnar_mesh_config(hosts, queue_capacity=16, pops_per_round=2)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.general.stop_time = stop_ms * MS
    cfg.general.heartbeat_interval = None
    return cfg


def _mixed_cfg():
    """The mesh plus two lane-TCP flows on the TIERED stream backend."""
    cfg = flagship_mesh_config(
        40, sim_seconds=1, queue_capacity=16, pops_per_round=2,
        stream_pairs=2, stream_bytes=40_000)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.experimental.tpu_stream_events_per_round = 4
    cfg.general.stop_time = 400 * MS
    cfg.general.heartbeat_interval = None
    return cfg


_LOSSY = """
general: {stop_time: 1500ms, seed: 11, heartbeat_interval: null}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 host_bandwidth_up "2 Mbit" host_bandwidth_down "1 Mbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.05 ]
      ]
experimental: {network_backend: tpu, tpu_lane_queue_capacity: 2048}
hosts:
  srv:
    network_node_id: 0
    processes: [{path: tgen-server}]
  cli:
    count: 6
    network_node_id: 0
    processes:
      - path: tgen-client
        args: --server srv --interval 5ms --size 1400
"""


def _engine(kind: str) -> TpuEngine:
    """A fresh engine of one of the shapes the law is held on."""
    if kind == "mixed":
        eng = TpuEngine(_mixed_cfg())
        assert eng.params.stream_tiered
        return eng
    eng = TpuEngine(_plain_cfg(), netobs=kind == "netobs")
    if kind == "sharded":
        if len(jax.devices()) < 2:
            pytest.skip("needs two (virtual) devices")
        eng.attach_mesh(parallel.make_mesh(2))
    return eng


def _same(a, b) -> None:
    assert len(a.event_log) > 0 and a.rounds > 1
    assert a.event_log == b.event_log  # record for record
    assert a.counters == b.counters
    assert a.rounds == b.rounds


@pytest.fixture
def builds(monkeypatch):
    """Spy: every entry into ``TpuEngine.initial_state``."""
    seen = []
    real = TpuEngine.initial_state

    def spy(self, *a, **kw):
        seen.append(self)
        return real(self, *a, **kw)

    monkeypatch.setattr(TpuEngine, "initial_state", spy)
    return seen


# -- (a), (b): later runs start from the kept state ---------------------------

KINDS = ["plain", "mixed", "sharded", "netobs"]


@pytest.mark.parametrize("kind", KINDS)
def test_three_device_runs_on_one_engine_equal_a_fresh_engines(kind, builds):
    fresh = _engine(kind).run(mode="device")
    del builds[:]
    eng = _engine(kind)
    runs = [eng.run(mode="device") for _ in range(3)]
    reused = []
    for r in runs:
        _same(r, fresh)
    # the same once more, reading the gauge after each run
    for _ in range(2):
        eng.run(mode="device")
        reused.append(eng.lane_plane["state_reused"])
    assert builds == [eng]  # built once, in the first run
    assert reused == [1, 1]
    if kind == "sharded":
        assert len(eng._kept.q_thi.devices()) == 2
        assert eng.lane_plane["mesh_devices"] == 2


@pytest.mark.parametrize("kind", ["plain", "mixed", "sharded"])
def test_a_step_run_after_a_device_run_starts_from_the_kept_state(
        kind, builds):
    eng = _engine(kind)
    first = eng.run(mode="device")
    assert eng.lane_plane["state_reused"] == 0
    stepped = eng.run(mode="step")
    assert eng.lane_plane["state_reused"] == 1
    _same(stepped, first)
    _same(eng.run(mode="device"), first)
    assert builds == [eng]


@pytest.mark.parametrize("kind", ["plain", "sharded"])
def test_every_run_is_handed_the_kept_arrays_themselves(kind):
    """No copy, no transfer: the program's argument IS the kept state, and
    the kept state outlives the program (nothing donates it)."""
    eng = _engine(kind)
    first = eng.run(mode="device")
    handed, run_fn = [], eng._run_fn
    eng._run_fn = lambda state: (handed.append(state), run_fn(state))[1]
    _same(eng.run(mode="device"), first)
    assert handed[0] is eng._kept
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng._kept))
    if kind == "sharded":
        sh = parallel.state_shardings(eng.mesh)
        for field in ("q_thi", "send_seq", "log", "rounds"):
            x = getattr(eng._kept, field)
            assert x.sharding.is_equivalent_to(getattr(sh, field), x.ndim)


def test_initial_state_is_still_a_fresh_state_per_call():
    eng = _engine("plain")
    eng.run(mode="device")
    a, b = eng.initial_state(), eng.initial_state()
    assert a.q_thi is not b.q_thi and a.q_thi is not eng._kept.q_thi
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(eng._kept)):
        assert isinstance(x, jax.Array) and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- (c): what drops the kept state -------------------------------------------


def test_attach_mesh_drops_the_kept_state(builds):
    if len(jax.devices()) < 2:
        pytest.skip("needs two (virtual) devices")
    eng = _engine("plain")
    one = eng.run(mode="device")
    assert eng._kept is not None
    eng.attach_mesh(parallel.make_mesh(2))
    assert eng._kept is None
    two = eng.run(mode="device")
    assert eng.lane_plane["state_reused"] == 0
    assert eng.lane_plane["mesh_devices"] == 2
    assert len(eng._kept.q_thi.devices()) == 2
    _same(two, _engine("sharded").run(mode="device"))
    _same(two, one)  # and equal at any mesh shape
    assert builds.count(eng) == 2


# -- (d): resume ---------------------------------------------------------------


def test_a_resume_run_neither_reads_nor_replaces_the_kept_state(builds):
    eng = _engine("plain")
    first = eng.run(mode="device")
    kept = eng._kept
    resume = jax.device_get(eng.initial_state())  # a checkpoint at epoch 0
    del builds[:]
    resumed = eng.run(mode="step", resume_state=resume, resume_epoch=0)
    assert builds == [] and eng._kept is kept
    assert eng.lane_plane["state_reused"] == 0
    _same(resumed, first)
    # on an engine that has not run, a resume run keeps nothing
    other = _engine("plain")
    _same(other.run(mode="step", resume_state=resume, resume_epoch=0), first)
    assert other._kept is None and builds == []


# -- (e): collect is one readback ----------------------------------------------


def _parent_counters(eng, s) -> dict:
    """The parent commit's ``collect`` arithmetic, one array at a time, on
    the same final state (no stream tier)."""
    out = {}

    def add(key, val):
        if val:
            out[key] = int(val)

    model = np.asarray(eng.tables.model)
    tgen = np.isin(model, [lanes.M_TGEN_MESH, lanes.M_TGEN_CLIENT,
                           lanes.M_TGEN_SERVER])
    add("tgen_recv_bytes", np.asarray(s.recv_bytes)[tgen].sum())
    add("phold_hops", np.asarray(s.n_hops)[model == lanes.M_PHOLD].sum())
    add("lane_iters", int(s.iters))
    add("lane_delivered", np.asarray(s.n_delivered).sum())
    add("lane_drop_loss", np.asarray(s.n_loss).sum())
    add("lane_drop_codel", np.asarray(s.n_codel).sum())
    add("lane_drop_queue", np.asarray(s.n_queue).sum())
    add("lane_sends", np.asarray(s.n_sends).sum())
    return out


@pytest.fixture
def readbacks(monkeypatch):
    """Spy: ``jax.device_get`` calls, and every ``np.asarray`` handed a
    device array (an implicit, blocking, one-array readback)."""
    seen = {"device_get": 0, "asarray_of_device": 0}
    real_get, real_asarray = jax.device_get, np.asarray

    def device_get(tree):
        seen["device_get"] += 1
        return real_get(tree)

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            seen["asarray_of_device"] += 1
        return real_asarray(a, *args, **kw)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(np, "asarray", asarray)
    return seen


def test_collect_with_the_log_off_is_one_device_get(readbacks):
    eng = TpuEngine(ConfigOptions.from_yaml(_LOSSY), log_capacity=0)
    state = jax.block_until_ready(
        lanes.make_run_fn(eng.params, eng.tables)(eng.initial_state()))
    want = _parent_counters(eng, state)
    assert want["lane_drop_loss"] > 0 and want["lane_drop_codel"] > 0
    rounds = int(state.rounds)
    readbacks.update(device_get=0, asarray_of_device=0)
    res = eng.collect(state, 0.0)
    assert readbacks == {"device_get": 1, "asarray_of_device": 0}
    assert res.counters == want
    assert res.rounds == rounds and res.event_log == []
    assert eng.lane_plane["device_log_records"] == 0


@pytest.mark.parametrize("kind", ["plain", "mixed"])
def test_collect_with_the_log_on_adds_only_the_logs_filled_rows(
        kind, readbacks):
    eng = _engine(kind)
    state = jax.block_until_ready(
        lanes.make_run_fn(eng.params, eng.tables)(eng.initial_state()))
    readbacks.update(device_get=0, asarray_of_device=0)
    res = eng.collect(state, 0.0)
    assert readbacks == {"device_get": 1, "asarray_of_device": 1}
    assert len(res.event_log) == eng.lane_plane["device_log_records"] > 0
    if kind == "mixed":
        assert res.counters["stream_complete"] == 2
        assert res.counters["stream_rx_bytes"] == 80_000


@pytest.mark.parametrize("kind", ["plain", "mixed", "netobs"])
def test_collect_reads_host_copies_and_nothing_else(kind, monkeypatch):
    """What ``collect`` computes from holds no device array but the log: a
    read of a leaf ``_read_back`` does not list meets ``None`` and fails,
    it cannot become one more blocking transfer."""
    eng = _engine(kind)
    seen = []
    real = TpuEngine._read_back

    def spy(self, s):
        seen.append(real(self, s))
        return seen[-1]

    monkeypatch.setattr(TpuEngine, "_read_back", spy)
    res = eng.run(mode="device")
    (host,) = seen
    assert isinstance(host.log, jax.Array)
    leaves = jax.tree.leaves(host._replace(log=()))
    assert leaves and all(isinstance(x, np.ndarray) for x in leaves)
    assert host.q_thi is None and host.up_tokens is None
    assert int(host.n_sends.sum()) == res.counters["lane_sends"] or (
        kind == "mixed")  # the tier's sends are counted in stream.v
    if kind == "mixed":
        assert host.stream.q is None and host.stream.v.ndim == 2
    if kind == "netobs":
        snap = eng.netobs_snapshot()
        assert int(snap["arrays"]["sent"].sum()) == res.counters["lane_sends"]


# -- (f): the raises still fire, from hand-made states -------------------------


def _bad(field, value=-1):
    def make(s):
        arr = np.zeros(np.shape(getattr(s, field)), dtype=np.int32)
        arr.flat[-1] = value
        return s._replace(**{field: arr})
    return make


def _bad_tier(row):
    def make(s):
        v = np.array(s.stream.v)
        v[row, 0] = -1 if row == lstr.TV_SEND_SEQ else 3
        return s._replace(stream=s.stream._replace(v=v))
    return make


@pytest.mark.parametrize("kind, make, match", [
    ("plain", _bad("send_seq"), "counter send_seq wrapped past 2\\*\\*31"),
    ("plain", _bad("local_seq"), "counter local_seq wrapped"),
    ("plain", _bad("n_delivered"), "counter n_delivered wrapped"),
    ("plain", _bad("n_sends"), "counter n_sends wrapped"),
    ("plain", _bad("recv_bytes"), "counter recv_bytes wrapped"),
    ("plain", _bad("m_peer_offset"), "counter m_peer_offset wrapped"),
    ("mixed", _bad_tier(lstr.TV_SEND_SEQ), "tier counter send_seq wrapped"),
    ("plain", _bad("n_queue", 2), "2 events dropped on capacity overflow: 2 off the tail of a lane QUEUE \\(it holds 16\\) or by the CROSS block"),
    ("mixed", _bad_tier(lstr.TV_N_QUEUE),
     "3 events dropped on capacity overflow: 3 off the tail of a stream-tier QUEUE"),
    ("plain", _bad("log_lost", 7),
     "event log overflowed: .* \\(7 records lost\\)"),
], ids=["send_seq", "local_seq", "n_delivered", "n_sends", "recv_bytes",
        "m_peer_offset", "tier_send_seq", "queue_overflow",
        "tier_queue_overflow", "log_overflow"])
def test_collect_still_raises_from_a_hand_made_state(kind, make, match):
    eng = _engine(kind)
    state = make(eng.initial_state())
    with pytest.raises(RuntimeError, match=match):
        eng.collect(state, 0.0)
    if "queue" in match:  # not strict: the drop is a counter, not a raise
        eng.strict_capacity = False
        assert eng.collect(state, 0.0).counters["lane_drop_queue"] in (2, 3)


# -- (g): the gauge, the phases -------------------------------------------------


def test_state_reused_reads_0_then_1_and_reaches_sim_stats(tmp_path):
    cfg = _plain_cfg()
    cfg.general.data_directory = str(tmp_path / "data")
    cfg.experimental.obs_metrics = True
    sim = Simulation(cfg, event_log=False)
    sim.run()
    stats = json.loads((sim.data_dir / "sim-stats.json").read_text())
    assert stats["lane_plane"]["state_reused"] == 0
    assert sim.obs.finalized["report"]["gauges"]["state_reused"] == 0
    eng = sim.engine
    eng.obs = Recorder()
    eng.run(mode="device")
    assert eng.lane_plane["state_reused"] == 1
    assert eng.obs.finalize()["report"]["gauges"]["state_reused"] == 1
