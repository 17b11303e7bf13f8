"""The host-phase clock (shadow_tpu/obs/clock.py) and the spans both
drivers cut their host work into (docs/observability.md "reading a turn").

1. **The clock's arithmetic** — a span books its SELF time, so the phases
   of a turn tile it and sum to its wall; ``phase_s`` is the column sums of
   the rows; the ring is bounded; spans outside a turn reach the totals
   only; the sums the accepted readers divide by are exact.
2. **The hybrid turn** (serial and two workers) — ``device_sync_s`` and
   ``syscall_service_s`` ARE their phases' sums; the workers' own walls lie
   inside their rounds; a rolled-back turn is one row; the profiler sees
   exactly the table's names.
3. **obs** — the Recorder is handed the same pairs under its documented
   names (``device_turn`` is the blocking wait, ``dispatch`` the call
   before it), the event log does not move, ``METRICS_*.json`` keeps the
   totals and leaves the ring out.
4. **The fused driver** — ``fused/state_build``, ``fused/dispatch``,
   ``fused/device_wait``, ``fused/collect``, obs on or off.
"""

import json
import subprocess
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from shadow_tpu.backend import tpu_engine
from shadow_tpu.backend.hybrid import (
    TURN_NOTES, TURN_PHASES, HybridEngine, MpHybridEngine)
from shadow_tpu.config.columnar import columnar_mesh_config
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.obs import Recorder
from shadow_tpu.obs import clock as clock_mod
from shadow_tpu.obs.clock import RING_TURNS, TurnClock

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"
MS = 1_000_000


class Names:
    """A stub annotator: the names the clock would hand the profiler."""

    def __init__(self) -> None:
        self.seen: list[str] = []
        self.rows: list[tuple] = []

    def __call__(self, name: str, **stats):
        if stats:  # a journal clock's closed row, handed to the trace
            self.rows.append((name, stats))
        else:
            self.seen.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None


def _clock(owner=None, **kw) -> TurnClock:
    c = TurnClock(
        owner or SimpleNamespace(obs=None), "t", ("a", "b", "own"),
        notes=("n", "m"), turn_phase="own", **kw)
    c.names = Names()
    c.use_annotator(c.names)
    return c


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# -- 1. the clock's arithmetic -------------------------------------------------


def test_nested_spans_book_self_time_and_tile_the_turn():
    c = _clock()
    with c.turn():
        with c.span("a") as outer:
            _spin(0.002)
            with c.span("b") as inner:
                _spin(0.003)
        _spin(0.001)
        c.note("n", 7)
        c.add("m", 2)
        c.add("m", 3)
    (row,) = c.ring
    assert row.b == inner.dur >= 0.003
    # the parent's time is its duration less what its child covers
    assert row.a == pytest.approx(outer.dur - inner.dur, abs=1e-12)
    assert 0.002 <= row.a < outer.dur
    assert row.own >= 0.001
    assert row.a + row.b + row.own == pytest.approx(
        row.t_end - row.t_start, abs=1e-12)
    assert (row.turn, row.n, row.m) == (0, 7, 5)
    assert c.names.seen == ["t/own", "t/a", "t/b"]
    # a phase's span is one object, entered again each time
    assert c.span("a") is outer and c.span("b") is inner


def test_phase_s_is_the_column_sums_and_the_ring_is_bounded():
    c = _clock()
    for i in range(100):
        with c.turn():
            with c.span("a"):
                pass
            if i % 3 == 0:
                with c.span("b"):
                    pass
    assert len(c.ring) == c.turns == 100
    for p in c.phases:
        assert c.phase_s[p] == pytest.approx(
            sum(getattr(r, p) for r in c.ring), rel=1e-9, abs=1e-15)
    for _ in range(RING_TURNS):
        with c.turn():
            pass
    assert len(c.ring) == RING_TURNS == c.ring.maxlen
    assert c.ring[-1].turn == c.turns - 1 == RING_TURNS + 99
    assert c.ring[0].turn == 100  # the oldest rows went


def test_spans_outside_a_turn_reach_the_totals_and_leave_no_row():
    c = _clock()
    with c.span("a"):
        _spin(0.0005)
    c.note("n", 1)  # no turn is open: nothing to set
    c.add("m", 1)
    assert c.phase_s["a"] >= 0.0005 and not c.ring and c.turns == 0
    with pytest.raises(KeyError):
        c.span("no_such_phase")


def test_the_sums_readers_divide_by_are_exact():
    stats = {"ab_s": 0.0, "a_s": 0.0}
    c = _clock(totals=(stats, {"ab_s": ("a", "b"), "a_s": ("a",)}))
    for _ in range(50):
        with c.turn():
            with c.span("a"):
                pass
            with c.span("b"):
                pass
    assert stats["a_s"] == c.phase_s["a"] > 0
    assert stats["ab_s"] == c.phase_s["a"] + c.phase_s["b"]


def test_obs_gets_the_same_pair_under_its_own_name():
    rec = Recorder(trace=True)
    c = _clock(SimpleNamespace(obs=rec), obs_map={
        "a": ("injection", None, "rows"),
        "b": ("syscall_service", "round", "window_end"),
    })
    with c.turn():
        with c.span("a", 3) as a:
            pass
        a_dur = a.dur
        with c.span("a", 0):  # carried no rows: not forwarded
            pass
        with c.span("b") as b:
            b.detail = 99  # known only inside the block
    walls = rec.metrics.phase_wall_s()
    assert walls == {"injection": a_dur, "syscall_service": b.dur}
    assert "own" not in walls  # not in the map: not forwarded
    events = {e["cat"]: e for e in rec.tracer.events}
    assert events["injection"]["args"] == {"rows": 3}
    assert events["syscall_service"]["name"] == "round"
    assert events["syscall_service"]["args"] == {"window_end": 99}


def test_a_profiler_session_records_the_phases_and_no_session_nothing(
        tmp_path):
    """The real annotator: inside a ``jax.profiler`` session opened as the
    benchmark opens it (host tracer level 1, no Python tracer) every span
    is a TraceMe of the host plane, nested as the spans were; outside one a
    span enters no annotation at all."""
    import glob

    import jax
    from jax.profiler import ProfileData

    c = TurnClock(SimpleNamespace(obs=None), "hybrid", ("peek", "walk"),
                  turn_phase="walk")
    with c.turn():
        with c.span("peek") as sp:
            assert sp._ann is None  # no session: one flag test, no TraceMe
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with c.turn():
            with c.span("peek"):
                _spin(0.001)
            _spin(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    seen = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("hybrid/")}
    assert set(seen) == {"hybrid/walk", "hybrid/peek"}
    (w0, w1), (p0, p1) = seen["hybrid/walk"], seen["hybrid/peek"]
    assert w0 <= p0 < p1 <= w1 and p1 - p0 >= 1e6 and w1 - w0 >= 2e6
    assert len(c.ring) == 2


def test_what_a_span_costs_with_no_profiler_session():
    """A loop of stub turns of the hybrid's shape (ten spans a turn, real
    TraceAnnotations, no session open): the cost per turn is microseconds
    (docs/observability.md quotes the chip's reading; this only holds the
    order of magnitude, loosely, on a shared CPU)."""
    c = TurnClock(SimpleNamespace(obs=None), "hybrid", TURN_PHASES,
                  notes=TURN_NOTES, turn_phase="walk")
    n = 2_000
    t0 = time.perf_counter()
    for _ in range(n):
        with c.turn():
            for p in TURN_PHASES[:-1]:
                with c.span(p):
                    pass
            c.note("window_end_ns", 1)
    per_turn_us = (time.perf_counter() - t0) / n * 1e6
    assert per_turn_us < 500, per_turn_us  # ~15-30 us where it was read


# -- 2. the hybrid turn ----------------------------------------------------------


@pytest.fixture(scope="module")
def native_build():
    subprocess.run(
        ["make", "-C", str(REPO / "native")], check=True, capture_output=True)


def _cfg(data_dir: Path) -> ConfigOptions:
    """tests/test_hybrid_fusion.py's mixed scenario: pingpong's cadence
    stages sends whose arrivals land inside fused spans, so turns roll
    back, inject, egress and run rounds."""
    mesh = "\n".join(f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
""" for i in range(4))
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 2s, seed: 21, data_directory: {data_dir},
           heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu}}
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
""")


def _hybrid_run(tmp, kind: str, obs: bool):
    cfg = _cfg(tmp / "d")
    eng = (HybridEngine(cfg) if kind == "serial"
           else MpHybridEngine(cfg, workers=2))
    names = Names()
    eng.clock.use_annotator(names)
    if obs:
        eng.obs = Recorder(trace=True)
    calls = []
    result = eng.run(on_window=lambda *a: calls.append(a))
    assert not result.process_errors
    return SimpleNamespace(result=result, eng=eng, names=names, calls=calls,
                           st=eng.sync_stats, rows=list(eng.clock.ring))


@pytest.fixture(scope="module")
def runs(tmp_path_factory, native_build):
    """One run of each engine with a Recorder, made once per module."""
    made = {}

    def of(kind: str):
        if kind not in made:
            made[kind] = _hybrid_run(
                tmp_path_factory.mktemp(f"clock_{kind}"), kind, obs=True)
        return made[kind]

    return of


KINDS = pytest.mark.parametrize("kind", ["serial", "mp"])
HYBRID = pytest.mark.hybrid


@HYBRID
@KINDS
def test_the_accepted_walls_are_their_phases_sums(runs, kind):
    st = runs(kind).st
    ph = st["phase_s"]
    assert st["device_sync_s"] == ph["device_wait"] > 0
    assert st["syscall_service_s"] == (
        ph["service_ship"] + ph["service_collect"]) > 0
    assert (ph["service_ship"] > 0) == (kind == "mp")
    assert set(ph) == set(TURN_PHASES)
    assert all(isinstance(v, float) for v in ph.values())
    assert st["turn_spans"] is runs(kind).eng.clock.ring


@HYBRID
@KINDS
def test_every_row_tiles_its_turn_and_the_rows_sum_to_the_totals(runs, kind):
    run = runs(kind)
    rows, st = run.rows, run.st
    assert 0 < len(rows) < RING_TURNS  # the ring has not wrapped
    assert [r.turn for r in rows] == list(range(len(rows)))
    for r in rows:
        assert sum(getattr(r, p) for p in TURN_PHASES) == pytest.approx(
            r.t_end - r.t_start, abs=1e-9)
        assert all(getattr(r, p) >= 0 for p in TURN_PHASES[:-1])
        assert r.dispatches == 2 if r.rolled else r.dispatches == 1
        assert r.device_wait > 0 and r.window_end_ns > 0
    assert sum(r.dispatches for r in rows) == st["device_turns"]
    assert sum(r.n_staged for r in rows) == st["inject_rows"]
    assert sum(r.egress_rows for r in rows) == st["egress_rows"]
    assert sum(r.device_wait for r in rows) == pytest.approx(
        st["device_sync_s"], rel=1e-9)
    # host-only windows book their rounds to the totals and open no turn
    in_turns = sum(r.service_ship + r.service_collect for r in rows)
    assert in_turns <= st["syscall_service_s"] * (1 + 1e-9)
    for p in ("inject", "peek", "dispatch", "egress_read", "egress_apply",
              "walk"):
        assert sum(getattr(r, p) for r in rows) == pytest.approx(
            st["phase_s"][p], rel=1e-9)
    # turns do not overlap and follow one another
    assert all(a.t_end <= b.t_start for a, b in zip(rows, rows[1:]))
    # the caller's hook ran inside `callback` spans: once per window
    assert len(run.calls) > 0 and st["phase_s"]["callback"] > 0


@HYBRID
@KINDS
def test_a_rolled_back_turn_is_one_row(runs, kind):
    run = runs(kind)
    rolled = [r for r in run.rows if r.rolled]
    assert len(rolled) == run.st["fuse_rollbacks"] > 0
    assert all(r.dispatches == 2 for r in rolled)
    assert all(r.k_done >= 0 for r in run.rows)


@HYBRID
@KINDS
def test_the_workers_own_walls_lie_inside_their_rounds(runs, kind):
    run = runs(kind)
    st = run.st
    served = [r for r in run.rows if r.service_collect > 0]
    assert served
    for r in served:
        # a worker executes between the parent's first send and its last
        # receive: inside the round (ship + collect)
        assert 0 < r.worker_exec_max_s <= (
            r.service_ship + r.service_collect) * (1 + 1e-9)
    assert all(r.worker_exec_max_s == 0 for r in run.rows
               if r.service_collect == 0)
    assert st["worker_exec_sum_s"] >= st["worker_exec_max_s"] > 0
    assert st["worker_exec_max_s"] <= st["syscall_service_s"] * (1 + 1e-9)
    if kind == "serial":  # this process is the one worker
        assert st["worker_exec_sum_s"] == st["worker_exec_max_s"]
        assert st["worker_exec_max_s"] == st["phase_s"]["service_collect"]
    else:
        assert st["worker_exec_sum_s"] <= 2 * st["worker_exec_max_s"]


@HYBRID
@KINDS
def test_the_profiler_sees_exactly_the_tables_names(runs, kind):
    seen = set(runs(kind).names.seen)
    want = {f"hybrid/{p}" for p in TURN_PHASES}
    if kind == "serial":
        want.discard("hybrid/service_ship")  # one process: no ship leg
    assert seen == want


# -- 3. obs ----------------------------------------------------------------------


@HYBRID
@KINDS
def test_obs_is_handed_the_same_pairs_under_its_documented_names(runs, kind):
    run = runs(kind)
    ph = run.st["phase_s"]
    walls = run.eng.obs.metrics.phase_wall_s()
    # device_turn is the blocking wait (device_sync_s), dispatch the call
    assert walls["device_turn"] == pytest.approx(ph["device_wait"], rel=1e-9)
    assert walls["device_turn"] == pytest.approx(
        run.st["device_sync_s"], rel=1e-9)
    assert walls["dispatch"] == pytest.approx(ph["dispatch"], rel=1e-9)
    assert walls["syscall_service"] == pytest.approx(
        ph["service_collect"], rel=1e-9)
    assert walls["egress"] == pytest.approx(ph["egress_read"], rel=1e-9)
    assert walls["egress_apply"] == pytest.approx(
        ph["egress_apply"], rel=1e-9)
    if kind == "mp":
        assert walls["worker_pipe"] == pytest.approx(
            ph["service_ship"], rel=1e-9)
    else:
        assert "worker_pipe" not in walls
    # a turn that staged nothing leaves no injection span
    staged = [r for r in run.rows if r.n_staged]
    spans = run.eng.obs.metrics.report()["phases"]
    assert spans["injection"]["spans"] == len(staged)
    assert walls["injection"] == pytest.approx(
        sum(r.inject for r in staged), rel=1e-9)
    assert walls["injection"] <= ph["inject"]
    assert spans["walk"]["spans"] == len(run.rows)
    assert spans["device_turn"]["spans"] == run.st["device_turns"]
    for new in ("peek", "callback", "walk"):
        assert walls[new] == pytest.approx(ph[new], rel=1e-9)
    # obs's phases tile what the clock's do: nothing is counted twice
    assert sum(walls.values()) <= sum(ph.values()) * (1 + 1e-9)
    # and the trace's span sums are the report's (the obs law)
    for phase, wall in run.eng.obs.tracer.phase_wall_s().items():
        assert wall == pytest.approx(walls[phase], abs=1e-6)


@HYBRID
def test_the_event_log_is_the_same_obs_on_and_off(runs, tmp_path):
    on = runs("serial")
    off = _hybrid_run(tmp_path, "serial", obs=False)
    assert off.eng.obs is None
    assert on.result.log_tuples() == off.result.log_tuples()
    assert on.result.counters == off.result.counters
    ints = lambda st: {k: v for k, v in st.items() if isinstance(v, int)}
    assert ints(on.st) == ints(off.st)
    assert len(on.rows) == len(off.rows)
    assert set(on.names.seen) == set(off.names.seen)


@HYBRID
def test_metrics_json_keeps_the_totals_and_leaves_the_ring_out(
        tmp_path, native_build):
    cfg = _cfg(tmp_path / "d")
    cfg.general.stop_time = 500 * MS
    cfg.experimental.obs_metrics = True
    sim = Simulation(cfg)
    sim.run(write_data=False)
    rep = json.loads(Path(sim.obs.finalized["metrics_path"]).read_text())
    sync = rep["hybrid_sync"]
    assert "turn_spans" not in sync
    assert set(sync["phase_s"]) == set(TURN_PHASES)
    assert sync["phase_s"]["device_wait"] == sync["device_sync_s"] > 0
    assert sync["device_turns"] == sim.engine.sync_stats["device_turns"]
    assert len(sim.engine.sync_stats["turn_spans"]) > 0


# -- 4. the fused driver ---------------------------------------------------------


def _mesh_cfg(tmp_path):
    cfg = columnar_mesh_config(64, queue_capacity=16, pops_per_round=2)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.general.stop_time = 100 * MS
    cfg.general.data_directory = str(tmp_path / "d")
    cfg.general.heartbeat_interval = None
    return cfg


@pytest.mark.parametrize("obs", [False, True], ids=["obs_off", "obs_on"])
def test_the_fused_drivers_four_phases(tmp_path, obs):
    eng = tpu_engine.TpuEngine(_mesh_cfg(tmp_path), log_capacity=0)
    names = Names()
    eng.clock.use_annotator(names)
    if obs:
        eng.obs = Recorder()
    eng.run(mode="device")
    # a run is one turn of the clock: ``run`` is its residual
    assert names.seen == ["fused/run", "fused/state_build", "fused/dispatch",
                          "fused/device_wait", "fused/collect"]
    ph = eng.clock.phase_s
    assert tuple(ph) == tpu_engine.FUSED_PHASES + (tpu_engine.RUN_PHASE,)
    assert all(v > 0 for v in ph.values())
    assert len(eng.clock.ring) == 1
    if obs:
        walls = eng.obs.metrics.phase_wall_s()
        assert walls == {"state_build": ph["state_build"],
                         "dispatch": ph["dispatch"],
                         "device_turn": ph["device_wait"],
                         "collect": ph["collect"]}


def test_the_step_driver_books_every_round_to_the_same_two_phases(tmp_path):
    eng = tpu_engine.TpuEngine(_mesh_cfg(tmp_path), log_capacity=0)
    names = Names()
    eng.clock.use_annotator(names)
    eng.obs = Recorder(trace=True)
    res = eng.run(mode="step")
    rounds = res.rounds + 1  # the last call finds the run done
    assert names.seen.count("fused/dispatch") == rounds
    assert names.seen.count("fused/device_wait") == rounds
    assert set(names.seen) == {
        f"fused/{p}"
        for p in tpu_engine.FUSED_PHASES + (tpu_engine.RUN_PHASE,)}
    spans = eng.obs.metrics.report()["phases"]
    assert spans["device_turn"]["spans"] == spans["dispatch"]["spans"] == rounds
    waits = [e for e in eng.obs.tracer.events if e["cat"] == "device_turn"]
    assert {e["name"] for e in waits} == {"device_round"}
    assert all("active" in e["args"] for e in waits)
    assert eng.obs.metrics.phase_wall_s()["device_turn"] == pytest.approx(
        eng.clock.phase_s["device_wait"], rel=1e-9)


# -- 5. one journal row a run ------------------------------------------------------


def _tiles(row, phases) -> None:
    """The phases, ``run`` among them, sum to the row's wall."""
    assert sum(getattr(row, p) for p in phases) == pytest.approx(
        row.t_end - row.t_start, rel=1e-9)
    assert all(getattr(row, p) >= 0 for p in phases)


@pytest.mark.parametrize("mode", ["device", "step"])
def test_a_run_leaves_one_row_in_the_ring_and_in_the_journal(tmp_path, mode):
    journal = clock_mod.journal["fused"]
    eng = tpu_engine.TpuEngine(_mesh_cfg(tmp_path), log_capacity=0)
    before = len(journal)
    first = eng.run(mode=mode)
    again = eng.run(mode=mode)
    assert len(eng.clock.ring) == 2 == len(journal) - before
    rows = list(journal)[-2:]
    assert rows == list(eng.clock.ring)
    assert [r.turn for r in rows] == [0, 1]
    assert rows[0].owner == rows[1].owner
    for row, res in zip(rows, (first, again)):
        _tiles(row, eng.clock.phases)
        assert row.run > 0
        assert row.mode == int(mode == "step") and row.segments == 1
        assert row.rounds == res.rounds
        assert row.lane_iters == res.counters["lane_iters"]
        assert row.lanes == 64 and row.pops_per_iter == 2
        assert row.log_capacity == 0
    assert [r.state_reused for r in rows] == [0, 1]
    # ints all, under half a kilobyte
    assert all(isinstance(getattr(rows[0], n), int)
               for n in tpu_engine.FUSED_NOTES)
    assert len(rows[0]) * 8 < 512
    assert eng.run_row() == rows[1]._asdict()


def test_two_engines_rows_are_apart_by_owner(tmp_path):
    journal = clock_mod.journal["fused"]
    a = tpu_engine.TpuEngine(_mesh_cfg(tmp_path), log_capacity=0)
    b = tpu_engine.TpuEngine(_mesh_cfg(tmp_path), log_capacity=0)
    a.run(mode="device")
    b.run(mode="device")
    a.run(mode="device")
    last = list(journal)[-3:]
    assert last[0].owner == last[2].owner != last[1].owner
    assert [r.turn for r in last] == [0, 0, 1]


def test_a_faulted_run_is_one_row_of_as_many_dispatches_as_segments():
    import test_phold_mesh

    eng = tpu_engine.TpuEngine(test_phold_mesh._faulted_cfg("tpu"),
                               log_capacity=0)
    names = Names()
    eng.clock.use_annotator(names)
    eng.run(mode="device")
    segments = eng.lane_plane["fault_segments"]
    assert segments > 1
    assert names.seen[0] == "fused/run" and names.seen.count("fused/run") == 1
    assert names.seen.count("fused/dispatch") == segments
    assert names.seen.count("fused/fault_swap") == segments - 1
    assert len(eng.clock.ring) == 1
    row = eng.clock.ring[-1]
    assert row is clock_mod.journal["fused"][-1]
    assert row.segments == segments and row.fault_swap > 0
    # under a profiler session the row rides the trace too
    assert names.rows == [("fused/row", row._asdict())]
    assert eng.clock.phases == (
        tpu_engine.FUSED_PHASES
        + (tpu_engine.FAULT_PHASE, tpu_engine.RUN_PHASE))
    _tiles(row, eng.clock.phases)


def test_a_run_that_raises_still_closes_its_row():
    import test_phold_mesh

    eng = tpu_engine.TpuEngine(
        test_phold_mesh._cfg(64, 4, 5, tpu_cross_capacity=1),
        log_capacity=0)
    with pytest.raises(RuntimeError, match="capacity overflow"):
        eng.run(mode="device")
    assert len(eng.clock.ring) == 1
    _tiles(eng.clock.ring[-1], eng.clock.phases)
    # and the next run opens its own turn
    with pytest.raises(RuntimeError, match="capacity overflow"):
        eng.run(mode="device")
    assert len(eng.clock.ring) == 2


def test_the_journal_is_bounded_and_only_a_journal_clock_writes_it():
    plain = _clock()
    with plain.turn():
        pass
    assert "t" not in clock_mod.journal
    assert "owner" not in plain.ring[-1]._fields
    c = _clock(journal=True)
    other = _clock(journal=True)
    for _ in range(RING_TURNS + 5):
        with c.turn():
            pass
    with other.turn():
        pass
    rows = clock_mod.journal["t"]
    assert len(rows) == RING_TURNS == rows.maxlen
    assert rows[-1] is other.ring[-1] and rows[-2] is c.ring[-1]
    assert rows[-1].owner != rows[-2].owner
    assert rows[0].turn == 6  # the oldest rows went
    del clock_mod.journal["t"]


def test_trace_annotation_is_imported_in_one_module():
    hits = [p for p in (REPO / "shadow_tpu").rglob("*.py")
            if "TraceAnnotation" in p.read_text()
            and "import TraceAnnotation" in p.read_text()]
    assert hits == [Path(clock_mod.__file__)]
