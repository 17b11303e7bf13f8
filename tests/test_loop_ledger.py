"""The loop ledger (ISSUE 50): what the fused loop's iterations and windows
held, counted inside the program (``lanes.LaneState.loop_hist`` /
``loop_acc``, read in ``TpuEngine.collect``'s one transfer into
``lane_plane``).

(a) its laws, on the small parity configurations the suite already builds —
    PHOLD on one switch and on a routed lossy graph, gossip lossless,
    routed and under a fault schedule, the mixed tgen / stream mesh and the
    routed all-TCP tier — on ``mode="device"`` and ``mode="step"``;
(b) the ledger of a ``device`` run, a ``step`` run and mesh shapes 2 / 4
    are equal, and the results are still the oracle's;
(c) a check that shares no code with the ledger: ``round_iters`` of a
    ``device`` run is the histogram of the per-round differences of
    ``lane_iters`` a ``step`` run's ``on_window`` sees;
(d) a program of passive lanes carries none: no leaf, nothing traced, the
    lowered text the parent's;
(e) the gauges reach ``lane_plane``, ``sim-stats.json`` (with the run's own
    row, ``fused_run``) and the obs gauges;
(f) the one bucket law: ``obs.netobs.hist_percentile`` beside
    ``hist_bucket``, and the device's ``hist_fold_index``.
"""

import functools
import hashlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

import test_gossip_mesh as gossip_tests
import test_phold_mesh as phold_tests
import test_routed_factory as routed_tests
from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import LOOP_GAUGES, TpuEngine
from shadow_tpu.config.presets import mixed_flagship_config
from shadow_tpu.obs import netobs

#: counters one backend alone keeps (as benchmarks/lib/compare.py)
BACKEND_ONLY = {"lane_iters", "lane_delivered", "lane_sends"}


def _mixed(backend="tpu"):
    cfg = mixed_flagship_config(40, sim_seconds=1)
    cfg.experimental.network_backend = backend
    return cfg


def _gossip_faulted(backend="tpu"):
    return gossip_tests._wan_cfg(backend, stop_ms=2600,
                                 faults=gossip_tests._chaos())


def _gossip_ring(backend="tpu"):
    cfg = gossip_tests._cfg(16, 2, 2, backend, bursts=("1 s",))
    cfg.general.stop_time = 1200 * gossip_tests.MS
    return cfg


#: name -> backend -> configuration
CONFIGS = {
    "phold": lambda b="tpu": phold_tests._cfg(64, 4, 10, b),
    "phold_wan": lambda b="tpu": phold_tests._wan_cfg(b),
    "gossip": lambda b="tpu": gossip_tests._cfg(64, 4, 3, b),
    "gossip_wan": lambda b="tpu": gossip_tests._wan_cfg(b),
    "gossip_faulted": _gossip_faulted,
    "mixed": _mixed,
    "routed_tcp": lambda b="tpu": routed_tests.rehearsal(1, b),
}
NAMES = sorted(CONFIGS)
#: the sharded build takes gossip's fan-out in loop form, which XLA:CPU
#: runs 35x slower with every send (tests/test_gossip_mesh.py): on a mesh
#: the two gossip programs run as rings of 16 at degree 2, and a faulted
#: run's segments are single-device programs, mesh or none
CONFIGS_ON_A_MESH = {
    **{name: CONFIGS[name]
       for name in ("phold", "phold_wan", "mixed", "routed_tcp")},
    "gossip_ring": lambda b="tpu": _gossip_ring(b),
    "gossip_wan_ring": lambda b="tpu": gossip_tests._wan_cfg(
        b, nodes=16, degree=2, messages=2, graph_nodes=4, bursts=("1 s",),
        stop_ms=1400),
}
#: how a run is driven: the driver's mode and the mesh's devices
DRIVES = {"device": ("device", 1), "step": ("step", 1),
          "mesh2": ("device", 2), "mesh4": ("device", 4)}


@functools.lru_cache(maxsize=None)
def _run(name, drive):
    """``(lane_plane, result, row, params)`` of one run, log off."""
    mode, devices = DRIVES[drive]
    eng = TpuEngine({**CONFIGS, **CONFIGS_ON_A_MESH}[name](),
                    log_capacity=0)
    if devices > 1:
        eng.attach_mesh(parallel.make_mesh(devices))
    res = eng.run(mode=mode)
    return dict(eng.lane_plane), res, eng.run_row(), eng.params


@functools.lru_cache(maxsize=None)
def _oracle(name):
    return CpuEngine(CONFIGS[name]("cpu")).run()


def _ledger(plane):
    return {k: v for k, v in plane.items() if k.startswith("loop_")}


# -- (a) the laws --------------------------------------------------------------

@pytest.mark.parametrize("mode", ["device", "step"])
@pytest.mark.parametrize("name", NAMES)
def test_the_ledgers_laws(name, mode):
    plane, res, row, p = _run(name, mode)
    iters, rounds = res.counters["lane_iters"], res.rounds
    hist = plane["loop_hist"]
    assert sorted(hist) == ["round_iters", "scheme"]
    assert hist["scheme"] == "log2"
    by_round = hist["round_iters"]
    assert len(by_round) == netobs.HIST_BUCKETS

    # every window takes at least one iteration
    assert sum(by_round) == rounds <= iters
    top = max(b for b, v in enumerate(by_round) if v)
    assert plane["loop_round_iters_max"] <= iters
    assert netobs.hist_bucket(plane["loop_round_iters_max"]) == top
    assert (1 <= plane["loop_round_iters_p50"] <= plane["loop_round_iters_p95"]
            <= plane["loop_round_iters_max"])
    # the mean lies under the maximum
    assert iters <= rounds * plane["loop_round_iters_max"]

    # pop slots: live ones of those offered, at least one a popping lane,
    # at most ``pops_per_iter`` of them
    offered = iters * p.pops_per_iter * p.n_lanes
    assert plane["loop_active_lanes"] <= plane["loop_pop_slots"] <= offered
    assert plane["loop_pop_slots"] <= p.pops_per_iter * plane[
        "loop_active_lanes"]
    if not p.stream_tiered:
        # no iteration without a popping lane (only a tier's own are)
        assert plane["loop_active_lanes"] >= iters
    # (where every lane is a stream endpoint the [N] pass holds nothing:
    # the tier's slots are the work, and the ledger says so)
    tier_lanes = 2 * len(p.stream_clients) if p.stream_tiered else 0
    assert (plane["loop_active_lanes"] > 0) == (tier_lanes < p.n_lanes)
    if "phold_hops" in res.counters:
        # a hop is one PACKET and one DELIVERY pop; what is in flight at
        # the stop has popped neither or one
        alive = p.n_lanes * 4
        assert plane["loop_pop_slots"] >= 2 * res.counters["phold_hops"] - alive
    if p.stream_tiered:
        tier = iters * p.stream_pops * 2 * len(p.stream_clients)
        assert 0 < plane["loop_tier_pop_slots"] <= tier
    else:
        assert "loop_tier_pop_slots" not in plane

    assert 0 <= plane["loop_iters_no_send"] <= iters
    # something was sent, but where the [N] pass holds nothing
    assert (plane["loop_iters_no_send"] < iters) == (tier_lanes < p.n_lanes)

    # passes of the exchange: one an iteration, but where a fan-out
    # iteration's sending slots overran the budget
    passes = plane["loop_exchange_passes"]
    assert passes >= iters
    if p.sends_per_pop > 1:
        if plane["exchange_slot_peak"] <= plane["exchange_slot_budget"]:
            assert passes == iters
        # every iteration past one pass took at least one more
        assert iters - plane["exchange_compact_iters"] <= passes - iters
    else:
        assert passes == iters

    # the run's row carries what the plane does
    assert row["lane_iters"] == iters and row["rounds"] == rounds
    assert row["mode"] == int(mode == "step")
    assert row["lanes"] == p.n_lanes
    assert row["pops_per_iter"] == p.pops_per_iter
    for key in LOOP_GAUGES:
        assert row[key] == plane.get(key, 0)
    assert set(LOOP_GAUGES) >= {k for k in _ledger(plane) if k != "loop_hist"}


def test_a_fan_out_iteration_past_the_budget_takes_more_passes(monkeypatch):
    """The flood's front with the slot tile cut to 8: some iterations take
    several passes, and the ledger counts them."""
    monkeypatch.setattr(lanes, "_SLOT_TILE", 8)
    eng = TpuEngine(gossip_tests._cfg(64, 4, 3), log_capacity=0)
    res = eng.run(mode="device")
    plane, iters = eng.lane_plane, res.counters["lane_iters"]
    assert plane["exchange_slot_peak"] > plane["exchange_slot_budget"]
    assert plane["loop_exchange_passes"] > iters
    assert (iters - plane["exchange_compact_iters"]
            <= plane["loop_exchange_passes"] - iters)


# -- (b) one ledger however the run is driven ---------------------------------

@pytest.mark.parametrize("name, drive", [
    *((name, "step") for name in NAMES),
    *((name, drive) for name in sorted(CONFIGS_ON_A_MESH)
      for drive in ("mesh2", "mesh4")),
])
def test_the_ledger_is_the_device_runs_however_driven(name, drive):
    plane, res = _run(name, "device")[:2]
    other, other_res = _run(name, drive)[:2]
    assert _ledger(other) == _ledger(plane)
    assert other_res.counters == res.counters
    assert other_res.rounds == res.rounds


@pytest.mark.parametrize("name", NAMES)
def test_the_results_are_still_the_oracles(name):
    res = _run(name, "device")[1]
    oracle = _oracle(name)
    shared = {k: v for k, v in res.counters.items() if k not in BACKEND_ONLY}
    want = {k: v for k, v in oracle.counters.items() if k not in BACKEND_ONLY}
    # (the oracle counts tgen's sends; the lane engine's books are its own)
    want.pop("tgen_sent_bytes", None)
    assert shared == want
    assert res.rounds == oracle.rounds
    # no key of the ledger is a counter: the oracle has no iterations
    assert not any(k.startswith("loop_") for k in res.counters)


# -- (c) the step driver's own count -------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_round_iters_is_what_a_step_runs_windows_took(name):
    eng = TpuEngine(CONFIGS[name](), log_capacity=0)
    seen = []
    res = eng.run(
        mode="step",
        on_window=lambda *_a: seen.append(int(eng._live_state.iters)))
    took = np.diff([0] + seen)
    assert len(took) == res.rounds and took.sum() == res.counters["lane_iters"]
    want = [0] * netobs.HIST_BUCKETS
    for count in took:
        want[netobs.hist_bucket(count)] += 1
    plane = _run(name, "device")[0]
    assert plane["loop_hist"]["round_iters"] == want
    assert plane["loop_round_iters_max"] == took.max()


# -- (d) a program of passive lanes ---------------------------------------------

def test_a_program_of_passive_lanes_carries_no_ledger():
    eng = TpuEngine(phold_tests._passive_mesh(), log_capacity=0)
    assert eng.params.all_passive
    init = eng.initial_state()
    assert init.loop_hist == () and init.loop_acc == ()
    text = phold_tests._lowered(phold_tests._passive_mesh())
    assert (hashlib.sha256(text.encode()).hexdigest()
            == gossip_tests.PARENT_TEXT["passive_mesh"])
    eng.run(mode="device")
    assert not _ledger(eng.lane_plane)
    row = eng.run_row()
    assert row["lane_iters"] > 0 and row["lanes"] == eng.params.n_lanes
    assert all(row[key] == 0 for key in LOOP_GAUGES)
    # where some lane's model is active the ledger is in the carry
    active = TpuEngine(phold_tests._cfg(64, 4, 5), log_capacity=0)
    init = active.initial_state()
    assert init.loop_hist.shape == (lanes.NB_HIST_BUCKETS,)
    assert init.loop_acc.shape == (len(lanes.LoopAcc._fields),) == (7,)
    # the carry's last two leaves, and nothing else of it, are the ledger's
    packed, bare = lanes.pack_state(init), lanes.pack_state(
        eng.initial_state())
    assert len(packed) == len(bare) and bare[-2:] == ((), ())
    assert packed[2].shape == bare[2].shape  # the scalar vector
    back = lanes.unpack_state(packed)
    assert back.loop_acc.shape == (7,) and back.loop_hist.shape == (24,)
    assert lanes.unpack_state(bare).loop_acc == ()


# -- (e) wherever a run is read ---------------------------------------------------

def test_the_gauges_reach_sim_stats_and_obs(tmp_path):
    sim, _res = phold_tests._facade_run(64, 4, 10, tmp_path)
    stats = json.loads((sim.data_dir / "sim-stats.json").read_text())
    want = _run("phold", "device")[0]
    gauges = sim.obs.finalized["report"]["gauges"]
    keys = [k for k in _ledger(want) if k != "loop_hist"]
    assert len(keys) == len(LOOP_GAUGES) - 1 == 7  # no tier here
    for key in keys:
        assert stats["lane_plane"][key] == want[key] == gauges[key]
    # the histogram whole: sim-stats.json's alone
    assert stats["lane_plane"]["loop_hist"] == want["loop_hist"]
    assert "loop_hist" not in gauges
    # the run's own row: its phases tile its wall
    row = stats["fused_run"]
    phases = ("state_build", "dispatch", "device_wait", "collect", "run")
    assert sum(row[ph] for ph in phases) == pytest.approx(
        row["t_end"] - row["t_start"], rel=1e-6)
    assert row["loop_pop_slots"] == want["loop_pop_slots"]


# -- (f) one bucket law -------------------------------------------------------------

def test_a_percentile_is_its_buckets_upper_edge():
    pct = netobs.hist_percentile
    assert pct([0] * netobs.HIST_BUCKETS, 0.5) == 0
    hist = [0] * netobs.HIST_BUCKETS
    for count in (1, 1, 2, 3, 5, 9, 9, 9, 17, 40):
        hist[netobs.hist_bucket(count)] += 1
    assert hist[:6] == [2, 2, 1, 3, 1, 1]
    assert pct(hist, 0.5) == 7  # the fifth of ten: the bucket [4, 8)
    assert pct(hist, 0.8) == 15
    assert pct(hist, 0.95) == 63
    assert pct(hist, 0.95, ceiling=40) == 40
    assert pct(hist, 0.0) == 1


def test_the_devices_fold_is_the_hosts_bucket():
    counts = [0, 1, 2, 3, 4, 7, 8, 1000, 2 ** 23, 2 ** 30]
    do, idx = lanes.hist_fold_index(jnp.asarray(counts, dtype=jnp.int32),
                                    True)
    assert do.tolist() == [c > 0 for c in counts]
    assert idx.tolist() == [netobs.hist_bucket(c) if c
                            else lanes.NB_HIST_BUCKETS for c in counts]
    _do, off = lanes.hist_fold_index(jnp.int32(5), False)
    assert int(off) == lanes.NB_HIST_BUCKETS
    assert lanes.NB_HIST_BUCKETS == netobs.HIST_BUCKETS
