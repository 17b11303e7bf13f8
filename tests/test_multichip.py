"""Multi-chip sharded lane plane (shadow_tpu/parallel/, docs/multichip.md).

The contracts under test (conftest.py forces 8 virtual CPU devices, so
every mesh shape here runs on any box):

1. **Device-count invariance** — the mesh shape never changes results
   (the multi-chip analog of the reference's src/test/determinism/).
   The raw ``parallel.make_sharded_run_fn`` driver ends in a final
   ``LaneState`` equal field by field to the single-device run's (log
   as a set) and to the CPU oracle's log; the full Simulation facade
   with ``experimental.mesh_devices`` set produces a bit-identical
   event log AND a byte-identical ``NETOBS_*.json`` artifact at every
   mesh shape, netobs ON (the per-host counter block shards with its
   lanes, the [24] window histogram shard-then-reduces).
2. **Classification exhaustiveness** — ``parallel.check_classification``
   rejects unclassified, stale, and double-classified LaneState fields,
   so a future field cannot silently pick up the wrong sharding.
3. **Negotiation fallback law** — ``negotiate_devices`` never raises:
   over-asks and indivisible lane counts step down to the largest
   usable mesh.
4. **Columnar = classic** — the columnar 100k-host factory builds the
   same engine tables/params/initial events as the classic per-host
   walk, runs identically, and is rejected on the hybrid path.
5. **Hybrid transfer invariance** — the hybrid backend under a mesh
   keeps its ``sync_stats`` transfer counts and results unchanged
   (the host<->device boundary stays replicated).
"""

import copy
import json
import logging

import jax
import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import LaneCompatError, TpuEngine
from shadow_tpu.config.columnar import columnar_mesh_config
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.config.presets import flagship_mesh_config
from shadow_tpu.engine.sim import Simulation

pytestmark = pytest.mark.multichip


def _phold_cfg(data_dir, mesh_devices: int = 0) -> ConfigOptions:
    """8 phold hosts with netobs on, divisible by every mesh shape up to
    8.  2 pops/round, not the default 8: under a mesh the slot walk is
    a Python-unrolled body (``lanes._force_unroll``), and on XLA:CPU's
    per-op thunk dispatch 8 unrolled pops need 20-40 s to reach EACH
    all-gather rendezvous — a run that never ends in useful time — where
    2 pops end in seconds.  The invariance law does not depend on K."""
    return ConfigOptions.from_yaml(f"""
general: {{stop_time: 300ms, seed: 11, data_directory: {data_dir},
           heartbeat_interval: null}}
experimental: {{network_backend: tpu, netobs: true,
               tpu_events_per_round: 2,
               mesh_devices: {mesh_devices}}}
hosts:
  n:
    count: 8
    processes: [{{path: phold, args: --messages 3 --size 600}}]
""")


def _facade_run(tmp_path, d: int):
    """(log tuples, NETOBS bytes, engine) for a facade run at mesh
    request ``d`` (0 = single-device)."""
    sim = Simulation(_phold_cfg(tmp_path / f"d{d}", mesh_devices=d))
    res = sim.run(write_data=False)
    arts = sorted((tmp_path / f"d{d}").glob("NETOBS_*.json"))
    assert len(arts) == 1
    return res.log_tuples(), arts[0].read_bytes(), sim.engine


def _final_state(engine: TpuEngine, mesh=None) -> lanes.LaneState:
    """Final state of the fused free-run: single-device, or through the
    raw sharded run function under ``mesh``."""
    state = engine.initial_state()
    if mesh is None:
        run = lanes.make_run_fn(engine.params, engine.tables)
    else:
        state = parallel.shard_state(state, mesh)
        run = parallel.make_sharded_run_fn(engine.params, engine.tables, mesh)
    return jax.block_until_ready(run(state))


# -- 1. device-count invariance, netobs on --------------------------------


# the only tier-1 test of the raw sharded run function; tier-1 keeps the
# 2-device shape, the 8-device shapes run slow-marked and at gate scale in
# `make multichip-smoke`
@pytest.mark.parametrize(
    "n_devices", [2, pytest.param(8, marks=pytest.mark.slow)]
)
def test_sharded_run_bit_identical(tmp_path, monkeypatch, n_devices):
    walks = []  # (trace is under _force_unroll, site is lane-axis) per walk
    real_walk = lanes.scan_or_unroll

    def spy(step, carry, xs, length, spmd_unroll=False):
        walks.append((lanes._SPMD_UNROLL, spmd_unroll))
        return real_walk(step, carry, xs, length, spmd_unroll)

    monkeypatch.setattr(lanes, "scan_or_unroll", spy)
    engine = TpuEngine(_phold_cfg(tmp_path))
    single = _final_state(engine)
    assert walks and not any(forced for forced, _ in walks)
    sharded = _final_state(engine, parallel.make_mesh(n_devices))
    # under the mesh the slot walk took the forced-unroll form, the one a
    # chip runs — the cheap config must not buy its speed by dodging it
    assert (True, True) in walks
    # an idle run would pass the comparison below vacuously
    assert int(single.log_count) > 0 and int(single.rounds) > 1
    for field in lanes.LaneState._fields:
        a, b = np.asarray(getattr(single, field)), np.asarray(getattr(sharded, field))
        if field == "log":
            n = int(single.log_count)
            a, b = a[:n], b[:n]
            # log append order may differ across shardings; content may not
            a = a[np.lexsort(a.T[::-1])]
            b = b[np.lexsort(b.T[::-1])]
        np.testing.assert_array_equal(a, b, err_msg=field)


@pytest.mark.slow
def test_sharded_matches_cpu_reference(tmp_path):
    cfg = _phold_cfg(tmp_path)
    cpu = CpuEngine(cfg).run()
    engine = TpuEngine(cfg)
    final = _final_state(engine, parallel.make_mesh(8))
    tpu = engine.collect(final, wall=0.0)
    assert cpu.log_tuples() == tpu.log_tuples()


def test_facade_invariant_2dev(tmp_path):
    """Tier-1 slice of the invariance law (one box-affordable sharded
    compile); the 4/8-device shapes run below (slow) and at gate scale
    in ``make multichip-smoke``."""
    log1, netobs1, eng1 = _facade_run(tmp_path, 0)
    assert eng1.mesh is None
    assert log1  # a silent empty log would vacuously pass
    rep = json.loads(netobs1)
    assert rep["totals"]["sent"] > 0
    log2, netobs2, eng2 = _facade_run(tmp_path, 2)
    assert eng2.mesh is not None and eng2.mesh.devices.size == 2
    assert log2 == log1, "event log diverges at 2 devices"
    assert netobs2 == netobs1, "NETOBS diverges at 2 devices"


@pytest.mark.slow
def test_facade_invariant_4_and_8_dev(tmp_path):
    log1, netobs1, _ = _facade_run(tmp_path, 0)
    for d in (4, 8):
        log_d, netobs_d, eng_d = _facade_run(tmp_path, d)
        assert eng_d.mesh is not None
        assert eng_d.mesh.devices.size == d
        assert log_d == log1, f"event log diverges at {d} devices"
        assert netobs_d == netobs1, f"NETOBS diverges at {d} devices"


@pytest.mark.slow
def test_mesh_step_driver_matches_device(tmp_path):
    """The pausable step driver under a mesh (one sharded round per
    call) ends bit-identical to the fused sharded free-run."""
    eng_a = TpuEngine(_phold_cfg(tmp_path / "a"))
    eng_a.attach_mesh(parallel.make_mesh(2))
    ra = eng_a.run(mode="device")
    eng_b = TpuEngine(_phold_cfg(tmp_path / "b"))
    eng_b.attach_mesh(parallel.make_mesh(2))
    rb = eng_b.run(mode="step")
    assert ra.log_tuples() == rb.log_tuples()
    assert ra.counters == rb.counters


# -- 2. classification exhaustiveness -------------------------------------


def test_classification_covers_live_lanestate():
    parallel.check_classification()  # must not raise on the live fields


def test_classification_rejects_planted_field():
    fields = list(lanes.LaneState._fields) + ["planted_future_field"]
    with pytest.raises(AssertionError, match="planted_future_field"):
        parallel.check_classification(fields)


def test_classification_rejects_stale_field():
    fields = [f for f in lanes.LaneState._fields if f != "q_thi"]
    with pytest.raises(AssertionError, match="q_thi"):
        parallel.check_classification(fields)


def test_classification_partition_is_disjoint():
    assert not (parallel.LANE_FIELDS & parallel.REPLICATED_FIELDS)


# -- 3. negotiation fallback law ------------------------------------------


def test_negotiate_steps_down_to_divisor(caplog):
    with caplog.at_level(logging.WARNING, logger="shadow_tpu.parallel"):
        assert parallel.negotiate_devices(4, 6, available=8) == 3
    assert any("falling back" in r.message or "not divisible" in r.message
               for r in caplog.records)


def test_negotiate_caps_at_available():
    assert parallel.negotiate_devices(8, 8, available=2) == 2


def test_negotiate_never_exceeds_lanes():
    assert parallel.negotiate_devices(8, 1, available=8) == 1


def test_negotiate_all_available_default():
    assert parallel.negotiate_devices(None, 16, available=8) == 8


def test_negotiate_from_config_mesh_devices():
    """``mesh_devices`` is the one spelling of a mesh request: 0 and 1 ask
    for none, N for up to N, more than the process has for what it has."""
    cfg = flagship_mesh_config(8, sim_seconds=1, backend="tpu")
    have = len(jax.devices())
    assert have == 8
    for asked, got in ((0, 1), (1, 1), (4, 4), (2 * have, have)):
        cfg.experimental.mesh_devices = asked
        assert parallel.negotiate_from_config(cfg, 8) == got


def test_engine_rejects_indivisible_mesh():
    cfg = flagship_mesh_config(6, sim_seconds=1, backend="tpu")
    eng = TpuEngine(cfg)
    with pytest.raises(LaneCompatError, match="divisible"):
        eng.attach_mesh(parallel.make_mesh(4))


# -- 4. columnar factory ---------------------------------------------------


def test_columnar_constants_match_lanes():
    from shadow_tpu.config import columnar as cmod

    assert cmod.M_TGEN_MESH == lanes.M_TGEN_MESH
    assert cmod.EV_LOCAL == lanes.LOCAL


def test_columnar_tables_equal_classic():
    import jax

    ea = TpuEngine(flagship_mesh_config(32, sim_seconds=1, backend="tpu"))
    eb = TpuEngine(columnar_mesh_config(32, sim_seconds=1))
    assert ea.params == eb.params
    la = jax.tree_util.tree_leaves(ea.tables)
    lb = jax.tree_util.tree_leaves(eb.tables)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(ea._init_cols, eb._init_cols):
        assert np.array_equal(a, b)


def test_columnar_run_matches_classic(tmp_path):
    ra = TpuEngine(
        flagship_mesh_config(16, sim_seconds=1, backend="tpu")
    ).run(mode="device")
    rb = TpuEngine(columnar_mesh_config(16, sim_seconds=1)).run(
        mode="device"
    )
    assert ra.log_tuples() == rb.log_tuples()
    assert ra.counters == rb.counters


def test_columnar_hosts_match_classic_expansion():
    ca = flagship_mesh_config(5, sim_seconds=1, backend="tpu")
    cb = columnar_mesh_config(5, sim_seconds=1)
    assert [h.hostname for h in cb.hosts] == [
        h.hostname for h in ca.hosts
    ]
    assert len(cb.hosts) == 5 and cb.hosts[-1].processes[0].path == "tgen-mesh"


def test_columnar_rejected_on_hybrid():
    cfg = columnar_mesh_config(8, sim_seconds=1)
    ext = np.zeros(8, dtype=bool)
    ext[0] = True
    with pytest.raises(LaneCompatError, match="columnar"):
        TpuEngine(cfg, external=ext)


def test_columnar_100k_scale_builds_fast():
    """The acceptance bound, at 1/10 scale to keep tier-1 lean: table
    construction is vectorized, so 10k hosts must build in well under
    3 s (100k measured ~2 s end to end; scripts/multichip_smoke.py and
    the bench run the full 100k point)."""
    import time

    t0 = time.perf_counter()
    cfg = columnar_mesh_config(10_000, sim_seconds=1)
    eng = TpuEngine(cfg)
    eng.initial_state()
    assert time.perf_counter() - t0 < 3.0
    assert len(cfg.hosts) == 10_000
    assert int(eng.tables.model[0]) == lanes.M_TGEN_MESH


# -- 5. hybrid transfer invariance under mesh ------------------------------


TRANSFER_KEYS = ("device_turns", "scalar_reads", "h2d_copies",
                 "inject_blocks", "inject_rows", "inject_bytes",
                 "egress_head_reads", "egress_reads", "egress_rows",
                 "egress_bytes")


@pytest.fixture(scope="module")
def native_build():
    import subprocess
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    subprocess.run(
        ["make", "-C", str(repo / "native")], check=True,
        capture_output=True,
    )


@pytest.mark.hybrid
@pytest.mark.slow
def test_hybrid_sync_stats_unchanged_under_mesh(tmp_path, native_build):
    from tests.test_turns import _hybrid_cfg

    cfg = _hybrid_cfg(tmp_path / "h0", workers=1, turns=False)
    cfg.experimental.tpu_events_per_round = 2  # see _phold_cfg
    base = Simulation(cfg)
    r0 = base.run(write_data=False)
    s0 = dict(base.engine.sync_stats)
    cfg = _hybrid_cfg(tmp_path / "h2", workers=1, turns=False)
    cfg.experimental.tpu_events_per_round = 2
    cfg.experimental.mesh_devices = 2
    meshed = Simulation(cfg)
    r2 = meshed.run(write_data=False)
    s2 = dict(meshed.engine.sync_stats)
    assert meshed.engine.device.mesh is not None
    assert meshed.engine.device.mesh.devices.size == 2
    assert r2.log_tuples() == r0.log_tuples()
    for k in TRANSFER_KEYS:
        assert s2.get(k) == s0.get(k), f"sync_stats[{k}] changed under mesh"
