"""The hybrid turn's host<->device boundary (ISSUE 40): ONE packed
``int32[W]`` block in (``lanes.TurnBlock``), the egress head beside the
scalars out (``lanes.HYB_EGRESS_HEAD``).

1. ``TurnBlock``: pack -> unpack gives every value back bit for bit, on
   the host (numpy) and on the device (jax).
2. The device entry point fed the packed block computes what the parent's
   argument list computed: the body below the entry's first line is the
   parent's, so the reference hands it the old arguments directly.
3. ``HybridEngine._read_egress``: the head serves a turn's rows with no
   device program; only what lies past it is read, and counted.
4. The fused cells never enter any of this: three more tiny fused
   programs keep the lowered text they had at the parent commit (beside
   the three ``tests/test_gossip_mesh.py`` pins).
"""

import hashlib
import subprocess
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.options import ConfigOptions

REPO = Path(__file__).resolve().parents[1]
BUILD = REPO / "native" / "build"
MS = 1_000_000


# -- 1. the layout ----------------------------------------------------------


def _random_values(lay, seed):
    rng = np.random.default_rng(seed)

    def word(n):
        return rng.integers(-2**31, 2**31 - 1, n, dtype=np.int32)

    inj = {name: word(lay.inject_batch) for name in lay.COLUMNS}
    inj["valid"] = rng.integers(0, 2, lay.inject_batch).astype(bool)
    return inj, word(lay.ext_slots), word(lay.ext_slots), int(word(1)[0]), 5


@pytest.mark.parametrize("inject_batch", [8, 512])
@pytest.mark.parametrize("where", ["host", "device"])
def test_pack_then_unpack_is_the_identity(inject_batch, where):
    lay = lanes.TurnBlock(inject_batch, 16)
    assert lay.width == 7 * inject_batch + 2 * 16 + 2
    inj, hi, lo, used, k = _random_values(lay, inject_batch)
    block = lay.pack(inj, hi, lo, used, k)
    assert block.dtype == np.int32 and block.shape == (lay.width,)
    if where == "device":
        block = jnp.array(block)
    inj2, hi2, lo2, used2, k2 = lay.unpack(block)
    assert sorted(inj2) == sorted(lay.COLUMNS)
    for name in lay.COLUMNS:
        got = np.asarray(inj2[name])
        assert got.dtype == inj[name].dtype, name
        assert np.array_equal(got, inj[name]), name
    assert np.array_equal(hi2, hi) and np.array_equal(lo2, lo)
    assert (int(used2), int(k2)) == (used, k)
    # the injection part leads the block: the standalone merge's layout
    # (no schedule) reads the same columns
    lead = lanes.TurnBlock(inject_batch, 0).injection(block)
    assert all(np.array_equal(lead[n], inj[n]) for n in lay.COLUMNS)


def test_an_empty_block_injects_nothing_and_schedules_nothing():
    lay = lanes.TurnBlock(8, 9)
    inj, hi, lo, used, k = lay.unpack(lay.empty())
    assert not inj["valid"].any()
    assert (inj["thi"] == lanes.NEVER32).all()
    assert (inj["tlo"] == lanes.NEVER32).all()
    assert (hi == lanes.NEVER32).all() and (lo == lanes.NEVER32).all()
    assert (int(used), int(k)) == (lanes.NEVER32, 0)


# -- 2. the device entry point ----------------------------------------------

K_CAP, SLOTS = 3, 9


def _tiny_engine():
    """Eight tgen lanes on one switch, two of them external (as
    backend/hybrid.py marks managed hosts): their deliveries egress."""
    hosts = "\n".join(f"""
  h{i:02d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 5ms --size 600
        start_time: 0 s
""" for i in range(8))
    cfg = ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 5, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu, tpu_inject_batch: 8,
                use_dynamic_runahead: true}}
hosts:
{hosts}
""")
    external = np.zeros(8, dtype=bool)
    external[:2] = True
    return TpuEngine(cfg, external=external)


def _old_arguments(p):
    """A turn's inputs as the parent passed them: two schedule arrays, a
    Python int, the injection dict, a numpy scalar."""
    b = p.inject_batch
    inj = {"valid": np.zeros(b, dtype=bool),
           "dst": np.zeros(b, dtype=np.int32),
           "thi": np.full(b, lanes.NEVER32, dtype=np.int32),
           "tlo": np.full(b, lanes.NEVER32, dtype=np.int32),
           "auxh": np.zeros(b, dtype=np.int32),
           "auxl": np.zeros(b, dtype=np.int32),
           "size": np.zeros(b, dtype=np.int32)}
    for i in range(3):  # three staged PACKETs from external lane 0
        arrival = 2 * MS + 1000 * i
        inj["valid"][i] = True
        inj["dst"][i] = 3 + i
        inj["thi"][i], inj["tlo"][i] = arrival >> 31, arrival & lanes.MASK31
        inj["auxh"][i] = lanes.PACKET << lanes.AUX_KIND_SHIFT
        inj["auxl"][i] = 100 + i
        inj["size"][i] = 700
    times = np.array([1, 6, 11, 30] + [40] * (SLOTS - 4), dtype=np.int64) * MS
    ext_hi = (times >> 31).astype(np.int32)
    ext_lo = (times & lanes.MASK31).astype(np.int32)
    return ext_hi, ext_lo, 900_000, inj, np.int32(K_CAP)


def test_the_packed_block_computes_what_the_argument_list_did(monkeypatch):
    eng = _tiny_engine()
    p, tb = eng.params, eng.tables
    assert p.dynamic_runahead and p.inject_batch == 8
    state = eng.initial_state()
    ext_hi, ext_lo, used, inj, k_eff = _old_arguments(p)
    lay = lanes.TurnBlock(p.inject_batch, SLOTS)
    got_s, got_sc = lanes.make_hybrid_fused_fn(p, tb, K_CAP, SLOTS)(
        state, jnp.array(lay.pack(inj, ext_hi, ext_lo, used, k_eff)))

    # the reference: the same body, handed the parent's five arguments
    # as the parent's first lines prepared them (no block, no slicing)
    def old_entry(self, args):
        o_hi, o_lo, o_used, o_inj, o_k = args
        return (o_inj, jnp.asarray(o_hi, dtype=jnp.int32),
                jnp.asarray(o_lo, dtype=jnp.int32),
                jnp.asarray(o_used, dtype=jnp.int32),
                jnp.asarray(o_k, dtype=jnp.int32))

    monkeypatch.setattr(lanes.TurnBlock, "unpack", old_entry)
    run = lanes._build_hybrid_fused_run(p, tb, K_CAP, SLOTS)
    ref_s, ref_sc = jax.jit(
        lambda s, a, b, c, d, e: run(s, (a, b, c, d, e))
    )(state, ext_hi, ext_lo, used, inj, k_eff)

    got_sc, ref_sc = np.asarray(got_sc), np.asarray(ref_sc)
    n_sc = lanes.HYB_WE_BASE + K_CAP
    head = lanes.HYB_EGRESS_HEAD
    assert got_sc.shape == (n_sc + 6 * head,) and got_sc.dtype == np.int64
    assert np.array_equal(got_sc, ref_sc)
    for a, b in zip(jax.tree.leaves(got_s), jax.tree.leaves(ref_s)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the turn did something: windows consumed, the fold taken, rows out
    assert got_sc[lanes.HYB_K_DONE] == K_CAP
    assert list(got_sc[lanes.HYB_WE_BASE:n_sc]) == [2 * MS, 7 * MS, 12 * MS]
    assert got_sc[lanes.HYB_MIN_USED] == 900_000
    count = int(got_sc[lanes.HYB_EGRESS_COUNT])
    assert count >= 3
    # ... and the head IS the buffer's first rows
    rows = lanes.hyb_egress_rows(got_sc, K_CAP)
    assert rows.shape == (head, 6)
    assert np.array_equal(rows, np.asarray(got_s.egress[:head]))
    # the standalone merge reads the same block's injection part
    merged = lanes.make_inject_fn(p, tb)(
        state, jnp.array(lay.pack(inj, ext_hi, ext_lo, used, k_eff)))
    want = jax.jit(lambda s, i: lanes._inject_merge(p, tb, s, i))(state, inj)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- 3. the egress read -----------------------------------------------------


@pytest.fixture(scope="module")
def hybrid_engine(tmp_path_factory):
    """A HybridEngine that is never run: ``_read_egress`` needs its clock,
    its counters and the device's shapes."""
    from shadow_tpu.backend.hybrid import HybridEngine

    subprocess.run(["make", "-C", str(REPO / "native")], check=True,
                   capture_output=True)
    tmp = tmp_path_factory.mktemp("turn_block")
    cfg = ConfigOptions.from_yaml(f"""
general: {{stop_time: 1s, seed: 3, data_directory: {tmp / 'd'},
          heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu}}
hosts:
  cli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.2, "9000", "2", "100"]
  srv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "2"]
  zm0:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
""")
    eng = HybridEngine(cfg)
    yield eng
    eng.finalize()


def _counts(eng):
    return {k: eng.sync_stats[k] for k in (
        "egress_head_reads", "egress_reads", "egress_rows", "egress_bytes")}


@pytest.mark.parametrize(
    "which", ["none", "one", "head", "head_plus_1", "full"])
def test_read_egress_returns_the_first_count_rows(hybrid_engine, which):
    eng = hybrid_engine
    cap = eng.device.params.egress_capacity
    h = lanes.HYB_EGRESS_HEAD
    assert h < cap
    count = {"none": 0, "one": 1, "head": h, "head_plus_1": h + 1,
             "full": cap}[which]
    buf = np.arange(cap * 6, dtype=np.int64).reshape(cap, 6) * 7 + 1
    state, head = SimpleNamespace(egress=jnp.array(buf)), buf[:h].copy()
    before = _counts(eng)
    rows = eng._read_egress(state, count, 0, head)
    assert rows == buf[:count].tolist()
    delta = {k: v - before[k] for k, v in _counts(eng).items()}
    if count == 0:
        assert not any(delta.values())
    elif count <= h:  # whole from the packed read-back: no device read
        assert delta == {"egress_head_reads": 1, "egress_reads": 0,
                         "egress_rows": count, "egress_bytes": 0}
    else:  # the tail alone, its end padded to a power of two
        span = 1 << (count - 1).bit_length()
        assert delta == {"egress_head_reads": 0, "egress_reads": 1,
                         "egress_rows": count,
                         "egress_bytes": (min(span, cap) - h) * 48}
    with pytest.raises(RuntimeError, match="egress buffer overflowed"):
        eng._read_egress(state, count, 1, head)


# -- 4. the fused cells' programs -------------------------------------------

#: sha256 of the lowered text of three tiny fused programs AT THE PARENT
#: COMMIT (PR 39, c0d8953): the gossip body, the routed lossy all-TCP
#: tier, and the passive mesh sharded over two devices (the only code of
#: ``parallel/mesh.py`` a fused cell runs).  With the three pins of
#: ``tests/test_gossip_mesh.py`` (PHOLD, the passive mesh, the stream
#: lanes) they are the seven fused cells' kinds of program: none enters
#: the hybrid's entry points, so none may move.  A later PR that changes
#: the body changes these on purpose: recompute them on its own parent.
#: PR 41 did, for the gossip body alone: every gossip program gained the
#: propagation histogram (``gossip_age``; 4c9ad9dc... at PR 39).
#: ``gossip_wan`` (PR 42) is the gossip body on a routed, lossy graph, whose
#: sends read their path from per-peer rows: computed on PR 42's OWN tree
#: (its parent gathered from the [G, G] tables and had no such pin), so
#: the next PR that touches ``one_send`` sees it move.  PR 43 changed the
#: gossip body on purpose (a fan-out program's exchange runs over its
#: sending slots, ``lanes._merge_append`` step 2): both gossip pins are of
#: PR 43's own tree (4fa8d862... and 1b8ad06a... at PR 42); the two
#: programs in which a pop sends once did not move.  PR 46 changed the
#: gossip body on purpose again (its rows carry ONE payload word, ``plo``:
#: six operands in the row sort, seven in the exchange sort, a ``[6, N,
#: C]`` carry): both gossip pins are of PR 46's own tree (4e17a3a5... and
#: c726742e... at its parent, 635dc0e); the other two, recomputed there,
#: did not move.  PR 47 changed the gossip body on purpose once more (a
#: PACKET pop looks its message up in the seen bitmap and a known copy
#: delivered inside the window queues no DELIVERY row,
#: ``lanes.gossip_elides``; one more scalar, ``gossip_elided``, in the
#: carry): both gossip pins are of PR 47's own tree (ea1881e1... and
#: 7462edac... at its parent, 64be6b1); the other two did not move.
#: PR 50 changed every program SOME lane of which runs an active model on
#: purpose (the loop ledger: two more leaves of the carry, ``lanes.LaneState.
#: loop_hist`` and ``loop_acc``): the three pins below are of PR 50's own
#: tree (f2ce5c73..., 6b3203eb... and c44fe83e... at its parent, aca1e6f);
#: the passive mesh, which carries no ledger, did not move.
PARENT_TEXT = {
    "gossip":
        "0d0cf9b939eaea1b84bd260cb9c91f94a7eefab12c07f2b8c23911b1a8548a5d",
    "gossip_wan":
        "b0be5fdf7347c2bfaa9d63e8f71d54ed8a8d80301eebf05a87d445cf6269e5b0",
    "routed_tcp_loss":
        "b11063dffcb96fc16a7c2cb5649168a445b5cf3125a3fe4b3dd6ea43e502a808",
    "sharded_passive_mesh":
        "ab2e7fc35e0fc7691f3c802bbc8dcd72fc1a0abfcb88407f476a165333343648",
}


def _lowered_text(name):
    if name == "gossip":
        import test_gossip_mesh

        cfg = test_gossip_mesh._cfg(64, 4, 3)
    elif name == "gossip_wan":
        import test_gossip_mesh

        cfg = test_gossip_mesh._wan_cfg()
    elif name == "routed_tcp_loss":
        import test_routed_factory

        cfg = test_routed_factory.rehearsal(1, "tpu")
    else:
        import test_phold_mesh

        cfg = test_phold_mesh._passive_mesh()
    eng = TpuEngine(cfg, log_capacity=0)
    if name != "sharded_passive_mesh":
        fn = lanes.make_run_fn(eng.params, eng.tables)
    else:
        fn = parallel.make_sharded_run_fn(
            eng.params, eng.tables, parallel.make_mesh(2))
    return fn.lower(eng.initial_state()).as_text()


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_a_fused_program_is_the_parents(name):
    text = _lowered_text(name)
    assert "TurnBlock" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[name]
