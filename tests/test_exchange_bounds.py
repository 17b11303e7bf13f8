"""The exchange's segment bounds (``lanes._merge_append``, ISSUE 29).

Lane ``d``'s slice of the destination-sorted sends is ``start[d]``,
``cnt[d]``.  Two laws find them and the static shape picks one: the one-hot
histogram in one matmul under ``lanes._ONEHOT_BUDGET`` (every tier-1 shape,
the 10k cells), the same histogram accumulated over chunks of the sends
past it (the 100k cells; before ISSUE 29 a ``searchsorted`` whose binary
search was a ``while`` of per-element gathers).  The laws under test:

(a) both give the integers ``searchsorted`` gives, on any column;
(b) a run that takes the wide law equals the CPU oracle — counters, rounds
    and the whole event log — on one device and on a 4-device virtual mesh,
    and strict capacity still raises on a cross-block shed;
(c) the gauge ``lane_plane["exchange_bounds_wide"]`` says which law ran;
(d) the wide law puts no gather, scatter or sort into the program, and
    its one loop has none either.

The wide law is forced at small widths by patching the budget to 0: a
shape the code observes, not an option.
"""

import copy
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.cpu_engine import CpuEngine
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.columnar import columnar_mesh_config
from shadow_tpu.config.options import ConfigOptions

MS = 1_000_000
CROSS_CAP = 8


# -- (a) the laws, on columns ------------------------------------------------


def _column(kind: str, n: int, k: int, rng) -> np.ndarray:
    """A pre-sort destination column [k * n]; an invalid send has dst n."""
    m = k * n
    if kind == "random":  # ~4 % invalid, most lanes hit, some empty
        dst = rng.integers(0, n, size=m)
        dst[rng.random(m) < 0.04] = n
    elif kind == "empty_lanes":  # every send to a tenth of the lanes
        dst = rng.choice(np.arange(0, n, 10), size=m)
    elif kind == "all_invalid":
        dst = np.full(m, n)
    elif kind == "one_hot_lane":  # one lane receives far more than cross_cap
        dst = rng.integers(0, n, size=m)
        dst[: min(m, 5 * CROSS_CAP)] = n // 2
    elif kind == "ends":  # the first and the last lane only
        dst = np.where(rng.random(m) < 0.5, 0, n - 1)
    else:
        raise AssertionError(kind)
    return rng.permutation(dst).astype(np.int32)


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("n", [128, 200, 1_000])  # two are no multiple of 128
@pytest.mark.parametrize(
    "kind", ["random", "empty_lanes", "all_invalid", "one_hot_lane", "ends"])
def test_both_laws_give_searchsorteds_bounds(kind, n, k, monkeypatch):
    # chunks of 96 sends: 2 to 84 of them, the last one ragged in most cases
    monkeypatch.setattr(lanes, "_ONEHOT_CHUNK", 96)
    rng = np.random.default_rng(zlib.crc32(f"{kind}-{n}-{k}".encode()))
    dst = _column(kind, n, k, rng)
    edges = np.searchsorted(np.sort(dst), np.arange(n + 1), side="left")
    want = (edges[:n], np.diff(edges))
    if kind == "one_hot_lane":
        assert want[1][n // 2] > CROSS_CAP
    for law in (lanes._bounds_by_onehot_chunked, lanes._bounds_by_onehot):
        start, cnt = jax.jit(law, static_argnums=1)(jnp.asarray(dst), n)
        assert start.dtype == cnt.dtype == jnp.int32
        np.testing.assert_array_equal(start, want[0], err_msg=law.__name__)
        np.testing.assert_array_equal(cnt, want[1], err_msg=law.__name__)


def test_the_switch_sits_where_the_one_hots_outgrow_their_budget():
    first_wide = {}
    for k in (2, 8):
        n = 1
        while not lanes.exchange_bounds_wide(k * n, n):
            n += 1
        first_wide[k] = n
    assert first_wide == {2: 38_837, 8: 16_384}
    # the benchmark's widths: 10k under it, 100k past it (K = 2)
    assert not lanes.exchange_bounds_wide(20_000, 10_000)
    assert lanes.exchange_bounds_wide(200_000, 100_000)


# -- (b), (c) a run under the wide law ---------------------------------------


def _mesh_cfg(tmp_path, hosts=200, stop_ms=200):
    """The mesh cells' configuration (16 / 2 / 8) at 200 lanes — no
    multiple of 128, a multiple of 4 — with the device log on."""
    cfg = columnar_mesh_config(hosts, queue_capacity=16, pops_per_round=2)
    cfg.experimental.tpu_cross_capacity = CROSS_CAP
    cfg.general.stop_time = stop_ms * MS
    cfg.general.data_directory = str(tmp_path / "d")
    cfg.general.heartbeat_interval = None
    return cfg


def _oracle(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.experimental.network_backend = "cpu"
    return CpuEngine(cfg).run()


#: lane-engine bookkeeping the oracle does not keep (and one it alone keeps);
#: ``lane_drop_loss`` / ``lane_drop_codel`` are the oracle's too since PR 32
OWN = {"lane_iters", "lane_delivered", "lane_sends", "lane_drop_queue",
       "tgen_sent_bytes"}


def _shared(counters):
    return {k: v for k, v in counters.items() if k not in OWN}


@pytest.fixture(scope="module")
def mesh_oracle(tmp_path_factory):
    return _oracle(_mesh_cfg(tmp_path_factory.mktemp("oracle")))


@pytest.mark.parametrize("devices", [1, 4])
def test_a_wide_run_equals_the_oracle(
        tmp_path, monkeypatch, mesh_oracle, devices):
    if len(jax.devices()) < devices:
        pytest.skip("needs four (virtual) devices")
    monkeypatch.setattr(lanes, "_ONEHOT_BUDGET", 0)
    monkeypatch.setattr(lanes, "_ONEHOT_CHUNK", 128)  # 400 sends: 4 chunks
    eng = TpuEngine(_mesh_cfg(tmp_path))
    if devices > 1:
        eng.attach_mesh(parallel.make_mesh(devices))
    res = eng.run(mode="device")
    assert eng.lane_plane["exchange_bounds_wide"] == 1
    assert eng.lane_plane["mesh_devices"] == devices
    assert res.rounds == mesh_oracle.rounds > 1
    assert _shared(res.counters) == _shared(mesh_oracle.counters)
    assert res.counters.get("lane_drop_queue", 0) == 0
    assert len(res.event_log) == 200 * 18  # 20 windows: 18 deliveries a host
    assert res.log_tuples() == mesh_oracle.log_tuples()


def test_the_narrow_law_runs_unpatched_and_the_gauge_says_so(
        tmp_path, mesh_oracle):
    eng = TpuEngine(_mesh_cfg(tmp_path))
    res = eng.run(mode="device")
    assert eng.lane_plane["exchange_bounds_wide"] == 0
    assert res.log_tuples() == mesh_oracle.log_tuples()


_FAN_IN = """
general: {{stop_time: 300ms, seed: 11, data_directory: {data},
           heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu, tpu_cross_capacity: 2,
               tpu_events_per_round: 2}}
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


@pytest.mark.parametrize("budget", [0, lanes._ONEHOT_BUDGET],
                         ids=["wide", "narrow"])
def test_strict_capacity_raises_on_a_cross_block_shed(
        tmp_path, monkeypatch, budget):
    """Six clients' sends reach one server in one iteration through a
    cross block of two: ``cnt - cross_cap`` of them are shed before the
    merge, under either law the same number, and strict capacity raises."""
    monkeypatch.setattr(lanes, "_ONEHOT_BUDGET", budget)
    cfg = ConfigOptions.from_yaml(_FAN_IN.format(data=tmp_path / "f"))
    eng = TpuEngine(cfg)
    with pytest.raises(RuntimeError, match="or by the CROSS block .it holds 2; .* experimental.tpu_cross_capacity") as e:
        eng.run(mode="device")
    shed = int(re.match(r"(\d+) events dropped", str(e.value)).group(1))
    loose = TpuEngine(cfg, strict_capacity=False).run(mode="device")
    assert loose.counters["lane_drop_queue"] == shed > 0


# -- (d) what the wide law lowers to -----------------------------------------


def _ops(text: str) -> dict:
    return {op: len(re.findall(rf"stablehlo\.{op}\b", text))
            for op in ("while", "gather", "scatter", "sort")}


def test_the_chunked_law_is_one_loop_of_matmuls():
    """Some tens of steps at the 100k cells' shape, each a slice of the
    column, two compares and a matmul: nothing whose cost is per element."""
    text = jax.jit(lanes._bounds_by_onehot_chunked, static_argnums=1).lower(
        jax.ShapeDtypeStruct((200_000,), jnp.int32), 100_000).as_text()
    assert _ops(text) == {"while": 1, "gather": 0, "scatter": 0, "sort": 0}
    assert text.count("stablehlo.dot_general") == 1
    assert 200_000 > lanes._ONEHOT_CHUNK  # more than one step


def test_the_100k_lane_program_has_no_search_loop(tmp_path):
    """The mesh cells' timed program (log off) at 100 000 lanes takes the
    wide law as it stands.  It differs from the 10 000-lane program, which
    has no search, by the chunk loop alone: no sort, gather or scatter
    beyond the narrow program's (before ISSUE 29: a ``while`` of 18 gathers
    of 100 001 single elements)."""
    lowered = {}
    for hosts in (10_000, 100_000):
        eng = TpuEngine(_mesh_cfg(tmp_path, hosts=hosts), log_capacity=0)
        assert lanes.exchange_bounds_wide(
            eng.params.exchange_entries, hosts) == (hosts == 100_000)
        lowered[hosts] = _ops(lanes.make_run_fn(
            eng.params, eng.tables).lower(eng.initial_state()).as_text())
    narrow, wide = lowered[10_000], lowered[100_000]
    assert wide == {**narrow, "while": narrow["while"] + 1}
