"""AOT compiles for the attached chip, kept as tests (on-chip-measurement §2).

The TPU compiler is installed here and compiles for a DESCRIBED ``v5e:2x2``
device without a chip attached.  Each case lowers one program of the main
path at the shape ``chip_smoke.py`` / ``benchmarks/run.py`` run it, with the
accelerator branches taken: ``lanes.scan_or_unroll`` and the slot body's
``slot_dataflow`` switch on
``jax.default_backend() != "cpu"``, which every other test pins to the CPU —
so each case patches ``jax.default_backend`` to ``"tpu"`` around its trace.
What the compiler refuses here it would refuse on the chip, at no chip time.
A compile that passes is not a chip run: nothing executes, no number from
here is a device number.

The topology is described inside a module-scoped fixture that skips when it
cannot be (only one process may load libtpu; under xdist only the worker
that is handed this file does) — never at import or collection.  The
persistent compile cache is off around these compiles: an executable built
for a described device cannot be read back without the chip.

Full-width compiles of the cases kept narrow here are recorded once in
CHANGES.md (PR 23).
"""

import os
import re
import signal

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from shadow_tpu import parallel
from shadow_tpu.backend import lanes
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.presets import (
    flagship_mesh_config,
    mixed_flagship_config,
)

MS = 1_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / libtpu held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Loading libtpu installs a C-level SIGTERM handler that prints a stack
    # trace before dying.  The tier-1 command is run under `timeout`, which
    # TERMs the whole process group: that trace would land on pytest's
    # progress line and the dots on it would go uncounted.  Restore the
    # default so this worker ends as quietly as every other one.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The program's accelerator branches, steered from the test (never a
    program option): ``jax.default_backend()`` answers ``"tpu"``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lanes.jax.default_backend() == "tpu"


def _shapes(tree, sharding):
    """ShapeDtypeStructs on ``sharding`` — a described device holds no
    array, so programs are lowered against shapes."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _pure_cfg(n_hosts: int, stop_ns: int):
    """chip_smoke.py ``pure_cfg``."""
    cfg = flagship_mesh_config(n_hosts, queue_capacity=16, pops_per_round=2)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.general.stop_time = stop_ns
    return cfg


def _fits(compiled, limit_bytes: int = 16 << 30) -> int:
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
    )
    assert 0 < total < limit_bytes, mem
    return total


@pytest.fixture(scope="module")
def udp_flagship_compiled(one_chip):
    """The 10 000-lane UDP flagship, fused free-run, exactly as
    chip_smoke.py phase a sends it through the facade (device log on):
    ONE compile for the cases that read it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")  # as ``as_tpu``
        eng = TpuEngine(_pure_cfg(10_000, 150 * MS))
        assert eng.params.n_lanes == 10_000
        state = _shapes(eng.initial_state(), one_chip)
        return lanes.make_run_fn(eng.params, eng.tables).lower(
            state).compile()


def test_udp_flagship_run_fn_full_width(udp_flagship_compiled):
    _fits(udp_flagship_compiled)


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=(%[\w.\-]+)"
)


def _unguarded_table_gathers(text: str) -> list[str]:
    """Gathers out of an ``s32[1025]`` table (CoDel's ``codel_div``) in a
    computation the program reaches WITHOUT entering a conditional's
    branch: every such gather runs on every trip of the loop."""
    comps: dict[str, list[str]] = {}
    entry = name = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif name is not None:
            comps[name].append(line)
    assert entry is not None
    always, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in always:
            continue
        always.add(comp)
        for line in comps[comp]:
            # a conditional's branch_computations are NOT followed
            todo += _CALLED.findall(line)
    bad = []
    for comp in always:
        tables = {
            m.group(1) for line in comps[comp]
            if (m := re.match(r"\s*(%[\w.\-]+) = s32\[1025\]", line))
        }
        for line in comps[comp]:
            m = re.search(r" gather\((%[\w.\-]+),", line)
            if m and m.group(1) in tables:
                bad.append(f"{comp}: {line.strip()[:120]}")
    return bad


def test_codel_table_gather_only_inside_a_conditional(udp_flagship_compiled):
    """No ``gather(s32[1025], s32[N])`` stands in the loop body outside a
    conditional's branch (PR 36: four of them were over half of every wide
    cell's wall): the enter branch selects two constants, the dropping
    branch gathers under ``lax.cond``.  The parser is held to a program
    that does gather unconditionally, so an empty list means something."""
    text = udp_flagship_compiled.as_text()
    assert "/codel_offer/cond/" in text
    assert " conditional(" in text
    assert _unguarded_table_gathers(text) == []


def test_the_gather_parser_sees_an_unguarded_table_gather(one_chip):
    table = jax.ShapeDtypeStruct((1025,), np.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((10_000,), np.int32, sharding=one_chip)
    text = jax.jit(lambda t, i: t[i]).lower(table, idx).compile().as_text()
    assert len(_unguarded_table_gathers(text)) == 1


def test_phold_run_fn_full_width(one_chip, as_tpu):
    """The 10 000-LP PHOLD mesh at the factory's shapes (42 / 18 / 2: a
    64-column merge row), log off, as cell ``phold10k_m4`` times it: the
    first program with the active model's ``ins_*`` channel, the per-send
    peer draw and the shape peaks, and its scopes in the compiled text
    (``scripts/hlo_stats.py phold10k_m4 --scope phold_draw``)."""
    from shadow_tpu.config.scenarios import phold_mesh_config

    cfg = phold_mesh_config(10_000, 4, 256, "10 ms", "1 Gbit")
    cfg.general.stop_time = 500 * MS
    eng = TpuEngine(cfg, log_capacity=0)
    p = eng.params
    assert (p.capacity, p.cross_cap, p.pops_per_iter) == (42, 18, 2)
    assert not p.all_passive
    state = _shapes(eng.initial_state(), one_chip)
    compiled = lanes.make_run_fn(p, eng.tables).lower(state).compile()
    _fits(compiled)
    text = compiled.as_text()
    for scope in ("phold_draw", "row_merge", "window_gather",
                  "exchange_bounds"):
        assert f"/{scope}/" in text, scope


@pytest.fixture(scope="module")
def gossip_wan_compiled(one_chip):
    """Ethereum-style gossip over a routed, lossy graph as cell
    ``gossip10k_wan_slot`` times it (D = 8, the seed's words as
    arguments), kept at 1 000 nodes over 50 graph nodes, the fan-out in
    the loop form the chip takes: ONE compile for the cases that read it."""
    from shadow_tpu.config.scenarios import gossip_mesh_config

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")  # as ``as_tpu``
        cfg = gossip_mesh_config(1_000, 8, 1, ("1 s",), 8, 512,
                                 bandwidth="1 Gbit", graph_nodes=50,
                                 graph_seed=1)
        cfg.general.stop_time = 1200 * MS
        eng = TpuEngine(cfg, log_capacity=0)
        p, tb = eng.params, eng.tables
        assert p.has_loss and p.sends_per_pop == 8
        assert tb.lat.shape == (50, 50) and tb.g_lat.shape == (8, 1_000)
        assert lanes.path_sends(p, tb) == (8, 0)
        # 2 000 slots, 256 of them (2 048 rows) a pass of the exchange
        assert (p.exchange_slot_budget, p.exchange_entries) == (256, 2_048)
        word = jax.ShapeDtypeStruct((), np.uint32, sharding=one_chip)
        return lanes.make_run_fn(p, tb).lower(
            _shapes(eng.initial_state(), one_chip), word, word).compile()


def test_gossip_wan_run_fn_reads_rows_and_gathers_no_path(
        gossip_wan_compiled):
    """Each send's path is read from the lane's per-peer rows — no
    ``gather`` in the compiled ``path_lookup`` scope (PR 42; 64 of them
    were 79 % of the cell's device time)."""
    _fits(gossip_wan_compiled)
    lines = gossip_wan_compiled.as_text().splitlines()
    scoped = [line for line in lines if "/path_lookup/" in line]
    assert scoped and not [line for line in scoped if " gather(" in line]
    # the parser sees the program's other gathers (the window's)
    assert any(" gather(" in line for line in lines)


def _scope_gather_operands(text: str, scope: str) -> list[list[int]]:
    """The operand shape of every ``gather`` the compiled text holds under
    ``scope`` (a ``jax.named_scope``), as a list of dimensions."""
    shapes, found = {}, []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", line)
        if m:
            shapes[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    for line in text.splitlines():
        m = re.search(r" gather\((%[\w.\-]+),", line)
        if m and f"/{scope}/" in line:
            found.append(shapes[m.group(1)])
    return found


def test_the_compacted_exchange_gathers_rows_never_send_words(
        gossip_wan_compiled):
    """The sending slots' words are fetched as ONE row a slot out of the
    ``[K x N, 4 F + 3]`` table (gossip's rows carry one payload word,
    ISSUE 46): the compiled ``exchange_compact`` scope
    holds that row gather and no gather whose operand is an ``[N]``-minor
    send word (PR 41 priced those at 6.7–11.2 ns an element); the exchange
    under it sorts ``S_b x F`` rows, and nothing sorts the K x F x N of
    the send channel."""
    text = gossip_wan_compiled.as_text()
    operands = _scope_gather_operands(text, "exchange_compact")
    assert operands == [[2 * 1_000, 4 * 8 + 3]]
    assert not [dims for dims in operands if dims[-1] == 1_000]
    sorted_rows = {
        int(re.search(r"s32\[(\d+)[,\]]", line).group(1))
        for line in text.splitlines() if " sort(" in line}
    assert 2_048 in sorted_rows and 16_000 not in sorted_rows
    for scope in ("exchange_compact", "exchange_sort", "exchange_bounds",
                  "row_merge"):
        assert f"/{scope}/" in text, scope


def _sort_operands(text: str) -> dict:
    """``{named scope: operand count}`` of every ``sort`` a compiled text
    holds (the scope is the last part of its ``op_name`` before the
    sort)."""
    found = {}
    for line in text.splitlines():
        m = re.search(r" sort\(([^)]*)\)", line)
        if m:
            scope = re.search(r'op_name="[^"]*?(\w+)/sort"', line)
            found[scope.group(1) if scope else ""] = m.group(1).count("%")
    return found


def test_a_gossip_row_carries_one_payload_word(gossip_wan_compiled):
    """Gossip's rows carry ``plo`` alone (ISSUE 46, ``LaneParams.
    payload_words`` == 1): the compiled row sort takes six operands (the
    4-word key, the size, the message id), the exchange sort those and the
    destination, and the packed queue state is ``[6, N, C]`` — nowhere a
    seventh word."""
    text = gossip_wan_compiled.as_text()
    assert _sort_operands(text) == {
        "row_merge": 6, "exchange_sort": 7, "exchange_compact": 1}
    assert "s32[6,1000," in text and "s32[7,1000," not in text


def test_a_packet_pops_bitmap_lookup_adds_no_sort_and_no_gather(
        gossip_wan_compiled):
    """A gossip lane's PACKET pop looks its message up in the seen bitmap
    by the compare the publish and DELIVERY pops already made (ISSUE 47,
    ``lanes.gossip_elides``): the compiled program holds the three sorts
    and four gathers it held at the parent (64be6b1), none of either under
    ``gossip_seen``, and the run's count of elided rows is a word of its
    carry."""
    text = gossip_wan_compiled.as_text()
    lines = text.splitlines()
    assert sum(" sort(" in line for line in lines) == 3
    assert sum(" gather(" in line for line in lines) == 4
    seen = [line for line in lines if "/gossip_seen/" in line]
    assert seen and not [
        line for line in seen if " gather(" in line or " sort(" in line]
    assert "gossip_elided" in text


def test_the_send_word_parser_sees_an_element_gather(one_chip):
    """The guard above is held to a program that DOES pick elements out of
    a lanes-minor ``[F, N]`` word under the scope."""
    def pick(words, idx):
        with jax.named_scope("exchange_compact"):
            return words[:, idx]

    words = jax.ShapeDtypeStruct((8, 1_000), np.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((256,), np.int32, sharding=one_chip)
    text = jax.jit(pick).lower(words, idx).compile().as_text()
    operands = _scope_gather_operands(text, "exchange_compact")
    assert operands and all(dims[-1] == 1_000 for dims in operands)


def test_udp_round_fn_step_driver(one_chip, as_tpu):
    """The step driver's one-round kernel (run-control / checkpointing),
    kept at 1 000 lanes (same body; the full width is the case above)."""
    eng = TpuEngine(_pure_cfg(1_000, 150 * MS), netobs=True)
    state = _shapes(eng.initial_state(), one_chip)
    compiled = lanes.make_round_fn(eng.params, eng.tables).lower(
        state
    ).compile()
    _fits(compiled)


def test_mixed_run_fn_tiered(one_chip, as_tpu):
    """The mixed TCP/UDP mesh on the tiered stream path — the unconditional
    masked slot body (``slot_dataflow``) only exists off-CPU.  Compile time
    is set by the unrolled tier walk, not the lane count (~110 s at the
    flagship's 16 tier pops per iteration at ANY width), so this case keeps
    1 000 lanes and 4 tier pops: the same branches, a quarter of the
    unrolled copies.  The flagship compile is recorded in CHANGES.md."""
    cfg = mixed_flagship_config(1_000)
    cfg.general.stop_time = 120 * MS
    cfg.experimental.tpu_stream_events_per_round = 4
    eng = TpuEngine(cfg)
    assert eng.params.stream_tiered
    state = _shapes(eng.initial_state(), one_chip)
    compiled = lanes.make_run_fn(eng.params, eng.tables).lower(state).compile()
    _fits(compiled)


def _relay_chain_engine(tmp_path):
    """The device half of ``managed_relay_chains_large`` (151 managed
    processes over 1 000 lane hosts) without spawning anything: managed
    hosts are EXTERNAL lanes, as backend/hybrid.py marks them."""
    from shadow_tpu.backend.hybrid import config_has_managed
    from shadow_tpu.config.scenarios import managed_relay_chains_large
    from shadow_tpu.models.base import _REGISTRY

    cfg = managed_relay_chains_large(tmp_path / "data", sim_seconds=4)
    assert config_has_managed(cfg) and len(cfg.hosts) == 1151
    external = np.array([
        any(p.path not in _REGISTRY for p in h.processes) for h in cfg.hosts
    ])
    assert int(external.sum()) == 151
    return cfg, TpuEngine(cfg, external=external)


def _empty_turn_block(p, slots, k):
    """The turn's one host-to-device block (``lanes.TurnBlock``) as
    backend/hybrid.py ships it: no row valid, nothing scheduled, the
    dynamic-runahead fold untouched, depth ``k``."""
    lay = lanes.TurnBlock(p.inject_batch, slots)
    block = lay.empty()
    block[lay.k_at] = k
    return block


def test_hybrid_turn_inject_and_fused_k(one_chip, as_tpu, tmp_path):
    """The hybrid backend's device entry points at the relay-chain-large
    shape: the injection merge, and the k-window fused turn at both depth
    caps a user can reach — 1 (one window per dispatch) and the
    configured ``hybrid_fuse_k``."""
    cfg, eng = _relay_chain_engine(tmp_path)
    state = _shapes(eng.initial_state(), one_chip)
    k_cfg = int(cfg.experimental.hybrid_fuse_k)
    assert k_cfg >= 2
    for k in (1, k_cfg):
        slots = max(2 * k, 9)  # HybridEngine._ext_slots
        fused_fn, inject_fn = eng.make_hybrid_fns(k, slots)
        block = _shapes(_empty_turn_block(eng.params, slots, k), one_chip)
        compiled = fused_fn.lower(state, block).compile()
        _fits(compiled)
        # one block in; out, beside the state, one packed vector that
        # carries the egress head (no second read-back program)
        assert compiled.out_info[1].shape == (
            lanes.HYB_WE_BASE + k + 6 * lanes.HYB_EGRESS_HEAD,)
    _fits(inject_fn.lower(state, block).compile())


def _scatter_update_shapes(stablehlo: str) -> list[str]:
    """The update operand's ``<shape x dtype>`` of every scatter in a
    lowered module's text."""
    lines = stablehlo.splitlines()
    shapes = []
    for i, line in enumerate(lines):
        if "stablehlo.scatter" not in line:
            continue
        sig = next(m for m in (
            re.search(r"\}\) : \(.*tensor<([^>]*)>\) ->", tail)
            for tail in lines[i:]) if m)
        shapes.append(sig.group(1))
    return shapes


def test_hybrid_turn_offers_no_candidate_row_scatter(as_tpu, tmp_path):
    """The hybrid turn at the benchmark cell's shape (lowering alone, no
    described device): the parent appended to the log and the egress buffer
    by scattering EVERY candidate row — ``[N x (K + Cx), 6]`` for the merge
    tail, ``[K x N, 6]`` per iteration, ``[N, 6]`` per slot, int64 — and the
    chip's scatter costs what it is offered (PERF.md PR 27).  Appends now
    write blocks of the valid rows (``lanes._append_rows``): no scatter of
    six-column int64 rows is left, of any length."""
    cfg, eng = _relay_chain_engine(tmp_path)
    p = eng.params
    state = eng.initial_state()
    texts = []
    # a depth cap of 1, and the configured one (the cell runs that one)
    for fuse_k in (1, int(cfg.experimental.hybrid_fuse_k)):
        slots = max(2 * fuse_k, 9)
        texts.append(eng.make_hybrid_fns(fuse_k, slots)[0].lower(
            state, _empty_turn_block(p, slots, fuse_k)).as_text())
    n, k = p.n_lanes, p.pops_per_iter
    offered = {n * (k + p.cross_cap), n * (2 * k + p.cross_cap), k * n}
    for text in texts:
        updates = _scatter_update_shapes(text)
        assert not [u for u in updates if u.endswith("x6xi64")], updates
        assert not [u for u in updates  # "i64" alone: a scalar update
                    if u.split("x")[0] in map(str, offered)], updates
        # the appends are there: a block write per site (tail, per-slot
        # records, K unrolled slots' egress), each inside its own trip loop
        assert text.count("stablehlo.dynamic_update_slice") >= 2 + k


def test_sharded_run_fn_on_described_mesh(topo, as_tpu):
    """``parallel.make_sharded_run_fn`` on a Mesh of the four described
    devices: GSPMD must insert collectives for the cross-lane exchange, and
    the program must leave its argument alone (it is the initial state the
    engine keeps and starts every run from).  1 000 lanes here; the 10 000-
    and 100 000-lane compiles are in CHANGES.md."""
    eng = TpuEngine(_pure_cfg(1_000, 150 * MS), log_capacity=0)
    mesh = Mesh(np.array(topo.devices), (parallel.HOST_AXIS,))
    assert mesh.devices.size == 4
    sh = parallel.state_shardings(mesh)
    # per FIELD, not per leaf: planes compiled out are empty tuples, and
    # the stream field is a nested pytree under one (replicated) sharding
    state = lanes.LaneState(**{
        f: _shapes(getattr(eng.initial_state(), f), getattr(sh, f))
        for f in lanes.LaneState._fields
    })
    run_fn = parallel.make_sharded_run_fn(eng.params, eng.tables, mesh)
    compiled = run_fn.lower(state).compile()
    _fits(compiled)
    text = compiled.as_text()
    collectives = [
        op for op in ("all-gather", "all-reduce", "collective-permute",
                      "all-to-all", "reduce-scatter")
        if op in text
    ]
    assert collectives, "no collective in the sharded program"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0, "the kept state was donated"
    # the lane axis really is split: per-device argument bytes are a
    # fraction of the whole state's
    whole = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state)
    )
    assert mem.argument_size_in_bytes < whole


_RESULT = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\{([\d,]*)(?::T\(([\d,]+)\))?"
)


def _padded_result_bytes(text: str) -> int:
    """The largest array any instruction of a compiled TPU program writes,
    in bytes AS STORED: the chip keeps an array in tiles (``T(8,128)`` in
    the layout), so the two minor dimensions round up to the tile."""
    worst = 0
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or not m.group(2):
            continue
        dtype, dims, order, tile = m.groups()
        dims = [int(d) for d in dims.split(",")]
        order = [int(d) for d in order.split(",")] if order else []
        tile = [int(t) for t in tile.split(",")] if tile else []
        # minor-to-major order; the tile covers the minor-most dimensions
        for t, d in zip(reversed(tile), order):
            dims[d] = -(-dims[d] // t) * t
        bits = int(re.search(r"\d+", dtype).group()) if dtype != "pred" else 8
        worst = max(worst, int(np.prod(dims)) * bits // 8)
    return worst


@pytest.mark.parametrize(
    "n,k,a,c",
    [
        (10_000, 2, 5, 8),    # the 10k cells' exchange: rows of A*2v = 80
        (100_000, 2, 5, 8),   # the 100k cells'
        (10_000, 2, 7, 16),   # payload operands at a wider cross_cap: rows
    ],                        # of 224 span two tiles
)
def test_window_gather_row_fills_its_tile(one_chip, n, k, a, c):
    """ISSUE 33's layout rule in the compiled text: the exchange's window
    gather writes no buffer larger than twice its lane-minor ``[A, N, 2v]``
    block.  (Gathering rows ``v`` = 8 wide, the chip padded them sixteen-
    fold and relaid the padded buffer out twice: ``copy.47`` / ``copy.48``,
    82 MB at 10 000 lanes and 820 MB at 100 000 against a block of 3.2 /
    32 MB.)  The helper alone, at the shapes the cells give it; its values
    are ``tests/test_window_gather.py``'s."""
    arr = jax.ShapeDtypeStruct((n * k,), np.int32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((n,), np.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda xs, s: lanes._window_gather(xs, s, c)
    ).lower([arr] * a, start).compile()
    text = compiled.as_text()
    v = 1 << max(c - 1, 1).bit_length()
    block = a * 2 * v * n * 4
    assert "window_gather" in text  # the stage's name in a trace
    assert _padded_result_bytes(text) <= 2 * block, _padded_result_bytes(text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * block
    # one index a lane: no instruction carries the two-row [2N, ...] shape
    assert not re.search(rf"\[{2 * n},{a},{v}\]", text)


def test_sweep_kernel_bench_shape(one_chip, as_tpu):
    """The fleet-sweep kernel at a sweep's shape: 8 scenarios x
    1 000 lanes, tables/stop bounds/states all traced and stacked."""
    from shadow_tpu.sweep import SweepSpec, expand_variants

    cfg = flagship_mesh_config(
        1_000, sim_seconds=5, queue_capacity=16, pops_per_round=2
    )
    cfg.experimental.tpu_cross_capacity = 8
    variants = expand_variants(cfg, SweepSpec.seed_grid(cfg.general.seed, 8))
    eng = TpuEngine(variants[0].cfg, log_capacity=0)

    def stacked(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (8, *np.shape(x)), x.dtype, sharding=one_chip
            ),
            tree,
        )

    stop = jax.ShapeDtypeStruct((8,), np.int32, sharding=one_chip)
    sweep_fn = eng.make_sweep_fn()
    compiled = sweep_fn.lower(
        stacked(eng.sweep_tables()), stop, stop, stacked(eng.initial_state())
    ).compile()
    _fits(compiled)
    assert sweep_fn.traces == 1
