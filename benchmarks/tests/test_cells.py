"""A cell is data: loading, the one general generator, and the rule that
nothing under benchmarks/ knows a cell's name."""

import dataclasses
import json
import re

import pytest

from conftest import BENCH, MANIFEST, REPO
from lib import cells, peaks


def test_evaluate_and_subst():
    env = {"hosts": 10000, "c": 3, "k": 2, "name": "x"}
    assert cells.evaluate("hosts // 100", env) == 100
    assert cells.evaluate("hosts - 2 * c + 7", env) == 10001
    assert cells.subst("{1500 + 400 * k + 97 * c}ms", env) == "2591ms"
    assert cells.subst("sc{c:05d}", env) == "sc00003"
    assert cells.subst("{hosts - 2 * c}", env) == 9994  # whole field: typed
    assert cells.subst(["{c:d}", {"exited": 0}], env) == ["3", {"exited": 0}]
    for bad in ("__import__('os')", "hosts ** 2", "nope + 1", "hosts.real",
                "hosts % 7", "-c", "min(c, k)", "'x'"):
        with pytest.raises(cells.CellError):
            cells.evaluate(bad, env)


def test_unknown_cell_and_missing_file(tiny_root, tmp_path):
    with pytest.raises(cells.CellError, match="no workload"):
        cells.load_cell("no_such_cell", tiny_root)
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    man["workloads"][0]["traffic"] = "absent"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(cells.CellError, match="missing"):
        cells.load_cell(man["workloads"][0]["name"], tmp_path)


def test_metrics_follow_their_workloads_key(tiny_root):
    chains = cells.load_cell("tiny_chains", tiny_root)
    udp = cells.load_cell("tiny_udp", tiny_root)
    names = lambda ms: {m["name"] for m in ms}  # noqa: E731
    assert "sim10ms_wall_p95_ms" in names(chains.end_to_end)
    assert "sim10ms_wall_p95_ms" not in names(udp.end_to_end)
    assert "iters_per_window" in names(udp.per_layer)
    assert "iters_per_window" not in names(chains.per_layer)
    assert "device_idle_share" in names(udp.per_layer) & names(chains.per_layer)


def _same(a, b):
    assert [dataclasses.asdict(h) for h in a.hosts] == [
        dataclasses.asdict(h) for h in b.hosts]
    assert dataclasses.asdict(a.experimental) == dataclasses.asdict(
        b.experimental)
    assert a.network.graph.inline == b.network.graph.inline


def test_the_cells_are_the_shapes_the_program_presets_give():
    """The configuration files were copied from config/presets.py and
    config/scenarios.py; while those stand, the copies must agree."""
    from shadow_tpu.config.presets import (
        flagship_mesh_config,
        mixed_flagship_config,
    )
    from shadow_tpu.config.scenarios import managed_relay_chains_large

    pure = flagship_mesh_config(10_000, queue_capacity=16, pops_per_round=2)
    pure.experimental.tpu_cross_capacity = 8
    build = lambda name: cells.build_config(  # noqa: E731
        cells.load_cell(name), seed=41, backend="tpu", stop_ns=10**9,
        data_dir="d")
    _same(build("mesh10k_udp"), pure)
    _same(build("mesh10k_mixed"), mixed_flagship_config(10_000))
    _same(build("hybrid151_chains"), managed_relay_chains_large("d"))


def test_program_options_that_are_gone_are_skipped(tiny_root):
    cell = cells.load_cell("tiny_udp", tiny_root)
    said = []
    cfg = cells.build_config(
        cell, seed=2**31 + 5, backend="cpu", stop_ns=10**8, data_dir="d",
        say=said.append, extra_options={"tpu_knob_deleted_by_c4": 3})
    assert cfg.experimental.tpu_lane_queue_capacity == 16
    assert 0 < cfg.general.seed < 2**31
    assert "tpu_knob_deleted_by_c4" in said[-1] and "skipped" in said[-1]


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_every_per_layer_metric_has_its_reader():
    import run

    for m in MANIFEST["per_layer"]:
        mod = run.load_module("layer_metrics", m["name"])
        assert mod.UNIT == m["unit"]  # name, layer, moves: the manifest's
        assert mod.read({}) is None  # nothing to read: nothing returned
    for c in MANIFEST["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        run.load_module("runners", cfg["runner"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["source"] == c["source"]


def test_no_code_under_benchmarks_names_a_cell():
    """Nothing in the harness may branch on a cell's, a configuration's or
    a traffic mix's name: code files (tests aside) never spell one."""
    names = {w["name"] for w in MANIFEST["workloads"]}
    names |= {c["name"] for c in MANIFEST["configs"]}
    names |= {w["traffic"] for w in MANIFEST["workloads"]} - {"udp"}
    pat = re.compile("|".join(sorted(re.escape(n) for n in names)))
    code = [p for p in BENCH.rglob("*.py") if "tests" not in p.parts]
    assert len(code) > 10
    hits = [(str(p.relative_to(BENCH)), m.group(0))
            for p in code for m in [pat.search(p.read_text())] if m]
    assert not hits, hits
    # "udp" is also a protocol's name; as a traffic name it may only be
    # compared, so look for it as a quoted word
    quoted = [str(p) for p in code
              if re.search(r"""["']udp["']""", p.read_text())]
    assert not quoted, quoted
