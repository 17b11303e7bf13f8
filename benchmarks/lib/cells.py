"""A cell is data: an entry of ``workloads`` in BENCHMARK.json naming a
configuration file and a traffic file.  This module finds those files and
turns them into the program's ``ConfigOptions`` through one general
generator; nothing here knows a cell's name."""

from __future__ import annotations

import ast
import dataclasses
import importlib
import json
import operator
import re
from pathlib import Path

#: the benchmark's own directory (code: runners, layer metrics) and the
#: checkout it sits in (the program, native/)
BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parent


class CellError(ValueError):
    """BENCHMARK.json, a configuration file or a traffic file is wrong."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    end_to_end: list  # BENCHMARK.json entries that apply to this cell
    per_layer: list

    @property
    def runner(self) -> str:
        return self.config["runner"]

    @property
    def params(self) -> dict:
        """The configuration's parameters, then the traffic mix's (which
        may refer to those before them as ``{name}``)."""
        env: dict = {}
        for src in (self.config, self.traffic):
            for key, val in src.get("parameters", {}).items():
                env[key] = subst(val, env)
        return env


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = REPO) -> Cell:
    """Read cell ``name`` from ``root/BENCHMARK.json``; the configuration is
    the manifest's ``file``, the traffic mix ``traffic/<traffic>.json``
    beside the configuration's directory."""
    root = Path(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json "
                        f"(has {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r}: no config {w['config']!r}")
    cfg_path = root / configs[w["config"]]["file"]
    tr_path = cfg_path.parent.parent / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, tr_path):
        if not p.is_file():
            raise CellError(f"workload {name!r}: missing {p}")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads(cfg_path.read_text()),
        traffic=json.loads(tr_path.read_text()),
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )


# -- {expression} substitution ---------------------------------------------

_OPS = {
    ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
    ast.FloorDiv: operator.floordiv,
}


def evaluate(expr: str, env: dict):
    """Integer arithmetic over the names of ``env``: + - * //."""

    def ev(node):
        if isinstance(node, ast.Constant) and type(node.value) is int:
            return node.value
        if isinstance(node, ast.Name):
            if node.id not in env:
                raise CellError(f"unknown name {node.id!r} in {expr!r}")
            return env[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        raise CellError(f"unsupported expression {expr!r}")

    return ev(ast.parse(expr.strip(), mode="eval").body)


_FIELD = re.compile(r"\{([^{}]+)\}")


def subst(value, env: dict):
    """Replace every ``{expr}`` / ``{expr:format}`` in a string (or in the
    strings of a list / dict) by its value under ``env``.  A string that is
    one whole field keeps the value's type."""
    if isinstance(value, list):
        return [subst(v, env) for v in value]
    if isinstance(value, dict):
        return {k: subst(v, env) for k, v in value.items()}
    if not isinstance(value, str):
        return value

    def one(m):
        expr, _, fmt = m.group(1).partition(":")
        return format(evaluate(expr, env), fmt)

    whole = _FIELD.fullmatch(value)
    if whole and ":" not in whole.group(1):
        return evaluate(whole.group(1), env)
    return _FIELD.sub(one, value)


# -- configuration + traffic -> ConfigOptions ------------------------------


def _expand_groups(groups: list, env: dict) -> dict:
    """Host groups to the ``hosts:`` mapping.  A group is emitted once, or
    once for every combination of its ``for`` loops (``[[var, count], ...]``,
    outer first); ``count`` makes it a replicated host group."""
    hosts: dict = {}

    def emit(g: dict, scope: dict) -> None:
        name = subst(g["name"], scope)
        if name in hosts:
            raise CellError(f"host group {name!r} defined twice")
        doc = {"network_node_id": subst(g.get("node", 0), scope)}
        if "count" in g:
            doc["count"] = int(subst(g["count"], scope))
            if doc["count"] < 1:
                raise CellError(f"host group {name!r}: count {doc['count']}")
        procs = []
        for p in g["processes"]:
            q = {"path": subst(p["path"], scope)}
            for key in ("args", "start_time", "expected_final_state"):
                if key in p:
                    q[key] = subst(p[key], scope)
            procs.append(q)
        doc["processes"] = procs
        hosts[name] = doc

    def loop(g: dict, loops: list, scope: dict) -> None:
        if not loops:
            emit(g, scope)
            return
        (var, count), rest = loops[0], loops[1:]
        for i in range(int(subst(count, scope))):
            loop(g, rest, {**scope, var: i})

    for g in groups:
        loop(g, g.get("for", []), env)
    return hosts


def set_program_options(cfg, options: dict, say) -> None:
    """Set each ``experimental.*`` knob only if the field still exists
    (ROADMAP C4 will delete some), and say which were set."""
    done, gone = [], []
    for key, val in options.items():
        if hasattr(cfg.experimental, key):
            setattr(cfg.experimental, key, val)
            done.append(f"{key}={val}")
        else:
            gone.append(key)
    say(f"program_options set: {', '.join(done) or 'none'}"
        + (f"; no longer fields, skipped: {', '.join(gone)}" if gone else ""))


def cfg_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; the program's seed is a positive int32."""
    return seed % (2**31 - 1) + 1


def build_config(cell: Cell, *, seed: int, backend: str, stop_ns: int,
                 data_dir, say=lambda _m: None, extra_options=None):
    """The cell's configuration under its traffic as ``ConfigOptions``,
    through ``ConfigOptions.from_yaml`` (never config/presets.py or
    scenarios.py, which later PRs may edit)."""
    import yaml

    from shadow_tpu.config.options import ConfigOptions

    config, traffic = cell.config, cell.traffic
    env = {**cell.params, "native": str(REPO / "native" / "build"),
           "seed": cfg_seed(seed), "chips": cell.chips}
    if "factory" in config:
        # a columnar / programmatic configuration: "module:function" of the
        # program, called with the substituted factory_args
        mod, _, fn = config["factory"].partition(":")
        cfg = getattr(importlib.import_module(mod), fn)(
            **subst(config.get("factory_args", {}), env))
        cfg.general.seed = env["seed"]
    else:
        groups = config.get("host_groups", []) + traffic.get("host_groups", [])
        doc = {
            "general": {**config.get("general", {}), "stop_time": "1 s",
                        "seed": env["seed"], "heartbeat_interval": None},
            "network": {"graph": {"type": "gml",
                                  "inline": subst(
                                      config["network"]["gml"], env)}},
            "hosts": _expand_groups(groups, env),
        }
        cfg = ConfigOptions.from_yaml(yaml.safe_dump(doc))
    cfg.general.stop_time = int(stop_ns)
    cfg.general.data_directory = str(data_dir)
    cfg.general.heartbeat_interval = None
    cfg.experimental.network_backend = backend
    options = {**config.get("program_options", {}),
               **traffic.get("program_options", {}), **(extra_options or {})}
    set_program_options(cfg, options, say)
    return cfg
