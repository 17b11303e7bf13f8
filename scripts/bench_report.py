#!/usr/bin/env python
"""Aggregate BENCH_r0*.json into one legible perf-trajectory table.

Every round's driver (or in-container) bench snapshot lands as a
``BENCH_r0N.json`` blob at the repo root, each carrying a ``parsed``
dict of bench.py's JSON line.  This script folds them into a single
key × round table — the repo's perf history — with a delta column
against the reference's 6.38× headline for the rate keys that chase it
(ROADMAP open items 1 and 3).

Usage::

    python scripts/bench_report.py                 # markdown to stdout
    python scripts/bench_report.py --format json   # machine-readable
    python scripts/bench_report.py --write trajectory.md

No BENCH_r0N.json is tracked at present (the pre-chip records were
deleted in PR 23; the on-chip history is PERF.md and the driver's
ledger); the script renders whatever blobs ``--repo`` holds.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE_SPEEDUP = 6.38  # BASELINE.md: the fork's measured headline

# rate keys measured against the 6.38 target (sim-s / wall-s)
TARGET_KEYS = (
    "value",
    "mixed_sim_s_per_wall_s",
    "managed_sim_s_per_wall_s",
    "hybrid_sim_s_per_wall_s",
)

# stable row order: headline first, then the ladder, then context keys
KEY_ORDER = [
    "value",
    "mixed_sim_s_per_wall_s",
    "managed_sim_s_per_wall_s",
    "hybrid_sim_s_per_wall_s",
    "cpu_sim_s_per_wall_s",
    "speedup_vs_cpu_backend",
    "configs.tgen_mesh_10k_udp",
    "configs.tgen_mesh_10k_mixed",
    "configs.tgen_mesh_1k_mixed",
    "configs.udp_star_100",
    "configs.transfer_2host",
    "configs.managed_relay_chains",
    "configs.managed_relay_chains_large_hybrid",
    "hybrid_phase_wall_s.device_turn",
    "hybrid_phase_wall_s.syscall_service",
    "hybrid_phase_wall_s.worker_pipe",
    "hybrid_phase_wall_s.injection",
    "hybrid_phase_wall_s.egress",
    "hybrid_sync.device_sync_s",
    "hybrid_sync.syscall_service_s",
    "hybrid_sync.device_turns",
    # device-turn ledger keys (obs/turns.py — ROADMAP item 1's
    # instrument: why each blocking turn exists and how many could fuse)
    "turns",
    "empty_injection_turns",
    "fusable_runs",
    "fusable_run_p50",
    "fusable_run_p99",
    "fusable_run_max",
    "kfusion_headroom",
    "kfusion_headroom_freerun",
    # realized k-window fusion (ISSUE 13: backend/hybrid.py fused law)
    "hybrid_fused_runs",
    "hybrid_fused_windows",
    "hybrid_turns_saved",
    "hybrid_fuse_rollbacks",
    "hybrid_achieved_fusion",
    "hybrid_unfused_turns",
    "hybrid_async_hits",
    "hybrid_async_misses",
    # netobs telemetry keys (drop-cause / retransmit totals + the
    # burst-window histogram buckets — open item 3's evidence base;
    # mixed_window_hist.b* buckets follow in the sorted tail)
    "mesh_drops.loss",
    "mesh_drops.codel",
    "mesh_drops.queue",
    "mixed_drops.loss",
    "mixed_drops.codel",
    "mixed_drops.queue",
    "mixed_retransmits",
    "mixed_windows",
    "mixed_throttled",
    # flowtrace burst attribution (obs/flowtrace.py — which flow classes
    # fill the busy mixed_window_hist buckets; the per-bucket class
    # ranking stays machine-readable in the BENCH json's
    # mixed_flow_attribution.buckets list)
    "mixed_flow_attribution.sample",
    "mixed_flow_attribution.num_events",
    "mixed_flow_attribution.num_flows",
    "mixed_flow_attribution.events_lost",
    # fleet-sweep throughput (shadow_tpu/sweep/, docs/sweep.md): an
    # S-scenario seed grid through ONE compiled vmapped kernel —
    # whole-scenario completions per hour plus the compile-amortization
    # ratio (S x serial-with-compile wall over the batch wall)
    "scenarios_per_hour",
    "sweep_compile_amortization",
    "sweep_size",
    "sweep_hosts",
    "sweep_sim_seconds",
    "sweep_batch_wall_s",
    "sweep_serial_wall_s",
    "sweep_traces",
    # multi-chip sharded lane plane (shadow_tpu/parallel/,
    # docs/multichip.md): the columnar 100k-host mesh sharded over the
    # host axis — the sharded rate, the 1-device reference, and the
    # strong-scaling efficiency rate(D) / (D x rate(1))
    "multichip_sim_s_per_wall_s",
    "multichip_1dev_sim_s_per_wall_s",
    "multichip_scaling_efficiency",
    "multichip_devices",
    "multichip_hosts",
    "multichip_sim_seconds",
    "multichip_build_s",
    "configs.columnar_mesh_100k_sharded",
]

KEY_LABEL = {
    "value": "tgen_mesh_10k (headline)",
}

# bucket histograms render as ONE compact sparkline row per group
# instead of a raw b0..bN key explosion (the per-bucket values stay
# machine-readable in --format json)
HIST_GROUPS = ("mixed_window_hist", "fusable_run_hist")
HIST_KEY_RE = re.compile(
    r"^(" + "|".join(HIST_GROUPS) + r")\.b(\d+)$"
)
SPARK_CHARS = "·▁▂▃▄▅▆▇█"  # index 0 = empty bucket, 1..8 = scaled


def sparkline(buckets: list[int]) -> str:
    """Deterministic unicode sparkline: each bucket scales against the
    row's max (empty buckets print the midline dot)."""
    vmax = max(buckets, default=0)
    if vmax <= 0:
        return "—"
    return "".join(
        SPARK_CHARS[0] if v <= 0 else SPARK_CHARS[1 + (7 * int(v)) // vmax]
        for v in buckets
    )


def hist_tables(
    rounds: dict[str, dict[str, object]],
) -> dict[str, dict[str, list[int]]]:
    """group -> round tag -> dense bucket list (width = the max bucket
    index seen for that group across all rounds, so columns align)."""
    width: dict[str, int] = {}
    raw: dict[str, dict[str, dict[int, int]]] = {}
    for tag, flat in rounds.items():
        for key, val in flat.items():
            m = HIST_KEY_RE.match(key)
            if not m:
                continue
            group, idx = m.group(1), int(m.group(2))
            width[group] = max(width.get(group, 0), idx + 1)
            raw.setdefault(group, {}).setdefault(tag, {})[idx] = int(val)
    return {
        group: {
            tag: [cells.get(i, 0) for i in range(width[group])]
            for tag, cells in per_tag.items()
        }
        for group, per_tag in raw.items()
    }


def _flatten(d: dict, prefix: str = "") -> dict[str, object]:
    out: dict[str, object] = {}
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = v
    return out


def load_rounds(repo: str = REPO) -> dict[str, dict[str, object]]:
    """round tag (``r01``...) -> flattened numeric keys of ``parsed``."""
    rounds: dict[str, dict[str, object]] = {}
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        m = re.search(r"BENCH_(r\d+)\.json$", path)
        if not m:
            continue
        with open(path) as f:
            doc = json.load(f)
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            flat = _flatten(parsed)
            # a single-scenario round (e.g. HYBRID_ONLY) aliases its one
            # rate as "value"; the dedicated key already carries it, so
            # drop the alias rather than pollute the headline row
            if not str(parsed.get("metric", "")).startswith(
                "sim_seconds_per_wall_second"
            ):
                flat.pop("value", None)
                flat.pop("vs_baseline", None)
            rounds[m.group(1)] = flat
    return rounds


def build_table(
    rounds: dict[str, dict[str, object]],
) -> tuple[list[str], list[str]]:
    """(ordered round tags, ordered row keys present in any round)."""
    # numeric round order: lexicographic would put r100 before r99
    tags = sorted(rounds, key=lambda t: int(t[1:]))
    seen: set[str] = set()
    for flat in rounds.values():
        seen.update(flat)
    # per-bucket histogram keys collapse into sparkline rows (below)
    seen = {k for k in seen if not HIST_KEY_RE.match(k)}
    keys = [k for k in KEY_ORDER if k in seen]
    # every remaining key follows the curated order — nested (dotted)
    # ones included, so a new phase/sync key can never silently vanish
    # from the history table
    keys += sorted(k for k in seen if k not in KEY_ORDER)
    return tags, keys


def _fmt(v: object) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def render_markdown(rounds: dict[str, dict[str, object]]) -> str:
    tags, keys = build_table(rounds)
    lines = [
        "# Bench trajectory",
        "",
        "Generated by `python scripts/bench_report.py` from the "
        "`BENCH_r0N.json` blobs under `--repo` "
        "artifacts — re-run it when a new round lands.  Rate keys are "
        "sim-seconds per wall-second; `Δ vs 6.38` divides the latest "
        f"value by the reference headline ({REFERENCE_SPEEDUP}×, "
        "BASELINE.md) for the keys that chase it.  Each BENCH file's "
        "`source`/`device` names where its numbers ran; per-phase "
        "`hybrid_phase_wall_s.*` keys are the obs-measured wall "
        "attribution (docs/observability.md).  Bucket histograms "
        "(`mixed_window_hist`, `fusable_run_hist`) render as one "
        "sparkline row each — log2 buckets left to right from b0, "
        "scaled per cell; `·` is an empty bucket (raw values: "
        "`--format json`).",
        "",
    ]
    header = "| key | " + " | ".join(tags) + " | Δ vs 6.38 |"
    sep = "|---" * (len(tags) + 2) + "|"
    lines += [header, sep]
    for key in keys:
        cells = [_fmt(rounds[t].get(key)) for t in tags]
        delta = ""
        if key in TARGET_KEYS:
            latest = None
            for t in reversed(tags):
                if rounds[t].get(key) is not None:
                    latest = rounds[t][key]
                    break
            if latest is not None:
                delta = f"{float(latest) / REFERENCE_SPEEDUP:.2%}"
        label = KEY_LABEL.get(key, key)
        lines.append(f"| `{label}` | " + " | ".join(cells) + f" | {delta} |")
    hists = hist_tables(rounds)
    for group in HIST_GROUPS:
        if group not in hists:
            continue
        cells = [
            sparkline(hists[group][t]) if t in hists[group] else "—"
            for t in tags
        ]
        lines.append(
            f"| `{group}` (log2 buckets, b0→) | "
            + " | ".join(cells) + " |  |"
        )
    lines.append("")
    return "\n".join(lines)


def render_json(rounds: dict[str, dict[str, object]]) -> str:
    tags, keys = build_table(rounds)
    return json.dumps(
        {
            "reference_speedup": REFERENCE_SPEEDUP,
            "rounds": tags,
            "table": {
                key: {t: rounds[t].get(key) for t in tags} for key in keys
            },
            # the sparkline rows' raw buckets, machine-readable
            "histograms": hist_tables(rounds),
        },
        indent=2,
    )


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="aggregate BENCH_r0N.json into a trajectory table"
    )
    p.add_argument("--format", choices=("markdown", "json"), default="markdown")
    p.add_argument(
        "--write", metavar="FILE", default=None,
        help="write the rendered table to FILE instead of stdout",
    )
    p.add_argument(
        "--repo", default=REPO, help="repo root holding BENCH_r0N.json"
    )
    ns = p.parse_args(argv)
    rounds = load_rounds(ns.repo)
    if not rounds:
        print("bench_report: no BENCH_r0N.json artifacts found", file=sys.stderr)
        return 1
    text = (
        render_markdown(rounds) if ns.format == "markdown"
        else render_json(rounds)
    )
    if ns.write:
        with open(ns.write, "w") as f:
            f.write(text if text.endswith("\n") else text + "\n")
        print(f"bench_report: wrote {ns.write}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
