#!/usr/bin/env python
"""Fleet-sweep driver: batch S whole simulations into ONE compiled
vmapped lane kernel and export the ``SWEEP_<name>-S<k>.json`` artifact
(docs/sweep.md).

The sweep axes come from, in precedence order:

1. ``--spec SPEC.yaml`` (or ``experimental.sweep_spec`` in the config):
   a sweep-spec document with ``seeds`` / ``faults`` / ``overrides``
   axes, expanded as a Cartesian product;
2. ``--sweep-size N`` (or ``experimental.sweep_size``): the seed-grid
   shorthand — seeds ``base .. base + N - 1``, no other axes.

Worked example — the partition/heal fault demo swept over a 4-seed
grid, every scenario batched into one kernel on the lane backend:

    JAX_PLATFORMS=cpu python scripts/sweep.py examples/partition-heal.yaml \\
        --sweep-size 4 --backend tpu --data-directory /tmp/sweep.data

Prints one JSON line with the batch wall time and the headline
``scenarios_per_hour`` throughput key (whole-batch wall divided into S
scenario-completions, scaled to an hour), and writes the SWEEP artifact
through the Recorder lifecycle into ``--data-directory``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", help="base scenario config (YAML)")
    ap.add_argument(
        "--spec",
        help="sweep spec YAML (axes: seeds/faults/overrides); "
        "defaults to experimental.sweep_spec from the config",
    )
    ap.add_argument(
        "--sweep-size", type=int, default=None,
        help="seed-grid shorthand: N seeds from general.seed upward; "
        "defaults to experimental.sweep_size from the config",
    )
    ap.add_argument("--name", default=None, help="sweep/artifact name")
    ap.add_argument(
        "--backend", choices=("cpu", "tpu"), default=None,
        help="override experimental.network_backend for the whole fleet "
        "(tpu = the batched lane kernel; cpu = the serial oracle arm)",
    )
    ap.add_argument(
        "--data-directory", default=None,
        help="artifact output dir (SWEEP_*.json via the Recorder)",
    )
    args = ap.parse_args(argv)

    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.obs.recorder import Recorder
    from shadow_tpu.sweep import (
        SweepEngine,
        SweepSpec,
        build_report,
        expand_variants,
    )
    from shadow_tpu.sweep.report import artifact_name

    base = ConfigOptions.from_yaml_file(args.config)
    if args.backend is not None:
        base.experimental.network_backend = args.backend

    spec_path = args.spec or base.experimental.sweep_spec
    if spec_path is not None:
        spec = SweepSpec.from_yaml(Path(spec_path).read_text())
    else:
        size = (
            args.sweep_size
            if args.sweep_size is not None
            else base.experimental.sweep_size
        )
        if size < 1:
            ap.error(
                "no sweep axes: pass --spec/--sweep-size or set "
                "experimental.sweep_spec/sweep_size in the config"
            )
        spec = SweepSpec.seed_grid(base.general.seed, size)
    if args.name is not None:
        spec.name = args.name

    variants = expand_variants(base, spec)
    sweep = SweepEngine(variants)
    results = sweep.run()
    report = build_report(sweep, results, name=spec.name)

    if sweep.backend == "cpu":
        wall = sweep._cpu_wall
    else:
        wall = results[0].wall_seconds
    line = {
        "sweep": spec.name,
        "size": sweep.size,
        "backend": sweep.backend,
        # where the batched lane kernel ran (None for the cpu oracle arm)
        "device": (
            sweep.engines[0].device_info() if sweep.backend == "tpu"
            else None
        ),
        "traces": sweep.traces,
        "wall_seconds": round(wall, 3),
        "scenarios_per_hour": round(sweep.size * 3600.0 / wall, 1),
        "sim_seconds_each": variants[0].cfg.general.stop_time / 1_000_000_000,
    }

    if args.data_directory is not None:
        rec = Recorder(
            run_id=f"sweep_{spec.name}", out_dir=args.data_directory
        )
        rec.add_artifact(artifact_name(report), report)
        fin = rec.finalize(extra={"sweep": line})
        line["artifacts"] = fin.get("artifact_paths", [])

    print(json.dumps(line, sort_keys=True))
    return 0


if __name__ == "__main__":
    from shadow_tpu.device import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
