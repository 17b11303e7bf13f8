"""Dump op-category counts of the compiled bench while-body (static
analysis — counts only; it says nothing of time).

Usage: python scripts/hlo_stats.py [hosts] [--text out.txt]
"""

import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import shadow_tpu  # noqa: F401
from shadow_tpu.backend import lanes
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.presets import (
    flagship_mesh_config,
    mixed_flagship_config,
)


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else 10000
    if "--mixed" in sys.argv:
        cfg = mixed_flagship_config(n, sim_seconds=5)
    else:
        cfg = flagship_mesh_config(
            n, sim_seconds=5, queue_capacity=16, pops_per_round=2
        )
    eng = TpuEngine(cfg, log_capacity=0)
    run_fn = lanes.make_run_fn(eng.params, eng.tables)
    state = eng.initial_state()
    compiled = run_fn.lower(state).compile()
    txt = compiled.as_text()
    if "--text" in sys.argv:
        out = sys.argv[sys.argv.index("--text") + 1]
        with open(out, "w") as f:
            f.write(txt)
        print(f"wrote {len(txt)} bytes to {out}")

    # count ops inside the while body computation
    lines = txt.splitlines()
    print(f"total HLO lines: {len(lines)}")
    cat = {}
    for ln in lines:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([a-z0-9\-]+)\(", ln)
        if not m:
            continue
        op = m.group(1)
        cat[op] = cat.get(op, 0) + 1
    for op, cnt in sorted(cat.items(), key=lambda kv: -kv[1]):
        print(f"{cnt:6d}  {op}")
    # fusion/sort/copy summary
    for key in ("fusion", "sort", "copy", "custom-call", "while"):
        print(f"summary {key}: {cat.get(key, 0)}")


if __name__ == "__main__":
    main()
