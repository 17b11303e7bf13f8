#!/usr/bin/env python3
"""The control of "how ``correct`` is decided": the plain reference put in
the program's place with ONE stated guarantee broken, held to the very
comparison a run makes — and it has to come out as not correct.

    python benchmarks/control.py --workload <name> --seeds 1,2,3

The guarantee broken is the conservative window: the configuration's
``control_options`` set ``experimental.runahead`` above the smallest link
latency (the lookahead), the step that would tempt a later PR because it
halves the number of windows.  Both sides are the CPU oracle
(``network_backend: cpu``), at the sizes a run compares: the traffic's
whole timed horizon (without the event log where the mix has a
``check_ms``, because the timed program of such a cell keeps none) and,
where it has one, the ``check_ms`` horizon with the log.  So this needs no
chip and the benchmark's own runs never run it.  Prints, for every seed,
each number compared beside its limit for the sound reference against
itself (all 0) and for the control (some above 0); exits 0 only if the
control failed the comparison on every seed.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
for p in (str(BENCH_DIR.parent), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_of(cell, seed: int, tmp: Path, say=print):
    """``(sound, control)`` comparisons of cell ``cell`` on ``seed``."""
    from lib import compare
    from lib.cells import build_config
    from shadow_tpu.backend.cpu_engine import CpuEngine

    traffic = cell.traffic
    if "control_options" not in cell.config:
        raise ValueError(f"configuration {cell.config.get('name')!r} names "
                         "no control_options")
    # (stop_time, with the event log): what the cell's runner compares
    horizons = [(int(traffic["horizon_sim_s"] * 1e9), "check_ms" not in traffic)]
    if "check_ms" in traffic:
        horizons.append((int(traffic["check_ms"] * 1e6), True))

    def one(tag: str, stop_ns: int, extra=None):
        d = tmp / f"{tag}{seed}_{stop_ns}"
        res = CpuEngine(build_config(
            cell, seed=seed, backend="cpu", stop_ns=stop_ns, data_dir=d,
            extra_options=extra)).run()
        return res, (compare.host_outputs(d) or None)

    sound, control = compare.Comparison(), compare.Comparison()
    for stop_ns, log in horizons:
        at = f"{stop_ns / 1e9:g} sim-s"
        ref, ref_out = one("ref", stop_ns)
        again, again_out = one("again", stop_ns)
        ctl, ctl_out = one("control", stop_ns, cell.config["control_options"])
        compare.compare_results(sound, f"reference again, {at}", again, ref,
                                out_got=again_out, out_ref=ref_out, log=log)
        compare.compare_results(control, f"control, {at}", ctl, ref,
                                out_got=ctl_out, out_ref=ref_out, log=log)
    return sound, control


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)

    import shadow_tpu  # noqa: F401
    from lib.cells import load_cell

    cell = load_cell(args.workload)
    if cell.runner == "hybrid":
        from runners.hybrid import build_native

        build_native(print)
    tmp = Path(tempfile.mkdtemp(prefix="control_"))
    caught = True
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            sound, control = control_of(cell, seed, tmp)
            for name, cmp in (("sound", sound), ("control", control)):
                for line in cmp.lines():
                    print(f"[control] seed {seed} {name}: {line}")
            print(f"[control] seed {seed}: sound correct={sound.ok}, "
                  f"control correct={control.ok}")
            caught = caught and sound.ok and not control.ok
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"[control] {args.workload}: the control "
          + ("came out as not correct on every seed" if caught
             else "was NOT caught (or the sound reference differed)"))
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
