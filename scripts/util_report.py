"""Device-utilization report (XLA cost-analysis estimate, not a trace).

For the pure and mixed flagship meshes: wall time per while-iteration on
the default device, XLA cost-analysis flops / bytes per iteration, and
the estimated fraction of chip peak (compute and HBM bandwidth).  Not
measured on the attached chip yet; ROADMAP.md A0 replaces the peak
constants below with a table keyed by ``device_kind``.

Usage: python scripts/util_report.py [out.json]
       (default: chiprun_out/util_report.json — an ignored directory)
Env: UTIL_HOSTS (10000), UTIL_SIM_S (5), UTIL_REPEATS (3)
"""

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import shadow_tpu  # noqa: F401
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.presets import (
    flagship_mesh_config,
    mixed_flagship_config,
)

# TPU v5e (lite) public peaks; the report records the assumed values so a
# different chip just needs these constants adjusted
PEAK_BF16_FLOPS = 394e12
PEAK_HBM_BPS = 819e9


def calibrated_fraction(est: float, wall_per_iter: float,
                        peak: float) -> dict:
    """Fraction-of-peak from an XLA cost-analysis estimate, calibrated so
    the reported value can never exceed 1.0 (a physical impossibility).

    XLA's HloCostAnalysis counts the while body once but folds in
    prologue/epilogue work, and the peak constants are nominal — so a
    near-peak workload can produce a raw fraction slightly above 1.
    That over-peak reading means "at the ceiling", not "623x under it":
    the fraction clamps to 1.0 and the raw value is reported alongside
    so the calibration stays auditable (and monotone — adjacent
    measurements of the same workload stay comparable across the 1.0
    boundary, unlike re-dividing by the iteration count, which would
    collapse a 1.05 reading to ~0.002).
    """
    if not est or wall_per_iter <= 0 or peak <= 0:
        return {"frac": None, "raw_frac": None, "calibration": "no-data"}
    raw = est / wall_per_iter / peak
    if raw <= 1.0:
        frac, how = raw, "per_iter"
    else:
        frac, how = 1.0, "clamped"
    return {
        "frac": round(frac, 8),
        "raw_frac": round(raw, 8),
        "calibration": how,
    }

N = int(os.environ.get("UTIL_HOSTS", "10000"))
SIM_S = int(os.environ.get("UTIL_SIM_S", "5"))
REPEATS = int(os.environ.get("UTIL_REPEATS", "3"))


def probe(tag: str, cfg) -> dict:
    import jax

    eng = TpuEngine(cfg, log_capacity=0)
    best = eng.run(mode="device", precompile=True)
    for i in range(REPEATS - 1):
        r = eng.run(mode="device")
        if r.sim_seconds_per_wall_second > best.sim_seconds_per_wall_second:
            best = r
    # cost analysis from the engine's cached executable (no second
    # compile).  NOTE: XLA's HloCostAnalysis counts a while body ONCE
    # (trip count unknown), so the totals approximate ONE iteration plus
    # prologue/epilogue — they are reported as per-iteration ESTIMATES,
    # not divided by the executed count.
    ca = eng._compiled.cost_analysis()
    flops_body = float(ca.get("flops", 0.0))
    bytes_body = float(ca.get("bytes accessed", 0.0))
    # resident device state: a hard lower bound on per-iteration traffic
    # (the while carry is read and written every trip)
    state_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(eng.initial_state())
        if hasattr(x, "dtype")
    )
    iters = int(best.counters.get("lane_iters", 0)) or 1
    wall = best.wall_seconds
    wall_per_iter = wall / iters
    out = {
        "hosts": N,
        "sim_seconds": SIM_S,
        "rate_sim_s_per_wall_s": round(best.sim_seconds_per_wall_second, 4),
        "iters": iters,
        "iters_per_sim_s": round(iters / SIM_S, 1),
        "wall_s": round(wall, 4),
        "wall_per_iter_us": round(wall_per_iter * 1e6, 2),
        "state_bytes": int(state_bytes),
        "est_flops_per_iter": round(flops_body, 1),
        "est_bytes_per_iter": round(bytes_body, 1),
        "est_flops_frac_of_peak": calibrated_fraction(
            flops_body, wall_per_iter, PEAK_BF16_FLOPS
        ),
        "est_hbm_bw_frac_of_peak": calibrated_fraction(
            bytes_body, wall_per_iter, PEAK_HBM_BPS
        ),
    }
    print(tag, json.dumps(out))
    return out


def main() -> None:
    # default output lands in the ignored chiprun_out/ directory: a
    # report is a run artifact, never a tracked record
    out_path = (
        sys.argv[1] if len(sys.argv) > 1
        else os.path.join("chiprun_out", "util_report.json")
    )
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    pure_cfg = flagship_mesh_config(
        N, sim_seconds=SIM_S, queue_capacity=16, pops_per_round=2
    )
    pure_cfg.experimental.tpu_cross_capacity = 8
    report = {
        "assumed_peaks": {
            "bf16_flops": PEAK_BF16_FLOPS,
            "hbm_bytes_per_s": PEAK_HBM_BPS,
        },
        "note": (
            "integer/sort-bound workload: the flops fraction is expected "
            "to be ~0; HBM bandwidth fraction is the meaningful ceiling"
        ),
        "pure": probe("pure", pure_cfg),
        "mixed": probe("mixed", mixed_flagship_config(N, sim_seconds=SIM_S)),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print("wrote", out_path)


if __name__ == "__main__":
    main()
