"""Quick pure-mesh rate probe on the current default device.

Usage: python scripts/mesh_probe.py [sim_seconds] [repeats]
Env: PROBE_HOSTS (10000), PROBE_CROSS (8), PROBE_CAP (16), PROBE_K (2),
     PROBE_UNROLL (1)

End-to-end, precompiled, best-of-N (see docs/tpu-backend.md).
"""

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import shadow_tpu  # noqa: F401
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.presets import flagship_mesh_config

SIM_S = int(sys.argv[1]) if len(sys.argv) > 1 else 5
REPEATS = int(sys.argv[2]) if len(sys.argv) > 2 else 3
N = int(os.environ.get("PROBE_HOSTS", "10000"))

cfg = flagship_mesh_config(
    N, sim_seconds=SIM_S,
    queue_capacity=int(os.environ.get("PROBE_CAP", "16")),
    pops_per_round=int(os.environ.get("PROBE_K", "2")),
)
cfg.experimental.tpu_cross_capacity = int(os.environ.get("PROBE_CROSS", "8"))
cfg.experimental.tpu_round_unroll = int(os.environ.get("PROBE_UNROLL", "1"))

eng = TpuEngine(cfg, log_capacity=0)
t0 = time.perf_counter()
best = eng.run(mode="device", precompile=True)
compile_s = time.perf_counter() - t0 - best.wall_seconds
rates = [best.sim_seconds_per_wall_second]
for i in range(REPEATS - 1):
    r = eng.run(mode="device")
    rates.append(r.sim_seconds_per_wall_second)
    if r.sim_seconds_per_wall_second > best.sim_seconds_per_wall_second:
        best = r
iters = best.counters.get("lane_iters", 0)
print(
    f"hosts={N} sim_s={SIM_S} cap={cfg.experimental.tpu_lane_queue_capacity}"
    f" K={cfg.experimental.tpu_events_per_round}"
    f" cross={cfg.experimental.tpu_cross_capacity}"
    f" unroll={cfg.experimental.tpu_round_unroll}"
)
print(f"compile ~{compile_s:.1f}s  iters={iters}")
print(f"rates: {[round(x, 3) for x in rates]}")
print(
    f"best {best.sim_seconds_per_wall_second:.4f} sim_s/wall_s  "
    f"{best.wall_seconds / max(iters, 1) * 1e3:.3f} ms/iter"
)
