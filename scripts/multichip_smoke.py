#!/usr/bin/env python
"""End-to-end multi-chip smoke (``make multichip-smoke``, wired into
``make gate``).

Forces an 8-virtual-device CPU JAX backend (no TPU pod needed) and
certifies the sharded lane plane (docs/multichip.md):

1. **Device-count invariance, netobs on** — the phold facade run
   produces a bit-identical event log and byte-identical NETOBS
   artifact at 1, 2, 4, and 8 devices.
2. **Mixed-mesh invariance** — the mixed TCP/UDP flagship (stream tier
   + datagram mesh crossing it) is bit-identical at 1 vs 8 devices.
3. **Nonzero per-device work** — every shard of the 8-device phold
   run's per-lane send counters is nonzero: the mesh actually spreads
   the simulation, nobody idles.  (The mixed run's stream-pair sends
   ride the replicated stream tier, so its per-lane counters are the
   wrong probe for this.)
4. **Hybrid transfer invariance** — the managed hybrid run under a
   2-device mesh keeps every ``sync_stats`` transfer count and the
   event log unchanged (the host<->device boundary stays replicated).
5. **Columnar 100k startup** — the columnar factory builds a 100k-host
   engine + initial state in under 30 s (the classic per-host walk is
   the thing this path deletes).

Exit 0 = all assertions hold; any failure raises (nonzero exit).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

# BEFORE jax import: 8 virtual CPU devices
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
BUILD = REPO / "native" / "build"


def _phold_yaml(data_dir: Path, mesh_devices: int) -> str:
    return f"""
general: {{stop_time: 300ms, seed: 11, data_directory: {data_dir},
           heartbeat_interval: null}}
experimental: {{network_backend: tpu, netobs: true,
               tpu_events_per_round: 2, mesh_devices: {mesh_devices}}}
hosts:
  n:
    count: 8
    processes: [{{path: phold, args: --messages 3 --size 600}}]
"""


def _hybrid_yaml(data_dir: Path, mesh_devices: int) -> str:
    mesh = "\n".join(f"""
  zm{i:03d}:
    network_node_id: 0
    processes:
      - path: tgen-mesh
        args: --interval 50ms --size 600
        start_time: 0 s
""" for i in range(4))
    return f"""
general: {{stop_time: 1s, seed: 21, data_directory: {data_dir},
           heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{network_backend: tpu, hybrid_workers: 1,
               mesh_devices: {mesh_devices}}}
hosts:
  cli:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [client, 11.0.0.2, "9000", "3", "100"]
  srv:
    network_node_id: 0
    processes:
      - path: {BUILD / 'pingpong'}
        args: [server, "9000", "3"]
{mesh}
"""


def main() -> int:
    import jax
    import numpy as np

    from shadow_tpu import parallel
    from shadow_tpu.backend.tpu_engine import TpuEngine
    from shadow_tpu.config.columnar import columnar_mesh_config
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.config.presets import mixed_flagship_config
    from shadow_tpu.engine.sim import Simulation

    assert len(jax.devices()) >= 8, (
        f"expected 8 virtual devices, have {len(jax.devices())} "
        "(XLA_FLAGS must be set before jax import)"
    )
    tmp = Path(tempfile.mkdtemp(prefix="multichip-smoke-"))
    try:
        # -- 1. phold facade invariance at 1/2/4/8, netobs on -------------
        runs = {}
        for d in (0, 2, 4, 8):
            dd = tmp / f"phold{d}"
            cfg = ConfigOptions.from_yaml(_phold_yaml(dd, d))
            sim = Simulation(cfg)
            res = sim.run(write_data=False)
            arts = sorted(dd.glob("NETOBS_*.json"))
            assert len(arts) == 1, arts
            runs[d] = (res.log_tuples(), arts[0].read_bytes())
            want = d if d else 1
            got = sim.engine.mesh.devices.size if sim.engine.mesh else 1
            assert got == want, f"mesh size {got} != requested {want}"
        base_log, base_netobs = runs[0]
        assert base_log, "phold run produced an empty event log"
        assert json.loads(base_netobs)["totals"]["sent"] > 0
        for d in (2, 4, 8):
            assert runs[d][0] == base_log, f"event log diverges at {d} dev"
            assert runs[d][1] == base_netobs, f"NETOBS diverges at {d} dev"
        print("multichip-smoke: phold invariant at 1/2/4/8 devices (netobs on)")

        # -- 3. nonzero per-device work (phold: every lane sends) ---------
        ph = TpuEngine(
            ConfigOptions.from_yaml(_phold_yaml(tmp / "pholdw", 0))
        )
        ph.attach_mesh(parallel.make_mesh(8))
        run_fn = parallel.make_sharded_run_fn(ph.params, ph.tables, ph._mesh)
        final = jax.block_until_ready(
            run_fn(ph.place_state(ph.initial_state()))
        )
        per_shard = [
            int(np.asarray(sh.data).sum())
            for sh in final.n_sends.addressable_shards
        ]
        assert len(per_shard) == 8 and all(c > 0 for c in per_shard), (
            f"idle shard in per-device send counts: {per_shard}"
        )
        print(f"multichip-smoke: per-device sends all nonzero {per_shard}")

        # -- 2. mixed-mesh (stream tier + datagram mesh) invariance -------
        single = TpuEngine(mixed_flagship_config(8, sim_seconds=1))
        ref = single.run(mode="device")
        meshed = TpuEngine(mixed_flagship_config(8, sim_seconds=1))
        meshed.attach_mesh(parallel.make_mesh(8))
        got = meshed.run(mode="device")
        assert got.log_tuples() == ref.log_tuples(), (
            "mixed-mesh event log diverges under the 8-device mesh"
        )
        assert got.counters == ref.counters
        print("multichip-smoke: mixed mesh bit-identical at 8 devices")

        # -- 4. hybrid transfer invariance --------------------------------
        s0 = Simulation(ConfigOptions.from_yaml(_hybrid_yaml(tmp / "h0", 0)))
        r0 = s0.run(write_data=False)
        s2 = Simulation(ConfigOptions.from_yaml(_hybrid_yaml(tmp / "h2", 2)))
        r2 = s2.run(write_data=False)
        assert s2.engine.device.mesh is not None
        assert r2.log_tuples() == r0.log_tuples(), (
            "hybrid event log diverges under the mesh"
        )
        keys = ("device_turns", "scalar_reads", "h2d_copies",
                "inject_blocks", "inject_rows", "inject_bytes",
                "egress_head_reads", "egress_reads", "egress_rows",
                "egress_bytes")
        a, b = dict(s0.engine.sync_stats), dict(s2.engine.sync_stats)
        for k in keys:
            assert a.get(k) == b.get(k), (
                f"hybrid sync_stats[{k}]: {a.get(k)} -> {b.get(k)} under mesh"
            )
        print("multichip-smoke: hybrid transfers unchanged under 2-device mesh")

        # -- 5. columnar 100k startup bound -------------------------------
        t0 = time.perf_counter()
        cfg = columnar_mesh_config(100_000, sim_seconds=1)
        eng = TpuEngine(cfg)
        eng.initial_state()
        dt = time.perf_counter() - t0
        assert dt < 30.0, f"100k-host columnar startup took {dt:.1f}s"
        print(f"multichip-smoke: 100k-host columnar startup in {dt:.1f}s")
        print("multichip-smoke: OK")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
