#!/usr/bin/env python
"""Headline benchmark: sim-seconds per wall-second on the 10k-host tgen
all-to-all mesh (BASELINE.md north-star config #4), TPU lane backend.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device": {"platform", "kind", "count"}, ...}.  ``device`` is read from
the devices the timed engine placed its lane state on.  A rate is a
device metric: the script exits nonzero unless that platform is ``tpu``.
The one exception is a caller that put ``JAX_PLATFORMS=cpu`` into the
environment itself (``make bench-hybrid``, a CI smoke of the code path):
the line then says ``"platform": "cpu"`` and is not a chip number.

``vs_baseline`` divides by the reference's best in-repo measured
sim/wall speedup (6.38x, fork Ethereum-testnet study, BASELINE.md) — the
only quantitative end-to-end number the reference publishes.  The extra
keys record:

- ``mixed_sim_s_per_wall_s`` (+ flow counters): the MIXED TCP/UDP mesh
  of north-star config #4 at FULL scale — the UDP mesh with lane-TCP
  stream flows (backend/lanes_stream.py on device, int32 pairs);
- ``managed_sim_s_per_wall_s``: the MANAGED-process path — relay chains
  of real OS binaries (tcpecho/relay under the shim) with model
  background traffic (config/scenarios.py), the workload class the
  reference's 6.38x was measured on (MyTest/SUMMARY.md) — serviced by
  the parallel MpCpuEngine (``managed_cpu_workers`` reports the actual
  post-clamp worker count of the engine that ran);
- ``hybrid_sim_s_per_wall_s`` (+ ``hybrid_*``): the HYBRID backend at
  the reference's own scale point — 151 managed OS processes in relay
  chains whose syscall plane runs across ``hybrid_workers`` spawned
  workers while every packet (theirs + 1000 tgen lane hosts) rides the
  TPU lane data plane (backend/hybrid.py, ROADMAP open item 1).  The
  ``hybrid_sync`` sub-dict is the host<->device sync-cost breakdown
  (device-sync vs syscall-service wall, per-turn transfer counts/bytes)
  that docs/hybrid.md's analysis is reproduced from;
- ``configs``: the full BASELINE.md evaluation ladder — (1) 2-host
  transfer, (2) 100-host UDP star, (3) 1k mixed mesh, (4) the 10k mixed
  mesh above, (5) the managed relay-chain scenario — each as
  sim-s/wall-s so regressions are visible per tier;
- ``cpu_sim_s_per_wall_s`` / ``speedup_vs_cpu_backend``: the OTHER side
  of the north-star ratio — the same workload timed on the CPU
  thread-per-host path (shorter sim; the rate is steady-state);
- ``scenarios_per_hour`` / ``sweep_compile_amortization``: the FLEET
  throughput plane (shadow_tpu/sweep/, docs/sweep.md) — an S-scenario
  seed grid batched through ONE compiled vmapped kernel, reported as
  whole-scenario completions per hour, with the amortization ratio
  (S x one serial from-scratch wall, compile included, over the batch
  wall) showing what the single compile buys;
- ``multichip_*``: the SHARDED lane plane (shadow_tpu/parallel/,
  docs/multichip.md) — the columnar 100k-host tgen mesh with its
  per-lane arrays sharded over every available device
  (``Mesh(("hosts",))``), vs the same scenario on one device.
  ``multichip_scaling_efficiency`` = rate(D) / (D x rate(1)) is the
  honest strong-scaling number; on forced virtual CPU devices it is
  expected well below 1 (one physical socket), on a real pod slice it
  is the headline.

Env knobs (for local runs; the driver uses the defaults):
  SHADOW_TPU_BENCH_HOSTS         lanes in the mesh    (default 10000)
  SHADOW_TPU_BENCH_SIM_SECONDS   simulated duration   (default 30)
  SHADOW_TPU_BENCH_MIXED_HOSTS   mixed-mesh lanes     (default 10000; 0 skips)
  SHADOW_TPU_BENCH_CPU_SIM_SECONDS  cpu-side duration (default 1; 0 skips)
  SHADOW_TPU_BENCH_LADDER        1 = run the config ladder (default 1)
  SHADOW_TPU_BENCH_MANAGED       1 = run the managed scenario (default 1)
  SHADOW_TPU_BENCH_MANAGED_WORKERS  managed syscall workers (default: cores)
  SHADOW_TPU_BENCH_HYBRID        1 = run the hybrid scenario (default 1)
  SHADOW_TPU_BENCH_HYBRID_ONLY   1 = run ONLY the hybrid scenario (make
                                 bench-hybrid; default 0)
  SHADOW_TPU_BENCH_HYBRID_LANES  hybrid lane (tgen peer) hosts (default 1000)
  SHADOW_TPU_BENCH_HYBRID_CHAINS hybrid relay chains (default 25 -> 151 procs)
  SHADOW_TPU_BENCH_HYBRID_SIM_SECONDS  hybrid simulated duration (default 10)
  SHADOW_TPU_BENCH_HYBRID_WORKERS  hybrid syscall workers (default 0 = cores)
  SHADOW_TPU_BENCH_FLOWS         1 = run the untimed flowtrace evidence
                                 pass on the mixed mesh (default 1)
  SHADOW_TPU_BENCH_FLOWS_SAMPLE  flowtrace sampling fraction (default 0.02)
  SHADOW_TPU_BENCH_SWEEP         1 = run the fleet-sweep batch (default 1)
  SHADOW_TPU_BENCH_SWEEP_SIZE    scenarios per sweep batch (default 8)
  SHADOW_TPU_BENCH_SWEEP_HOSTS   lanes per sweep scenario (default 1000)
  SHADOW_TPU_BENCH_SWEEP_SIM_SECONDS  sweep simulated duration (default 5)
  SHADOW_TPU_BENCH_MULTICHIP     1 = run the sharded-plane scaling point
                                 (default 1)
  SHADOW_TPU_BENCH_MULTICHIP_ONLY  1 = run ONLY the sharded-plane point
                                 (default 0)
  SHADOW_TPU_BENCH_MULTICHIP_HOSTS  columnar mesh lanes (default 100000)
  SHADOW_TPU_BENCH_MULTICHIP_SIM_SECONDS  sharded-run duration (default 2)
  SHADOW_TPU_BENCH_MULTICHIP_DEVICES  mesh size (default 0 = all devices)
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import shadow_tpu  # noqa: F401  (enables jax x64 mode)
from shadow_tpu.backend.tpu_engine import TpuEngine
from shadow_tpu.config.presets import (
    flagship_mesh_config,
    mixed_flagship_config,
    transfer_pair_config,
    udp_star_config,
)

REFERENCE_SPEEDUP = 6.38  # BASELINE.md: 180 sim-s in 28.23 wall-s

N_HOSTS = int(os.environ.get("SHADOW_TPU_BENCH_HOSTS", "10000"))
SIM_SECONDS = int(os.environ.get("SHADOW_TPU_BENCH_SIM_SECONDS", "30"))
# best-of count (run-to-run spread unmeasured on the attached chip; the
# benchmark PR replaces best-of with a median, ROADMAP.md A0)
REPEATS = int(os.environ.get("SHADOW_TPU_BENCH_REPEATS", "5"))
MIXED_HOSTS = int(os.environ.get("SHADOW_TPU_BENCH_MIXED_HOSTS", "10000"))
CPU_SIM_SECONDS = int(os.environ.get("SHADOW_TPU_BENCH_CPU_SIM_SECONDS", "1"))
LADDER = os.environ.get("SHADOW_TPU_BENCH_LADDER", "1") == "1"
MANAGED = os.environ.get("SHADOW_TPU_BENCH_MANAGED", "1") == "1"
MANAGED_WORKERS = int(os.environ.get(
    "SHADOW_TPU_BENCH_MANAGED_WORKERS", str(os.cpu_count() or 1)
))
HYBRID = os.environ.get("SHADOW_TPU_BENCH_HYBRID", "1") == "1"
HYBRID_ONLY = os.environ.get("SHADOW_TPU_BENCH_HYBRID_ONLY", "0") == "1"
HYBRID_LANES = int(os.environ.get("SHADOW_TPU_BENCH_HYBRID_LANES", "1000"))
HYBRID_CHAINS = int(os.environ.get("SHADOW_TPU_BENCH_HYBRID_CHAINS", "25"))
HYBRID_SIM_SECONDS = int(os.environ.get(
    "SHADOW_TPU_BENCH_HYBRID_SIM_SECONDS", "10"
))
HYBRID_WORKERS = int(os.environ.get("SHADOW_TPU_BENCH_HYBRID_WORKERS", "0"))
# netobs evidence run (burst-window histogram for ROADMAP open item 3):
# one extra UNTIMED mixed-mesh run with the telemetry plane on — the
# timed best-of runs stay netobs-off so the headline numbers are clean
NETOBS = os.environ.get("SHADOW_TPU_BENCH_NETOBS", "1") == "1"
# and one with the flowtrace plane on: which flow classes populate the
# busy mixed_window_hist buckets (untimed — flowtrace forces the
# untiered stream path, an equivalent but slower execution)
FLOWS = os.environ.get("SHADOW_TPU_BENCH_FLOWS", "1") == "1"
FLOWS_SAMPLE = float(os.environ.get("SHADOW_TPU_BENCH_FLOWS_SAMPLE", "0.02"))
SWEEP = os.environ.get("SHADOW_TPU_BENCH_SWEEP", "1") == "1"
SWEEP_SIZE = int(os.environ.get("SHADOW_TPU_BENCH_SWEEP_SIZE", "8"))
SWEEP_HOSTS = int(os.environ.get("SHADOW_TPU_BENCH_SWEEP_HOSTS", "1000"))
SWEEP_SIM_SECONDS = int(os.environ.get(
    "SHADOW_TPU_BENCH_SWEEP_SIM_SECONDS", "5"
))
MULTICHIP = os.environ.get("SHADOW_TPU_BENCH_MULTICHIP", "1") == "1"
MULTICHIP_ONLY = os.environ.get(
    "SHADOW_TPU_BENCH_MULTICHIP_ONLY", "0"
) == "1"
MULTICHIP_HOSTS = int(os.environ.get(
    "SHADOW_TPU_BENCH_MULTICHIP_HOSTS", "100000"
))
MULTICHIP_SIM_SECONDS = int(os.environ.get(
    "SHADOW_TPU_BENCH_MULTICHIP_SIM_SECONDS", "2"
))
MULTICHIP_DEVICES = int(os.environ.get(
    "SHADOW_TPU_BENCH_MULTICHIP_DEVICES", "0"
))


# the caller's own pin, read before anything can change it: the only
# case in which a non-TPU platform is accepted (and named in the output)
CALLER_PINNED_CPU = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"


def _require_chip(device: dict) -> None:
    """Fail, do not fall back: a rate from XLA:CPU must never be printed
    under a device key.  ``device`` is a shadow_tpu.device record."""
    if device["platform"] == "tpu" or CALLER_PINNED_CPU:
        return
    print(
        f"bench.py: lane state was placed on {device} — not a TPU.  Run "
        "on the chip, or set JAX_PLATFORMS=cpu yourself for a CPU smoke "
        "of the code path (the output line then says cpu).",
        file=sys.stderr,
    )
    sys.exit(3)


def _pure_cfg(sim_seconds, backend="tpu"):
    cfg = flagship_mesh_config(
        N_HOSTS, sim_seconds=sim_seconds, queue_capacity=16,
        pops_per_round=2, backend=backend,
    )
    # the mesh's round-robin spray is a permutation: each lane receives
    # exactly one packet per window, so a narrow cross block suffices
    # (strict mode would raise if it ever overflowed)
    cfg.experimental.tpu_cross_capacity = 8
    return cfg


def _best_device_rate(cfg, repeats=None, mesh=None):
    """Best sim-s/wall-s over a few device runs of one precompiled
    program, and the device record of the engine that ran them."""
    eng = TpuEngine(cfg, log_capacity=0)
    if mesh is not None:
        eng.attach_mesh(mesh)
    best = eng.run(mode="device", precompile=True)
    for _ in range(max((repeats or REPEATS) - 1, 0)):
        r = eng.run(mode="device")
        if r.sim_seconds_per_wall_second > best.sim_seconds_per_wall_second:
            best = r
    return best, eng.device_info()


def _netobs_evidence(cfg):
    """One netobs-enabled run of ``cfg``: the burst-window histogram
    (nonzero log2 buckets) plus the bucket-throttle total, straight from
    the device telemetry plane (obs/netobs.py).  Untimed — the counters
    are cheap adds, but the evidence run stays separate from the
    best-of timing samples either way.  (Drop/retransmit totals come
    from the TIMED run's own counters — one source of truth.)"""
    import copy as _copy

    cfg = _copy.deepcopy(cfg)
    cfg.experimental.netobs = True
    eng = TpuEngine(cfg, log_capacity=0)
    eng.run(mode="device")
    snap = eng.netobs_snapshot()
    hist = snap["window_hist"]
    return {
        "window_hist": {
            f"b{i}": int(v) for i, v in enumerate(hist) if v
        },
        "windows": int(hist.sum()),
        "throttled": int(snap["arrays"]["throttled"].sum()),
    }


def _flows_evidence(cfg):
    """One flowtrace-enabled run of ``cfg``: the burst-attribution
    ranking — which flow classes (mesh->mesh, stream->stream, ...)
    populate which mixed_window_hist occupancy buckets — from the
    per-flow lifecycle plane (obs/flowtrace.py).  Untimed: flowtrace
    drops the stream tier (bit-identical results, slower execution), so
    this run never mixes with the best-of timing samples.  Sampled
    (FLOWS_SAMPLE of flow pairs) with ``events_lost`` reported, so a
    truncated ring is visible rather than silently biased."""
    import copy as _copy

    from shadow_tpu.obs import flowtrace as ftr

    cfg = _copy.deepcopy(cfg)
    cfg.experimental.flowtrace = True
    cfg.experimental.flowtrace_sample = FLOWS_SAMPLE
    cfg.experimental.flowtrace_capacity = 1 << 20
    # untiered stream packets ride the main [N] queue: the tiered shape
    # (capacity 16) is far too narrow for a 2 MB stream's in-flight win
    cfg.experimental.tpu_lane_queue_capacity = 4096
    eng = TpuEngine(cfg, log_capacity=0)
    eng.run(mode="device")
    snap = eng.flowtrace_snapshot()
    events, trunc = ftr.canonical_events(
        snap["raw"], cfg.experimental.flowtrace_capacity
    )
    names = [h.hostname for h in cfg.hosts]
    report = ftr.build_report(
        "bench", "tpu", cfg.general.seed, names, events,
        trunc + snap["ring_lost"], *ftr.sample_thresh(FLOWS_SAMPLE),
        cfg.experimental.flowtrace_capacity,
    )
    return {
        "sample": FLOWS_SAMPLE,
        "num_events": report["num_events"],
        "num_flows": report["num_flows"],
        "events_lost": report["events_lost"],
        "buckets": [
            {
                "bucket": b["bucket"],
                "windows": b["windows"],
                "top": {
                    tc["class"]: tc["arrivals"] for tc in b["top_classes"]
                },
            }
            for b in report["burst_attribution"]["buckets"]
        ],
    }


def _build_native() -> None:
    repo = os.path.dirname(os.path.abspath(__file__))
    subprocess.run(["make", "-C", os.path.join(repo, "native")],
                   check=True, capture_output=True)


def _managed_rate():
    """The managed-process scenario (relay chains of real binaries) on
    the PARALLEL CPU engine (MpCpuEngine: one spawned syscall worker per
    core, the reference's thread-per-core analog), timed end-to-end as
    sim-s/wall-s.  ``managed_cpu_workers`` is read from the engine that
    actually ran (post-clamp), never assumed."""
    from shadow_tpu.backend.cpu_mp import MpCpuEngine
    from shadow_tpu.config.scenarios import (
        managed_chain_config,
        managed_proc_count,
    )

    _build_native()
    chains, cpc, peers, sim_s = 8, 2, 40, 30
    tmp = tempfile.mkdtemp(prefix="shadow_bench_managed_")
    try:
        cfg = managed_chain_config(
            os.path.join(tmp, "data"), chains=chains,
            clients_per_chain=cpc, peers=peers, sim_seconds=sim_s,
        )
        engine = MpCpuEngine(cfg, workers=MANAGED_WORKERS)
        t0 = time.perf_counter()
        result = engine.run()
        wall = time.perf_counter() - t0
        ok = not result.process_errors
        return {
            "managed_sim_s_per_wall_s": round(sim_s / wall, 4),
            "managed_hosts": len(cfg.hosts),
            "managed_procs": managed_proc_count(chains, cpc),
            "managed_cpu_workers": engine.workers,
            "managed_ok": bool(ok),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _hybrid_rate():
    """The HYBRID flagship (ROADMAP open item 1): 151 managed OS
    processes over 1000+ lane hosts — syscall plane across N worker
    processes, every packet on the TPU lane data plane.  Reports the
    steady-state rate (the engine's run loop), the end-to-end wall
    (construction + compile included), flow-completion counters, the
    host<->device sync-cost breakdown the analysis doc is built from,
    and the obs-measured per-phase wall attribution
    (``hybrid_phase_wall_s``, docs/observability.md)."""
    from shadow_tpu.backend.hybrid import MpHybridEngine
    from shadow_tpu.config.scenarios import (
        managed_proc_count,
        managed_relay_chains_large,
    )
    from shadow_tpu.obs import Recorder

    _build_native()
    tmp = tempfile.mkdtemp(prefix="shadow_bench_hybrid_")
    try:
        cfg = managed_relay_chains_large(
            os.path.join(tmp, "data"), chains=HYBRID_CHAINS,
            peers=HYBRID_LANES, sim_seconds=HYBRID_SIM_SECONDS,
            hybrid_workers=HYBRID_WORKERS,
        )
        # engine built directly: log_capacity=0 skips the device event
        # log (1000 lanes x 20 sends/s overflow the 200k default, and a
        # bench diffs counters, not logs) — the Simulation facade path is
        # what the parity/determinism tests exercise.  The device-turn
        # ledger rides the TIMED run: its rows derive from host-side
        # values the window law reads anyway (zero extra transfers), and
        # its fusion-headroom keys are ROADMAP item 1's design input.
        eng = MpHybridEngine(cfg, workers=HYBRID_WORKERS, log_capacity=0)
        eng.obs = Recorder(run_id="bench-hybrid", turns=True)
        t0 = time.perf_counter()
        result = eng.run()
        total = time.perf_counter() - t0
        sync = {
            k: (round(v, 3) if isinstance(v, float) else int(v))
            for k, v in getattr(eng, "sync_stats", {}).items()
            if isinstance(v, (int, float))  # not phase_s, not the turn ring
        }
        phase_wall = {
            k: round(v, 3)
            for k, v in sorted(eng.obs.metrics.phase_wall_s().items())
        }
        ledger = eng.obs.turns
        ledger.finish()
        tsum = ledger.summary()
        turn_keys = {
            "turns": tsum["turns"],
            "turn_causes": {
                k: v for k, v in tsum["cause_counts"].items() if v
            },
            "empty_injection_turns": tsum["empty_injection_turns"],
            "fusable_runs": tsum["fusable_runs"],
            "fusable_run_p50": tsum["fusable_run_p50"],
            "fusable_run_p99": tsum["fusable_run_p99"],
            "fusable_run_max": tsum["fusable_run_max"],
            # speculative (empty-injection) ceiling + the provable
            # free-run collapse — ROADMAP item 1b / 1a respectively
            "kfusion_headroom": tsum["kfusion_headroom"],
            "kfusion_headroom_freerun": tsum["kfusion_headroom_freerun"],
            "fusable_run_hist": {
                f"b{i}": int(v)
                for i, v in enumerate(ledger.run_hist) if v
            },
            # realized k-window fusion (ISSUE 13): dispatches that
            # covered >= 2 validated windows, the blocking turns they
            # eliminated (net of rollback rebuilds), and the achieved
            # collapse vs the PR 11 headroom predictions above
            "hybrid_fused_runs": tsum["fused_turns"],
            "hybrid_fused_windows": tsum["fused_windows_total"],
            "hybrid_turns_saved": tsum["turns_saved"],
            "hybrid_fuse_rollbacks": tsum["rollbacks"],
            "hybrid_achieved_fusion": tsum["achieved_fusion"],
            "hybrid_unfused_turns": tsum["implied_unfused_turns"],
            "hybrid_async_hits": int(
                eng.sync_stats.get("async_dispatch_hits", 0)
            ),
            "hybrid_async_misses": int(
                eng.sync_stats.get("async_dispatch_misses", 0)
            ),
        }
        return {
            "hybrid_sim_s_per_wall_s": round(
                result.sim_seconds_per_wall_second, 4
            ),
            "hybrid_total_wall_s": round(total, 2),
            "hybrid_hosts": len(cfg.hosts),
            "hybrid_lane_hosts": HYBRID_LANES,
            "hybrid_procs": managed_proc_count(HYBRID_CHAINS, 3),
            "hybrid_workers": getattr(eng, "workers", 1),
            "hybrid_device": eng.device_info(),
            "hybrid_ok": not result.process_errors,
            "hybrid_managed_exits_clean": int(
                result.counters.get("managed_exit_clean", 0)
            ),
            "hybrid_tcp_rx_bytes": int(
                result.counters.get("managed_tcp_rx_bytes", 0)
            ),
            "hybrid_tgen_recv_bytes": int(
                result.counters.get("tgen_recv_bytes", 0)
            ),
            "hybrid_rounds": int(result.rounds),
            "hybrid_sync": sync,
            "hybrid_phase_wall_s": phase_wall,
            **turn_keys,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _sweep_rate():
    """The fleet-throughput keys (shadow_tpu/sweep/): an S-scenario seed
    grid batched through ONE compiled vmapped kernel vs one serial
    from-scratch run of the same scenario.  Both walls include their own
    single compile, so ``sweep_compile_amortization`` = S x serial /
    batch is the honest whole-campaign speedup (compile amortized across
    the fleet + device-parallel execution), and ``scenarios_per_hour``
    is the headline fleet rate the batch sustains."""
    from shadow_tpu.sweep import SweepEngine, SweepSpec, expand_variants

    cfg = flagship_mesh_config(
        SWEEP_HOSTS, sim_seconds=SWEEP_SIM_SECONDS, queue_capacity=16,
        pops_per_round=2,
    )
    cfg.experimental.tpu_cross_capacity = 8
    variants = expand_variants(
        cfg, SweepSpec.seed_grid(cfg.general.seed, SWEEP_SIZE)
    )
    sweep = SweepEngine(variants, log_capacity=0)
    results = sweep.run()
    batch_wall = results[0].wall_seconds
    serial = TpuEngine(variants[0].cfg, log_capacity=0).run(mode="device")
    return {
        "scenarios_per_hour": round(SWEEP_SIZE * 3600.0 / batch_wall, 1),
        "sweep_size": SWEEP_SIZE,
        "sweep_hosts": SWEEP_HOSTS,
        "sweep_sim_seconds": SWEEP_SIM_SECONDS,
        "sweep_batch_wall_s": round(batch_wall, 3),
        "sweep_serial_wall_s": round(serial.wall_seconds, 3),
        "sweep_traces": sweep.traces,
        "sweep_compile_amortization": round(
            SWEEP_SIZE * serial.wall_seconds / batch_wall, 2
        ),
    }


def _multichip_rate():
    """The sharded-lane-plane scaling point (shadow_tpu/parallel/): the
    columnar 100k-host tgen mesh with its per-lane arrays sharded over
    every available device vs the identical scenario on ONE device.
    Both sides are best-of-2 device runs with their own compile
    excluded (precompile=True), so the ratio is steady-state execution.
    ``multichip_scaling_efficiency`` = rate(D) / (D x rate(1)) — the
    strong-scaling efficiency of the collective event exchange.  On
    forced virtual CPU devices (one physical socket) this is expected
    well below 1; the keys exist so a real pod run drops straight into
    the same trajectory."""
    import jax

    from shadow_tpu import parallel
    from shadow_tpu.config.columnar import columnar_mesh_config

    def _cfg():
        cfg = columnar_mesh_config(
            MULTICHIP_HOSTS, sim_seconds=MULTICHIP_SIM_SECONDS,
            queue_capacity=16, pops_per_round=2,
        )
        # round-robin spray is a permutation (see _pure_cfg)
        cfg.experimental.tpu_cross_capacity = 8
        return cfg

    t0 = time.perf_counter()
    eng = TpuEngine(_cfg(), log_capacity=0)
    eng.initial_state()
    build_s = time.perf_counter() - t0

    n_dev = parallel.negotiate_devices(
        MULTICHIP_DEVICES or None, MULTICHIP_HOSTS,
        available=jax.device_count(),
    )
    base, device = _best_device_rate(_cfg(), repeats=2)
    rate1 = base.sim_seconds_per_wall_second
    if n_dev > 1:
        best, device = _best_device_rate(
            _cfg(), repeats=2, mesh=parallel.make_mesh(n_dev)
        )
        rate_n = best.sim_seconds_per_wall_second
    else:
        rate_n = rate1
    return {
        "multichip_device": device,
        "multichip_devices": n_dev,
        "multichip_hosts": MULTICHIP_HOSTS,
        "multichip_sim_seconds": MULTICHIP_SIM_SECONDS,
        "multichip_build_s": round(build_s, 3),
        "multichip_sim_s_per_wall_s": round(rate_n, 4),
        "multichip_1dev_sim_s_per_wall_s": round(rate1, 4),
        "multichip_scaling_efficiency": round(
            rate_n / (n_dev * rate1), 4
        ) if rate1 > 0 else 0.0,
    }


def _emit(out: dict, device: dict) -> None:
    """The one output line, naming the device the timed state ran on."""
    _require_chip(device)
    print(json.dumps({**out, "device": device}))


def main() -> None:
    import jax

    from shadow_tpu.device import describe_devices, enable_compile_cache

    enable_compile_cache()
    # fail before any work is spent when JAX found no chip; the output
    # line re-checks against the devices the engines actually used
    _require_chip(describe_devices(jax.devices()[:1]))
    if MULTICHIP_ONLY:
        # the sharded-plane scaling point alone, one JSON line
        out = {"metric": "multichip_sim_s_per_wall_s", "unit": "sim_s/wall_s"}
        out.update(_multichip_rate())
        out["value"] = out["multichip_sim_s_per_wall_s"]
        out["vs_baseline"] = round(out["value"] / REFERENCE_SPEEDUP, 4)
        _emit(out, out.pop("multichip_device"))
        return
    if HYBRID_ONLY:
        # make bench-hybrid: the hybrid scenario alone, one JSON line
        out = {"metric": "hybrid_sim_s_per_wall_s", "unit": "sim_s/wall_s"}
        out.update(_hybrid_rate())
        out["value"] = out["hybrid_sim_s_per_wall_s"]
        out["vs_baseline"] = round(out["value"] / REFERENCE_SPEEDUP, 4)
        _emit(out, out.pop("hybrid_device"))
        return

    result, device = _best_device_rate(_pure_cfg(SIM_SECONDS))
    value = result.sim_seconds_per_wall_second

    out = {
        "metric": f"sim_seconds_per_wall_second_tgen_mesh_{N_HOSTS}",
        "value": round(value, 4),
        "unit": "sim_s/wall_s",
        "vs_baseline": round(value / REFERENCE_SPEEDUP, 4),
    }
    configs = {"tgen_mesh_10k_udp": round(value, 4)}
    out["mesh_drops"] = {
        "loss": int(result.counters.get("lane_drop_loss", 0)),
        "codel": int(result.counters.get("lane_drop_codel", 0)),
        "queue": int(result.counters.get("lane_drop_queue", 0)),
    }

    # the MIXED TCP/UDP mesh (north-star config #4's full shape): the
    # stream tier on device alongside the datagram mesh, at FULL 10k lanes
    if MIXED_HOSTS > 0:
        mr, _ = _best_device_rate(
            mixed_flagship_config(MIXED_HOSTS, sim_seconds=5)
        )
        out["mixed_hosts"] = MIXED_HOSTS
        out["mixed_sim_s_per_wall_s"] = round(
            mr.sim_seconds_per_wall_second, 4
        )
        out["mixed_stream_pairs"] = max(MIXED_HOSTS // 100, 1)
        out["mixed_stream_flows_done"] = int(
            mr.counters.get("stream_flows_done", 0)
        )
        out["mixed_iters"] = int(mr.counters.get("lane_iters", 0))
        # per-scenario drop/retransmit totals from the timed run's own
        # counters (free: they ride the existing collect readback)
        out["mixed_drops"] = {
            "loss": int(mr.counters.get("lane_drop_loss", 0)),
            "codel": int(mr.counters.get("lane_drop_codel", 0)),
            "queue": int(mr.counters.get("lane_drop_queue", 0)),
        }
        out["mixed_retransmits"] = int(
            mr.counters.get("stream_retransmits", 0)
        )
        configs["tgen_mesh_10k_mixed"] = out["mixed_sim_s_per_wall_s"]
        if NETOBS:
            # the burst-window histogram: open item 3's evidence base —
            # where the mixed mesh's windows actually bunch up
            ev = _netobs_evidence(
                mixed_flagship_config(MIXED_HOSTS, sim_seconds=5)
            )
            out["mixed_window_hist"] = ev["window_hist"]
            out["mixed_windows"] = ev["windows"]
            out["mixed_throttled"] = ev["throttled"]
        if FLOWS:
            # burst ATTRIBUTION: which flow classes fill those buckets
            out["mixed_flow_attribution"] = _flows_evidence(
                mixed_flagship_config(MIXED_HOSTS, sim_seconds=5)
            )

    # BASELINE.md ladder configs 1-3 (4 is above, 5 is the managed run)
    if LADDER:
        r1, _ = _best_device_rate(
            transfer_pair_config(sim_seconds=60), repeats=2
        )
        configs["transfer_2host"] = round(r1.sim_seconds_per_wall_second, 4)
        r2, _ = _best_device_rate(
            udp_star_config(100, sim_seconds=30), repeats=2
        )
        configs["udp_star_100"] = round(r2.sim_seconds_per_wall_second, 4)
        r3, _ = _best_device_rate(
            mixed_flagship_config(1000, sim_seconds=10), repeats=2
        )
        configs["tgen_mesh_1k_mixed"] = round(
            r3.sim_seconds_per_wall_second, 4
        )

    # config #5: the MANAGED relay-chain scenario (real binaries) — the
    # workload class the reference measured itself on
    if MANAGED:
        m = _managed_rate()
        out.update(m)
        configs["managed_relay_chains"] = m["managed_sim_s_per_wall_s"]

    # the HYBRID backend on the large relay-chain scenario: the managed
    # workload class at the reference's scale point, syscall plane across
    # worker processes + packet plane on the lanes
    if HYBRID:
        h = _hybrid_rate()
        out.update(h)
        configs["managed_relay_chains_large_hybrid"] = h[
            "hybrid_sim_s_per_wall_s"
        ]

    # the FLEET throughput plane: S whole scenarios per compiled kernel
    if SWEEP:
        out.update(_sweep_rate())

    # the SHARDED lane plane: the columnar 100k-host mesh over every
    # available device vs one device (docs/multichip.md)
    if MULTICHIP:
        mc = _multichip_rate()
        out.update(mc)
        configs["columnar_mesh_100k_sharded"] = mc[
            "multichip_sim_s_per_wall_s"
        ]

    out["configs"] = configs

    # the OTHER side of the north-star ratio: the PARALLEL CPU backend on
    # the headline workload (shorter sim — the rate is steady-state).
    # MpCpuEngine spawns one worker per core, the honest analog of the
    # reference's thread-per-core scheduler for pure-model hosts
    if CPU_SIM_SECONDS > 0:
        from shadow_tpu.backend.cpu_mp import MpCpuEngine

        workers = int(os.environ.get(
            "SHADOW_TPU_BENCH_CPU_WORKERS", str(os.cpu_count() or 1)
        ))
        cpu_cfg = _pure_cfg(CPU_SIM_SECONDS, backend="cpu")
        cpu_eng = MpCpuEngine(cpu_cfg, workers=workers)
        t0 = time.perf_counter()
        cpu_eng.run()
        cpu_rate = CPU_SIM_SECONDS / (time.perf_counter() - t0)
        out["cpu_sim_s_per_wall_s"] = round(cpu_rate, 4)
        out["speedup_vs_cpu_backend"] = round(value / cpu_rate, 2)
        out["cpu_parallelism"] = cpu_eng.workers  # effective, post-clamp
    _emit(out, device)


if __name__ == "__main__":
    main()
