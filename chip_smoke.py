#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that shadow-tpu still starts on the chip.

Drives the main path once on ONE attached TPU, through the entry points a
user calls (``Simulation`` and ``shadow_tpu.__main__.main``), at the repo's
flagship widths, and holds every result to the CPU oracle:

  a. pure lane plane: the bench's 10 000-host UDP mesh through the facade on
     the fused device driver, the step driver and the CPU oracle — counters,
     event log and per-host telemetry equal three ways; then the same mesh
     at 1 000 hosts over a 1 sim-s horizon (event log vs oracle);
  b. mixed TCP/UDP mesh on the tiered stream path: 10 000 hosts to
     stop_time with every stream flow accounted for, counters and event
     log identical to the oracle at that full width;
  c. the north-star path: ``native/`` rebuilt from source, then 151 real OS
     processes over 1 000 lane hosts on the hybrid engine with spawned
     syscall workers, equal to ``network_backend: cpu`` in process output,
     counters and event log;
  d. the CLI called in-process (``--determinism-check`` on examples/phold.yaml)
     and the checkpoint -> ``--resume`` round trip on the tpu backend.

Every phase is a hard assertion; nothing is caught and carried past.  One
process, one ``import jax``; it exits nonzero before any simulation unless
``jax.devices()[0].platform == "tpu"`` (no CPU fallback).  Rates printed on
the way are labelled "smoke, not a benchmark".  The LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--multichip`` (run by hand on a four-chip host; the driver never passes
it) runs ONLY the sharded lane plane and what it is compared with: the
columnar 100 000-host tgen mesh on a 4-device ``Mesh`` vs one device of the
same process.  Its last line reports ``"count": 4``.

``--rehearse`` is the off-chip rehearsal of on-chip-measurement §2: the same
control flow at tiny sizes on XLA:CPU.  It proves paths and arguments, never
a result: it prints NO result line.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
MS = 1_000_000  # ns

# Sizes.  Widths are the repo's flagship widths and are never cut; horizons
# are what fits the facade's fixed 200 000-record device event log (a 10k
# mesh delivers ~1M packets per sim-s) and the 1200 s contract.
REAL = dict(
    udp_hosts=10_000, udp_ns=150 * MS,
    udp_log_hosts=1_000, udp_log_ns=1_000 * MS,
    mixed_hosts=10_000, mixed_ns=120 * MS,
    chains=25, clients=3, peers=1_000, hybrid_s=4,
    phold_stop="2s",
    multi_hosts=100_000, multi_s=2,
)
TINY = dict(
    udp_hosts=64, udp_ns=150 * MS,
    udp_log_hosts=32, udp_log_ns=300 * MS,
    mixed_hosts=200, mixed_ns=120 * MS,
    chains=2, clients=2, peers=20, hybrid_s=4,
    phold_stop="500ms",
    multi_hosts=256, multi_s=1,
)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- compile accounting ------------------------------------------------------


class CompileProbe:
    """Counts XLA compiles, their seconds and persistent-cache traffic off
    ``jax.monitoring`` — per phase, so a warm second run is visibly cheaper."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.setup_s = 0.0
        self.hits = 0
        self.misses = 0
        self.on_compile = None  # optional callback(seconds)
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
            if self.on_compile is not None:
                self.on_compile(secs)
        if event.startswith("/jax/core/compile/"):
            self.setup_s += secs  # trace + lowering + backend compile

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.compile_s, self.hits, self.misses)


PROBE: CompileProbe = None  # set by main(), once jax is imported


@contextlib.contextmanager
def phase(name: str, summary: dict):
    say(f"phase {name}: start")
    c0, s0, h0, m0 = PROBE.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    c1, s1, h1, m1 = PROBE.snapshot()
    row = {
        "wall_s": round(wall, 2),
        "compiles": c1 - c0,
        "compile_s": round(s1 - s0, 2),
        "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
    }
    summary[name] = row
    say(
        f"phase {name}: PASSED wall={row['wall_s']}s "
        f"compiles={row['compiles']} compile_s={row['compile_s']} "
        f"cache_hits={row['cache_hits']} cache_misses={row['cache_misses']}"
    )


# -- running and comparing ---------------------------------------------------


def run_facade(cfg, backend: str, data_dir: Path, **experimental):
    """One ``Simulation(cfg).run()`` — the normal entry point.  Returns
    ``(result, sim, sim-stats dict)``; the set-up seconds (trace + compile)
    spent inside the run are left on ``sim.setup_s``."""
    from shadow_tpu.engine.sim import Simulation

    cfg = copy.deepcopy(cfg)
    cfg.experimental.network_backend = backend
    cfg.general.data_directory = str(data_dir)
    cfg.general.heartbeat_interval = None
    for key, val in experimental.items():
        assert hasattr(cfg.experimental, key), key
        setattr(cfg.experimental, key, val)
    sim = Simulation(cfg)
    setup0 = PROBE.setup_s
    result = sim.run()
    sim.setup_s = PROBE.setup_s - setup0
    stats = json.loads((data_dir / "sim-stats.json").read_text())
    assert sim.failovers == 0 and stats["failovers"] == 0, (
        f"{data_dir.name}: the device path failed over to the cpu engine"
    )
    assert sim.restarts == 0, f"{data_dir.name}: unexpected restart"
    assert result.sim_time_ns == cfg.general.stop_time, (
        f"{data_dir.name}: stopped at {result.sim_time_ns}"
    )
    assert not result.process_errors, result.process_errors
    return result, sim, stats


def assert_on_device(stats: dict, device: dict, what: str) -> None:
    """sim-stats.json must name the very device JAX reported."""
    assert stats["backend"] == "tpu", (what, stats["backend"])
    assert stats["device"] == device, (
        f"{what}: sim-stats names {stats['device']}, expected {device}"
    )


#: counters only one backend keeps (its own bookkeeping, not a simulated
#: statistic): the lane program's iteration/launch counts, the oracle's
#: sender-side byte total.
BACKEND_ONLY = {"lane_iters", "lane_delivered", "lane_sends",
                "tgen_sent_bytes"}


def assert_counters_equal(a, b, what: str) -> None:
    keys = (set(a.counters) | set(b.counters)) - BACKEND_ONLY
    diff = {
        k: (a.counters.get(k), b.counters.get(k))
        for k in sorted(keys) if a.counters.get(k) != b.counters.get(k)
    }
    assert not diff, f"{what}: counters differ: {diff}"
    assert a.rounds == b.rounds, f"{what}: rounds {a.rounds} != {b.rounds}"


def assert_logs_equal(a, b, what: str) -> int:
    la, lb = a.log_tuples(), b.log_tuples()
    if la != lb:
        n = min(len(la), len(lb))
        i = next((i for i in range(n) if la[i] != lb[i]), n)
        raise AssertionError(
            f"{what}: event logs differ at record {i} of "
            f"{len(la)}/{len(lb)}: {la[i:i + 1]} vs {lb[i:i + 1]}"
        )
    assert la, f"{what}: empty event log"
    return len(la)


def assert_netobs_equal(eng_a, eng_b, what: str) -> dict:
    """Per-host telemetry (obs/netobs.py): every per-host counter array and
    the window histogram bit-identical; no record lost, nothing shed."""
    import numpy as np

    sa, sb = eng_a.netobs_snapshot(), eng_b.netobs_snapshot()
    assert sa is not None and sb is not None, f"{what}: no netobs snapshot"
    for key in sorted(set(sa["arrays"]) | set(sb["arrays"])):
        xa, xb = np.asarray(sa["arrays"][key]), np.asarray(sb["arrays"][key])
        if not np.array_equal(xa, xb):
            bad = np.flatnonzero(xa != xb)
            raise AssertionError(
                f"{what}: per-host {key} differs on {bad.size} hosts, "
                f"first host {bad[0]}: {xa[bad[0]]} vs {xb[bad[0]]}"
            )
    assert np.array_equal(sa["window_hist"], sb["window_hist"]), (
        f"{what}: window histograms differ"
    )
    for snap in (sa, sb):
        assert int(snap.get("log_lost", 0)) == 0, f"{what}: log_lost"
        assert int(np.asarray(snap["arrays"]["drop_cross_shed"]).sum()) == 0, (
            f"{what}: cross-block shed"
        )
    return {"sent": int(np.asarray(sa["arrays"]["sent"]).sum())}


def smoke_rate(result, setup_s: float) -> str:
    """One reading of a first call.  The engine's wall includes tracing and
    compiling its own program (``setup_s``, off jax.monitoring, also counts
    the small set-up compiles around it), so a rate is printed only where
    the run clearly outlasts its set-up — and is a smoke reading even then."""
    wall = result.wall_seconds
    out = f"engine wall {wall:.2f}s, first call (trace+compile {setup_s:.2f}s)"
    if wall > 2 * setup_s and wall - setup_s > 1.0:
        rate = result.sim_time_ns / 1e9 / (wall - setup_s)
        out += f"; {rate:.3f} sim-s/wall-s after set-up (smoke, not a benchmark)"
    return out


def clean_rate(sim_ns: int, wall: float) -> str:
    """A precompiled program's single timed execution."""
    return (
        f"{wall:.3f}s wall, {sim_ns / 1e9 / wall:.3f} sim-s/wall-s, one "
        "reading of a precompiled program (smoke, not a benchmark)"
    )


# -- phase a: pure lane plane ------------------------------------------------


def pure_cfg(n_hosts: int, stop_ns: int):
    """The pure-mesh shape of the benchmark's ``tgen_mesh_10k``."""
    from shadow_tpu.config.presets import flagship_mesh_config

    cfg = flagship_mesh_config(n_hosts, queue_capacity=16, pops_per_round=2)
    cfg.experimental.tpu_cross_capacity = 8
    cfg.general.stop_time = stop_ns
    return cfg


def phase_a(sz: dict, tmp: Path, device: dict) -> None:
    cfg = pure_cfg(sz["udp_hosts"], sz["udp_ns"])
    fused, sim_f, st_f = run_facade(cfg, "tpu", tmp / "a_fused")
    assert_on_device(st_f, device, "a fused")
    say(f"a: fused driver, {sz['udp_hosts']} hosts: "
        f"{smoke_rate(fused, sim_f.setup_s)}")
    # the step driver is what run-control and checkpointing select; naming
    # a checkpoint directory (no periodic writes) is the facade's switch.
    # netobs rides this run and the oracle: the per-host counters
    step, sim_s, st_s = run_facade(
        cfg, "tpu", tmp / "a_step", netobs=True,
        checkpoint_dir=str(tmp / "a_step" / "ck"),
    )
    assert_on_device(st_s, device, "a step")
    say(f"a: step driver, {step.rounds} rounds: "
        f"{smoke_rate(step, sim_s.setup_s)}")
    oracle, sim_o, _ = run_facade(cfg, "cpu", tmp / "a_cpu", netobs=True)
    for name, r in (("fused", fused), ("step", step)):
        assert_counters_equal(r, oracle, f"a {name} vs oracle")
        n = assert_logs_equal(r, oracle, f"a {name} vs oracle")
    tot = assert_netobs_equal(sim_s.engine, sim_o.engine, "a step vs oracle")
    assert fused.counters["tgen_recv_bytes"] > 0 and tot["sent"] > 0
    say(
        f"a: {sz['udp_hosts']} hosts x {sz['udp_ns'] / 1e9:g} sim-s: fused == "
        f"step == oracle ({n} log records, {oracle.rounds} rounds, per-host "
        "telemetry identical, log_lost=0, no cross-block shed)"
    )
    # a longer horizon (100 windows) at a width whose log fits
    cfg = pure_cfg(sz["udp_log_hosts"], sz["udp_log_ns"])
    dev, sim_d, st = run_facade(cfg, "tpu", tmp / "a_log_tpu")
    assert_on_device(st, device, "a log")
    ora, _, _ = run_facade(cfg, "cpu", tmp / "a_log_cpu")
    assert_counters_equal(dev, ora, "a log")
    n = assert_logs_equal(dev, ora, "a log")
    say(
        f"a: {sz['udp_log_hosts']} hosts x {sz['udp_log_ns'] / 1e9:g} "
        f"sim-s: event log bit-identical to the oracle ({n} records): "
        f"{smoke_rate(dev, sim_d.setup_s)}"
    )


# -- phase b: mixed TCP/UDP mesh ---------------------------------------------


def mixed_cfg(n_hosts: int, stop_ns: int):
    from shadow_tpu.config.presets import mixed_flagship_config

    cfg = mixed_flagship_config(n_hosts)
    cfg.general.stop_time = stop_ns
    return cfg


def phase_b(sz: dict, tmp: Path, device: dict) -> None:
    n = sz["mixed_hosts"]
    pairs = max(n // 100, 1)
    cfg = mixed_cfg(n, sz["mixed_ns"])
    res, sim, st = run_facade(cfg, "tpu", tmp / "b_tpu")
    assert_on_device(st, device, "b")
    eng = sim.engine
    assert eng.params.stream_tiered, "b: the tiered stream path did not engage"
    # every stream flow accounted for: one tier row pair per configured
    # flow, every flow past its handshake (bytes on the wire), none lost
    assert eng._s_flows == pairs, (eng._s_flows, pairs)
    done = int(res.counters.get("stream_flows_done", 0))
    assert 0 <= done <= pairs
    assert 0 < res.counters["stream_rx_bytes"] <= pairs * 2_000_000
    assert res.counters["stream_rx_segs"] >= pairs
    assert res.counters["tgen_recv_bytes"] > 0
    # one compile buys the whole comparison: the oracle runs the same
    # full-width config, so bit-identity is checked at 10 000 hosts
    # rather than on a second, narrower program
    ora, _, _ = run_facade(cfg, "cpu", tmp / "b_cpu")
    assert_counters_equal(res, ora, "b")
    nrec = assert_logs_equal(res, ora, "b")
    say(
        f"b: mixed mesh, {n} hosts, {pairs} stream flows on the tiered path "
        f"ran to stop_time ({done} complete, "
        f"{res.counters['stream_rx_bytes']} stream bytes in "
        f"{res.counters['stream_rx_segs']} segments); counters and event "
        f"log ({nrec} records) bit-identical to the oracle: "
        f"{smoke_rate(res, sim.setup_s)}"
    )


# -- phase c: real binaries over the device data plane -----------------------


def build_native() -> None:
    """``native/`` from tracked sources into a clean build directory."""
    for tool in ("make", "cc"):
        if shutil.which(tool) is None:
            raise RuntimeError(
                f"phase c needs `{tool}` to build native/ from source"
            )
    shutil.rmtree(REPO / "native" / "build", ignore_errors=True)
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", str(REPO / "native")], check=True,
                   capture_output=True)
    for name in ("libshadow_shim.so", "tcpecho", "relay"):
        assert (REPO / "native" / "build" / name).exists(), name
    say(f"c: native/ rebuilt from source in {time.perf_counter() - t0:.1f}s")


class ChildWatch(threading.Thread):
    """Samples the hybrid engine's spawned syscall workers while the parent
    holds the chip: each must be pinned to the CPU platform and must never
    map libtpu (libtpu admits one process per chip)."""

    def __init__(self, get_procs) -> None:
        super().__init__(daemon=True)
        self.get_procs = get_procs
        self.seen: dict[int, dict] = {}
        self.violations: list[str] = []
        self._stop_evt = threading.Event()

    def run(self) -> None:
        while not self._stop_evt.wait(0.25):
            for p in self.get_procs():
                pid = p.pid
                try:
                    env = Path(f"/proc/{pid}/environ").read_bytes().split(b"\0")
                    maps = Path(f"/proc/{pid}/maps").read_text()
                except OSError:
                    continue  # not started yet / already gone
                if not maps:
                    continue  # exited, not yet reaped: /proc reads empty
                rec = self.seen.setdefault(pid, {"samples": 0})
                rec["samples"] += 1
                if b"JAX_PLATFORMS=cpu" not in env:
                    self.violations.append(f"pid {pid}: not pinned to cpu")
                if "libtpu" in maps:
                    self.violations.append(f"pid {pid}: mapped libtpu")

    def stop(self) -> None:
        self._stop_evt.set()
        self.join()


def host_outputs(data_dir: Path) -> dict:
    """Every managed process's stdout/stderr bytes, keyed by relative path."""
    out = {}
    for path in sorted((data_dir / "hosts").rglob("*")):
        if path.is_file() and path.suffix in (".stdout", ".stderr"):
            out[str(path.relative_to(data_dir))] = path.read_bytes()
    return out


HYBRID_COUNTERS = ("udp_tx_bytes", "udp_rx_bytes", "managed_exit_clean",
                   "managed_tcp_rx_bytes", "tgen_recv_bytes")


def phase_c(sz: dict, tmp: Path, device: dict) -> None:
    from shadow_tpu.backend.hybrid import MpHybridEngine
    from shadow_tpu.config.scenarios import (
        managed_chain_config,
        managed_proc_count,
    )
    from shadow_tpu.engine.sim import Simulation

    build_native()
    shape = dict(chains=sz["chains"], clients_per_chain=sz["clients"],
                 peers=sz["peers"], sim_seconds=sz["hybrid_s"],
                 rounds=8, size=2048)
    n_procs = managed_proc_count(sz["chains"], sz["clients"])

    # the device run: parent owns the chip, syscall plane on spawned workers
    cfg = managed_chain_config(tmp / "c_tpu", backend="tpu",
                               hybrid_workers=0, **shape)
    sim = Simulation(cfg)
    # sim.engine appears during run(); its spawned workers are _mp[1]
    watch = ChildWatch(lambda: getattr(sim.engine, "_mp", ((), ()))[1])
    late = {"compiles": 0, "seconds": 0.0}

    def on_compile(secs: float) -> None:
        eng = sim.engine
        if eng is not None and eng.sync_stats["device_turns"] >= 1:
            late["compiles"] += 1
            late["seconds"] += secs

    PROBE.on_compile = on_compile
    watch.start()
    setup0 = PROBE.setup_s
    try:
        hyb = sim.run()
    finally:
        watch.stop()
        PROBE.on_compile = None
    setup_s = PROBE.setup_s - setup0
    eng = sim.engine
    stats = json.loads((tmp / "c_tpu" / "sim-stats.json").read_text())
    assert_on_device(stats, device, "c hybrid")
    assert isinstance(eng, MpHybridEngine), type(eng).__name__
    assert eng.workers >= 2, f"c: only {eng.workers} syscall worker"
    assert sim.failovers == 0 and sim.restarts == 0
    sync = eng.sync_stats
    assert sync["dispatch_retries"] == 0, sync["dispatch_retries"]
    procs = eng._mp[1]
    assert len(procs) == eng.workers
    codes = [p.exitcode for p in procs]
    assert codes == [0] * eng.workers, f"c: worker exit codes {codes}"
    assert not watch.violations, watch.violations[:5]
    assert len(watch.seen) == eng.workers, (
        f"c: sampled {len(watch.seen)} of {eng.workers} workers"
    )

    # the same config on the cpu backend
    ora = Simulation(
        managed_chain_config(tmp / "c_cpu", backend="cpu", **shape)
    ).run()
    # the horizon is cut to a few sim-s, so late-starting clients (and the
    # origin that waits for all of them) are still running at stop_time:
    # the final-state report must be the SAME on both backends
    assert sorted(hyb.process_errors) == sorted(ora.process_errors), (
        hyb.process_errors, ora.process_errors
    )
    for key in HYBRID_COUNTERS:
        assert hyb.counters.get(key) == ora.counters.get(key), (
            f"c: {key}: {hyb.counters.get(key)} != {ora.counters.get(key)}"
        )
    assert hyb.rounds == ora.rounds, (hyb.rounds, ora.rounds)
    nrec = assert_logs_equal(hyb, ora, "c hybrid vs cpu")
    out_t, out_c = host_outputs(tmp / "c_tpu"), host_outputs(tmp / "c_cpu")
    assert out_t.keys() == out_c.keys() and out_t, "c: process output files"
    bad = [k for k in out_t if out_t[k] != out_c[k]]
    assert not bad, f"c: process output differs: {bad[:5]}"
    assert hyb.counters["managed_exit_clean"] > 0
    assert hyb.counters["managed_tcp_rx_bytes"] > 0
    turns = sync["device_turns"]
    say(
        f"c: {n_procs} OS processes over {sz['peers']} lane hosts, "
        f"{eng.workers} workers (all exit 0, cpu-pinned, none mapped "
        f"libtpu): counters, {nrec} log records, {len(out_t)} output files "
        f"equal to network_backend: cpu; {hyb.counters['managed_exit_clean']}"
        " clean exits; dispatch_retries=0 failovers=0"
    )
    say(
        f"c: {turns} device turns / {hyb.rounds} rounds, "
        f"device_sync {sync['device_sync_s']:.2f}s = "
        f"{sync['device_sync_s'] / max(turns, 1) * 1e3:.2f} ms/turn (smoke; "
        f"blocking readback wall over all turns, lazy compiles included), "
        f"syscall_service {sync['syscall_service_s']:.2f}s; compilations "
        f"after the first turn: {late['compiles']} "
        f"({late['seconds']:.1f}s); {smoke_rate(hyb, setup_s)}"
    )


# -- phase d: the CLI, in-process --------------------------------------------


def phase_d(sz: dict, tmp: Path, device: dict) -> None:
    from shadow_tpu.__main__ import main as cli_main

    # a child `python -m shadow_tpu` cannot have the chip while this
    # process holds it: the CLI is called as a function
    rc = cli_main([
        str(REPO / "examples" / "phold.yaml"), "--network-backend", "tpu",
        "--determinism-check", "--stop-time", sz["phold_stop"],
        "--data-directory", str(tmp / "d_det"),
    ])
    assert rc == 0, f"d: --determinism-check exited {rc}"
    rc = cli_main([
        str(REPO / "examples" / "phold.yaml"), "--network-backend", "tpu",
        "--stop-time", sz["phold_stop"],
        "--data-directory", str(tmp / "d_run"),
    ])
    assert rc == 0, f"d: CLI run exited {rc}"
    stats = json.loads((tmp / "d_run" / "sim-stats.json").read_text())
    assert_on_device(stats, device, "d cli")
    assert stats["failovers"] == 0
    say("d: CLI --determinism-check and plain run exit 0; sim-stats.json "
        f"names {stats['device']}")
    spec = importlib.util.spec_from_file_location(
        "_checkpoint_smoke", REPO / "scripts" / "checkpoint_smoke.py"
    )
    ck = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ck)
    n = ck._round_trip(tmp, "tpu")
    for name in ("tpu-ref", "tpu-full", "tpu-res"):
        st = json.loads((tmp / name / "sim-stats.json").read_text())
        assert_on_device(st, device, f"d {name}")
        assert st["failovers"] == 0
    say(f"d: checkpoint -> --resume round trip byte-identical on the tpu "
        f"backend ({n} checkpoints)")


# -- --multichip: the sharded lane plane -------------------------------------


def phase_multichip(sz: dict) -> None:
    import jax
    import numpy as np

    from shadow_tpu import parallel
    from shadow_tpu.backend.tpu_engine import TpuEngine
    from shadow_tpu.config.columnar import columnar_mesh_config

    n, want = sz["multi_hosts"], 4

    def make_cfg():
        cfg = columnar_mesh_config(n, sim_seconds=sz["multi_s"],
                                   queue_capacity=16, pops_per_round=2)
        cfg.experimental.tpu_cross_capacity = 8
        cfg.experimental.mesh_devices = want
        return cfg

    cfg = make_cfg()
    n_dev = parallel.negotiate_from_config(cfg, n)
    assert n_dev == want, f"mesh stepped down to {n_dev} of {want} devices"
    mesh = parallel.make_mesh(n_dev)
    mesh_devs = list(mesh.devices.flat)
    assert len({d.id for d in mesh_devs}) == want

    # sharded: the engine's own placement + the driver parallel/ hands it
    eng = TpuEngine(cfg, log_capacity=0, netobs=True)
    eng.attach_mesh(mesh)
    state = eng.place_state(eng.initial_state())

    def check_placement(s, what: str) -> None:
        for f in sorted(parallel.LANE_FIELDS):
            x = getattr(s, f)
            if not isinstance(x, jax.Array):
                continue  # plane compiled out
            shards = x.addressable_shards
            assert len(shards) == want, (what, f, len(shards))
            assert {sh.device.id for sh in shards} == {
                d.id for d in mesh_devs}, (what, f)
            for sh in shards:
                assert sh.data.shape[0] == n // want, (
                    what, f, sh.data.shape)
        for f in ("rounds", "iters", "now_we_hi", "nb_hist"):
            x = getattr(s, f)
            shards = x.addressable_shards
            assert len(shards) == want, (what, f)
            for sh in shards:
                assert sh.data.shape == x.shape, (what, f, sh.data.shape)

    check_placement(state, "placed state")
    run_fn = parallel.make_sharded_run_fn(eng.params, eng.tables, mesh)
    compiled = run_fn.lower(state).compile()  # set-up, outside the timer
    t0 = time.perf_counter()
    final = jax.block_until_ready(compiled(state))
    wall4 = time.perf_counter() - t0
    # the program leaves its argument alone (an engine keeps it and starts
    # its next run from it); nothing below reads `state` again
    assert not state.q_thi.is_deleted(), "sharded input was consumed"
    del state
    check_placement(final, "final state")
    per_shard = [
        int(np.asarray(sh.data).sum())
        for sh in final.n_sends.addressable_shards
    ]
    assert all(v > 0 for v in per_shard), f"idle shard: sends {per_shard}"
    res4 = eng.collect(final, wall4)
    assert eng.device_info()["count"] == want, eng.device_info()
    nb4 = eng.netobs_snapshot()

    # the same scenario on one device of the same process
    cfg1 = make_cfg()
    cfg1.experimental.mesh_devices = 0
    eng1 = TpuEngine(cfg1, log_capacity=0, netobs=True)
    res1 = eng1.run(mode="device", precompile=True)
    assert eng1.device_info()["count"] == 1, eng1.device_info()
    nb1 = eng1.netobs_snapshot()

    assert res4.counters == res1.counters, {
        k: (res4.counters.get(k), res1.counters.get(k))
        for k in set(res4.counters) | set(res1.counters)
        if res4.counters.get(k) != res1.counters.get(k)
    }
    assert res4.rounds == res1.rounds
    for key in sorted(nb1["arrays"]):
        assert np.array_equal(nb4["arrays"][key], nb1["arrays"][key]), (
            f"per-host {key} differs between 4 devices and 1"
        )
    assert np.array_equal(nb4["window_hist"], nb1["window_hist"])
    assert res4.counters["tgen_recv_bytes"] > 0
    say(
        f"multichip: {n} hosts x {sz['multi_s']} sim-s on "
        f"{[str(d) for d in mesh_devs]}: {n // want} lanes per device, "
        f"per-shard sends {per_shard}; counters and per-host telemetry "
        f"bit-identical to one device ({res1.rounds} rounds)"
    )
    say(f"multichip: 4 devices: {clean_rate(res4.sim_time_ns, wall4)}")
    say(f"multichip: 1 device: "
        f"{clean_rate(res1.sim_time_ns, res1.wall_seconds)}")


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the 4-device sharded phase (four chips)")
    ap.add_argument("--phases", default="a,b,c,d",
                    help="comma list of default phases to run (debugging; "
                    "the result line is printed only when all four ran)")
    ap.add_argument("--rehearse", action="store_true",
                    help="off-chip rehearsal at tiny sizes on XLA:CPU; "
                    "prints no result line")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        print(
            f"chip_smoke: jax.devices()[0].platform is {platform!r}, not "
            "'tpu' — no accelerator, no result (there is no CPU fallback)",
            file=sys.stderr,
        )
        return 2
    if args.rehearse and platform == "tpu":
        print("chip_smoke: --rehearse is for machines without the chip",
              file=sys.stderr)
        return 2
    want = 4 if args.multichip else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} device(s), JAX reports {len(devs)}",
              file=sys.stderr)
        return 2

    import jaxlib

    import shadow_tpu  # noqa: F401  (enables x64)
    from shadow_tpu.device import describe_devices, enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # version string only; absent off-chip installs
        libtpu = "unknown"
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} "
        f"python {sys.version.split()[0]}")
    say(f"devices: {devs}")
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")
    # the lane program places single-device state on JAX's default device
    device = describe_devices(devs[:1])
    sz = TINY if args.rehearse else REAL
    global PROBE
    PROBE = CompileProbe()
    summary: dict = {}
    t_all = time.perf_counter()
    phases = [p for p in args.phases.split(",") if p]
    tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.multichip:
            with phase("multichip", summary):
                phase_multichip(sz)
        else:
            if "a" in phases:
                with phase("a", summary):
                    phase_a(sz, tmp, device)
            if "b" in phases:
                with phase("b", summary):
                    phase_b(sz, tmp, device)
            if "c" in phases:
                with phase("c", summary):
                    phase_c(sz, tmp, device)
            if "d" in phases:
                with phase("d", summary):
                    phase_d(sz, tmp, device)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    total = time.perf_counter() - t_all
    say(f"summary: {json.dumps(summary)}")
    say(f"total wall {total:.1f}s, compile {PROBE.compile_s:.1f}s in "
        f"{PROBE.compiles} compiles, cache hits {PROBE.hits} misses "
        f"{PROBE.misses}")
    out_dir = REPO / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / ("chip_smoke_multichip.json" if args.multichip
                else "chip_smoke.json")).write_text(json.dumps({
                    "phases": summary, "total_wall_s": round(total, 1),
                    "device": describe_devices(devs),
                    "rehearsal": bool(args.rehearse),
                }, indent=1) + "\n")
    if args.rehearse:
        say("REHEARSAL passed (XLA:CPU, tiny sizes) — not a chip run, "
            "no result line")
        return 0
    if not args.multichip and phases != ["a", "b", "c", "d"]:
        say(f"partial run (phases {phases}) — no result line")
        return 0
    print(json.dumps({"ok": True, "device": describe_devices(devs)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
