#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; its configuration
and traffic are data files, its runner is ``runners/<runner>.py`` (named in
the configuration file), and each per-layer metric is
``layer_metrics/<name>.py``.  Nothing here branches on a cell's name.

The LAST line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``.  Earlier lines say what was compared beside each limit.
A run that finds no TPU, or fewer chips than the cell asks for, exits
nonzero and prints no result: there is no CPU fallback.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
for p in (str(REPO), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)


def say(msg: str) -> None:
    """An earlier line of the run, with the seconds since process start."""
    print(f"[bench {time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, found by name (a name may hold
    dots, so it is loaded by path)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    """What a runner is handed: the cell, the run's arguments, a scratch
    directory, the compile probe, and the clock that splits set-up from
    the reference's time (which is in neither set-up nor the window).  The
    peak of device memory is read when the first reference span opens, so
    that it stays the timed program's: a runner runs whatever the check
    alone needs on the device inside ``reference``."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 tmp: Path, probe, t_start: float, devices) -> None:
        self.cell = cell
        self.devices = list(devices)
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.tmp = tmp
        self.probe = probe
        self.t_start = t_start
        self.say = say
        self.reference_spans: list[tuple[float, float]] = []
        self._peak: int | None | bool = False  # False until read

    def program_peak_bytes(self) -> int | None:
        """Peak bytes in use on the fullest device, as first read."""
        if self._peak is False:
            self._peak = memory_peak_bytes(self.devices)
        return self._peak

    @contextlib.contextmanager
    def reference(self, what: str):
        """Time spent in the plain reference and the comparison."""
        self.program_peak_bytes()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self.reference_spans.append((t0, t1))
            say(f"reference: {what} took {t1 - t0:.2f}s (not set-up, not "
                "the window)")

    def build(self, backend: str, stop_ns: int, tag: str,
              extra_options=None):
        from lib.cells import build_config

        return build_config(
            self.cell, seed=self.seed, backend=backend, stop_ns=stop_ns,
            data_dir=self.tmp / tag, say=say, extra_options=extra_options)

    def setup_seconds(self, window_open: float) -> float:
        """Process start to the opening of the window, less the
        reference's time before it."""
        ref = sum(min(t1, window_open) - t0
                  for t0, t1 in self.reference_spans if t0 < window_open)
        return window_open - self.t_start - ref


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device; None where the backend
    does not report it (XLA:CPU in the rehearsals)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def drive(cell, seed: int, seconds: float, trace: bool, devices,
          t_start: float | None = None) -> dict:
    """Everything of a run after the look for a chip: run the cell's
    runner, read the metrics, decide ``correct``.  Returns the result
    object (the tests call this with XLA:CPU devices)."""
    from lib.peaks import peaks_for
    from lib.probe import CompileProbe
    from shadow_tpu.device import describe_devices

    t_start = T_START if t_start is None else t_start
    probe = CompileProbe()
    tmp = Path(tempfile.mkdtemp(prefix="bench_"))
    try:
        ctx = Ctx(cell, seed, seconds, trace, tmp, probe, t_start, devices)
        runner = load_module("runners", cell.runner)
        out = runner.run(ctx)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    w_open, w_close = out["window"]
    raw = out["raw"]
    raw["setup_s"] = ctx.setup_seconds(w_open)
    raw["trace_compile_s"] = probe.trace_compile_before(w_open)
    raw["compile_secs_in_window"] = probe.compiles_between(w_open, w_close)
    for w, secs, backend in probe.events:
        if backend and w_open <= w <= w_close:
            say(f"backend compile (or cache read) of {secs:.3f}s ended "
                f"{w - w_open:.2f}s into the window")
    say(f"window: {w_close - w_open:.3f}s wall; set-up {raw['setup_s']:.3f}s; "
        f"compile cache hits {probe.hits} misses {probe.misses}; "
        f"backend compiles inside the window: "
        f"{len(raw['compile_secs_in_window'])}")

    cmp = out["comparison"]
    for line in cmp.lines():
        say(line)
    correct = cmp.ok and out["failed"] == 0

    peak = ctx.program_peak_bytes()
    end_to_end = dict(out["end_to_end"])
    end_to_end["setup_s"] = raw["setup_s"]
    if peak is not None:
        end_to_end["peak_hbm_mb"] = peak / 1e6
        hbm = peaks_for(ctx.devices[0].device_kind)["hbm_bytes"]
        say(f"peak of device memory before the check: {peak / 1e6:.3f} MB, "
            f"{100.0 * peak / hbm:.2f}% of the chip's {hbm / 1e9:g} GB")

    device = dict(describe_devices(ctx.devices))
    if out["device_info"] != device:
        raise RuntimeError(
            f"the program placed its state on {out['device_info']}, the cell "
            f"was given {device}")
    device["memory_peak_bytes"] = peak
    metrics: dict = {}
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in end_to_end:
                if device["platform"] != "tpu":
                    # a rehearsal on XLA:CPU, which reports no memory
                    say(f"rehearsal: no {m['name']} off the chip")
                    continue
                raise RuntimeError(
                    f"cell {cell.name!r} did not produce the end-to-end "
                    f"metric {m['name']!r}")
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}
        return result
    tr = out.get("trace")
    if tr is None:
        raise RuntimeError(f"runner {cell.runner!r} took no trace")
    raw["trace"] = tr
    device["busy_s"] = tr["busy_s"]
    device["window_s"] = tr["window_s"]
    result["breakdown"] = tr["breakdown"]
    for m in cell.per_layer:
        mod = load_module("layer_metrics", m["name"])
        if mod.UNIT != m["unit"]:
            raise RuntimeError(
                f"per-layer metric {m['name']!r}: BENCHMARK.json says unit "
                f"{m['unit']!r}, its reader {mod.UNIT!r}")
        value = mod.read(raw)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from lib.cells import CellError, load_cell

    try:
        cell = load_cell(args.workload)
        import shadow_tpu  # noqa: F401  (the system under test; enables x64)
    except (CellError, OSError, ImportError) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2

    import jax

    from lib.peaks import peaks_for
    from shadow_tpu.device import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(
            f"benchmarks/run.py: cell {cell.name!r} needs {cell.chips} TPU "
            f"chip(s); JAX reports {len(devs)} x {devs[0].platform!r} — no "
            "accelerator, no result (there is no CPU fallback)",
            file=sys.stderr)
        return 3
    peaks_for(devs[0].device_kind)  # an unknown device is an error
    # every program goes to the cache, the sub-second lazy ones too, so
    # that only the first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache_dir = enable_compile_cache()
    say(f"cell {cell.name}: config {cell.config.get('name')} x traffic "
        f"{cell.traffic.get('name')}, runner {cell.runner}, chips "
        f"{cell.chips}, seed {args.seed}, seconds {args.seconds:g}, trace "
        f"{args.trace}; jax {jax.__version__}; devices {devs}; compile "
        f"cache {cache_dir}")
    result = drive(cell, args.seed, args.seconds, bool(args.trace),
                   devs[:cell.chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
