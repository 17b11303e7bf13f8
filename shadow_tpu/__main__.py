"""CLI entry point: ``python -m shadow_tpu [options] <config.yaml>``.

Mirrors the reference's CLI layering (src/main/core/configuration.rs:52
CliOptions over src/main/shadow.rs:480): a YAML config file (or ``-`` for
stdin, as the reference supports) with CLI flags merged on top, plus
``--show-config`` to print the merged result and exit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys

import shadow_tpu


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="shadow_tpu",
        description="TPU-native discrete-event network simulator "
        "(Shadow-capability rebuild)",
    )
    p.add_argument("config", help="YAML simulation config, or '-' for stdin")
    p.add_argument("--version", action="version", version=shadow_tpu.__version__)
    p.add_argument(
        "--show-config", action="store_true", help="print merged config and exit"
    )
    # common flags with dedicated spellings (the reference's CliOptions)
    flag_map = {
        "--seed": "general.seed",
        "--stop-time": "general.stop_time",
        "--bootstrap-end-time": "general.bootstrap_end_time",
        "--parallelism": "general.parallelism",
        "--data-directory": "general.data_directory",
        "--log-level": "general.log_level",
        "--heartbeat-interval": "general.heartbeat_interval",
        "--network-backend": "experimental.network_backend",
        "--runahead": "experimental.runahead",
        "--resume": "experimental.resume_from",
        "--checkpoint-every-windows": "experimental.checkpoint_every_windows",
        "--checkpoint-dir": "experimental.checkpoint_dir",
    }
    for flag, key in flag_map.items():
        p.add_argument(flag, dest=key, default=None, metavar="V")
    p.add_argument(
        "--progress", action="store_true", help="log heartbeat progress lines"
    )
    p.add_argument(
        "--run-control",
        action="store_true",
        help="interactive pause/step/restart console on stdin "
        "(p / c / cN / n / s / s:<pid> / r / rN at window boundaries)",
    )
    p.add_argument(
        "--perf-logging",
        action="store_true",
        help="print [window-agg]/[host-exec-agg] parallelism telemetry",
    )
    p.add_argument(
        "--obs-metrics",
        action="store_true",
        help="record per-phase wall metrics and write a METRICS_*.json "
        "run report (shadow_tpu/obs/, docs/observability.md)",
    )
    p.add_argument(
        "--obs-trace",
        action="store_true",
        help="record phase spans and export a Chrome-trace/Perfetto JSON "
        "(implies --obs-metrics)",
    )
    p.add_argument(
        "--netobs",
        action="store_true",
        help="record per-host network telemetry (sent/delivered/bytes, "
        "drop-cause accounting, burst-window histogram) and write a "
        "NETOBS_*.json run report (docs/observability.md)",
    )
    p.add_argument(
        "--flowtrace",
        action="store_true",
        help="record per-flow packet-lifecycle events (send, bucket "
        "wait, queue-enter, drop-with-cause, retransmit, delivery) and "
        "write a FLOWS_*.json run report with burst attribution "
        "(docs/observability.md)",
    )
    p.add_argument(
        "--obs-turns",
        action="store_true",
        help="record the device-turn ledger (turn-cause accounting + "
        "fusable-run-length measurement) and write a TURNS_*.json run "
        "report (docs/observability.md)",
    )
    p.add_argument(
        "--set",
        dest="overrides",
        action="append",
        default=[],
        metavar="SECTION.FIELD=VALUE",
        help="generic dotted-key config override (repeatable)",
    )
    p.add_argument(
        "--event-log",
        action="store_true",
        help="keep the event log and write it, canonically sorted (the "
        "determinism-diff artifact); without it the tpu backend runs with "
        "no device event log — counters and sim-stats.json are the same, "
        "and a run is not bounded by the log's capacity",
    )
    p.add_argument(
        "--determinism-check",
        action="store_true",
        help="run the simulation twice and fail unless both runs produce "
        "bit-identical event orderings and counters (the reference's "
        "determinism test, src/test/determinism/, as a CLI mode)",
    )
    return p


def parse_overrides(ns: argparse.Namespace) -> dict[str, object]:
    overrides: dict[str, object] = {}
    for key, val in vars(ns).items():
        if "." in key and val is not None:
            overrides[key] = val
    for item in ns.overrides:
        key, sep, val = item.partition("=")
        if not sep:
            raise SystemExit(f"--set expects SECTION.FIELD=VALUE, got {item!r}")
        overrides[key] = val
    return overrides


def main(argv: list[str] | None = None) -> int:
    from shadow_tpu.config.options import ConfigError, ConfigOptions
    from shadow_tpu.engine.sim import Simulation, device_log_readers

    ns = build_parser().parse_args(argv)
    try:
        if ns.config == "-":
            cfg = ConfigOptions.from_yaml(sys.stdin.read())
        else:
            cfg = ConfigOptions.from_yaml_file(ns.config)
        overrides = parse_overrides(ns)
        if ns.run_control:
            overrides["experimental.run_control"] = True
        if ns.perf_logging:
            overrides["experimental.perf_logging"] = True
        if ns.obs_metrics:
            overrides["experimental.obs_metrics"] = True
        if ns.obs_trace:
            overrides["experimental.obs_trace"] = True
        if ns.netobs:
            overrides["experimental.netobs"] = True
        if ns.flowtrace:
            overrides["experimental.flowtrace"] = True
        if ns.obs_turns:
            overrides["experimental.obs_turns"] = True
        cfg.apply_overrides(overrides)
        cfg.validate()
    except (ConfigError, OSError, KeyError) as e:
        print(f"config error: {e}", file=sys.stderr)
        return 2

    # async buffered logging (the reference's logger crate: records are
    # queued by the emitting thread, formatted+written by a listener
    # thread, each line prefixed with the simulated clock)
    from shadow_tpu.utils.shadow_log import install_async_logging

    install_async_logging(
        level=getattr(logging, cfg.general.log_level.upper(), logging.INFO),
        stream=sys.stderr,
    )
    if ns.show_config:
        print(json.dumps(dataclasses.asdict(cfg), indent=2, default=str))
        return 0

    if ns.determinism_check:
        from shadow_tpu.engine.determinism import determinism_check

        try:
            report = determinism_check(cfg)
        except Exception as e:
            print(f"simulation failed: {e}", file=sys.stderr)
            return 1
        print(report.describe(), file=sys.stderr)
        return 0 if report.identical else 1

    from shadow_tpu.engine.checkpoint import GracefulShutdown

    # a run keeps a device event log only when something will read it:
    # --event-log, or a configuration whose pcap capture rides the log
    sim = Simulation(
        cfg, event_log=ns.event_log or bool(device_log_readers(cfg))
    )
    try:
        result = sim.run()
    except GracefulShutdown as g:
        # SIGINT/SIGTERM: the run stopped cleanly at a window boundary
        # (final checkpoint written, artifacts flushed, workers reaped);
        # exit 75 (EX_TEMPFAIL) marks the run as resumable
        print(
            f"graceful shutdown (signal {g.signum}): resume with "
            "--resume <checkpoint>",
            file=sys.stderr,
        )
        return GracefulShutdown.EXIT_CODE
    except Exception as e:  # surface backend errors with a nonzero exit
        print(f"simulation failed: {e}", file=sys.stderr)
        return 1
    if ns.event_log:
        path = sim.write_event_log(result)
        print(f"event log: {path}", file=sys.stderr)
    if result.process_errors:
        for err in result.process_errors:
            print(f"process error: {err}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    # process entry only: main() called as a function (tests,
    # chip_smoke.py) leaves the cache decision to its caller
    from shadow_tpu.device import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
