"""Crash-safe checkpoints: the on-disk resume anchor (docs/robustness.md).

One recovery law governs every leg of the crash-safety layer:
**deterministic replay from the newest valid state**.  Because the
engines are bit-deterministic (docs/determinism.md), a serialized engine
state *is* the run's prefix: resuming from it and replaying the suffix
reproduces the uninterrupted run byte-for-byte — the event-log suffix
and the final NETOBS/TURNS artifacts match exactly (METRICS wall-clock
fields are excluded from the contract; wall time never replays).

The container format (``STCKPT1``)::

    b"STCKPT1\\n"                      magic (8 bytes)
    u64 big-endian header length
    <header JSON>                      version, backend_kind, epoch_ns,
                                       windows, seed, config_sha,
                                       payload_sha256, summary, ...
    <payload bytes>                    cloudpickle blob (engine + obs
                                       accumulator state)

The header is readable without unpickling anything — that is what
``python -m shadow_tpu.tools checkpoint-inspect`` and retention-scan
validation rely on.  The payload hash is verified before a single byte
is unpickled; the config fingerprint binds a checkpoint to the
determinism-relevant portion of its config (the fault schedule and
observability/runtime knobs are deliberately excluded so a faulted run's
checkpoint validates against the disarmed resume config — the
checkpoint-anchored failover path depends on this).

Checkpoints are scoped to the pure-lane backends (cpu, cpu_mp, tpu).
The hybrid backend's managed native processes hold live OS state (file
descriptors, futexes, real memory) that cannot be snapshotted from the
parent; its crash-safety story is the dispatch retry law plus the
failover boundary (docs/robustness.md).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import struct
from dataclasses import asdict
from pathlib import Path
from typing import Optional

log = logging.getLogger("shadow_tpu.checkpoint")

MAGIC = b"STCKPT1\n"
VERSION = 1

#: backends whose full simulation state is host-serializable
CHECKPOINTABLE_BACKENDS = ("cpu", "cpu_mp", "tpu")


class CheckpointError(RuntimeError):
    """A checkpoint could not be written, read, or validated."""


class ResumeRequest(Exception):
    """Unwound from a window boundary by the run-control ``resume``
    verb: the facade catches it (like ``RestartRequest``), loads the
    named checkpoint, and re-enters the run loop from it."""

    def __init__(self, path: str) -> None:
        self.path = path
        super().__init__(f"resume from {path}")


class GracefulShutdown(BaseException):
    """SIGINT/SIGTERM landed: the run stopped at a window boundary,
    wrote its final checkpoint, and is unwinding for a clean exit.

    Derives from ``BaseException`` (like ``KeyboardInterrupt``) so
    engine-level ``except Exception`` recovery paths — failover,
    worker supervision — never swallow an operator's stop request.
    """

    #: distinct exit code (EX_TEMPFAIL: the run can be resumed)
    EXIT_CODE = 75

    def __init__(self, signum: int) -> None:
        self.signum = signum
        super().__init__(f"graceful shutdown on signal {signum}")


# -- config fingerprint ------------------------------------------------------

# cfg sections/fields that do not participate in simulation determinism:
# changing any of these between the checkpointed run and the resume run
# must not invalidate the checkpoint.  The fault section is excluded
# wholesale — checkpoint-anchored failover resumes with stalls disarmed.
_GENERAL_EXCLUDE = frozenset({
    "data_directory", "template_directory", "log_level",
    "heartbeat_interval", "progress", "parallelism",
})
_EXPERIMENTAL_EXCLUDE_PREFIXES = ("obs_", "checkpoint_", "netobs_")
_EXPERIMENTAL_EXCLUDE = frozenset({
    "run_control", "perf_logging", "resume_from",
    "worker_heartbeat_s", "worker_restart_max", "dispatch_retry_max",
    "use_cpu_pinning",
})


def _canonical(obj):
    if isinstance(obj, dict):
        return {k: _canonical(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, Path):
        return str(obj)
    return obj


def config_fingerprint(cfg) -> str:
    """SHA-256 over the determinism-relevant portion of a config.

    Two configs with equal fingerprints produce bit-identical
    simulations (same world, workload, seed, and lane semantics), so a
    checkpoint from one may resume under the other.
    """
    doc = asdict(cfg)
    doc.pop("faults", None)
    gen = doc.get("general") or {}
    for k in list(gen):
        if k in _GENERAL_EXCLUDE:
            gen.pop(k)
    exp = doc.get("experimental") or {}
    for k in list(exp):
        if k in _EXPERIMENTAL_EXCLUDE or k.startswith(
            _EXPERIMENTAL_EXCLUDE_PREFIXES
        ):
            exp.pop(k)
    # netobs itself (the boolean) changes lane-state shape on the tpu
    # backend, so it stays in the fingerprint; the netobs_* tuning
    # knobs above do not.
    exp["netobs"] = bool(getattr(cfg.experimental, "netobs", False))
    exp["obs_turns"] = bool(getattr(cfg.experimental, "obs_turns", False))
    blob = json.dumps(_canonical(doc), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


# -- container read/write ----------------------------------------------------

def write_checkpoint(path: str | Path, header: dict, payload: dict) -> Path:
    """Serialize ``payload`` (cloudpickle) and write the STCKPT1
    container atomically: tmp file in the destination directory, fsync,
    rename.  A reader never observes a partial checkpoint."""
    import cloudpickle

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    blob = cloudpickle.dumps(payload)
    hdr = dict(header)
    hdr["version"] = VERSION
    hdr["payload_len"] = len(blob)
    hdr["payload_sha256"] = hashlib.sha256(blob).hexdigest()
    hdr_bytes = json.dumps(hdr, sort_keys=True).encode()
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack(">Q", len(hdr_bytes)))
        f.write(hdr_bytes)
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def read_header(path: str | Path) -> dict:
    """Read and validate the container header without touching the
    payload (beyond an on-disk length check)."""
    path = Path(path)
    with open(path, "rb") as f:
        magic = f.read(len(MAGIC))
        if magic != MAGIC:
            raise CheckpointError(
                f"{path}: not a shadow-tpu checkpoint (bad magic)"
            )
        (hlen,) = struct.unpack(">Q", f.read(8))
        if hlen <= 0 or hlen > 16 * 1024 * 1024:
            raise CheckpointError(f"{path}: implausible header length {hlen}")
        try:
            hdr = json.loads(f.read(hlen).decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise CheckpointError(f"{path}: corrupt header ({e})") from e
    if hdr.get("version") != VERSION:
        raise CheckpointError(
            f"{path}: unsupported checkpoint version {hdr.get('version')!r}"
            f" (this build reads version {VERSION})"
        )
    body = path.stat().st_size - len(MAGIC) - 8 - hlen
    if body != hdr.get("payload_len"):
        raise CheckpointError(
            f"{path}: truncated payload ({body} bytes on disk, header"
            f" says {hdr.get('payload_len')})"
        )
    return hdr


def read_checkpoint(path: str | Path) -> tuple[dict, dict]:
    """Full verified read: header + hash-checked, unpickled payload."""
    import cloudpickle

    path = Path(path)
    hdr = read_header(path)
    with open(path, "rb") as f:
        f.seek(len(MAGIC))
        (hlen,) = struct.unpack(">Q", f.read(8))
        f.seek(len(MAGIC) + 8 + hlen)
        blob = f.read()
    digest = hashlib.sha256(blob).hexdigest()
    if digest != hdr.get("payload_sha256"):
        raise CheckpointError(
            f"{path}: payload hash mismatch (expected"
            f" {hdr.get('payload_sha256')}, got {digest})"
        )
    return hdr, cloudpickle.loads(blob)


def validate_for_config(hdr: dict, cfg) -> None:
    """Refuse a resume whose config diverges on determinism-relevant
    fields — a resumed run under a different world/workload/seed would
    silently break the bit-identity contract."""
    want = config_fingerprint(cfg)
    got = hdr.get("config_sha")
    if got != want:
        raise CheckpointError(
            "checkpoint config fingerprint mismatch: checkpoint was taken"
            f" under config {got}, resume config is {want} — the"
            " determinism-relevant configuration differs (world, workload,"
            " seed, or lane semantics), so an exact resume is impossible"
        )


# -- retention + discovery ---------------------------------------------------

class CheckpointManager:
    """Owns one run's checkpoint directory: naming, atomic writes,
    keep-N retention, and newest-valid discovery.

    File naming is ``ckpt_<run_id>_w<windows>.stckpt`` — the window
    ordinal orders checkpoints without parsing headers; discovery still
    validates each candidate (hash + fingerprint) before trusting it.
    """

    def __init__(
        self,
        directory: str | Path,
        run_id: str,
        cfg,
        keep: int = 3,
    ) -> None:
        self.directory = Path(directory)
        self.run_id = run_id
        self.keep = max(1, int(keep))
        self.cfg = cfg
        self.config_sha = config_fingerprint(cfg)
        self.last_path: Optional[Path] = None

    def _name(self, windows: int) -> str:
        return f"ckpt_{self.run_id}_w{windows:08d}.stckpt"

    def save(
        self,
        payload: dict,
        *,
        backend_kind: str,
        epoch_ns: int,
        windows: int,
        summary: Optional[dict] = None,
    ) -> Path:
        if backend_kind not in CHECKPOINTABLE_BACKENDS:
            raise CheckpointError(
                f"backend {backend_kind!r} is not checkpointable"
                f" (supported: {', '.join(CHECKPOINTABLE_BACKENDS)})"
            )
        header = {
            "backend_kind": backend_kind,
            "run_id": self.run_id,
            "epoch_ns": int(epoch_ns),
            "windows": int(windows),
            "seed": int(self.cfg.general.seed),
            "config_sha": self.config_sha,
            "summary": summary or {},
        }
        path = self.directory / self._name(windows)
        write_checkpoint(path, header, payload)
        self.last_path = path
        self._prune()
        return path

    def _prune(self) -> None:
        files = sorted(self.directory.glob(f"ckpt_{self.run_id}_w*.stckpt"))
        for stale in files[: -self.keep]:
            try:
                stale.unlink()
            except OSError:  # pragma: no cover - racing cleanup is fine
                pass

    def candidates(self) -> list[Path]:
        """This run's checkpoint files, newest (highest window) first."""
        return sorted(
            self.directory.glob(f"ckpt_{self.run_id}_w*.stckpt"),
            reverse=True,
        )

    def newest_valid(
        self, backend_kind: Optional[str] = None
    ) -> Optional[tuple[dict, dict, Path]]:
        """Scan newest-first for a checkpoint that passes every check
        (magic, version, payload hash, config fingerprint, and — when
        given — backend kind).  Invalid candidates are skipped with a
        warning, not fatal: recovery wants the newest *valid* state."""
        for path in self.candidates():
            try:
                hdr, payload = read_checkpoint(path)
                validate_for_config(hdr, self.cfg)
                if (
                    backend_kind is not None
                    and hdr.get("backend_kind") != backend_kind
                ):
                    raise CheckpointError(
                        f"backend kind {hdr.get('backend_kind')!r}, need"
                        f" {backend_kind!r}"
                    )
            except Exception as e:
                log.warning("skipping checkpoint %s: %s", path, e)
                continue
            return hdr, payload, path
        return None


# -- CLI inspector -----------------------------------------------------------

def inspect_main(argv: list[str]) -> int:
    """``python -m shadow_tpu.tools checkpoint-inspect <ckpt> [...]`` —
    print each checkpoint's header and verify its payload hash."""
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m shadow_tpu.tools checkpoint-inspect"
              " <checkpoint.stckpt> [...]")
        return 0 if argv else 2
    status = 0
    for arg in argv:
        path = Path(arg)
        try:
            hdr = read_header(path)
            with open(path, "rb") as f:
                f.seek(len(MAGIC))
                (hlen,) = struct.unpack(">Q", f.read(8))
                f.seek(len(MAGIC) + 8 + hlen)
                digest = hashlib.sha256(f.read()).hexdigest()
            ok = digest == hdr.get("payload_sha256")
        except (OSError, CheckpointError) as e:
            print(f"{path}: INVALID ({e})")
            status = 1
            continue
        print(f"{path}:")
        print(f"  version:      {hdr['version']}")
        print(f"  backend:      {hdr.get('backend_kind')}")
        print(f"  run_id:       {hdr.get('run_id')}")
        print(f"  seed:         {hdr.get('seed')}")
        print(f"  epoch_ns:     {hdr.get('epoch_ns')}")
        print(f"  windows:      {hdr.get('windows')}")
        print(f"  config_sha:   {hdr.get('config_sha')}")
        print(f"  payload:      {hdr.get('payload_len')} bytes,"
              f" sha256 {'OK' if ok else 'MISMATCH'}")
        summary = hdr.get("summary") or {}
        if summary:
            print("  summary:")
            for k in sorted(summary):
                print(f"    {k}: {summary[k]}")
        if not ok:
            status = 1
    return status
