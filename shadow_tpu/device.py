"""Where the lane program ran, and where its compiled programs are kept.

``--network-backend tpu`` selects the JAX lane program; which device that
program runs on is JAX's choice (the attached TPU, or XLA:CPU when the
environment pins ``JAX_PLATFORMS=cpu``).  Every result therefore names the
device beside the backend: ``describe_devices`` turns the devices an engine
placed its state on into the ``{platform, kind, count}`` record that
``sim-stats.json``, ``METRICS_*.json``, the start-up log line,
``benchmarks/run.py`` and ``chip_smoke.py`` all carry.

``enable_compile_cache`` is the one place the persistent XLA compile cache
is switched on.  Process entry points call it (``python -m shadow_tpu``,
``benchmarks/run.py``, ``chip_smoke.py``, ``scripts/sweep.py``); ``import
shadow_tpu`` does not, so library users and the test suite decide for
themselves.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Iterable

#: the fixed in-checkout cache directory (.gitignore'd).  The directory is
#: part of the cache key's environment: a path that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def describe_devices(devices: Iterable) -> dict:
    """``{platform, kind, count}`` of a set of JAX devices, named after
    the lowest-id one (a mesh is homogeneous)."""
    devs = sorted(set(devices), key=lambda d: d.id)
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def format_device(info) -> str:
    """One-token rendering for log lines: ``tpu:TPU v5 lite x1``."""
    if not info:
        return "none"
    return f"{info['platform']}:{info['kind']} x{info['count']}"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX reads it
    itself, so nothing is set in code.  Otherwise the cache lives at the
    fixed ``DEFAULT_CACHE_DIR`` inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
