"""Test harness configuration.

Tests run on XLA:CPU with 8 virtual devices (multi-chip meshes are exercised
on them; the chip itself is reached only through ``chip_smoke.py``).  The
driver and ``make`` targets put ``JAX_PLATFORMS=cpu`` in the environment and
JAX honours it; the ``jax.config`` pin below makes a bare ``pytest`` do the
same.  The virtual device count is an ``XLA_FLAGS`` entry and must be in the
environment before JAX first initialises its backend, hence here.

The persistent compile cache stays off under test: the in-process entry
points never enable it (shadow_tpu/device.py), and child ``python -m
shadow_tpu`` processes inherit the switch below.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import shadow_tpu  # noqa: E402,F401  (enables jax x64 mode)
