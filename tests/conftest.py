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

Every test runs under a wall limit (``WALL_LIMIT_S``, or its own
``@pytest.mark.wall_limit(seconds)``).  A test that outruns it is stopped
from OUTSIDE the stuck call: a watchdog thread names the test's node id on
the real stderr, dumps every thread's stack and ends the process.  Under
xdist that is one worker: the session reports ``worker 'gwN' crashed while
running '<node id>'`` as that test's failure, replaces the worker and goes
on.  A signal handler could not do this — Python runs handlers between
bytecodes of the main thread, and the tests that spin here sit inside one
C++ call (an XLA execution) that never comes back to bytecode.  Without
the limit the only thing that ever stopped such a test was the ``timeout``
around the whole suite, which took every test behind it along and named
nothing.  (A C call that HOLDS the interpreter lock while it spins would
starve the watchdog too; XLA and ctypes release it.)
"""

import faulthandler
import os
import sys
import threading

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import shadow_tpu  # noqa: E402,F401  (enables jax x64 mode)

# the slowest honest tier-1 test takes 130-190 s under the driver's six
# workers (tests/test_native_rawclone.py::test_raw_clone_churn_reclaims:
# 129 s in PR 44's run, 192 s in its issue's)
WALL_LIMIT_S = 300


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "wall_limit(seconds): this test's wall limit, in place of "
        f"conftest.WALL_LIMIT_S = {WALL_LIMIT_S}",
    )


def _stop_process(item, limit):
    try:
        # capture has fd 2 pointed at a temp file that dies with the process
        capman = item.config.pluginmanager.getplugin("capturemanager")
        if capman is not None:
            capman.suspend(in_=True)
        sys.stderr.write(
            f"\nWALL LIMIT: {item.nodeid} ran past its {limit} s; "
            "ending this process. Stacks of all its threads:\n"
        )
        sys.stderr.flush()
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    finally:
        os._exit(1)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    marker = item.get_closest_marker("wall_limit")
    limit = marker.args[0] if marker else WALL_LIMIT_S
    watchdog = threading.Timer(limit, _stop_process, (item, limit))
    watchdog.daemon = True
    watchdog.start()
    try:
        return (yield)
    finally:
        watchdog.cancel()


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    """xdist, controller side: a worker died in ``crashitem`` and the
    failure is reported.  The loadfile scheduler has put the REST of that
    file back in its queue with the crashed test still pending; a test
    that outran its limit would outrun it again on every replacement
    worker, so strike it from whichever queue now holds it.  (``--dist
    load`` has no such queue and drops the crashed test by itself.)"""
    if not hasattr(sched, "workqueue"):
        return
    for units in (sched.workqueue, *sched.assigned_work.values()):
        for unit in units.values():
            if crashitem in unit:
                unit[crashitem] = True
