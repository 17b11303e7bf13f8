"""The per-test wall limit of tests/conftest.py, under test itself.

A pytest session runs in a subprocess on a three-test file whose middle
test blocks for good inside one C call: a second ``pthread_mutex_lock`` on
a mutex its own thread holds.  A ``time.sleep`` would prove nothing — a
signal handler ends it.  This call is the spinning XLA execution's stand-in
(that one costs 20 s of compile): ``pthread_mutex_lock`` does not return on
a signal, so the C-level handler only sets a flag and the Python-level one,
which runs between bytecodes of the main thread, never gets its turn.  The
blocked test arms exactly such a ``SIGALRM`` handler first, so the session's
output shows that it did not fire and the watchdog did.
"""

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

BLOCKED = '''
import ctypes
import signal

import pytest


def test_before():
    pass


@pytest.mark.wall_limit(3)
def test_blocked():
    def handler(signum, frame):
        raise RuntimeError("the SIGALRM handler ran")

    signal.signal(signal.SIGALRM, handler)
    signal.alarm(1)
    libc = ctypes.CDLL(None)
    mutex = ctypes.create_string_buffer(256)  # a pthread_mutex_t and room
    assert libc.pthread_mutex_init(mutex, None) == 0
    assert libc.pthread_mutex_lock(mutex) == 0
    libc.pthread_mutex_lock(mutex)  # never returns


def test_after():
    pass
'''


@pytest.mark.parametrize(
    "xdist", [(), ("-p", "xdist", "-n", "2", "--dist", "loadfile")],
    ids=["one_process", "xdist"],
)
def test_blocked_c_call_ends_as_one_named_failure(tmp_path, xdist):
    (tmp_path / "test_blocked.py").write_text(BLOCKED)
    # the file lives outside tests/, so conftest.py is handed over as a
    # plugin; cwd makes `tests` importable
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest",
         "-p", "no:cacheprovider", "-q", *xdist, str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode not in (0, 124), out
    assert "the SIGALRM handler ran" not in out, out
    named = [ln for ln in out.splitlines() if ln.startswith("WALL LIMIT: ")]
    assert len(named) == 1 and "test_blocked.py::test_blocked" in named[0], out
    assert "in test_blocked" in out, out  # the stack dump reached the log
    if xdist:
        # the worker is replaced and the rest of the file still runs
        assert "crashed while running 'test_blocked.py::test_blocked'" in out
        assert "1 failed, 2 passed" in out, out
    else:
        # one process cannot outlive its own main thread: test_before is
        # reported, the limit names what it stops, nothing runs after
        assert proc.stdout.strip() == ".", out
