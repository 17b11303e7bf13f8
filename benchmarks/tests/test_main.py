"""The command itself: no chip, no result."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH, REPO

ARGS = ["--workload", "mesh10k_udp", "--seed", "3000000000", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(REPO, BENCH / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_alone_with_the_manifest_it_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "benchmarks" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
