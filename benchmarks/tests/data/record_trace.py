"""How benchmarks/tests/data/small_tpu.xplane.pb was recorded (on the chip):

    chiprun -- python benchmarks/tests/data/record_trace.py

A jitted ``while_loop`` of a few integer ops runs three times under
``jax.profiler``; the ``.xplane.pb`` is copied to ``chiprun_out/`` and the
plane / line structure is printed, which is what ``lib/trace.py`` was
written against.  Not part of any run of the benchmark.
"""

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[3]


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def prog(x):
        def body(c):
            i, v = c
            v = jnp.sort(v * 3 + i) % 1000
            return i + 1, v

        return jax.lax.while_loop(lambda c: c[0] < 20, body, (0, x))[1]

    x = jnp.arange(4096, dtype=jnp.int32)
    jax.block_until_ready(prog(x))
    d = tempfile.mkdtemp(prefix="rec_trace_")
    jax.profiler.start_trace(d)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(prog(x))
        time.sleep(0.01)
    span = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    shutil.copy(path, out / "small_tpu.xplane.pb")
    print("span_s", span)
    for pl in ProfileData.from_file(path).planes:
        lines = list(pl.lines)
        print("plane", repr(pl.name), len(lines))
        for ln in lines:
            evs = list(ln.events)
            print("   line", repr(ln.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
