"""What the TPU compiler makes of a benchmark cell's timed program, without
a chip: an AOT compile for a DESCRIBED ``v5e:2x2`` (the installed TPU
compiler; nothing runs), then the program's sizes and its operations in
order of the compiler's own ``estimated_cycles``.

Usage: python scripts/hlo_stats.py <workload> [--top N] [--scope NAME]
                                   [--text out.txt]

``<workload>`` is a cell of BENCHMARK.json on the ``fused_mesh`` runner
(one chip, or four: the sharded program on a mesh of the described
devices).  ``--scope`` keeps the operations whose ``op_name`` carries that
``jax.named_scope`` (``window_gather``, ``exchange_bounds``, ...).

A compile is not a chip run: the cycles are the compiler's cost model, not
times (PR 33: it put two copies at 137 + 607 us where the chip took 78 +
70).  What it does tell: every buffer's shape, layout and tile — a result
``s32[20000,5,8]{2,1,0:T(8,128)}`` is stored padded to ``[20000,8,128]`` —
which operations exist (``copy``, ``gather``, collectives), the temporaries
(``temp``) and the generated code that ``peak_hbm_mb`` counts.
"""

import argparse
import os
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "benchmarks")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from shadow_tpu import parallel  # noqa: E402
from shadow_tpu.backend import lanes  # noqa: E402
from shadow_tpu.backend.tpu_engine import TpuEngine  # noqa: E402

_OP = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ([a-z\-]+)\(")


def compile_cell(workload: str):
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding
    from lib import cells

    # the program's accelerator branches (as tests/test_chip_compile.py)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    cell = cells.load_cell(workload, REPO)
    cfg = cells.build_config(
        cell, seed=1, backend="tpu", data_dir="/tmp/hlo_stats",
        stop_ns=int(cell.traffic["horizon_sim_s"] * 1e9),
    )
    eng = TpuEngine(cfg, log_capacity=0)
    init = eng.initial_state()

    def shapes(tree, sharding):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    if cell.chips > 1:
        mesh = Mesh(np.array(topo.devices[:cell.chips]),
                    (parallel.HOST_AXIS,))
        sh = parallel.state_shardings(mesh)
        state = lanes.LaneState(**{
            f: shapes(getattr(init, f), getattr(sh, f))
            for f in lanes.LaneState._fields})
        run_fn = parallel.make_sharded_run_fn(eng.params, eng.tables, mesh)
        return run_fn.lower(state).compile()
    dev = SingleDeviceSharding(topo.devices[0])
    args = (shapes(init, dev),)
    if eng._fault_overlay is not None:
        # a faulted cell: ONE program for every segment, whose path
        # leaves, stop pair and seed words are arguments
        paths = {f: getattr(eng.tables, f) for f in eng._path_fields}
        i32, u32 = (jax.ShapeDtypeStruct((), t, sharding=dev)
                    for t in (np.int32, np.uint32))
        return lanes.make_run_fn(eng.params, eng.tables, epochs=True).lower(
            *args, shapes(paths, dev), i32, i32, u32, u32).compile()
    if eng.params.has_loss:  # the seed's two words are arguments
        args += (jax.ShapeDtypeStruct((), np.uint32, sharding=dev),) * 2
    return lanes.make_run_fn(eng.params, eng.tables).lower(*args).compile()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--scope", default="")
    ap.add_argument("--text")
    opts = ap.parse_args()
    compiled = compile_cell(opts.workload)
    text = compiled.as_text()
    if opts.text:
        Path(opts.text).write_text(text)
    mem = compiled.memory_analysis()
    print(f"{opts.workload}: code {mem.generated_code_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} arguments "
          f"{mem.argument_size_in_bytes} bytes (a compile, not a chip run)")
    rows = []
    for line in text.splitlines():
        m, cyc = _OP.match(line), re.search(r'"estimated_cycles":"(\d+)"',
                                            line)
        name = re.search(r'op_name="([^"]*)"', line)
        name = name.group(1) if name else ""
        if m and cyc and opts.scope in name:
            rows.append((int(cyc.group(1)), m.group(1), m.group(3),
                         m.group(2), name[-48:]))
    rows.sort(reverse=True)
    print(f"{len(rows)} operations, {sum(r[0] for r in rows)} estimated "
          "cycles (the compiler's, per execution of each)")
    for cyc, name, op, shape, where in rows[:opts.top]:
        print(f"{cyc:>10} {name:<34} {op:<8} {shape[:56]:<56} {where}")


if __name__ == "__main__":
    main()
