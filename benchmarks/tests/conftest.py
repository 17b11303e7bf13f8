"""CPU rehearsals of the benchmark: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q`` from the root of the repo.  They prove paths, control
flow and the arithmetic; a time or a rate never comes from here."""

import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# four virtual devices for the rehearsal of a `chips: 4` cell; an XLA_FLAGS
# entry has to be in the environment before JAX first initialises
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(REPO), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import shadow_tpu  # noqa: E402,F401  (enables x64)

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())


def _tiny_root(tmp: Path) -> Path:
    """A root of new files alone: a manifest, two configurations and three
    traffic mixes cut to sizes XLA:CPU runs in seconds.  The runners and
    the per-layer readers are the benchmark's own, unchanged."""
    cfgs = {c["name"]: json.loads((REPO / c["file"]).read_text())
            for c in MANIFEST["configs"]}
    mesh = copy.deepcopy(cfgs["tgen_mesh_10k"])
    mesh["name"] = "tgen_mesh_tiny"
    mesh["parameters"]["hosts"] = 200
    chains = copy.deepcopy(cfgs["relay_chains_151"])
    chains["name"] = "relay_chains_tiny"
    chains["parameters"].update(chains=2, clients_per_chain=2, peers=20)
    chains["program_options"]["hybrid_workers"] = 2
    traffic = {p.stem: json.loads(p.read_text())
               for p in (BENCH / "traffic").glob("*.json")}
    traffic["udp"].update(horizon_sim_s=0.3, check_ms=150)
    traffic["udp"]["parameters"]["deliveries_per_host"] = 28  # 30 windows - 2
    traffic["mixed_tcp"].update(horizon_sim_s=2, check_ms=120)
    traffic["chains_bg"].update(horizon_sim_s=4, trace_from_sim_s=2.0,
                                trace_wall_s=0.2)
    (tmp / "b" / "configs").mkdir(parents=True)
    (tmp / "b" / "traffic").mkdir()
    for c in (mesh, chains):
        (tmp / "b" / "configs" / f"{c['name']}.json").write_text(json.dumps(c))
    for name, t in traffic.items():
        (tmp / "b" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    rename = {"mesh10k_udp": "tiny_udp", "mesh10k_mixed": "tiny_mixed",
              "hybrid151_chains": "tiny_chains"}
    man = copy.deepcopy(MANIFEST)
    man["paths"] = ["b"]
    man["configs"] = [
        {**c, "name": n, "file": f"b/configs/{n}.json"}
        for c, n in zip(man["configs"], ("tgen_mesh_tiny", "relay_chains_tiny"))
    ]
    cfg_of = {"tgen_mesh_10k": "tgen_mesh_tiny",
              "relay_chains_151": "relay_chains_tiny"}
    for w in man["workloads"]:
        w["name"] = rename[w["name"]]
        w["config"] = cfg_of[w["config"]]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[x] for x in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("cells"))


@pytest.fixture(scope="session")
def cpu_devices():
    return jax.devices()[:1]
