"""Keeps ``tests/conftest.py`` working as the manifest grows, without an
edit to it (a PR that adds cells may add files here and edit none).

Its ``tiny_root`` fixture cuts the manifest's FIRST TWO configurations to
rehearsal sizes and renames the cells on them by a fixed table, so a
manifest entry that came later makes it raise ``KeyError`` before any test
of the old cells runs.  Once collection is over, the hook below wraps the
function that fixture calls (``_tiny_root``) so that, while it runs, the
module's ``MANIFEST`` is the manifest it was written for — the first two
configurations, the cells on them, and each metric's ``workloads`` list cut
to those cells; the whole manifest is back when it returns, and the tests
that read ``MANIFEST`` see all of it.  Later configurations rehearse from
their own test files (a root of their own under ``tmp_path``).

A ``benchmark`` PR can delete this file by giving that fixture a filter of
its own.
"""

import copy
import functools


def manifest_of_first_two_configs(manifest: dict) -> dict:
    man = copy.deepcopy(manifest)
    man["configs"] = man["configs"][:2]
    kept = {c["name"] for c in man["configs"]}
    man["workloads"] = [w for w in man["workloads"] if w["config"] in kept]
    cells = {w["name"] for w in man["workloads"]}
    for key in ("end_to_end", "per_layer"):
        out = []
        for m in man[key]:
            if "workloads" in m:
                m["workloads"] = [c for c in m["workloads"] if c in cells]
                if not m["workloads"]:
                    continue  # a metric of later cells only
            out.append(m)
        man[key] = out
    return man


def pytest_collection_modifyitems(config):
    for mod in config.pluginmanager.get_plugins():
        build = getattr(mod, "_tiny_root", None)
        if build is None or not hasattr(mod, "MANIFEST") or hasattr(
                build, "__wrapped__"):
            continue

        @functools.wraps(build)
        def first_two(tmp, build=build, mod=mod):
            whole = mod.MANIFEST
            mod.MANIFEST = manifest_of_first_two_configs(whole)
            try:
                return build(tmp)
            finally:
                mod.MANIFEST = whole

        mod._tiny_root = first_two
