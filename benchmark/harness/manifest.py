"""Finds everything by name: the cell in ``BENCHMARK.json``, its workload
and configuration files, and the driver, metric reader, flops function and
reference that go with them. No registry: a later PR adds files and manifest
entries and edits nothing here."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, or None where there is none."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload_name):
    """-> (manifest, cell entry, workload file, config file) for one cell."""
    manifest = load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload_name not in cells:
        raise SystemExit(
            f"unknown workload {workload_name!r}; BENCHMARK.json has {sorted(cells)}"
        )
    cell = cells[workload_name]
    workload = load_json("workloads", f"{workload_name}.json")
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    for key in ("config", "traffic", "chips"):
        if workload[key] != cell[key]:
            raise SystemExit(
                f"{workload_name}: workload file says {key}={workload[key]!r}, "
                f"BENCHMARK.json says {cell[key]!r}"
            )
    return manifest, cell, workload, config


def metrics_for(manifest, group, workload_name):
    """The metrics of ``group`` ('end_to_end' | 'per_layer') due in this cell."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or workload_name in m["workloads"]
    ]
