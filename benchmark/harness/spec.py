"""Loading a cell from data: BENCHMARK.json, the configuration's file, the
traffic mix's file, and the family / reference / metric modules by name.

The benchmark directory is the one this package sits in and the repository
root is its parent, so a copy of the tree elsewhere finds its own files.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError("missing file {}".format(path))
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, loaded once per process (a
    family's loss function must keep one identity, because the Trainer's warm
    slot keys on it)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    key = "_benchmark_{}_{}".format(
        kind, name.replace("-", "_").replace(".", "_"))
    if key in sys.modules:
        return sys.modules[key]
    if not os.path.isfile(path):
        raise SpecError("no {} named {!r}: {} does not exist".format(
            kind, name, path))
    spec = importlib.util.spec_from_file_location(key, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[key] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[key]
        raise
    return module


def load_cell(name: str) -> dict:
    """Everything one run needs, as plain data (it is pickled to spawned
    runner processes): the workload entry, the configuration, the mix, and
    the metric entries this cell reports."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError("no workload {!r} in BENCHMARK.json (have: {})".format(
            name, ", ".join(sorted(cells))))
    workload = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if workload["config"] not in configs:
        raise SpecError("workload {!r} names the unknown config {!r}".format(
            name, workload["config"]))
    entry = configs[workload["config"]]
    config = _read_json(os.path.join(ROOT, entry["file"]))
    mix = _read_json(os.path.join(BENCH_DIR, "traffic",
                                  workload["traffic"] + ".json"))

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name,
        "workload": workload,
        "config": config,
        "mix": mix,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "run_seconds": bench["run_seconds"],
    }
