"""Find the benchmark's parts by name.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a cell's correctness limits ``limits/<cell>.json``,
a per-layer metric the module ``metrics/<name>.py`` and a work counter the
module ``work/<name>.py``, all under this directory. Adding any of them is
adding a file and, for cells and metrics, an entry in ``BENCHMARK.json``:
nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> dict:
    """The ``workloads`` entry called ``name``."""
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, root: str = ROOT) -> dict:
    for c in benchmark(root)["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def limits(cell_name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "limits", f"{cell_name}.json"))


def _module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str):
    """A per-layer metric's reader module (``UNIT``, ``LAYER``, ``MOVES``,
    ``read(record)``)."""
    return _module("metrics", name)


def work(name: str):
    """A work counter module (``work(geometry, hparams) -> (flops, bytes)``)."""
    return _module("work", name)


def end_to_end_for(cell_name: str, root: str = ROOT) -> list:
    """The end-to-end entries of BENCHMARK.json that this cell reports."""
    return [m for m in benchmark(root)["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer_for(cell_name: str, root: str = ROOT) -> list:
    """The per-layer entries of BENCHMARK.json that this cell reports."""
    e2e = {m["name"] for m in end_to_end_for(cell_name, root)}
    return [m for m in benchmark(root)["per_layer"]
            if cell_name in m.get("workloads", [cell_name]) and m["moves"] in e2e]
