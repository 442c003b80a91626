"""Everything of one cell is found by name: its entry in BENCHMARK.json,
its configuration file, its traffic file, the system that serves its
configuration, the reference of its configuration and one reader per
metric."""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> tuple:
    """(cell entry, configuration entry) of the cell called ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    return w, cfgs[w["config"]]


def config(entry: dict, root: str = ROOT) -> dict:
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    if cfg.get("name") != entry["name"]:
        raise ValueError(f"{entry['file']} is not configuration "
                         f"{entry['name']!r}")
    return cfg


def traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.`` and
    ``-``, so they are loaded by path)."""
    path = os.path.join(BENCH, kind, name + ".py")
    mod_name = "bench_" + kind + "_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, cell_name: str, layer: bool) -> list:
    """The metric entries a cell reports: its end-to-end metrics, or with
    ``layer`` its per-layer ones."""
    key = "per_layer" if layer else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell_name in m["workloads"]]
