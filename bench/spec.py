"""Find a cell's files by name: BENCHMARK.json, configuration, cell
settings, traffic mix, generator and metric readers."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "configs", name + ".json"))


def cell_settings(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "cells", name + ".json"))


def traffic(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "traffic", name + ".json"))


def workload(name: str, bench: Dict[str, Any] = None) -> Dict[str, Any]:
    """Everything one cell runs with, resolved from its name."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return {"entry": w, "config": config(w["config"]),
            "cell": cell_settings(name), "traffic": traffic(w["traffic"])}


def metrics_for(name: str, section: str,
                bench: Dict[str, Any] = None) -> list:
    """The `section` ("end_to_end" or "per_layer") metrics cell `name`
    reports: those without a workloads list, and those that list it."""
    bench = bench if bench is not None else benchmark()
    return [m for m in bench[section]
            if "workloads" not in m or name in m["workloads"]]
