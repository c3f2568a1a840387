"""Find a cell's files by the names `BENCHMARK.json` gives.

Nothing here knows a cell, a configuration, a traffic mix, a driver or a
per-layer metric by name: a later PR adds one as files plus one entry.
"""

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """A run that cannot be a result: wrong machine, wrong files, a broken rule."""


def _load_json(path: str, what: str, name: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise BenchError(f"unknown {what} {name!r}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _load_module(directory: str, what: str, name: str):
    path = os.path.join(HERE, directory, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"unknown {what} {name!r}: no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"), "benchmark file", "BENCHMARK.json")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has: {known}")


def config(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return _load_json(os.path.join(ROOT, entry["file"]), "config", name)
    raise BenchError(f"unknown config {name!r}: not under configs in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return _load_json(os.path.join(HERE, "traffic", f"{name}.json"), "traffic", name)


def driver(name: str):
    return _load_module("drivers", "driver", name)


def layer_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    return _load_module("layer_metrics", "per-layer metric", name).read


def metrics_of(bench: Dict[str, Any], group: str, cell_name: str) -> List[Dict[str, Any]]:
    """The metrics of `end_to_end` or `per_layer` that this cell reports. A
    metric without a `workloads` key is reported by every cell or, per layer,
    by every cell that reports the end-to-end metric it moves."""
    reported = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cell_name in m["workloads"]}
    return [m for m in bench[group]
            if (cell_name in m["workloads"] if "workloads" in m
                else m.get("moves", m["name"]) in reported)]


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _load_json(os.path.join(HERE, "peaks.json"), "table", "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"device_kind {device_kind!r} is not in chipbench/peaks.json")
    return table["devices"][device_kind]
