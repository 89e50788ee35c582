"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is one ``workloads`` entry. Everything that belongs to one
configuration, traffic mix, per-layer metric or cell lives in a file of
its own, named after it:

- ``bench/configs/<config>.json``   sizes of the model as it is run
- ``bench/traffic/<traffic>.json``  the traffic mix's parameters
- ``bench/limits/<workload>.json``  the correctness limits of the cell
- ``bench/metrics/<metric>.py``     the reader of one per-layer metric

so a later cell, mix or metric is added by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return _load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _load_json(BENCH / "traffic" / f"{name}.json")


def limits(workload_name: str) -> Dict[str, float]:
    return _load_json(BENCH / "limits" / f"{workload_name}.json")["limits"]


def metrics_for(workload_name: str, trace: bool) -> List[Dict[str, Any]]:
    """The metric entries this cell reports in a run of this kind: its
    end-to-end metrics with ``--trace 0``, its per-layer ones with 1."""
    spec = benchmark()
    out = []
    for m in spec["per_layer" if trace else "end_to_end"]:
        if workload_name in m.get("workloads", [workload_name]):
            out.append(m)
    return out


def metric_reader(name: str) -> ModuleType:
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
