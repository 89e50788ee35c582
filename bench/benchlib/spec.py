"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is one ``workloads`` entry. Everything that belongs to one
configuration, traffic mix, per-layer metric or cell lives in a file of
its own, named after it:

- ``bench/configs/<config>.json``   sizes of the model as it is run
- ``bench/traffic/<traffic>.json``  the traffic mix's parameters
- ``bench/limits/<workload>.json``  the correctness limits of the cell
- ``bench/metrics/<metric>.py``     the reader of one per-layer metric

and each configuration names its architecture's plain reference, a
module at ``cfg["reference"]`` (a path from the repository's root), so
a later cell, mix, metric or architecture is added by adding files and
entries.

A reference module provides:

- ``served_logit_gaps(cfg, seed, sequences, precisions)``, for serving:
  for each sequence (``prompt`` and ``served`` token lists) and each
  served position, the gap between the float32 reference's best logit
  and its logit for the served token (key ``served``), and for each
  other precision the gap of the token that precision puts first (key:
  the precision);
- ``train_reference(cfg, opt, seed, batches, precision=, devices=,
  rows_of=)``, for training: the first ``len(batches)`` optimizer steps
  from the seed's weights, as ``losses`` per step, ``first_grad`` and
  ``delta`` (leaf name -> norm, named as the program's leaves are);
- ``"fp8"`` among the precisions of both: the control, the same
  computation one step below the bfloat16 the configurations compute in;
- ``unmodelled(mc)``: the names of what the program's ``ModelConfig``
  runs that the reference does not model (empty where it models all);
- ``train_flops_per_token(cfg, seq_len)``, ``decode_flops(cfg,
  contexts)`` and ``prefill_flops(cfg, prompt_lens)``: the model FLOPs
  of the architecture, read by the ``mfu.*`` metrics.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
_REFERENCES: Dict[Path, ModuleType] = {}


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


def _load_module(name: str, path: Path) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod     # as an import would (dataclasses need it)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    return _load_module("bench_metric_" + name.replace(".", "_"),
                        BENCH / "metrics" / f"{name}.py")


def reference(cfg: Dict[str, Any]) -> ModuleType:
    """The plain reference module that configuration ``cfg`` names,
    loaded once per process (its jitted functions keep their caches)."""
    if "reference" not in cfg:
        raise SystemExit(f"bench: configuration {cfg['name']!r} names no "
                         f"reference module")
    path = (ROOT / cfg["reference"]).resolve()
    if path not in _REFERENCES:
        _REFERENCES[path] = _load_module(
            f"bench_reference_{len(_REFERENCES)}", path)
    return _REFERENCES[path]
