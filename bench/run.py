#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process holds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and correctness limits are found
by the names in ``BENCHMARK.json`` (see ``benchlib/spec.py``). The run
sets up (weights from the seed on the device, every program shape the
window uses compiled or loaded from the compile cache in the checkout's
``.jax_cache/``), measures for ``--seconds``, then checks what the timed
path produced against the plain float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each compared number beside its limit, also printed as the
last lines of standard error. With ``--trace 1`` the line also carries
``breakdown`` (the device ops that took most time, and idle device time
by what the host was doing). Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import device, spec  # noqa: E402
from benchlib import trace as trace_mod  # noqa: E402
from benchlib.window import Window  # noqa: E402

WORK = ROOT / ".bench_work"


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    import jax

    from repro.launch.cache import use_compile_cache as program_cache

    program_cache()
    # cache every program, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(entry: Dict[str, Any], cfg: Dict, mix: Dict,
             limits: Dict[str, float], metrics: List[Dict[str, Any]],
             seed: int, seconds: float, trace: bool, devs,
             t_process: float, **cell_kwargs) -> Dict[str, Any]:
    """Run one cell on ``devs`` and return its result line."""
    WORK.mkdir(exist_ok=True)
    # one directory of its own per run, so that runs side by side (the
    # tests' workers) never share their data or checkpoints
    work = Path(tempfile.mkdtemp(prefix=f"{entry['name']}-{seed}-",
                                 dir=WORK))
    try:
        window = Window(seconds, str(work / "trace") if trace else None,
                        t_process)
        if mix["kind"] == "train":
            from benchlib import train_cell
            res = train_cell.run(cfg, mix, seed, devs, work / "train",
                                 window, limits, **cell_kwargs)
            res["train_tokens_per_s"] = res["tokens"] / res["window_s"]
        elif mix["kind"] == "serve":
            from benchlib import serve_cell
            res = serve_cell.run(cfg, mix, seed, devs, window, limits,
                                 **cell_kwargs)
        else:
            raise SystemExit(f"bench: no cell runner for traffic kind "
                             f"{mix['kind']!r}")
        dev = dict(device.describe(devs),
                   memory_peak_bytes=res["memory_peak_bytes"])
        line: Dict[str, Any] = {"correct": res["correct"],
                                "attempted": res["attempted"],
                                "failed": res["failed"]}
        if trace:
            tr = trace_mod.load(str(work / "trace"))
            busy = trace_mod.busy_s(tr)
            dev.update(busy_s=busy, window_s=tr.window_s)
            rec = dict(res, kind=mix["kind"], cfg=cfg, mix=mix,
                       chips=len(devs), trace=tr, busy_s=busy,
                       window_s=tr.window_s,
                       peaks=device.peaks(devs[0].device_kind))
            values = {m["name"]: spec.metric_reader(m["name"]).read(rec)
                      for m in metrics}
            line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in metrics
                               if values[m["name"]] is not None}
            line["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                                 "idle_gaps": trace_mod.idle_gaps(tr)}
        else:
            line["metrics"] = {m["name"]: {"value": res[m["name"]],
                                           "unit": m["unit"]}
                               for m in metrics}
        line["device"] = dev
        line["checks"] = res["checks"]
        line["readings"] = res["readings"]
        line["stand_ins"] = res["stand_ins"]
        return line
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> None:
    args = parse_args(argv)
    entry = spec.workload(args.workload)
    devs = device.require_chips(entry["chips"])
    use_compile_cache()
    line = run_cell(entry, spec.config(entry["config"]),
                    spec.traffic(entry["traffic"]),
                    spec.limits(entry["name"]),
                    spec.metrics_for(entry["name"], bool(args.trace)),
                    args.seed, args.seconds, bool(args.trace), devs,
                    T_PROCESS)
    readings = line.pop("readings")
    line.pop("stand_ins")                # none in the benchmark's own runs
    checks = line.pop("checks")
    line["checks"] = checks          # the compared numbers come last
    print(f"bench: readings {json.dumps(readings)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
