#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

  python3 bench/controls/calibrate.py --workload <name> --seeds 11,12,... \
      [--control-seeds 3] [--seconds S] [--out FILE]

Runs the cell once per seed in one process (the set-up compiles once)
and prints, per seed, one JSON line of readings: ``program`` (the timed
path against the float32 reference) and, for the first
``--control-seeds`` seeds, the fp8 control (the reference module that
the configuration names, computed with fp8 matmuls, in the program's
place) and, for training cells, each
planted fault (half of each batch left out; on several chips the
exchange left out), each also judged against the cell's committed
limits as the program is (``stand_ins``: each must come out
``correct: false``). The lower reading of a number is the largest the
program gives over the seeds, the upper the smallest the control or a
fault gives; ``bench/limits/<workload>.json`` records both and the
limit set between them. The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import device, spec  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    mod_spec = importlib.util.spec_from_file_location("bench_run",
                                                      BENCH / "run.py")
    harness = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(harness)

    entry = spec.workload(args.workload)
    devs = device.require_chips(entry["chips"])
    harness.use_compile_cache()
    mix = spec.traffic(entry["traffic"])
    seconds = (args.seconds if args.seconds is not None
               else spec.benchmark()["run_seconds"])
    faults = ()
    if mix["kind"] == "train":
        faults = ("half_batch",) + (("no_exchange",) if len(devs) > 1
                                    else ())
    out = open(args.out, "a") if args.out else None
    t0 = T_PROCESS
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        kw = {}
        if i < args.control_seeds:
            kw["controls"] = ("fp8",)
            if faults:
                kw["faults"] = faults
        line = harness.run_cell(
            entry, spec.config(entry["config"]), mix,
            spec.limits(entry["name"]),
            spec.metrics_for(entry["name"], False), seed, seconds, False,
            devs, t0, **kw)
        rec = json.dumps({"seed": seed, "readings": line["readings"],
                          "correct": line["correct"],
                          "checks": line["checks"],
                          "stand_ins": line["stand_ins"],
                          "metrics": line["metrics"],
                          "memory_peak_bytes":
                              line["device"]["memory_peak_bytes"]})
        print(rec, flush=True)
        if out:
            out.write(rec + "\n")
            out.flush()
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
