"""Emit the §Roofline table from dry-run artifacts (artifacts/dryrun).

Reads every <cell>.json the dry-run produced, computes the three-term
roofline (TPU v5e constants) and prints it as a markdown table.
"""
from __future__ import annotations

import glob
import json
import os
from typing import List

from repro.roofline.report import RooflineRow, format_table

_ART = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts")
# prefer the final consistent grid when present
DEFAULT_DIR = (os.path.join(_ART, "dryrun_final")
               if os.path.isdir(os.path.join(_ART, "dryrun_final"))
               else os.path.join(_ART, "dryrun"))


def load_rows(art_dir: str = DEFAULT_DIR) -> List[RooflineRow]:
    rows = []
    for f in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        m = json.load(open(f))
        if m.get("status") != "ok":
            continue
        rows.append(RooflineRow(
            arch=m["arch"], shape=m["shape"], mesh=m["mesh"],
            chips=m["chips"], kind=m["kind"],
            hlo_flops=m["cost"]["hlo_flops"],
            hlo_bytes=m["cost"]["hlo_bytes"],
            ici_bytes=m["collectives"]["ici_bytes"],
            dcn_bytes=m["collectives"]["dcn_bytes"],
            model_flops=m["model_flops"]))
    return rows


def main(art_dir: str = DEFAULT_DIR, quiet: bool = False):
    rows = load_rows(art_dir)
    if not rows:
        print(f"[roofline] no artifacts in {art_dir}; run "
              f"`python -m repro.launch.dryrun` first")
        return []
    if not quiet:
        print("\n== Roofline (TPU v5e: 197 TF/s bf16, 819 GB/s HBM, "
              "~200 GB/s ICI, 25 GB/s DCN per chip) ==")
        print(format_table(sorted(
            rows, key=lambda r: (r.arch, r.shape, r.mesh))))
        doms = {}
        for r in rows:
            doms[r.dominant] = doms.get(r.dominant, 0) + 1
        print(f"   dominant terms: {doms}")
    return rows


if __name__ == "__main__":
    main()
