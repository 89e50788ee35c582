import os
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=512")

# NOTE: the two lines above MUST precede every other import — jax locks
# the device count at first init. No `from __future__` here for the same
# reason (it would have to be line 1).

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**input_specs).compile()`` must succeed
on the single-pod (16,16) mesh AND the 2-pod (2,16,16) mesh for every
assigned architecture x input shape. Failures here (sharding mismatch,
OOM at compile, unsupported collective) are bugs in the system.

Artifacts per cell (written to --out):
  <cell>.json   memory_analysis + cost_analysis + collective stats
  <cell>.hlo    optimized HLO text (optional, --save-hlo)

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out artifacts/dryrun
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def _cell_id(arch: str, shape: str, mesh_kind: str) -> str:
    return f"{arch}__{shape}__{mesh_kind}"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               het_mode: str = "allreduce", compression: str = "none",
               accum: int = 1):
    """Build and lower one cell. Returns (lowered, meta)."""
    from repro.configs import base
    from repro.configs.base import HetConfig, OptimizerConfig, TrainConfig
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import build_model

    cfg = base.resolve(arch)
    shape = base.SHAPES[shape_name]
    ok, why = base.shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": True, "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    if shape.kind == "train":
        if accum == 1:
            accum = base.accum_for(cfg, multi_pod)
        elif accum <= 0:
            accum = 1
    meta: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": mesh.size, "kind": shape.kind,
        "params": model.cfg.param_count(),
        "params_active": model.cfg.active_param_count(),
        "het_mode": het_mode, "compression": compression,
        "accum": accum if shape.kind == "train" else 1,
    }
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tcfg = TrainConfig(
                model=cfg, shape=shape,
                het=HetConfig(grad_reduction=het_mode,
                              compression=compression, accum_steps=accum),
                optimizer=base.optimizer_for(cfg))
            step = steps.build_train_step(model, tcfg, mesh)
            state_sh = steps.state_shapes(model, tcfg, mesh)
            batch_sh = steps.input_specs(cfg, shape, model, "train")
            lowered = step.lower(state_sh, batch_sh)
        elif shape.kind == "prefill":
            step = steps.build_prefill_step(model, shape, mesh)
            params_sh = jax.eval_shape(model.init_params,
                                       jax.random.PRNGKey(0))
            ins = steps.input_specs(cfg, shape, model, "prefill")
            lowered = step.lower(params_sh, ins["inputs"])
        else:  # decode
            step = steps.build_decode_step(model, shape, mesh)
            params_sh = jax.eval_shape(model.init_params,
                                       jax.random.PRNGKey(0))
            ins = steps.input_specs(cfg, shape, model, "decode")
            lowered = step.lower(params_sh, ins["tokens"], ins["cache"],
                                 ins["pos"])
    return lowered, meta


def analyze(lowered, meta: Dict[str, Any], pod_size: int = 256
            ) -> Dict[str, Any]:
    from repro.roofline import hlo as hlo_mod
    from repro.roofline.report import model_flops_for

    t0 = time.time()
    compiled = lowered.compile()
    meta["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    meta["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_device_bytes": int(ma.argument_size_in_bytes
                                 + ma.temp_size_in_bytes
                                 + ma.output_size_in_bytes
                                 - ma.alias_size_in_bytes),
    }
    meta["fits_16gb_cpu_measured"] = \
        meta["memory"]["peak_device_bytes"] < 16e9
    # TPU-true estimate: exact state + temp/2 (undo the CPU backend's
    # bf16->f32 GEMM-operand legalization, documented in EXPERIMENTS.md)
    meta["memory"]["tpu_estimate_bytes"] = int(
        ma.argument_size_in_bytes + ma.temp_size_in_bytes / 2)
    meta["fits_16gb"] = meta["memory"]["tpu_estimate_bytes"] < 16e9

    ca = compiled.cost_analysis()
    chips = meta["chips"]

    hlo_text = compiled.as_text()
    # XLA's cost_analysis counts while bodies ONCE — the layer scan would
    # under-report by ~num_layers x. program_costs() rebuilds trip-count-
    # weighted FLOPs/bytes from the HLO call graph (roofline/hlo.py).
    pc = hlo_mod.program_costs(hlo_text)
    meta["cost"] = {
        "per_device_flops": pc.flops,
        "per_device_bytes": pc.hbm_bytes,
        "hlo_flops": pc.flops * chips,
        "hlo_bytes": pc.hbm_bytes * chips,
        "xla_unweighted_flops": float(ca.get("flops", 0.0)),
        "xla_unweighted_bytes": float(ca.get("bytes accessed", 0.0)),
        "dot_count": pc.dot_count,
    }
    stats = hlo_mod.collective_stats(hlo_text, pod_size=pod_size)
    meta["collectives"] = {
        "ici_bytes": stats.ici_bytes, "dcn_bytes": stats.dcn_bytes,
        "count": stats.count, "by_type": stats.bytes_by_type,
    }

    from repro.configs import base as cfgbase
    shape = cfgbase.SHAPES[meta["shape"]]
    tokens = (shape.tokens if meta["kind"] != "decode"
              else shape.global_batch)    # decode: 1 new token per seq
    meta["model_flops"] = model_flops_for(meta["params_active"], tokens,
                                          meta["kind"])
    return meta, hlo_text


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False, het_mode: str = "allreduce",
             compression: str = "none", accum: int = 1) -> Dict[str, Any]:
    mesh_kind = "multi" if multi_pod else "single"
    cell = _cell_id(arch, shape_name, mesh_kind)
    t0 = time.time()
    try:
        lowered, meta = lower_cell(arch, shape_name, multi_pod,
                                   het_mode=het_mode,
                                   compression=compression, accum=accum)
        if lowered is None:
            meta.update({"arch": arch, "shape": shape_name,
                         "mesh": mesh_kind, "status": "skipped"})
            print(f"[dryrun] {cell}: SKIP ({meta['reason']})")
        else:
            meta, hlo_text = analyze(lowered, meta)
            meta["status"] = "ok"
            mem_gb = meta["memory"]["peak_device_bytes"] / 1e9
            tpu_gb = meta["memory"]["tpu_estimate_bytes"] / 1e9
            print(f"[dryrun] {cell}: OK compile={meta['compile_s']}s "
                  f"mem/dev={mem_gb:.2f}GB (tpu~{tpu_gb:.2f}GB) "
                  f"fits={meta['fits_16gb']} "
                  f"flops/dev={meta['cost']['per_device_flops']:.3e}")
            if save_hlo:
                with open(os.path.join(out_dir, cell + ".hlo"), "w") as fh:
                    fh.write(hlo_text)
    except Exception as e:  # a failed cell is a bug — record it loudly
        meta = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "error", "error": repr(e),
                "traceback": traceback.format_exc()}
        print(f"[dryrun] {cell}: ERROR {e!r}")
    meta["wall_s"] = round(time.time() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell + ".json"), "w") as fh:
        json.dump(meta, fh, indent=1, default=str)
    return meta


def main() -> int:
    from repro.configs import base

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--het-mode", default="allreduce",
                    choices=["allreduce", "hierarchical"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--accum", type=int, default=1,
                    help="override gradient-accumulation (1 = per-arch policy)")
    args = ap.parse_args()

    archs = base.list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = (list(base.SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                results.append(run_cell(
                    arch, shape, mesh_kind == "multi", args.out,
                    save_hlo=args.save_hlo, het_mode=args.het_mode,
                    compression=args.compression, accum=args.accum))
    bad = [r for r in results if r.get("status") == "error"]
    ok = [r for r in results if r.get("status") == "ok"]
    skipped = [r for r in results if r.get("status") == "skipped"]
    print(f"\n[dryrun] {len(ok)} ok, {len(skipped)} skipped, "
          f"{len(bad)} failed")
    for r in bad:
        print(f"  FAILED: {r['arch']} x {r['shape']} x {r['mesh']}: "
              f"{r['error']}")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
