"""Pipeline benchmark: capacity-sized stages vs uniform stages vs DP.

Exercises the ``HetConfig.pipeline_stages`` stack end to end on the
8-host-device mesh and the host-side timeline model
(core/pipeline.py), with three fail-loud acceptance invariants:

  exactness    fp32 / grad_clip=0 / allreduce / scan_layers=False:
               the stages=2 1F1B step (per-stage VJP segments, one
               deterministic microbatch program order) must be
               BIT-IDENTICAL — losses AND params — to the pure-DP
               (stages=1) step over the same global batch. Pipelining
               is a schedule, not a numeric.
  modeled      on a 2:1 pod-speed skew (speeds (2, 1), L=12 layers,
               S=2 stages, M=8 microbatches, DCN 12.5 GB/s, 0.5 GB of
               gradient per layer), the capacity-sized stage cut
               ([8, 4] layers — fast pod holds more depth) must give a
               strictly smaller modeled 1F1B makespan than BOTH the
               uniform cut ([6, 6], the bubble the skew inflates) and
               pure capacity-planned DP (which pays the full-gradient
               DCN sync pipelining avoids). 1F1B must also not lose to
               GPipe on the same cut.
  restore      a checkpoint saved under one stage plan (capacities
               (3, 1) -> layer cut [3, 1]) must restore into a
               DIFFERENT stage plan (uniform [2, 2]) and continue
               BIT-IDENTICALLY to an uninterrupted run — params are
               stored per-leaf, so the stage partition is placement
               metadata, not state (steps.checkpoint_format records it
               via core/pipeline.py stage_record for the restore-time
               log + validation only).

The CPU host mesh runs stages sequentially, so no wall-clock speedup
is claimed from the measured leg; the skew argument lives in the
modeled timeline, same convention as overlap_bench. Emits
``BENCH_pipeline.json`` (``--out`` to relocate).
"""
from __future__ import annotations

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import json
import tempfile
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import base
from repro.configs.base import (HetConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig)
from repro.core import capacity, dummy
from repro.core import pipeline as pipe
from repro.data import synthetic
from repro.launch import steps
from repro.launch.mesh import make_mesh
from repro.launch.sharding import named
from repro.models.model import build_model

# the modeled-skew scenario (ISSUE 8 acceptance constants)
MODEL_L = 12                 # layers in the modeled stack
MODEL_S = 2                  # pipeline stages
MODEL_M = 8                  # microbatches in flight
MODEL_SPEEDS = (2.0, 1.0)    # 2:1 pod skew
MODEL_MB_ROWS = 4
MODEL_ROW_LAYER_S = 2e-3     # per-row per-layer fwd compute at speed 1
MODEL_ACT_BYTES = 5e7        # stage-boundary activation per microbatch
MODEL_DCN_BPS = 12.5e9       # 100 Gb/s DCN
MODEL_PARAM_BYTES_LAYER = 0.5e9


def _measured_leg(num_steps: int) -> Dict[str, Any]:
    """stages=2 vs pure DP on the host mesh: bit-exactness + wall."""
    cfg = dataclasses.replace(base.smoke_config("olmo-1b"),
                              compute_dtype="float32",
                              scan_layers=False)
    model = build_model(cfg)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shape = ShapeConfig("t", 16, 8, "train")
    rec = synthetic.make_lm_records(16, 17, cfg.vocab_size, seed=5)
    plan = capacity.plan_capacities(16, [1, 1, 1, 1])
    packed = dummy.pack_global_batch(
        {"inputs": rec["inputs"][:, :16],
         "labels": rec["labels"][:, :16]}, plan)
    batch = {k: jnp.asarray(v) for k, v in packed.items()}

    def run(stages):
        tcfg = TrainConfig(
            model=cfg, shape=shape,
            het=HetConfig(grad_reduction="allreduce", accum_steps=4,
                          pipeline_stages=stages),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                      grad_clip=0.0))
        with jax.set_mesh(mesh):
            state = steps.init_train_state(model, tcfg, mesh,
                                           jax.random.PRNGKey(0))
            step = steps.build_train_step(model, tcfg, mesh)
            losses, t0 = [], None
            for i in range(num_steps):
                state, met = step(state, batch)
                losses.append(float(met["loss"]))
                if i == 0:            # first step pays compilation
                    t0 = time.time()
            wall = (time.time() - t0) / max(num_steps - 1, 1)
        return losses, jax.device_get(state), wall

    dp_losses, dp_state, dp_wall = run(1)
    pp_losses, pp_state, pp_wall = run(2)
    if dp_losses != pp_losses:
        raise SystemExit(
            f"pipeline_bench: stages=2 losses diverged from pure DP "
            f"(fp32/clip=0 must be bit-identical): {dp_losses} vs "
            f"{pp_losses}")
    for a, b in zip(jax.tree.leaves(dp_state.params),
                    jax.tree.leaves(pp_state.params)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise SystemExit(
                "pipeline_bench: stages=2 params diverged bitwise "
                "from pure DP after the bit-identical loss trajectory "
                "— the per-stage VJP/accumulation order regressed")
    return {
        "losses": dp_losses,
        "exact_match": True,
        "dp_avg_ms": dp_wall * 1e3,
        "pipeline_avg_ms": pp_wall * 1e3,
    }


def _modeled_leg() -> Dict[str, Any]:
    """The 2:1-skew stage-sizing argument, checked loudly."""
    cap_plan = pipe.plan_stages(MODEL_L, MODEL_SPEEDS)
    uni_plan = pipe.uniform_stages(MODEL_L, MODEL_S)
    kw = dict(num_microbatches=MODEL_M, mb_rows=MODEL_MB_ROWS,
              row_layer_time=MODEL_ROW_LAYER_S,
              act_bytes_per_mb=MODEL_ACT_BYTES,
              dcn_bytes_per_s=MODEL_DCN_BPS)
    t_cap = pipe.modeled_pipeline_step_time(cap_plan, MODEL_SPEEDS, **kw)
    t_uni = pipe.modeled_pipeline_step_time(uni_plan, MODEL_SPEEDS, **kw)
    t_gpipe = pipe.modeled_pipeline_step_time(cap_plan, MODEL_SPEEDS,
                                              schedule="gpipe", **kw)
    t_dp = pipe.modeled_dp_step_time(
        MODEL_L, MODEL_SPEEDS,
        global_rows=MODEL_M * MODEL_MB_ROWS,
        row_layer_time=MODEL_ROW_LAYER_S,
        param_bytes_per_layer=MODEL_PARAM_BYTES_LAYER,
        dcn_bytes_per_s=MODEL_DCN_BPS)
    if not (t_cap < t_uni):
        raise SystemExit(
            f"pipeline_bench: capacity-sized stages "
            f"({cap_plan.layers_per_stage.tolist()}) modeled at "
            f"{t_cap:.4f}s do not beat uniform stages "
            f"({uni_plan.layers_per_stage.tolist()}) at {t_uni:.4f}s "
            f"on the 2:1 skew — stage sizing regressed")
    if not (t_cap < t_dp):
        raise SystemExit(
            f"pipeline_bench: capacity-sized pipeline modeled at "
            f"{t_cap:.4f}s does not beat pure capacity-planned DP at "
            f"{t_dp:.4f}s — the full-gradient sync term vanished from "
            f"the DP model or boundary traffic exploded")
    if not (t_cap <= t_gpipe):
        raise SystemExit(
            f"pipeline_bench: 1F1B ({t_cap:.4f}s) modeled slower than "
            f"GPipe ({t_gpipe:.4f}s) on the same cut")
    return {
        "layers_capacity": cap_plan.layers_per_stage.tolist(),
        "layers_uniform": uni_plan.layers_per_stage.tolist(),
        "capacity_s": t_cap,
        "uniform_s": t_uni,
        "gpipe_s": t_gpipe,
        "dp_s": t_dp,
        "speedup_vs_uniform": t_uni / t_cap,
        "speedup_vs_dp": t_dp / t_cap,
    }


def _restore_leg() -> Dict[str, Any]:
    """Save under stage cut [3,1]; restore into [2,2]; bit-identical."""
    cfg = dataclasses.replace(base.smoke_config("olmo-1b"),
                              compute_dtype="float32",
                              scan_layers=False, num_layers=4)
    model = build_model(cfg)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shape = ShapeConfig("t", 16, 8, "train")
    rec = synthetic.make_lm_records(16, 17, cfg.vocab_size, seed=7)
    plan = capacity.plan_capacities(16, [1, 1, 1, 1])
    packed = dummy.pack_global_batch(
        {"inputs": rec["inputs"][:, :16],
         "labels": rec["labels"][:, :16]}, plan)
    batch = {k: jnp.asarray(v) for k, v in packed.items()}

    def tcfg_for(caps):
        return TrainConfig(
            model=cfg, shape=shape,
            het=HetConfig(grad_reduction="allreduce", accum_steps=4,
                          pipeline_stages=2, capacities=caps),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                      grad_clip=0.0))

    t_skew, t_uni = tcfg_for((3.0, 1.0)), tcfg_for(())
    cut_skew = steps.stage_plan_for(model, t_skew).layers_per_stage
    cut_uni = steps.stage_plan_for(model, t_uni).layers_per_stage
    assert cut_skew.tolist() != cut_uni.tolist(), (cut_skew, cut_uni)

    # uninterrupted reference: 2 steps under the uniform cut
    with jax.set_mesh(mesh):
        st = steps.init_train_state(model, t_uni, mesh,
                                    jax.random.PRNGKey(0))
        f_uni = steps.build_train_step(model, t_uni, mesh)
        st, m1 = f_uni(st, batch)
        st, m2 = f_uni(st, batch)
    ref = jax.device_get(st)
    ref_loss2 = float(m2["loss"])

    # interrupted: 1 step under the SKEWED cut, save, restore into the
    # uniform cut, continue
    with jax.set_mesh(mesh):
        st = steps.init_train_state(model, t_skew, mesh,
                                    jax.random.PRNGKey(0))
        f_skew = steps.build_train_step(model, t_skew, mesh)
        st, m1b = f_skew(st, batch)
    if float(m1b["loss"]) != float(m1["loss"]):
        raise SystemExit(
            "pipeline_bench: step-1 loss differs between stage cuts "
            "— the pipeline schedule changed the numerics")
    host1 = jax.device_get(st)
    ckdir = tempfile.mkdtemp(prefix="pipeline_bench_ck_")
    mgr = CheckpointManager(ckdir)
    fmt_skew = steps.checkpoint_format(model, t_skew, mesh)
    assert fmt_skew["pipeline"]["plan"]["rows_per_rank"] == \
        cut_skew.tolist()
    mgr.save(1, host1, meta={"plan": plan, "format": fmt_skew},
             block=True)

    host, meta = mgr.restore(steps.state_shapes(model, t_uni, mesh))
    saved_cut = meta["format"]["pipeline"]["plan"]["rows_per_rank"]
    with jax.set_mesh(mesh):
        sr = jax.device_put(
            host, named(mesh, steps.state_specs(model, t_uni, mesh)))
        sr, m2b = f_uni(sr, batch)
    got = jax.device_get(sr)
    if float(m2b["loss"]) != ref_loss2:
        raise SystemExit(
            f"pipeline_bench: post-restore loss {float(m2b['loss'])!r} "
            f"!= uninterrupted {ref_loss2!r} across the stage-plan "
            f"change {saved_cut} -> {cut_uni.tolist()}")
    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(got.params)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise SystemExit(
                "pipeline_bench: params diverged bitwise after the "
                f"cross-stage-plan restore {saved_cut} -> "
                f"{cut_uni.tolist()}")
    return {
        "saved_cut": saved_cut,
        "restored_cut": cut_uni.tolist(),
        "bit_identical": True,
    }


def main(quick: bool = False,
         out: str = "BENCH_pipeline.json") -> Dict[str, Any]:
    res: Dict[str, Any] = {
        "exactness": _measured_leg(num_steps=2 if quick else 4),
        "modeled": _modeled_leg(),
        "restore": _restore_leg(),
    }
    mo = res["modeled"]
    print(f"| cut | modeled step s |")
    print(f"| capacity {mo['layers_capacity']} | {mo['capacity_s']:.4f} |")
    print(f"| uniform {mo['layers_uniform']} | {mo['uniform_s']:.4f} |")
    print(f"| gpipe-on-capacity | {mo['gpipe_s']:.4f} |")
    print(f"| pure DP | {mo['dp_s']:.4f} |")
    with open(out, "w") as fh:
        json.dump(res, fh, indent=2)
    print(f"[pipeline_bench] wrote {out}; stages=2 bit-identical to "
          f"DP: {res['exactness']['exact_match']}; capacity cut "
          f"{mo['speedup_vs_uniform']:.2f}x vs uniform, "
          f"{mo['speedup_vs_dp']:.2f}x vs pure DP on 2:1 skew; "
          f"cross-stage-plan restore bit-identical: "
          f"{res['restore']['bit_identical']}")
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fewer measured steps, same invariants")
    ap.add_argument("--out", default="BENCH_pipeline.json")
    args = ap.parse_args()
    main(quick=args.quick, out=args.out)
