"""End-to-end heterogeneous training: a ~100M-parameter decoder LM.

The full production path on host devices: sharded synthetic corpus ->
capacity plan (unequal "nodes", one degrading mid-run) -> prefetching
loader -> SPMD weighted train step -> straggler replanning ->
checkpointing. This is the paper's Figure-1 pipeline in one script.

Run (full, ~100M params, a few hundred steps — takes a while on CPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python examples/het_train.py --steps 300

Quick check:
  ... python examples/het_train.py --steps 20 --small
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import (HetConfig, ModelConfig, OptimizerConfig,
                                ShapeConfig, TrainConfig)
from repro.core import capacity
from repro.core.straggler import StragglerMonitor
from repro.data.dataset import ShardedDataset
from repro.data.loader import PrefetchLoader
from repro.data.sampler import HetSampler
from repro.data.synthetic import build_synthetic_corpus
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_specs, named
from repro.models.model import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--small", action="store_true",
                    help="~6M params instead of ~100M (quick check)")
    ap.add_argument("--ckpt-dir", default="/tmp/het_train_example")
    args = ap.parse_args()

    if args.small:
        cfg = ModelConfig(name="het-demo-6m", num_layers=4, d_model=256,
                          num_heads=8, num_kv_heads=4, d_ff=704,
                          vocab_size=2048, remat="none")
        seq, gbatch = 64, 8
    else:
        # ~100M params: 12L x 768 (GPT-2-small-like, SwiGLU)
        cfg = ModelConfig(name="het-demo-100m", num_layers=12,
                          d_model=768, num_heads=12, num_kv_heads=12,
                          d_ff=2048, vocab_size=32000, remat="none")
        seq, gbatch = 128, 8
    model = build_model(cfg)
    print(f"[example] {cfg.name}: {cfg.param_count():,} params")

    n_dev = len(jax.devices())
    dp = min(n_dev, 4)
    mesh = make_mesh((dp, 1), ("data", "model"))
    print(f"[example] mesh: data={dp} (heterogeneous 'nodes')")

    # unequal node capacities, paper-style (fast, fast, slow, slower)
    caps = [2.0, 1.5, 1.0, 0.5][:dp]
    plan = capacity.plan_capacities(gbatch, caps, headroom=1.5)
    print(f"[example] plan: rows/rank={plan.rows_per_rank.tolist()} "
          f"buffer={plan.buffer_rows} efficiency={plan.efficiency():.2f}")

    corpus = build_synthetic_corpus("/tmp/het_train_corpus",
                                    num_seqs=max(64, 2 * gbatch),
                                    seq_len=seq + 1,
                                    vocab=cfg.vocab_size,
                                    rows_per_shard=32)
    ds = ShardedDataset(corpus)
    sampler = HetSampler(ds, plan, seed=0)
    loader = PrefetchLoader(sampler, depth=2)

    tcfg = TrainConfig(model=cfg,
                       shape=ShapeConfig("ex", seq, gbatch, "train"),
                       het=HetConfig(), optimizer=OptimizerConfig(
                           lr=1e-3, warmup_steps=20,
                           total_steps=args.steps))
    with jax.set_mesh(mesh):
        state = steps_mod.init_train_state(model, tcfg, mesh,
                                           jax.random.PRNGKey(0))
        step_fn = steps_mod.build_train_step(model, tcfg, mesh)
        bspecs = named(mesh, batch_specs(cfg, mesh, plan.padded_rows))

        mgr = CheckpointManager(args.ckpt_dir, keep=2)
        monitor = StragglerMonitor(num_ranks=dp, replan_interval=50)
        step, epoch, losses = 0, 0, []
        t0 = time.time()
        while step < args.steps:
            for raw in loader.iter_epoch(epoch):
                if step >= args.steps:
                    break
                batch = jax.device_put(
                    {"inputs": jnp.asarray(raw["inputs"][:, :seq]),
                     "labels": jnp.asarray(raw["labels"][:, :seq]),
                     "weights": jnp.asarray(raw["weights"][:, :seq])},
                    bspecs)
                ts = time.time()
                state, met = step_fn(state, batch)
                dt = time.time() - ts
                losses.append(float(met["loss"]))
                step += 1
                # simulate rank 2 degrading after step 100 (thermal
                # throttling): its reported step time doubles
                times = [dt] * dp
                if step > 100 and dp > 2:
                    times[2] = dt * 2
                monitor.observe(times)
                if monitor.should_replan():
                    plan = monitor.replan(plan)
                    sampler.set_plan(plan)
                    print(f"[example] step {step}: replanned -> "
                          f"{plan.rows_per_rank.tolist()}")
                if step % 25 == 0:
                    print(f"[example] step {step:4d} "
                          f"loss {losses[-1]:.4f} ({dt * 1e3:.0f} ms)")
                if step % 100 == 0:
                    mgr.save(step, jax.device_get(state),
                             meta={"epoch": epoch})
            epoch += 1
        mgr.save(step, jax.device_get(state), meta={"epoch": epoch},
                 block=True)
    print(f"[example] {step} steps in {time.time() - t0:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0]
    print("[example] OK")


if __name__ == "__main__":
    main()
