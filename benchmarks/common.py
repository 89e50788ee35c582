"""Shared benchmark harness: timed heterogeneous training runs.

Mirrors the paper's experimental setup on host devices: each "node" is a
DP rank; heterogeneous configs assign unequal capacities (the paper's
GPU mixes); homogeneous configs assign equal ones. We measure avg step
time, total training time, expansion (efficiency) and speedup — the
columns of paper Table 3.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs import base as cfgbase
from repro.configs.base import (HetConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig)
from repro.core import capacity as cap
from repro.core.dummy import pack_global_batch
from repro.data.synthetic import make_lm_records
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_specs, named
from repro.models.model import build_model


@dataclasses.dataclass
class BenchResult:
    name: str
    nodes: int
    het: bool
    steps: int
    avg_step_s: float
    total_s: float
    final_loss: float
    first_loss: float

    def row(self, base: Optional["BenchResult"] = None) -> str:
        speedup = base.total_s / self.total_s if base else 1.0
        expansion = speedup / self.nodes if base else 1.0
        return (f"| {self.name:14s} | {self.nodes:5d} | "
                f"{'het' if self.het else 'hom':3s} | {self.steps:5d} | "
                f"{self.avg_step_s * 1e3:10.1f} | {self.total_s:8.2f} | "
                f"{self.final_loss:9.4f} | {expansion:9.2f} | "
                f"{speedup:7.2f} |")


HEADER = (f"| {'config':14s} | nodes | h/h | steps | avg step ms |"
          f"  total s | fin. loss | expansion | speedup |")


def run_training(
    name: str,
    cfg,
    *,
    data_parallel: int,
    capacities: Sequence[float],
    global_batch: int,
    seq_len: int,
    steps: int,
    seed: int = 0,
    lr: float = 3e-3,
    label_smoothing: float = 0.0,
    mask_lm: bool = False,
) -> BenchResult:
    """One timed run. ``data_parallel`` host devices form the DP mesh."""
    model = build_model(cfg)
    mesh = make_mesh((data_parallel, 1), ("data", "model"))
    shape = ShapeConfig("bench", seq_len, global_batch, "train")
    tcfg = TrainConfig(model=cfg, shape=shape, het=HetConfig(),
                       optimizer=OptimizerConfig(
                           lr=lr, warmup_steps=max(steps // 10, 2),
                           schedule="inverse_sqrt",
                           betas=(0.9, 0.98), eps=1e-9))

    plan = cap.plan_capacities(global_batch, capacities)
    rec = make_lm_records(4 * global_batch, seq_len + 1, cfg.vocab_size,
                          seed=seed)
    rng = np.random.default_rng(seed)

    with jax.set_mesh(mesh):
        state = steps_mod.init_train_state(model, tcfg, mesh,
                                           jax.random.PRNGKey(seed))
        step_fn = steps_mod.build_train_step(model, tcfg, mesh)
        bspecs = named(mesh, batch_specs(cfg, mesh, plan.padded_rows))

        def make_batch(i):
            lo = (i * global_batch) % (3 * global_batch)
            samples = {"inputs": rec["inputs"][lo:lo + global_batch,
                                               :seq_len],
                       "labels": rec["labels"][lo:lo + global_batch,
                                               :seq_len]}
            tw = None
            if mask_lm:
                # BERT-style: only masked positions carry loss weight
                tw = (rng.random((global_batch, seq_len)) < 0.15
                      ).astype(np.float32)
                tw[:, 0] = 1.0               # never all-zero
            packed = pack_global_batch(samples, plan, token_weights=tw)
            return jax.device_put(
                {k: jnp.asarray(v) for k, v in packed.items()}, bspecs)

        # warmup (compile)
        state, m0 = step_fn(state, make_batch(0))
        first_loss = float(m0["loss"])
        t0 = time.time()
        last = first_loss
        for i in range(1, steps + 1):
            state, met = step_fn(state, make_batch(i))
            last = met["loss"]
        last = float(last)
        total = time.time() - t0
    return BenchResult(name=name, nodes=data_parallel,
                       het=len(set(capacities)) > 1, steps=steps,
                       avg_step_s=total / steps, total_s=total,
                       final_loss=last, first_loss=first_loss)


def grid_configs(max_nodes: int) -> List[Tuple[str, int, List[float]]]:
    """The paper's 1 / 2(hom) / 2(het) / 4(hom) / 4(het) / 8(het) grid."""
    grid = [("1 node", 1, [1.0])]
    if max_nodes >= 2:
        grid += [("2 (hom)", 2, [1.0, 1.0]),
                 ("2 (het)", 2, [1.5, 0.5])]
    if max_nodes >= 4:
        grid += [("4 (hom)", 4, [1.0] * 4),
                 ("4 (het)", 4, [1.5, 1.5, 0.5, 0.5])]
    if max_nodes >= 8:
        grid += [("8 (het)", 8, [2.0, 1.5, 1.5, 1.0, 1.0, 0.5, 0.5, 0.0])]
    return grid
