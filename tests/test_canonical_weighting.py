"""weighting="canonical" end to end: config gating, the sampler's
plan-independent canonical row layout, and the headline guarantee —
bit-identical training trajectories across capacity replans."""
import subprocess
import sys
import os
import textwrap

import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.configs.base import HetConfig
from repro.core import capacity
from repro.data import sampler, synthetic
from repro.data.dataset import ShardedDataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, devices: int = 4, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_canonical_config_gating():
    """The order-canonical sum must be the ONLY reduction: every engine
    that regroups it (buckets, hierarchy, compression, accumulation) is
    rejected at validate() time with an actionable message."""
    HetConfig(weighting="canonical").validate()    # plain allreduce: ok
    bad = [HetConfig(weighting="canonical", grad_reduction="hierarchical"),
           HetConfig(weighting="canonical",
                     grad_reduction="bucketed_allreduce", bucket_mb=4.0),
           HetConfig(weighting="canonical", compression="int8"),
           HetConfig(weighting="canonical", overlap="buckets",
                     grad_reduction="bucketed_allreduce", bucket_mb=4.0),
           HetConfig(weighting="canonical", accum_steps=2)]
    for het in bad:
        with pytest.raises(ValueError, match="canonical"):
            het.validate()
    assert "canonical" in cfgbase.WEIGHTING_MODES


def test_canonical_pack_is_plan_independent(tmp_path):
    """Same epoch, same batch index => byte-identical canonical batches
    under different capacity plans, with partial tails padded by
    trailing weight-0 rows (never interleaved)."""
    corpus = synthetic.build_synthetic_corpus(
        str(tmp_path / "c"), num_seqs=20, seq_len=16, vocab=64,
        rows_per_shard=8, seed=0)
    ds = ShardedDataset(corpus)
    plan_a = capacity.plan_capacities(6, [2, 1])
    plan_b = capacity.plan_capacities(6, [1, 3])
    smp_a = sampler.HetSampler(ds, plan_a, seed=3, canonical_order=True)
    smp_b = sampler.HetSampler(ds, plan_b, seed=3, canonical_order=True)
    batches_a = list(smp_a.iter_epoch(0))
    batches_b = list(smp_b.iter_epoch(0))
    assert len(batches_a) == len(batches_b) == 4     # 6+6+6+2
    for ba, bb in zip(batches_a, batches_b):
        for k in ba:
            np.testing.assert_array_equal(ba[k], bb[k])
        assert ba["inputs"].shape[0] == 6            # static shape
    tail = batches_a[-1]["weights"]
    assert np.all(tail[:2] > 0) and np.all(tail[2:] == 0)
    # the SPMD layout, by contrast, IS plan-dependent: rank buffers
    smp_r = sampler.HetSampler(ds, plan_a, seed=3)
    rows_spmd = next(iter(smp_r))["inputs"].shape[0]
    assert rows_spmd == plan_a.padded_rows != 6 or rows_spmd != 6


@pytest.mark.slow
def test_canonical_bit_identity_across_replans():
    """The wired train step (launch/steps.py canonical path + the
    sampler's canonical layout): a run that replans mid-stream — rows
    shifting between DP ranks — produces the bit-identical per-step
    loss sequence and final params as a run under a fixed plan, on the
    same global row stream. fp32 sums are not associative, so this
    only holds because the aggregation is order-canonical."""
    out = run_child("""
        import dataclasses, tempfile
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import base
        from repro.configs.base import TrainConfig, HetConfig, \\
            OptimizerConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.core import capacity
        from repro.data import sampler, synthetic
        from repro.data.dataset import ShardedDataset

        cfg = dataclasses.replace(base.smoke_config("olmo-1b"),
                                  compute_dtype="float32")
        m = build_model(cfg)
        corpus = synthetic.build_synthetic_corpus(
            tempfile.mkdtemp() + "/c", num_seqs=20, seq_len=16,
            vocab=cfg.vocab_size, rows_per_shard=8, seed=0)
        ds = ShardedDataset(corpus)
        shape = ShapeConfig("t", 16, 6, "train")
        tcfg = TrainConfig(
            model=cfg, shape=shape,
            het=HetConfig(weighting="canonical").validate(),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2))

        def run(plans):           # plans: one CapacityPlan per step
            mesh = make_mesh((2, 2), ("data", "model"))
            smp = sampler.HetSampler(ds, plans[0], seed=3,
                                     canonical_order=True)
            entries = smp.epoch_batches(0)
            losses, state = [], None
            with jax.set_mesh(mesh):
                state = steps.init_train_state(m, tcfg, mesh,
                                               jax.random.PRNGKey(0))
                step = steps.build_train_step(m, tcfg, mesh)
                for i, entry in enumerate(entries):
                    smp.set_plan(plans[i])
                    batch = {k: jnp.asarray(v)
                             for k, v in smp.pack(entry).items()}
                    state, met = step(state, batch)
                    losses.append(np.asarray(met["loss"]))
                params = jax.device_get(state.params)
            return losses, params

        fixed = capacity.plan_capacities(6, [2, 1])
        la, pa = run([fixed] * 4)
        lb, pb = run([capacity.plan_capacities(6, [1, 1])] * 2 +
                     [capacity.plan_capacities(6, [3, 1])] * 2)
        for i, (x, y) in enumerate(zip(la, lb)):
            assert x.tobytes() == y.tobytes(), (i, x, y)
        mism = [k for k, (u, v) in enumerate(zip(
                    jax.tree.leaves(pa), jax.tree.leaves(pb)))
                if np.asarray(u).tobytes() != np.asarray(v).tobytes()]
        assert not mism, f"params differ at leaves {mism}"
        print("losses", [float(x) for x in la])
        print("OK")
        """)
    assert "OK" in out
