"""Multi-device SPMD tests (subprocess with 8 host devices).

The main pytest process keeps the default 1-device world (per project
convention: only the dry-run forces device counts), so anything needing
a mesh runs in a child interpreter with XLA_FLAGS set before jax import.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_spmd_train_step_equals_single_process():
    """The jit-level invariant: the sharded weighted train step computes
    the same loss as local single-process math on the same batch."""
    out = run_child("""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import base
        from repro.configs.base import TrainConfig, HetConfig, \\
            OptimizerConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.core import capacity, dummy, weighting
        from repro.data import synthetic
        import dataclasses

        cfg = dataclasses.replace(base.smoke_config("tinyllama-1.1b"),
                                  compute_dtype="float32")
        m = build_model(cfg)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", 16, 8, "train")
        tcfg = TrainConfig(model=cfg, shape=shape,
                           het=HetConfig(accum_steps=1),
                           optimizer=OptimizerConfig(lr=0.0,
                                                     warmup_steps=1,
                                                     grad_clip=0.0))
        rec = synthetic.make_lm_records(8, 17, cfg.vocab_size, seed=3)
        plan = capacity.plan_capacities(8, [2, 1, 1, 0])
        packed = dummy.pack_global_batch(
            {"inputs": rec["inputs"][:, :16],
             "labels": rec["labels"][:, :16]}, plan)
        with jax.set_mesh(mesh):
            state = steps.init_train_state(m, tcfg, mesh,
                                           jax.random.PRNGKey(0))
            step = steps.build_train_step(m, tcfg, mesh)
            batch = {k: jnp.asarray(v) for k, v in packed.items()}
            params_before = jax.device_get(state.params)
            _, met = step(state, batch)
        spmd_loss = float(met["loss"])

        # single-process reference over the union of real rows
        ref_batch = {"inputs": jnp.asarray(rec["inputs"][:, :16]),
                     "labels": jnp.asarray(rec["labels"][:, :16]),
                     "weights": jnp.ones((8, 16))}
        o, w, _ = m.loss_fn(params_before, ref_batch)
        ref_loss = float(o / w)
        print("spmd", spmd_loss, "ref", ref_loss)
        assert abs(spmd_loss - ref_loss) < 1e-4, (spmd_loss, ref_loss)
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_reduction_modes_agree():
    """allreduce vs hierarchical vs the bucketed engine (per-leaf and
    flat-buffer) produce identical trajectories on the exact paths;
    int8-compressed stays within quantization tolerance."""
    out = run_child("""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import base
        from repro.configs.base import TrainConfig, HetConfig, \\
            OptimizerConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.core import capacity, dummy
        from repro.data import synthetic

        cfg = dataclasses.replace(base.smoke_config("olmo-1b"),
                                  compute_dtype="float32")
        m = build_model(cfg)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", 16, 8, "train")
        rec = synthetic.make_lm_records(8, 17, cfg.vocab_size, seed=5)
        plan = capacity.plan_capacities(8, [1, 1, 1, 1])
        packed = dummy.pack_global_batch(
            {"inputs": rec["inputs"][:, :16],
             "labels": rec["labels"][:, :16]}, plan)

        def run(mode, compress, bucket_mb=0.0):
            tcfg = TrainConfig(model=cfg, shape=shape,
                               het=HetConfig(grad_reduction=mode,
                                             compression=compress,
                                             bucket_mb=bucket_mb),
                               optimizer=OptimizerConfig(
                                   lr=1e-3, warmup_steps=2))
            with jax.set_mesh(mesh):
                state = steps.init_train_state(m, tcfg, mesh,
                                               jax.random.PRNGKey(0))
                step = steps.build_train_step(m, tcfg, mesh)
                batch = {k: jnp.asarray(v) for k, v in packed.items()}
                losses = []
                for _ in range(4):
                    state, met = step(state, batch)
                    losses.append(float(met["loss"]))
            return losses

        base_l = run("allreduce", "none")
        hier_l = run("hierarchical", "none")
        hierb_l = run("hierarchical", "none", bucket_mb=0.05)
        comp_l = run("hierarchical", "int8")
        compb_l = run("hierarchical", "int8", bucket_mb=0.05)
        bar_l = run("bucketed_allreduce", "none", bucket_mb=0.05)
        print(base_l, hier_l, hierb_l, comp_l, compb_l, bar_l)
        for exact in (hier_l, hierb_l, bar_l):
            for a, b in zip(base_l, exact):
                assert abs(a - b) < 2e-3, (a, b)
        for comp in (comp_l, compb_l):
            for a, b in zip(base_l, comp):
                assert abs(a - b) < 3e-2, (a, b)
        assert comp_l[-1] < comp_l[0]
        assert compb_l[-1] < compb_l[0]
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_bucketed_exchange_matches_per_leaf_psum():
    """Direct equivalence under the 8-device mesh: the bucketed
    flat-buffer exchange == per-leaf psum (exact) and stays within int8
    tolerance compressed, with error feedback capturing the residual."""
    out = run_child("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core import buckets as bkt
        from repro.core import hierarchical as hier

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        pods = 2
        k = jax.random.PRNGKey(0)
        tree = {"w": jax.random.normal(k, (67, 33)),
                "b": jax.random.normal(jax.random.fold_in(k, 1), (129,)),
                "s": jax.random.normal(jax.random.fold_in(k, 2),
                                       (3, 7, 5)).astype(jnp.bfloat16)}
        layout = bkt.build_layout(tree, bucket_mb=1e-3,
                                  multiple_of=pods * 256)
        stacked = jax.tree.map(
            lambda v: jnp.stack([v, (-0.5 * v.astype(jnp.float32)
                                     ).astype(v.dtype)]), tree)
        ref = jax.tree.map(
            lambda v: np.asarray(v, np.float32) * 0.5, tree)

        def bucketed(compress):
            def f(gl):
                g = jax.tree.map(lambda a: a[0], gl)
                flat = bkt.pack_buckets(g, layout)
                red, _ = bkt.exchange_buckets(
                    flat, None, axis="pod", axis_size=pods,
                    compress=compress)
                return bkt.unpack_buckets(red, layout)
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("pod"), out_specs=P(),
                axis_names={"pod"}, check_vma=False))

        def per_leaf_psum(gl):
            g = jax.tree.map(lambda a: a[0].astype(jnp.float32), gl)
            return jax.tree.map(lambda a: jax.lax.psum(a, "pod"), g)

        exact = bucketed(False)(stacked)
        plain = jax.jit(jax.shard_map(
            per_leaf_psum, mesh=mesh, in_specs=P("pod"), out_specs=P(),
            axis_names={"pod"}, check_vma=False))(stacked)
        for a, b, c in zip(jax.tree.leaves(exact), jax.tree.leaves(ref),
                           jax.tree.leaves(plain)):
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       atol=2e-2)   # bf16 leaf storage
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(c, np.float32),
                atol=2e-2)
        # f32 leaves must be exact vs the per-leaf psum
        np.testing.assert_allclose(np.asarray(exact["w"]),
                                   np.asarray(plain["w"]), atol=1e-5)

        comp = bucketed(True)(stacked)
        for a, b in zip(jax.tree.leaves(comp), jax.tree.leaves(ref)):
            scale = max(1e-3, float(np.abs(b).max()))
            assert float(np.abs(np.asarray(a, np.float32) - b).max()) \\
                < 0.05 * scale + 0.02

        # 3-level bucketed hierarchical (manual over pod AND data)
        layout3 = bkt.build_layout(tree, bucket_mb=1e-3,
                                   multiple_of=2 * pods * 256)
        def f3(gl):
            g = jax.tree.map(lambda a: a[0], gl)
            out, _ = hier.hierarchical_reduce_bucketed(
                g, None, layout3, data_size=2, pod_size=pods)
            return out
        stacked4 = jax.tree.map(
            lambda v: jnp.stack([v.astype(jnp.float32)] * 4), tree)
        out3 = jax.jit(jax.shard_map(
            f3, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
            axis_names={"pod", "data"}, check_vma=False))(stacked4)
        for a, b in zip(jax.tree.leaves(out3), jax.tree.leaves(tree)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32),
                4 * np.asarray(b, np.float32), rtol=2e-2, atol=5e-2)
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cell_compiles_multi_pod():
    """One real dry-run cell on the production 512-chip mesh inside the
    child (the full grid is exercised by launch/dryrun.py)."""
    out = run_child("""
        from repro.launch import dryrun
        lowered, meta = dryrun.lower_cell("xlstm-125m", "train_4k", True)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes > 0
        print("chips", meta["chips"])
        assert meta["chips"] == 512
        print("OK")
        """, devices=512)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restart_resumes_identically():
    """Checkpoint on a 2-pod mesh, restart on a 1-pod mesh (re-mesh):
    the next-step loss matches continuing on the original mesh."""
    out = run_child("""
        import jax, jax.numpy as jnp
        import numpy as np, tempfile, dataclasses
        from repro.configs import base
        from repro.configs.base import TrainConfig, HetConfig, \\
            OptimizerConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.core import capacity, dummy
        from repro.data import synthetic
        from repro.checkpoint.checkpoint import CheckpointManager

        cfg = dataclasses.replace(base.smoke_config("tinyllama-1.1b"),
                                  compute_dtype="float32")
        m = build_model(cfg)
        shape = ShapeConfig("t", 16, 8, "train")
        ocfg = OptimizerConfig(lr=1e-3, warmup_steps=2, grad_clip=1.0)
        rec = synthetic.make_lm_records(16, 17, cfg.vocab_size, seed=9)

        def batch_for(plan, lo, hi):
            packed = dummy.pack_global_batch(
                {"inputs": rec["inputs"][lo:hi, :16],
                 "labels": rec["labels"][lo:hi, :16]}, plan)
            return {k: jnp.asarray(v) for k, v in packed.items()}

        # phase 1: 2-pod mesh, 2 steps, checkpoint
        mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        tcfg = TrainConfig(model=cfg, shape=shape, het=HetConfig(),
                           optimizer=ocfg)
        plan4 = capacity.plan_capacities(8, [1, 1, 1, 1])
        with jax.set_mesh(mesh2):
            state = steps.init_train_state(m, tcfg, mesh2,
                                           jax.random.PRNGKey(0))
            step2 = steps.build_train_step(m, tcfg, mesh2)
            state, _ = step2(state, batch_for(plan4, 0, 8))
            host = jax.device_get(state)
            state, met_next = step2(state, batch_for(plan4, 8, 16))
        loss_continue = float(met_next["loss"])

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, host, meta={"seed": 0}, block=True)

            # phase 2: pod lost -> re-mesh to single pod, restore, resume
            mesh1 = make_mesh((4, 2), ("data", "model"))
            with jax.set_mesh(mesh1):
                fresh = steps.init_train_state(m, tcfg, mesh1,
                                               jax.random.PRNGKey(0))
                restored_host, meta = mgr.restore(jax.device_get(fresh))
                specs = steps.state_specs(m, tcfg, mesh1)
                from repro.launch.sharding import named
                restored = jax.device_put(
                    type(fresh)(*restored_host), named(mesh1, specs))
                step1 = steps.build_train_step(m, tcfg, mesh1)
                # same global batch, same plan rows (4 DP ranks)
                _, met_re = step1(restored, batch_for(plan4, 8, 16))
        loss_resumed = float(met_re["loss"])
        print("continue", loss_continue, "resumed", loss_resumed)
        assert abs(loss_continue - loss_resumed) < 1e-4
        print("OK")
        """)
    assert "OK" in out
