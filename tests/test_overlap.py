"""Overlapped per-bucket pipeline + flat-view optimizer: exactness.

Single-device tests cover the packed-layout views (decay mask, segment
ids) and the flat AdamW/LAMB math against the pytree optimizers; the
pipeline itself (and the fused train step, both reduction modes,
including error-feedback state) is exercised under the 8-device mesh in
a subprocess, per the project convention that only children force
device counts.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.core import buckets as bkt
from repro.launch.mesh import make_mesh
from repro.optim import adam, lamb

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


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "w": jax.random.normal(ks[0], (37, 8), jnp.float32),
        "b": jax.random.normal(ks[1], (13,), jnp.float32),
        "deep": {"m": jax.random.normal(ks[2], (5, 3, 2), jnp.float32),
                 "s": jax.random.normal(ks[3], (101,), jnp.float32)},
    }


def test_decay_mask_and_segment_ids_follow_leaf_structure():
    tree = _tree()
    layout = bkt.build_layout(tree, bucket_mb=1e-4, multiple_of=8)
    dm = np.asarray(bkt.decay_mask(layout)).reshape(-1)
    sid = np.asarray(bkt.segment_ids(layout)).reshape(-1)
    n_leaves = len(layout.sizes)
    for i, (off, n, shape) in enumerate(zip(layout.offsets, layout.sizes,
                                            layout.shapes)):
        assert (dm[off:off + n] == (1.0 if len(shape) >= 2 else 0.0)).all()
        assert (sid[off:off + n] == i).all()
    # padding: decays nothing, lands in the drop segment
    assert (dm[layout.total:] == 0.0).all()
    assert (sid[layout.total:] == n_leaves).all()


def test_apply_update_flat_bitwise_matches_tree_adam():
    """No clipping: the packed elementwise math IS apply_update."""
    params = _tree(0)
    grads = jax.tree.map(lambda p: 0.1 * p + 0.01, _tree(1))
    cfg = OptimizerConfig(grad_clip=0.0, weight_decay=0.01)
    state = adam.init_state(params, cfg)
    state = state._replace(step=jnp.asarray(3, jnp.int32))
    lr = jnp.float32(1e-3)
    new_p, new_s, _ = adam.apply_update(params, grads, state, cfg, lr)

    layout = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    pb = bkt.pack_buckets(params, layout)
    gb = bkt.pack_buckets(grads, layout)
    fp, fm, fv = adam.apply_update_flat(
        pb, gb, bkt.pack_buckets(state.m, layout),
        bkt.pack_buckets(state.v, layout), state.step + 1, cfg, lr,
        decay_mask=bkt.decay_mask(layout))
    flat_tree = bkt.unpack_buckets(fp, layout)
    for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(flat_tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(fm),
                                  np.asarray(bkt.pack_buckets(new_s.m,
                                                              layout)))
    np.testing.assert_array_equal(np.asarray(fv),
                                  np.asarray(bkt.pack_buckets(new_s.v,
                                                              layout)))


def test_apply_update_flat_clip_scale_matches_tree_clip():
    params = _tree(0)
    grads = jax.tree.map(lambda p: 2.5 * p + 0.3, _tree(1))
    cfg = OptimizerConfig(grad_clip=0.5, weight_decay=0.01)
    state = adam.init_state(params, cfg)
    lr = jnp.float32(1e-3)
    new_p, _, met = adam.apply_update(params, grads, state, cfg, lr)

    layout = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    gb = bkt.pack_buckets(grads, layout)
    gnorm = jnp.sqrt(jnp.sum(gb * gb))
    # flat and per-leaf norms group the same summands differently —
    # equal to fp tolerance, not bitwise
    np.testing.assert_allclose(float(gnorm), float(met["grad_norm"]),
                               rtol=1e-6)
    cs = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    fp, _, _ = adam.apply_update_flat(
        bkt.pack_buckets(params, layout), gb,
        bkt.pack_buckets(state.m, layout),
        bkt.pack_buckets(state.v, layout), state.step + 1, cfg, lr,
        decay_mask=bkt.decay_mask(layout), clip_scale=cs)
    for a, b in zip(jax.tree.leaves(new_p),
                    jax.tree.leaves(bkt.unpack_buckets(fp, layout))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_lamb_flat_trust_ratios_match_tree_lamb():
    params = _tree(0)
    grads = jax.tree.map(lambda p: 0.2 * p + 0.05, _tree(1))
    cfg = OptimizerConfig(name="lamb", grad_clip=0.0, weight_decay=0.01)
    state = adam.init_state(params, cfg)
    lr = jnp.float32(1e-2)
    new_p, _, met = lamb.apply_update(params, grads, state, cfg, lr)

    layout = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    fp, _, _, trust = lamb.apply_update_flat(
        bkt.pack_buckets(params, layout),
        bkt.pack_buckets(grads, layout),
        bkt.pack_buckets(state.m, layout),
        bkt.pack_buckets(state.v, layout), state.step + 1, cfg, lr,
        decay_mask=bkt.decay_mask(layout),
        seg_ids=bkt.segment_ids(layout), num_leaves=len(layout.sizes))
    for a, b in zip(jax.tree.leaves(new_p),
                    jax.tree.leaves(bkt.unpack_buckets(fp, layout))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    np.testing.assert_allclose(float(trust), float(met["trust_ratio"]),
                               rtol=1e-5)


def test_lamb_streamed_form_bitwise_matches_barrier_form():
    """The backward-overlap flush pipeline streams LAMB per bucket
    (flat_adamw_terms + bucket_norm_terms hooks, one trailing
    apply_trust). That streamed form must be BITWISE identical to the
    whole-stack barrier ``apply_update_flat`` given the same reduced
    stack — the contract is that both compute per-leaf norms through
    the same per-bucket calls combined in the same bucket-index order
    (lamb.combine_norm_terms)."""
    params = _tree(0)
    grads = jax.tree.map(lambda p: 0.2 * p + 0.05, _tree(1))
    cfg = OptimizerConfig(name="lamb", grad_clip=0.0, weight_decay=0.01)
    state = adam.init_state(params, cfg)
    lr = jnp.float32(1e-2)
    layout = bkt.build_layout(params, bucket_mb=1e-4, multiple_of=8)
    pb = bkt.pack_buckets(params, layout)
    gb = bkt.pack_buckets(grads, layout)
    mb = bkt.pack_buckets(state.m, layout)
    vb = bkt.pack_buckets(state.v, layout)
    dmask = bkt.decay_mask(layout)
    segs = bkt.segment_ids(layout)
    n_leaves = len(layout.sizes)
    step = state.step + 1
    assert pb.ndim == 2 and pb.shape[0] > 1   # multi-bucket or vacuous

    # barrier form: one call over the whole stack
    bp, bm, bv, _ = lamb.apply_update_flat(
        pb, gb, mb, vb, step, cfg, lr, decay_mask=dmask,
        seg_ids=segs, num_leaves=n_leaves)

    # streamed form: per-bucket hooks in flush order (scrambled to
    # prove order-independence of the trailing pass), partials
    # combined in canonical bucket-index order
    rows = [None] * pb.shape[0]
    flush_order = list(reversed(range(pb.shape[0])))
    for k in flush_order:
        pf, upd, mf, vf = adam.flat_adamw_terms(
            pb[k], gb[k], mb[k], vb[k], step, cfg,
            decay_mask=dmask[k])
        psq, usq = lamb.bucket_norm_terms(pf, upd, segs[k], n_leaves)
        rows[k] = (pf, upd, mf, vf, psq, usq)
    trust = lamb.trust_from_norms(
        lamb.combine_norm_terms([r[4] for r in rows]),
        lamb.combine_norm_terms([r[5] for r in rows]))
    pf = jnp.stack([r[0] for r in rows])
    upd = jnp.stack([r[1] for r in rows])
    sp = lamb.apply_trust(pf, upd, lr, segs, trust).astype(pb.dtype)
    sm = jnp.stack([r[2] for r in rows]).astype(mb.dtype)
    sv = jnp.stack([r[3] for r in rows]).astype(vb.dtype)

    np.testing.assert_array_equal(np.asarray(bp), np.asarray(sp))
    np.testing.assert_array_equal(np.asarray(bm), np.asarray(sm))
    np.testing.assert_array_equal(np.asarray(bv), np.asarray(sv))


def test_overlap_config_validation():
    """overlap='buckets'/'backward' must refuse configs they cannot
    pipeline — one clear ValueError at build time, not a failure deep
    in the pipeline."""
    import dataclasses
    from repro.configs import base as cfgs
    from repro.configs.base import HetConfig, TrainConfig
    from repro.launch.steps import train_schedule
    from repro.models.model import build_model

    mesh = make_mesh((1, 1), ("data", "model"))
    model = build_model(cfgs.smoke_config("olmo-1b"))
    for het, err in ((HetConfig(overlap="buckets"), "explicit"),
                     (HetConfig(overlap="backward"), "explicit"),
                     (HetConfig(overlap="buckets",
                                grad_reduction="bucketed_allreduce"),
                      "bucket_mb"),
                     (HetConfig(overlap="banana"), "not one of")):
        tcfg = TrainConfig(model=model.cfg, het=het)
        with pytest.raises(ValueError, match=err):
            train_schedule(model, tcfg, mesh)
    ok = TrainConfig(model=model.cfg, het=HetConfig(
        overlap="buckets", grad_reduction="bucketed_allreduce",
        bucket_mb=0.05))
    assert train_schedule(model, ok, mesh).kind == "overlap_buckets"
    none = dataclasses.replace(ok, het=HetConfig())
    assert train_schedule(model, none, mesh).kind == "allreduce"


def test_backward_overlap_build_validation():
    """overlap='backward' model/mesh rules: scanned stacks and
    non-uniform plans are refused with actionable messages."""
    import dataclasses
    from repro.configs import base as cfgs
    from repro.configs.base import HetConfig, TrainConfig
    from repro.launch.steps import validate_train_config
    from repro.models.model import build_model

    mesh = make_mesh((1, 1), ("data", "model"))
    het = HetConfig(overlap="backward",
                    grad_reduction="bucketed_allreduce", bucket_mb=0.05)

    scanned = build_model(cfgs.smoke_config("olmo-1b"))
    with pytest.raises(ValueError, match="scan_layers"):
        validate_train_config(
            scanned, TrainConfig(model=scanned.cfg, het=het), mesh)

    xl_cfg = dataclasses.replace(cfgs.smoke_config("xlstm-125m"),
                                 scan_layers=False)
    xl = build_model(xl_cfg)
    with pytest.raises(ValueError, match="uniform"):
        validate_train_config(xl, TrainConfig(model=xl_cfg, het=het),
                              mesh)

    un_cfg = dataclasses.replace(cfgs.smoke_config("olmo-1b"),
                                 scan_layers=False)
    un = build_model(un_cfg)
    validate_train_config(un, TrainConfig(model=un_cfg, het=het), mesh)


def test_bucket_readiness_maps_layer_partition_to_buckets():
    """The readiness schedule: a bucket is flushable at the LATEST
    backward stage of any element it contains; padding never delays."""
    tree = {"emb": jnp.zeros((40,)), "layers": jnp.zeros((4, 30)),
            "z_head": jnp.zeros((25,))}
    layout = bkt.build_layout(tree, bucket_mb=40 * 4 / (1 << 20),
                              multiple_of=5)
    # flatten order: emb(40), layers(120), z_head(25); stream total 185
    L = 4
    pieces = [
        [(0, 40, L + 1)],                               # emb: last
        [(l * 30, 30, L - l) for l in range(L)],        # back-to-front
        [(0, 25, 0)],                                   # head: first
    ]
    ready = bkt.bucket_readiness(layout, pieces)
    assert len(ready) == layout.num_buckets
    be = layout.bucket_elems
    for k, r in enumerate(ready):
        stages = set()
        for (off, size), leaf_pieces in zip(
                zip(layout.offsets, layout.sizes), pieces):
            for p_off, n, stage in leaf_pieces:
                lo, hi = off + p_off, off + p_off + n
                if lo < (k + 1) * be and hi > k * be:
                    stages.add(stage)
        assert r == max(stages), (k, r, stages)
    # the bucket holding the embedding always waits for the last stage
    assert ready[0] == L + 1
    # mismatched pieces fail loudly
    with pytest.raises(ValueError, match="tile"):
        bkt.bucket_readiness(layout, [[(1, 39, 0)], pieces[1],
                                      pieces[2]])


def test_flush_pipeline_double_buffer_and_ordering():
    """BucketFlushPipeline: prep(next) issues before exchange(current),
    results assemble in bucket-index order, finish() refuses missing
    flushes."""
    readiness = (2, 0, 1, 0)            # flush order: 1, 3, 2, 0
    log = []

    def prep(k, raw_k):
        log.append(("prep", k))
        return raw_k

    def exchange(k, prepared):
        log.append(("exchange", k))
        return prepared * 10.0, None

    pipe = bkt.BucketFlushPipeline(readiness, prep, exchange)
    raw = jnp.arange(4.0)
    for stage in range(3):
        pipe.flush_ready_buckets(stage, lambda k: raw[k])
    outs, errs, _ = pipe.finish()
    assert errs is None
    np.testing.assert_array_equal(np.asarray(jnp.stack(outs)),
                                  [0.0, 10.0, 20.0, 30.0])
    # double buffer: each bucket's prep precedes the PREVIOUS bucket's
    # exchange; exchanges run in flush (readiness) order
    assert log == [("prep", 1), ("prep", 3), ("exchange", 1),
                   ("prep", 2), ("exchange", 3), ("prep", 0),
                   ("exchange", 2), ("exchange", 0)]

    pipe2 = bkt.BucketFlushPipeline(readiness, prep, exchange)
    pipe2.flush_ready_buckets(0, lambda k: raw[k])
    with pytest.raises(ValueError, match="finish"):
        pipe2.finish()


@pytest.mark.slow
def test_overlapped_exchange_bitwise_matches_monolithic():
    """Per-bucket pipeline == monolithic exchange, bit for bit (fp32
    AND int8 with error feedback, key=None), plus the 3-level
    hierarchical pipeline."""
    out = run_child("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core import buckets as bkt
        from repro.core import hierarchical as hier

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        pods = 2
        rng = np.random.default_rng(0)
        tree = {"w": jnp.asarray(rng.standard_normal((130, 17)),
                                 jnp.float32),
                "b": jnp.asarray(rng.standard_normal((251,)),
                                 jnp.float32)}
        layout = bkt.build_layout(tree, bucket_mb=1e-3,
                                  multiple_of=pods * 256)
        assert layout.num_buckets >= 2
        stacked = jax.tree.map(lambda v: jnp.stack([v, -0.5 * v]), tree)

        def run(compress, overlapped, with_err):
            def f(gl):
                g = jax.tree.map(lambda a: a[0], gl)
                flat = bkt.pack_buckets(g, layout)
                e = (jnp.zeros_like(flat) + 0.01 if with_err else None)
                if overlapped:
                    red, ne, _ = bkt.exchange_buckets_overlapped(
                        flat, e, axis="pod", axis_size=pods,
                        compress=compress)
                else:
                    red, ne = bkt.exchange_buckets(
                        flat, e, axis="pod", axis_size=pods,
                        compress=compress, total=layout.total)
                return red, (ne if ne is not None else jnp.zeros(()))
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("pod"),
                out_specs=(P(), P("pod")) if with_err else (P(), P()),
                axis_names={"pod"}, check_vma=False))(stacked)

        for compress, with_err in ((False, False), (True, False),
                                   (True, True)):
            r_m, e_m = run(compress, False, with_err)
            r_o, e_o = run(compress, True, with_err)
            np.testing.assert_array_equal(np.asarray(r_m),
                                          np.asarray(r_o))
            if with_err:
                np.testing.assert_array_equal(np.asarray(e_m),
                                              np.asarray(e_o))
        # value sanity: sum of the contributions
        ref = bkt.pack_buckets(jax.tree.map(lambda v: 0.5 * v, tree),
                               layout)
        np.testing.assert_allclose(np.asarray(r_o)[:, :256],
                                   np.asarray(ref)[:, :256], atol=0.05)

        # layout with >= 1 ALL-padding tail block: the monolithic
        # exchange skips quantizing it (exchange_buckets total=...);
        # with the reachable (zero) error tail the pipeline must still
        # agree bitwise, and the tail error must stay pinned to zero
        tree_p = {"w": jnp.asarray(rng.standard_normal((1500,)),
                                   jnp.float32)}
        layout_p = bkt.build_layout(tree_p, bucket_mb=4096 / (1 << 20),
                                    multiple_of=pods * 256)
        pad = layout_p.padded_total - layout_p.total
        assert pad >= 256, (layout_p.padded_total, layout_p.total)
        stacked_p = jax.tree.map(lambda v: jnp.stack([v, -0.5 * v]),
                                 tree_p)

        def run_pad(overlapped):
            def f(gl):
                g = jax.tree.map(lambda a: a[0], gl)
                flat = bkt.pack_buckets(g, layout_p)
                err0 = jnp.zeros_like(flat)      # reachable state
                if overlapped:
                    red, ne, _ = bkt.exchange_buckets_overlapped(
                        flat, err0, axis="pod", axis_size=pods,
                        compress=True)
                else:
                    red, ne = bkt.exchange_buckets(
                        flat, err0, axis="pod", axis_size=pods,
                        compress=True, total=layout_p.total)
                return red, ne
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("pod"),
                out_specs=(P(), P("pod")),
                axis_names={"pod"}, check_vma=False))(stacked_p)

        r_m, e_m = run_pad(False)
        r_o, e_o = run_pad(True)
        np.testing.assert_array_equal(np.asarray(r_m), np.asarray(r_o))
        np.testing.assert_array_equal(np.asarray(e_m), np.asarray(e_o))
        tail = np.asarray(e_m).reshape(2, -1)[:, layout_p.total:]
        assert (tail == 0.0).all()

        # 3-level hierarchical pipeline (manual over pod AND data)
        layout3 = bkt.build_layout(tree, bucket_mb=1e-3,
                                   multiple_of=2 * pods * 256)
        stacked4 = jax.tree.map(
            lambda v: jnp.stack([v.astype(jnp.float32)] * 4), tree)

        def run3(overlapped, compress, with_err):
            def f(gl):
                g = jax.tree.map(lambda a: a[0], gl)
                e = (jnp.zeros((layout3.num_buckets,
                                layout3.bucket_elems // 2),
                               jnp.float32) + 0.01 if with_err else None)
                fn = (hier.hierarchical_reduce_bucketed_overlapped
                      if overlapped else hier.hierarchical_reduce_bucketed)
                out, ne = fn(g, e, layout3, data_size=2, pod_size=pods,
                             compress=compress)
                return out, (ne if ne is not None else jnp.zeros(()))
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(("pod", "data")),
                out_specs=(P(), P(("pod", "data"))) if with_err
                else (P(), P()),
                axis_names={"pod", "data"}, check_vma=False))(stacked4)

        for compress, with_err in ((False, False), (True, True)):
            o_m, e_m = run3(False, compress, with_err)
            o_o, e_o = run3(True, compress, with_err)
            for a, b in zip(jax.tree.leaves(o_m), jax.tree.leaves(o_o)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            if with_err:
                np.testing.assert_array_equal(np.asarray(e_m),
                                              np.asarray(e_o))
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_fused_overlap_train_step_matches_monolithic():
    """Full train steps: overlap='buckets' vs 'none' — bit-identical
    (fp32, no clip, streamed per-bucket updates), tolerance-equal with
    clipping / int8 error feedback, for BOTH reduction modes."""
    out = run_child("""
        import dataclasses
        import jax, jax.numpy as jnp
        import numpy as np
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

        def run(mode, compress, overlap, clip):
            tcfg = TrainConfig(model=cfg, shape=shape,
                               het=HetConfig(grad_reduction=mode,
                                             compression=compress,
                                             bucket_mb=0.05,
                                             overlap=overlap),
                               optimizer=OptimizerConfig(
                                   lr=1e-3, warmup_steps=2,
                                   grad_clip=clip))
            with jax.set_mesh(mesh):
                state = steps.init_train_state(m, tcfg, mesh,
                                               jax.random.PRNGKey(0))
                step = steps.build_train_step(m, tcfg, mesh)
                batch = {k: jnp.asarray(v) for k, v in packed.items()}
                losses = []
                for _ in range(3):
                    state, met = step(state, batch)
                    losses.append(float(met["loss"]))
            return losses, jax.device_get(state)

        # streamed fused path (clip=0): bit-identical params + losses
        l0, s0 = run("bucketed_allreduce", "none", "none", 0.0)
        l1, s1 = run("bucketed_allreduce", "none", "buckets", 0.0)
        assert l0 == l1, (l0, l1)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # clip-barrier path: tolerance (norm-grouping differs)
        l0, s0 = run("bucketed_allreduce", "none", "none", 1.0)
        l1, s1 = run("bucketed_allreduce", "none", "buckets", 1.0)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)

        # hierarchical + int8 + error feedback: err state must track
        l0, s0 = run("hierarchical", "int8", "none", 1.0)
        l1, s1 = run("hierarchical", "int8", "buckets", 1.0)
        for a, b in zip(l0, l1):
            assert abs(a - b) < 1e-4, (l0, l1)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)
        np.testing.assert_allclose(np.asarray(s0.err),
                                   np.asarray(s1.err), atol=1e-6)
        assert np.any(np.asarray(s1.err) != 0.0)   # feedback is live
        print("OK")
        """, timeout=900)
    assert "OK" in out


@pytest.mark.slow
def test_backward_overlap_train_step_matches_monolithic():
    """overlap='backward' (buckets flushed DURING backprop): full train
    steps vs the monolithic path and the after-backward pipeline, with
    scan_layers=False (the unrolled program class the staged backward
    requires). fp32 grad_clip=0 is bit-identical — losses AND params —
    for both reduction modes; the clip barrier and LAMB paths are
    bit-identical to overlap='buckets' (same barrier update over the
    same reduced stack); int8 + error feedback tracks bitwise
    (per-bucket exchanges are order-independent)."""
    out = run_child("""
        import dataclasses
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import base
        from repro.configs.base import TrainConfig, HetConfig, \\
            OptimizerConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.core import capacity, dummy
        from repro.data import synthetic

        cfg = dataclasses.replace(base.smoke_config("olmo-1b"),
                                  compute_dtype="float32",
                                  scan_layers=False)
        m = build_model(cfg)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", 16, 8, "train")
        rec = synthetic.make_lm_records(8, 17, cfg.vocab_size, seed=5)
        plan = capacity.plan_capacities(8, [1, 1, 1, 1])
        packed = dummy.pack_global_batch(
            {"inputs": rec["inputs"][:, :16],
             "labels": rec["labels"][:, :16]}, plan)

        def run(mode, compress, overlap, clip, opt="adamw", accum=1):
            tcfg = TrainConfig(model=cfg, shape=shape,
                               het=HetConfig(grad_reduction=mode,
                                             compression=compress,
                                             bucket_mb=0.05,
                                             overlap=overlap,
                                             accum_steps=accum),
                               optimizer=OptimizerConfig(
                                   name=opt, lr=1e-3, warmup_steps=2,
                                   grad_clip=clip))
            with jax.set_mesh(mesh):
                state = steps.init_train_state(m, tcfg, mesh,
                                               jax.random.PRNGKey(0))
                step = steps.build_train_step(m, tcfg, mesh)
                batch = {k: jnp.asarray(v) for k, v in packed.items()}
                losses = []
                for _ in range(3):
                    state, met = step(state, batch)
                    losses.append(float(met["loss"]))
            return losses, jax.device_get(state)

        def assert_bitwise(s0, s1):
            for a, b in zip(jax.tree.leaves(s0.params),
                            jax.tree.leaves(s1.params)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))

        # fp32, clip=0, fused stream: bit-identical to the monolithic
        # path AND the after-backward pipeline (ACCEPTANCE criterion)
        l0, s0 = run("bucketed_allreduce", "none", "none", 0.0)
        l1, s1 = run("bucketed_allreduce", "none", "backward", 0.0)
        l2, s2 = run("bucketed_allreduce", "none", "buckets", 0.0)
        assert l0 == l1 == l2, (l0, l1, l2)
        assert_bitwise(s0, s1)
        assert_bitwise(s1, s2)

        # clip barrier: exchanges still flush during backprop, update
        # behind the barrier — bit-identical to the 'buckets' barrier
        l1, s1 = run("bucketed_allreduce", "none", "backward", 1.0)
        l2, s2 = run("bucketed_allreduce", "none", "buckets", 1.0)
        assert l1 == l2, (l1, l2)
        assert_bitwise(s1, s2)

        # LAMB: backward STREAMS it (per-bucket moments + norm
        # partials mid-flush, one trailing trust pass); buckets keeps
        # the whole-stack barrier — both must stay bitwise-equal
        l1, s1 = run("bucketed_allreduce", "none", "backward", 0.0,
                     opt="lamb")
        l2, s2 = run("bucketed_allreduce", "none", "buckets", 0.0,
                     opt="lamb")
        assert l1 == l2, (l1, l2)
        assert_bitwise(s1, s2)

        # hierarchical + int8 + error feedback, fused stream: the
        # per-bucket exchange is order-independent, so the flush
        # schedule must track the after-backward pipeline bitwise —
        # err state included
        l1, s1 = run("hierarchical", "int8", "backward", 0.0)
        l2, s2 = run("hierarchical", "int8", "buckets", 0.0)
        assert l1 == l2, (l1, l2)
        assert_bitwise(s1, s2)
        np.testing.assert_array_equal(np.asarray(s1.err),
                                      np.asarray(s2.err))
        assert np.any(np.asarray(s1.err) != 0.0)

        # gradient accumulation: every microbatch's backward is staged,
        # flushes fire only during the last one. Losses stay bitwise;
        # params are tolerance-equal (the monolithic whole-grad and the
        # staged per-layer VJPs compile into different fp contexts at
        # accum > 1)
        l0, s0 = run("bucketed_allreduce", "none", "none", 0.0,
                     accum=2)
        l1, s1 = run("bucketed_allreduce", "none", "backward", 0.0,
                     accum=2)
        assert l0 == l1, (l0, l1)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)

        # embedding_stub frontend (no token table; inputs are (B,S,d)
        # embeddings — regression: positions must come from the
        # POST-embed activation, not inputs.shape[-1]): losses bitwise,
        # params to fp-rounding tolerance (this arch's program class
        # drifts ~1e-7 between whole-grad and staged compilation)
        scfg = dataclasses.replace(base.smoke_config("musicgen-large"),
                                   compute_dtype="float32",
                                   scan_layers=False)
        assert scfg.frontend == "embedding_stub"
        sm = build_model(scfg)
        sbatch = {
            "inputs": jnp.asarray(np.random.default_rng(1)
                                  .standard_normal((8, 16, scfg.d_model)),
                                  jnp.bfloat16),
            "labels": jnp.asarray(np.random.default_rng(2)
                                  .integers(0, scfg.vocab_size, (8, 16)),
                                  jnp.int32),
            "weights": jnp.ones((8, 16), jnp.float32),
        }

        def run_stub(overlap):
            tcfg = TrainConfig(model=scfg, shape=shape,
                               het=HetConfig(
                                   grad_reduction="bucketed_allreduce",
                                   bucket_mb=0.05, overlap=overlap),
                               optimizer=OptimizerConfig(
                                   lr=1e-3, warmup_steps=2,
                                   grad_clip=0.0))
            with jax.set_mesh(mesh):
                state = steps.init_train_state(sm, tcfg, mesh,
                                               jax.random.PRNGKey(0))
                step = steps.build_train_step(sm, tcfg, mesh)
                losses = []
                for _ in range(2):
                    state, met = step(state, sbatch)
                    losses.append(float(met["loss"]))
            return losses, jax.device_get(state)

        l0, s0 = run_stub("none")
        l1, s1 = run_stub("backward")
        assert l0 == l1, (l0, l1)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)
        print("OK")
        """, timeout=1800)
    assert "OK" in out
