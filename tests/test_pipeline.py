"""Heterogeneous pipeline parallelism: stage planning + 1F1B schedule.

Fast tests cover the capacity-sized stage partition (core/pipeline.py:
the DP planner's largest-remainder math reused with rows=layers),
checkpoint record round-trips, the 1F1B / GPipe schedules and their
deterministic global program order, the modeled-timeline invariants,
and config validation. The end-to-end bar — the stages=2 pipelined
train step bit-identical to pure DP — runs under the 8-device mesh in
a subprocess, per the project convention that only children force
device counts.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import base as cfgs
from repro.configs.base import HetConfig, TrainConfig
from repro.core import capacity
from repro.core import pipeline as pipe
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, devices: int = 8, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


# --------------------------------------------------------------------------
# stage planning


def test_plan_stages_capacity_sized_contiguous():
    splan = pipe.plan_stages(12, (2.0, 1.0))
    assert splan.layers_per_stage.tolist() == [8, 4]
    assert splan.num_stages == 2
    assert splan.boundaries.tolist() == [0, 8, 12]
    assert splan.stage_ranges() == [(0, 8), (8, 12)]
    for layer in range(12):
        assert splan.stage_of_layer(layer) == (0 if layer < 8 else 1)
    with pytest.raises(ValueError, match="outside"):
        splan.stage_of_layer(12)


def test_plan_stages_every_stage_gets_a_layer():
    """Extreme skew cannot starve a stage below 1 layer (min_rows=1 —
    a stage cannot run all-dummy, the forward passes through it)."""
    splan = pipe.plan_stages(4, (1000.0, 1.0, 1.0))
    assert splan.layers_per_stage.min() >= 1
    assert int(splan.layers_per_stage.sum()) == 4


def test_plan_stages_rejects_dead_and_overcut():
    with pytest.raises(ValueError, match="must be > 0"):
        pipe.plan_stages(8, (2.0, 0.0))
    with pytest.raises(ValueError, match="must be > 0"):
        pipe.plan_stages(8, (1.0, -1.0))
    with pytest.raises(ValueError, match="non-empty"):
        pipe.plan_stages(8, ())
    with pytest.raises(ValueError, match="cannot cut"):
        pipe.plan_stages(2, (1.0, 1.0, 1.0))


def test_stage_record_roundtrip_and_malformed_rejected():
    splan = pipe.plan_stages(12, (3.0, 1.0))
    rec = pipe.stage_record(splan)
    back = pipe.stage_from_record(rec)
    assert back.num_layers == splan.num_layers
    np.testing.assert_array_equal(back.layers_per_stage,
                                  splan.layers_per_stage)
    # and through JSON, the way checkpoints carry it
    import json
    back2 = pipe.stage_from_record(json.loads(json.dumps(rec)))
    np.testing.assert_array_equal(back2.layers_per_stage,
                                  splan.layers_per_stage)

    with pytest.raises(ValueError, match="malformed"):
        pipe.stage_from_record("stages=2")
    with pytest.raises(ValueError, match="malformed"):
        pipe.stage_from_record({"num_layers": 12})   # no plan
    bad = dict(rec, num_layers=13)                   # sum mismatch
    with pytest.raises(ValueError, match="sums to"):
        pipe.stage_from_record(bad)


def test_stage_plan_for_uses_capacities_only_when_stage_shaped():
    from repro.launch.steps import stage_plan_for
    from repro.models.model import build_model

    cfg = cfgs.smoke_config("olmo-1b")
    cfg = cfg.__class__(**{**cfg.__dict__, "num_layers": 4})
    model = build_model(cfg)

    def het(stages, caps):
        return TrainConfig(model=cfg, het=HetConfig(
            pipeline_stages=stages, accum_steps=max(stages, 1),
            capacities=caps))

    assert stage_plan_for(model, het(1, ())) is None
    # stage-shaped capacities size the cut
    assert stage_plan_for(model, het(2, (3.0, 1.0))) \
        .layers_per_stage.tolist() == [3, 1]
    # DP-rank-shaped (wrong length) or zero-containing -> uniform cut
    assert stage_plan_for(model, het(2, (2.0, 1.0, 1.0, 0.0))) \
        .layers_per_stage.tolist() == [2, 2]
    assert stage_plan_for(model, het(2, ())) \
        .layers_per_stage.tolist() == [2, 2]


# --------------------------------------------------------------------------
# schedules


@pytest.mark.parametrize("schedule", pipe.SCHEDULES)
@pytest.mark.parametrize("S,M", [(1, 1), (2, 4), (3, 5), (4, 4)])
def test_stage_schedule_is_complete_and_ordered(schedule, S, M):
    sched = pipe.stage_schedule(S, M, schedule)
    assert len(sched) == S
    for s, ops in enumerate(sched):
        fwd = [m for kind, m in ops if kind == pipe.FWD]
        bwd = [m for kind, m in ops if kind == pipe.BWD]
        # every microbatch forwarded and backwarded exactly once, in
        # microbatch order (the gradient-accumulation add order)
        assert fwd == list(range(M))
        assert bwd == list(range(M))


def test_1f1b_warmup_bounds_live_microbatches():
    """Stage s holds at most S - s live forwards before its first
    backward — the memory bound that distinguishes 1F1B from GPipe."""
    S, M = 4, 8
    sched = pipe.stage_schedule(S, M, "1f1b")
    for s, ops in enumerate(sched):
        live, peak = 0, 0
        for kind, _ in ops:
            live += 1 if kind == pipe.FWD else -1
            peak = max(peak, live)
        assert peak <= S - s, (s, peak)
    # GPipe by contrast peaks at M on every stage
    gp = pipe.stage_schedule(S, M, "gpipe")
    assert all(sum(1 for k, _ in ops if k == pipe.FWD) == M
               for ops in gp)


def test_stage_schedule_rejects_bad_inputs():
    with pytest.raises(ValueError, match="schedule"):
        pipe.stage_schedule(2, 4, "interleaved")
    with pytest.raises(ValueError, match=">= 1"):
        pipe.stage_schedule(0, 4)
    with pytest.raises(ValueError, match=">= 1"):
        pipe.stage_schedule(2, 0)


@pytest.mark.parametrize("schedule", pipe.SCHEDULES)
@pytest.mark.parametrize("S,M", [(2, 2), (3, 6), (4, 5)])
def test_program_order_respects_dependencies(schedule, S, M):
    order = pipe.program_order(S, M, schedule)
    assert len(order) == len(set(order)) == 2 * S * M
    pos = {op: i for i, op in enumerate(order)}
    for m in range(M):
        for s in range(S):
            if s > 0:
                assert pos[(s, pipe.FWD, m)] > pos[(s - 1, pipe.FWD, m)]
            assert pos[(s, pipe.BWD, m)] > pos[(s, pipe.FWD, m)]
            if s < S - 1:
                assert pos[(s, pipe.BWD, m)] > pos[(s + 1, pipe.BWD, m)]


def test_program_order_backwards_per_stage_in_microbatch_order():
    """B ops of a fixed stage appear in microbatch order — per-leaf
    grad accumulation at each B event reproduces unrolled_accumulate's
    add order (the bit-exactness hook for _build_pipeline_step)."""
    for schedule in pipe.SCHEDULES:
        order = pipe.program_order(3, 5, schedule)
        for s in range(3):
            bs = [m for (st, kind, m) in order
                  if st == s and kind == pipe.BWD]
            assert bs == sorted(bs)


# --------------------------------------------------------------------------
# modeled timelines


_MODEL_KW = dict(num_microbatches=8, mb_rows=4, row_layer_time=2e-3,
                 act_bytes_per_mb=5e7, dcn_bytes_per_s=12.5e9)


def test_modeled_capacity_cut_beats_uniform_and_dp_on_skew():
    speeds = (2.0, 1.0)
    t_cap = pipe.modeled_pipeline_step_time(
        pipe.plan_stages(12, speeds), speeds, **_MODEL_KW)
    t_uni = pipe.modeled_pipeline_step_time(
        pipe.uniform_stages(12, 2), speeds, **_MODEL_KW)
    t_dp = pipe.modeled_dp_step_time(
        12, speeds, global_rows=32, row_layer_time=2e-3,
        param_bytes_per_layer=0.5e9, dcn_bytes_per_s=12.5e9)
    assert t_cap < t_uni < t_dp * 1.01
    assert t_cap < t_dp


def test_modeled_1f1b_no_worse_than_gpipe():
    speeds = (2.0, 1.0)
    splan = pipe.plan_stages(12, speeds)
    t_1f1b = pipe.modeled_pipeline_step_time(splan, speeds, **_MODEL_KW)
    t_gpipe = pipe.modeled_pipeline_step_time(splan, speeds,
                                              schedule="gpipe",
                                              **_MODEL_KW)
    assert t_1f1b <= t_gpipe


def test_modeled_uniform_cut_optimal_without_skew():
    """No skew: the uniform cut is the best capacity answer, and the
    planner produces exactly it."""
    speeds = (1.0, 1.0)
    assert pipe.plan_stages(12, speeds).layers_per_stage.tolist() == [6, 6]


def test_modeled_time_rejects_speed_shape_mismatch():
    with pytest.raises(ValueError, match="speeds"):
        pipe.modeled_pipeline_step_time(pipe.uniform_stages(12, 2),
                                        (1.0, 1.0, 1.0), **_MODEL_KW)


# --------------------------------------------------------------------------
# config validation


def test_pipeline_config_validation():
    from repro.launch.steps import validate_train_config
    from repro.models.model import build_model

    cfg = cfgs.smoke_config("olmo-1b")
    mesh = make_mesh((1, 1), ("data", "model"))

    def tcfg(model_cfg, **het_kw):
        return TrainConfig(model=model_cfg, het=HetConfig(
            pipeline_stages=2, accum_steps=2, **het_kw))

    # scanned stack: the per-stage VJP segments need the unrolled form
    scanned = build_model(cfg)
    assert cfg.scan_layers
    with pytest.raises(ValueError, match="scan_layers"):
        validate_train_config(scanned, tcfg(cfg), mesh)

    import dataclasses
    flat_cfg = dataclasses.replace(cfg, scan_layers=False)
    flat = build_model(flat_cfg)
    validate_train_config(flat, tcfg(flat_cfg), mesh)   # supported

    # more stages than layers
    thin_cfg = dataclasses.replace(cfg, scan_layers=False, num_layers=1)
    thin = build_model(thin_cfg)
    with pytest.raises(ValueError, match="pipeline_stages"):
        validate_train_config(thin, tcfg(thin_cfg), mesh)

    # a pipe mesh axis must be sized to pipeline_stages
    pipe_mesh = make_mesh((1, 1, 1), ("pipe", "data", "model"))
    with pytest.raises(ValueError, match="pipe"):
        validate_train_config(flat, tcfg(flat_cfg), pipe_mesh)

    # HetConfig.validate owns the mesh-independent combos
    with pytest.raises(ValueError, match="accum_steps"):
        HetConfig(pipeline_stages=2, accum_steps=1).validate()
    with pytest.raises(ValueError, match="overlap"):
        HetConfig(pipeline_stages=2, accum_steps=2,
                  overlap="buckets", bucket_mb=1.0,
                  grad_reduction="bucketed_allreduce").validate()
    with pytest.raises(ValueError, match="hierarchical"):
        HetConfig(pipeline_stages=2, accum_steps=2,
                  grad_reduction="hierarchical").validate()
    with pytest.raises(ValueError, match="canonical"):
        HetConfig(pipeline_stages=2, accum_steps=2,
                  weighting="canonical").validate()


def test_checkpoint_format_records_stage_plan():
    import dataclasses
    from repro.launch import steps
    from repro.models.model import build_model

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = dataclasses.replace(cfgs.smoke_config("olmo-1b"),
                              scan_layers=False, num_layers=4)
    model = build_model(cfg)
    tcfg = TrainConfig(model=cfg, het=HetConfig(
        pipeline_stages=2, accum_steps=2, capacities=(3.0, 1.0)))
    fmt = steps.checkpoint_format(model, tcfg, mesh)
    assert fmt["pipeline"]["num_layers"] == 4
    assert fmt["pipeline"]["plan"]["rows_per_rank"] == [3, 1]
    back = pipe.stage_from_record(fmt["pipeline"])
    assert back.layers_per_stage.tolist() == [3, 1]

    plain = TrainConfig(model=cfg, het=HetConfig())
    assert steps.checkpoint_format(model, plain, mesh)["pipeline"] \
        is None


# --------------------------------------------------------------------------
# the end-to-end bar: pipelined step == pure DP


@pytest.mark.slow
def test_pipeline_step_matches_pure_dp():
    """stages=2 1F1B over the (pod, data, model) mesh vs stages=1 pure
    DP on the same global batch: fp32/clip=0/allreduce is bit-identical
    (losses AND params, AdamW and LAMB, gpipe too — the schedule is
    not a numeric); the bucketed engine keeps losses bitwise with
    params at fp-rounding level (XLA fuses the attention backward
    differently at any VJP cut)."""
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
        rec = synthetic.make_lm_records(16, 17, cfg.vocab_size, seed=5)
        plan = capacity.plan_capacities(16, [1, 1, 1, 1])
        packed = dummy.pack_global_batch(
            {"inputs": rec["inputs"][:, :16],
             "labels": rec["labels"][:, :16]}, plan)
        batch = {k: jnp.asarray(v) for k, v in packed.items()}

        def run(stages, mode="allreduce", opt="adamw",
                schedule="1f1b"):
            tcfg = TrainConfig(model=cfg, shape=shape,
                het=HetConfig(grad_reduction=mode,
                              bucket_mb=0.05 if mode != "allreduce"
                              else 0.0,
                              accum_steps=4, pipeline_stages=stages,
                              pipeline_schedule=schedule),
                optimizer=OptimizerConfig(name=opt, lr=1e-3,
                                          warmup_steps=2,
                                          grad_clip=0.0))
            with jax.set_mesh(mesh):
                state = steps.init_train_state(m, tcfg, mesh,
                                               jax.random.PRNGKey(0))
                step = steps.build_train_step(m, tcfg, mesh)
                losses = []
                for _ in range(2):
                    state, met = step(state, batch)
                    losses.append(float(met["loss"]))
            return losses, jax.device_get(state)

        def bitwise(s0, s1):
            for a, b in zip(jax.tree.leaves(s0.params),
                            jax.tree.leaves(s1.params)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))

        l0, s0 = run(1)
        l1, s1 = run(2)
        assert l0 == l1, (l0, l1)
        bitwise(s0, s1)

        lg, sg = run(2, schedule="gpipe")
        assert l0 == lg, (l0, lg)
        bitwise(s0, sg)

        l4, s4 = run(1, opt="lamb")
        l5, s5 = run(2, opt="lamb")
        assert l4 == l5, (l4, l5)
        bitwise(s4, s5)

        l2, s2 = run(1, mode="bucketed_allreduce")
        l3, s3 = run(2, mode="bucketed_allreduce")
        assert l2 == l3, (l2, l3)
        for a, b in zip(jax.tree.leaves(s2.params),
                        jax.tree.leaves(s3.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6)
        print("OK")
        """, timeout=1200)
    assert "OK" in out
