"""Core modules: compression/error feedback, accumulation, straggler,
elastic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import accumulate, capacity, compression, elastic, straggler


# --------------------------------------------------------------------------
# compression + error feedback
# --------------------------------------------------------------------------


def test_error_feedback_accumulates_what_quantization_loses():
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (512,)) * 2}
    err = compression.init_error_state(g)
    (q, s), err2 = compression.compress_tree(g, err)
    deq = compression.decompress_tree(q, s, g)
    # error state == exactly the quantization residual
    np.testing.assert_allclose(
        np.asarray(err2["w"]), np.asarray(g["w"] - deq["w"]), atol=1e-6)


def test_error_feedback_converges_sgd():
    """Compressed-SGD with error feedback tracks exact SGD on a convex
    problem; without it the bias is visibly worse."""
    target = jax.random.normal(jax.random.PRNGKey(1), (256,))

    def run(error_feedback):
        x = jnp.zeros((256,))
        err = jnp.zeros((256,))
        for i in range(150):
            g = x - target
            corrected = g + (err if error_feedback else 0.0)
            from repro.kernels.quantize import ref as q_ref
            q, s = q_ref.quantize_int8(corrected * 64, block_size=256)
            deq = q_ref.dequantize_int8(q, s, corrected.shape, 256) / 64
            if error_feedback:
                err = corrected - deq
            x = x - 0.1 * deq
        return float(jnp.linalg.norm(x - target))

    assert run(True) < 1e-2
    assert run(True) <= run(False) + 1e-6


def test_compression_ratio():
    g = {"a": jnp.zeros((1024, 1024))}
    r = compression.compression_ratio(g, block_size=256)
    assert 0.25 < r < 0.27          # int8 + fp32 scale per 256 block


# --------------------------------------------------------------------------
# accumulation scan core
# --------------------------------------------------------------------------


def test_split_microbatches_error_cases():
    batch = {"x": jnp.zeros((12, 4))}
    # 12 rows: accum=5 never divides
    with pytest.raises(ValueError, match="not divisible"):
        accumulate.split_microbatches(batch, accum_steps=5)
    # divisible by accum alone but not by accum x ranks
    with pytest.raises(ValueError, match="not divisible"):
        accumulate.split_microbatches(batch, accum_steps=4, num_ranks=5)
    # valid split preserves shape bookkeeping
    mbs = accumulate.split_microbatches(batch, accum_steps=3, num_ranks=2)
    assert mbs["x"].shape == (3, 4, 4)


def test_split_microbatches_rank_locality():
    """Every microbatch must take an equal slice of EVERY rank's rows."""
    rows = jnp.arange(8)[:, None] * jnp.ones((1, 2))
    mbs = accumulate.split_microbatches({"x": rows}, accum_steps=2,
                                        num_ranks=2)
    # rank 0 owns rows 0-3, rank 1 rows 4-7; microbatch 0 must hold the
    # first half of each rank's buffer
    np.testing.assert_array_equal(
        np.asarray(mbs["x"][0, :, 0]), [0, 1, 4, 5])
    np.testing.assert_array_equal(
        np.asarray(mbs["x"][1, :, 0]), [2, 3, 6, 7])


def test_scan_accumulate_matches_direct_sum():
    """The shared scan core returns unscaled sums identical to a loop."""
    params = {"w": jnp.array([1.0, -2.0, 0.5])}
    mbs = {"x": jnp.arange(12.0).reshape(3, 4)}

    def obj(p, mb):
        o = (p["w"].sum() * mb["x"]).sum()
        return o, jnp.float32(mb["x"].size)

    grad_fn = jax.value_and_grad(obj, has_aux=True)
    g, o, w = accumulate.scan_accumulate(grad_fn, params, mbs)
    assert float(w) == 12.0
    ref_o = sum(float(obj(params, {"x": mbs["x"][i]})[0]) for i in range(3))
    assert abs(float(o) - ref_o) < 1e-5
    np.testing.assert_allclose(np.asarray(g["w"]),
                               np.full((3,), float(mbs["x"].sum())),
                               rtol=1e-6)


def test_scan_accumulate_carry_dtype_policy():
    params = {"a": jnp.zeros((2,), jnp.bfloat16),
              "b": jnp.zeros((2,), jnp.float32)}
    mbs = {"x": jnp.ones((2, 2))}

    def obj(p, mb):
        o = ((p["a"].astype(jnp.float32) + p["b"]) * mb["x"]).sum()
        return o, jnp.float32(1.0)

    grad_fn = jax.value_and_grad(obj, has_aux=True)

    def carry_dtype(p):
        return p.dtype if p.dtype == jnp.bfloat16 else jnp.float32

    g, _, _ = accumulate.scan_accumulate(grad_fn, params, mbs,
                                         carry_dtype=carry_dtype)
    assert g["a"].dtype == jnp.bfloat16
    assert g["b"].dtype == jnp.float32


# --------------------------------------------------------------------------
# straggler monitor
# --------------------------------------------------------------------------


def test_straggler_shifts_load_to_fast_ranks():
    mon = straggler.StragglerMonitor(num_ranks=3, replan_interval=1)
    plan = capacity.homogeneous_plan(30, 3, headroom=1.5)
    for _ in range(5):
        mon.observe([1.0, 2.0, 4.0])
    new = mon.replan(plan)
    assert new.rows_per_rank[0] > new.rows_per_rank[1] > \
        new.rows_per_rank[2]
    assert new.rows_per_rank.sum() == 30


def test_dead_rank_detection_and_escalation():
    mon = straggler.StragglerMonitor(num_ranks=2, replan_interval=1,
                                     dead_timeout_steps=2)
    plan = capacity.homogeneous_plan(8, 2)        # no headroom
    mon.observe([1.0, None])
    assert len(mon.dead_ranks()) == 0
    mon.observe([1.0, None])
    assert list(mon.dead_ranks()) == [1]
    with pytest.raises(straggler.RemeshRequired):
        mon.replan(plan)
    # with headroom the same failure is absorbed without a remesh
    plan_h = capacity.homogeneous_plan(8, 2, headroom=2.0)
    new = mon.replan(plan_h)
    assert new.rows_per_rank.tolist() == [8, 0]


def test_immediate_replan_on_newly_dead_rank():
    """A rank dying right after a window boundary must trigger a replan
    NOW, not ``replan_interval`` steps later — and once handled, the
    same dead rank must not keep re-triggering every step."""
    mon = straggler.StragglerMonitor(num_ranks=3, replan_interval=100,
                                     dead_timeout_steps=2)
    plan = capacity.homogeneous_plan(6, 3, headroom=2.0)
    mon.observe([1.0, 1.0, 1.0])
    assert not mon.should_replan()
    mon.observe([1.0, 1.0, None])
    assert not mon.should_replan()        # one miss is not dead yet
    mon.observe([1.0, 1.0, None])
    assert mon.should_replan()            # dead: immediate, mid-window
    new = mon.replan(plan)
    assert new.rows_per_rank[2] == 0
    # handled: the still-dead rank must not re-fire off-window
    mon.observe([1.0, 1.0, None])
    assert not mon.should_replan()
    # ... but a SECOND death re-triggers immediately
    mon.observe([1.0, None, None])
    mon.observe([1.0, None, None])
    assert mon.should_replan()
    assert sorted(mon.dead_ranks().tolist()) == [1, 2]


def test_remesh_required_escalation_chains_planner_error():
    """The RemeshRequired raised when survivors cannot fit the global
    batch carries the planner's ValueError as its cause."""
    mon = straggler.StragglerMonitor(num_ranks=2, replan_interval=1,
                                     dead_timeout_steps=1)
    plan = capacity.homogeneous_plan(8, 2)        # buffer 4, no headroom
    mon.observe([1.0, None])                      # rank 1 dead instantly
    with pytest.raises(straggler.RemeshRequired) as ei:
        mon.replan(plan)
    assert isinstance(ei.value.__cause__, ValueError)


def test_monitor_recreated_after_remesh_matches_new_mesh():
    """Regression for the re-mesh handoff: the old monitor rejects the
    new mesh's step-time width loudly, and a monitor/plan rebuilt from
    the RemeshDecision line up with the surviving topology."""
    topo = elastic.MeshTopology(pods=2, data_per_pod=2, model=1)
    d = elastic.plan_remesh(topo, alive_pods=[0], global_rows=8)
    assert d.restart_required
    assert len(d.plan.rows_per_rank) == d.topology.dp_size == 2

    old = straggler.StragglerMonitor(num_ranks=topo.dp_size)
    with pytest.raises(ValueError, match="re-mesh"):
        old.observe([1.0] * d.topology.dp_size)   # stale width: loud

    fresh = straggler.StragglerMonitor(num_ranks=d.topology.dp_size,
                                       replan_interval=1)
    fresh.observe([1.0, 2.0])
    new = fresh.replan(d.plan)
    assert len(new.rows_per_rank) == d.topology.dp_size
    assert new.rows_per_rank.sum() == 8


@given(times=st.lists(st.floats(min_value=0.1, max_value=10.0),
                      min_size=2, max_size=6))
@settings(max_examples=50, deadline=None)
def test_replan_conserves_global_batch(times):
    n = len(times)
    mon = straggler.StragglerMonitor(num_ranks=n, replan_interval=1)
    plan = capacity.homogeneous_plan(4 * n, n, headroom=4.0)
    for _ in range(3):
        mon.observe(times)
    new = mon.replan(plan)
    assert new.rows_per_rank.sum() == 4 * n
    assert new.buffer_rows == plan.buffer_rows    # no shape change


def test_replan_from_step_times_all_dead_but_one():
    """inf is the sanctioned dead-rank marker: with every rank but one
    dead, the survivor inherits the whole global batch."""
    plan = capacity.homogeneous_plan(12, 3, headroom=4.0)
    new = capacity.replan_from_step_times(
        plan, np.array([np.inf, 2.0, np.inf]))
    assert new.rows_per_rank.tolist() == [0, 12, 0]
    assert new.global_rows == plan.global_rows
    # all dead is unplannable, not silently zero-rowed
    with pytest.raises(ValueError, match="all ranks dead"):
        capacity.replan_from_step_times(
            plan, np.array([np.inf, np.inf, np.inf]))


def test_replan_from_step_times_rejects_garbage_measurements():
    """A zero/negative/NaN step time is a broken monitor, not a fast
    rank — it must raise loudly NAMING the offending ranks, never
    silently starve a healthy one."""
    plan = capacity.homogeneous_plan(12, 3)
    for bad, offenders in (([1.0, 0.0, 2.0], [1]),
                           ([-0.5, 1.0, 2.0], [0]),
                           ([1.0, np.nan, -1.0], [1, 2])):
        with pytest.raises(ValueError, match="must be positive") as ei:
            capacity.replan_from_step_times(plan, np.asarray(bad))
        for r in offenders:
            assert f"{offenders}" in str(ei.value)
    # shape mismatch is its own loud error
    with pytest.raises(ValueError, match="shape"):
        capacity.replan_from_step_times(plan, np.ones(4))


def test_replan_after_plan_record_roundtrip():
    """plan -> plan_record -> plan_from_record is bit-faithful and the
    round-tripped plan replans identically to the original (the
    checkpoint-resume path feeds replan exactly this way)."""
    import json
    plan = capacity.plan_capacities(30, [4.0, 2.0, 1.0], headroom=1.5)
    back = capacity.plan_from_record(
        json.loads(json.dumps(capacity.plan_record(plan))))
    np.testing.assert_array_equal(back.rows_per_rank,
                                  plan.rows_per_rank)
    np.testing.assert_array_equal(back.capacities, plan.capacities)
    assert (back.buffer_rows, back.global_rows) == \
        (plan.buffer_rows, plan.global_rows)
    ema = np.array([1.0, 3.0, np.inf])
    a = capacity.replan_from_step_times(plan, ema)
    b = capacity.replan_from_step_times(back, ema)
    np.testing.assert_array_equal(a.rows_per_rank, b.rows_per_rank)
    assert a.rows_per_rank[2] == 0                # dead rank drained


# --------------------------------------------------------------------------
# elastic re-mesh
# --------------------------------------------------------------------------


def test_remesh_noop_when_all_alive():
    topo = elastic.MeshTopology(pods=2, data_per_pod=4, model=2)
    d = elastic.plan_remesh(topo, alive_pods=[0, 1], global_rows=64)
    assert not d.restart_required
    assert d.plan.global_rows == 64


def test_remesh_on_pod_loss_keeps_global_batch():
    topo = elastic.MeshTopology(pods=2, data_per_pod=4, model=2)
    d = elastic.plan_remesh(topo, alive_pods=[1], global_rows=64)
    assert d.restart_required
    assert d.topology.mesh_shape() == (4, 2)
    assert d.plan.global_rows == 64               # exact resume invariant
    assert d.plan.rows_per_rank.sum() == 64
    assert elastic.validate_resume_equivalence(d.plan, d.plan)


def test_remesh_heterogeneous_pod_capacities():
    topo = elastic.MeshTopology(pods=3, data_per_pod=2, model=1)
    d = elastic.plan_remesh(topo, alive_pods=[0, 2], global_rows=30,
                            capacities_per_pod=[2.0, 1.0, 1.0])
    assert d.restart_required
    # surviving pods 0 (cap 2) and 2 (cap 1): pod 0 ranks get ~2x rows
    rows = d.plan.rows_per_rank
    assert rows[:2].sum() > rows[2:].sum()
    assert rows.sum() == 30
