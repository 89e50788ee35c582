"""Serving-engine tests: paged-attention parity at ragged depths (GQA
and absorbed-MLA), block allocator / capacity router / scheduler
bookkeeping, and the compile-once property of the jitted decode step."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.launch import serve as serve_mod
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models.kvcache import PagedLayout
from repro.models.model import build_model
from repro.serve import (BlockPool, CapacityRouter, Request, Scheduler,
                         pod_block_pools)
from repro.serve.engine import _trace_count
from repro.serve.scheduler import default_bucket_lens

# one GQA and one absorbed-MLA architecture exercise both paged layouts
PAGED_ARCHS = ["olmo-1b", "deepseek-v2-236b"]


def _model(arch, **over):
    cfg = dataclasses.replace(cfgbase.smoke_config(arch), **over)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    return cfg, model, params


def _disjoint_tables(batch, mb):
    return jnp.asarray([[b * mb + j for j in range(mb)]
                        for b in range(batch)], jnp.int32)


def _ragged_setup(arch, **over):
    """Three sequences at depths 5/9/12 inside one 16-position layout:
    prefill 12 bucket-padded tokens, then one decode step at each
    sequence's own kv_len."""
    cfg, model, params = _model(arch, **over)
    rng = np.random.default_rng(1)
    bs, batch, s_pad = 4, 3, 12
    lens = np.array([5, 9, 12], np.int32)
    layout = PagedLayout(block_size=bs, num_blocks=batch * 4,
                         max_blocks_per_seq=4)     # 16 positions
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, 16)),
                    jnp.int32)
    tables = _disjoint_tables(batch, 4)
    cache = model.init_paged_cache(layout)
    lg_pre, cache = model.prefill_paged(params, x[:, :s_pad],
                                        jnp.asarray(lens), cache, tables)
    nxt = x[np.arange(batch), lens]               # token at each depth
    lg_dec, _ = model.decode_paged(params, nxt, cache, tables,
                                   jnp.asarray(lens))
    # reference: full-context forward of the same tokens, read at each
    # sequence's own position (causal => trailing rows are inert)
    full = model.logits_fn(params, x)
    ref_pre = np.asarray(full)[np.arange(batch), lens - 1]
    ref_dec = np.asarray(full)[np.arange(batch), lens]
    aux = (model, params, x, lens, s_pad, layout,
           np.asarray(lg_pre))
    return np.asarray(lg_pre), np.asarray(lg_dec), ref_pre, ref_dec, aux


def _contiguous_refs(model, params, x, lens, s_pad, layout):
    """The pre-paging serving path at the same tensor shapes as the
    paged one: contiguous cache sized to the paged gather width
    (max_blocks_per_seq * block_size), full-batch prefill over the same
    bucket-padded inputs, then one scalar-position decode call per
    distinct depth (row b read at its own pos — the other rows are
    computed but discarded). Identical shapes everywhere mean identical
    fp32 reduction trees, so the comparison can demand bit-equality."""
    batch = x.shape[0]
    last_logits, cache = model.prefill(params, x[:, :s_pad],
                                       max_len=layout.max_seq_len)
    nxt = jnp.asarray(x[np.arange(batch), lens])
    rows = [np.asarray(model.decode(params, nxt, cache,
                                    jnp.int32(int(lens[b])))[0])[b]
            for b in range(batch)]
    return np.asarray(last_logits), np.stack(rows)


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_ragged_decode_bitwise_fp32(arch):
    """fp32 + dense attention: the paged path (block scatter/gather,
    bucket padding, per-sequence kv_len masks) must be bit-identical to
    the contiguous-cache path over the same tokens at the same shapes —
    any drift means block indexing or the padding masks leak into the
    math. (The full-context forward runs at a different sequence length
    => different reduction trees; it is the TOLERANCE reference below.)"""
    lg_pre, lg_dec, ref_pre, ref_dec, aux = _ragged_setup(
        arch, compute_dtype="float32", attention_impl="dense")
    model, params, x, lens, s_pad, layout, _ = aux
    cont_pre, cont_dec = _contiguous_refs(model, params, x, lens,
                                          s_pad, layout)
    # contiguous prefill only reports the final position: row 2's real
    # length equals the bucket, so its ragged read lands there
    np.testing.assert_array_equal(lg_pre[2], cont_pre[2])
    np.testing.assert_array_equal(lg_dec, cont_dec)
    # and rounding-level agreement with the full-context forward
    assert np.max(np.abs(lg_pre - ref_pre)) < 1e-4
    assert np.max(np.abs(lg_dec - ref_dec)) < 1e-4


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_ragged_decode_tolerance_compute_dtype(arch):
    """Default compute dtype (+ the arch's own attention impl): paged
    and full-context logits agree to rounding, and pick the same next
    token at every ragged depth."""
    lg_pre, lg_dec, ref_pre, ref_dec, _ = _ragged_setup(arch)
    scale = max(1.0, float(np.max(np.abs(ref_dec))))
    assert np.max(np.abs(lg_pre - ref_pre)) < 6e-2 * scale
    assert np.max(np.abs(lg_dec - ref_dec)) < 6e-2 * scale
    np.testing.assert_array_equal(np.argmax(lg_dec, -1),
                                  np.argmax(ref_dec, -1))


def test_paged_layout_validation():
    layout = PagedLayout(block_size=4, num_blocks=8, max_blocks_per_seq=2)
    assert layout.null_block == 8
    assert layout.max_seq_len == 8
    assert layout.blocks_for(1) == 1 and layout.blocks_for(5) == 2
    with pytest.raises(ValueError):
        PagedLayout(block_size=0, num_blocks=8, max_blocks_per_seq=2)
    with pytest.raises(ValueError):
        PagedLayout(block_size=4, num_blocks=0, max_blocks_per_seq=2)


def test_block_pool_alloc_free():
    layout = PagedLayout(block_size=4, num_blocks=6, max_blocks_per_seq=3)
    pool = BlockPool(layout)
    a = pool.alloc(4)
    assert len(set(a)) == 4 and pool.num_free == 2
    with pytest.raises(RuntimeError):
        pool.alloc(3)                       # only 2 left
    pool.free(a[:2])
    assert pool.num_free == 4
    with pytest.raises(RuntimeError):
        pool.free(a[:1])                    # double free
    # pod extents partition the pool disjointly
    pools = pod_block_pools(layout, 2)
    blocks = pools[0].alloc(pools[0].num_blocks) + \
        pools[1].alloc(pools[1].num_blocks)
    assert sorted(blocks) == list(range(6))


def test_capacity_router_limits_and_route():
    r = CapacityRouter(7, [1.0, 0.5, 0.25])
    assert sum(r.limits) == 7
    assert list(r.limits) == sorted(r.limits, reverse=True)
    # empty pods: fastest wins; then fills proportionally
    assert r.route([0, 0, 0]) == 0
    assert r.route([r.limits[0], 0, 0]) == 1
    assert r.route(list(r.limits)) is None  # all full
    with pytest.raises(ValueError):
        CapacityRouter(0, [1.0])
    with pytest.raises(ValueError):
        CapacityRouter(4, [0.0, 0.0])


def _sched(slots=2, num_blocks=8, mb=4, speeds=(1.0,)):
    layout = PagedLayout(block_size=4, num_blocks=num_blocks,
                         max_blocks_per_seq=mb)
    return Scheduler(layout, CapacityRouter(slots, speeds), slots), layout


def test_scheduler_submit_validation():
    sched, layout = _sched()
    with pytest.raises(ValueError):
        sched.submit(Request(0, (), 4))              # empty prompt
    with pytest.raises(ValueError):
        sched.submit(Request(0, (1,), 0))            # no token budget
    with pytest.raises(ValueError):
        sched.submit(Request(0, (1,) * 15, 4))       # > max_seq_len
    assert default_bucket_lens(layout) == (4, 8, 16)


def test_scheduler_fifo_and_slot_reuse():
    sched, _ = _sched(slots=2, num_blocks=8)
    for rid in range(3):
        sched.submit(Request(rid, (1, 2, 3), 2))
    admitted = sched.try_admit()
    assert [s.rid for s in admitted] == [0, 1]       # FIFO, slots=2
    assert sched.try_admit() == []                   # no free slot
    done = admitted[0]
    done.generated = [7, 7]
    sched.finish(done)
    nxt = sched.try_admit()
    assert [s.rid for s in nxt] == [2]
    assert nxt[0].slot == done.slot                  # slot recycled
    assert sched.allocated_blocks() == 2


def test_scheduler_preempts_newest_when_blocks_run_out():
    # 3 blocks total, 2 sequences each holding 1 and growing: when the
    # pool dries up the NEWEST admission is evicted and re-queued at
    # the queue front with its generated tokens folded into the prompt
    sched, _ = _sched(slots=2, num_blocks=3)
    sched.submit(Request(0, (1, 2, 3), 8))
    sched.submit(Request(1, (4, 5, 6), 8))
    s0, s1 = sched.try_admit()
    s0.kv_len, s1.kv_len = 4, 4                      # both need block 2
    s0.generated = [9]
    s1.generated = [8]
    assert sched.ensure_next_block(s0)               # takes the last one
    assert sched.ensure_next_block(s1) is False      # s1 preempts itself
    assert sched.preemptions == 1
    req = sched.waiting[0]
    assert req.rid == 1 and req.prompt == (4, 5, 6, 8)
    assert req.max_new_tokens == 7
    assert sched.active_per_pod == [1]


def test_decode_step_compiles_once():
    """One engine run over mixed lengths + staggered arrivals compiles
    the decode step exactly once (fixed shapes, donated cache)."""
    cfg, model, _ = _model("olmo-1b", compute_dtype="float32",
                           attention_impl="dense")
    mesh = make_mesh((1, 1), ("data", "model"))
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(0))
    layout = PagedLayout(block_size=4, num_blocks=12,
                         max_blocks_per_seq=4)
    reqs = [Request(0, (1, 2, 3), 4, 0.0),
            Request(1, tuple(range(1, 8)), 3, 0.5),
            Request(2, (9, 8), 5, 4.0)]
    with jax.set_mesh(mesh):
        eng = serve_mod.build_engine(model, params, mesh, layout,
                                     slots=2, prefill_batch=2,
                                     pod_speeds=[1.0])
        res = eng.run(reqs)
    assert _trace_count(eng.decode_fn) == 1
    assert {r: len(t) for r, t in res.tokens.items()} == {0: 4, 1: 3,
                                                          2: 5}
    assert res.stats["decode_steps"] > 0
    assert res.stats["block_util_peak"] <= 1.0


# --------------------------------------------------------------------------
# PR 9: pallas decode kernels on the engine hot path
# --------------------------------------------------------------------------


_PR9_REQS = [Request(0, (1, 2, 3), 4, 0.0),
             Request(1, tuple(range(1, 8)), 3, 0.5),
             Request(2, (9, 8), 5, 4.0)]


def _engine_run(impl):
    cfg, model, _ = _model("olmo-1b", compute_dtype="float32",
                           attention_impl=impl)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(0))
    layout = PagedLayout(block_size=4, num_blocks=12,
                         max_blocks_per_seq=4)
    with jax.set_mesh(mesh):
        eng = serve_mod.build_engine(model, params, mesh, layout,
                                     slots=2, prefill_batch=2,
                                     pod_speeds=[1.0])
        res = eng.run(list(_PR9_REQS))
    return eng, res


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_decode_step_counts_its_pool_moves_once_per_build(arch):
    """The decode step is compiled once, ahead of its first call, and
    ``serve.pool_move_bytes`` read from that executable: the layer scan
    carries the pool (GQA and MLA alike), so nothing a layer's slab in
    size is copied, sliced or restacked, and every ``serve.decode`` span
    carries the gauge."""
    from repro import obs

    cfg, model, _ = _model(arch, compute_dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(0))
    layout = PagedLayout(block_size=4, num_blocks=12, max_blocks_per_seq=4)
    obs.reset()
    with jax.set_mesh(mesh):
        eng = serve_mod.build_engine(model, params, mesh, layout, slots=2,
                                     prefill_batch=2, pod_speeds=[1.0])
        res = eng.run(list(_PR9_REQS))
    c = obs.counters()
    assert c["jit.traces.paged_decode_step"] == 1
    assert c["jit.compiles.paged_decode_step"] == 1
    assert _trace_count(eng.decode_fn) == 1
    assert c["serve.pool_move_bytes"] == 0
    moved = [sp.attrs["pool_move_bytes"] for sp in obs.spans()
             if sp.name == "serve.decode"]
    assert moved == [0] * res.stats["decode_steps"]


@pytest.mark.pallas_interpret
def test_engine_pallas_token_identical_to_reference():
    """A full compile-once engine run with attention_impl='pallas'
    (in-kernel block gather, interpret-mode on CPU) emits exactly the
    same tokens as the reference engine on the same trace — the fp32-
    bitwise kernel parity surviving scatter, scheduling and argmax."""
    eng_ref, res_ref = _engine_run("reference")
    eng_pal, res_pal = _engine_run("pallas")
    assert _trace_count(eng_pal.decode_fn) == 1
    assert res_ref.stats["attention_impl"] == "reference"
    assert res_pal.stats["attention_impl"] == "pallas"
    assert res_pal.tokens == res_ref.tokens
    assert res_pal.stats["decode_steps"] == res_ref.stats["decode_steps"]


@pytest.mark.pallas_interpret
def test_engine_pallas_retrace_guard_still_fires():
    """The fixed-shape fail-loud contract survives the kernel swap:
    poking the pallas decode step with a wider slot batch after a clean
    run makes _assert_no_retrace raise."""
    eng, _ = _engine_run("pallas")
    assert _trace_count(eng.decode_fn) == 1
    layout = PagedLayout(block_size=4, num_blocks=12,
                         max_blocks_per_seq=4)
    wide = 3                                  # engine compiled slots=2
    tables = jnp.full((wide, 4), layout.null_block, jnp.int32)
    tables = tables.at[:, 0].set(jnp.arange(wide))
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        cache = eng.init_cache_fn()
        eng.decode_fn(jnp.zeros((wide,), jnp.int32), cache, tables,
                      jnp.zeros((wide,), jnp.int32))
    with pytest.raises(RuntimeError, match="retraced"):
        eng._assert_no_retrace()


def test_serve_batch_spec_warns_once_per_build(caplog, monkeypatch):
    """Regression: the replicated-batch fallback warning fires once at
    step-BUILD time, not once per decode step — 3 decode steps after a
    non-divisible build must add no further warnings."""
    import logging

    cfg, model, _ = _model("olmo-1b", compute_dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(0))
    layout = PagedLayout(block_size=4, num_blocks=12,
                         max_blocks_per_seq=4)
    # pretend the mesh has a DP extent of 2 so slots=3 is non-divisible
    monkeypatch.setattr(steps_mod, "dp_size", lambda m: 2)
    slots = 3
    with caplog.at_level(logging.WARNING, logger="repro.launch.steps"):
        with jax.set_mesh(mesh):
            decode = steps_mod.build_paged_decode_step(model, mesh,
                                                       layout, slots)
            cache = jax.jit(functools.partial(model.init_paged_cache,
                                              layout))()
            tables = jnp.full((slots, 4), layout.null_block, jnp.int32)
            tables = tables.at[:, 0].set(jnp.arange(slots))
            kv_lens = jnp.zeros((slots,), jnp.int32)
            toks = jnp.zeros((slots,), jnp.int32)
            for _ in range(3):
                _, cache = decode(params, toks, cache, tables, kv_lens)
    warns = [r for r in caplog.records
             if "FULLY-REPLICATED" in r.getMessage()]
    assert len(warns) == 1, (
        f"expected exactly one build-time fallback warning, got "
        f"{len(warns)}")
