"""Property-based parity for the paged decode kernels (PR 9 tentpole).

The paged Pallas kernels gather KV blocks through the block table
INSIDE the kernel; the reference path materializes the window in HBM
first (``.at[tables].get(mode="fill", fill_value=0)``). Both kernels
(GQA flash decode and absorbed-MLA decode) are streaming online
softmaxes over block tiles — a different reduction order than the
dense reference — so parity is a tolerance at the compute dtype,
swept across ragged kv_lens / block sizes / head counts / GQA group
sizes.

Pools are layer-stacked, (L, N, ...), as the decode step's layer scan
carries them, with distinct data in every layer; each kernel test runs
at the first, a middle and the last layer, so a kernel that read
another layer's blocks fails.

Everything runs in interpret mode (``pallas_interpret`` marker) on the
CPU backend; tests/test_tpu_compile.py compiles the same kernels for a
described v5e at real widths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.mla_decode import ops as mla_ops

pytestmark = pytest.mark.pallas_interpret


def _ragged_tables(rng, batch, mb, bs, n_pool):
    """Prefix-mapped block tables + ragged effective kv_lens.

    Each sequence maps just enough distinct pool blocks for its depth;
    the rest of its table row is NULL (== n_pool). Depths deliberately
    hit block boundaries (1, bs, s_g) as well as interiors.
    """
    s_g = mb * bs
    kv_lens = np.asarray(
        [int(rng.integers(1, s_g + 1)) for _ in range(batch)], np.int32)
    perm = rng.permutation(n_pool)
    tables = np.full((batch, mb), n_pool, np.int32)
    used = 0
    for i in range(batch):
        nb = -(-int(kv_lens[i]) // bs)
        tables[i, :nb] = perm[used:used + nb]
        used += nb
    return jnp.asarray(tables), jnp.asarray(kv_lens)


LAYERS = 3
# the layer a test attends: first, middle, last
AT_LAYER = pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])


def _per_layer(examples: int) -> int:
    """A property test's examples, shared out over the AT_LAYER cases."""
    return -(-examples // LAYERS)


def _stacked(rng, shape, dtype):
    """A layer-stacked pool (LAYERS, *shape), unit-normal and distinct in
    every layer."""
    return jnp.asarray(rng.standard_normal((LAYERS,) + shape), dtype)


# Absolute tolerance per compute dtype for outputs that are convex
# combinations of unit-normal values: fp32 allows reduction-order
# drift only; bf16 allows one rounding of an O(1) output (2^-8 ulp at
# 1.0, doubled for values up to ~4). A kernel that dropped a block or
# mis-masked a position would miss both by orders of magnitude.
ATOL = {"float32": 2e-5, "bfloat16": 2 ** -6}


@AT_LAYER
@settings(max_examples=_per_layer(25), deadline=None)
@given(bs=st.sampled_from([2, 4, 8]),
       mb=st.integers(min_value=1, max_value=4),
       hkv=st.sampled_from([1, 2, 3]),
       q_per_kv=st.sampled_from([1, 2, 4]),
       d=st.sampled_from([4, 8, 16]),
       dtype=st.sampled_from(sorted(ATOL)),
       lens_seed=st.integers(min_value=0, max_value=2 ** 16))
def test_gqa_paged_pallas_bitwise_vs_reference(pallas_interpret, layer, bs,
                                               mb, hkv, q_per_kv, d, dtype,
                                               lens_seed):
    """GQA paged kernel vs materialize-then-attend, within the compute
    dtype's tolerance (the name predates the streaming kernel, which is
    no longer bitwise)."""
    rng = np.random.default_rng((bs, mb, hkv, q_per_kv, d, lens_seed))
    batch = int(rng.integers(1, 5))
    h = hkv * q_per_kv
    n_pool = batch * mb + 2           # spare blocks stay unmapped
    tables, kv_lens = _ragged_tables(rng, batch, mb, bs, n_pool)
    q = jnp.asarray(rng.standard_normal((batch, 1, h, d)), dtype)
    k_pool = _stacked(rng, (n_pool, bs, hkv, d), dtype)
    v_pool = _stacked(rng, (n_pool, bs, hkv, d), dtype)
    layer = jnp.int32(layer)
    out_ref = attn_ops.flash_decode_paged(
        q, k_pool, v_pool, tables, kv_lens, layer, impl="reference")
    out_pal = attn_ops.flash_decode_paged(
        q, k_pool, v_pool, tables, kv_lens, layer, impl="pallas",
        interpret=pallas_interpret)
    assert out_pal.dtype == out_ref.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(
        np.asarray(out_ref, np.float32), np.asarray(out_pal, np.float32),
        rtol=0, atol=ATOL[dtype],
        err_msg=f"shapes bs={bs} mb={mb} hkv={hkv} qpk={q_per_kv} d={d} "
                f"kv_lens={np.asarray(kv_lens).tolist()}")


@AT_LAYER
@settings(max_examples=_per_layer(20), deadline=None)
@given(bs=st.sampled_from([2, 4, 8]),
       mb=st.integers(min_value=1, max_value=4),
       h=st.sampled_from([2, 4, 8]),
       r=st.sampled_from([8, 16]),
       dr=st.sampled_from([4, 8]),
       lens_seed=st.integers(min_value=0, max_value=2 ** 16))
def test_mla_paged_pallas_tolerance_vs_reference(pallas_interpret, layer,
                                                 bs, mb, h, r, dr,
                                                 lens_seed):
    rng = np.random.default_rng((bs, mb, h, r, dr, lens_seed))
    batch = int(rng.integers(1, 4))
    n_pool = batch * mb + 2
    tables, kv_lens = _ragged_tables(rng, batch, mb, bs, n_pool)
    q_abs = jnp.asarray(rng.standard_normal((batch, h, r)), jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((batch, h, dr)), jnp.float32)
    ckv = _stacked(rng, (n_pool, bs, r), jnp.float32)
    kr = _stacked(rng, (n_pool, bs, dr), jnp.float32)
    scale = (r + dr) ** -0.5
    layer = jnp.int32(layer)
    out_ref = mla_ops.mla_decode_paged_attention(
        q_abs, q_r, ckv, kr, tables, kv_lens, layer, scale,
        impl="reference")
    out_pal = mla_ops.mla_decode_paged_attention(
        q_abs, q_r, ckv, kr, tables, kv_lens, layer, scale, impl="pallas",
        interpret=pallas_interpret)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_pal),
                               atol=2e-5)


@AT_LAYER
def test_gqa_paged_null_sentinel_fully_masked(pallas_interpret, layer):
    """An inactive slot (all-NULL table, kv_len 1) must match the
    reference's zero-fill gather — the clamped DMA source block holds
    real data the kernel is required to zero out."""
    rng = np.random.default_rng(7)
    bs, mb, hkv, d, n_pool = 4, 3, 2, 8, 6
    k_pool = _stacked(rng, (n_pool, bs, hkv, d), jnp.float32)
    v_pool = _stacked(rng, (n_pool, bs, hkv, d), jnp.float32)
    layer = jnp.int32(layer)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, d)), jnp.float32)
    tables = jnp.asarray(
        [[0, 1, n_pool],              # active: 2 mapped blocks
         [n_pool, n_pool, n_pool]],   # inactive slot: all NULL
        jnp.int32)
    kv_lens = jnp.asarray([2 * bs, 1], jnp.int32)
    out_ref = attn_ops.flash_decode_paged(
        q, k_pool, v_pool, tables, kv_lens, layer, impl="reference")
    out_pal = attn_ops.flash_decode_paged(
        q, k_pool, v_pool, tables, kv_lens, layer, impl="pallas",
        interpret=pallas_interpret)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_pal),
                               rtol=0, atol=ATOL["float32"])


@pytest.mark.parametrize("bs,mb,dtype,layer,d", [
    (8, 5, "float32", 0, 8), (2, 9, "float32", 1, 8),
    (16, 11, "float32", 2, 8), (32, 9, "bfloat16", 1, 8),
    (4, 16, "bfloat16", 0, 8), (16, 11, "float32", 1, 128),
    (8, 5, "bfloat16", 2, 128)])
def test_gqa_paged_walk_stops_at_the_length(pallas_interpret, bs, mb, dtype,
                                            layer, d):
    """Nothing past a sequence's length is read: every pool block that no
    sequence maps below its length holds NaN, one slot keeps stale
    mapped entries past its length, and the output is finite and equal
    to the reference on a copy of the pool with those blocks zeroed.
    Every other layer of the pool is NaN throughout, so reading any
    block of another layer shows too: a head dim under 128 is widened
    with the layer's slab taken out of the pool first, one of 128 is
    read by the kernel straight out of the whole pool, as at phi4-mini's
    widths. With more than 128 // bs table entries the walk takes
    several chunks, the last one partial."""
    rng = np.random.default_rng((bs, mb))
    hkv, q_per_kv = 2, 3
    h, s_g = hkv * q_per_kv, mb * bs
    # ragged lengths well below the table's width, on and off block edges
    lens = np.asarray([1, bs, bs + 1, max(2, s_g * 2 // 5), 1], np.int32)
    batch = len(lens)
    n_pool = batch * mb + 3
    perm = rng.permutation(n_pool)
    tables = np.full((batch, mb), n_pool, np.int32)
    used = 0
    for i in range(batch - 1):          # the last slot is inactive: NULL
        nb = -(-int(lens[i]) // bs)
        tables[i, :nb] = perm[used:used + nb]
        used += nb
    live = perm[:used]
    poisoned = perm[used:]
    nb3 = -(-int(lens[3]) // bs)
    tables[3, nb3] = poisoned[0]        # stale entries, mapped past the length
    tables[3, mb - 1] = poisoned[1]
    tables[0, 1] = poisoned[2]
    q = jnp.asarray(rng.standard_normal((batch, 1, h, d)), dtype)
    clean_k = rng.standard_normal((n_pool, bs, hkv, d))
    clean_v = rng.standard_normal((n_pool, bs, hkv, d))
    clean_k[poisoned] = 0.0
    clean_v[poisoned] = 0.0
    k_pool, v_pool = clean_k.copy(), clean_v.copy()
    k_pool[poisoned] = np.nan
    v_pool[poisoned] = np.nan
    assert np.isfinite(k_pool[live]).all()
    tables, kv_lens = jnp.asarray(tables), jnp.asarray(lens)
    def stack(pool):
        """The pool as layer ``layer`` of LAYERS, every other layer NaN."""
        full = np.full((LAYERS,) + pool.shape, np.nan)
        full[layer] = pool
        return jnp.asarray(full, dtype)

    out_pal = attn_ops.flash_decode_paged(
        q, stack(k_pool), stack(v_pool), tables, kv_lens, jnp.int32(layer),
        impl="pallas", interpret=pallas_interpret)
    out_ref = attn_ops.flash_decode_paged(
        q, stack(clean_k), stack(clean_v), tables, kv_lens,
        jnp.int32(layer), impl="reference")
    out_pal = np.asarray(out_pal, np.float32)
    assert np.isfinite(out_pal).all()
    np.testing.assert_allclose(np.asarray(out_ref, np.float32), out_pal,
                               rtol=0, atol=ATOL[dtype])


def test_model_level_paged_decode_bitwise_fp32(pallas_interpret):
    """Full-model parity: decode_paged logits with attention_impl=
    'pallas' match the reference engine path in fp32 within tolerance
    (GQA and MLA alike), through scatter + attention + unembed."""
    from repro.configs import base as cfgbase
    from repro.models import kvcache as kvc
    from repro.models.model import build_model

    for arch in ("olmo-1b", "deepseek-v2-236b"):
        cfg = dataclasses.replace(
            cfgbase.smoke_config(arch), param_dtype="float32",
            compute_dtype="float32", remat="none")
        model_r = build_model(cfg)
        model_p = build_model(
            dataclasses.replace(cfg, attention_impl="pallas"))
        layout = kvc.PagedLayout(block_size=4, num_blocks=24,
                                 max_blocks_per_seq=4)
        params = jax.jit(model_r.init_params)(jax.random.PRNGKey(0))
        kv_lens = jnp.asarray([5, 9, 12], jnp.int32)
        tables_np = np.full((3, 4), layout.null_block, np.int32)
        blk = 0
        for i in range(3):
            nb = layout.blocks_for(int(kv_lens[i]) + 1)
            tables_np[i, :nb] = np.arange(blk, blk + nb)
            blk += nb
        tables = jnp.asarray(tables_np)
        key = jax.random.PRNGKey(1)
        cache_r, cache_p = {}, {}
        for name, leaf in model_r.init_paged_cache(layout).items():
            key, k2 = jax.random.split(key)
            content = jax.random.normal(k2, leaf.shape, leaf.dtype)
            cache_r[name], cache_p[name] = content, content
        toks = jnp.asarray([3, 1, 4], jnp.int32)
        lr, _ = model_r.decode_paged(params, toks, cache_r, tables,
                                     kv_lens)
        lp, _ = model_p.decode_paged(params, toks, cache_p, tables,
                                     kv_lens)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lp),
                                   atol=1e-4, err_msg=arch)


def test_unknown_impl_raises():
    z4 = jnp.zeros((1, 1, 2, 4))
    pool = jnp.zeros((1, 2, 2, 2, 4))
    tbl = jnp.zeros((1, 1), jnp.int32)
    lens = jnp.ones((1,), jnp.int32)
    layer = jnp.int32(0)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attn_ops.flash_decode_paged(z4, pool, pool, tbl, lens, layer,
                                    impl="nope")
    with pytest.raises(ValueError, match="unknown mla decode impl"):
        mla_ops.mla_decode_paged_attention(
            jnp.zeros((1, 2, 8)), jnp.zeros((1, 2, 4)),
            jnp.zeros((1, 2, 2, 8)), jnp.zeros((1, 2, 2, 4)), tbl, lens,
            layer, 0.1, impl="nope")
