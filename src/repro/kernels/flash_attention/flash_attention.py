"""Pallas TPU flash-attention forward kernel.

Design (TPU-native, not a CUDA port):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
    innermost, *sequential* ("arbitrary") grid axis, so the fp32 running
    softmax state (acc, m, l) lives in VMEM scratch and persists across kv
    iterations — the TPU grid is executed in order, which replaces the
    CUDA notion of a per-CTA loop over KV tiles.
  * BlockSpec tiles: q (1, 1, block_q, D) and k/v (1, 1, block_kv, D) are
    MXU-aligned (block sizes multiples of 128 where the head dim allows);
    GQA is expressed in the k/v index_map (kv head = q head // q_per_kv)
    so no repeated-KV tensor is ever materialized in HBM.
  * Causal masking is positional (q_offset supports decode/chunked
    prefill); fully-masked kv blocks are skipped via ``pl.when`` so they
    cost a grid tick but no FLOPs.

Validated in interpret mode against ref.mha_dense (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, block_q: int, block_kv: int, causal: bool,
               q_offset: int, seq_kv: int, num_kv_blocks: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block is live unless causal pruning removes it entirely:
    # smallest q position in this block >= largest kv position needed.
    q_start = qb * block_q + q_offset
    kv_start = kb * block_kv
    live = (not causal) or True
    run = jnp.logical_or(jnp.logical_not(jnp.bool_(causal)),
                         q_start + block_q - 1 >= kv_start)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = (q_start +
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0))
        kpos = (kv_start +
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1))
        mask = kpos < seq_kv
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...][:, 0]                          # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...] * corr[:, None] +
                      jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[...] = (acc_ref[...] * corr[:, None] +
                        jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new[:, None]

    @pl.when(kb == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,                      # (B, Sq, H, D)
    k: jnp.ndarray,                      # (B, Skv, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int = 0,
    softmax_scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    q_per_kv = h // hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    block_q = min(block_q, max(sq, 8))
    block_kv = min(block_kv, max(skv, 8))
    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    # (B, S, H, D) -> (B, H, S, D) so the tile is a clean (block, D) matrix
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    n_q = qt.shape[2] // block_q
    n_kv = kt.shape[2] // block_kv

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, q_offset=q_offset, seq_kv=skv, num_kv_blocks=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bi, hi, qi, ki: (bi, hi // q_per_kv, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bi, hi, qi, ki: (bi, hi // q_per_kv, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :sq, :]
    return jnp.moveaxis(out, 1, 2)


# --------------------------------------------------------------------------
# paged flash decode (serving hot path: block-table gather INSIDE the kernel)
# --------------------------------------------------------------------------


def _paged_decode_kernel(tables, lens, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float,
                         block_size: int, max_blocks: int, null_block: int,
                         kv_heads: int, q_per_kv: int):
    """Grid (B, MB); j sequential. Step j DMAs sequence bi's j-th mapped
    KV block straight from the pool (the block-table lookup happens in
    the BlockSpec index_map via scalar prefetch — no materialized window
    in HBM) and folds it into an fp32 online softmax whose state
    (acc (H, D), m, l) persists in VMEM scratch across blocks.

    Every matmul is 2-D: per kv head g, the (q_per_kv, D) query group
    against the block's (bs, D) keys, then the (q_per_kv, bs)
    probabilities against its values. Mosaic lowers at most one batch
    dim per in-kernel matmul, and the scratch stays O(H * D) whatever
    the window, so a 2048-token window fits scoped VMEM.
    """
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # NULL (unmapped) blocks were clamped to a real pool slot by the
    # index_map; zero the tile as the reference's `mode="fill"` gather
    # does (its positions are masked by kv_len anyway)
    is_null = tables[bi, j] == null_block
    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (q_per_kv, block_size), 1)
    valid = kpos < lens[bi]
    for g in range(kv_heads):
        rows = pl.ds(g * q_per_kv, q_per_kv)
        q = q_ref[0, 0, rows, :].astype(jnp.float32) * scale
        k = jnp.where(is_null, 0.0, k_ref[0, :, g, :].astype(jnp.float32))
        v = jnp.where(is_null, 0.0, v_ref[0, :, g, :].astype(jnp.float32))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = (l_ref[rows, :] * corr +
                          jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[rows, :] = (acc_ref[rows, :] * corr +
                            jax.lax.dot_general(
                                p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
        m_ref[rows, :] = m_new

    @pl.when(j == max_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_paged_pallas(
    q: jnp.ndarray,                      # (B, 1, H, D)
    k_pool: jnp.ndarray,                 # (N, bs, Hkv, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,           # (B, MB) int32, NULL == N
    kv_lens: jnp.ndarray,                # (B,) int32 EFFECTIVE lengths
    *,
    softmax_scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-query GQA decode over a paged KV pool, gather in-kernel.

    ``kv_lens`` are the effective context lengths (positions
    ``>= kv_lens[i]`` are masked); the new token's K/V must already be
    scattered into the pool. Returns (B, 1, H, D) in q's dtype, within
    compute-dtype tolerance of gathering the window with
    ``mode="fill"`` and running ``ref.mha_dense(causal=False,
    kv_len=kv_lens)`` (a streaming softmax sums in a different order).

    HBM traffic per step is ONE pass over the mapped window (the
    index_map-driven DMA), vs the materialized path's gather-read +
    window-write + attend-read — see benchmarks/serve_bench.py's decode
    roofline for the byte model.
    """
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"paged decode expects a single query, got {sq}")
    n_pool, bs, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=bs, max_blocks=mb,
        null_block=n_pool, kv_heads=hkv, q_per_kv=h // hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, 1, h, d),
                         lambda bi, j, tbl, lens: (bi, 0, 0, 0)),
            # block-table indirection lives HERE: the DMA source block is
            # tbl[bi, j] (clamped for NULL; the kernel zeroes those tiles)
            pl.BlockSpec((1, bs, hkv, d),
                         lambda bi, j, tbl, lens: (
                             jnp.minimum(tbl[bi, j], n_pool - 1), 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, d),
                         lambda bi, j, tbl, lens: (
                             jnp.minimum(tbl[bi, j], n_pool - 1), 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, d),
                               lambda bi, j, tbl, lens: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),     # acc
            pltpu.VMEM((h, 1), jnp.float32),     # running max
            pltpu.VMEM((h, 1), jnp.float32),     # running sum
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q, k_pool, v_pool)
