"""Pallas TPU kernel: absorbed-MLA flash decode (one HBM pass).

§Perf pair 3's conclusion realized at kernel level: the XLA dense decode
reads the latent cache TWICE (score matmul + value matmul) and round-
trips a (B, H, S) probability matrix through HBM; a host-level chunk
loop can't fix it because the cache's S dim is sharded (it breaks the
auto split-K — measured +60% ICI). Inside a kernel the fix is natural:

  grid = (batch, S_chunks) with the chunk axis sequential; each (chunk,
  r) latent tile is loaded into VMEM ONCE and used for BOTH the score
  contraction and the weighted value accumulation; the fp32 online-
  softmax state (acc (H, r), m, l) lives in scratch across chunks.

HBM traffic per token-step: |cache| instead of 2|cache| + |probs|
(~2.2x less at 32k context). On a sequence-sharded cache the kernel runs
per shard under shard_map with an (m, l, acc) cross-shard combine — the
same split-K math the dense path gets from XLA, minus the double read.

Validated in interpret mode against ref.mla_decode_dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qa_ref, qr_ref, ckv_ref, kr_ref, len_ref, out_ref,
            acc_ref, m_ref, l_ref, *, scale, chunk, num_chunks,
            heads, rank):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qa = qa_ref[0]                                 # (H, r)
    qr = qr_ref[0]                                 # (H, Dr)
    ckv = ckv_ref[0]                               # (chunk, r) — ONE load
    kr = kr_ref[0]                                 # (chunk, Dr)
    kv_len = len_ref[0, 0]

    s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) +
         jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)) * scale
    kpos = ci * chunk + jax.lax.broadcasted_iota(
        jnp.int32, (heads, chunk), 1)
    s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev = m_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr[:, None] + \
        jnp.sum(p, axis=-1, keepdims=True)
    # value accumulation REUSES the VMEM-resident ckv tile
    acc_ref[...] = (acc_ref[...] * corr[:, None] +
                    jax.lax.dot_general(
                        p.astype(ckv.dtype), ckv,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new[:, None]

    @pl.when(ci == num_chunks - 1)
    def _finish():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def mla_decode_pallas(q_abs, q_r, ckv, kr, kv_len, scale,
                      *, chunk: int = 512, interpret: bool = False):
    b, h, r = q_abs.shape
    dr = q_r.shape[-1]
    s = ckv.shape[1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        kr = jnp.pad(kr, ((0, 0), (0, pad), (0, 0)))
    n_chunks = ckv.shape[1] // chunk

    kernel = functools.partial(_kernel, scale=float(scale), chunk=chunk,
                               num_chunks=n_chunks, heads=h, rank=r)
    out = pl.pallas_call(
        kernel,
        grid=(b, n_chunks),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, h, dr), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, chunk, r), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, dr), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1), lambda bi, ci: (bi, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda bi, ci: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((h, r), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q_abs, q_r, ckv, kr, kv_len.reshape(b, 1).astype(jnp.int32))
    return out


# --------------------------------------------------------------------------
# paged variant: block-table gather inside the kernel (serving hot path)
# --------------------------------------------------------------------------


def _paged_kernel(tables, lens, layer, qa_ref, qr_ref, ckv_ref, kr_ref,
                  out_ref, acc_ref, m_ref, l_ref, *, scale, block_size,
                  max_blocks, null_block, heads):
    """Grid (B, MB); j sequential. The chunk axis of the contiguous
    kernel becomes the sequence's logical block axis: each step's
    (bs, r) latent tile is DMA'd straight from the pool block of layer
    ``layer[0]`` named by the block table (scalar-prefetch index_map) —
    NULL blocks arrive clamped and are zeroed, then fully masked by
    kv_len. The fp32 online-softmax state persists in scratch; the
    VMEM-resident ckv tile is reused for both the score and the value
    matmul, preserving the one-HBM-pass property on the paged pool.
    """
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    is_null = tables[bi, j] == null_block
    qa = qa_ref[0]                                 # (H, r)
    qr = qr_ref[0]                                 # (H, Dr)
    ckv = jnp.where(is_null, 0, ckv_ref[0])        # (bs, r) — ONE load
    kr = jnp.where(is_null, 0, kr_ref[0])          # (bs, Dr)

    s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) +
         jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)) * scale
    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (heads, block_size), 1)
    s = jnp.where(kpos < lens[bi], s, NEG_INF)

    m_prev = m_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr[:, None] + \
        jnp.sum(p, axis=-1, keepdims=True)
    # value accumulation REUSES the VMEM-resident ckv tile
    acc_ref[...] = (acc_ref[...] * corr[:, None] +
                    jax.lax.dot_general(
                        p.astype(ckv.dtype), ckv,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new[:, None]

    @pl.when(j == max_blocks - 1)
    def _finish():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def mla_decode_paged_pallas(q_abs, q_r, ckv_pool, kr_pool, block_tables,
                            kv_lens, layer, scale, *,
                            interpret: bool = False):
    """Absorbed-MLA decode over layer ``layer`` of a layer-stacked paged
    latent pool, gather in-kernel.

    q_abs (B, H, r); q_r (B, H, Dr); ckv_pool (L, N, bs, r); kr_pool
    (L, N, bs, Dr); block_tables (B, MB) int32 with NULL == N; kv_lens
    (B,) int32 EFFECTIVE lengths (positions >= kv_lens[i] masked); layer
    () int32, scalar-prefetched with the tables and lengths, so the
    index maps read the layer's blocks out of the whole pool. Returns
    (B, H, r) fp32 attention output in latent space, within compute-
    dtype tolerance of the materialize-then-attend reference.
    """
    b, h, r = q_abs.shape
    dr = q_r.shape[-1]
    _, n_pool, bs, _ = ckv_pool.shape
    mb = block_tables.shape[1]

    def pool_block(bi, j, tbl, lens, lyr):
        return lyr[0], jnp.minimum(tbl[bi, j], n_pool - 1), 0, 0

    kernel = functools.partial(
        _paged_kernel, scale=float(scale), block_size=bs, max_blocks=mb,
        null_block=n_pool, heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda bi, j, tbl, lens, lyr: (bi, 0, 0)),
            pl.BlockSpec((1, h, dr),
                         lambda bi, j, tbl, lens, lyr: (bi, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, bs, r), pool_block),
            pl.BlockSpec((pl.squeezed, 1, bs, dr), pool_block),
        ],
        out_specs=pl.BlockSpec((1, h, r),
                               lambda bi, j, tbl, lens, lyr: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, r), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q_abs, q_r, ckv_pool,
      kr_pool)
