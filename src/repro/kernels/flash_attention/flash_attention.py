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

CHUNK_TOKENS = 128   # tokens folded per unit of work: one MXU tile of rows
LANES = 128          # a vreg's lanes; the head dim is padded to a multiple


def _paged_decode_kernel(tables, lens, layer, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, slot_ref, *, scale: float,
                         block_size: int, max_blocks: int, chunk_blocks: int,
                         null_block: int, kv_heads: int, q_per_kv: int):
    """Grid (B,), sequential; step b attends slot b's query to its context.

    The layer-stacked K/V pools stay in HBM; only layer ``layer[0]``'s
    blocks are read. Slot b needs ``nb = ceil(lens[b] / bs)``
    blocks; they are walked in ``ceil(nb / P)`` chunks of P blocks by an
    in-kernel loop, each block one async copy of its contiguous
    (bs, Hkv, D) slab into a VMEM chunk buffer. The buffer is doubled:
    the next chunk's copies (the next slot's first chunk after a slot's
    last) start before the current chunk is folded. No entry at or past
    ``nb`` is copied or computed; a NULL entry below ``nb`` is not copied
    but zero-filled, as the reference's ``mode="fill"`` gather reads it.

    A chunk is folded with one 2-D matmul per operand, every kv head at
    once: the chunk viewed as (P*bs*Hkv, D) rows, one per (token, kv
    head), against all H queries; a score whose row belongs to another
    kv head than its query's, or to a position at or past ``lens[b]``,
    is masked. Rows not copied hold stale or uninitialised VMEM, so V is
    zeroed by position as well. Q.K runs in the promoted dtype of q and
    the pool (exact products, f32 sums) and the scale is applied to the
    f32 scores; probabilities, P.V and the online-softmax state
    (acc (H, D), m, l) are f32.
    """
    b = pl.program_id(0)
    bs, per_chunk = block_size, chunk_blocks
    rows = per_chunk * bs * kv_heads        # chunk rows, (token, kv head)
    h, d = q_ref.shape[2], q_ref.shape[3]

    def n_blocks(i):
        return jnp.minimum(pl.cdiv(lens[i], bs), max_blocks)

    def fetch(i, c, slot, start):
        """Start (or wait for) the copies of slot i's chunk c into slot."""
        nb = n_blocks(i)
        for p in range(per_chunk):
            j = c * per_chunk + p
            blk = tables[i, jnp.minimum(j, max_blocks - 1)]

            @pl.when((j < nb) & (blk != null_block))
            def _copy():
                for kv, hbm, buf in ((0, k_hbm, k_buf), (1, v_hbm, v_buf)):
                    cp = pltpu.make_async_copy(hbm.at[layer[0], blk],
                                               buf.at[slot, p],
                                               sems.at[kv, slot])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

            if start:
                @pl.when((j < nb) & (blk == null_block))
                def _zero():
                    k_buf[slot, p] = jnp.zeros(k_buf.shape[2:], k_buf.dtype)
                    v_buf[slot, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        fetch(0, 0, 0, start=True)

    n_chunks = jnp.maximum(pl.cdiv(n_blocks(b), per_chunk), 1)
    length = lens[b]
    q = q_ref[0, 0]
    dt = jnp.promote_types(q.dtype, k_buf.dtype)
    q = q.astype(dt)
    # row r of a chunk is kv head r % Hkv; query head i reads kv head
    # i // q_per_kv
    same_head = (jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1),
                             kv_heads) ==
                 jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0),
                             q_per_kv))

    def fold(c, carry):
        acc, m, l = carry
        slot = slot_ref[0]

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            fetch(b, c + 1, 1 - slot, start=True)

        @pl.when((c + 1 == n_chunks) & (b + 1 < pl.num_programs(0)))
        def _next_slot():
            fetch(b + 1, 0, 1 - slot, start=True)

        fetch(b, c, slot, start=False)
        slot_ref[0] = 1 - slot
        # rows below `live` hold positions < lens[b]
        live = (length - c * per_chunk * bs) * kv_heads
        k = k_buf[slot].reshape(rows, d).astype(dt)
        v = v_buf[slot].reshape(rows, d).astype(jnp.float32)
        v = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < live, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = same_head & (
            jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1) < live)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, n_chunks, fold,
        (jnp.zeros((h, d), jnp.float32), jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32)))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_decode_paged_pallas(
    q: jnp.ndarray,                      # (B, 1, H, D)
    k_pool: jnp.ndarray,                 # (L, N, bs, Hkv, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,           # (B, MB) int32, NULL == N
    kv_lens: jnp.ndarray,                # (B,) int32 EFFECTIVE lengths
    layer: jnp.ndarray,                  # () int32, the layer to attend
    *,
    softmax_scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-query GQA decode over layer ``layer`` of a layer-stacked
    paged KV pool, gather in-kernel.

    ``kv_lens`` are the effective context lengths (positions
    ``>= kv_lens[i]`` are masked); the new token's K/V must already be
    scattered into the pool. Returns (B, 1, H, D) in q's dtype, within
    compute-dtype tolerance of gathering the window with
    ``mode="fill"`` and running ``ref.mha_dense(causal=False,
    kv_len=kv_lens)`` (a streaming softmax sums in a different order).

    One pallas_call, grid (B,): the block tables, lengths and layer are
    scalar-prefetched, q and the output are (1, 1, H, D) blocks per
    slot, and the whole pools stay in HBM, so a layer scan can carry
    them and update them in place. Each slot reads only its
    ``ceil(kv_lens[i] / bs)`` mapped blocks of the layer, in chunks of
    ``P = max(1, 128 // bs)`` blocks (at most MB), double-buffered in
    VMEM (see ``_paged_decode_kernel``); its HBM traffic is its context
    rounded up to a block, whatever the table's width.
    """
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"paged decode expects a single query, got {sq}")
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    pad = -d % LANES
    if pad:
        # Mosaic copies a slab out of an HBM array only at lane-aligned
        # widths: zero lanes leave every score and output lane as it was.
        # Only the layer's slab is taken out and widened, not the pool
        widen = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        out = flash_decode_paged_pallas(
            widen(q), widen(k_pool[layer][None]), widen(v_pool[layer][None]),
            block_tables, kv_lens, jnp.int32(0), softmax_scale=scale,
            interpret=interpret)
        return out[..., :d]
    _, n_pool, bs, hkv, _ = k_pool.shape
    mb = block_tables.shape[1]
    chunk = min(max(1, CHUNK_TOKENS // bs), mb)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=bs, max_blocks=mb,
        chunk_blocks=chunk, null_block=n_pool, kv_heads=hkv,
        q_per_kv=h // hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h, d),
                         lambda i, tbl, lens, lyr: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, 1, h, d),
                               lambda i, tbl, lens, lyr: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, bs, hkv, d), k_pool.dtype),
            pltpu.VMEM((2, chunk, bs, hkv, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K or V, buffer)
            pltpu.SMEM((1,), jnp.int32),         # buffer of the next fold
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_pool, v_pool)
