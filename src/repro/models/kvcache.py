"""KV caches and single-token decode attention (GQA + absorbed MLA).

Two cache families live here:

**Contiguous** (static-batch serving, one slab per sequence slot;
stacked with a leading L dim by the stack):
  GQA : k/v (B, S_max, Hkv, Dh) in compute dtype
  MLA : c_kv (B, S_max, r) latent + k_rope (B, S_max, Dr) — the
        compressed-latent cache that makes DeepSeek-V2 decode cheap.

**Paged** (continuous-batching serving, ``repro.serve``): the cache is
a pool of fixed-size blocks — the inference twin of the flat bucket
stack in core/buckets.py — and each sequence owns a *block table*
mapping its logical block j to a physical pool slot:
  GQA : k/v (L, N, bs, Hkv, Dh)
  MLA : c_kv (L, N, bs, r) + k_rope (L, N, bs, Dr)
where N = pool blocks and bs = block size. The layer scan of a decode
step carries the whole layer-stacked pool: each layer writes its new
token at ``[layer, block, offset]`` and its attention reads the layer's
blocks out of the whole pool, so nothing slices a layer out or stacks
it back. Decode takes a per-sequence
``kv_lens`` vector instead of the scalar ``pos``: every sequence in the
batch sits at its own depth, so long and short requests share one
decode step without padding to the global max. Writes at out-of-pool
block ids (the NULL_BLOCK sentinel of retired/empty slots) are
dropped; gathers of unmapped blocks return zeros, exactly matching the
zero-initialized contiguous cache — which is what keeps the paged path
bit-identical to the static path in fp32.

Decode attention is single-query attention over the cache with a
``kv_len`` mask; MLA uses the *absorbed* formulation: W_uk is folded into
the query and W_uv into the output so the latent is never decompressed —
scores are (B, H, S) against the shared latent, MQA-style.

Sharding at scale (launch/sharding.py): caches shard batch over the DP
axes; when per-device batch is small and the cache is large (deepseek
decode_32k), the sequence dim shards over "model" instead and the
softmax is computed with a cross-shard logsumexp fix-up (split-K) — see
launch/steps.py. Paged pools shard KV heads / the latent rank over
"model" (``sharding.paged_cache_specs``); the block dim stays
replicated so block tables index identically on every rank.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.flash_attention import ref as attn_ref
from repro.kernels.mla_decode import ops as mla_ops
from repro.models.blocks import (ParallelCtx, _cast, apply_rope,
                                 attention_qkv, batch_spec, constrain,
                                 mla_latent, mla_queries)


# --------------------------------------------------------------------------
# cache constructors
# --------------------------------------------------------------------------


def init_gqa_cache(cfg: ModelConfig, num_layers: int, batch: int,
                   max_len: int) -> Dict[str, jnp.ndarray]:
    cdt = jnp.dtype(cfg.compute_dtype)
    shape = (num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


def init_mla_cache(cfg: ModelConfig, num_layers: int, batch: int,
                   max_len: int) -> Dict[str, jnp.ndarray]:
    cdt = jnp.dtype(cfg.compute_dtype)
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((num_layers, batch, max_len, m.kv_lora_rank), cdt),
        "k_rope": jnp.zeros((num_layers, batch, max_len, m.rope_head_dim),
                            cdt),
    }


# --------------------------------------------------------------------------
# GQA decode
# --------------------------------------------------------------------------


def attention_decode(params, x: jnp.ndarray, cfg: ModelConfig,
                     ctx: ParallelCtx, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos: jnp.ndarray):
    """One-token attention. x (B, 1, d); caches (B, S_max, Hkv, Dh).

    ``pos`` is the scalar index of the new token (kv_len becomes pos+1).
    Returns (y (B, 1, d), (k_cache, v_cache) updated).
    """
    b = x.shape[0]
    positions = jnp.reshape(pos, (1,))
    q, k, v = attention_qkv(params, x, cfg, positions)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
    kv_len = jnp.full((b,), pos + 1, jnp.int32)
    # dense single-query attention: with the cache sequence dim sharded
    # over "model" (split-K spec), XLA partitions the softmax reduction
    # across ranks automatically. A chunked python-level loop over the
    # sharded dim BREAKS that (each chunk broadcast to all ranks) —
    # measured +60% ICI (a refuted hypothesis).
    out = attn_ref.mha_dense(q, k_cache, v_cache, causal=False,
                             kv_len=kv_len)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    y = out @ _cast(params["wo"], cfg.compute_dtype)
    return constrain(y, ctx, batch_spec(ctx, None, None)), (k_cache, v_cache)


# --------------------------------------------------------------------------
# MLA decode (absorbed, latent-space attention)
# --------------------------------------------------------------------------


def mla_decode(params, x: jnp.ndarray, cfg: ModelConfig, ctx: ParallelCtx,
               ckv_cache: jnp.ndarray, kr_cache: jnp.ndarray,
               pos: jnp.ndarray):
    """One-token MLA attention over the compressed-latent cache.

    x (B, 1, d); ckv_cache (B, S_max, r); kr_cache (B, S_max, Dr).

    Dense (non-chunked) on purpose: the latent cache's sequence dim is
    sharded over "model" (split-K, launch/sharding.py) and XLA
    partitions the softmax + weighted-sum reductions across ranks
    automatically. A host-level chunk loop over the sharded dim forces
    per-chunk broadcasts instead (+60% ICI measured) — refuted §Perf
    hypothesis; the one-HBM-pass variant belongs in a Pallas kernel.
    """
    b = x.shape[0]
    m, h = cfg.mla, cfg.num_heads
    cdt = cfg.compute_dtype
    positions = jnp.reshape(pos, (1,))
    q_nope, q_rope = mla_queries(params, x, cfg, positions)  # (B,1,H,*)
    c_kv, k_r = mla_latent(params, x, cfg, positions)        # (B,1,r),(B,1,Dr)
    ckv_cache = jax.lax.dynamic_update_slice(
        ckv_cache, c_kv.astype(ckv_cache.dtype), (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        kr_cache, k_r.astype(kr_cache.dtype), (0, pos, 0))

    # absorb W_uk into the query: q_abs[b,h,r] = q_nope . W_uk[.,h,.]
    w_uk = _cast(params["w_uk"], cdt).reshape(
        m.kv_lora_rank, h, m.nope_head_dim)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(cdt)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    scores = (jnp.einsum("bhr,bsr->bhs", q_abs, ckv_cache,
                         preferred_element_type=jnp.float32) +
              jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(cdt),
                         kr_cache,
                         preferred_element_type=jnp.float32)) * scale
    s_max = ckv_cache.shape[1]
    mask = jnp.arange(s_max)[None, None, :] <= pos
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
    out_lat = jnp.einsum("bhs,bsr->bhr", probs, ckv_cache,
                         preferred_element_type=jnp.float32)
    w_uv = _cast(params["w_uv"], cdt).reshape(
        m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", out_lat.astype(cdt), w_uv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, h * m.v_head_dim).astype(cdt)
    y = out @ _cast(params["wo"], cdt)
    return (constrain(y, ctx, batch_spec(ctx, None, None)),
            (ckv_cache, kr_cache))


# --------------------------------------------------------------------------
# paged cache: layout, constructors, prefill scatter
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Geometry of a paged KV pool.

    The pool holds ``num_blocks`` physical blocks of ``block_size``
    tokens each; a sequence may map at most ``max_blocks_per_seq``
    logical blocks. Unmapped block-table entries hold ``null_block``
    (== num_blocks, one past the pool): scatters there are dropped and
    gathers there fill with zeros, so a NULL entry behaves exactly like
    untouched zero-initialized cache.
    """
    block_size: int
    num_blocks: int
    max_blocks_per_seq: int

    def __post_init__(self):
        if self.block_size <= 0 or self.num_blocks <= 0:
            raise ValueError(
                f"PagedLayout needs positive block_size/num_blocks, got "
                f"{self.block_size}/{self.num_blocks}")
        if self.max_blocks_per_seq <= 0:
            raise ValueError("PagedLayout.max_blocks_per_seq must be "
                             f"positive, got {self.max_blocks_per_seq}")

    @property
    def null_block(self) -> int:
        return self.num_blocks

    @property
    def max_seq_len(self) -> int:
        return self.block_size * self.max_blocks_per_seq

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold n_tokens (ceil-div; 0 tokens -> 0)."""
        return -(-n_tokens // self.block_size)


def init_gqa_paged_cache(cfg: ModelConfig, num_layers: int,
                         layout: PagedLayout) -> Dict[str, jnp.ndarray]:
    cdt = jnp.dtype(cfg.compute_dtype)
    shape = (num_layers, layout.num_blocks, layout.block_size,
             cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


def init_mla_paged_cache(cfg: ModelConfig, num_layers: int,
                         layout: PagedLayout) -> Dict[str, jnp.ndarray]:
    cdt = jnp.dtype(cfg.compute_dtype)
    m = cfg.mla
    base = (num_layers, layout.num_blocks, layout.block_size)
    return {
        "c_kv": jnp.zeros(base + (m.kv_lora_rank,), cdt),
        "k_rope": jnp.zeros(base + (m.rope_head_dim,), cdt),
    }


def write_prefill_blocks(paged: Dict[str, jnp.ndarray],
                         contiguous: Dict[str, jnp.ndarray],
                         block_tables: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Scatter a contiguous prefill cache into the paged pool.

    ``contiguous`` leaves are (L, B, S_pad, ...) with S_pad a multiple
    of the block size; ``block_tables`` is (B, >= S_pad // bs). Row j
    of sequence i's chunked cache lands in physical block
    ``block_tables[i, j]``; NULL entries drop the write. Tokens past a
    sequence's real length carry padding-token K/V — they are masked
    out by the per-sequence ``kv_lens`` at decode and overwritten in
    place as decode advances, so they never reach an output.
    """
    def _scatter(dst, src):
        l, b, s_pad = src.shape[:3]
        bs = dst.shape[2]
        if s_pad % bs:
            raise ValueError(
                f"prefill length {s_pad} not a multiple of block size "
                f"{bs}")
        nc = s_pad // bs
        chunks = src.reshape((l, b, nc, bs) + src.shape[3:])
        return dst.at[:, block_tables[:, :nc]].set(
            chunks.astype(dst.dtype), mode="drop")

    return {name: _scatter(paged[name], contiguous[name])
            for name in paged}


# --------------------------------------------------------------------------
# paged GQA decode (per-sequence kv_lens + block tables)
# --------------------------------------------------------------------------


def attention_decode_paged(params, x: jnp.ndarray, cfg: ModelConfig,
                           ctx: ParallelCtx, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, layer: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           kv_lens: jnp.ndarray):
    """One-token attention of layer ``layer`` over the layer-stacked
    paged pool, one depth per sequence.

    x (B, 1, d); caches (L, N, bs, Hkv, Dh); layer () int32;
    block_tables (B, MB) int32; kv_lens (B,) int32 — tokens already
    cached per sequence (the new token is written at position kv_lens[i]
    and attended to, so the effective context is kv_lens + 1). Sequences
    whose current block is NULL (inactive slots) write nowhere, gather
    zeros, and produce garbage the caller discards.
    Returns (y (B, 1, d), (k_cache, v_cache) updated): one scatter of
    B tokens into each whole pool, which a scan carrying the pools
    applies in place.
    """
    b = x.shape[0]
    bs = k_cache.shape[2]
    positions = kv_lens[:, None]                        # (B, 1)
    q, k, v = attention_qkv(params, x, cfg, positions)
    blk = jnp.take_along_axis(
        block_tables, (kv_lens // bs)[:, None], axis=1)[:, 0]
    off = kv_lens % bs
    k_cache = k_cache.at[layer, blk, off].set(
        k[:, 0].astype(k_cache.dtype), mode="drop")
    v_cache = v_cache.at[layer, blk, off].set(
        v[:, 0].astype(v_cache.dtype), mode="drop")
    # attention over the pool, per cfg.attention_impl: the reference
    # path gathers each sequence's mapped blocks back into a dense view
    # (NULL entries fill with zeros — bit-identical to untouched
    # contiguous cache, which keeps it bitwise equal to
    # attention_decode in fp32); the pallas path gathers blocks through
    # the block table INSIDE the kernel (no HBM window), within
    # compute-dtype tolerance of the reference, and runs interpreted
    # with a loud warning where the backend can't compile Pallas.
    out = attn_ops.flash_decode_paged(
        q, k_cache, v_cache, block_tables, kv_lens + 1, layer,
        impl=cfg.attention_impl,
        interpret=(cfg.attention_impl == "pallas" and
                   compat.pallas_interpret_fallback(
                       "paged GQA decode (attention_impl='pallas')")))
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    y = out @ _cast(params["wo"], cfg.compute_dtype)
    return constrain(y, ctx, batch_spec(ctx, None, None)), (k_cache, v_cache)


# --------------------------------------------------------------------------
# paged MLA decode (absorbed, latent-space attention)
# --------------------------------------------------------------------------


def mla_decode_paged(params, x: jnp.ndarray, cfg: ModelConfig,
                     ctx: ParallelCtx, ckv_cache: jnp.ndarray,
                     kr_cache: jnp.ndarray, layer: jnp.ndarray,
                     block_tables: jnp.ndarray, kv_lens: jnp.ndarray):
    """Paged twin of :func:`mla_decode`, for layer ``layer`` of the
    layer-stacked latent pool.

    x (B, 1, d); ckv_cache (L, N, bs, r); kr_cache (L, N, bs, Dr);
    layer () int32; block_tables (B, MB); kv_lens (B,). Same absorbed
    formulation — scores against the gathered latent view, mask
    positions >= kv_len+1. The new token is written at
    ``[layer, block, offset]`` of the whole pool, as in
    :func:`attention_decode_paged`.

    ``cfg.attention_impl="pallas"`` replaces the materialized gather
    with the in-kernel block-table stream
    (kernels/mla_decode/mla_decode.py, one HBM pass over the latent
    pool), within compute-dtype tolerance of this reference; on
    backends that can't compile Pallas it runs interpreted with a loud
    warning (compat.pallas_interpret_fallback).
    """
    b = x.shape[0]
    m, h = cfg.mla, cfg.num_heads
    cdt = cfg.compute_dtype
    bs = ckv_cache.shape[2]
    positions = kv_lens[:, None]                        # (B, 1)
    q_nope, q_rope = mla_queries(params, x, cfg, positions)  # (B,1,H,*)
    c_kv, k_r = mla_latent(params, x, cfg, positions)   # (B,1,r),(B,1,Dr)
    blk = jnp.take_along_axis(
        block_tables, (kv_lens // bs)[:, None], axis=1)[:, 0]
    off = kv_lens % bs
    ckv_cache = ckv_cache.at[layer, blk, off].set(
        c_kv[:, 0].astype(ckv_cache.dtype), mode="drop")
    kr_cache = kr_cache.at[layer, blk, off].set(
        k_r[:, 0].astype(kr_cache.dtype), mode="drop")
    w_uk = _cast(params["w_uk"], cdt).reshape(
        m.kv_lora_rank, h, m.nope_head_dim)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(cdt)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    if cfg.attention_impl == "pallas":
        out_lat = mla_ops.mla_decode_paged_attention(
            q_abs, q_rope[:, 0].astype(cdt), ckv_cache, kr_cache,
            block_tables, kv_lens + 1, layer, scale, impl="pallas",
            interpret=compat.pallas_interpret_fallback(
                "paged MLA decode (attention_impl='pallas')"))
    else:
        ckv_g = ckv_cache.at[layer, block_tables].get(
            mode="fill", fill_value=0).reshape(b, -1, m.kv_lora_rank)
        kr_g = kr_cache.at[layer, block_tables].get(
            mode="fill", fill_value=0).reshape(b, -1, m.rope_head_dim)
        scores = (jnp.einsum("bhr,bsr->bhs", q_abs, ckv_g,
                             preferred_element_type=jnp.float32) +
                  jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(cdt),
                             kr_g,
                             preferred_element_type=jnp.float32)) * scale
        s_g = ckv_g.shape[1]
        mask = jnp.arange(s_g)[None, None, :] < \
            (kv_lens + 1)[:, None, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
        out_lat = jnp.einsum("bhs,bsr->bhr", probs, ckv_g,
                             preferred_element_type=jnp.float32)
    w_uv = _cast(params["w_uv"], cdt).reshape(
        m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", out_lat.astype(cdt), w_uv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, h * m.v_head_dim).astype(cdt)
    y = out @ _cast(params["wo"], cdt)
    return (constrain(y, ctx, batch_spec(ctx, None, None)),
            (ckv_cache, kr_cache))

