"""Public attention op with implementation dispatch.

``impl``:
  * "reference"  — chunked online-softmax jnp (CPU dry-run / oracle-adjacent)
  * "dense"      — full score matrix (tiny shapes, tests)
  * "pallas"     — Pallas TPU kernel (``flash_attention.py``); on non-TPU
                   backends tests run it with interpret=True.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ref


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int = 0,
    softmax_scale: Optional[float] = None,
    impl: str = "reference",
    chunk_size: int = 512,
    kv_len: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    if impl == "dense":
        return ref.mha_dense(q, k, v, causal=causal, q_offset=q_offset,
                             softmax_scale=softmax_scale, kv_len=kv_len)
    if impl == "reference":
        return ref.mha_chunked(q, k, v, causal=causal, q_offset=q_offset,
                               softmax_scale=softmax_scale,
                               chunk_size=chunk_size, kv_len=kv_len)
    if impl == "pallas":
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_pallas,
        )
        return flash_attention_pallas(
            q, k, v, causal=causal, q_offset=q_offset,
            softmax_scale=softmax_scale, interpret=interpret)
    raise ValueError(f"unknown attention impl '{impl}'")


def flash_decode_paged(
    q: jnp.ndarray,                      # (B, 1, H, D)
    k_pool: jnp.ndarray,                 # (L, N, bs, Hkv, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,           # (B, MB) int32, NULL == N
    kv_lens: jnp.ndarray,                # (B,) int32 effective lengths
    layer: jnp.ndarray,                  # () int32
    *,
    softmax_scale: Optional[float] = None,
    impl: str = "reference",
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-query GQA decode over layer ``layer`` of a layer-stacked
    paged pool (serving hot path).

    ``impl``:
      * "reference"/"dense" — materialize-then-attend: gather each
        sequence's mapped blocks of the layer into a dense
        (B, MB*bs, Hkv, D) window in HBM (NULL blocks fill with zeros)
        and run ``ref.mha_dense``.
      * "pallas" — in-kernel block gather: the kernel reads the layer's
        blocks through the block table straight from the whole pool in
        HBM, so no window and no per-layer slice is ever materialized.
        Within compute-dtype tolerance of the reference path.

    ``kv_lens`` are effective context lengths: positions >= kv_lens[i]
    are masked, so callers attending to a just-written token pass
    ``cached + 1``.
    """
    if impl in ("reference", "dense"):
        b = q.shape[0]
        k_g = k_pool.at[layer, block_tables].get(
            mode="fill", fill_value=0).reshape(b, -1, *k_pool.shape[3:])
        v_g = v_pool.at[layer, block_tables].get(
            mode="fill", fill_value=0).reshape(b, -1, *v_pool.shape[3:])
        return ref.mha_dense(q, k_g, v_g, causal=False,
                             softmax_scale=softmax_scale, kv_len=kv_lens)
    if impl == "pallas":
        from repro.kernels.flash_attention.flash_attention import (
            flash_decode_paged_pallas,
        )
        return flash_decode_paged_pallas(
            q, k_pool, v_pool, block_tables, kv_lens, layer,
            softmax_scale=softmax_scale, interpret=interpret)
    raise ValueError(f"unknown attention impl '{impl}'")
