"""Absorbed-MLA decode op with implementation dispatch (see ref.py)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.mla_decode import ref


def mla_decode_attention(q_abs, q_r, ckv, kr, kv_len, scale,
                         *, impl: str = "dense", chunk: int = 512,
                         interpret: bool = False):
    if impl == "dense":
        return ref.mla_decode_dense(q_abs, q_r, ckv, kr, kv_len, scale)
    if impl == "pallas":
        from repro.kernels.mla_decode.mla_decode import mla_decode_pallas
        return mla_decode_pallas(q_abs, q_r, ckv, kr, kv_len, scale,
                                 chunk=chunk, interpret=interpret)
    raise ValueError(f"unknown mla decode impl '{impl}'")


def mla_decode_paged_attention(q_abs, q_r, ckv_pool, kr_pool,
                               block_tables, kv_lens, layer, scale,
                               *, impl: str = "reference",
                               interpret: bool = False):
    """Absorbed-MLA decode over layer ``layer`` of a layer-stacked paged
    latent pool.

    ckv_pool (L, N, bs, r); kr_pool (L, N, bs, Dr); block_tables (B, MB)
    int32 with NULL == N; kv_lens (B,) effective lengths; layer () int32.
    Returns out_lat (B, H, r) fp32.

    ``impl``: "reference"/"dense" gathers the layer's mapped blocks into
    a dense (B, MB*bs, ...) window (NULL fills zeros) and runs
    ``ref.mla_decode_dense``; "pallas" streams the layer's pool blocks
    through the block table inside the kernel (one HBM pass, no window,
    no per-layer slice).
    """
    if impl in ("reference", "dense"):
        b = q_abs.shape[0]
        ckv_g = ckv_pool.at[layer, block_tables].get(
            mode="fill", fill_value=0).reshape(b, -1, ckv_pool.shape[-1])
        kr_g = kr_pool.at[layer, block_tables].get(
            mode="fill", fill_value=0).reshape(b, -1, kr_pool.shape[-1])
        return ref.mla_decode_dense(q_abs, q_r, ckv_g, kr_g, kv_lens,
                                    scale)
    if impl == "pallas":
        from repro.kernels.mla_decode.mla_decode import (
            mla_decode_paged_pallas,
        )
        return mla_decode_paged_pallas(q_abs, q_r, ckv_pool, kr_pool,
                                       block_tables, kv_lens, layer, scale,
                                       interpret=interpret)
    raise ValueError(f"unknown mla decode impl '{impl}'")
