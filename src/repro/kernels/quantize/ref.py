"""Pure-jnp oracle for per-block int8 quantization (gradient compression).

Used by core/compression.py on the cross-pod (DCN) gradient reduction —
the beyond-paper distributed-optimization trick. Per-block absmax scaling;
optional stochastic rounding keeps the compressed SGD unbiased.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def quantize_int8(
    x: jnp.ndarray,
    *,
    block_size: int = 256,
    key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(flat) f32 -> (int8 values, f32 per-block scales)."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    padded = -(-n // block_size) * block_size
    flat = jnp.pad(flat, (0, padded - n))
    blocks = flat.reshape(-1, block_size)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    scaled = blocks / scale
    if key is not None:
        noise = jax.random.uniform(key, scaled.shape) - 0.5
        q = jnp.clip(jnp.round(scaled + noise), -127, 127)
    else:
        q = jnp.clip(jnp.round(scaled), -127, 127)
    return q.astype(jnp.int8), scale[:, 0]


def dequantize_int8(
    q: jnp.ndarray, scale: jnp.ndarray, shape, block_size: int = 256,
) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape)


def dequant_accum(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Fused dequantize-and-accumulate oracle (reduction receive side).

    ``q``: (ranks, blocks, block_size) int8 — one quantized contribution
    per peer rank; ``scale``: (ranks, blocks) f32 per-block scales.
    Returns (blocks, block_size) f32 = sum_r q[r] * scale[r] — the
    summed shard without ever materializing per-rank f32 copies.
    """
    return jnp.einsum("rbk,rb->bk", q.astype(jnp.float32),
                      scale.astype(jnp.float32))
