"""Pallas TPU kernels for per-block int8 quantization (grad compression).

Used on the cross-pod (DCN) gradient reduction path: fp32 gradient shards
are quantized to int8 + per-block fp32 scales (4.06x compression) before
the pod-axis exchange. Stochastic rounding keeps the compressed update
unbiased; the noise tensor is generated outside the kernel with
jax.random so the kernel stays deterministic and testable.

Two kernels:
  * ``quantize_int8_pallas`` — send side. Grid tiles rows of a
    (num_blocks, block_size) view; absmax, scale and rounding are all
    VPU element-wise work — the kernel exists to keep the quantize
    fused and VMEM-resident next to the collective rather than
    round-tripping through HBM. The bucketed reduction
    (core/buckets.py) calls it ONCE over the whole concatenated bucket
    stack, not per pytree leaf.
  * ``dequant_accum_pallas`` — receive side. After the cross-pod
    exchange each rank holds one int8 contribution per peer for its
    shard; this kernel fuses dequantize (q * scale) with the
    accumulation over peers, so the per-peer f32 expansion never leaves
    VMEM. The peer loop is unrolled (pod counts are small static
    numbers).

Both validated in interpret mode against ref.py oracles.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quant_kernel(x_ref, noise_ref, q_ref, s_ref, *, stochastic: bool):
    x = x_ref[...].astype(jnp.float32)                # (rows, block)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    scaled = x / scale
    if stochastic:
        scaled = scaled + (noise_ref[...] - 0.5)
    q = jnp.clip(jnp.round(scaled), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def quantize_int8_pallas(
    x: jnp.ndarray,
    *,
    block_size: int = 256,
    key: Optional[jax.Array] = None,
    rows_per_tile: int = 256,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    padded = -(-n // block_size) * block_size
    flat = jnp.pad(flat, (0, padded - n))
    blocks = flat.reshape(-1, block_size)
    nb = blocks.shape[0]
    rows = min(rows_per_tile, nb)
    pad_rows = (-nb) % rows
    if pad_rows:
        blocks = jnp.pad(blocks, ((0, pad_rows), (0, 0)))
    nb_p = blocks.shape[0]
    stochastic = key is not None
    noise = (jax.random.uniform(key, blocks.shape) if stochastic
             else jnp.zeros_like(blocks))

    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, stochastic=stochastic),
        grid=(nb_p // rows,),
        in_specs=[
            pl.BlockSpec((rows, block_size), lambda i: (i, 0)),
            pl.BlockSpec((rows, block_size), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, block_size), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb_p, block_size), jnp.int8),
            jax.ShapeDtypeStruct((nb_p, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(blocks, noise)
    return q[:nb], s[:nb, 0]


# --------------------------------------------------------------------------
# fused dequantize-accumulate (receive side of the bucketed reduction)
# --------------------------------------------------------------------------


def _dequant_accum_kernel(q_ref, s_ref, o_ref, *, ranks: int):
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for r in range(ranks):                       # static unroll, ranks small
        acc = acc + q_ref[r].astype(jnp.float32) * s_ref[r]
    o_ref[...] = acc


def dequant_accum_pallas(
    q: jnp.ndarray,
    s: jnp.ndarray,
    *,
    rows_per_tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """sum_r q[r] * s[r] for q (ranks, blocks, B) int8, s (ranks, blocks).

    Returns (blocks, B) f32. One grid step per row tile; the rank loop
    is unrolled inside the kernel so the dequantized f32 values are
    consumed by the accumulator without an HBM round trip.
    """
    ranks, nb, block = q.shape
    rows = min(rows_per_tile, nb)
    pad_rows = (-nb) % rows
    if pad_rows:
        q = jnp.pad(q, ((0, 0), (0, pad_rows), (0, 0)))
        s = jnp.pad(s, ((0, 0), (0, pad_rows)))
    nb_p = q.shape[1]
    out = pl.pallas_call(
        functools.partial(_dequant_accum_kernel, ranks=ranks),
        grid=(nb_p // rows,),
        in_specs=[
            pl.BlockSpec((ranks, rows, block), lambda i: (0, i, 0)),
            pl.BlockSpec((ranks, rows, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_p, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, s[..., None].astype(jnp.float32))
    return out[:nb]
