"""Beyond-paper: int8 gradient compression with error feedback.

Applied ONLY to the cross-pod ("pod" axis / DCN) leg of the gradient
reduction — the slow, heterogeneous link that is the TPU analogue of the
paper's campus Ethernet. In-pod (ICI) reductions stay full precision.

Scheme (per leaf or per bucket, per step):
  1. e_corrected = grad + error_state           (error feedback)
  2. q, scales  = blockwise int8 quantize (kernels/quantize)
  3. exchange q + scales across pods (hierarchical.py / buckets.py do
     the collective; the bucketed path fuses scales into the int8 wire
     payload via ``fuse_payload`` so each exchange is ONE collective)
  4. error_state' = e_corrected - dequant(q)    (what compression lost)

Error feedback makes the compressed reduction converge like the exact
one (Karimireddy et al. 2019); the quantizer's stochastic rounding keeps
single-step bias near zero as well.

The per-leaf ``compress_tree``/``decompress_tree`` walk below is the
legacy path (one quantize + one exchange per pytree leaf); the bucketed
flat-buffer engine in core/buckets.py quantizes whole bucket stacks in
a single kernel call and should be preferred on hot paths.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.quantize import ops as q_ops
from repro.kernels.quantize import ref as q_ref


def init_error_state(grads: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_leaf(g: jnp.ndarray, err: jnp.ndarray,
                  key: Optional[jax.Array] = None,
                  block_size: int = 256, impl: str = "reference"
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (q int8 blocks, scales, new_error)."""
    corrected = g.astype(jnp.float32) + err
    q, s = q_ops.quantize_int8(corrected, block_size=block_size, key=key,
                               impl=impl)
    deq = q_ref.dequantize_int8(q, s, corrected.shape, block_size)
    return q, s, corrected - deq


def compress_tree(grads: Any, err_state: Any,
                  key: Optional[jax.Array] = None,
                  block_size: int = 256, impl: str = "reference"):
    """Quantize every leaf. Returns ((q_tree, s_tree), new_err_state)."""
    leaves, treedef = jax.tree.flatten(grads)
    errs = treedef.flatten_up_to(err_state)
    keys = (jax.random.split(key, len(leaves)) if key is not None
            else [None] * len(leaves))
    qs, ss, nes = [], [], []
    for g, e, k in zip(leaves, errs, keys):
        q, s, ne = compress_leaf(g, e, k, block_size, impl)
        qs.append(q)
        ss.append(s)
        nes.append(ne)
    return ((treedef.unflatten(qs), treedef.unflatten(ss)),
            treedef.unflatten(nes))


def decompress_tree(q_tree: Any, s_tree: Any, shapes: Any,
                    block_size: int = 256) -> Any:
    """Dequantize every leaf back to the original shapes pytree."""
    return jax.tree.map(
        lambda q, s, ref: q_ref.dequantize_int8(q, s, ref.shape, block_size),
        q_tree, s_tree, shapes)


def fuse_payload(q: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Fuse int8 values + f32 scales into ONE wire buffer per block.

    ``q``: (..., blocks, block_size) int8, ``s``: (..., blocks) f32.
    The result is an int8 buffer of block_size + 4 bytes per block —
    the scale bit-cast into 4 trailing bytes — so a compressed exchange
    is a single collective instead of one for values + one for scales.
    """
    s_bytes = jax.lax.bitcast_convert_type(s, jnp.int8)
    return jnp.concatenate([q, s_bytes], axis=-1)


def split_payload(payload: jnp.ndarray, block_size: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of :func:`fuse_payload`: -> (q int8, s f32), lossless."""
    q = payload[..., :block_size]
    s = jax.lax.bitcast_convert_type(payload[..., block_size:],
                                     jnp.float32)
    return q, s


def compression_ratio(grads: Any, block_size: int = 256) -> float:
    """Bytes(int8+scales) / bytes(fp32) for a gradient pytree."""
    fp = sum(g.size * 4 for g in jax.tree.leaves(grads))
    comp = sum(g.size * 1 + -(-g.size // block_size) * 4
               for g in jax.tree.leaves(grads))
    return comp / fp
