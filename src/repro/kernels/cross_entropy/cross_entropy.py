"""Pallas TPU blocked large-vocab cross-entropy kernel.

Motivation: phi4-mini has a 200,064-entry vocabulary; materializing the
(tokens, vocab) logit matrix at bf16 for train_4k (1M tokens global) is
the dominant activation. This kernel fuses the lm_head matmul with an
online logsumexp so only (block_t, block_v) logit tiles ever exist, in
VMEM.

Design:
  * grid = (token_blocks, vocab_blocks); vocab is the innermost
    *sequential* axis; per-token running (max, sumexp, true_logit,
    sum_logits) accumulators live in VMEM scratch across vocab ticks.
  * hidden tile (block_t, D) stays resident across the whole vocab sweep
    of one token block (constant index_map on the vocab axis); lm_head
    streams as (D, block_v) MXU-aligned tiles.
  * labels arrive as one-hot-free int32; the true logit is extracted with
    a where-sum inside the tile that contains it.
  * emits per-token nll and weight untouched — the weighted HetSeq
    (sum, weight-sum) contract is applied by ops.py so the aggregation
    math is shared with the reference path.

Validated in interpret mode against ref.ce_dense.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ce_kernel(h_ref, w_ref, lab_ref, nll_ref,
               m_ref, l_ref, true_ref, sum_ref, *,
               block_t: int, block_v: int, vocab: int, num_v_blocks: int,
               label_smoothing: float, logit_softcap: float):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        true_ref[...] = jnp.zeros_like(true_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    h = h_ref[...].astype(jnp.float32)                     # (bt, D)
    w = w_ref[...].astype(jnp.float32)                     # (D, bv)
    logits = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    col = (vb * block_v +
           jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1))
    valid = col < vocab
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = (l_ref[...] * corr[:, None] +
                  jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1,
                          keepdims=True))
    m_ref[...] = m_new[:, None]

    labels = lab_ref[...][:, 0]                            # (bt,) int32
    is_label = col == labels[:, None]
    true_ref[...] += jnp.sum(jnp.where(is_label, logits, 0.0), axis=-1,
                             keepdims=True)
    if label_smoothing > 0.0:
        sum_ref[...] += jnp.sum(jnp.where(valid, logits, 0.0), axis=-1,
                                keepdims=True)

    @pl.when(vb == num_v_blocks - 1)
    def _finish():
        lse = m_ref[...][:, 0] + jnp.log(jnp.maximum(l_ref[...][:, 0], 1e-30))
        nll = lse - true_ref[...][:, 0]
        if label_smoothing > 0.0:
            mean_logit = sum_ref[...][:, 0] / vocab
            nll = (1.0 - label_smoothing) * nll + \
                label_smoothing * (lse - mean_logit)
        nll_ref[...] = nll[:, None]


def cross_entropy_pallas(
    hidden: jnp.ndarray,                 # (T, D)
    lm_head: jnp.ndarray,                # (D, V)
    labels: jnp.ndarray,                 # (T,) int32
    weights: jnp.ndarray,                # (T,) f32
    *,
    label_smoothing: float = 0.0,
    logit_softcap: float = 0.0,
    block_t: int = 256,
    block_v: int = 1024,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    t, d = hidden.shape
    v = lm_head.shape[1]
    block_t = min(block_t, max(t, 8))
    block_v = min(block_v, max(v, 128))
    pad_t = (-t) % block_t
    pad_v = (-v) % block_v
    if pad_t:
        hidden = jnp.pad(hidden, ((0, pad_t), (0, 0)))
        labels = jnp.pad(labels, (0, pad_t))
    if pad_v:
        lm_head = jnp.pad(lm_head, ((0, 0), (0, pad_v)))
    n_t = hidden.shape[0] // block_t
    n_v = lm_head.shape[1] // block_v

    kernel = functools.partial(
        _ce_kernel, block_t=block_t, block_v=block_v, vocab=v,
        num_v_blocks=n_v, label_smoothing=label_smoothing,
        logit_softcap=logit_softcap)

    nll = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((hidden.shape[0], 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),    # running max
            pltpu.VMEM((block_t, 1), jnp.float32),    # running sumexp
            pltpu.VMEM((block_t, 1), jnp.float32),    # true logit
            pltpu.VMEM((block_t, 1), jnp.float32),    # sum logits (smoothing)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(hidden, lm_head.astype(hidden.dtype), labels[:, None].astype(jnp.int32))
    nll = nll[:t, 0]
    w = weights.astype(jnp.float32)
    return jnp.sum(nll * w), jnp.sum(w)
