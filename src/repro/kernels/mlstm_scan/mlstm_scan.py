"""Pallas TPU kernel for the xLSTM mLSTM chunkwise-parallel scan.

Same TPU pattern as ssd_scan: grid = (batch, head_blocks, chunks) with the
chunk axis sequential; the stabilized matrix memory (C_hat, n_hat, m) is
VMEM scratch carried across chunk ticks. Within a chunk the math is dense
MXU work on (Q, dk)/(Q, dv) tiles with log-space stabilization identical
to ref.mlstm_chunked.

Validated in interpret mode against ref.mlstm_sequential.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref,
                  h_ref, cfin_ref, nfin_ref, mfin_ref,
                  c_ref, n_ref, m_ref, *,
                  chunk: int, num_chunks: int, dk: int, dv: int):
    ci = pl.program_id(2)
    scale = dk ** -0.5

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)

    qb = q_ref[0].astype(jnp.float32)            # (bh, Q, dk)
    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)            # (bh, Q, dv)
    ib = i_ref[0, :, :, 0].astype(jnp.float32)   # (bh, Q)
    fb = f_ref[0, :, :, 0].astype(jnp.float32)

    lf = jax.nn.log_sigmoid(fb)
    bcs = jnp.cumsum(lf, axis=-1)                # (bh, Q) inclusive
    g = bcs[:, -1]                               # (bh,)
    m = m_ref[...][:, 0]                         # (bh,)

    # intra-chunk log weights D_ij = b_i - b_j + i~_j (j <= i)
    Dm = bcs[:, :, None] - bcs[:, None, :] + ib[:, None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = ii >= jj
    Dm = jnp.where(tri[None], Dm, -jnp.inf)
    m_intra = jnp.max(Dm, axis=-1)               # (bh, Q)
    m_inter = bcs + m[:, None]
    m_i = jnp.maximum(m_intra, m_inter)
    intra = jnp.exp(Dm - m_i[:, :, None])        # (bh, Q, Q)

    qk = jax.lax.dot_general(qb, kb, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    w_intra = intra * qk
    num = jax.lax.dot_general(w_intra, vb, (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)
    den = jnp.sum(w_intra, axis=-1)              # (bh, Q)
    inter_w = jnp.exp(m_inter - m_i)             # (bh, Q)
    qC = jax.lax.dot_general(qb, c_ref[...], (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)  # (bh,Q,dv)
    num += inter_w[:, :, None] * qC * scale
    qn = jnp.einsum("hik,hk->hi", qb, n_ref[...])
    den += inter_w * qn * scale
    h_out = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))[:, :, None]
    h_ref[0] = h_out.astype(h_ref.dtype)

    # state update (stabilized by the new running max m')
    w_state = g[:, None] - bcs + ib              # (bh, Q)
    m_new = jnp.maximum(g + m, jnp.max(w_state, axis=-1))
    carry_w = jnp.exp(g + m - m_new)             # (bh,)
    kw = jnp.exp(w_state - m_new[:, None])       # (bh, Q)
    kkw = kw[:, :, None] * kb                    # (bh, Q, dk)
    c_ref[...] = (carry_w[:, None, None] * c_ref[...] +
                  jax.lax.dot_general(kkw, vb, (((1,), (1,)), ((0,), (0,))),
                                      preferred_element_type=jnp.float32))
    n_ref[...] = (carry_w[:, None] * n_ref[...] + jnp.sum(kkw, axis=1))
    m_ref[...] = m_new[:, None]

    @pl.when(ci == num_chunks - 1)
    def _finish():
        cfin_ref[0] = c_ref[...]
        nfin_ref[0] = n_ref[...]
        mfin_ref[0] = m_ref[...]


def mlstm_scan_pallas(
    q: jnp.ndarray,                    # (B, S, H, dk)
    k: jnp.ndarray,
    v: jnp.ndarray,                    # (B, S, H, dv)
    i_pre: jnp.ndarray,                # (B, S, H)
    f_pre: jnp.ndarray,
    *,
    chunk_size: int = 128,
    initial_state=None,
    block_h: int = 4,
    interpret: bool = False,
):
    if initial_state is not None:
        raise NotImplementedError(
            "pallas mlstm_scan starts from zero state (train/prefill); "
            "decode uses mlstm_decode_step")
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    orig_s = s
    chunk = min(chunk_size, s)
    pad = (-s) % chunk
    block_h = min(block_h, h)
    if h % block_h != 0:
        block_h = 1

    def hm(t):
        return jnp.moveaxis(t, 2, 1)             # (B, H, S, F)

    qt, kt, vt = hm(q), hm(k), hm(v)
    it = hm(i_pre[..., None])
    ft = hm(f_pre[..., None])
    if pad:
        p4 = ((0, 0), (0, 0), (0, pad), (0, 0))
        qt = jnp.pad(qt, p4)
        kt = jnp.pad(kt, p4)
        vt = jnp.pad(vt, p4)
        # pad gates: i -> -inf (no input), f -> +big (keep state)
        it = jnp.pad(it, p4, constant_values=NEG_BIG)
        ft = jnp.pad(ft, p4, constant_values=30.0)
    s_p = qt.shape[2]
    nc = s_p // chunk
    nh = h // block_h

    kernel = functools.partial(_mlstm_kernel, chunk=chunk, num_chunks=nc,
                               dk=dk, dv=dv)

    hseq, cfin, nfin, mfin = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, block_h, chunk, dk),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, dk),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, dv),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_h, chunk, dv),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, dk, dv),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, block_h, dk),
                         lambda bi, hi, ci: (bi, hi, 0)),
            pl.BlockSpec((1, block_h, 1),
                         lambda bi, hi, ci: (bi, hi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dk), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_h, dk, dv), jnp.float32),
            pltpu.VMEM((block_h, dk), jnp.float32),
            pltpu.VMEM((block_h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, it, ft)
    out = jnp.moveaxis(hseq[:, :, :orig_s, :], 1, 2)
    return out, (cfin, nfin, mfin[:, :, 0])
