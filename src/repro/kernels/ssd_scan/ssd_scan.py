"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm (Mamba2 paper, listing 1):
  * grid = (batch, head_blocks, chunks); the chunk axis is the innermost
    *sequential* grid axis, and the (block_h, P, N) fp32 SSM state lives
    in VMEM scratch across chunk ticks — the cross-chunk recurrence that
    a GPU implementation does with a separate scan kernel happens for
    free in the TPU grid order.
  * within a chunk everything is dense matmul on the MXU: the (Q, Q)
    intra-chunk kernel L, the (Q, N)x(N, Q) C·Bᵀ Gram matrix, and the
    state in/out projections. Q = chunk_size (default 128/256) and
    N = state_dim are MXU-friendly.
  * B/C group broadcasting (ngroups < heads) is done by the wrapper so
    the kernel sees per-head B/C; the wrapper transposes to head-major
    (B, H, S, ...) so tiles are clean 2-D matrices per head.

Validated in interpret mode against ref.ssd_sequential.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, fin_ref, state_ref, *,
                chunk: int, num_chunks: int, block_h: int,
                head_p: int, state_n: int, use_d: bool):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)            # (bh, Q, P)
    dt = dt_ref[0, :, :, 0].astype(jnp.float32)  # (bh, Q)
    A = a_ref[...][:, 0].astype(jnp.float32)     # (bh,)
    Bm = b_ref[0].astype(jnp.float32)            # (bh, Q, N)
    Cm = c_ref[0].astype(jnp.float32)            # (bh, Q, N)

    dA_log = dt * A[:, None]                     # (bh, Q)
    A_cum = jnp.cumsum(dA_log, axis=-1)          # inclusive
    # intra-chunk decay kernel: L[h,i,j] = exp(Acum_i - Acum_j), i >= j
    diff = A_cum[:, :, None] - A_cum[:, None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = ii >= jj
    L = jnp.where(tri[None], jnp.exp(diff), 0.0)  # (bh, Q, Q)

    dx = dt[:, :, None] * x                      # (bh, Q, P)
    # diagonal block: (C Bᵀ ⊙ L) · (dt x)
    G = jax.lax.dot_general(Cm, Bm, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # (bh,Q,Q)
    y = jax.lax.dot_general(G * L, dx, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # (bh,Q,P)
    # off-diagonal: contribution of the carried state
    state = state_ref[...]                       # (bh, P, N)
    y += jnp.exp(A_cum)[:, :, None] * jax.lax.dot_general(
        Cm, state, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)      # (bh, Q, P)
    if use_d:
        y += x * d_ref[...][:, 0][:, None, None].astype(jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: decayed carry + chunk contribution
    decay_state = jnp.exp(A_cum[:, -1:] - A_cum)  # (bh, Q)
    wdx = decay_state[:, :, None] * dx            # (bh, Q, P)
    chunk_state = jax.lax.dot_general(
        wdx, Bm, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # (bh, P, N)
    state_ref[...] = (jnp.exp(A_cum[:, -1])[:, None, None] * state +
                      chunk_state)

    @pl.when(ci == num_chunks - 1)
    def _finish():
        fin_ref[0] = state_ref[...]


def ssd_scan_pallas(
    x: jnp.ndarray,                    # (B, S, H, P)
    dt: jnp.ndarray,                   # (B, S, H)
    A: jnp.ndarray,                    # (H,)
    Bm: jnp.ndarray,                   # (B, S, G, N)
    Cm: jnp.ndarray,                   # (B, S, G, N)
    D: Optional[jnp.ndarray] = None,   # (H,)
    *,
    chunk_size: int = 128,
    initial_state: Optional[jnp.ndarray] = None,
    block_h: int = 8,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if initial_state is not None:
        raise NotImplementedError(
            "pallas ssd_scan starts from zero state (train/prefill); "
            "decode uses ssd_decode_step")
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    g = Bm.shape[2]
    orig_s = s
    chunk = min(chunk_size, s)
    pad = (-s) % chunk
    block_h = min(block_h, h)
    if h % block_h != 0:
        block_h = 1

    # head-major layout; dt=0 padding keeps state and contributes nothing
    def hm(t):  # (B, S, H, F) -> (B, H, S, F)
        return jnp.moveaxis(t, 2, 1)

    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=2) if rep > 1 else Bm
    Ch = jnp.repeat(Cm, rep, axis=2) if rep > 1 else Cm
    xt, Bt, Ct = hm(x), hm(Bh), hm(Ch)
    dtt = hm(dt[..., None])                       # (B, H, S, 1)
    if pad:
        cfgpad = ((0, 0), (0, 0), (0, pad), (0, 0))
        xt = jnp.pad(xt, cfgpad)
        Bt = jnp.pad(Bt, cfgpad)
        Ct = jnp.pad(Ct, cfgpad)
        dtt = jnp.pad(dtt, cfgpad)
    s_p = xt.shape[2]
    nc = s_p // chunk
    nh = h // block_h
    use_d = D is not None
    d_in = (D if use_d else jnp.zeros((h,), jnp.float32))[:, None]

    kernel = functools.partial(
        _ssd_kernel, chunk=chunk, num_chunks=nc, block_h=block_h,
        head_p=p, state_n=n, use_d=use_d)

    y, fin = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, block_h, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((block_h, 1), lambda bi, hi, ci: (hi, 0)),
            pl.BlockSpec((1, block_h, chunk, n),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, n),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((block_h, 1), lambda bi, hi, ci: (hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_h, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, p, n),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_p, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32)[:, None], Bt, Ct,
      d_in.astype(jnp.float32))
    y = jnp.moveaxis(y[:, :, :orig_s, :], 1, 2)   # back to (B, S, H, P)
    return y, fin
