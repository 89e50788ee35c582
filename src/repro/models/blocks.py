"""Transformer building blocks: norms, RoPE, GQA/MLA attention, MLP, MoE.

All blocks are pure functions ``apply(params, x, ...)`` over plain dict
pytrees; ``init_*`` builds matching params. Params are stored in
``cfg.param_dtype`` and cast to ``cfg.compute_dtype`` at use. Distribution
is expressed outside (launch/sharding.py) except where the block itself is
a distributed algorithm (MoE expert parallelism, split-K decode) — those
take a :class:`ParallelCtx`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as attn_ops


# --------------------------------------------------------------------------
# Parallel context
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """How a model invocation is distributed.

    ``mesh=None`` means single-device (smoke tests); blocks then use their
    local math paths. ``dp_axes`` spans (pod, data); ``tp_axis`` is the
    model/tensor axis used for TP, EP and split-K sequence sharding.
    """

    mesh: Optional[Mesh] = None
    dp_axes: Tuple[str, ...] = ()
    tp_axis: Optional[str] = None

    @property
    def distributed(self) -> bool:
        return self.mesh is not None

    @property
    def tp_size(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for ax in self.dp_axes:
            n *= self.mesh.shape[ax]
        return n


LOCAL_CTX = ParallelCtx()


def _cast(x: jnp.ndarray, dtype_str: str) -> jnp.ndarray:
    return x.astype(jnp.dtype(dtype_str))


def constrain(x: jnp.ndarray, ctx: ParallelCtx, spec: P) -> jnp.ndarray:
    """with_sharding_constraint if distributed, else identity.

    Uses the bare-PartitionSpec form (ambient mesh): inside a partially-
    manual shard_map region (the hierarchical pod reduction) a
    NamedSharding over the full mesh would mix Manual and Auto axes.
    """
    if not ctx.distributed:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def batch_spec(ctx: ParallelCtx, *rest) -> P:
    """PartitionSpec with batch dim over DP axes followed by ``rest``."""
    return P(ctx.dp_axes if ctx.dp_axes else None, *rest)


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------


def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, key) -> Dict[str, jnp.ndarray]:
    dt = jnp.dtype(cfg.param_dtype)
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dt)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dt),
                "bias": jnp.zeros((cfg.d_model,), dt)}
    if cfg.norm == "nonparam_ln":        # OLMo: no affine params
        return {}
    raise ValueError(cfg.norm)


def apply_norm(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
               cfg: ModelConfig, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        xf = xf * params["scale"].astype(jnp.float32)
    else:  # layernorm / nonparam_ln
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        if params:
            xf = xf * params["scale"].astype(jnp.float32)
            if "bias" in params:
                xf = xf + params["bias"].astype(jnp.float32)
    return xf.astype(x.dtype)


def rms_norm_gated(x: jnp.ndarray, gate: jnp.ndarray,
                   scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Mamba2 gated RMSNorm: norm(x * silu(gate)) * scale."""
    xf = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x (B, S, H, D) with positions (S,) or (B, S); rotate-half convention."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        ang = ang[None, :, None, :]                   # (1, S, 1, D/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, :, None, :]                      # (B, S, 1, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key) -> Dict[str, jnp.ndarray]:
    dt = jnp.dtype(cfg.param_dtype)
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (d, h * dh), dt),
        "wk": dense_init(k2, (d, hkv * dh), dt),
        "wv": dense_init(k3, (d, hkv * dh), dt),
        "wo": dense_init(k4, (h * dh, d), dt, fan_in=h * dh),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _qk_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def attention_qkv(params, x: jnp.ndarray, cfg: ModelConfig,
                  positions: jnp.ndarray):
    """Project to rotated q, k and v. Returns (q, k, v) in (B,S,H,Dh)."""
    b, s, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cdt = jnp.dtype(cfg.compute_dtype)
    q = (x @ _cast(params["wq"], cfg.compute_dtype)).reshape(b, s, h, dh)
    k = (x @ _cast(params["wk"], cfg.compute_dtype)).reshape(b, s, hkv, dh)
    v = (x @ _cast(params["wv"], cfg.compute_dtype)).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = _qk_norm(q, params["q_norm"])
        k = _qk_norm(k, params["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q.astype(cdt), k.astype(cdt), v.astype(cdt)


def attention_block(params, x: jnp.ndarray, cfg: ModelConfig,
                    ctx: ParallelCtx, positions: jnp.ndarray,
                    q_offset: int = 0, return_kv: bool = False):
    """Full-sequence causal attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = attention_qkv(params, x, cfg, positions)
    q = constrain(q, ctx, batch_spec(ctx, None, ctx.tp_axis, None))
    k = constrain(k, ctx, batch_spec(ctx, None,
                                     ctx.tp_axis if cfg.num_kv_heads >= ctx.tp_size else None,
                                     None))
    v = constrain(v, ctx, batch_spec(ctx, None,
                                     ctx.tp_axis if cfg.num_kv_heads >= ctx.tp_size else None,
                                     None))
    out = attn_ops.flash_attention(
        q, k, v, causal=True, q_offset=q_offset,
        impl=cfg.attention_impl if s > 1 else "dense",
        interpret=(s > 1 and cfg.attention_impl == "pallas" and
                   compat.pallas_interpret_fallback(
                       "flash attention (attention_impl='pallas')")))
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    y = out @ _cast(params["wo"], cfg.compute_dtype)
    y = constrain(y, ctx, batch_spec(ctx, None, None))
    if return_kv:
        return y, (k, v)
    return y


# --------------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# --------------------------------------------------------------------------


def init_mla(cfg: ModelConfig, key) -> Dict[str, jnp.ndarray]:
    dt = jnp.dtype(cfg.param_dtype)
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qd = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 8)
    p: Dict[str, jnp.ndarray] = {
        "w_dkv": dense_init(ks[0], (d, m.kv_lora_rank), dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "w_kr": dense_init(ks[1], (d, m.rope_head_dim), dt),
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, h * m.nope_head_dim), dt),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, h * m.v_head_dim), dt),
        "wo": dense_init(ks[4], (h * m.v_head_dim, d), dt),
    }
    if m.q_lora_rank > 0:
        p["w_dq"] = dense_init(ks[5], (d, m.q_lora_rank), dt)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dt)
        p["w_uq"] = dense_init(ks[6], (m.q_lora_rank, h * qd), dt)
    else:
        p["wq"] = dense_init(ks[5], (d, h * qd), dt)
    return p


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def mla_queries(params, x, cfg: ModelConfig, positions):
    """q split into (q_nope (B,S,H,dn), q_rope (B,S,H,dr))."""
    b, s, _ = x.shape
    m, h = cfg.mla, cfg.num_heads
    qd = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank > 0:
        ql = _rms(x @ _cast(params["w_dq"], cfg.compute_dtype), params["q_norm"])
        q = (ql @ _cast(params["w_uq"], cfg.compute_dtype)).reshape(b, s, h, qd)
    else:
        q = (x @ _cast(params["wq"], cfg.compute_dtype)).reshape(b, s, h, qd)
    q_nope = q[..., :m.nope_head_dim]
    q_rope = apply_rope(q[..., m.nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_latent(params, x, cfg: ModelConfig, positions):
    """Compressed KV latent: (c_kv (B,S,r), k_rope (B,S,dr))."""
    c_kv = _rms(x @ _cast(params["w_dkv"], cfg.compute_dtype), params["kv_norm"])
    k_r = x @ _cast(params["w_kr"], cfg.compute_dtype)
    k_r = apply_rope(k_r[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_r


def mla_block(params, x, cfg: ModelConfig, ctx: ParallelCtx,
              positions, q_offset: int = 0, return_kv: bool = False):
    """Train/prefill MLA: decompress per-head k/v, run flash attention."""
    b, s, _ = x.shape
    m, h = cfg.mla, cfg.num_heads
    q_nope, q_rope = mla_queries(params, x, cfg, positions)
    c_kv, k_r = mla_latent(params, x, cfg, positions)
    k_nope = (c_kv @ _cast(params["w_uk"], cfg.compute_dtype)
              ).reshape(b, s, h, m.nope_head_dim)
    v = (c_kv @ _cast(params["w_uv"], cfg.compute_dtype)
         ).reshape(b, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :], (b, s, h, m.rope_head_dim))],
        axis=-1)
    q = constrain(q, ctx, batch_spec(ctx, None, ctx.tp_axis, None))
    k = constrain(k, ctx, batch_spec(ctx, None, ctx.tp_axis, None))
    v = constrain(v, ctx, batch_spec(ctx, None, ctx.tp_axis, None))
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    # pad v to qk head dim so the kernel sees uniform D, then slice back
    dqk = m.nope_head_dim + m.rope_head_dim
    if m.v_head_dim < dqk:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dqk - m.v_head_dim)))
    out = attn_ops.flash_attention(
        q, k, v, causal=True, q_offset=q_offset, softmax_scale=scale,
        impl=cfg.attention_impl if s > 1 else "dense",
        interpret=(s > 1 and cfg.attention_impl == "pallas" and
                   compat.pallas_interpret_fallback(
                       "MLA flash attention (attention_impl='pallas')")))
    out = out[..., :m.v_head_dim].reshape(b, s, h * m.v_head_dim)
    y = out @ _cast(params["wo"], cfg.compute_dtype)
    y = constrain(y, ctx, batch_spec(ctx, None, None))
    if return_kv:
        # cache the *compressed* latent (the MLA decode-path optimization)
        return y, (c_kv, k_r)
    return y


# --------------------------------------------------------------------------
# Dense MLP
# --------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None,
             d_model: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    dt = jnp.dtype(cfg.param_dtype)
    d = d_model or cfg.d_model
    ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {"w_gate": dense_init(k1, (d, ff), dt),
                "w_up": dense_init(k2, (d, ff), dt),
                "w_down": dense_init(k3, (ff, d), dt, fan_in=ff)}
    return {"w_up": dense_init(k1, (d, ff), dt),
            "w_down": dense_init(k2, (ff, d), dt, fan_in=ff)}


def mlp_block(params, x: jnp.ndarray, cfg: ModelConfig,
              ctx: ParallelCtx) -> jnp.ndarray:
    cdt = cfg.compute_dtype
    if "w_gate" in params:
        g = x @ _cast(params["w_gate"], cdt)
        u = x @ _cast(params["w_up"], cdt)
        g = constrain(g, ctx, batch_spec(ctx, None, ctx.tp_axis))
        u = constrain(u, ctx, batch_spec(ctx, None, ctx.tp_axis))
        act = jax.nn.silu(g) if cfg.activation == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(x @ _cast(params["w_up"], cdt))
        h = constrain(h, ctx, batch_spec(ctx, None, ctx.tp_axis))
    y = h @ _cast(params["w_down"], cdt)
    return constrain(y, ctx, batch_spec(ctx, None, None))


# --------------------------------------------------------------------------
# Mixture of Experts (GShard-style top-k, sort-free capacity dispatch)
# --------------------------------------------------------------------------
#
# Expert parallelism exploits that activations are replicated over the TP
# ("model") axis between blocks: each model-rank owns E/tp experts, selects
# the tokens routed to *its* experts locally (no all-to-all), runs its
# expert FFNs, scatters back, and a single psum over the model axis merges
# expert contributions — the same collective Megatron pays for a dense FFN.


def init_moe(cfg: ModelConfig, key) -> Dict[str, jnp.ndarray]:
    dt = jnp.dtype(cfg.param_dtype)
    mo = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p: Dict[str, jnp.ndarray] = {
        "router": dense_init(ks[0], (d, mo.num_experts), dt),
        "w_gate": dense_init(ks[1], (mo.num_experts, d, mo.expert_d_ff), dt,
                             fan_in=d),
        "w_up": dense_init(ks[2], (mo.num_experts, d, mo.expert_d_ff), dt,
                           fan_in=d),
        "w_down": dense_init(ks[3], (mo.num_experts, mo.expert_d_ff, d), dt,
                             fan_in=mo.expert_d_ff),
    }
    if mo.num_shared_experts > 0:
        ff = mo.shared_d_ff * mo.num_shared_experts
        p["shared"] = init_mlp(cfg, ks[4], d_ff=ff)
    return p


def _moe_compute_local(x2d: jnp.ndarray, gates: jnp.ndarray,
                       eidx: jnp.ndarray, w_gate, w_up, w_down,
                       e_start: int, e_local: int, capacity: int,
                       cfg: ModelConfig) -> jnp.ndarray:
    """Dispatch tokens to experts [e_start, e_start+e_local), compute, combine.

    x2d (T, d); gates/eidx (T, k). Returns this expert-range's contribution
    (T, d) — caller sums contributions across ranges (psum over EP axis).
    """
    t, d = x2d.shape
    k = eidx.shape[1]
    flat_e = eidx.reshape(-1)                         # (T*k,) token-major
    local_e = flat_e - e_start
    valid = (local_e >= 0) & (local_e < e_local)
    local_e_c = jnp.where(valid, local_e, 0)
    # position of each (token, expert) slot within its expert queue
    onehot = jax.nn.one_hot(local_e_c, e_local, dtype=jnp.int32) * valid[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot          # exclusive prefix count
    pos_in_e = jnp.take_along_axis(pos, local_e_c[:, None], axis=1)[:, 0]
    keep = valid & (pos_in_e < capacity)
    slot_e = jnp.where(keep, local_e_c, e_local).reshape(t, k)   # OOB -> drop
    slot_c = jnp.where(keep, pos_in_e, capacity).reshape(t, k)
    # gather tokens into (E_local, C, d) buffers; loop over the k routing
    # slots so we never materialize a (T*k, d) gather
    buf = jnp.zeros((e_local, capacity, d), x2d.dtype)
    for j in range(k):
        buf = buf.at[slot_e[:, j], slot_c[:, j]].add(x2d, mode="drop")
    # expert FFN (batched over local experts)
    cdt = cfg.compute_dtype
    g = jnp.einsum("ecd,edf->ecf", buf, _cast(w_gate, cdt))
    u = jnp.einsum("ecd,edf->ecf", buf, _cast(w_up, cdt))
    act = jax.nn.silu(g) if cfg.activation in ("swiglu", "silu") else jax.nn.gelu(g)
    eo = jnp.einsum("ecf,efd->ecd", act * u, _cast(w_down, cdt))
    # combine back, weighted by router gates
    y = jnp.zeros((t, d), eo.dtype)
    for j in range(k):
        gj = gates[:, j].astype(eo.dtype)
        y = y + eo.at[slot_e[:, j], slot_c[:, j]].get(
            mode="fill", fill_value=0.0) * gj[:, None]
    return y


def _router(params, x2d: jnp.ndarray, cfg: ModelConfig):
    """Top-k routing. Returns (gates (T,k) f32, eidx (T,k) i32, aux_loss)."""
    mo = cfg.moe
    # native-dtype GEMM with f32 accumulation — a plain astype(f32) of
    # x2d materializes a (T, d) fp32 copy (XLA hoists it out of loops)
    logits = jax.lax.dot_general(
        x2d, _cast(params["router"], x2d.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, mo.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # GShard load-balancing aux loss: E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                        # mean router prob
    ce = jnp.mean(
        jax.nn.one_hot(eidx[:, 0], mo.num_experts, dtype=jnp.float32), axis=0)
    aux = mo.num_experts * jnp.sum(me * ce) * mo.aux_loss_coef
    return gates, eidx, aux


def moe_block(params, x: jnp.ndarray, cfg: ModelConfig,
              ctx: ParallelCtx, train: bool = True
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss). x (B, S, d).

    ``train=False`` (prefill/decode) uses the generous eval capacity —
    and for single-token decode the exact no-drop capacity — since
    capacity dropping is a training-time regularizer, not serving
    behaviour.
    """
    mo = cfg.moe
    b, s, d = x.shape

    def capacity_for(tokens: int, experts: int) -> int:
        if not train and s == 1:
            return max(8, -(-tokens * mo.top_k // 8) * 8)   # no-drop decode
        cf = mo.capacity_factor if train else mo.capacity_factor_eval
        cap = int(math.ceil(tokens * mo.top_k * cf / experts))
        return max(8, -(-cap // 8) * 8)                # pad to multiple of 8

    if not ctx.distributed or ctx.tp_axis is None:
        x2d = x.reshape(b * s, d)
        gates, eidx, aux = _router(params, x2d, cfg)
        y = _moe_compute_local(
            x2d, gates.astype(x.dtype), eidx,
            params["w_gate"], params["w_up"], params["w_down"],
            0, mo.num_experts, capacity_for(b * s, mo.num_experts), cfg)
        out = y.reshape(b, s, d)
    else:
        tp = ctx.tp_size
        e_local = mo.num_experts // tp
        dp = ctx.dp_size
        t_local = (b // dp) * s if b >= dp else s
        cap = capacity_for(t_local, mo.num_experts)
        mesh = ctx.mesh
        dp_axes = ctx.dp_axes

        def sharded_moe(x_loc, router_w, w_gate, w_up, w_down):
            bl, sl, dl = x_loc.shape
            x2d = x_loc.reshape(bl * sl, dl)
            gates, eidx, aux = _router({"router": router_w}, x2d, cfg)
            rank = jax.lax.axis_index(ctx.tp_axis)
            y = _moe_compute_local(
                x2d, gates.astype(x_loc.dtype), eidx,
                w_gate, w_up, w_down,
                rank * e_local, e_local, cap, cfg)
            y = jax.lax.psum(y, ctx.tp_axis)
            aux = aux / jax.lax.psum(1.0, dp_axes) if dp_axes else aux
            aux = jax.lax.psum(aux, dp_axes) if dp_axes else aux
            return y.reshape(bl, sl, dl), aux

        spec_x = P(dp_axes if dp_axes else None, None, None)
        # mesh=None -> ambient mesh: a concrete all-Auto mesh object
        # would clash with the partially-manual context inside the
        # hierarchical pod reduction (nested shard_map)
        out, aux = jax.shard_map(
            sharded_moe, mesh=None,
            in_specs=(spec_x, P(None, None),
                      P(ctx.tp_axis, None, None), P(ctx.tp_axis, None, None),
                      P(ctx.tp_axis, None, None)),
            out_specs=(spec_x, P()),
            check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])

    if mo.num_shared_experts > 0:
        out = out + mlp_block(params["shared"], x, cfg, ctx)
    return out, (aux if isinstance(aux, jnp.ndarray) else jnp.float32(aux))
