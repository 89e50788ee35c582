"""Composable decoder stack covering all assigned architecture families.

Stack plans (derived from the config):
  uniform — dense / moe / mla archs: one scanned stack of identical
            (attention, ffn) layers. ``lax.scan`` over stacked params with
            a configurable remat policy.
  zamba   — Mamba2 backbone; a single weight-shared attention block is
            applied after every ``hybrid.attn_every`` mamba layers
            (outer scan over groups, inner scan over mamba layers).
  xlstm   — alternating mLSTM / sLSTM blocks, scanned over pairs.

Public API (used by model.py):
  init_params(cfg, key)                         -> params pytree
  hidden_states(params, embeds, cfg, ctx)       -> (hidden, aux_loss)
  prefill(params, embeds, cfg, ctx, max_len)    -> (hidden, cache)
  decode_step(params, embeds, cfg, ctx, cache, pos) -> (hidden, cache)
  embed_tokens / unembed
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import xlstm as xl
from repro.models.blocks import (LOCAL_CTX, ParallelCtx, _cast, apply_norm,
                                 attention_block, batch_spec, constrain,
                                 dense_init, embed_init, init_attention,
                                 init_mla, init_mlp, init_moe, init_norm,
                                 mla_block, mlp_block, moe_block)
from repro.models.kvcache import (PagedLayout, attention_decode,
                                  attention_decode_paged, init_gqa_cache,
                                  init_gqa_paged_cache, init_mla_cache,
                                  init_mla_paged_cache, mla_decode,
                                  mla_decode_paged)
from repro.models.ssm import (init_mamba, mamba_block, mamba_decode_step,
                              mamba_dims)


def stack_plan(cfg: ModelConfig) -> str:
    if cfg.xlstm.enabled:
        return "xlstm"
    if cfg.hybrid.enabled:
        return "zamba"
    if cfg.ssm.enabled:
        return "mamba"
    return "uniform"


def _stacked_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)      # "full"


# --------------------------------------------------------------------------
# uniform layer (dense / moe / mla)
# --------------------------------------------------------------------------


def _attn_init(cfg: ModelConfig, key):
    return init_mla(cfg, key) if cfg.mla.enabled else init_attention(cfg, key)


def init_uniform_layer(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    p: Dict[str, Any] = {
        "ln1": init_norm(cfg, ks[0]),
        "attn": _attn_init(cfg, ks[1]),
        "ln2": init_norm(cfg, ks[2]),
    }
    if cfg.moe.enabled:
        p["moe"] = init_moe(cfg, ks[3])
        if cfg.moe.dense_residual:
            p["dense"] = init_mlp(cfg, ks[4], d_ff=cfg.d_ff)
    else:
        p["mlp"] = init_mlp(cfg, ks[3])
    return p


def apply_uniform_layer(p, x: jnp.ndarray, cfg: ModelConfig,
                        ctx: ParallelCtx, positions: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.mla.enabled:
        a = mla_block(p["attn"], h, cfg, ctx, positions)
    else:
        a = attention_block(p["attn"], h, cfg, ctx, positions)
    x = x + a
    h2 = apply_norm(p["ln2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        m, aux = moe_block(p["moe"], h2, cfg, ctx)
        if "dense" in p:
            m = m + mlp_block(p["dense"], h2, cfg, ctx)
    else:
        m = mlp_block(p["mlp"], h2, cfg, ctx)
    return x + m, aux


def apply_uniform_layer_prefill(p, x, cfg, ctx, positions):
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.mla.enabled:
        a, kv = mla_block(p["attn"], h, cfg, ctx, positions, return_kv=True)
    else:
        a, kv = attention_block(p["attn"], h, cfg, ctx, positions,
                                return_kv=True)
    x = x + a
    h2 = apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, _ = moe_block(p["moe"], h2, cfg, ctx, train=False)
        if "dense" in p:
            m = m + mlp_block(p["dense"], h2, cfg, ctx)
    else:
        m = mlp_block(p["mlp"], h2, cfg, ctx)
    return x + m, kv


def apply_uniform_layer_decode(p, x, cfg, ctx, cache_l, pos):
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.mla.enabled:
        a, new_cache = mla_decode(p["attn"], h, cfg, ctx,
                                  cache_l["c_kv"], cache_l["k_rope"], pos)
        new_cache = {"c_kv": new_cache[0], "k_rope": new_cache[1]}
    else:
        a, new_cache = attention_decode(p["attn"], h, cfg, ctx,
                                        cache_l["k"], cache_l["v"], pos)
        new_cache = {"k": new_cache[0], "v": new_cache[1]}
    x = x + a
    h2 = apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, _ = moe_block(p["moe"], h2, cfg, ctx, train=False)
        if "dense" in p:
            m = m + mlp_block(p["dense"], h2, cfg, ctx)
    else:
        m = mlp_block(p["mlp"], h2, cfg, ctx)
    return x + m, new_cache


def apply_uniform_layer_decode_paged(p, x, cfg, ctx, pool, layer,
                                     block_tables, kv_lens):
    """Paged twin of apply_uniform_layer_decode: layer ``layer`` of the
    whole layer-stacked pool (L, N, bs, ...), addressed through
    per-sequence block tables + kv_lens instead of a contiguous
    (B, S_max, ...) slab and a scalar pos. Returns the pool with the
    layer's new token written."""
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.mla.enabled:
        a, new_cache = mla_decode_paged(p["attn"], h, cfg, ctx,
                                        pool["c_kv"], pool["k_rope"],
                                        layer, block_tables, kv_lens)
        new_cache = {"c_kv": new_cache[0], "k_rope": new_cache[1]}
    else:
        a, new_cache = attention_decode_paged(p["attn"], h, cfg, ctx,
                                              pool["k"], pool["v"], layer,
                                              block_tables, kv_lens)
        new_cache = {"k": new_cache[0], "v": new_cache[1]}
    x = x + a
    h2 = apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, _ = moe_block(p["moe"], h2, cfg, ctx, train=False)
        if "dense" in p:
            m = m + mlp_block(p["dense"], h2, cfg, ctx)
    else:
        m = mlp_block(p["mlp"], h2, cfg, ctx)
    return x + m, new_cache


# --------------------------------------------------------------------------
# zamba layers (mamba backbone + shared attention block)
# --------------------------------------------------------------------------


def init_mamba_layer(cfg: ModelConfig, key) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    return {"ln": init_norm(cfg, k1), "mamba": init_mamba(cfg, k2)}


def init_shared_attn(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg, ks[0]), "attn": init_attention(cfg, ks[1])}
    if cfg.hybrid.shared_attn_d_ff > 0:
        p["ln2"] = init_norm(cfg, ks[2])
        p["mlp"] = init_mlp(cfg, ks[3], d_ff=cfg.hybrid.shared_attn_d_ff)
    return p


def _apply_shared_attn(p, x, cfg, ctx, positions):
    h = apply_norm(p["ln1"], x, cfg)
    x = x + attention_block(p["attn"], h, cfg, ctx, positions)
    if "mlp" in p:
        h2 = apply_norm(p["ln2"], x, cfg)
        x = x + mlp_block(p["mlp"], h2, cfg, ctx)
    return x


def _apply_shared_attn_prefill(p, x, cfg, ctx, positions):
    h = apply_norm(p["ln1"], x, cfg)
    a, kv = attention_block(p["attn"], h, cfg, ctx, positions,
                            return_kv=True)
    x = x + a
    if "mlp" in p:
        h2 = apply_norm(p["ln2"], x, cfg)
        x = x + mlp_block(p["mlp"], h2, cfg, ctx)
    return x, kv


def _apply_shared_attn_decode(p, x, cfg, ctx, k_cache, v_cache, pos):
    h = apply_norm(p["ln1"], x, cfg)
    a, (k_cache, v_cache) = attention_decode(p["attn"], h, cfg, ctx,
                                             k_cache, v_cache, pos)
    x = x + a
    if "mlp" in p:
        h2 = apply_norm(p["ln2"], x, cfg)
        x = x + mlp_block(p["mlp"], h2, cfg, ctx)
    return x, (k_cache, v_cache)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    plan = stack_plan(cfg)
    ke, kl, kn, kh, ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    params: Dict[str, Any] = {}
    if cfg.frontend == "token":
        params["embed"] = embed_init(ke, (cfg.vocab_size, cfg.d_model), dt)
    params["final_norm"] = init_norm(cfg, kn)
    if not (cfg.tie_embeddings and cfg.frontend == "token"):
        params["lm_head"] = dense_init(
            kh, (cfg.d_model, cfg.vocab_size), dt)

    if plan == "uniform":
        params["layers"] = _stacked_init(
            lambda k: init_uniform_layer(cfg, k), kl, cfg.num_layers)
    elif plan == "mamba":
        params["layers"] = _stacked_init(
            lambda k: init_mamba_layer(cfg, k), kl, cfg.num_layers)
    elif plan == "zamba":
        params["layers"] = _stacked_init(
            lambda k: init_mamba_layer(cfg, k), kl, cfg.num_layers)
        params["shared_attn"] = init_shared_attn(cfg, ks)
    elif plan == "xlstm":
        n_pairs = cfg.num_layers // 2
        k1, k2 = jax.random.split(kl)
        params["mlstm_layers"] = _stacked_init(
            lambda k: {"ln": init_norm(cfg, jax.random.fold_in(k, 0)),
                       "blk": xl.init_mlstm_block(cfg, k)}, k1, n_pairs)
        params["slstm_layers"] = _stacked_init(
            lambda k: {"ln": init_norm(cfg, jax.random.fold_in(k, 0)),
                       "blk": xl.init_slstm_block(cfg, k)}, k2, n_pairs)
    return params


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------


def embed_tokens(params, inputs: jnp.ndarray, cfg: ModelConfig,
                 ctx: ParallelCtx) -> jnp.ndarray:
    """inputs: token ids (B, S) int32 — or, for embedding_stub frontends,
    precomputed frame/patch embeddings (B, S, d_model)."""
    if cfg.frontend == "token":
        x = jnp.take(params["embed"], inputs, axis=0)
    else:
        x = inputs
    x = x.astype(jnp.dtype(cfg.compute_dtype))
    return constrain(x, ctx, batch_spec(ctx, None, None))


def unembed(params, hidden: jnp.ndarray, cfg: ModelConfig,
            ctx: ParallelCtx) -> jnp.ndarray:
    """hidden (..., d) -> logits (..., V)."""
    if cfg.tie_embeddings and cfg.frontend == "token":
        w = _cast(params["embed"], cfg.compute_dtype).T
    else:
        w = _cast(params["lm_head"], cfg.compute_dtype)
    logits = hidden @ w
    if cfg.logit_softcap > 0.0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return constrain(logits, ctx, batch_spec(ctx, None, ctx.tp_axis))


def lm_head_matrix(params, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings and cfg.frontend == "token":
        return _cast(params["embed"], cfg.compute_dtype).T
    return _cast(params["lm_head"], cfg.compute_dtype)


# --------------------------------------------------------------------------
# forward (training / scoring): hidden states, no cache
# --------------------------------------------------------------------------


def hidden_states(params, embeds: jnp.ndarray, cfg: ModelConfig,
                  ctx: ParallelCtx) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """embeds (B, S, d) -> (hidden (B, S, d), aux_loss scalar)."""
    plan = stack_plan(cfg)
    b, s, _ = embeds.shape
    positions = jnp.arange(s)

    def sp(x):
        # sequence-parallel residual stream: the layer-scan carry (which
        # full remat saves per layer) shards S over the model axis
        return constrain(x, ctx, batch_spec(ctx, ctx.tp_axis, None))

    x = sp(embeds)
    aux_total = jnp.zeros((), jnp.float32)

    if plan == "uniform":
        def body(carry, lp):
            x, aux = carry
            x2, a = apply_uniform_layer(lp, x, cfg, ctx, positions)
            return (sp(x2), aux + a), None
        body = _remat(body, cfg)
        if cfg.scan_layers:
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                             params["layers"])
        else:
            # unrolled stack (cfg.scan_layers=False): same per-layer
            # body, python loop instead of lax.scan. Required by
            # HetConfig.overlap="backward" — the staged layer-by-layer
            # backward is an unrolled program, and XLA compiles dots
            # inside a scan body differently from top-level dots
            # (last-bit fp differences), so bit-exact overlap needs the
            # monolithic path unrolled too. Costs an L-times-larger HLO.
            for layer in range(cfg.num_layers):
                lp = jax.tree.map(lambda a: a[layer], params["layers"])
                (x, aux_total), _ = body((x, aux_total), lp)

    elif plan == "mamba":
        def body(carry, lp):
            x2 = carry + mamba_block(
                lp["mamba"], apply_norm(lp["ln"], carry, cfg), cfg, ctx)
            return sp(x2), None
        body = _remat(body, cfg)
        x, _ = jax.lax.scan(body, x, params["layers"])

    elif plan == "zamba":
        every = cfg.hybrid.attn_every
        groups = cfg.num_layers // every
        gl = jax.tree.map(
            lambda a: a.reshape(groups, every, *a.shape[1:]),
            params["layers"])
        shared = params["shared_attn"]

        def group_body(carry, group_params):
            def inner(xc, lp):
                return xc + mamba_block(
                    lp["mamba"], apply_norm(lp["ln"], xc, cfg), cfg, ctx
                ), None
            xg, _ = jax.lax.scan(inner, carry, group_params)
            xg = _apply_shared_attn(shared, xg, cfg, ctx, positions)
            return sp(xg), None
        group_body = _remat(group_body, cfg)
        x, _ = jax.lax.scan(group_body, x, gl)

    elif plan == "xlstm":
        def pair_body(carry, lp):
            mp, sp_ = lp
            xc = carry + xl.mlstm_block(
                mp["blk"], apply_norm(mp["ln"], carry, cfg), cfg, ctx)
            xc = xc + xl.slstm_block(
                sp_["blk"], apply_norm(sp_["ln"], xc, cfg), cfg, ctx)
            return sp(xc), None
        pair_body = _remat(pair_body, cfg)
        x, _ = jax.lax.scan(pair_body, x,
                            (params["mlstm_layers"], params["slstm_layers"]))

    return apply_norm(params["final_norm"], x, cfg), aux_total


# --------------------------------------------------------------------------
# staged backward segments (HetConfig.overlap="backward")
#
# The backward-overlap pipeline needs gradients layer by layer, so the
# loss is decomposed into VJP-able segments over the uniform block
# stack (a jax.remat-style staged backward: the forward saves only the
# residual-stream carry at every layer boundary, and each segment's
# VJP recomputes its own activations — exactly what jax.checkpoint
# does inside the monolithic scan). Segment math is IDENTICAL to the
# hidden_states/loss_fn path: layer_fn is the scan body, head_fn is
# final-norm + LM head + weighted CE, embed_fn the token embedding, so
# with cfg.scan_layers=False the staged gradients are bit-identical to
# jax.grad of the monolithic objective (asserted by
# tests/test_overlap.py).
#
# Stage numbering (backward completion order): stage 0 = head
# (final_norm, lm_head / tied embed — lands first), stage s in [1, L]
# = layer L-s, stage L+1 = the embedding table (lands last; a tied
# table also receives a head-stage contribution, so its grad is only
# final at L+1). core/buckets.py::bucket_readiness maps these stages
# onto the flat bucket grid.
# --------------------------------------------------------------------------


def supports_staged_backward(cfg: ModelConfig) -> bool:
    """The staged backward covers the uniform stack plan (dense / moe /
    mla); the mamba/zamba/xlstm plans keep the scanned backward."""
    return stack_plan(cfg) == "uniform"


def head_param_keys(cfg: ModelConfig) -> Tuple[str, ...]:
    """Top-level param keys whose grads land at stage 0 (the head)."""
    if cfg.tie_embeddings and cfg.frontend == "token":
        return ("final_norm", "embed")
    return ("final_norm", "lm_head")


def staged_uniform_segments(cfg: ModelConfig, ctx: ParallelCtx, *,
                            label_smoothing: float = 0.0,
                            ce_impl: str = "reference") -> Dict[str, Any]:
    """The VJP-able segment functions of the uniform-stack objective.

    Returns a dict of pure functions (each vmap/vjp-able per DP rank):

      embed_fn(embed_params, inputs)        -> x0 (stage L+1 forward)
      layer_fn(lp, x, positions)            -> (x', aux_l) — the
                                               hidden_states scan body
      head_fn(head_params, x, labels, weights) -> (ce_sum, w_sum)

    The caller composes ``objective = ce_sum + (sum aux_l) *
    stop_grad(w_sum)`` (model.py's aggregation contract) and drives the
    backward newest-stage-first, handing each landed gradient to the
    bucket flush pipeline.
    """
    def sp(x):
        return constrain(x, ctx, batch_spec(ctx, ctx.tp_axis, None))

    def embed_fn(embed_params, inputs):
        return sp(embed_tokens(embed_params, inputs, cfg, ctx))

    def layer_fn(lp, x, positions):
        x2, a = apply_uniform_layer(lp, x, cfg, ctx, positions)
        return sp(x2), a

    def head_fn(head_params, x, labels, weights):
        hidden = apply_norm(head_params["final_norm"], x, cfg)
        b, s, d = hidden.shape
        lm_w = lm_head_matrix(head_params, cfg)
        from repro.kernels.cross_entropy import ops as ce_ops
        return ce_ops.weighted_cross_entropy(
            hidden.reshape(b * s, d), lm_w,
            labels.reshape(-1).astype(jnp.int32),
            weights.reshape(-1).astype(jnp.float32),
            label_smoothing=label_smoothing,
            logit_softcap=cfg.logit_softcap,
            impl=ce_impl)

    return {"embed_fn": embed_fn, "layer_fn": layer_fn,
            "head_fn": head_fn, "head_keys": head_param_keys(cfg)}


def pipeline_stage_fns(cfg: ModelConfig, ctx: ParallelCtx,
                       stage_ranges, *,
                       label_smoothing: float = 0.0,
                       ce_impl: str = "reference") -> Dict[str, Any]:
    """staged_uniform_segments generalized so a segment boundary can be
    a pipeline cut (core/pipeline.py StagePlan).

    ``stage_ranges``: list of (start, stop) contiguous layer ranges
    covering [0, num_layers). Returns the staged_uniform_segments dict
    plus ``stage_fwd``: a list of per-stage VJP-able functions

      stage_fwd[s](layer_slice, x, aux, positions) -> (x', aux')

    where ``layer_slice`` is the params["layers"] pytree sliced to the
    stage's leading-dim range. Each stage is the same per-layer
    ``layer_fn`` chain as the monolithic unrolled stack — aux threads
    through the carry so the cross-stage composition reproduces
    ``hidden_states``'s add order bit-for-bit. The embedding belongs to
    stage 0 (run embed_fn before stage_fwd[0]) and the head to the last
    stage (run head_fn after stage_fwd[-1]) — transformer-side contract
    for ``launch/steps.py::_build_pipeline_step``.
    """
    if stack_plan(cfg) != "uniform":
        raise ValueError(
            f"pipeline stages require the uniform stack plan; "
            f"{cfg.name} uses '{stack_plan(cfg)}'")
    ranges = [(int(a), int(b)) for a, b in stage_ranges]
    covered = 0
    for s, (start, stop) in enumerate(ranges):
        if start != covered or stop <= start:
            raise ValueError(
                f"stage_ranges must tile [0, {cfg.num_layers}) "
                f"contiguously; stage {s} got [{start}, {stop}) after "
                f"{covered} covered layers")
        covered = stop
    if covered != cfg.num_layers:
        raise ValueError(
            f"stage_ranges cover {covered} layers, model has "
            f"{cfg.num_layers}")

    segs = staged_uniform_segments(
        cfg, ctx, label_smoothing=label_smoothing, ce_impl=ce_impl)
    layer_fn = segs["layer_fn"]

    def make_stage(num_layers_s):
        def stage_fwd(layer_slice, x, aux, positions):
            for i in range(num_layers_s):
                lp = jax.tree.map(lambda a: a[i], layer_slice)
                x, a = layer_fn(lp, x, positions)
                aux = aux + a
            return x, aux
        return stage_fwd

    segs["stage_fwd"] = [make_stage(stop - start) for start, stop in ranges]
    segs["stage_ranges"] = ranges
    return segs


# --------------------------------------------------------------------------
# prefill: forward + cache construction
# --------------------------------------------------------------------------


def prefill(params, embeds: jnp.ndarray, cfg: ModelConfig, ctx: ParallelCtx,
            max_len: int) -> Tuple[jnp.ndarray, Any]:
    """Returns (hidden (B, S, d), cache). Cache covers max_len positions."""
    plan = stack_plan(cfg)
    b, s, _ = embeds.shape
    positions = jnp.arange(s)
    x = embeds
    pad = max_len - s

    def pad_cache(kv):       # (L?, B, S, ...) -> (..., max_len, ...)
        return jnp.pad(kv, ((0, 0), (0, 0), (0, pad)) +
                       ((0, 0),) * (kv.ndim - 3)) if pad else kv

    if plan == "uniform":
        def body(xc, lp):
            x2, kv = apply_uniform_layer_prefill(lp, xc, cfg, ctx, positions)
            return x2, kv
        x, kvs = jax.lax.scan(body, x, params["layers"])
        if cfg.mla.enabled:
            cache = {"c_kv": pad_cache(kvs[0]), "k_rope": pad_cache(kvs[1])}
        else:
            cache = {"k": pad_cache(kvs[0]), "v": pad_cache(kvs[1])}

    elif plan == "mamba":
        def body(xc, lp):
            y, st = mamba_block(lp["mamba"], apply_norm(lp["ln"], xc, cfg),
                                cfg, ctx, return_state=True)
            return xc + y, st
        x, states = jax.lax.scan(body, x, params["layers"])
        cache = {"conv": states[0], "ssm": states[1]}

    elif plan == "zamba":
        every = cfg.hybrid.attn_every
        groups = cfg.num_layers // every
        gl = jax.tree.map(
            lambda a: a.reshape(groups, every, *a.shape[1:]),
            params["layers"])
        shared = params["shared_attn"]

        def group_body(xc, group_params):
            def inner(xg, lp):
                y, st = mamba_block(
                    lp["mamba"], apply_norm(lp["ln"], xg, cfg), cfg, ctx,
                    return_state=True)
                return xg + y, st
            xg, states = jax.lax.scan(inner, xc, group_params)
            xg, kv = _apply_shared_attn_prefill(shared, xg, cfg, ctx,
                                                positions)
            return xg, (states, kv)
        x, (states, kvs) = jax.lax.scan(group_body, x, gl)
        cache = {"conv": states[0].reshape(cfg.num_layers,
                                           *states[0].shape[2:]),
                 "ssm": states[1].reshape(cfg.num_layers,
                                          *states[1].shape[2:]),
                 "attn_k": pad_cache(kvs[0]), "attn_v": pad_cache(kvs[1])}

    elif plan == "xlstm":
        def pair_body(xc, lp):
            mp, sp = lp
            ym, mst = xl.mlstm_block(
                mp["blk"], apply_norm(mp["ln"], xc, cfg), cfg, ctx,
                return_state=True)
            xc = xc + ym
            ys, sst = xl.slstm_block(
                sp["blk"], apply_norm(sp["ln"], xc, cfg), cfg, ctx,
                return_state=True)
            return xc + ys, (mst, sst)
        x, (mst, sst) = jax.lax.scan(
            pair_body, x, (params["mlstm_layers"], params["slstm_layers"]))
        cache = {"mlstm": mst, "slstm": sst}

    return apply_norm(params["final_norm"], x, cfg), cache


# --------------------------------------------------------------------------
# decode: one token, cache update
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    """Zero-initialized cache matching prefill()'s output structure."""
    plan = stack_plan(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    L = cfg.num_layers
    if plan == "uniform":
        if cfg.mla.enabled:
            return init_mla_cache(cfg, L, batch, max_len)
        return init_gqa_cache(cfg, L, batch, max_len)
    if plan == "mamba":
        d_inner, nheads, conv_ch, _ = mamba_dims(cfg)
        return {
            "conv": jnp.zeros((L, batch, cfg.ssm.conv_kernel - 1, conv_ch),
                              cdt),
            "ssm": jnp.zeros((L, batch, nheads, cfg.ssm.head_dim,
                              cfg.ssm.state_dim), cdt),
        }
    if plan == "zamba":
        d_inner, nheads, conv_ch, _ = mamba_dims(cfg)
        groups = cfg.num_layers // cfg.hybrid.attn_every
        return {
            "conv": jnp.zeros((L, batch, cfg.ssm.conv_kernel - 1, conv_ch),
                              cdt),
            "ssm": jnp.zeros((L, batch, nheads, cfg.ssm.head_dim,
                              cfg.ssm.state_dim), cdt),
            "attn_k": jnp.zeros((groups, batch, max_len, cfg.num_kv_heads,
                                 cfg.head_dim), cdt),
            "attn_v": jnp.zeros((groups, batch, max_len, cfg.num_kv_heads,
                                 cfg.head_dim), cdt),
        }
    if plan == "xlstm":
        n_pairs = cfg.num_layers // 2
        d_inner, h, dk = xl.mlstm_dims(cfg)
        k = cfg.xlstm.conv_kernel
        d = cfg.d_model
        return {
            "mlstm": (jnp.zeros((n_pairs, batch, k - 1, d_inner), cdt),
                      (jnp.zeros((n_pairs, batch, h, dk, dk), jnp.float32),
                       jnp.zeros((n_pairs, batch, h, dk), jnp.float32),
                       jnp.full((n_pairs, batch, h), -1e30, jnp.float32))),
            "slstm": (jnp.zeros((n_pairs, batch, k - 1, d), cdt),
                      (jnp.zeros((n_pairs, batch, d), jnp.float32),
                       jnp.zeros((n_pairs, batch, d), jnp.float32),
                       jnp.full((n_pairs, batch, d), -1e30, jnp.float32),
                       jnp.zeros((n_pairs, batch, d), jnp.float32))),
        }
    raise ValueError(plan)


def decode_step(params, embeds: jnp.ndarray, cfg: ModelConfig,
                ctx: ParallelCtx, cache: Any, pos: jnp.ndarray
                ) -> Tuple[jnp.ndarray, Any]:
    """embeds (B, 1, d) new-token embeddings -> (hidden (B, 1, d), cache)."""
    plan = stack_plan(cfg)
    x = embeds

    if plan == "uniform":
        def body(xc, inp):
            lp, cache_l = inp
            x2, new_cache = apply_uniform_layer_decode(lp, xc, cfg, ctx,
                                                       cache_l, pos)
            return x2, new_cache
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))

    elif plan == "mamba":
        def body(xc, inp):
            lp, conv, ssm = inp
            y, (conv2, ssm2) = mamba_decode_step(
                lp["mamba"], apply_norm(lp["ln"], xc, cfg), cfg, ctx,
                (conv, ssm))
            return xc + y, (conv2, ssm2)
        x, (conv2, ssm2) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]))
        new_cache = {"conv": conv2, "ssm": ssm2}

    elif plan == "zamba":
        every = cfg.hybrid.attn_every
        groups = cfg.num_layers // every
        gl = jax.tree.map(
            lambda a: a.reshape(groups, every, *a.shape[1:]),
            params["layers"])
        gconv = cache["conv"].reshape(groups, every, *cache["conv"].shape[1:])
        gssm = cache["ssm"].reshape(groups, every, *cache["ssm"].shape[1:])
        shared = params["shared_attn"]

        def group_body(xc, inp):
            gp, conv_g, ssm_g, k_g, v_g = inp

            def inner(xg, lp_state):
                lp, conv, ssm = lp_state
                y, st = mamba_decode_step(
                    lp["mamba"], apply_norm(lp["ln"], xg, cfg), cfg, ctx,
                    (conv, ssm))
                return xg + y, st
            xg, (conv2, ssm2) = jax.lax.scan(inner, xc,
                                             (gp, conv_g, ssm_g))
            xg, (k2, v2) = _apply_shared_attn_decode(shared, xg, cfg, ctx,
                                                     k_g, v_g, pos)
            return xg, (conv2, ssm2, k2, v2)
        x, (conv2, ssm2, k2, v2) = jax.lax.scan(
            group_body, x, (gl, gconv, gssm, cache["attn_k"],
                            cache["attn_v"]))
        new_cache = {
            "conv": conv2.reshape(cfg.num_layers, *conv2.shape[2:]),
            "ssm": ssm2.reshape(cfg.num_layers, *ssm2.shape[2:]),
            "attn_k": k2, "attn_v": v2,
        }

    elif plan == "xlstm":
        def pair_body(xc, inp):
            mp, sp, mst_conv, mst_cell, sst_conv, sst_cell = inp
            ym, mst2 = xl.mlstm_block_decode(
                mp["blk"], apply_norm(mp["ln"], xc, cfg), cfg, ctx,
                (mst_conv, mst_cell))
            xc = xc + ym
            ys, sst2 = xl.slstm_block_decode(
                sp["blk"], apply_norm(sp["ln"], xc, cfg), cfg, ctx,
                (sst_conv, sst_cell))
            return xc + ys, (mst2, sst2)
        mconv, mcell = cache["mlstm"]
        sconv, scell = cache["slstm"]
        x, (mst2, sst2) = jax.lax.scan(
            pair_body, x, (params["mlstm_layers"], params["slstm_layers"],
                           mconv, mcell, sconv, scell))
        new_cache = {"mlstm": (mst2[0], mst2[1]),
                     "slstm": (sst2[0], sst2[1])}

    return apply_norm(params["final_norm"], x, cfg), new_cache


# --------------------------------------------------------------------------
# paged decode: per-sequence depths over a shared block pool
# --------------------------------------------------------------------------


def init_paged_cache(cfg: ModelConfig, layout: PagedLayout) -> Any:
    """Zero-initialized paged block pool (uniform attention stacks only:
    recurrent plans keep O(1) state per sequence — nothing to page)."""
    plan = stack_plan(cfg)
    if plan != "uniform":
        raise ValueError(
            f"paged KV cache supports the uniform attention stack only, "
            f"got stack plan {plan!r}")
    if cfg.mla.enabled:
        return init_mla_paged_cache(cfg, cfg.num_layers, layout)
    return init_gqa_paged_cache(cfg, cfg.num_layers, layout)


def decode_step_paged(params, embeds: jnp.ndarray, cfg: ModelConfig,
                      ctx: ParallelCtx, cache: Any,
                      block_tables: jnp.ndarray, kv_lens: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, Any]:
    """One token per sequence against the paged pool.

    embeds (B, 1, d); block_tables (B, MB) int32; kv_lens (B,) int32 —
    each sequence attends to its own kv_lens[i] cached tokens plus the
    new one. Returns (hidden (B, 1, d), cache).

    The layer scan carries the whole layer-stacked pool with the hidden
    state and runs over the layers' parameters and indices: each layer
    writes its tokens into the carried pool in place and its attention
    reads its own blocks out of it, so no layer's pool is sliced out or
    stacked back.
    """
    if stack_plan(cfg) != "uniform":
        raise ValueError("decode_step_paged requires the uniform stack")

    def body(carry, inp):
        xc, pool = carry
        lp, layer = inp
        return apply_uniform_layer_decode_paged(
            lp, xc, cfg, ctx, pool, layer, block_tables, kv_lens), None
    (x, new_cache), _ = jax.lax.scan(
        body, (embeds, cache),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return apply_norm(params["final_norm"], x, cfg), new_cache
