"""Plain reference of the dense decoder the cells run, in float32.

Written from the published description of the architecture (pre-norm
decoder, rotary positions on every head dimension with the
rotate-half convention, grouped-query attention where query head ``h``
reads key/value head ``h // (H / Hkv)``, SwiGLU feed-forward, tied
input and output embedding, non-parametric LayerNorm or RMSNorm with
eps 1e-5) in straightforward ``jax.numpy``, with every matmul at
``Precision.HIGHEST``. No kernels, no cache, no batching tricks, and
nothing imported from the program under test.

Weights are drawn from the seed by the initialisation the configuration
states: normal(0, 1/fan_in) for every matrix, normal(0, 0.02) for the
embedding, unit norm scales, each rounded to the parameter dtype, with
the same key tree (seed -> 5 keys -> embed / layers / ...; the layer key
split once per layer, then 5 ways, attention 4 ways and the feed-forward
3 ways). A test checks at a small size that these are the program's
weights bit for bit.

``precision="fp8"`` is the control: the same computation with every
matmul operand rounded to float8 e4m3 with a per-tensor scale, the step
below the bfloat16 the configurations compute in.

The interface of a reference module is in ``spec.py``: this one also
names what it does not model (``unmodelled``) and gives the dense FLOP
counts of ``flops.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.flops import (decode_flops, prefill_flops,  # noqa: F401
                            train_flops_per_token)

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-5
FP8_MAX = 448.0        # largest finite float8 e4m3fn


def unmodelled(mc) -> List[str]:
    """What the program's ``ModelConfig`` would run that this dense,
    tied, SwiGLU decoder does not model."""
    off = {"logit_softcap": bool(mc.logit_softcap),
           "qk_norm": mc.qk_norm,
           "moe": mc.moe.enabled,
           "mla": mc.mla.enabled,
           "ssm": mc.ssm.enabled,
           "xlstm": mc.xlstm.enabled,
           "hybrid": mc.hybrid.enabled,
           f"frontend {mc.frontend}": mc.frontend != "token",
           "untied embeddings": not mc.tie_embeddings,
           f"activation {mc.activation}": mc.activation != "swiglu",
           f"norm {mc.norm}": mc.norm not in ("rmsnorm", "nonparam_ln")}
    return [name for name, on in off.items() if on]


def fake_fp8(x: jnp.ndarray) -> jnp.ndarray:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_einsum(spec: str, a, b):
    """A matmul as fp8 training does it: both operands rounded to fp8,
    and in the backward pass the incoming gradient too, each with its
    own per-tensor scale; products summed in float32."""
    return jnp.einsum(spec, fake_fp8(a), fake_fp8(b), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    return fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     fake_fp8(a), fake_fp8(b))
    return vjp(fake_fp8(g))


fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec: str, a, b, precision: str):
    if precision == "fp8":
        return fp8_einsum(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def _head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def _dense(key, shape, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(
        shape[0]))
    return w.astype(dtype).astype(jnp.float32)


def _norm_params(cfg) -> Dict[str, jnp.ndarray]:
    if cfg["norm"] == "rmsnorm":
        return {"scale": jnp.ones((cfg["d_model"],), jnp.float32)}
    if cfg["norm"] == "nonparam_ln":
        return {}
    raise ValueError(f"reference has no norm {cfg['norm']!r}")


def _root_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 5)


def embed_weights(cfg, seed: int) -> jnp.ndarray:
    ke = _root_keys(seed)[0]
    dt = jnp.dtype(cfg["param_dtype"])
    w = jax.random.normal(ke, (cfg["vocab_size"], cfg["d_model"]),
                          jnp.float32) * 0.02
    return w.astype(dt).astype(jnp.float32)


def layer_weights(cfg, seed: int, layer) -> Dict[str, Any]:
    """Layer ``layer`` (may be traced) of the seed's weights."""
    kl = _root_keys(seed)[1]
    k = jax.random.split(kl, cfg["num_layers"])[layer]
    ks = jax.random.split(k, 5)
    ka = jax.random.split(ks[1], 4)
    km = jax.random.split(ks[3], 3)
    d, dh, ff = cfg["d_model"], _head_dim(cfg), cfg["d_ff"]
    h, hkv = cfg["num_heads"], cfg["num_kv_heads"]
    dt = jnp.dtype(cfg["param_dtype"])
    return {
        "ln1": _norm_params(cfg),
        "attn": {"wq": _dense(ka[0], (d, h * dh), dt),
                 "wk": _dense(ka[1], (d, hkv * dh), dt),
                 "wv": _dense(ka[2], (d, hkv * dh), dt),
                 "wo": _dense(ka[3], (h * dh, d), dt)},
        "ln2": _norm_params(cfg),
        "mlp": {"w_gate": _dense(km[0], (d, ff), dt),
                "w_up": _dense(km[1], (d, ff), dt),
                "w_down": _dense(km[2], (ff, d), dt)},
    }


def all_weights(cfg, seed: int) -> Dict[str, Any]:
    return {"embed": embed_weights(cfg, seed),
            "layers": [layer_weights(cfg, seed, i)
                       for i in range(cfg["num_layers"])],
            "final_norm": _norm_params(cfg)}


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def norm(cfg, p, x):
    if cfg["norm"] == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + NORM_EPS)
        return x * p["scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + NORM_EPS)


def rope(x, theta: float):
    """x (B, S, H, D), positions 0..S-1, rotate-half over all of D."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg, p, x, precision: str):
    b, s, _ = x.shape
    h, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], _head_dim(cfg)
    q = einsum("bsd,de->bse", x, p["wq"], precision).reshape(b, s, h, dh)
    k = einsum("bsd,de->bse", x, p["wk"], precision).reshape(b, s, hkv, dh)
    v = einsum("bsd,de->bse", x, p["wv"], precision).reshape(b, s, hkv, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(dh)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = einsum("bhqk,bkhd->bqhd", probs, v, precision)
    return einsum("bse,ed->bsd", out.reshape(b, s, h * dh), p["wo"],
                  precision)


def mlp(p, x, precision: str):
    g = einsum("bsd,df->bsf", x, p["w_gate"], precision)
    u = einsum("bsd,df->bsf", x, p["w_up"], precision)
    return einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], precision)


def layer(cfg, p, x, precision: str):
    x = x + attention(cfg, p["attn"], norm(cfg, p["ln1"], x), precision)
    return x + mlp(p["mlp"], norm(cfg, p["ln2"], x), precision)


def logits(embed, h, precision: str):
    return einsum("...d,vd->...v", h, embed, precision)


# --------------------------------------------------------------------------
# training: loss, gradients and AdamW over the first steps
# --------------------------------------------------------------------------


def loss_sums(cfg, params, inputs, labels, weights, precision: str):
    """(weighted cross-entropy sum, weight sum) over rows (B, S)."""
    x = params["embed"][inputs]
    for lp in params["layers"]:
        x = jax.checkpoint(functools.partial(layer, cfg,
                                             precision=precision))(lp, x)
    h = norm(cfg, params["final_norm"], x)
    lg = logits(params["embed"], h, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * weights), jnp.sum(weights)


def leaf_norms(params) -> Dict[str, float]:
    """L2 norm of every weight matrix, named ``embed`` or
    ``layers.<i>.<block>.<name>`` (norm scales included where the
    configuration has them)."""
    out = {"embed": float(jnp.linalg.norm(params["embed"]))}
    for i, lp in enumerate(params["layers"]):
        for block, leaves in lp.items():
            for name, w in leaves.items():
                out[f"layers.{i}.{block}.{name}"] = float(
                    jnp.sqrt(jnp.sum(w * w)))
    for name, w in params["final_norm"].items():
        out[f"final_norm.{name}"] = float(jnp.sqrt(jnp.sum(w * w)))
    return out


def learning_rate(opt, step: int) -> float:
    """inverse_sqrt: linear warm-up, then lr * sqrt(warmup / step)."""
    if opt["schedule"] != "inverse_sqrt":
        raise ValueError(f"reference has no schedule {opt['schedule']!r}")
    s, warm = max(float(step), 1.0), max(float(opt["warmup_steps"]), 1.0)
    return opt["lr"] * min(s / warm, math.sqrt(warm / s))


def train_reference(cfg, opt, seed: int, batches: Sequence[Dict],
                    precision: str = "float32", devices=None,
                    rows_of: Optional[Sequence[np.ndarray]] = None
                    ) -> Dict[str, Any]:
    """Run the first ``len(batches)`` AdamW steps from the seed's weights.

    ``batches``: per step, host arrays ``inputs``, ``labels``,
    ``weights`` (rows, S). ``rows_of``: per step, the row indices to
    use (all rows whose weight is not all zero, unless a test takes
    some away). Rows are processed a chunk of ``len(devices)`` at a
    time, one row per device, the gradient of the chunk summed across
    them. Returns each step's loss, the leaf norms of the first
    (clipped) gradient as the optimizer takes it, and the leaf norms of
    the parameters' change over all the steps.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("r",))
    rows_spec = NamedSharding(mesh, P("r"))
    repl = NamedSharding(mesh, P())
    b1, b2 = opt["betas"]

    def objective(p, inp, lab, w):
        o, ws = loss_sums(cfg, p, inp, lab, w, precision)
        return o, ws

    grad_fn = jax.jit(jax.value_and_grad(objective, has_aux=True),
                      in_shardings=(repl, rows_spec, rows_spec, rows_spec),
                      out_shardings=((repl, repl), repl))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    @jax.jit
    def adamw(p, g, m, v, wsum, step, lr):
        g = jax.tree.map(lambda x: x / wsum, g)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9)), g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

        def upd(w, a, c):
            u = (a / bc1) / (jnp.sqrt(c / bc2) + opt["eps"])
            if w.ndim >= 2:
                u = u + opt["weight_decay"] * w
            return w - lr * u
        return jax.tree.map(upd, p, m, v), m, v, g

    params = jax.device_put(all_weights(cfg, seed), repl)
    start = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    first_grad: Dict[str, float] = {}
    n = len(devices)
    for step, batch in enumerate(batches, start=1):
        rows = (rows_of[step - 1] if rows_of is not None else
                np.flatnonzero(batch["weights"].sum(axis=1) > 0))
        gsum, osum, wsum = None, 0.0, 0.0
        for lo in range(0, len(rows), n):
            idx = list(rows[lo:lo + n])
            chunk = {k: batch[k][idx] for k in
                     ("inputs", "labels", "weights")}
            pad = n - len(idx)
            if pad:       # a short last chunk: rows of weight zero
                chunk = {k: np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                    for k, a in chunk.items()}
            (o, w), g = grad_fn(params, chunk["inputs"],
                                chunk["labels"],
                                chunk["weights"].astype(np.float32))
            gsum = g if gsum is None else add(gsum, g)
            osum += float(o)
            wsum += float(w)
        losses.append(osum / wsum)
        params, m, v, g = adamw(params, gsum, m, v, jnp.float32(wsum),
                                jnp.float32(step),
                                jnp.float32(learning_rate(opt, step)))
        if step == 1:
            first_grad = leaf_norms(g)
        del g, gsum
    delta = jax.tree.map(jnp.subtract, params, start)
    return {"losses": losses, "first_grad": first_grad,
            "delta": leaf_norms(delta)}


# --------------------------------------------------------------------------
# serving: logits over prompts and served tokens, layer by layer
# --------------------------------------------------------------------------


def _bucket(n: int, step: int = 256) -> int:
    return -(-n // step) * step


def served_logit_gaps(cfg, seed: int, sequences: Sequence[Dict],
                      precisions: Sequence[str] = ("float32",),
                      chunk: int = 512) -> Dict[str, np.ndarray]:
    """For each sequence (``prompt`` and ``served`` token lists), the
    gap at each served position between the float32 reference's best
    logit and its logit for a token: the served token (key
    ``served``), and, for each other precision, the token that
    precision's forward puts first (key = the precision).

    Runs one sequence at a time through one layer at a time, the
    layer's weights drawn from the seed when it is reached, so that a
    3.8e9-parameter model fits one chip in float32.
    """
    gen_layer = jax.jit(functools.partial(layer_weights, cfg, seed))
    fwd = jax.jit(functools.partial(layer, cfg), static_argnames="precision")
    fnorm = jax.jit(functools.partial(norm, cfg))
    embed = jax.jit(functools.partial(embed_weights, cfg, seed))()
    final = _norm_params(cfg)
    xs: Dict[str, List[jnp.ndarray]] = {p: [] for p in precisions}
    for s in sequences:
        toks = list(s["prompt"]) + list(s["served"][:-1])
        t = _bucket(len(toks))
        ids = np.zeros((1, t), np.int32)
        ids[0, :len(toks)] = toks
        x0 = embed[jnp.asarray(ids)]
        for p in precisions:
            xs[p].append(x0)
    for i in range(cfg["num_layers"]):
        w = gen_layer(i)
        for p in precisions:
            xs[p] = [fwd(w, x, precision=p) for x in xs[p]]
        del w

    @jax.jit
    def gap_of(embed, h, tok):
        ref = logits(embed, h, "float32")
        picked = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - picked

    out = {"served": []}
    for p in precisions:
        if p != "float32":
            out[p] = []
    for j, s in enumerate(sequences):
        first = len(s["prompt"]) - 1
        n = len(s["served"])
        served = np.asarray(s["served"], np.int32)
        hs = {p: fnorm(final, xs[p][j][0, first:first + n])
              for p in precisions}
        for lo in range(0, n, chunk):
            hi = min(n, lo + chunk)
            pad = chunk - (hi - lo)
            h32 = jnp.pad(hs["float32"][lo:hi], ((0, pad), (0, 0)))
            toks = {"served": np.pad(served[lo:hi], (0, pad))}
            for p in precisions:
                if p != "float32":
                    # the control's token comes from its own hidden
                    # state; its gap is read on the float32 logits
                    hp = jnp.pad(hs[p][lo:hi], ((0, pad), (0, 0)))
                    toks[p] = jnp.argmax(_logits_jit(embed, hp, p),
                                         axis=-1).astype(jnp.int32)
            for key, tok in toks.items():
                out[key].append(np.asarray(
                    gap_of(embed, h32, jnp.asarray(tok, jnp.int32)))[:hi - lo])
    return {k: np.concatenate(v) if v else np.zeros((0,))
            for k, v in out.items()}


@functools.partial(jax.jit, static_argnames="precision")
def _logits_jit(embed, h, precision):
    return logits(embed, h, precision)
