"""Plain reference of the dense decoder, its training state sharded
over the cell's chips.

A model whose float32 weights, starting copy, AdamW moments and
gradient sum outgrow one chip (olmo-1b whole: ~28 GB a chip if each
were held whole) cannot be checked by ``benchlib/reference.py``, which
holds every one of them on every device. This module keeps that
module's equations, imported from it: the same weights from the seed,
layer, norm, tied logits and cross entropy, and its AdamW and leaf
norms written out again, in float32 with every matmul at
``Precision.HIGHEST``. Where the state lives differs: each leaf is
split over the devices on its largest dimension that their count
divides (FSDP), and ``jit`` gathers a layer's weights where the layer
uses them and reduce-scatters their gradient. The layers' weights are
held stacked and run by a ``lax.scan``, each layer a ``jax.checkpoint``
as in the dense loss, so the step compiles one layer instead of every
layer, and the backward pass gathers a layer's weights again instead of
keeping all of them.

Everything else a reference module provides (``spec.py``) is the dense
module's, re-exported: serving, the ``"fp8"`` control's matmul,
``unmodelled`` and the FLOP counts.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchlib import reference as dense
from benchlib.reference import (all_weights, decode_flops,  # noqa: F401
                                embed_weights, fp8_einsum, layer,
                                layer_weights, leaf_norms, learning_rate,
                                loss_sums, prefill_flops,
                                served_logit_gaps, train_flops_per_token,
                                unmodelled)

AXIS = "r"


def fsdp_spec(shape: Sequence[int], n: int) -> P:
    """Split the largest dimension that ``n`` divides (the first of
    equals); a leaf with none stays whole on every device."""
    dims = [i for i, d in enumerate(shape) if d % n == 0]
    if n == 1 or not dims:
        return P()
    i = max(dims, key=lambda j: shape[j])
    return P(*([None] * i + [AXIS]))


def _sharded(mesh: Mesh, fn, *args):
    """``fn(*args)`` computed straight into FSDP shardings."""
    out = jax.tree.map(
        lambda s: NamedSharding(mesh, fsdp_spec(s.shape, mesh.size)),
        jax.eval_shape(fn, *args))
    return jax.jit(fn, out_shardings=out)(*args)


def sharded_weights(cfg, seed: int, mesh: Mesh) -> Dict[str, Any]:
    """``reference.all_weights(cfg, seed)`` in FSDP shardings over
    ``mesh``, the layers' leaves stacked (layer first)."""
    layers = jax.vmap(functools.partial(layer_weights, cfg, seed))
    return {"embed": _sharded(mesh, functools.partial(embed_weights, cfg,
                                                      seed)),
            "layers": _sharded(mesh, layers,
                               jnp.arange(cfg["num_layers"])),
            "final_norm": _sharded(mesh, functools.partial(
                dense._norm_params, cfg))}


def sharded_loss_sums(cfg, whole: NamedSharding, params, inputs, labels,
                      weights, precision: str):
    """``reference.loss_sums`` over stacked layers, each weight
    gathered whole (``whole``) where it is used."""
    def gather(tree):
        return jax.lax.with_sharding_constraint(tree, whole)

    def one_layer(x, lp):
        return layer(cfg, gather(lp), x, precision), None

    embed = gather(params["embed"])
    x = embed[inputs]
    x, _ = jax.lax.scan(jax.checkpoint(one_layer), x, params["layers"])
    h = dense.norm(cfg, gather(params["final_norm"]), x)
    lg = dense.logits(embed, h, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * weights), jnp.sum(weights)


def stacked_leaf_norms(params) -> Dict[str, float]:
    """``reference.leaf_norms`` of stacked layers: the same names
    (``embed``, ``layers.<i>.<block>.<name>``, ``final_norm.<name>``)."""
    out = {"embed": float(jnp.linalg.norm(params["embed"]))}
    for block, leaves in params["layers"].items():
        for name, w in leaves.items():
            norms = jnp.sqrt(jnp.sum(w * w, axis=tuple(range(1, w.ndim))))
            for i, x in enumerate(np.asarray(norms)):
                out[f"layers.{i}.{block}.{name}"] = float(x)
    for name, w in params["final_norm"].items():
        out[f"final_norm.{name}"] = float(jnp.sqrt(jnp.sum(w * w)))
    return out


def train_reference(cfg, opt, seed: int, batches: Sequence[Dict],
                    precision: str = "float32", devices=None,
                    rows_of: Optional[Sequence[np.ndarray]] = None
                    ) -> Dict[str, Any]:
    """``reference.train_reference``, its state sharded over
    ``devices``: the first ``len(batches)`` AdamW steps from the seed's
    weights, rows a chunk of ``len(devices)`` at a time, one row per
    device. Returns each step's loss, the leaf norms of the first
    (clipped) gradient as the optimizer takes it, and the leaf norms of
    the parameters' change over all the steps."""
    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), (AXIS,))
    rows_spec = NamedSharding(mesh, P(AXIS))
    whole = NamedSharding(mesh, P())
    b1, b2 = opt["betas"]

    with jax.default_matmul_precision("highest"):
        params = sharded_weights(cfg, seed, mesh)
        state_spec = jax.tree.map(lambda x: x.sharding, params)

        def objective(p, inp, lab, w):
            return sharded_loss_sums(cfg, whole, p, inp, lab, w, precision)

        grad_fn = jax.jit(jax.value_and_grad(objective, has_aux=True),
                          in_shardings=(state_spec, rows_spec, rows_spec,
                                        rows_spec),
                          out_shardings=((whole, whole), state_spec))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=0)

        # weight decay on matrices, as the dense reference's w.ndim >= 2
        # (a stacked layer leaf has the layer dimension in front)
        decay = {"embed": params["embed"].ndim >= 2,
                 "layers": jax.tree.map(lambda x: x.ndim >= 3,
                                        params["layers"]),
                 "final_norm": jax.tree.map(lambda x: x.ndim >= 2,
                                            params["final_norm"])}

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def adamw(p, g, m, v, wsum, step, lr):
            g = jax.tree.map(lambda x: x / wsum, g)
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9)), g)
            m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
            v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
            bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

            def upd(w, a, c, d):
                u = (a / bc1) / (jnp.sqrt(c / bc2) + opt["eps"])
                if d:
                    u = u + opt["weight_decay"] * w
                return w - lr * u
            return jax.tree.map(upd, p, m, v, decay), m, v, g

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
                first_grad = stacked_leaf_norms(g)
            del g, gsum
        delta = jax.tree.map(jnp.subtract, params, start)
        return {"losses": losses, "first_grad": first_grad,
                "delta": stacked_leaf_norms(delta)}
