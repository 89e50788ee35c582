"""Beyond-paper: hierarchical (ICI-then-DCN) gradient reduction.

On a multi-pod mesh ("pod", "data", "model"), a flat all-reduce over
("pod","data") pushes full-gradient traffic over the slow cross-pod DCN
link. The hierarchical schedule:

  1. in-pod reduce-scatter over "data" (fast ICI) — each in-pod rank
     owns a 1/data_size shard of the pod-local gradient sum;
  2. cross-pod all-reduce of the *shard only* over "pod" (DCN) —
     optionally int8-compressed with error feedback (compression.py);
  3. in-pod all-gather over "data" to rebuild the full gradient.

Cross-pod bytes drop by data_size (16x) x compression (~3.9x) vs the
flat reduction. Expressed with shard_map(axis_names={"pod","data"})
so the "model" axis stays under automatic (pjit) partitioning.

Two granularities:
  * ``hierarchical_reduce_leaf`` / ``hierarchical_reduce_tree`` — the
    legacy per-leaf walk: one schedule instance per pytree leaf, so a
    transformer's dozens of leaves cost dozens of latency-bound DCN
    collectives per step.
  * ``hierarchical_reduce_bucketed`` — the flat-buffer engine
    (core/buckets.py): the whole tree is packed into fixed-size f32
    buckets first, then ONE reduce-scatter, ONE cross-pod exchange and
    ONE gather move the entire stack. This is the hot-path variant;
    the reduce-scatter over "data" runs before the pack-side quantize,
    so only 1/data_size of the buffer exists per rank when the DCN leg
    fires.
  * ``hierarchical_reduce_bucketed_overlapped`` — the same 3-level
    schedule as a double-buffered per-bucket pipeline: bucket k+1's
    in-pod reduce-scatter + quantize run while bucket k's DCN exchange
    is in flight (2 DCN collectives per bucket instead of 2 total —
    the latency/overlap trade benchmarks/overlap_bench.py models).

This module provides the *manual-collective* building blocks for the
fully-manual ({pod, data}) mesh regions used by the distributed tests
and benchmarks. The train step (launch/steps.py) runs a partially-
manual variant of the same schedule: manual over "pod" only, with the
in-pod legs left to XLA's automatic ("data"-FSDP) partitioning — its
``HetConfig.overlap`` path therefore pipelines the flat engine
(core/buckets.py) over the pod axis rather than calling the 3-level
functions here (wiring the fully-manual 3-level pipeline into the step
is an open ROADMAP item).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import buckets as bkt
from repro.kernels.quantize import ops as q_ops
from repro.kernels.quantize import ref as q_ref


def _pad_to(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, (-flat.shape[0]) % mult))


def hierarchical_reduce_leaf(
    g: jnp.ndarray,
    err: Optional[jnp.ndarray],
    *,
    data_axis: str = "data",
    pod_axis: str = "pod",
    data_size: int,
    pod_size: int,
    compress: bool = False,
    block_size: int = 256,
    key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Inside shard_map(manual over {pod, data}): reduce one leaf.

    ``g`` is this rank's local gradient contribution (sum over its
    tokens). Returns (globally summed gradient, new error state).
    """
    shape = g.shape
    flat = _pad_to(g.astype(jnp.float32), data_size)
    # 1) in-pod reduce-scatter over ICI: each rank owns a shard
    shard = jax.lax.psum_scatter(
        flat.reshape(data_size, -1), data_axis, scatter_dimension=0,
        tiled=False)
    # 2) cross-pod reduction over DCN
    if compress:
        corrected = shard + (err if err is not None else 0.0)
        q, s = q_ops.quantize_int8(corrected, block_size=block_size, key=key)
        deq_local = q_ref.dequantize_int8(q, s, corrected.shape, block_size)
        new_err = corrected - deq_local
        # int8 payload + per-block scales cross the DCN link; the sum
        # is rebuilt from the per-pod (values, scales) pairs
        q_all = jax.lax.all_gather(q, pod_axis)
        s_all = jax.lax.all_gather(s, pod_axis)
        shard = jnp.einsum("pbk,pb->bk", q_all.astype(jnp.float32),
                           s_all).reshape(-1)[:shard.shape[0]]
    else:
        new_err = err
        shard = jax.lax.psum(shard, pod_axis)
    # 3) in-pod all-gather over ICI to rebuild the full leaf
    full = jax.lax.all_gather(shard, data_axis).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return full[:n].reshape(shape), new_err


def hierarchical_reduce_tree(
    grads: Any,
    err_state: Optional[Any],
    *,
    data_axis: str = "data",
    pod_axis: str = "pod",
    data_size: int,
    pod_size: int,
    compress: bool = False,
    block_size: int = 256,
    key: Optional[jax.Array] = None,
) -> Tuple[Any, Optional[Any]]:
    """LEGACY: apply hierarchical_reduce_leaf across a gradient pytree.

    One full schedule (and its DCN collectives) per leaf — prefer
    :func:`hierarchical_reduce_bucketed` on hot paths.
    """
    leaves, treedef = jax.tree.flatten(grads)
    errs = (treedef.flatten_up_to(err_state) if err_state is not None
            else [None] * len(leaves))
    keys = (jax.random.split(key, len(leaves)) if key is not None
            else [None] * len(leaves))
    outs, nerrs = [], []
    for g, e, k in zip(leaves, errs, keys):
        o, ne = hierarchical_reduce_leaf(
            g, e, data_axis=data_axis, pod_axis=pod_axis,
            data_size=data_size, pod_size=pod_size,
            compress=compress, block_size=block_size, key=k)
        outs.append(o)
        nerrs.append(ne)
    new_err = (treedef.unflatten(nerrs) if err_state is not None else None)
    return treedef.unflatten(outs), new_err


def hierarchical_reduce_bucketed(
    grads: Any,
    err: Optional[jnp.ndarray],
    layout: bkt.BucketLayout,
    *,
    data_axis: str = "data",
    pod_axis: str = "pod",
    data_size: int,
    pod_size: int,
    compress: bool = False,
    block_size: int = 256,
    key: Optional[jax.Array] = None,
    impl: str = "reference",
) -> Tuple[Any, Optional[jnp.ndarray]]:
    """Bucketed 3-level reduction, inside shard_map(manual={pod, data}).

    The whole pytree is packed into the (num_buckets, bucket_elems)
    stack, reduce-scattered over "data" in ONE collective, the
    1/data_size shard crosses the DCN link through the bucketed
    exchange (core/buckets.py — two collectives, int8 payload when
    ``compress``), and ONE in-pod gather rebuilds the stack. The error
    state ``err`` is this rank's flat
    (num_buckets, bucket_elems / data_size) slice.

    The layout must be built with
    ``multiple_of = data_size * pod_size * block_size``.
    """
    flat = bkt.pack_buckets(grads, layout)            # (nb, be)
    nb, be = flat.shape
    if be % data_size:
        raise ValueError(
            f"bucket_elems {be} not divisible by data_size {data_size}")
    # 1) in-pod reduce-scatter (ICI): one collective for the whole stack
    shard = jax.lax.psum_scatter(
        flat.reshape(nb, data_size, be // data_size), data_axis,
        scatter_dimension=1, tiled=False)             # (nb, be/data)
    # 2) cross-pod bucketed exchange (DCN)
    red, new_err = bkt.exchange_buckets(
        shard, err, axis=pod_axis, axis_size=pod_size,
        compress=compress, block_size=block_size, key=key, impl=impl)
    # 3) in-pod all-gather (ICI): rebuild the full stack
    full = jax.lax.all_gather(red, data_axis)
    flat = jnp.moveaxis(full, 0, 1).reshape(nb, be)
    return bkt.unpack_buckets(flat, layout), new_err


def hierarchical_reduce_bucketed_overlapped(
    grads: Any,
    err: Optional[jnp.ndarray],
    layout: bkt.BucketLayout,
    *,
    data_axis: str = "data",
    pod_axis: str = "pod",
    data_size: int,
    pod_size: int,
    compress: bool = False,
    block_size: int = 256,
    key: Optional[jax.Array] = None,
    impl: str = "reference",
) -> Tuple[Any, Optional[jnp.ndarray]]:
    """Double-buffered 3-level pipeline, inside shard_map(manual={pod,
    data}).

    Per-bucket version of :func:`hierarchical_reduce_bucketed`: while
    bucket *k*'s cross-pod (DCN) exchange is in flight, bucket *k+1*
    runs its in-pod reduce-scatter + send-side quantize — the ICI legs
    and the quantize kernels hide behind the slow link exactly like the
    flat pipeline in core/buckets.py (whose per-bucket building blocks
    this reuses). ``err`` is this rank's flat
    (num_buckets, bucket_elems / data_size) slice.
    """
    flat = bkt.pack_buckets(grads, layout)              # (nb, be)
    nb, be = flat.shape
    if be % data_size:
        raise ValueError(
            f"bucket_elems {be} not divisible by data_size {data_size}")
    shard = be // data_size
    if shard % pod_size:
        raise ValueError(
            f"in-pod shard {shard} not divisible by pod_size {pod_size}")
    if compress and (shard // pod_size) % block_size:
        raise ValueError(
            f"per-pod shard {shard // pod_size} not divisible by "
            f"block_size {block_size}; build the layout with "
            f"multiple_of={data_size * pod_size * block_size}")
    want_err = compress and err is not None
    e = err.reshape(nb, pod_size, shard // pod_size) if want_err else None
    onehot = bkt.rank_onehot(pod_axis, pod_size)

    def prep(k, raw_k, err_k):
        # in-pod reduce-scatter (ICI) for bucket k, then the cross-pod
        # send-side leg — both overlap bucket k-1's DCN exchange
        sh = jax.lax.psum_scatter(
            raw_k.reshape(data_size, shard), data_axis,
            scatter_dimension=0, tiled=False)           # (shard,)
        bkey = key
        if compress and bkey is not None:
            bkey = jax.random.fold_in(bkey, k)
            bkey = jax.random.fold_in(
                bkey, jnp.argmax(onehot).astype(jnp.int32))
        return bkt.prepare_bucket(
            sh.reshape(pod_size, shard // pod_size), err_k,
            compress=compress, block_size=block_size, key=bkey,
            impl=impl, interpret=False)

    def exchange(prepared):
        payload, resid1 = prepared
        red_k, nerr_k = bkt.exchange_prepared_bucket(
            payload, resid1, axis=pod_axis, axis_size=pod_size,
            compress=compress, block_size=block_size, impl=impl,
            interpret=False, onehot=onehot)             # (shard,)
        # in-pod all-gather (ICI) rebuilds bucket k as it lands
        full = jax.lax.all_gather(red_k, data_axis)
        return full.reshape(be), nerr_k

    # shared driver: bucket k+1's ICI reduce-scatter + quantize (prep)
    # overlap bucket k's in-flight DCN exchange; the last bucket runs
    # in an epilogue so the prep's ICI reduce-scatter is never issued
    # for a dead (wrapped-around) bucket
    outs, nerrs, _ = bkt.run_overlapped_pipeline(
        nb, prep, exchange, raw=flat, err=e)
    new_err = nerrs.reshape(nb, shard) if want_err else None
    return bkt.unpack_buckets(outs, layout), new_err


def cross_pod_bytes(grads: Any, num_params_bytes: int = 4,
                    data_size: int = 16, compress: bool = False,
                    block_size: int = 256) -> int:
    """Analytic DCN bytes per step for the reduction (for §Roofline)."""
    total = sum(g.size for g in jax.tree.leaves(grads))
    shard = total // data_size
    if not compress:
        return shard * num_params_bytes * 2          # psum ~ 2x shard bytes
    payload = shard * 1 + -(-shard // block_size) * 4
    return payload * 2
