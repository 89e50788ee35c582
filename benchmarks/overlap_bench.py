"""Overlap benchmark: monolithic vs double-buffered per-bucket pipeline.

Measures, on the 8-host-device mesh (2 pods x 2 data x 2 model), the
fused reduce+update schedules wired behind ``HetConfig.overlap``:

  serial   — monolithic: pack -> 2-collective exchange
             (core/buckets.py::exchange_buckets) -> one flat AdamW
             update over the whole stack; link and compute take turns.
  overlap  — double-buffered pipeline
             (core/buckets.py::exchange_buckets_overlapped): bucket
             k+1's quantize/pack runs while bucket k's exchange is in
             flight, and the per-bucket flat-view AdamW update
             (optim/adam.py::apply_update_flat) is fused into the
             pipeline the moment each bucket lands.

For each mode it reports the measured wall time on the host mesh plus a
**modeled pipeline timeline**: per-bucket link occupancy comes from the
analytic byte models (``modeled_bucket_link_bytes``, the native-DCN
schedule) at an assumed DCN bandwidth, and per-bucket compute occupancy
(send-side pack/quantize, landing-side optimizer) from an assumed HBM
bandwidth on the touched bytes. The modeled serial time is the sum of
all three legs over all buckets; the modeled overlapped time is the
standard 3-stage pipeline recurrence

    prep_done[k] = prep_done[k-1] + t_prep[k]
    link_done[k] = max(link_done[k-1], prep_done[k]) + t_link[k]
    upd_done[k]  = max(upd_done[k-1], link_done[k]) + t_upd[k]

whose total approaches max(compute, link) instead of their sum as the
bucket count grows. The CPU host mesh executes collectives eagerly and
cannot actually overlap, so MEASURED wall time is reported for both
modes but the acceptance invariant is on the model (checked loudly in
``--quick`` and on every full run): modeled overlapped step time must
be strictly below modeled serial, and the fused pipeline must be
bit-identical (fp32) to the monolithic reduce+update.

Backward overlap (``HetConfig.overlap="backward"``): a third schedule
flushes buckets DURING backprop — each bucket's exchange is issued the
moment its last contributing layer's cotangent lands
(core/buckets.py::bucket_readiness + BucketFlushPipeline). The bench
builds a synthetic LAYERED gradient tree (head / stacked layers /
embedding, the uniform-stack partition), derives the readiness
schedule, and models the bwd+link timeline: per-stage backward compute
from HBM-touched bytes, per-bucket link occupancy gated on the
bucket's readiness stage. The acceptance invariant is that the modeled
backward-overlap step time is STRICTLY below the after-backward
("buckets") pipeline — the link works while the backward still
computes instead of idling through it — and that the flush-ordered
pipeline is bit-identical to the monolithic exchange (readiness order
must not change values).

Emits ``BENCH_overlap.json`` (``--out`` to relocate).
"""
from __future__ import annotations

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import argparse
import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.reduce_bench import count_pod_collectives, \
    synthetic_grad_tree
from repro.configs.base import OptimizerConfig
from repro.core import buckets as bkt
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.optim import adam

_BLOCK = steps_mod._BLOCK

# modeled fabric: 100 Gb/s DCN (the slow heterogeneous link the paper's
# campus Ethernet maps to) and one HBM-class memory system feeding the
# pack/quantize and optimizer legs
DCN_BYTES_PER_S = 12.5e9
HBM_BYTES_PER_S = 900e9


def modeled_timeline(layout: bkt.BucketLayout, ranks: int, *,
                     compress: bool, block_size: int = _BLOCK
                     ) -> Dict[str, Any]:
    """Per-bucket 3-stage pipeline model (prep | link | update)."""
    nb = layout.num_buckets
    bucket_f32 = layout.bucket_elems * 4
    # send-side leg: read the raw bucket (+ error state and int8 write
    # for the compressed path); landing-side: AdamW touches p/m/v
    # read+write plus the reduced gradient read = 7 bucket-sized passes
    prep_passes = 3.0 if compress else 1.0
    t_prep = [prep_passes * bucket_f32 / HBM_BYTES_PER_S] * nb
    t_link = [bkt.modeled_bucket_link_bytes(
        layout, ranks, k, compress=compress, block_size=block_size)
        / DCN_BYTES_PER_S for k in range(nb)]
    t_upd = [7.0 * bucket_f32 / HBM_BYTES_PER_S] * nb

    timeline = []
    prep_done = link_done = upd_done = 0.0
    for k in range(nb):
        prep_start = prep_done
        prep_done = prep_start + t_prep[k]
        link_start = max(link_done, prep_done)
        link_done = link_start + t_link[k]
        upd_start = max(upd_done, link_done)
        upd_done = upd_start + t_upd[k]
        timeline.append({
            "bucket": k,
            "prep_s": [prep_start, prep_done],
            "link_s": [link_start, link_done],
            "update_s": [upd_start, upd_done],
        })
    serial = sum(t_prep) + sum(t_link) + sum(t_upd)
    return {
        "serial_model_s": serial,
        "overlap_model_s": upd_done,
        "model_speedup": serial / upd_done,
        "link_total_s": sum(t_link),
        "compute_total_s": sum(t_prep) + sum(t_upd),
        "dcn_bytes_per_s": DCN_BYTES_PER_S,
        "hbm_bytes_per_s": HBM_BYTES_PER_S,
        "timeline": timeline,
    }


# modeled backward compute: recompute-forward + backward passes over a
# stage's parameter bytes (the staged backward is remat-style — each
# layer's VJP re-reads its params ~BWD_PASSES times against HBM)
BWD_PASSES = 6.0


def synthetic_layered_tree(num_layers: int, d: int,
                           vocab: int) -> Dict[str, jnp.ndarray]:
    """A uniform-stack-shaped gradient tree: embedding table, stacked
    per-layer matrices, head. Mirrors the layer partition the staged
    backward flushes against (models/transformer.py)."""
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return {
        "embed": arr(vocab, d),
        "layers": {"attn": arr(num_layers, d, 3 * d),
                   "mlp_in": arr(num_layers, d, 4 * d),
                   "mlp_out": arr(num_layers, 4 * d, d)},
        "head": arr(d, vocab),
    }


def layered_pieces(tree: Dict[str, jnp.ndarray], num_layers: int):
    """Per-leaf (offset, n, stage) pieces for the synthetic tree — the
    uniform-stack backward partition: head at stage 0, layer l at
    stage L - l, embedding at stage L + 1."""
    L = num_layers
    pieces = []
    stage_bytes = [0.0] * (L + 2)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        top = path[0].key
        n = int(np.prod(leaf.shape))
        if top == "layers":
            per = n // L
            pieces.append([(l * per, per, L - l) for l in range(L)])
            for l in range(L):
                stage_bytes[L - l] += per * 4
        elif top == "embed":
            pieces.append([(0, n, L + 1)])
            stage_bytes[L + 1] += n * 4
        else:
            pieces.append([(0, n, 0)])
            stage_bytes[0] += n * 4
    return pieces, stage_bytes


def modeled_backward_timeline(layout: bkt.BucketLayout, ranks: int,
                              readiness, stage_bytes, *,
                              compress: bool,
                              block_size: int = _BLOCK
                              ) -> Dict[str, Any]:
    """Bwd+link timeline for the backward-overlap flush schedule.

    The staged backward walks stages 0..S-1 (head, layers back to
    front, embed) at ``BWD_PASSES`` HBM passes over each stage's
    parameter bytes; bucket *k*'s send-side prep can start no earlier
    than ``stage_done[readiness[k]]``, then the standard 3-stage
    prep | link | update pipeline recurrence applies in flush order.
    The after-backward ("buckets") pipeline is the SAME recurrence
    gated on the full backward being done — so the comparison isolates
    exactly the early-flush win: link time hidden under backward
    compute.
    """
    nb = layout.num_buckets
    bucket_f32 = layout.bucket_elems * 4
    t_prep = [(3.0 if compress else 1.0) * bucket_f32 / HBM_BYTES_PER_S
              ] * nb
    t_link = [bkt.modeled_bucket_link_bytes(
        layout, ranks, k, compress=compress, block_size=block_size)
        / DCN_BYTES_PER_S for k in range(nb)]
    t_upd = [7.0 * bucket_f32 / HBM_BYTES_PER_S] * nb

    t_bwd = [BWD_PASSES * b / HBM_BYTES_PER_S for b in stage_bytes]
    stage_done = []
    t = 0.0
    for s in range(len(t_bwd)):
        t += t_bwd[s]
        stage_done.append(t)
    bwd_total = t

    def pipeline(ready_at):
        prep_done = link_done = upd_done = 0.0
        timeline = []
        order = sorted(range(nb), key=lambda k: (readiness[k], k))
        for k in order:
            prep_start = max(prep_done, ready_at(k))
            prep_done = prep_start + t_prep[k]
            link_start = max(link_done, prep_done)
            link_done = link_start + t_link[k]
            upd_start = max(upd_done, link_done)
            upd_done = upd_start + t_upd[k]
            timeline.append({"bucket": k,
                             "ready_s": ready_at(k),
                             "prep_s": [prep_start, prep_done],
                             "link_s": [link_start, link_done],
                             "update_s": [upd_start, upd_done]})
        return upd_done, timeline

    bwd_overlap_total, timeline = pipeline(
        lambda k: stage_done[readiness[k]])
    after_backward_total, _ = pipeline(lambda k: bwd_total)
    return {
        "bwd_total_s": bwd_total,
        "backward_overlap_model_s": max(bwd_overlap_total, bwd_total),
        "after_backward_model_s": after_backward_total,
        "model_speedup_vs_after_backward":
            after_backward_total / max(bwd_overlap_total, bwd_total),
        "link_total_s": sum(t_link),
        "readiness": list(readiness),
        "bwd_passes": BWD_PASSES,
        "timeline": timeline,
    }


def bench_backward(mesh, pods: int, bucket_mb: float, iters: int,
                   compress: bool, *, num_layers: int = 6, d: int = 64,
                   vocab: int = 512) -> Dict[str, Any]:
    """The backward-overlap flush schedule: modeled timeline + a
    flush-ORDER pipeline run on the host mesh asserting the readiness
    order cannot change values (bit-identical to the monolithic
    exchange)."""
    tree = synthetic_layered_tree(num_layers, d, vocab)
    layout = bkt.build_layout(tree, bucket_mb=bucket_mb,
                              multiple_of=pods * _BLOCK)
    pieces, stage_bytes = layered_pieces(tree, num_layers)
    readiness = bkt.bucket_readiness(layout, pieces)
    weights = [1.0, -0.5][:pods]
    stacked = jax.tree.map(
        lambda v: jnp.stack([w * v for w in weights]), tree)
    spec = jax.tree.map(lambda _: NamedSharding(mesh, P("pod")), stacked)
    stacked = jax.device_put(stacked, spec)

    def serial(gl):
        g = jax.tree.map(lambda a: a[0], gl)
        flat = bkt.pack_buckets(g, layout)
        red, _ = bkt.exchange_buckets(
            flat, None, axis="pod", axis_size=pods, compress=compress,
            block_size=_BLOCK, total=layout.total)
        return red

    def flush_ordered(gl):
        g = jax.tree.map(lambda a: a[0], gl)
        flat = bkt.pack_buckets(g, layout)
        x = flat.reshape(layout.num_buckets, pods, -1)
        onehot = bkt.rank_onehot("pod", pods)

        def prep(k, raw_k):
            return bkt.prepare_bucket(
                raw_k, None, compress=compress, block_size=_BLOCK,
                key=None, impl="reference", interpret=False)

        def exchange(k, prepared):
            payload, resid1 = prepared
            return bkt.exchange_prepared_bucket(
                payload, resid1, axis="pod", axis_size=pods,
                compress=compress, block_size=_BLOCK, impl="reference",
                interpret=False, onehot=onehot)

        pipe = bkt.BucketFlushPipeline(readiness, prep, exchange)
        for stage in range(num_layers + 2):
            pipe.flush_ready_buckets(stage, lambda k: x[k])
        outs, _, _ = pipe.finish()
        return jnp.stack(outs)

    results: Dict[str, Any] = {}
    outs = {}
    for name, f in (("serial", serial), ("flush_ordered", flush_ordered)):
        sm = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                           out_specs=P(), axis_names={"pod"},
                           check_vma=False)
        jf = jax.jit(sm)
        out = jax.block_until_ready(jf(stacked))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jax.block_until_ready(jf(stacked))
        results[name] = {"avg_ms": (time.perf_counter() - t0) / iters
                         * 1e3}
        outs[name] = out
    np.testing.assert_array_equal(np.asarray(outs["serial"]),
                                  np.asarray(outs["flush_ordered"]))
    results["exact_match"] = True
    results["model"] = modeled_backward_timeline(
        layout, pods, readiness, stage_bytes, compress=compress)
    results["_layout"] = {
        "total_bytes": layout.total_bytes,
        "bucket_elems": layout.bucket_elems,
        "num_buckets": layout.num_buckets,
        "num_layers": num_layers,
        "compress": compress,
    }
    return results


def bench_modes(tree: Dict[str, jnp.ndarray], mesh, pods: int,
                bucket_mb: float, iters: int,
                compress: bool) -> Dict[str, Any]:
    layout = bkt.build_layout(tree, bucket_mb=bucket_mb,
                              multiple_of=pods * _BLOCK)
    ocfg = OptimizerConfig(grad_clip=0.0)     # streamable fused update
    dmask = bkt.decay_mask(layout)
    lr = jnp.float32(1e-3)
    step_no = jnp.ones((), jnp.int32)
    weights = [1.0, -0.5][:pods]
    stacked = jax.tree.map(
        lambda v: jnp.stack([w * v for w in weights]), tree)
    spec = jax.tree.map(lambda _: NamedSharding(mesh, P("pod")), stacked)
    stacked = jax.device_put(stacked, spec)
    pb0 = bkt.pack_buckets(tree, layout)      # stand-in packed params
    m0 = jnp.zeros_like(pb0)
    v0 = jnp.zeros_like(pb0)

    def serial(gl, pb, m, v):
        g = jax.tree.map(lambda a: a[0], gl)
        flat = bkt.pack_buckets(g, layout)
        red, _ = bkt.exchange_buckets(
            flat, None, axis="pod", axis_size=pods, compress=compress,
            block_size=_BLOCK, total=layout.total)
        return adam.apply_update_flat(pb, red, m, v, step_no, ocfg, lr,
                                      decay_mask=dmask)

    def overlap(gl, pb, m, v):
        g = jax.tree.map(lambda a: a[0], gl)
        flat = bkt.pack_buckets(g, layout)

        def hook(carry, red_k, xs_k, k):
            p_k, m_k, v_k, dm_k = xs_k
            return carry, adam.apply_update_flat(
                p_k, red_k, m_k, v_k, step_no, ocfg, lr,
                decay_mask=dm_k)

        outs, _, _ = bkt.exchange_buckets_overlapped(
            flat, None, axis="pod", axis_size=pods, compress=compress,
            block_size=_BLOCK, bucket_fn=hook, fn_carry=0.0,
            bucket_xs=(pb, m, v, dmask))
        return outs

    results: Dict[str, Any] = {}
    outs = {}
    for name, f in (("serial", serial), ("overlap", overlap)):
        sm = jax.shard_map(f, mesh=mesh,
                           in_specs=(P("pod"), P(), P(), P()),
                           out_specs=(P(), P(), P()),
                           axis_names={"pod"}, check_vma=False)
        jf = jax.jit(sm)
        out = jax.block_until_ready(jf(stacked, pb0, m0, v0))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jax.block_until_ready(jf(stacked, pb0, m0, v0))
        dt = (time.perf_counter() - t0) / iters
        outs[name] = out
        results[name] = {
            "avg_ms": dt * 1e3,
            "collectives": count_pod_collectives(sm, stacked, pb0, m0,
                                                 v0),
        }
    # the fused pipeline must be exactly the monolithic reduce+update
    for a, b in zip(jax.tree.leaves(outs["serial"]),
                    jax.tree.leaves(outs["overlap"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    results["exact_match"] = True
    results["model"] = modeled_timeline(layout, pods, compress=compress)
    results["_layout"] = {
        "total_bytes": layout.total_bytes,
        "bucket_elems": layout.bucket_elems,
        "num_buckets": layout.num_buckets,
        "compress": compress,
    }
    return results


def check_invariants(res: Dict[str, Any]) -> None:
    """Acceptance invariant — fail loudly on regression."""
    for mode in ("backward_fp32", "backward_int8"):
        m = res[mode]["model"]
        assert res[mode]["exact_match"], (
            f"{mode}: flush-ordered pipeline diverged from the "
            f"monolithic exchange")
        assert (m["backward_overlap_model_s"]
                < m["after_backward_model_s"]), (
            f"{mode}: modeled backward-overlap step "
            f"{m['backward_overlap_model_s']:.3e}s not strictly below "
            f"the after-backward pipeline "
            f"{m['after_backward_model_s']:.3e}s")
        # flushing during backprop can never beat the physical floors
        assert m["backward_overlap_model_s"] >= m["bwd_total_s"]
        assert m["backward_overlap_model_s"] >= m["link_total_s"]
    for mode in ("fp32", "int8"):
        nb = res[mode]["_layout"]["num_buckets"]
        assert nb >= 2, (
            f"{mode}: layout collapsed to {nb} bucket(s) — nothing to "
            f"pipeline; lower --bucket-mb so the tree splits into >= 2 "
            f"buckets")
        m = res[mode]["model"]
        assert m["overlap_model_s"] < m["serial_model_s"], (
            f"{mode}: modeled overlapped step {m['overlap_model_s']:.3e}s "
            f"not strictly below serial {m['serial_model_s']:.3e}s")
        assert res[mode]["exact_match"]
        # the pipeline trades launches for overlap: 2 per bucket
        nb = res[mode]["_layout"]["num_buckets"]
        assert res[mode]["overlap"]["collectives"] <= 2 * nb, (
            f"{mode}: {res[mode]['overlap']['collectives']} collectives "
            f"exceeds 2/bucket bound {2 * nb}")


def main(quick: bool = False, out: str = "BENCH_overlap.json",
         bucket_mb: float = 0.25) -> Dict[str, Any]:
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pods = 2
    if quick:
        tree = synthetic_grad_tree(num_leaves=12, scale=24)
        bucket_mb = min(bucket_mb, 0.002)
        iters = 2
    else:
        tree = synthetic_grad_tree(num_leaves=48, scale=96)
        iters = 8

    bwd_kw = (dict(num_layers=4, d=32, vocab=256) if quick
              else dict(num_layers=8, d=96, vocab=1024))
    res: Dict[str, Any] = {
        "fp32": bench_modes(tree, mesh, pods, bucket_mb, iters,
                            compress=False),
        "int8": bench_modes(tree, mesh, pods, bucket_mb, iters,
                            compress=True),
        "backward_fp32": bench_backward(mesh, pods, bucket_mb, iters,
                                        compress=False, **bwd_kw),
        "backward_int8": bench_backward(mesh, pods, bucket_mb, iters,
                                        compress=True, **bwd_kw),
    }
    check_invariants(res)

    print(f"[overlap_bench] "
          f"{res['fp32']['_layout']['num_buckets']} buckets x "
          f"{res['fp32']['_layout']['bucket_elems']} elems")
    print("| mode | serial model ms | overlap model ms | model speedup |"
          " serial ms | overlap ms |")
    for mode in ("fp32", "int8"):
        m = res[mode]["model"]
        print(f"| {mode} | {m['serial_model_s'] * 1e3:15.3f} | "
              f"{m['overlap_model_s'] * 1e3:16.3f} | "
              f"{m['model_speedup']:13.2f} | "
              f"{res[mode]['serial']['avg_ms']:9.2f} | "
              f"{res[mode]['overlap']['avg_ms']:10.2f} |")
    print("| backward-overlap | bwd ms | after-bwd pipeline ms | "
          "bwd-overlap ms | speedup |")
    for mode in ("backward_fp32", "backward_int8"):
        m = res[mode]["model"]
        print(f"| {mode} | {m['bwd_total_s'] * 1e3:6.3f} | "
              f"{m['after_backward_model_s'] * 1e3:21.3f} | "
              f"{m['backward_overlap_model_s'] * 1e3:14.3f} | "
              f"{m['model_speedup_vs_after_backward']:7.2f} |")
    with open(out, "w") as fh:
        json.dump(res, fh, indent=2)
    print(f"[overlap_bench] wrote {out}; modeled overlapped step "
          f"{res['int8']['model']['model_speedup']:.2f}x faster than "
          f"serial (int8), backward-overlap "
          f"{res['backward_int8']['model']['model_speedup_vs_after_backward']:.2f}x "
          f"faster than the after-backward pipeline (int8), exact fp32 "
          f"match with monolithic: {res['fp32']['exact_match']}")
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small tree, 2 iters, invariant smoke check")
    ap.add_argument("--out", default="BENCH_overlap.json")
    ap.add_argument("--bucket-mb", type=float, default=0.25)
    args = ap.parse_args()
    main(quick=args.quick, out=args.out, bucket_mb=args.bucket_mb)
