"""Reduction benchmark: per-leaf vs bucketed cross-pod gradient exchange.

Measures, on the 8-host-device mesh (2 pods x 2 data x 2 model), the
four cross-pod reduction schedules wired behind ``HetConfig``:

  per_leaf        — legacy: one psum per pytree leaf
  per_leaf_int8   — legacy: one quantize + full-payload gathers per leaf
  bucketed        — flat-buffer engine: ONE psum_scatter + ONE gather
  bucketed_int8   — flat-buffer engine: ONE fused quantize + ONE payload
                    all_to_all + fused dequant-accum + ONE gather

For each path it reports:
  * cross-pod collective-launch count, counted from the jaxpr (the
    latency-bound quantity a heterogeneous DCN link cares about);
  * modeled per-rank DCN bytes (core/buckets.py byte models);
  * measured wall time per reduction on the host mesh;
  * max abs error vs the exact sum.

Acceptance invariant (checked loudly in ``--quick`` mode and on every
full run): the bucketed paths must issue at most
``ceil(total_param_bytes / bucket_bytes)`` = num_buckets cross-pod
collectives per step, and strictly fewer than the per-leaf paths.

Emits ``BENCH_reduce.json`` (``--out`` to relocate).
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
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import buckets as bkt
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh

_BLOCK = steps_mod._BLOCK
_COLLECTIVES = ("psum", "all_gather", "all_to_all", "reduce_scatter",
                "all_reduce", "ppermute")


def count_pod_collectives(fn, *args) -> int:
    """Count cross-pod collective eqns in the traced jaxpr of ``fn``."""
    closed = jax.make_jaxpr(fn)(*args)

    def mentions_pod(params) -> bool:
        for key in ("axes", "axis_name", "axis_index_groups"):
            v = params.get(key)
            if v is None:
                continue
            names = v if isinstance(v, (tuple, list)) else (v,)
            if any(n == "pod" for n in names):
                return True
        return False

    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _COLLECTIVES and \
                    mentions_pod(eqn.params):
                n += 1
            for v in eqn.params.values():
                for j in jax.tree.leaves(
                        v, is_leaf=lambda x: hasattr(x, "eqns")):
                    if hasattr(j, "eqns"):
                        n += walk(j)
                if hasattr(v, "jaxpr"):           # ClosedJaxpr
                    n += walk(v.jaxpr)
        return n

    return walk(closed.jaxpr)


def synthetic_grad_tree(num_leaves: int, scale: int,
                        seed: int = 0) -> Dict[str, jnp.ndarray]:
    """A transformer-shaped pytree: many mixed-size 1D/2D leaves."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(num_leaves):
        if i % 4 == 0:
            shape: Tuple[int, ...] = (scale + i,)              # biases/norms
        elif i % 4 == 1:
            shape = (scale, scale)                             # square proj
        elif i % 4 == 2:
            shape = (scale, 2 * scale + 1)                     # odd ffn
        else:
            shape = (3, scale, scale // 2)                     # stacked qkv
        tree[f"leaf_{i:02d}"] = jnp.asarray(
            rng.standard_normal(shape).astype(np.float32))
    return tree


def bench_paths(tree: Dict[str, jnp.ndarray], mesh, pods: int,
                bucket_mb: float, iters: int) -> Dict[str, Any]:
    layout = bkt.build_layout(tree, bucket_mb=bucket_mb,
                              multiple_of=pods * _BLOCK)
    # per-pod contributions: pod p holds tree * weight_p
    weights = [1.0, -0.5, 0.25, 2.0][:pods]
    stacked = jax.tree.map(
        lambda v: jnp.stack([w * v for w in weights]), tree)
    ref = jax.tree.map(lambda v: sum(w * np.asarray(v) for w in weights),
                       tree)
    spec = jax.tree.map(lambda _: NamedSharding(mesh, P("pod")), stacked)
    stacked = jax.device_put(stacked, spec)

    def per_leaf(compress):
        def f(gl):
            g = jax.tree.map(lambda a: a[0], gl)
            out, _ = steps_mod._cross_pod_reduce(g, (), compress, pods)
            return out
        return f

    def bucketed(compress):
        def f(gl):
            g = jax.tree.map(lambda a: a[0], gl)
            flat = bkt.pack_buckets(g, layout)
            red, _ = bkt.exchange_buckets(
                flat, None, axis="pod", axis_size=pods,
                compress=compress, block_size=_BLOCK,
                total=layout.total)
            return bkt.unpack_buckets(red, layout)
        return f

    paths = {
        "per_leaf": (per_leaf("none"), False, False),
        "per_leaf_int8": (per_leaf("int8"), True, False),
        "bucketed": (bucketed(False), False, True),
        "bucketed_int8": (bucketed(True), True, True),
    }

    results = {}
    for name, (f, compress, is_bucketed) in paths.items():
        sm = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                           out_specs=P(), axis_names={"pod"},
                           check_vma=False)
        jf = jax.jit(sm)
        out = jax.block_until_ready(jf(stacked))       # compile + warmup
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jax.block_until_ready(jf(stacked))
        dt = (time.perf_counter() - t0) / iters
        err = max(
            float(np.max(np.abs(np.asarray(a, np.float32) - b)))
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)))
        if is_bucketed:
            dcn = bkt.modeled_link_bytes(layout, pods, compress=compress,
                                         block_size=_BLOCK)
        else:
            dcn = bkt.modeled_per_leaf_bytes(tree, pods, compress=compress,
                                             block_size=_BLOCK)
        results[name] = {
            "collectives": count_pod_collectives(sm, stacked),
            "modeled_dcn_bytes_per_rank": dcn,
            "avg_ms": dt * 1e3,
            "max_abs_err": err,
        }
    results["_layout"] = {
        "leaves": len(jax.tree.leaves(tree)),
        "total_elems": layout.total,
        "total_bytes": layout.total_bytes,
        "bucket_mb": bucket_mb,
        "bucket_elems": layout.bucket_elems,
        "num_buckets": layout.num_buckets,
        "collective_bound": layout.num_buckets,
        # the schedule is 2 launches/step for the whole tree
        "native_bucketed_collectives": 2,
    }
    return results


def check_invariants(res: Dict[str, Any]) -> None:
    """The acceptance invariant — fail loudly on regression."""
    # the schedule has an inherent floor independent of bucket count:
    # 2 launches (exchange + broadcast legs); a layout with fewer
    # buckets than the floor cannot go below it
    floor = 2
    bound = max(res["_layout"]["collective_bound"], floor)
    for name in ("bucketed", "bucketed_int8"):
        c = res[name]["collectives"]
        assert c <= bound, (
            f"{name}: {c} cross-pod collectives exceeds "
            f"max(ceil(total_bytes/bucket_bytes), schedule floor)="
            f"{bound}")
    for b, pl in (("bucketed", "per_leaf"),
                  ("bucketed_int8", "per_leaf_int8")):
        assert res[b]["collectives"] < res[pl]["collectives"], (
            f"{b} ({res[b]['collectives']}) not fewer launches than "
            f"{pl} ({res[pl]['collectives']})")
    # exact paths must agree to fp tolerance; int8 to quantization tol
    assert res["bucketed"]["max_abs_err"] <= 1e-5
    assert res["per_leaf"]["max_abs_err"] <= 1e-5
    # the bucketed int8 wire payload counts DATA blocks only (the
    # all-padding tail blocks are never transmitted): packing whole
    # streams can never model MORE DCN bytes than quantizing leaf by
    # leaf, which pads every leaf up to a block boundary
    assert (res["bucketed_int8"]["modeled_dcn_bytes_per_rank"]
            <= res["per_leaf_int8"]["modeled_dcn_bytes_per_rank"]), (
        "bucketed int8 models more DCN bytes than per-leaf int8 "
        f"({res['bucketed_int8']['modeled_dcn_bytes_per_rank']} > "
        f"{res['per_leaf_int8']['modeled_dcn_bytes_per_rank']})")


def main(quick: bool = False, out: str = "BENCH_reduce.json",
         bucket_mb: float = 0.25) -> Dict[str, Any]:
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pods = 2
    if quick:
        tree = synthetic_grad_tree(num_leaves=12, scale=24)
        bucket_mb = min(bucket_mb, 0.002)    # keep several buckets
        iters = 2
    else:
        tree = synthetic_grad_tree(num_leaves=48, scale=96)
        iters = 8

    res = bench_paths(tree, mesh, pods, bucket_mb, iters)
    check_invariants(res)

    lay = res["_layout"]
    print(f"[reduce_bench] {lay['leaves']} leaves, "
          f"{lay['total_bytes'] / 1e6:.2f} MB grads, "
          f"{lay['num_buckets']} buckets x {lay['bucket_elems']} elems "
          f"(bound: <= {lay['collective_bound']} cross-pod collectives)")
    hdr = (f"| {'path':14s} | colls | modeled DCN MB | avg ms | "
           f"max abs err |")
    print(hdr)
    for name in ("per_leaf", "per_leaf_int8", "bucketed", "bucketed_int8"):
        r = res[name]
        print(f"| {name:14s} | {r['collectives']:5d} | "
              f"{r['modeled_dcn_bytes_per_rank'] / 1e6:14.3f} | "
              f"{r['avg_ms']:6.2f} | {r['max_abs_err']:11.2e} |")

    res["speedup"] = {
        "collective_reduction_exact":
            res["per_leaf"]["collectives"] / res["bucketed"]["collectives"],
        "collective_reduction_int8":
            res["per_leaf_int8"]["collectives"] /
            res["bucketed_int8"]["collectives"],
        "dcn_bytes_reduction_int8":
            res["per_leaf_int8"]["modeled_dcn_bytes_per_rank"] /
            res["bucketed_int8"]["modeled_dcn_bytes_per_rank"],
    }
    with open(out, "w") as fh:
        json.dump(res, fh, indent=2)
    print(f"[reduce_bench] wrote {out}; collective reduction "
          f"{res['speedup']['collective_reduction_exact']:.0f}x exact / "
          f"{res['speedup']['collective_reduction_int8']:.0f}x int8")
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small tree, 2 iters, invariant smoke check")
    ap.add_argument("--out", default="BENCH_reduce.json")
    ap.add_argument("--bucket-mb", type=float, default=0.25)
    args = ap.parse_args()
    main(quick=args.quick, out=args.out, bucket_mb=args.bucket_mb)
