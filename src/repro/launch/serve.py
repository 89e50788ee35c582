"""Continuous-batching serving driver on the heterogeneous mesh.

Replaces the old static-batch demo: requests arrive open-loop, are
routed across pods by capacity score (slow pods hold proportionally
fewer concurrent sequences), prefilled in length buckets into a paged
KV cache, and decoded one token per step at per-sequence depths —
finished sequences release their blocks immediately and new arrivals
take their slots mid-flight. See docs/architecture.md §serving engine.

Sharding note: the decode-slot batch and the prefill batch shard over
the DP axes ONLY when divisible by the DP extent; otherwise the step
builders fall back to fully-replicated batches and warn loudly (every
rank computes the whole batch — a real throughput loss, not a
cosmetic detail). Pick ``--slots``/``--prefill-batch`` as multiples of
prod(devices[:-1]).

Example (CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --smoke --slots 4 --requests 12 --pod-speeds 1,0.5
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgbase
from repro.configs.base import ShapeConfig
from repro.launch import sharding as shr
from repro.launch import steps as steps_mod
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import dp_size, make_mesh
from repro.models.kvcache import PagedLayout
from repro.models.model import Model, build_model
from repro.serve import (CapacityRouter, EngineConfig, Request, Scheduler,
                         ServeEngine)


def build_engine(model: Model, params, mesh, layout: PagedLayout,
                 slots: int, prefill_batch: int,
                 pod_speeds: Sequence[float],
                 bucket_lens: Optional[Sequence[int]] = None
                 ) -> ServeEngine:
    """Wire scheduler + jitted paged steps into a ServeEngine.

    Compiles one decode step (fixed (slots,) shapes, cache donated) and
    one prefill step per length bucket (fixed (prefill_batch, bucket)
    shapes, cache donated). Call — and run the engine — inside
    ``jax.set_mesh(mesh)``.
    """
    router = CapacityRouter(slots, pod_speeds)
    sched = Scheduler(layout, router, slots, bucket_lens)
    decode = steps_mod.build_paged_decode_step(model, mesh, layout, slots)
    prefill_fns = {
        b: functools.partial(
            steps_mod.build_paged_prefill_step(model, mesh, layout, b,
                                               prefill_batch),
            params)
        for b in sched.bucket_lens}
    cache_shape = jax.eval_shape(
        functools.partial(model.init_paged_cache, layout))
    cspecs = shr.paged_cache_specs(model.cfg, cache_shape, mesh)
    init_cache_fn = jax.jit(
        functools.partial(model.init_paged_cache, layout),
        out_shardings=shr.named(mesh, cspecs))
    return ServeEngine(EngineConfig(decode_slots=slots,
                                    prefill_batch=prefill_batch,
                                    attention_impl=model.cfg.attention_impl),
                       layout, sched, functools.partial(decode, params),
                       prefill_fns, init_cache_fn)


def synthetic_requests(n: int, vocab: int, rate: float,
                       prompt_lens: Tuple[int, int],
                       gen_lens: Tuple[int, int], seed: int
                       ) -> List[Request]:
    """Open-loop Poisson arrivals with mixed prompt/gen lengths."""
    rng = np.random.default_rng(seed)
    reqs, t = [], 0.0
    for rid in range(n):
        t += float(rng.exponential(1.0 / rate)) if rate > 0 else 0.0
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        glen = int(rng.integers(gen_lens[0], gen_lens[1] + 1))
        prompt = tuple(int(x) for x in rng.integers(0, vocab, plen))
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new_tokens=glen, arrival=t))
    return reqs


def static_generate(model: Model, params, mesh, prompts: np.ndarray,
                    gen: int) -> np.ndarray:
    """Static-batch reference path (the pre-engine serving loop): one
    shared prompt length, every sequence decodes ``gen`` tokens in
    lock-step. Kept as the bit-identity baseline for the paged path
    (benchmarks/serve_bench.py) and as the non-paged comparison point.
    Returns (B, gen) generated token ids."""
    batch, prompt_len = prompts.shape
    shape = ShapeConfig("serve-static", prompt_len + gen, batch, "decode")
    prefill = steps_mod.build_prefill_step(model, shape, mesh)
    decode = steps_mod.build_decode_step(model, shape, mesh)
    logits, cache = prefill(params, jnp.asarray(prompts, jnp.int32))
    out = [np.argmax(np.asarray(logits), axis=-1)]
    tok = jnp.asarray(out[-1], jnp.int32)
    for i in range(gen - 1):
        pos = jnp.int32(prompt_len + i)
        logits, cache = decode(params, tok, cache, pos)
        out.append(np.argmax(np.asarray(logits), axis=-1))
        tok = jnp.asarray(out[-1], jnp.int32)
    return np.stack(out, axis=1)


def paged_layout(args) -> PagedLayout:
    """The KV pool the CLI arguments ask for: blocks for the longest
    prompt + generation per slot, ``--num-blocks`` (0 = slots x that)
    in the pool."""
    max_seq = args.max_prompt + args.max_gen
    mbs = -(-max_seq // args.block_size)
    num_blocks = args.num_blocks or args.slots * mbs
    return PagedLayout(block_size=args.block_size,
                       num_blocks=num_blocks, max_blocks_per_seq=mbs)


def serve(args):
    cfg = (cfgbase.smoke_config(args.arch) if args.smoke
           else cfgbase.resolve(args.arch))
    if cfg.frontend != "token":
        raise SystemExit(f"--arch {args.arch}: the serving engine "
                         f"requires a token frontend")
    cfg = dataclasses.replace(cfg, attention_impl=args.attention_impl)
    model = build_model(cfg)
    dshape = tuple(int(x) for x in args.devices.split(","))
    axes = ("data", "model") if len(dshape) == 2 else ("pod", "data",
                                                       "model")
    mesh = make_mesh(dshape, axes)
    pod_speeds = ([float(s) for s in args.pod_speeds.split(",")]
                  if args.pod_speeds else [1.0] * dp_size(mesh))

    layout = paged_layout(args)
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(args.seed))
    reqs = synthetic_requests(
        args.requests, cfg.vocab_size, args.rate,
        (args.min_prompt, args.max_prompt), (args.min_gen, args.max_gen),
        args.seed)

    with jax.set_mesh(mesh):
        engine = build_engine(model, params, mesh, layout, args.slots,
                              args.prefill_batch, pod_speeds)
        result = engine.run(reqs)

    s = result.stats
    print(f"[serve] {cfg.name}: {s['requests']} requests, "
          f"{s['total_tokens']} tokens, pods {pod_speeds} "
          f"limits {s['pod_limits']}")
    print(f"[serve] modeled {s['modeled_tokens_per_sec']:.2f} tok/unit "
          f"(p50 {s['p50_time_per_token']:.3f} / "
          f"p99 {s['p99_time_per_token']:.3f} per token, "
          f"ttft {s['mean_ttft']:.3f})")
    print(f"[serve] measured ttft p50 {s['ttft_ms_p50']:.1f} / p99 "
          f"{s['ttft_ms_p99']:.1f} ms, token gap p50 "
          f"{s['token_gap_ms_p50']:.2f} / p99 {s['token_gap_ms_p99']:.2f} ms")
    print(f"[serve] {s['decode_steps']} decode steps, "
          f"{s['prefill_groups']} prefill groups, "
          f"{s['preemptions']} preemptions, block util "
          f"mean {s['block_util_mean']:.2f} peak {s['block_util_peak']:.2f},"
          f" wall {s['wall_seconds']:.1f}s")
    rid0 = min(result.tokens)
    print(f"[serve] sample tokens[{rid0}]: "
          f"{result.tokens[rid0][:12]}")
    return result


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", default="1,1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (concurrent sequences)")
    ap.add_argument("--prefill-batch", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged pool size (0 = slots x max blocks/seq)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="open-loop arrival rate (requests per unit)")
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--min-gen", type=int, default=4)
    ap.add_argument("--max-gen", type=int, default=32)
    ap.add_argument("--pod-speeds", default="",
                    help="comma list of modeled pod speeds "
                         "(default: 1.0 per DP rank)")
    ap.add_argument("--attention-impl", default="reference",
                    choices=list(cfgbase.ATTENTION_IMPLS),
                    help="decode attention kernels: 'pallas' gathers KV "
                         "blocks through the block table inside the "
                         "kernel (interpret-mode fallback, loudly, off "
                         "TPU); 'reference' materializes the window")
    return ap.parse_args(argv)


def main():
    use_compile_cache()
    serve(parse_args())


if __name__ == "__main__":
    main()
