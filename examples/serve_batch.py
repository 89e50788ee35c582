"""Batched serving: prefill a prompt batch, decode with a KV cache.

Uses the production serve steps (launch/steps.py) — the same lowering
the decode_32k dry-run cell proves at 512 chips — on a small model and
host devices, and reports prefill latency + decode throughput.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python examples/serve_batch.py
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import base as cfgbase
from repro.configs.base import ShapeConfig
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models.model import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    cfg = cfgbase.smoke_config(args.arch)
    model = build_model(cfg)
    n_dev = len(jax.devices())
    data = 2 if n_dev >= 4 else 1
    mdl = 2 if n_dev >= 4 else 1
    mesh = make_mesh((data, mdl), ("data", "model"))
    max_len = args.prompt_len + args.gen
    shape = ShapeConfig("serve", max_len, args.batch, "decode")

    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(0))
    with jax.set_mesh(mesh):
        prefill = steps_mod.build_prefill_step(model, shape, mesh)
        decode = steps_mod.build_decode_step(model, shape, mesh)
        rng = np.random.default_rng(0)
        prompts = jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size,
                                     (args.batch, args.prompt_len)),
                        jnp.int32),
            NamedSharding(mesh, P(("data",), None)))

        t0 = time.time()
        logits, cache = prefill(params, prompts)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        print(f"[serve] prefill({args.batch}x{args.prompt_len}) "
              f"{t_prefill * 1e3:.1f} ms")

        tok_sharding = NamedSharding(mesh, P(("data",)))
        tok = jax.device_put(jnp.argmax(logits, -1).astype(jnp.int32),
                             tok_sharding)
        out_tokens = [np.asarray(tok)]
        t0 = time.time()
        for i in range(args.gen):
            logits, cache = decode(params, tok, cache,
                                   jnp.int32(args.prompt_len + i))
            tok = jax.device_put(jnp.argmax(logits, -1).astype(jnp.int32),
                                 tok_sharding)
            out_tokens.append(np.asarray(tok))
        jax.block_until_ready(logits)
        t_dec = time.time() - t0
    toks = np.stack(out_tokens, 1)
    print(f"[serve] decoded {args.gen} tokens x {args.batch} seqs in "
          f"{t_dec * 1e3:.0f} ms ({args.batch * args.gen / t_dec:.1f} "
          f"tok/s)")
    print(f"[serve] sequence 0: {toks[0][:16].tolist()}")
    print("[serve] OK")


if __name__ == "__main__":
    main()
