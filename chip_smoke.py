#!/usr/bin/env python3
"""Bring-up smoke: the heterogeneous trainer and the paged server on a TPU.

Drives the program's own entry points, in one process, at published
model widths with random weights from ``--seed``:

* train: ``repro.launch.train.train()`` on olmo-1b (d_model 2048, 16
  heads, d_ff 8192, vocab 50304) cut in depth to 4 of its 16 layers,
  seq-len 2048, global batch 4, ``--devices 1,1``, 5 steps. Every loss
  must be finite and the last below the first.
* serve: ``repro.launch.serve.serve()`` on tinyllama-1.1b at full width
  and depth with ``--attention-impl pallas``: 8 slots, prefill batch 2,
  8 requests, prompts of 64-512 tokens, 16-64 generated. Every request
  must be answered, the jitted decode step must contain the compiled
  Pallas kernel (``tpu_custom_call``), and one decode step's logits
  must match ``attention_impl="reference"`` within a bf16 tolerance.

``--four-chips`` runs only the uneven-share data-parallel check:
``train()`` on ``--devices 4,1 --capacities 2,1,1,1`` against the same
global rows on one device; first-step losses must agree within a bf16
tolerance and the batch shards must sit on all four devices.

Progress goes to earlier lines; the last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero, names the platform it found, and prints no
result. Synthetic data and checkpoints live under ``.smoke/seed<N>/``
in the checkout and are removed at the end.

Usage:
  python chip_smoke.py [--seed N]
  python chip_smoke.py --four-chips [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# bf16 keeps 8 significant bits: one rounding of a value moves it by up
# to 2^-8 of itself. A first-step loss (a mean over ~10^4 tokens, same
# weights and rows, only the device partitioning differs) may move by
# one such rounding. A rank's rows missing from the loss is an O(1)
# relative error and fails the bound by far.
BF16_EPS = 2.0 ** -8
# Decode logits pass through 22 layers where the paged kernel and the
# reference round differently (another summation order, another MXU
# precision for f32 matmuls); how far that moves them is measured on
# the spot as the reference's own distance from an fp32 run, and the
# kernel may be off by this factor times that (see decode_parity).
GOLD_FACTOR = 2.0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX "
                         f"found {len(devs)}")
    return devs


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def olmo_cut():
    """olmo-1b at its published width, 4 of its 16 layers."""
    from repro.configs import base as cfgbase

    return dataclasses.replace(cfgbase.resolve("olmo-1b"), num_layers=4)


def run_train(work: Path, seed: int, devices: str, global_batch: int,
              steps: int, capacities: str = ""):
    from repro.launch import train as train_mod

    argv = ["--arch", "olmo-1b", "--steps", str(steps),
            "--global-batch", str(global_batch), "--seq-len", "2048",
            "--devices", devices, "--log-every", "1", "--warmup", "1",
            "--seed", str(seed),
            "--data-dir", str(work / "train_data"),
            "--ckpt-dir", str(work / f"ckpt_{devices.replace(',', 'x')}")]
    if capacities:
        argv += ["--capacities", capacities]
    res = train_mod.train(train_mod.parse_args(argv), olmo_cut())
    losses = res.get("losses", [])
    check(len(losses) == steps, f"train ran {len(losses)} of {steps} steps")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite train loss: {losses}")
    for i, (loss, dt) in enumerate(zip(losses, res["step_s"]), 1):
        log(f"train devices={devices} step {i} loss {loss!r} wall "
            f"{dt!r} s{' (includes compile)' if i == 1 else ''}")
    return res


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return repr(stats.get("peak_bytes_in_use", "not reported"))


def train_phase(work: Path, seed: int) -> None:
    res = run_train(work, seed, "1,1", global_batch=4, steps=5)
    losses = res["losses"]
    check(losses[-1] < losses[0],
          f"train loss did not fall: {losses[0]!r} -> {losses[-1]!r}")
    log(f"train ok: loss {losses[0]!r} -> {losses[-1]!r}, device 0 "
        f"peak_bytes_in_use {peak_bytes()}")


def serve_args(seed: int):
    from repro.launch import serve as serve_mod

    return serve_mod.parse_args([
        "--arch", "tinyllama-1.1b", "--attention-impl", "pallas",
        "--devices", "1,1", "--slots", "8", "--prefill-batch", "2",
        "--requests", "8", "--min-prompt", "64", "--max-prompt", "512",
        "--min-gen", "16", "--max-gen", "64", "--seed", str(seed)])


def serve_phase(seed: int) -> None:
    from repro.configs import base as cfgbase
    from repro.launch import serve as serve_mod

    args = serve_args(seed)
    t0 = time.time()
    result = serve_mod.serve(args)
    wall = time.time() - t0
    want = {r.rid: r.max_new_tokens for r in serve_mod.synthetic_requests(
        args.requests, cfgbase.resolve(args.arch).vocab_size, args.rate,
        (args.min_prompt, args.max_prompt), (args.min_gen, args.max_gen),
        args.seed)}
    got = {rid: len(toks) for rid, toks in result.tokens.items()}
    check(got == want, f"requests not all answered in full: generated "
                       f"{got}, asked {want}")
    check(result.stats.get("attention_impl") == "pallas",
          f"serve ran attention_impl={result.stats.get('attention_impl')}")
    log(f"serve ok: {len(got)}/{len(want)} requests answered, "
        f"{sum(got.values())} tokens, {result.stats['decode_steps']} "
        f"decode steps, wall {wall!r} s (includes compile); device 0 "
        f"peak_bytes_in_use {peak_bytes()}")
    decode_parity(args)


def decode_parity(args) -> None:
    """One decode step on the serve phase's model, layout and slot
    count, over a random paged cache, three ways: the pallas kernel and
    the reference path at the model's compute dtype, and the reference
    path in fp32 at full matmul precision (the gold).

    The bound on the bf16 paths is measured, not guessed: the pallas
    logits may sit no further from the reference's, nor from the gold,
    than ``GOLD_FACTOR`` times the reference's own distance from the
    gold. Both bf16 paths round at every one of the 22 layers; a
    kernel that dropped a block or mis-masked a position is off by
    O(1) and fails by far."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import base as cfgbase
    from repro.launch import serve as serve_mod
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model

    cfg = cfgbase.resolve(args.arch)
    models = {
        "pallas": build_model(dataclasses.replace(
            cfg, attention_impl="pallas")),
        "reference": build_model(dataclasses.replace(
            cfg, attention_impl="reference")),
        "fp32": build_model(dataclasses.replace(
            cfg, attention_impl="reference", compute_dtype="float32")),
    }
    mesh = make_mesh((1, 1), ("data", "model"))
    layout = serve_mod.paged_layout(args)
    slots = args.slots
    rng = np.random.default_rng(args.seed)
    # ragged depths; the new token lands at position kv_len, inside the
    # sequence's last mapped block
    kv_lens = rng.integers(args.min_prompt,
                           layout.max_seq_len - 1, size=slots)
    tables = np.full((slots, layout.max_blocks_per_seq), layout.null_block,
                     np.int32)
    perm = rng.permutation(layout.num_blocks)
    used = 0
    for i, n in enumerate(kv_lens):
        nb = layout.blocks_for(int(n) + 1)
        tables[i, :nb] = perm[used:used + nb]
        used += nb
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, slots), jnp.int32)
    tables = jnp.asarray(tables)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)

    def random_cache(dtype):
        # the same values for every path (bf16 -> fp32 is exact); the
        # step donates its cache, so each path gets its own copy
        shapes = jax.eval_shape(functools.partial(
            models["pallas"].init_paged_cache, layout))
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 1),
                                len(shapes))
        return {name: jax.random.normal(k, s.shape, s.dtype).astype(dtype)
                for k, (name, s) in zip(keys, sorted(shapes.items()))}

    params = steps_mod.init_params_sharded(models["pallas"], mesh,
                                           jax.random.PRNGKey(args.seed))
    logits = {}
    with jax.set_mesh(mesh):
        for name, model in models.items():
            precision = "highest" if name == "fp32" else None
            with jax.default_matmul_precision(precision):
                step = steps_mod.build_paged_decode_step(model, mesh,
                                                         layout, slots)
                cache = random_cache(model.cfg.compute_dtype)
                if name == "pallas":
                    hlo = step.lower(params, tokens, cache, tables,
                                     kv_lens).as_text()
                    check("tpu_custom_call" in hlo,
                          "the pallas decode step holds no "
                          "tpu_custom_call: the kernel did not compile "
                          "for the chip")
                out, _ = step(params, tokens, cache, tables, kv_lens)
            logits[name] = np.asarray(out, np.float32)
    lp, lr, lg = logits["pallas"], logits["reference"], logits["fp32"]
    check(all(np.isfinite(x).all() for x in (lp, lr, lg)),
          "non-finite decode logits")
    gap = float(np.abs(lp - lr).max())
    err_p = float(np.abs(lp - lg).max())
    err_r = float(np.abs(lr - lg).max())
    bound = GOLD_FACTOR * err_r
    agree = float(np.mean(lp.argmax(-1) == lr.argmax(-1)))
    agree_gold = float(np.mean(lp.argmax(-1) == lg.argmax(-1)))
    log(f"decode parity: max |pallas - reference| logit gap {gap!r}; "
        f"off the fp32 gold: pallas {err_p!r}, reference {err_r!r}; "
        f"bound {bound!r}; logit scale {float(np.abs(lg).max())!r}; "
        f"greedy tokens agree with the reference on {agree!r} and with "
        f"the gold on {agree_gold!r} of {slots} slots")
    check(gap <= bound, f"pallas decode logits off the reference by "
                        f"{gap!r} (> {bound!r})")
    check(err_p <= bound, f"pallas decode logits off the fp32 gold by "
                          f"{err_p!r} (> {bound!r})")


def four_chip_phase(work: Path, seed: int) -> None:
    """Uneven-share DP on 4 chips vs one device over the same rows."""
    import jax

    devs = jax.devices()
    het = run_train(work, seed, "4,1", global_batch=5, steps=2,
                    capacities="2,1,1,1")
    placed = het["batch_rows_by_device"]
    log(f"four-chip batch rows by device id: {placed}")
    check(sorted(placed) == sorted(d.id for d in devs[:4]),
          f"batch shards on devices {sorted(placed)}, not on all four "
          f"{sorted(d.id for d in devs[:4])}")
    one = run_train(work, seed, "1,1", global_batch=5, steps=2)
    a, b = het["first_loss"], one["first_loss"]
    gap = abs(a - b)
    log(f"first-step loss: 4 chips {a!r}, 1 device {b!r}, gap {gap!r}, "
        f"bound {BF16_EPS * abs(b)!r}")
    check(gap <= BF16_EPS * abs(b),
          f"4-chip first-step loss {a!r} off the 1-device loss {b!r}")


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip uneven-share check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)

    from repro.launch.cache import use_compile_cache

    log(f"device {devs[0].device_kind} x{len(devs)}, compile cache "
        f"{use_compile_cache()}")
    work = ROOT / ".smoke" / f"seed{args.seed}"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    try:
        if args.four_chips:
            four_chip_phase(work, args.seed)
        else:
            train_phase(work, args.seed)
            serve_phase(args.seed)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
