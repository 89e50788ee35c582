"""Continuous-batching serving benchmark (fail-loud) -> BENCH_serve.json.

Runs the real serving engine (repro/serve: paged KV cache, per-sequence
decode depths, capacity-aware admission) on a tiny fp32 model over an
open-loop mixed-length trace and asserts three invariants, loudly:

(a) **Continuous batching pays.** Modeled tokens/sec of the engine must
    be STRICTLY above a static-batch baseline modeled on the SAME trace
    with the same cost model (one unit == one decode-token on a
    speed-1.0 pod). The baseline is the pre-engine serving loop: FIFO
    batches of ``slots`` requests, wait for the whole batch to arrive,
    pad prefill to the batch-max prompt, decode in lock-step until the
    batch-max generation length, split rows evenly across pods
    (capacity-unaware). The engine admits on arrival, frees slots the
    moment a sequence finishes, and routes min-max active/speed — if it
    cannot beat lock-step padding under mixed-length traffic, the whole
    subsystem is dead weight.

(b) **Bit-identity.** For a single sequence the paged path must be an
    implementation detail: generated token ids from the engine (block
    tables, bucket-padded prefill, ``mode="drop"`` scatter /
    ``mode="fill"`` gather) must equal ``launch/serve.static_generate``
    (contiguous cache, scalar position) exactly, token for token, in
    fp32 with dense attention. Any drift means the block indexing or
    padding masks leak into the math.

(c) **Capacity-aware routing.** Under saturation (arrivals all at t=0,
    2x the slot count) with skewed pod speeds, per-pod peak concurrency
    must equal the CapacityPlan row split — proportional to speed, so a
    slower pod holds strictly fewer concurrent sequences than a faster
    one — and never exceed it.

(d) **Decode-step roofline.** Modeled HBM bytes/token of the paged
    Pallas decode kernels (`attention_impl="pallas"`: KV blocks
    gathered through the block table INSIDE the kernel, one DMA pass,
    scores/probs never leave VMEM) must be STRICTLY below the
    materialize-then-attend model (`"reference"`: gather read + window
    write + attend re-read, plus fp32 score/prob round-trips) at every
    realistic (max_blocks, block_size) point, for both GQA and
    absorbed-MLA head geometries. The measured leg runs the engine on
    the smoke trace with both impls and asserts token-identity — the
    kernel's byte advantage is only claimable if its math is the
    reference's math.

Also records block-pool utilization (mean/peak) and the p50/p99 modeled
time-per-token of the engine run. Quick mode shrinks the trace; the
invariants are identical in both tiers. The emitted JSON is
byte-deterministic given ``seed`` — wall-clock timings are printed,
never written.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "src"))

import jax
import numpy as np

jax.config.update("jax_platform_name", "cpu")

from repro.configs import base as cfgbase
from repro.launch import serve as serve_mod
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models.kvcache import PagedLayout
from repro.models.model import build_model
from repro.serve import Request


def _tiny_model():
    # fp32 + dense attention: bitwise-reproducible reference math
    cfg = dataclasses.replace(
        cfgbase.smoke_config("tinyllama-1.1b"),
        compute_dtype="float32", attention_impl="dense",
        num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
        d_ff=64, vocab_size=64)
    return cfg, build_model(cfg)


def _layout(slots: int, max_seq: int, block_size: int = 4) -> PagedLayout:
    mbs = -(-max_seq // block_size)
    return PagedLayout(block_size=block_size, num_blocks=slots * mbs,
                       max_blocks_per_seq=mbs)


def _even_split(rows: int, pods: int) -> List[int]:
    base, rem = divmod(rows, pods)
    return [base + (1 if p < rem else 0) for p in range(pods)]


def _gqa_decode_bytes(mb: int, bs: int, hkv: int, q_per_kv: int,
                      dh: int, itemsize: int) -> Dict[str, int]:
    """Modeled HBM bytes to decode ONE token of ONE sequence through
    ONE GQA attention layer, paged KV window of ``mb`` blocks x ``bs``
    tokens.

    kernel (in-kernel gather, flash_decode_paged_pallas): each K/V
    block crosses HBM->VMEM exactly once via the block-table-driven
    DMA; q in, o out; scores/probs live in VMEM scratch only.

    materialize (reference): ``.at[tables].get`` reads the window and
    WRITES a contiguous copy, attend re-reads it, and the dense softmax
    round-trips fp32 scores and probs (write+read each) through HBM.
    """
    h = hkv * q_per_kv
    window = 2 * mb * bs * hkv * dh * itemsize       # K + V blocks
    qo = 2 * h * dh * itemsize                       # q read + out write
    probs = 4 * h * mb * bs * 4                      # scores + probs, wr+rd, fp32
    return {"kernel": window + qo,
            "materialize": 3 * window + qo + probs}


def _mla_decode_bytes(mb: int, bs: int, h: int, r: int, dr: int,
                      itemsize: int) -> Dict[str, int]:
    """Same model for absorbed-MLA decode (latent rank ``r``, rope dim
    ``dr``). The streaming kernel reads each ckv/kr tile once and
    reuses the ckv tile in VMEM for BOTH the score and value matmuls;
    the reference gathers, writes the window, then reads ckv twice
    (score + value) and kr once, with the same fp32 prob round-trips.
    """
    s_g = mb * bs
    ckv, kr = s_g * r * itemsize, s_g * dr * itemsize
    qo = (h * (r + dr) + h * r) * itemsize           # q_abs+q_r in, out
    probs = 4 * h * s_g * 4
    return {"kernel": ckv + kr + qo,
            "materialize": 4 * ckv + 3 * kr + qo + probs}


def _static_baseline(reqs: Sequence[Request], slots: int,
                     speeds: Sequence[float]) -> Dict:
    """Model the pre-engine static-batch loop on the same trace.

    Same cost model as ServeEngine: prefill of an L-padded group costs
    max_p rows_p * L / speed_p, one decode iteration costs
    max_p rows_p / speed_p. FIFO batches of ``slots``; a batch starts
    only when its last member has arrived AND the previous batch
    finished; every row decodes to the batch-max generation length.
    """
    order = sorted(reqs, key=lambda r: (r.arrival, r.rid))
    t, total = 0.0, 0
    batches = 0
    for lo in range(0, len(order), slots):
        batch = order[lo:lo + slots]
        start = max(t, max(r.arrival for r in batch))
        l_max = max(len(r.prompt) for r in batch)
        g_max = max(r.max_new_tokens for r in batch)
        rows = _even_split(len(batch), len(speeds))
        dt_prefill = max(rows[p] * l_max / speeds[p]
                         for p in range(len(speeds)) if rows[p] > 0)
        dt_iter = max(rows[p] / speeds[p]
                      for p in range(len(speeds)) if rows[p] > 0)
        # prefill emits token 1; g_max - 1 lock-step decode iterations
        t = start + dt_prefill + (g_max - 1) * dt_iter
        total += sum(r.max_new_tokens for r in batch)
        batches += 1
    return {"modeled_time": t, "total_tokens": total,
            "modeled_tokens_per_sec": total / t if t > 0 else 0.0,
            "batches": batches}


def _run_engine(model, params, mesh, layout, slots, prefill_batch,
                speeds, reqs):
    with jax.set_mesh(mesh):
        eng = serve_mod.build_engine(model, params, mesh, layout,
                                     slots, prefill_batch, speeds)
        return eng.run(reqs)


def main(quick: bool = False, out: str = "BENCH_serve.json",
         seed: int = 0) -> Dict:
    t_all = time.time()
    cfg, model = _tiny_model()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(seed))
    failures: List[str] = []
    record: Dict = {"quick": quick, "seed": seed,
                    "arch": cfg.name, "compute_dtype": cfg.compute_dtype}

    # -- smoke: 3 mixed-length arrivals end to end ------------------------
    slots = 4
    layout = _layout(slots, max_seq=24)
    smoke_reqs = [Request(rid=0, prompt=(1, 2, 3), max_new_tokens=4,
                          arrival=0.0),
                  Request(rid=1, prompt=tuple(range(1, 12)),
                          max_new_tokens=2, arrival=1.0),
                  Request(rid=2, prompt=(5, 6), max_new_tokens=6,
                          arrival=2.0)]
    res = _run_engine(model, params, mesh, layout, slots, 2,
                      [1.0, 0.5], smoke_reqs)
    short = {r.rid: len(res.tokens[r.rid]) for r in smoke_reqs}
    want = {r.rid: r.max_new_tokens for r in smoke_reqs}
    record["smoke"] = {"tokens_per_request": short,
                       "decode_steps": res.stats["decode_steps"]}
    if short != want:
        failures.append(f"smoke: generated lengths {short} != "
                        f"requested {want}")

    # -- (a) continuous vs static-batch modeled throughput ----------------
    n_req = 12 if quick else 24
    slots = 4
    layout = _layout(slots, max_seq=24 + 16)
    reqs = serve_mod.synthetic_requests(
        n_req, cfg.vocab_size, rate=0.25, prompt_lens=(4, 24),
        gen_lens=(2, 16), seed=seed)
    speeds = [1.0, 0.5]
    res = _run_engine(model, params, mesh, layout, slots, 2, speeds, reqs)
    static = _static_baseline(reqs, slots, speeds)
    cont_tps = res.stats["modeled_tokens_per_sec"]
    ok_tp = cont_tps > static["modeled_tokens_per_sec"]
    record["throughput"] = {
        "requests": n_req, "slots": slots, "pod_speeds": speeds,
        "continuous": {k: res.stats[k] for k in
                       ("modeled_time", "total_tokens",
                        "modeled_tokens_per_sec", "p50_time_per_token",
                        "p99_time_per_token", "mean_ttft",
                        "decode_steps", "prefill_groups",
                        "preemptions")},
        "static": static,
        "speedup": (cont_tps / static["modeled_tokens_per_sec"]
                    if static["modeled_tokens_per_sec"] > 0 else 0.0),
        "strictly_better": ok_tp,
    }
    record["block_util"] = {"mean": res.stats["block_util_mean"],
                            "peak": res.stats["block_util_peak"]}
    if res.stats["total_tokens"] != static["total_tokens"]:
        failures.append(
            f"throughput: engine generated {res.stats['total_tokens']} "
            f"tokens but the trace asks for {static['total_tokens']}")
    if not ok_tp:
        failures.append(
            f"throughput: continuous batching ({cont_tps:.3f} tok/unit) "
            f"is not strictly above the static-batch baseline "
            f"({static['modeled_tokens_per_sec']:.3f} tok/unit)")
    print(f"[serve_bench] throughput: continuous {cont_tps:.3f} vs "
          f"static {static['modeled_tokens_per_sec']:.3f} tok/unit "
          f"({record['throughput']['speedup']:.2f}x), block util "
          f"mean {res.stats['block_util_mean']:.2f} "
          f"peak {res.stats['block_util_peak']:.2f}")

    # -- (b) single-sequence bit-identity vs the static path --------------
    rng = np.random.default_rng(seed + 1)
    plen, gen = 7, 6
    prompt = tuple(int(x) for x in rng.integers(0, cfg.vocab_size, plen))
    layout = _layout(2, max_seq=plen + gen)
    res = _run_engine(model, params, mesh, layout, 2, 1, [1.0],
                      [Request(rid=0, prompt=prompt,
                               max_new_tokens=gen, arrival=0.0)])
    paged_toks = res.tokens[0]
    with jax.set_mesh(mesh):
        ref = serve_mod.static_generate(
            model, params, mesh,
            np.asarray([prompt], np.int32), gen)
    ref_toks = [int(x) for x in ref[0]]
    ok_bit = paged_toks == ref_toks
    record["bit_identity"] = {"prompt_len": plen, "gen": gen,
                              "paged": paged_toks, "static": ref_toks,
                              "identical": ok_bit}
    if not ok_bit:
        failures.append(f"bit_identity: paged {paged_toks} != "
                        f"static {ref_toks}")
    print(f"[serve_bench] bit_identity: paged==static {ok_bit} "
          f"({paged_toks})")

    # -- (c) capacity-aware routing under saturation ----------------------
    speeds = [1.0, 0.5, 0.25]
    slots = 7
    layout = _layout(slots, max_seq=20)
    reqs = serve_mod.synthetic_requests(
        2 * slots, cfg.vocab_size, rate=0.0, prompt_lens=(4, 10),
        gen_lens=(8, 10), seed=seed)
    res = _run_engine(model, params, mesh, layout, slots, 4, speeds, reqs)
    limits = res.stats["pod_limits"]
    peaks = res.stats["peak_active_per_pod"]
    ok_cap = all(pk <= lm for pk, lm in zip(peaks, limits))
    ok_sat = peaks == limits
    # strictly fewer concurrent rows on strictly slower pods
    ok_mono = all(
        limits[p] > limits[q]
        for p in range(len(speeds)) for q in range(len(speeds))
        if speeds[p] > 2 * speeds[q])
    record["routing"] = {"pod_speeds": speeds, "slots": slots,
                         "pod_limits": limits,
                         "peak_active_per_pod": peaks,
                         "within_limits": ok_cap, "saturated": ok_sat,
                         "monotone_in_speed": ok_mono}
    if not ok_cap:
        failures.append(f"routing: peak concurrency {peaks} exceeds "
                        f"capacity limits {limits}")
    if not ok_sat:
        failures.append(f"routing: under 2x-slot saturation peaks "
                        f"{peaks} never reached limits {limits}")
    if not ok_mono:
        failures.append(f"routing: limits {limits} not proportional to "
                        f"pod speeds {speeds}")
    print(f"[serve_bench] routing: speeds {speeds} -> limits {limits}, "
          f"peaks {peaks}")

    # -- (d) decode-step roofline: in-kernel gather vs materialize --------
    # Modeled leg: bytes/token swept at realistic paged-window shapes
    # (bf16 pools; GQA = llama-70B-ish 8 KV heads x 128, MLA =
    # deepseek-ish h=128 r=512 dr=64). The in-kernel-gather model must
    # be STRICTLY below materialize-then-attend at every point.
    sweep = []
    for mb in (4, 16, 64, 256):
        for bs in (16, 32):
            g = _gqa_decode_bytes(mb, bs, hkv=8, q_per_kv=4, dh=128,
                                  itemsize=2)
            m = _mla_decode_bytes(mb, bs, h=128, r=512, dr=64,
                                  itemsize=2)
            row = {"max_blocks": mb, "block_size": bs,
                   "gqa": g, "mla": m,
                   "gqa_ratio": g["materialize"] / g["kernel"],
                   "mla_ratio": m["materialize"] / m["kernel"]}
            sweep.append(row)
            for name, cell in (("gqa", g), ("mla", m)):
                if not cell["kernel"] < cell["materialize"]:
                    failures.append(
                        f"decode_roofline: {name} in-kernel-gather byte "
                        f"model ({cell['kernel']}) not strictly below "
                        f"materialize ({cell['materialize']}) at "
                        f"mb={mb} bs={bs}")
    ok_model = all(row[k]["kernel"] < row[k]["materialize"]
                   for row in sweep for k in ("gqa", "mla"))

    # Measured leg: same smoke trace, reference vs pallas engines (same
    # params — init is impl-independent). Off TPU/GPU the pallas path
    # runs in interpret mode (compat warns loudly), so wall time is
    # printed for eyeballs only; the recorded claim is token-identity.
    slots = 4
    layout = _layout(slots, max_seq=24)
    runs = {}
    for impl in ("reference", "pallas"):
        m_impl = build_model(
            dataclasses.replace(cfg, attention_impl=impl))
        t0 = time.time()
        runs[impl] = _run_engine(m_impl, params, mesh, layout, slots, 2,
                                 [1.0, 0.5], smoke_reqs)
        print(f"[serve_bench] roofline measured: {impl} smoke run "
              f"{time.time() - t0:.1f}s wall "
              f"({runs[impl].stats['decode_steps']} decode steps)")
    ok_tok = runs["pallas"].tokens == runs["reference"].tokens
    best = max(sweep, key=lambda r: r["gqa_ratio"])
    record["decode_roofline"] = {
        "itemsize": 2,
        "gqa_heads": {"hkv": 8, "q_per_kv": 4, "dh": 128},
        "mla_heads": {"h": 128, "r": 512, "dr": 64},
        "sweep": sweep,
        "kernel_strictly_better": ok_model,
        "measured": {
            "impls": sorted(runs),
            "decode_steps": {k: v.stats["decode_steps"]
                             for k, v in runs.items()},
            "token_identical": ok_tok,
        },
    }
    if not ok_tok:
        failures.append(
            f"decode_roofline: pallas engine tokens "
            f"{runs['pallas'].tokens} != reference "
            f"{runs['reference'].tokens}")
    print(f"[serve_bench] decode_roofline: modeled kernel<materialize "
          f"{ok_model} (best gqa ratio {best['gqa_ratio']:.2f}x at "
          f"mb={best['max_blocks']} bs={best['block_size']}), measured "
          f"pallas==reference tokens {ok_tok}")

    # wall time is printed, not recorded: the artifact must be
    # byte-deterministic given the seed
    with open(out, "w") as fh:
        json.dump(record, fh, indent=1,
                  default=lambda o: o.item()
                  if isinstance(o, np.generic) else str(o))
    print(f"[serve_bench] wrote {out} ({time.time() - t_all:.1f}s)")
    if failures:
        for f in failures:
            print(f"[serve_bench] INVARIANT BROKEN: {f}")
        raise SystemExit("[serve_bench] fail-loud: "
                         f"{len(failures)} invariant(s) broken")
    return record


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
