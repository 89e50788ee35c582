"""A serving cell: ``ServeEngine.run`` on an engine built by
``repro.launch.serve.build_engine``, timed and checked.

The harness wraps the engine's public ``decode_fn`` and ``prefill_fns``
(to time each step's completion and count the tokens it emits) and its
scheduler's ``finish`` (to keep each finished request's tokens). A
warm-up run sends one request per prefill bucket, so every program the
window uses is compiled before it. The window opens at the completion
of the backlog run's first decode step and closes by raising from the
decode wrapper. Then the engine is freed and the float32 reference
scores a sample of the finished requests, drawn from the seed with the
longest among them: for every served token, how far its reference logit
lies below the reference's best at that position. The reference is the
module the configuration names (``spec.reference``).
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchlib import check, device, program, spec, stats, traffic
from benchlib.window import Window, WindowClosed

ITL_PERCENTILE = 95.0
SAMPLE_TOKENS = 1024    # served tokens the reference scores, at least
SAMPLE_MAX = 6          # requests in the sample, at most


class ServeRecorder:
    """Times the engine's steps; see the module docstring."""

    def __init__(self, engine, window: Window):
        import jax

        self.window = window
        self.sched = engine.sched
        self.finished: List[Dict[str, Any]] = []
        self.stamps: List[float] = []
        self.active: List[int] = []
        self.window_tokens = 0
        self.traced_contexts: List[List[int]] = []
        self.traced_prompts: List[int] = []
        self.traced_tokens = 0

        decode = engine.decode_fn

        def timed_decode(tokens, cache, tables, kv_lens):
            running = self.sched.running.values()
            n = len(running)
            ctx = [s.kv_len + 1 for s in running] if self.window.tracing \
                else None
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                out = decode(tokens, cache, tables, kv_lens)
                jax.block_until_ready(out[0])
            self._decoded(time.perf_counter(), n, ctx)
            return out

        # the engine's no-retrace check looks through ``.func``
        timed_decode.func = getattr(decode, "func", decode)
        engine.decode_fn = timed_decode
        engine.prefill_fns = {b: self._timed_prefill(b, f)
                              for b, f in engine.prefill_fns.items()}
        finish = self.sched.finish

        def kept_finish(seq):
            self.finished.append({"rid": seq.rid, "prompt": seq.prompt,
                                  "generated": list(seq.generated)})
            finish(seq)

        self.sched.finish = kept_finish

    def _timed_prefill(self, bucket: int, fn):
        import jax

        def timed_prefill(prompts, lens, cache, tables):
            with jax.profiler.TraceAnnotation(f"bench.prefill_{bucket}"):
                out = fn(prompts, lens, cache, tables)
            real = [int(x) for x in np.asarray(lens) if x > 0]
            if self.window.open:
                self.window_tokens += len(real)    # each emits one token
                if self.window.tracing:
                    self.traced_prompts += real
                    self.traced_tokens += len(real)
            return out

        return timed_prefill

    def _decoded(self, t: float, n: int, ctx) -> None:
        if not self.window.open:
            if self.window.t_open is None:
                self.stamps.append(t)
                self.window.start(t)
            return
        self.stamps.append(t)
        self.active.append(n)
        self.window_tokens += n
        if self.window.tracing:
            self.traced_contexts.append(ctx)
            self.traced_tokens += n
        self.window.tick(t)

    def itl_p95_ms(self) -> float:
        gaps = np.diff(self.stamps) * 1e3
        return stats.weighted_percentile(list(gaps), self.active,
                                         ITL_PERCENTILE)


def sample(finished: List[Dict], want: Dict[int, tuple], seed: int
           ) -> List[Dict]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX``
    requests."""
    def served(f):
        prompt, _ = want[f["rid"]]
        return list(f["prompt"][len(prompt):]) + f["generated"]

    done = [dict(f, served=served(f), orig=want[f["rid"]][0])
            for f in finished]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["served"]))
    rest = [i for i in np.random.default_rng([seed, 2]).permutation(
        len(done)) if i != longest]
    out, n = [], 0
    for i in [longest] + rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(done[i])
        n += len(done[i]["served"])
    return out


def run(cfg: Dict, mix: Dict, seed: int, devs, window: Window,
        limits: Optional[Dict[str, float]], controls=()
        ) -> Dict[str, Any]:
    """Run the cell. ``controls`` (lower precisions of the reference in
    the program's place) are read by the control script only."""
    import jax

    from repro.launch import serve as serve_mod
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.models.kvcache import PagedLayout
    from repro.models.model import build_model
    from repro.serve import Request

    mc = program.model_config(cfg)
    s31 = program.seed31(seed)
    model = build_model(mc)
    mesh = make_mesh((1, 1), ("data", "model"), devs[:1])
    mbs = mix["max_seq_len"] // mix["block_size"]
    layout = PagedLayout(block_size=mix["block_size"],
                         num_blocks=mix["slots"] * mbs,
                         max_blocks_per_seq=mbs)
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(s31))
    backlog = traffic.backlog(mix, cfg["vocab_size"], seed)
    want = {rid: r for rid, r in enumerate(backlog)}
    warm = traffic.warmup(mix, cfg["vocab_size"], seed)
    with jax.set_mesh(mesh):
        engine = serve_mod.build_engine(
            model, params, mesh, layout, mix["slots"],
            mix["prefill_batch"], [1.0], bucket_lens=mix["bucket_lens"])
        engine.run([Request(rid=i, prompt=p, max_new_tokens=n)
                    for i, (p, n) in enumerate(warm)])
        rec = ServeRecorder(engine, window)
        try:
            engine.run([Request(rid=i, prompt=p, max_new_tokens=n)
                        for i, (p, n) in enumerate(backlog)])
        except WindowClosed:
            pass
        finally:
            if window.tracing:
                window.stop_trace(time.perf_counter())
    if window.t_close is None:
        raise RuntimeError("the backlog drained before the window closed")
    memory = device.memory_peak_bytes(devs)
    finished = rec.finished
    del engine, params, rec.sched
    gc.collect()

    picked = sample(finished, want, seed)
    complete = all(len(f["served"]) == want[f["rid"]][1] for f in picked)
    seqs = [{"prompt": f["orig"], "served": f["served"]} for f in picked]
    t_ref = time.perf_counter()
    gaps = spec.reference(cfg).served_logit_gaps(
        cfg, s31, seqs, ("float32",) + tuple(controls))
    readings = {"program": {"served_gap": float(np.max(gaps["served"]))
                            if len(gaps["served"]) else float("inf")},
                "reference_s": time.perf_counter() - t_ref}
    for p in controls:
        readings[p] = {"served_gap": float(np.max(gaps[p]))}
    checks = check.judge(readings["program"], limits) if limits else {}
    failed = 0
    if limits:
        lo = 0
        for f in picked:
            n = len(f["served"])
            if np.max(gaps["served"][lo:lo + n]) > limits["served_gap"]:
                failed += 1
            lo += n
    window_s = window.window_s
    return {
        "correct": bool(checks) and bool(picked) and complete
        and check.passes(checks),
        "stand_ins": check.judge_stand_ins(readings, controls, limits),
        "attempted": len(finished), "failed": failed,
        "setup_s": window.setup_s, "window_s": window_s,
        "serve_tokens_per_s": rec.window_tokens / window_s,
        "serve_itl_p95_ms": rec.itl_p95_ms(),
        "traced_contexts": rec.traced_contexts,
        "traced_prompts": rec.traced_prompts,
        "traced_tokens": rec.traced_tokens,
        "memory_peak_bytes": memory, "checks": checks,
        "readings": readings, "sampled_tokens": int(len(gaps["served"])),
        "sampled_requests": len(picked),
    }
