"""The continuous-batching decode loop.

Each iteration: ingest due arrivals, admit what fits (scheduler),
prefill the admitted prompts in length buckets, then run ONE decode
step for the whole slot batch — every active sequence advances one
token at its own depth (per-sequence ``kv_lens``), finished sequences
free their blocks immediately and their slots are refilled next
iteration. The decode step is compiled exactly once: fixed shapes
(D,), (D, MB), (D,); inactive slots carry kv_len=0 and all-NULL block
tables, so their writes drop and their outputs are discarded host-side.
The engine asserts the step never retraced at the end of a run.

**Modeled clock.** Real wall time on the host container measures the
emulated mesh, not the heterogeneous fleet, so throughput/latency stats
ride on a deterministic cost model in abstract time units, consistent
with the trainer's capacity math (one unit == one decode-token on a
speed-1.0 pod):

- decode iteration:  dt = max_p active_p / speed_p
- prefill of a bucket-L group: dt = max_p rows_p * L / speed_p

Both are max-over-pods because the mesh is one SPMD program — the step
returns when the slowest pod finishes, which is exactly why the router
gives slow pods proportionally fewer sequences (min-max of
active_p/speed_p is the HetSeq capacity argument on the serving side).

**Real clock.** Beside the model, the loop's host work is spanned
(``repro/obs.py``): each iteration is a ``serve.iteration`` holding
``serve.admit`` (arrivals, admission, bucketing), ``serve.prefill``
(with ``serve.fetch`` of its logits), ``serve.prepare`` (block tables
and the step's inputs, uploaded), ``serve.decode`` (the step call;
its ``pool_move_bytes`` is the step's ``serve.pool_move_bytes`` gauge),
``serve.fetch`` (logits to the host) and ``serve.sample`` (argmax and
emit). Each request's submission, admissions, first and last token
are ``request.*`` events sharing one ``req`` id; ``stats`` reports the
measured time to first token and gap between tokens from them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

import numpy as np

import jax.numpy as jnp

from repro import obs
from repro.models.kvcache import PagedLayout
from repro.serve.scheduler import Request, Scheduler, SeqState


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    decode_slots: int
    prefill_batch: int
    max_iterations: int = 100_000     # runaway-loop guard, fail loud
    # which decode attention ran (ModelConfig.attention_impl at build
    # time) — recorded in stats so a serving run is auditable about
    # whether the hot path used the in-kernel block gather
    attention_impl: str = "reference"


@dataclasses.dataclass
class ServeResult:
    tokens: Dict[int, List[int]]      # rid -> generated token ids
    stats: Dict[str, Any]


class ServeEngine:
    """Ties scheduler + jitted paged steps into a serving loop.

    ``decode_fn(params, tokens, cache, tables, kv_lens)`` and
    ``prefill_fns[bucket](params, prompts, lens, cache, tables)`` come
    from launch/steps.py (donated caches); ``init_cache_fn()`` builds
    the zeroed pool with the right shardings.
    """

    def __init__(self, cfg: EngineConfig, layout: PagedLayout,
                 scheduler: Scheduler,
                 decode_fn: Callable,
                 prefill_fns: Dict[int, Callable],
                 init_cache_fn: Callable[[], Any]):
        missing = [b for b in scheduler.bucket_lens
                   if b not in prefill_fns]
        if missing:
            raise ValueError(f"no prefill step for buckets {missing}")
        self.cfg = cfg
        self.layout = layout
        self.sched = scheduler
        self.decode_fn = decode_fn
        self.prefill_fns = prefill_fns
        self.init_cache_fn = init_cache_fn
        self._runs = 0                  # run() calls, for the spans

    # -- modeled costs -----------------------------------------------------

    def _decode_dt(self) -> float:
        speeds = self.sched.router.pod_speeds
        return max((a / speeds[p]
                    for p, a in enumerate(self.sched.active_per_pod)
                    if a > 0), default=0.0)

    def _prefill_dt(self, bucket: int, seqs: Sequence[SeqState]) -> float:
        speeds = self.sched.router.pod_speeds
        rows = [0] * len(speeds)
        for s in seqs:
            rows[s.pod] += 1
        return max((r * bucket / speeds[p]
                    for p, r in enumerate(rows) if r > 0), default=0.0)

    # -- the loop ----------------------------------------------------------

    def run(self, requests: Sequence[Request]) -> ServeResult:
        sched, layout = self.sched, self.layout
        NULL = layout.null_block
        D, MB = self.cfg.decode_slots, layout.max_blocks_per_seq

        arrivals = sorted(requests, key=lambda r: (r.arrival, r.rid))
        tokens_out: Dict[int, List[int]] = {r.rid: [] for r in arrivals}
        token_times: Dict[int, List[float]] = {r.rid: [] for r in arrivals}
        arrival_of = {r.rid: r.arrival for r in arrivals}

        self._runs += 1
        req_of: Dict[int, int] = {}         # rid -> obs request id
        cache = self.init_cache_fn()
        clock, ai = 0.0, 0
        decode_steps = prefill_groups = 0
        peak_active = [0] * sched.router.num_pods
        block_util_peak, block_util_sum, util_samples = 0.0, 0.0, 0
        wall0 = time.monotonic()

        def emit(seq: SeqState, tok: int, t: float) -> None:
            seq.generated.append(tok)
            seq.last_token = tok
            tokens_out[seq.rid].append(tok)
            token_times[seq.rid].append(t)
            if len(tokens_out[seq.rid]) == 1:
                obs.event("request.first_token", req=req_of[seq.rid])
            if seq.done:
                obs.event("request.last_token", req=req_of[seq.rid],
                          tokens=len(tokens_out[seq.rid]))
                sched.finish(seq)

        it = 0
        while ai < len(arrivals) or sched.waiting or sched.running:
            it += 1
            if it > self.cfg.max_iterations:
                raise RuntimeError(
                    f"serve loop exceeded {self.cfg.max_iterations} "
                    f"iterations — scheduler stuck?")
            with obs.span("serve.iteration"):
                with obs.span("serve.admit"):
                    # idle: jump the clock to the next arrival
                    if (not sched.running and not sched.waiting
                            and ai < len(arrivals)):
                        clock = max(clock, arrivals[ai].arrival)
                    while (ai < len(arrivals)
                           and arrivals[ai].arrival <= clock):
                        rid = arrivals[ai].rid
                        sched.submit(arrivals[ai])
                        req_of[rid] = obs.new_id()
                        obs.event("request.submit", req=req_of[rid],
                                  rid=rid)
                        ai += 1
                    admitted = sched.try_admit()
                    by_bucket: Dict[int, List[SeqState]] = {}
                    for seq in admitted:
                        obs.event("request.admit", req=req_of[seq.rid])
                        by_bucket.setdefault(
                            sched.bucket_for(len(seq.prompt)),
                            []).append(seq)
                for bucket in sorted(by_bucket):
                    group = by_bucket[bucket]
                    Bp = self.cfg.prefill_batch
                    for lo in range(0, len(group), Bp):
                        chunk = group[lo:lo + Bp]
                        cache, logits = self._prefill(chunk, bucket, Bp,
                                                      cache, NULL, MB)
                        clock += self._prefill_dt(bucket, chunk)
                        prefill_groups += 1
                        with obs.span("serve.sample"):
                            toks = np.argmax(logits[:len(chunk)], axis=-1)
                            for seq, tok in zip(chunk, toks):
                                seq.kv_len = len(seq.prompt)
                                emit(seq, int(tok), clock)

                if not sched.running:
                    continue
                with obs.span("serve.prepare"):
                    # grow block tables BEFORE the step (the new token
                    # writes at position kv_len); may preempt
                    # newest-first
                    for slot in sorted(sched.running):
                        seq = sched.running.get(slot)
                        if seq is not None and not \
                                sched.ensure_next_block(seq):
                            continue            # seq preempted itself
                    if not sched.running:
                        continue
                    tok_arr = np.zeros((D,), np.int32)
                    tbl_arr = np.full((D, MB), NULL, np.int32)
                    len_arr = np.zeros((D,), np.int32)
                    for slot, seq in sched.running.items():
                        tok_arr[slot] = seq.last_token
                        tbl_arr[slot, :len(seq.blocks)] = seq.blocks
                        len_arr[slot] = seq.kv_len
                    toks_d, tbl_d, lens_d = (jnp.asarray(tok_arr),
                                             jnp.asarray(tbl_arr),
                                             jnp.asarray(len_arr))
                active = len(sched.running)
                with obs.span("serve.decode", run=self._runs,
                              step=decode_steps + 1, active=active,
                              kv_tokens=int(len_arr.sum()) + active
                              ) as decoded:
                    logits, cache = self.decode_fn(toks_d, cache, tbl_d,
                                                   lens_d)
                    # set when the step was compiled
                    decoded.attrs["pool_move_bytes"] = obs.counters().get(
                        "serve.pool_move_bytes", 0)
                clock += self._decode_dt()
                decode_steps += 1
                for p, a in enumerate(sched.active_per_pod):
                    peak_active[p] = max(peak_active[p], a)
                util = sched.allocated_blocks() / layout.num_blocks
                block_util_peak = max(block_util_peak, util)
                block_util_sum += util
                util_samples += 1
                logits_h = fetch(logits)
                with obs.span("serve.sample"):
                    for slot, seq in list(sched.running.items()):
                        seq.kv_len += 1
                        emit(seq, int(np.argmax(logits_h[slot])), clock)

        wall = time.monotonic() - wall0
        self._assert_no_retrace()
        total_tokens = sum(len(v) for v in tokens_out.values())
        tpot = [(token_times[rid][-1] - arrival_of[rid]) / len(ts)
                for rid, ts in token_times.items() if ts]
        ttft = [ts[0] - arrival_of[rid]
                for rid, ts in token_times.items() if ts]
        stats = {
            "requests": len(arrivals),
            "total_tokens": total_tokens,
            "modeled_time": clock,
            "modeled_tokens_per_sec": (total_tokens / clock
                                       if clock > 0 else 0.0),
            "p50_time_per_token": (float(np.percentile(tpot, 50))
                                   if tpot else 0.0),
            "p99_time_per_token": (float(np.percentile(tpot, 99))
                                   if tpot else 0.0),
            "mean_ttft": float(np.mean(ttft)) if ttft else 0.0,
            "decode_steps": decode_steps,
            "prefill_groups": prefill_groups,
            "preemptions": sched.preemptions,
            "peak_active_per_pod": [int(x) for x in peak_active],
            "pod_limits": [int(x) for x in sched.router.limits],
            "block_util_peak": block_util_peak,
            "block_util_mean": (block_util_sum / util_samples
                                if util_samples else 0.0),
            "attention_impl": self.cfg.attention_impl,
            "wall_seconds": wall,
            **request_latency(obs.spans(), set(req_of.values())),
        }
        return ServeResult(tokens=tokens_out, stats=stats)

    def _prefill(self, chunk: Sequence[SeqState], bucket: int, Bp: int,
                 cache: Any, NULL: int, MB: int):
        prompts = np.zeros((Bp, bucket), np.int32)
        lens = np.zeros((Bp,), np.int32)
        tables = np.full((Bp, MB), NULL, np.int32)
        for i, seq in enumerate(chunk):
            prompts[i, :len(seq.prompt)] = seq.prompt
            lens[i] = len(seq.prompt)
            tables[i, :len(seq.blocks)] = seq.blocks
        with obs.span("serve.prefill", bucket=bucket, rows=len(chunk),
                      prompt_tokens=int(lens.sum())):
            logits, cache = self.prefill_fns[bucket](
                jnp.asarray(prompts), jnp.asarray(lens), cache,
                jnp.asarray(tables))
            return cache, fetch(logits)

    def _assert_no_retrace(self) -> None:
        """Fail loud if the decode step compiled more than once — a
        retrace means some input shape/dtype varied across iterations
        and the whole fixed-shape design is broken."""
        n = _trace_count(self.decode_fn)
        if n is not None and n > 1:
            raise RuntimeError(
                f"paged decode step retraced: {n} compilations for one "
                f"engine run (expected 1)")


def request_latency(records: Sequence[obs.Span], reqs: Set[int]
                    ) -> Dict[str, float]:
    """Measured latency of the requests ``reqs`` (their ``request.*``
    events on ``perf_counter_ns``), p50 and p99 over the requests in ms:
    ``ttft_ms`` from submission to the first token, ``token_gap_ms``
    each request's mean gap between its tokens. Requests whose events
    the ring no longer holds are left out."""
    events: Dict[int, Dict[str, obs.Span]] = {}
    for r in records:
        if r.name.startswith("request.") and r.attrs.get("req") in reqs:
            events.setdefault(r.attrs["req"], {})[r.name] = r
    ttft, gap = [], []
    for ev in events.values():
        sub = ev.get("request.submit")
        first = ev.get("request.first_token")
        last = ev.get("request.last_token")
        if sub and first:
            ttft.append((first.t0_ns - sub.t0_ns) * 1e-6)
        if first and last and last.attrs["tokens"] > 1:
            gap.append((last.t0_ns - first.t0_ns) * 1e-6
                       / (last.attrs["tokens"] - 1))
    out = {}
    for name, xs in (("ttft_ms", ttft), ("token_gap_ms", gap)):
        for q in (50, 99):
            out[f"{name}_p{q}"] = float(np.percentile(xs, q)) if xs \
                else 0.0
    return out


def fetch(logits) -> np.ndarray:
    """Bring a step's logits to the host."""
    with obs.span("serve.fetch", bytes=int(logits.nbytes)):
        return np.asarray(logits)


def _trace_count(fn) -> Optional[int]:
    target = getattr(fn, "func", fn)        # unwrap functools.partial
    size = getattr(target, "_cache_size", None)
    return size() if callable(size) else None
