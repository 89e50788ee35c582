"""The benchmark's arithmetic on the CPU: trace reduction, FLOP and byte
counts, the token-weighted percentile, the traffic generator, the
correctness numbers, and the data-driven layout of BENCHMARK.json."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for _p in (str(BENCH), str(BENCH.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchlib import check, flops, program, spec, stats, traffic  # noqa: E402
from benchlib import trace as tr  # noqa: E402


# -- trace reduction ---------------------------------------------------------


def _trace():
    # two devices over a 10 s window; device 0: compute 0-4, an
    # all-reduce 3-6 (1 s overlapped, 2 s exposed), compute 8-9;
    # device 1: compute 0-5 and a paged kernel 6-7
    ops = {
        "/device:TPU:0": [(0.0, 4.0, "fusion.1"), (3.0, 6.0, "all-reduce.7"),
                          (8.0, 9.0, "convolution.2")],
        "/device:TPU:1": [(0.0, 5.0, "fusion.1"),
                          (6.0, 7.0, "_paged_decode_kernel")],
    }
    spans = [(6.0, 8.0, "bench.decode_step"), (0.0, 10.0, tr.WINDOW_SPAN)]
    return tr.Trace(ops=ops, spans=spans, window=(0.0, 10.0))


def test_interval_union_and_intersection():
    assert tr.merge([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]
    assert tr.length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert tr.clip([(-1, 2, "a"), (9, 12, "b"), (20, 21, "c")],
                   (0, 10)) == [(0, 2, "a"), (9, 10, "b")]


def test_busy_idle_and_exposed_collective():
    t = _trace()
    # device 0 busy 0-6 and 8-9 = 7 s; device 1 busy 0-5, 6-7 = 6 s
    assert t.window_s == 10.0
    assert tr.busy_s(t) == pytest.approx(6.5)
    # device 0: collective 3-6 with compute till 4 -> 2 s; device 1 none
    assert tr.exposed_collective_s(t) == pytest.approx(1.0)
    reader = spec.metric_reader("exposed_collective_share.train")
    rec = {"kind": "train", "chips": 2, "trace": t, "window_s": 10.0}
    assert reader.read(rec) == pytest.approx(10.0)
    assert reader.read(dict(rec, chips=1)) is None


def test_kernel_time_and_top_ops():
    t = _trace()
    assert tr.kernel_s(t, r"paged_decode") == (pytest.approx(1.0), 1)
    top = tr.top_ops(t, k=2)
    assert top[0] == ["fusion.1", pytest.approx(4.5)]      # (4 + 5) / 2
    assert top[1] == ["all-reduce.7", pytest.approx(1.5)]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    gaps = dict((n, s) for n, s in tr.idle_gaps(_trace()))
    # device 0 idles 6-8 (under the decode span) and 9-10; device 1
    # idles 5-6 and 7-10, 7-8 of it under the decode span
    assert gaps["bench.decode_step"] == pytest.approx((2.0 + 1.0) / 2)
    assert gaps[tr.OUTSIDE_SPANS] == pytest.approx((1.0 + 1.0 + 2.0) / 2)


PAGED = ('%closed_call.13 = bf16[16,1,24,128]{3,2,1,0:T(8,128)(2,1)} '
         'custom-call(s32[16,128]{1,0} %copy-done.1, s32[16]{0} %add.2, '
         'bf16[16,1,24,128]{3,2,1,0} %pad.4), '
         'custom_call_target="tpu_custom_call"')


def test_names_leaves_and_the_paged_kernel_pattern():
    fus = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), kind=kLoop"
    assert tr.op_name(fus) == "fusion.9"
    assert tr.short_name(fus) == "fusion.9 f32[8]"
    assert not tr.is_collective(fus)       # an operand's name is not it
    assert tr.is_collective("%all-reduce-start.2 = f32[8]{0} "
                            "all-reduce-start(f32[8]{0} %p)")
    assert tr.short_name(PAGED) == \
        "closed_call.13 bf16[16,1,24,128] tpu_custom_call"
    # a while loop spans its body's ops: only the body's count
    evs = [(0.0, 10.0, "while.1"), (1.0, 2.0, "a"), (3.0, 9.0, "b"),
           (9.5, 10.0, "c"), (11.0, 12.0, "d")]
    assert [n for _, _, n in tr.leaves(evs)] == ["a", "b", "c", "d"]
    reader = spec.metric_reader("paged_attn_roofline.serve")
    import re
    assert re.search(reader.KERNEL, PAGED)
    assert not re.search(reader.KERNEL, PAGED.replace("s32[16,128]",
                                                      "bf16[16,128]"))


# -- FLOP and byte counts ----------------------------------------------------


def test_olmo_train_flops_against_a_hand_count():
    cfg = spec.config("olmo-1b-4l")
    # per layer: q, k, v, o 4 x 2048^2; SwiGLU 3 x 2048 x 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert flops.layer_matmul_params(cfg) == layer == 67_108_864
    head = 50304 * 2048
    assert flops.matmul_params(cfg) == 4 * layer + head == 371_458_048
    per_token = 6 * (4 * layer + head) + 12 * 4 * 2048 * 2048
    assert flops.train_flops_per_token(cfg, 2048) == per_token
    assert per_token == pytest.approx(2.43e9, rel=0.01)


def test_phi4_decode_and_paged_kernel_against_a_hand_count():
    cfg = spec.config("phi4-mini-3.8b")
    # q, o 3072 x 3072; k, v 3072 x 1024; SwiGLU 3 x 3072 x 8192
    layer = 2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192
    assert flops.layer_matmul_params(cfg) == layer
    head = 200064 * 3072
    total = 32 * layer + head
    assert total == pytest.approx(3.84e9, rel=0.01)
    # two sequences at contexts 100 and 300: 2 x params each, and q.k
    # plus p.v over 24 heads of 128 in 32 layers
    attn = 4 * 32 * 24 * 128 * (100 + 300)
    assert flops.decode_flops(cfg, [100, 300]) == 2 * 2 * total + attn
    cost = flops.paged_attention_cost(cfg, [100, 300])
    kv = 2 * (100 + 300) * 8 * 128 * 2           # K and V, bf16
    qo = 2 * 2 * 24 * 128 * 2                    # q in, out, per sequence
    assert cost == {"bytes": kv + qo, "flops": 4.0 * 24 * 128 * 400}


def test_prefill_counts_real_tokens_with_causal_context():
    cfg = spec.config("phi4-mini-3.8b")
    per_token = 2 * flops.matmul_params(cfg)
    attn1 = 4 * 32 * 24 * 128
    assert flops.prefill_flops(cfg, [3]) == \
        3 * per_token + attn1 * (1 + 2 + 3)


# -- percentile ----------------------------------------------------------


def test_token_weighted_percentile():
    # a gap of 10 ms before 1 token, then 20 ms before 16 tokens: 94 %
    # of tokens waited 20 ms
    assert stats.weighted_percentile([10.0, 20.0], [1, 16], 95) == 20.0
    assert stats.weighted_percentile([10.0, 20.0], [16, 1], 95) == 20.0
    assert stats.weighted_percentile([10.0, 20.0], [19, 1], 95) == 10.0
    vals = list(range(1, 101))
    assert stats.weighted_percentile(vals, [1] * 100, 95) == 95
    with pytest.raises(ValueError):
        stats.weighted_percentile([1.0], [1.0, 2.0], 50)


# -- traffic -----------------------------------------------------------------


def test_serve_traffic_is_deterministic_and_seeds_reorder_one_mix():
    mix = spec.traffic("decode_heavy")
    a = traffic.backlog(mix, 200064, 2 ** 40 + 7)
    b = traffic.backlog(mix, 200064, 2 ** 40 + 7)
    c = traffic.backlog(mix, 200064, 5)
    assert a == b
    assert a != c
    assert len(a) == mix["backlog"]
    blk = mix["block"]
    for reqs in (a, c):
        # the first block starts from the steady state (see below)
        for lo in range(blk, len(reqs), blk):
            lens = sorted(len(p) for p, _ in reqs[lo:lo + blk])
            outs = sorted(n for _, n in reqs[lo:lo + blk])
            assert lens == sorted(traffic.quantile_lengths(
                mix["prompt_tokens"], blk))
            assert outs == sorted(traffic.quantile_lengths(
                mix["output_tokens"], blk))
    for p, n in a[blk:]:
        assert 64 <= len(p) <= 512 and 256 <= n <= 1536
        assert len(p) + n <= mix["max_seq_len"]
        assert all(0 <= t < 200064 for t in p)
    mean_out = sum(n for _, n in a[blk:]) / (len(a) - blk)
    assert 550 <= mean_out <= 650


def test_first_block_starts_from_the_steady_state():
    mix = spec.traffic("decode_heavy")
    blk = mix["block"]
    outs = traffic.quantile_lengths(mix["output_tokens"], blk)
    slots = traffic.steady_state_slots(outs, blk)
    assert slots == traffic.steady_state_slots(outs, blk)
    # each slot holds one of the mix's lengths, split into what was
    # generated and what is left, and long outputs hold slots longer
    assert all(done + left in outs and left >= 1 for done, left in slots)
    biased = sum(d + x for d, x in slots) / blk
    assert biased == pytest.approx(sum(o * o for o in outs) / sum(outs),
                                   rel=0.05)
    for seed in (2 ** 40 + 7, 5):
        first = traffic.backlog(mix, 200064, seed)[:blk]
        prompts = sorted(traffic.quantile_lengths(mix["prompt_tokens"],
                                                  blk))
        # the prompt plus what was generated, then what is left
        assert sorted(n for _, n in first) == sorted(x for _, x in slots)
        done = sum(len(p) for p, _ in first) - sum(prompts)
        assert done == sum(d for d, _ in slots)
        for p, n in first:
            assert len(p) + n <= mix["max_seq_len"]
            assert len(p) <= max(mix["bucket_lens"])


def test_warmup_covers_every_bucket():
    mix = spec.traffic("decode_heavy")
    w = traffic.warmup(mix, 1000, 3)
    assert [len(p) for p, _ in w] == [min(b, mix["max_seq_len"] - 2)
                                      for b in mix["bucket_lens"]]
    assert all(len(p) + n <= mix["max_seq_len"] for p, n in w)
    assert w == traffic.warmup(mix, 1000, 3)


# -- correctness numbers -----------------------------------------------------


def test_leaf_gap_takes_the_worst_leaf_against_the_median_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    # c is tiny: its gap is measured against the median leaf (1.0)
    assert check.leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5},
                          ref) == pytest.approx(0.5, abs=1e-5)
    assert check.leaf_gap({"a": 1.1, "b": 2.0, "c": 1e-6},
                          ref) == pytest.approx(0.1)
    # a leaf the program left unmoved reads 1
    assert check.leaf_gap({"a": 0.0, "b": 2.0, "c": 1e-6}, ref) == 1.0


def test_train_numbers_leave_quiet_leaves_out_of_the_change():
    ref = {"losses": [10.0, 9.0], "first_grad": {"a": 1.0, "b": 1e-9},
           "delta": {"a": 1.0, "b": 1.0}}
    prog = {"losses": [10.01, 9.5], "first_grad": {"a": 1.0, "b": 0.0},
            "delta": {"a": 1.0, "b": 5.0}}
    n = check.train_numbers(prog, ref)
    assert n["first_loss_gap"] == pytest.approx(1e-3)
    assert n["delta_gap"] == 0.0            # b moves by round-off alone
    checks = check.judge(n, {"first_loss_gap": 1e-2, "delta_gap": 0.1})
    assert set(checks) == {"first_loss_gap", "delta_gap"}
    assert check.passes(checks)
    checks["first_loss_gap"]["value"] = math.nan
    assert not check.passes(checks)
    with pytest.raises(KeyError):
        check.judge(n, {"served_gap": 1.0})


# -- the benchmark's own files -----------------------------------------------


def test_every_cell_finds_its_files_by_name():
    bench = spec.benchmark()
    names = {m["name"] for m in bench["per_layer"]}
    for m in names:
        assert hasattr(spec.metric_reader(m), "read")
    for w in bench["workloads"]:
        cfg = spec.config(w["config"])
        ref = spec.reference(cfg)
        assert ref.unmodelled(program.model_config(cfg)) == []
        assert spec.traffic(w["traffic"])["kind"] in ("train", "serve")
        lim = spec.limits(w["name"])
        assert lim and all(v > 0 for v in lim.values())
        assert spec.metrics_for(w["name"], False)
        assert spec.metrics_for(w["name"], True)
    with open(BENCH / "peaks.json") as f:
        assert "TPU v5 lite" in json.load(f)["devices"]


def test_mfu_readers_count_the_references_flops_as_flops_py_does():
    peaks = {"bf16_flops_per_s": 197e12}
    serve = {"kind": "serve", "cfg": spec.config("phi4-mini-3.8b"),
             "traced_contexts": [[100, 300], [101, 301, 7]],
             "traced_prompts": [64, 500], "chips": 1, "peaks": peaks,
             "window_s": 4.0}
    cfg = serve["cfg"]
    need = (flops.decode_flops(cfg, [100, 300])
            + flops.decode_flops(cfg, [101, 301, 7])
            + flops.prefill_flops(cfg, [64, 500]))
    assert spec.metric_reader("mfu.serve").read(serve) == \
        100.0 * need / (4.0 * 197e12)
    train = {"kind": "train", "cfg": spec.config("olmo-1b-4l"),
             "mix": {"seq_len": 2048}, "traced_tokens": 11 * 8192,
             "chips": 1, "peaks": peaks, "window_s": 4.1}
    need = 11 * 8192 * flops.train_flops_per_token(train["cfg"], 2048)
    assert spec.metric_reader("mfu.train").read(train) == \
        100.0 * need / (4.1 * 197e12)
