"""The program's spans on the CPU: the engine and ``train()`` emit them
in order with their step attributes, they line up with a real profiler
trace, and the readers built on them (``benchlib/program_spans.py``)
read constructed traces as a hand count says."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from benchlib import program_spans as ps  # noqa: E402
from benchlib import spec  # noqa: E402
from benchlib import trace as trace_mod  # noqa: E402
from benchlib.window import Window  # noqa: E402
from repro import obs  # noqa: E402
from repro.obs import Span  # noqa: E402

NS = 1_000_000_000


def _children(spans, parent):
    return [s for s in sorted(spans, key=lambda s: s.t0_ns)
            if s.parent == parent.id]


# -- the program emits its spans ---------------------------------------------


def _serve_loop_order(spans):
    """Each ``serve.iteration``'s children in order: admit, any number
    of (prefill, sample), then prepare, decode, fetch, sample."""
    by_id = {s.id: s for s in spans}
    for it in (s for s in spans if s.name == "serve.iteration"):
        names = [c.name for c in _children(spans, it)]
        assert names[0] == "serve.admit", names
        k = 1
        while k < len(names) and names[k] == "serve.prefill":
            assert names[k + 1] == "serve.sample", names
            k += 2
        if k < len(names):
            assert names[k:] == ["serve.prepare", "serve.decode",
                                 "serve.fetch", "serve.sample"], names
    for p in (s for s in spans if s.name == "serve.prefill"):
        assert [c.name for c in _children(spans, p)] == ["serve.fetch"]
        assert p.attrs["rows"] >= 1 and p.attrs["prompt_tokens"] >= 1
        assert by_id[p.parent].name == "serve.iteration"


def test_engine_emits_its_spans_in_order_and_the_request_lifecycle():
    import jax

    from benchlib import program, traffic
    from repro.launch import serve as serve_mod
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.models.kvcache import PagedLayout
    from repro.models.model import build_model
    from repro.serve import Request

    cfg, mix = bench_tiny.phi4(), bench_tiny.serve_mix()
    model = build_model(program.model_config(cfg))
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    mbs = mix["max_seq_len"] // mix["block_size"]
    layout = PagedLayout(block_size=mix["block_size"],
                         num_blocks=mix["slots"] * mbs,
                         max_blocks_per_seq=mbs)
    params = steps_mod.init_params_sharded(model, mesh,
                                           jax.random.PRNGKey(0))
    warm = traffic.warmup(mix, cfg["vocab_size"], 5)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(warm)]
    obs.reset()
    with jax.set_mesh(mesh):
        engine = serve_mod.build_engine(
            model, params, mesh, layout, mix["slots"],
            mix["prefill_batch"], [1.0], bucket_lens=mix["bucket_lens"])
        first = engine.run(reqs)
        mark = obs.new_id()
        second = engine.run(reqs)
    spans = obs.spans()
    _serve_loop_order(spans)
    for run, res in ((1, first), (2, second)):
        steps = [s.attrs for s in spans if s.name == "serve.decode"
                 and s.attrs["run"] == run]
        assert [a["step"] for a in steps] == \
            list(range(1, res.stats["decode_steps"] + 1))
        assert all(1 <= a["active"] <= mix["slots"]
                   and a["kv_tokens"] > a["active"] for a in steps)
    # one submission, admission, first and last token per request of the
    # second run, all sharing one id, in that order on the clock
    events = {}
    for s in spans:
        if s.name.startswith("request.") and s.id > mark:
            events.setdefault(s.attrs["req"], []).append(s)
    assert len(events) == len(reqs)
    for evs in events.values():
        assert [e.name for e in evs] == [
            "request.submit", "request.admit", "request.first_token",
            "request.last_token"]
        assert [e.t0_ns for e in evs] == sorted(e.t0_ns for e in evs)
        assert evs[-1].attrs["tokens"] == len(
            second.tokens[evs[0].attrs["rid"]])
    s = second.stats
    assert 0 < s["ttft_ms_p50"] <= s["ttft_ms_p99"]
    assert 0 < s["token_gap_ms_p50"] <= s["token_gap_ms_p99"]
    # the decode step was built once, in the first run, not the second
    assert obs.counters()["jit.compiles.paged_decode_step"] == 1
    assert not any(s.attrs.get("compiles") for s in spans
                   if s.id > mark)


def test_train_emits_its_spans_in_order_and_times_steps_by_them(tmp_path):
    from benchlib import program
    from repro.launch import train as train_mod

    cfg = bench_tiny.olmo()
    argv = ["--arch", cfg["program_arch"], "--steps", "4",
            "--global-batch", "4", "--seq-len", "32", "--devices", "1,1",
            "--log-every", "2", "--ckpt-every", "2", "--warmup", "1",
            "--data-dir", str(tmp_path / "data"),
            "--ckpt-dir", str(tmp_path / "ckpt")]
    obs.reset()
    res = train_mod.train(train_mod.parse_args(argv),
                          program.model_config(cfg))
    spans = obs.spans()
    iters = sorted((s for s in spans if s.name == "train.iteration"),
                   key=lambda s: s.t0_ns)
    assert len(iters) == 4
    steps = []
    for k, it in enumerate(iters, 1):
        kids = _children(spans, it)
        assert [c.name for c in kids] == ["train.input", "train.step",
                                          "train.monitor"]
        assert kids[1].attrs["step"] == k
        steps.append(kids[1])
        saves = [c.name for c in _children(spans, kids[2])]
        assert saves == (["train.checkpoint"] if k % 2 == 0 else [])
    assert res["step_s"] == [(s.t1_ns - s.t0_ns) * 1e-9 for s in steps]
    assert res["wall_s"] >= sum(res["step_s"])
    assert obs.counters()["jit.compiles.train_step"] == 1
    assert steps[0].attrs["compiles"] >= 1
    assert not any(s.attrs.get("compiles") for s in steps[1:])


# -- the spans line up with a real profiler trace -----------------------------


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_program_spans_line_up_with_a_cpu_profile(kind, tmp_path):
    """A traced run of each cell's tiny stand-in on the CPU: the harness
    spans pair with the program's inside the slack, no compile happens
    in the window, and the idle readers read nothing (no device plane:
    the CPU's ops are not in the trace)."""
    import jax

    from benchlib import serve_cell, train_cell

    devs = jax.devices()[:1]
    window = Window(0.5, str(tmp_path / "trace"), time.perf_counter())
    if kind == "serve":
        res = serve_cell.run(bench_tiny.phi4(), bench_tiny.serve_mix(),
                             2 ** 33 + 5, devs, window, None)
    else:
        res = train_cell.run(bench_tiny.olmo(), bench_tiny.train_mix(),
                             2 ** 33 + 5, devs, tmp_path / "train", window,
                             None)
    tr = trace_mod.load(str(tmp_path / "trace"))
    al = ps.align(tr, obs.spans(), *ps.CALLS[kind])
    assert al is not None and al.pairs >= 2
    assert al.worst_s <= ps.SLACK_S
    rec = dict(res, kind=kind, trace=tr, window_s=tr.window_s)
    assert spec.metric_reader(f"compiles.{kind}").read(rec) == 0
    idle = {"serve": ["sample_idle_share.serve", "sched_idle_share.serve"],
            "train": ["input_idle_share.train"]}[kind]
    assert all(spec.metric_reader(m).read(rec) is None for m in idle)


# -- align, idle_by_span and the readers on constructed traces ---------------

OFFSET = -5.0            # trace time = program time - 5 s


def _span(i, parent, name, a, b, **attrs):
    return Span(i, parent, name, int(round(a * NS)), int(round(b * NS)),
                attrs)


def _serve_program(compiles_in_window=0):
    """A warm-up run (run 1) long before the window, then two engine
    iterations of run 2 at program times 15-19 and 19-23, each: admit
    0.5 s, prepare 0.5, decode 2 (steps 2 and 3), fetch 0.5, sample 0.4,
    0.1 s under the iteration alone."""
    spans = [_span(1, 0, "serve.decode", 1.0, 2.0, run=1, step=1,
                   compiles=3),
             _span(2, 0, "serve.decode", 2.0, 3.0, run=1, step=2)]
    i = 10
    for k, base in enumerate((15.0, 19.0)):
        it = i
        parts = [("serve.admit", 0.0, 0.5, {}),
                 ("serve.prepare", 0.5, 1.0,
                  {"compiles": compiles_in_window} if k else {}),
                 ("serve.decode", 1.0, 3.0, {"run": 2, "step": 2 + k}),
                 ("serve.fetch", 3.0, 3.5, {"bytes": 64}),
                 ("serve.sample", 3.5, 3.9, {})]
        for name, a, b, attrs in parts:
            i += 1
            spans.append(_span(i, it, name, base + a, base + b, **attrs))
        spans.append(_span(it, 0, "serve.iteration", base, base + 4.0))
        i += 1
    return spans


def _serve_trace(poke=0.0, devices=1):
    """Trace window 10-19; the device is busy 1.5-3.0 s into each
    iteration; the harness decode span sits inside the program's."""
    ops, spans = {}, [(10.0, 19.0, trace_mod.WINDOW_SPAN)]
    for k in range(2):
        t = 10.0 + 4 * k
        spans.append((t + 1.0001, t + 2.9999 + (poke if k else 0.0),
                      "bench.decode_step"))
    for d in range(devices):
        ops[f"/device:TPU:{d}"] = [(10.0 + 4 * k + 1.5, 10.0 + 4 * k + 3.0,
                                    "fusion.1") for k in range(2)]
    return trace_mod.Trace(ops=ops, spans=spans, window=(10.0, 19.0))


def test_align_pairs_the_last_run_and_refuses_a_span_that_pokes_out():
    al = ps.align(_serve_trace(), _serve_program(), *ps.CALLS["serve"])
    assert al.offset_s == pytest.approx(OFFSET, abs=1e-3)
    assert al.pairs == 2 and al.worst_s <= 0
    # 0.4 ms out of its program span: inside the slack; 1 ms: refused
    assert ps.align(_serve_trace(poke=0.4e-3), _serve_program(),
                    *ps.CALLS["serve"]) is not None
    assert ps.align(_serve_trace(poke=1e-3), _serve_program(),
                    *ps.CALLS["serve"]) is None
    # more harness spans than program calls of the run: refused
    short = [s for s in _serve_program() if s.attrs.get("step") != 3]
    assert ps.align(_serve_trace(), short, *ps.CALLS["serve"]) is None
    assert ps.align(_serve_trace(), [], *ps.CALLS["serve"]) is None


def test_idle_goes_to_the_innermost_program_span():
    idle = ps.idle_by_span(_serve_trace(devices=2), _serve_program(),
                           OFFSET)
    want = {"serve.admit": 1.0, "serve.prepare": 1.0, "serve.decode": 1.0,
            "serve.fetch": 1.0, "serve.sample": 0.8,
            "serve.iteration": 0.2, ps.OUTSIDE: 1.0}
    assert idle == pytest.approx(want)
    assert sum(idle.values()) == pytest.approx(
        9.0 - trace_mod.busy_s(_serve_trace()))


def _rec(kind, tr):
    return {"kind": kind, "trace": tr, "window_s": tr.window_s}


def test_serving_readers_on_a_constructed_trace(monkeypatch):
    monkeypatch.setattr(ps, "program_spans",
                        lambda: _serve_program(compiles_in_window=2))
    rec = _rec("serve", _serve_trace())
    read = {m: spec.metric_reader(m).read(rec)
            for m in ("sample_idle_share.serve", "sched_idle_share.serve",
                      "compiles.serve", "input_idle_share.train",
                      "compiles.train")}
    assert read["sample_idle_share.serve"] == pytest.approx(100 * 1.8 / 9)
    assert read["sched_idle_share.serve"] == pytest.approx(100 * 2.0 / 9)
    assert read["compiles.serve"] == 2          # the warm-up's 3 are out
    assert read["input_idle_share.train"] is None
    assert read["compiles.train"] is None
    # together under the device's idle share
    idle_share = spec.metric_reader("idle_share.serve").read(
        dict(rec, busy_s=trace_mod.busy_s(rec["trace"])))
    assert read["sample_idle_share.serve"] + \
        read["sched_idle_share.serve"] <= idle_share


@pytest.mark.parametrize("broken", ["poke", "no_obs"])
def test_readers_read_nothing_when_the_spans_do_not_line_up(monkeypatch,
                                                            broken):
    spans = None if broken == "no_obs" else _serve_program()
    monkeypatch.setattr(ps, "program_spans", lambda: spans)
    rec = _rec("serve", _serve_trace(poke=1e-3 if broken == "poke"
                                     else 0.0))
    for m in ("sample_idle_share.serve", "sched_idle_share.serve",
              "compiles.serve"):
        assert spec.metric_reader(m).read(rec) is None, m


def test_training_readers_on_a_constructed_trace(monkeypatch):
    from benchlib.train_cell import WARM_STEPS

    # steps WARM_STEPS+1 and +2 at program times 15-19 and 19-23: input
    # 1 s, step 2 s, monitor 1 s; the device is busy 1.2-3.0 s in
    spans, ops, harness = [], [], []
    for k, base in enumerate((15.0, 19.0)):
        it = 10 * (k + 1)
        spans += [_span(it + 1, it, "train.input", base, base + 1.0),
                  _span(it + 2, it, "train.step", base + 1.0, base + 3.0,
                        step=WARM_STEPS + 1 + k),
                  _span(it + 3, it, "train.monitor", base + 3.0,
                        base + 4.0),
                  _span(it, 0, "train.iteration", base, base + 4.0)]
        t = base + OFFSET
        harness.append((t + 1.001, t + 2.99, "bench.train_step"))
        ops.append((t + 1.2, t + 3.0, "fusion.2"))
    tr = trace_mod.Trace(ops={"/device:TPU:0": ops},
                         spans=harness + [(10.0, 18.0,
                                           trace_mod.WINDOW_SPAN)],
                         window=(10.0, 18.0))
    monkeypatch.setattr(ps, "program_spans", lambda: spans)
    rec = _rec("train", tr)
    assert spec.metric_reader("input_idle_share.train").read(rec) == \
        pytest.approx(100 * 2.0 / 8)
    assert spec.metric_reader("compiles.train").read(rec) == 0
    assert spec.metric_reader("sample_idle_share.serve").read(rec) is None
    idle = ps.idle_by_span(tr, spans, OFFSET)
    assert {k: v for k, v in idle.items() if v > 1e-9} == pytest.approx(
        {"train.input": 2.0, "train.step": 0.4, "train.monitor": 2.0})
    assert np.isclose(sum(idle.values()), 8.0 - trace_mod.busy_s(tr))
