"""The program's span recorder (repro/obs.py): nesting, the ring's
bound, counters, spans left by an exception, and compiles counted
against the span they happened in."""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def _by_name():
    return {s.name: s for s in obs.spans()}


def test_spans_nest_with_parent_ids_and_close_inner_first():
    with obs.span("outer", step=3) as outer:
        with obs.span("inner", bytes=8):
            pass
        with obs.span("second"):
            pass
    with obs.span("after"):
        pass
    got = obs.spans()
    assert [s.name for s in got] == ["inner", "second", "outer", "after"]
    s = _by_name()
    assert s["outer"].parent == 0 and s["after"].parent == 0
    assert s["inner"].parent == s["second"].parent == s["outer"].id
    assert s["outer"].attrs == {"step": 3}
    assert s["inner"].attrs == {"bytes": 8}
    assert s["outer"].t0_ns <= s["inner"].t0_ns <= s["inner"].t1_ns \
        <= s["second"].t0_ns <= s["second"].t1_ns <= s["outer"].t1_ns
    assert outer.seconds == (s["outer"].t1_ns - s["outer"].t0_ns) * 1e-9


def test_event_is_a_point_and_ids_tie_records_together():
    req = obs.new_id()
    with obs.span("loop"):
        first = obs.event("request.submit", req=req, rid=7)
    obs.event("request.last_token", req=req, tokens=3)
    s = _by_name()
    assert s["request.submit"].id == first
    assert s["request.submit"].parent == s["loop"].id
    assert s["request.submit"].t0_ns == s["request.submit"].t1_ns
    assert s["request.submit"].attrs["req"] == \
        s["request.last_token"].attrs["req"] == req
    assert len({first, req, s["loop"].id}) == 3


def test_ring_keeps_the_newest_records(monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=4))
    for i in range(10):
        with obs.span("x", i=i):
            pass
    assert [s.attrs["i"] for s in obs.spans()] == [6, 7, 8, 9]
    assert obs.RING == 1 << 16


def test_counters_add_and_reset():
    obs.count("a")
    obs.count("a", 4)
    obs.count("b", 2)
    assert obs.counters() == {"a": 5, "b": 2}
    obs.reset()
    assert obs.counters() == {} and obs.spans() == []


def test_a_span_left_by_an_exception_is_recorded_and_the_stack_unwinds():
    with pytest.raises(KeyError):
        with obs.span("outer"):
            with obs.span("raises", step=2):
                raise KeyError("window closed")
    with obs.span("next"):
        pass
    s = _by_name()
    assert s["raises"].parent == s["outer"].id
    assert s["raises"].t1_ns >= s["raises"].t0_ns
    assert s["next"].parent == 0


def test_a_retrace_is_counted_against_the_span_it_happened_in():
    @jax.jit
    def scaled_sum(x):
        return x * 3 + 1

    a, b = jnp.ones(5), jnp.ones(6)
    with obs.span("warm"):
        scaled_sum(a).block_until_ready()
    with obs.span("steady", step=1):
        scaled_sum(a).block_until_ready()
    with obs.span("steady", step=2):
        with obs.span("shape change"):
            scaled_sum(b).block_until_ready()        # a new shape
    by_step = {(s.name, s.attrs.get("step")): s for s in obs.spans()}
    assert by_step[("warm", None)].attrs["compiles"] >= 2
    assert "compiles" not in by_step[("steady", 1)].attrs
    assert "compiles" not in by_step[("steady", 2)].attrs
    # its trace (with those of the jitted operators inside it) and one
    # executable build, on the innermost open span only
    assert by_step[("shape change", None)].attrs["compiles"] >= 2
    c = obs.counters()
    assert c["jit.traces.scaled_sum"] == 2
    assert c["jit.compiles.scaled_sum"] == 2


# -- the train step's exchange counter and span attributes ------------------

TRAIN_ARGS = ["--arch", "olmo-1b", "--smoke", "--steps", "3",
              "--global-batch", "8", "--seq-len", "16", "--warmup", "1",
              "--log-every", "1000"]

EXCHANGE_SCRIPT = r"""
import json, sys
from repro import obs
from repro.launch import steps as steps_mod, train as train_mod
from repro.roofline import hlo

texts = []
count = steps_mod.exchange_bytes


def spy(text):
    texts.append(text)
    return count(text)


steps_mod.exchange_bytes = spy
args = train_mod.parse_args(json.loads(sys.argv[1]))
train_mod.train(args)
comps = hlo._split_computations(texts[0])
once = sum(hlo._shape_bytes(hlo._result_text(line))
           for lines in comps.values() for line in lines
           if hlo._line_op(line) in hlo._EXCHANGE_OPS)
print("RESULT", json.dumps({
    "texts": len(texts), "counted": hlo.exchange_bytes(texts[0]),
    "once": once, "whiles": texts[0].count(" while("),
    "counters": obs.counters(),
    "steps": [s.attrs for s in obs.spans() if s.name == "train.step"]}))
"""


def _train_in_child(tmp_path, devices):
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    argv = TRAIN_ARGS + ["--devices", devices,
                         "--data-dir", str(tmp_path / "data"),
                         "--ckpt-dir", str(tmp_path / "ckpt")]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", EXCHANGE_SCRIPT,
                           json.dumps(argv)], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


# 8 rows a step, dealt by the capacity plan: all 8 to one rank, or 2 to
# each of four
@pytest.mark.parametrize("devices,ranks,rows", [("4,1", 4, [2, 2, 2, 2]),
                                                ("1,1", 1, [8])])
def test_the_train_step_counts_its_exchange_once_per_build(
        tmp_path, devices, ranks, rows):
    got = _train_in_child(tmp_path, devices)
    c = got["counters"]
    # one trace and one executable build of the step, and the counter
    # read from that executable's own HLO
    assert got["texts"] == 1
    assert c["jit.traces.train_step"] == c["jit.compiles.train_step"] == 1
    assert c["train.exchange_bytes"] == got["counted"]
    if ranks == 1:
        assert got["counted"] == 0
    else:
        # the layer scan's collectives count once per layer
        assert got["whiles"] >= 1
        assert got["counted"] > got["once"] > 0
    assert [s["step"] for s in got["steps"]] == [1, 2, 3]
    for s in got["steps"]:
        assert (s["ranks"], s["rows"]) == (ranks, rows)
        assert s["exchange_bytes"] == got["counted"]
