"""The harness end to end on the CPU at small sizes: it refuses a
machine without a TPU, a sound run comes out correct, and a run whose
timed path is broken underneath comes out not correct, once for each
fault the cell can have. Also the low-precision control, kept here at a
size a test run holds: it must read well above the program."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402

TRAIN_LIMITS = bench_tiny.TRAIN_LIMITS
SERVE_LIMITS = bench_tiny.SERVE_LIMITS
_run = bench_tiny.run


def test_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(bench_tiny.BENCH / "run.py"), "--workload",
         "olmo1b-4l-train-1chip", "--seed", "1", "--seconds", "1"],
        cwd=bench_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def _patch_train_step(monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from repro.launch import steps as steps_mod

    build = steps_mod.build_train_step

    def broken(model, tcfg, mesh):
        step = build(model, tcfg, mesh)

        def frozen(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        def half(state, batch):
            w = batch["weights"]
            real = jnp.flatnonzero(w.sum(axis=1) > 0,
                                   size=tcfg.shape.global_batch)
            drop = real[tcfg.shape.global_batch // 2:]
            return step(state, dict(batch, weights=w.at[drop].set(0.0)))

        return {"frozen": frozen, "half_batch": half}[fault]

    monkeypatch.setattr(steps_mod, "build_train_step", broken)


def test_training_cell_sound_and_broken(monkeypatch):
    line = _run(bench_tiny.olmo(), bench_tiny.train_mix(), TRAIN_LIMITS)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    for fault in ("frozen", "half_batch"):
        with monkeypatch.context() as m:
            _patch_train_step(m, fault)
            line = _run(bench_tiny.olmo(), bench_tiny.train_mix(),
                        TRAIN_LIMITS)
        assert not line["correct"], (fault, line["checks"])


def test_training_control_reads_above_the_program():
    line = _run(bench_tiny.olmo(), bench_tiny.train_mix(), TRAIN_LIMITS,
                controls=("fp8",))
    prog, ctl = line["readings"]["program"], line["readings"]["fp8"]
    assert max(ctl[k] / prog[k] for k in prog) >= 3.0, line["readings"]
    # put in the program's place, the control fails the same limits
    assert line["correct"], line["checks"]
    assert not line["stand_ins"]["fp8"]["correct"], line["stand_ins"]


def test_serving_control_is_not_correct():
    line = _run(bench_tiny.phi4(), bench_tiny.serve_mix(), SERVE_LIMITS,
                seconds=1.5, controls=("fp8",))
    assert line["correct"], line["checks"]
    ctl = line["stand_ins"]["fp8"]
    assert not ctl["correct"], line["readings"]
    assert ctl["checks"]["served_gap"]["limit"] == SERVE_LIMITS["served_gap"]


def test_serving_cell_sound_and_a_token_altered(monkeypatch):
    import jax.numpy as jnp

    from repro.launch import steps as steps_mod

    line = _run(bench_tiny.phi4(), bench_tiny.serve_mix(), SERVE_LIMITS,
                seconds=1.5)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"serve_tokens_per_s",
                                    "serve_itl_p95_ms", "setup_s"}
    build = steps_mod.build_paged_decode_step

    def altered(*args, **kwargs):
        step = build(*args, **kwargs)

        def decode(*a):
            logits, cache = step(*a)
            return jnp.roll(logits, 1, axis=-1), cache
        return decode

    monkeypatch.setattr(steps_mod, "build_paged_decode_step", altered)
    line = _run(bench_tiny.phi4(), bench_tiny.serve_mix(), SERVE_LIMITS,
                seconds=1.5)
    assert not line["correct"], line["checks"]


DP_SCRIPT = r"""
import sys, time
sys.path.insert(0, sys.argv[1])
import bench_tiny, jax, jax.numpy as jnp
from benchlib import spec
from repro.launch import steps as steps_mod
R = bench_tiny.harness()
fault = sys.argv[2]
if fault == "no_exchange":
    build = steps_mod.build_train_step
    def broken(model, tcfg, mesh):
        step = build(model, tcfg, mesh)
        def local(state, batch):
            # only the first rank's rows reach the update: what each
            # rank computes when the exchange is left out
            w = batch["weights"]
            per_rank = w.shape[0] // 4
            keep = (jnp.arange(w.shape[0]) < per_rank)[:, None]
            return step(state, dict(batch, weights=w * keep))
        return local
    steps_mod.build_train_step = broken
line = R.run_cell({"name": "cpu-dp", "chips": 4}, bench_tiny.olmo(),
                  bench_tiny.train_mix("train_dp4_gb16_s2048"),
                  {"grad_gap": 0.01, "delta_gap": 0.01},
                  spec.metrics_for("olmo1b-4l-train-1chip", False),
                  2 ** 34 + 1, 0.5, False, jax.devices()[:4],
                  time.perf_counter())
print("CORRECT", line["correct"], line["checks"])
"""


@pytest.mark.parametrize("fault,want", [("none", True),
                                        ("no_exchange", False)])
def test_four_device_cell_sound_and_exchange_left_out(fault, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", DP_SCRIPT, str(Path(__file__).parent),
         fault], cwd=bench_tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = [ln for ln in proc.stdout.splitlines()
           if ln.startswith("CORRECT")]
    assert out and out[-1].startswith(f"CORRECT {want}"), out
