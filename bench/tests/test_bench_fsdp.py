"""The four-chip training cell's pieces on the CPU: the sharded
reference against the dense one on four emulated devices, the harness
end to end on a tiny four-device cell judged by the sharded reference,
and the cell's configuration against the program's registry."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from benchlib import program, spec  # noqa: E402

FSDP = "bench/benchlib/reference_fsdp.py"
CELL = "olmo1b-train-dp4"

# float32 rounding alone separates the two references: the sharded one
# sums each gradient over the devices in another order (reduce-scatter
# against all-reduce) and its norms over stacked layers. Read at this
# size: losses equal, leaf norms of the first gradient 1.5e-7 and of the
# change 3.7e-7 apart (relative); the limits leave ~30x room. The fp8
# control reads 1e-3 and more, so a lower precision cannot pass them.
RTOL = {"losses": 1e-6, "first_grad": 1e-5, "delta": 1e-5}

CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
import bench_tiny, jax, numpy as np
from benchlib import reference, reference_fsdp, spec
import jax.numpy as jnp
from repro.launch import steps as steps_mod

what = sys.argv[2]
cfg = dict(bench_tiny.olmo(), reference="bench/benchlib/reference_fsdp.py")
devs = jax.devices()[:4]
if what == "references":
    opt = bench_tiny.train_mix("train_dp4_gb16_s2048")["optimizer"]
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(3):
        w = np.ones((10, 32), np.float32)
        w[[3, 8]] = 0.0                     # two buffer rows
        batches.append({"inputs": rng.integers(0, 256, (10, 32), np.int32),
                        "labels": rng.integers(0, 256, (10, 32), np.int32),
                        "weights": w})
    out = {name: mod.train_reference(cfg, opt, 12345, batches, devices=devs)
           for name, mod in (("dense", reference),
                             ("sharded", reference_fsdp))}
    print("RESULT", json.dumps(out))
else:
    if what == "no_exchange":
        build = steps_mod.build_train_step
        def broken(model, tcfg, mesh):
            step = build(model, tcfg, mesh)
            def local(state, batch):
                # only the first rank's rows reach the update
                w = batch["weights"]
                keep = (jnp.arange(w.shape[0]) < w.shape[0] // 4)[:, None]
                return step(state, dict(batch, weights=w * keep))
            return local
        steps_mod.build_train_step = broken
    R = bench_tiny.harness()
    kw = ({"controls": ("fp8",), "faults": ("half_batch", "no_exchange")}
          if what == "sound" else {})
    line = R.run_cell({"name": "cpu-dp", "chips": 4}, cfg,
                      bench_tiny.train_mix("train_dp4_gb16_s2048"),
                      bench_tiny.TRAIN_LIMITS,
                      spec.metrics_for("olmo1b-train-dp4", False),
                      2 ** 34 + 3, 0.5, False, devs, time.perf_counter(),
                      **kw)
    print("RESULT", json.dumps({k: line[k] for k in
                                ("correct", "checks", "stand_ins",
                                 "readings")}))
"""


def _child(what):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(Path(__file__).parent), what],
        cwd=bench_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_reference_equals_the_dense_one_on_four_devices():
    got = _child("references")
    dense, sharded = got["dense"], got["sharded"]
    assert sharded["losses"] == pytest.approx(dense["losses"],
                                              rel=RTOL["losses"])
    for key in ("first_grad", "delta"):
        assert set(sharded[key]) == set(dense[key])
        for name, v in dense[key].items():
            assert sharded[key][name] == pytest.approx(v, rel=RTOL[key]), \
                (key, name)


@pytest.mark.parametrize("what,want", [("sound", True),
                                       ("no_exchange", False)])
def test_four_device_cell_judged_by_the_sharded_reference(what, want):
    got = _child(what)
    assert got["correct"] is want, got["checks"]
    if what == "sound":
        # both faults, in the program's place, fail; the control reads
        # well above the program (its limit is the chip's to set)
        for name in ("half_batch", "no_exchange"):
            assert not got["stand_ins"][name]["correct"], \
                (name, got["readings"][name])
        prog, ctl = got["readings"]["program"], got["readings"]["fp8"]
        assert max(ctl[k] / prog[k] for k in prog) >= 3.0, got["readings"]


def test_the_cells_configuration_is_the_registrys_olmo_1b():
    from repro.configs import olmo_1b

    cfg = spec.config("olmo-1b")
    mc, full = program.model_config(cfg), olmo_1b.full()
    for f in ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "norm", "activation",
              "rope_theta", "tie_embeddings"):
        assert getattr(mc, f) == getattr(full, f), f
    assert cfg["reduced"] == [] and cfg["published"]["num_layers"] == 16
    assert cfg["reference"] == FSDP
    assert spec.reference(cfg).unmodelled(mc) == []
    bench = spec.benchmark()
    entry = spec.workload(CELL)
    assert (entry["config"], entry["chips"]) == ("olmo-1b", 4)
    assert [c for c in bench["configs"]
            if c["name"] == "olmo-1b"][0]["reduced"] == []
    mix = spec.traffic(entry["traffic"])
    assert (mix["devices"], mix["global_batch"]) == ("4,1", 16)
    assert set(spec.limits(CELL)) == {"grad_gap", "delta_gap",
                                     "first_loss_gap"}
    per_layer = {m["name"] for m in spec.metrics_for(CELL, True)}
    for name in ("exposed_collective_share.train", "exchange_mb.train"):
        assert name in per_layer
        assert hasattr(spec.metric_reader(name), "read")
