"""Small stand-ins for the cells' configurations and mixes, for CPU
tests: the same files with small widths, so that every key the harness
reads is the real one. The serving stand-in keeps a 256-wide model so
that its logits spread as a real model's do (its top logits are not
all within rounding of each other)."""
from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import spec  # noqa: E402

SMALL = dict(num_layers=2, num_heads=4, d_ff=128)
# the cells' own limits hold the full-size readings; the small
# stand-ins read the float32 reference ~1e-3 off (grad_gap, delta_gap)
# and ~1e-2 (served_gap), faults 3e-2 and more, and the serving
# stand-in's fp8 control ~0.25
TRAIN_LIMITS = {"grad_gap": 0.01, "delta_gap": 0.01}
SERVE_LIMITS = {"served_gap": 0.08}


def olmo():
    return dict(spec.config("olmo-1b-4l"), name="olmo-tiny",
                d_model=64, head_dim=16, num_kv_heads=4, vocab_size=256,
                **SMALL)


def phi4(attention_impl="reference"):
    return dict(spec.config("phi4-mini-3.8b"), name="phi4-tiny",
                d_model=256, head_dim=64, num_kv_heads=2, vocab_size=512,
                attention_impl=attention_impl, **SMALL)


def train_mix(name="train_gb4_s2048"):
    return dict(spec.traffic(name), seq_len=32)


def serve_mix():
    return dict(spec.traffic("decode_heavy"), slots=4, max_seq_len=256,
                bucket_lens=[32, 64, 256], backlog=4000, block=4,
                prompt_tokens={"min": 16, "max": 64, "median": 32,
                               "sigma": 0.5},
                output_tokens={"min": 16, "max": 128, "median": 48,
                               "sigma": 0.5})


@functools.lru_cache(maxsize=None)
def harness():
    """bench/run.py as a module (its name would clash as ``run``)."""
    mod_spec = importlib.util.spec_from_file_location(
        "bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def run(cfg, mix, limits, seed=2 ** 33 + 5, seconds=0.5, **kw):
    """One run of a cell on the first CPU device, through the harness's
    ``run_cell``, reporting the metrics of the cell of the mix's kind."""
    import time

    import jax

    kind = "olmo1b-4l-train-1chip" if mix["kind"] == "train" \
        else "phi4mini-decode-heavy"
    return harness().run_cell(
        {"name": "cpu-test", "chips": 1}, cfg, mix, limits,
        spec.metrics_for(kind, False), seed, seconds, False,
        jax.devices()[:1], time.perf_counter(), **kw)
