"""Small stand-ins for the cells' configurations and mixes, for CPU
tests: the same files with small widths, so that every key the harness
reads is the real one. The serving stand-in keeps a 256-wide model so
that its logits spread as a real model's do (its top logits are not
all within rounding of each other)."""
from __future__ import annotations

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


def harness():
    """bench/run.py as a module (its name would clash as ``run``)."""
    mod_spec = importlib.util.spec_from_file_location(
        "bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
