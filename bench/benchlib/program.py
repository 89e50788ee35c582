"""The system under test, as a configuration file asks for it.

The only module of the benchmark that reads the program's configuration
classes: it builds the program's ``ModelConfig`` from a configuration
file and refuses a file that the program would not run as written.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

# keys of a configuration file that are fields of the program's
# ModelConfig; every one must come out as the file says
MODEL_FIELDS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
                "head_dim", "d_ff", "vocab_size", "norm", "activation",
                "rope_theta", "tie_embeddings", "param_dtype",
                "compute_dtype", "attention_impl", "remat")


def model_config(cfg: Dict[str, Any]):
    from repro.configs import base as cfgbase

    base = cfgbase.resolve(cfg["program_arch"])
    over = {k: cfg[k] for k in MODEL_FIELDS if k in cfg}
    mc = dataclasses.replace(base, name=cfg["name"], **over)
    wrong = {k: getattr(mc, k) for k in over if getattr(mc, k) != over[k]}
    # what the reference does not model must be off in the program
    if mc.logit_softcap or mc.qk_norm or mc.moe.enabled or \
            mc.mla.enabled or mc.frontend != "token":
        wrong["unmodelled"] = "softcap / qk_norm / moe / mla / frontend"
    if wrong:
        raise SystemExit(f"bench: the program would run {cfg['name']} "
                         f"with {wrong}, not as its file says")
    return mc


def seed31(seed: int) -> int:
    """The program's and the reference's PRNG seed for a run seed of
    any size (a run seed may exceed 32 bits)."""
    return int(seed) % (2 ** 31 - 1)
