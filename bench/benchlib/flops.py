"""Operations and bytes a dense decoder needs, from its shapes.

Model FLOPs count the work the algorithm requires, never recomputation:
a multiply-add is two operations. ``cfg`` is a configuration file's
dict (``bench/configs/<name>.json``).
"""
from __future__ import annotations

from typing import Dict, Iterable


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o and a gated
    (three-matrix) feed-forward."""
    d, dh = cfg["d_model"], head_dim(cfg)
    h, hkv, ff = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    return attn + 3 * d * ff


def matmul_params(cfg: Dict) -> int:
    """Non-embedding matmul weights plus the lm head (tied or not: the
    head is a matmul either way; the embedding lookup is not)."""
    return (cfg["num_layers"] * layer_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["d_model"])


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight, plus the attention
    score and value products at full (unmasked) length, 12 x layers x
    d_model x seq (the PaLM convention)."""
    return (6.0 * matmul_params(cfg)
            + 12.0 * cfg["num_layers"] * cfg["d_model"] * seq_len)


def attention_flops(cfg: Dict, context: int) -> float:
    """One query against ``context`` keys, all layers: q.k and p.v."""
    return 4.0 * cfg["num_layers"] * cfg["num_heads"] * head_dim(cfg) \
        * context


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    """One decode step: each active sequence's new token, with the
    context it attends to (cached tokens plus itself)."""
    contexts = list(contexts)
    return (2.0 * matmul_params(cfg) * len(contexts)
            + sum(attention_flops(cfg, c) for c in contexts))


def prefill_flops(cfg: Dict, prompt_lens: Iterable[int]) -> float:
    """Causal prefill of real prompt tokens: position p attends to p + 1
    keys. Bucket padding is not useful work and is not counted."""
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * matmul_params(cfg) * n
        total += attention_flops(cfg, 1) * n * (n + 1) / 2.0
    return total


def paged_attention_cost(cfg: Dict, contexts: Iterable[int],
                         kv_bytes: int = 2, act_bytes: int = 2
                         ) -> Dict[str, float]:
    """Bytes and FLOPs one layer's paged decode kernel call must move
    and do: each active sequence's K and V up to its context, its query
    in and its output out."""
    dh, h, hkv = head_dim(cfg), cfg["num_heads"], cfg["num_kv_heads"]
    contexts = list(contexts)
    kv = sum(2 * c * hkv * dh * kv_bytes for c in contexts)
    qo = len(contexts) * 2 * h * dh * act_bytes
    flops = sum(4.0 * h * dh * c for c in contexts)
    return {"bytes": float(kv + qo), "flops": flops}
