"""One generator for serving traffic, driven by a mix's parameters.

Lengths come from a clipped log-normal, but not by sampling: each block
of ``block`` requests holds the same lengths, the log-normal's quantiles
at (i + 0.5) / block, in an order drawn from the seed. So every seed
sends the same work in another order, and any prefix of whole blocks
holds the same mix. Token ids are uniform over the vocabulary.

The first block, which fills the slots at once, stands for the
sequences that hold the slots at a random moment of the steady state,
so that a window opens on the steady state and not on a cold batch:
each of its requests has an output length ``L`` drawn in proportion to
``L`` (the length-biased quantiles of the block's outputs), a share
``u`` of it already generated (the stratified values (j + 0.5) / block,
paired with the lengths by a fixed permutation), and so a prompt of the
mix's prompt plus ``round(L u)`` already generated tokens, and
``L - round(L u)`` tokens left to generate. Its slots finish and are
refilled at the steady state's rate, over contexts of the steady
state's length.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np


def quantile_lengths(spec: Dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of a log-normal with
    ``median`` and ``sigma``, rounded and clipped to [min, max]."""
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = int(round(math.exp(mu + spec["sigma"] * z)))
        out.append(min(max(x, spec["min"]), spec["max"]))
    return out


def steady_state_slots(lengths: List[int], n: int
                       ) -> List[Tuple[int, int]]:
    """``n`` (already generated, left to generate) pairs; see the module
    docstring. The same for every seed."""
    order = sorted(lengths)
    total = float(sum(order))
    cum = np.cumsum(order) / total
    biased = [order[int(np.searchsorted(cum, (i + 0.5) / n))]
              for i in range(n)]
    share = np.random.default_rng(0).permutation(n)
    out = []
    for L, j in zip(biased, share):
        done = min(int(round(L * (j + 0.5) / n)), L - 1)
        out.append((done, L - done))
    return out


def backlog(mix: Dict, vocab: int, seed: int
            ) -> List[Tuple[Tuple[int, ...], int]]:
    """(prompt token ids, tokens to generate) for every request of the
    backlog, in the order they are due (all at time 0)."""
    rng = np.random.default_rng(seed)
    block = mix["block"]
    prompts = quantile_lengths(mix["prompt_tokens"], block)
    outputs = quantile_lengths(mix["output_tokens"], block)
    fresh = [(0, o) for o in outputs]
    first = steady_state_slots(outputs, block)
    reqs = []
    for k in range(mix["backlog"] // block):
        pairs = first if k == 0 else fresh
        for p, i in zip(rng.permutation(prompts),
                        rng.permutation(len(pairs))):
            done, left = pairs[i]
            ids = rng.integers(0, vocab, int(p) + done)
            reqs.append((tuple(int(t) for t in ids), int(left)))
    return reqs


def warmup(mix: Dict, vocab: int, seed: int
           ) -> List[Tuple[Tuple[int, ...], int]]:
    """One request per prefill bucket, as long as the bucket (and the
    two tokens it generates) allow: every program shape the run uses."""
    rng = np.random.default_rng([seed, 1])
    return [(tuple(int(t) for t in rng.integers(
        0, vocab, min(b, mix["max_seq_len"] - 2))), 2)
        for b in mix["bucket_lens"]]
