"""The token-weighted percentile of the benchmark's tails."""
from __future__ import annotations

from typing import Sequence


def weighted_percentile(values: Sequence[float], weights: Sequence[float],
                        q: float) -> float:
    """The smallest value whose cumulative weight reaches ``q`` percent
    of the total: the inverse of the weighted distribution function, no
    interpolation. A value of weight ``w`` counts as ``w`` samples."""
    if len(values) != len(weights) or not values:
        raise ValueError("weighted_percentile needs as many weights as "
                         "values, and at least one")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} is outside [0, 100]")
    pairs = sorted(zip(values, weights))
    total = float(sum(w for _, w in pairs))
    if total <= 0:
        raise ValueError("weighted_percentile needs a positive total "
                         "weight")
    need = q / 100.0 * total
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= need and w > 0:
            return float(v)
    return float(pairs[-1][0])

