"""The comparisons that decide ``correct``: numbers and their limits.

Training reads three numbers against the float32 reference over the
first steps (the same rows, the same seed); the limit file of a cell
says which are compared:

- ``first_loss_gap``: the relative gap of the first step's loss (the
  later steps' losses swing with the signs of near-zero gradient
  elements, which Adam's first steps turn into whole learning-rate
  steps, so they are recorded and not compared);
- ``grad_gap``: the worst leaf of the first gradient as the optimizer
  takes it (after clipping);
- ``delta_gap``: the worst leaf of the parameters' change over the
  checked steps.

A leaf's gap is ``|norm_program - norm_reference|`` over the larger of
the reference's norm of that leaf and the median leaf's. Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone under Adam and are left out of ``delta_gap``.

Serving compares ``served_gap``: the widest gap by which a served
token's float32 reference logit lies below the reference's best logit
at that position.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Optional, Set

QUIET_GRAD = 1e-3      # of the median leaf's reference gradient


def leaf_gap(program: Dict[str, float], reference: Dict[str, float],
             leaves: Optional[Iterable[str]] = None) -> float:
    names = list(leaves if leaves is not None else reference)
    med = statistics.median(reference[n] for n in names)
    worst = 0.0
    for n in names:
        ref = reference[n]
        got = program.get(n, 0.0)
        if not math.isfinite(got):
            return math.inf
        worst = max(worst, abs(got - ref) / max(ref, med))
    return worst


def quiet_leaves(ref_grad: Dict[str, float]) -> Set[str]:
    med = statistics.median(ref_grad.values())
    return {n for n, g in ref_grad.items() if g < QUIET_GRAD * med}


def train_numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """``program`` and ``reference`` each hold ``losses`` (per checked
    step), ``first_grad`` and ``delta`` (leaf name -> norm)."""
    pl, rl = program["losses"][0], reference["losses"][0]
    moving = set(reference["delta"]) - quiet_leaves(reference["first_grad"])
    return {
        "first_loss_gap": (abs(pl - rl) / abs(rl) if math.isfinite(pl)
                           else math.inf),
        "grad_gap": leaf_gap(program["first_grad"],
                             reference["first_grad"]),
        "delta_gap": leaf_gap(program["delta"], reference["delta"],
                              sorted(moving)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit. A number with no limit is
    a reading only (see the limit file for why); a limit for a number
    the run did not produce is an error."""
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"no reading for the limits {sorted(missing)}")
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in limits.items()}


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def judge_stand_ins(readings: Dict[str, Dict[str, float]],
                    names: Iterable[str], limits: Optional[Dict[str, float]]
                    ) -> Dict[str, Dict]:
    """For each control or planted fault put in the program's place, its
    numbers judged against the cell's limits as the program's are, and
    whether it would come out ``correct`` (it must not)."""
    out = {}
    for name in names:
        checks = judge(readings[name], limits) if limits else {}
        out[name] = {"correct": bool(checks) and passes(checks),
                     "checks": checks}
    return out
