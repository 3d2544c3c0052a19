"""The comparison that decides `correct`: numbers worked out from the
timed path's readings and the plain reference's, each held to the limit
that the cell's file under `limits/` gives it.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

# a leaf whose gradient in the reference is under this share of the
# median leaf's moves under Adam by round-off alone: left out of the
# parameters' change
DEAD_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(program, reference, keep=None) -> float:
    """Largest gap between the two norms of a leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    scale = np.maximum(reference, np.median(reference))
    gaps = np.abs(program - reference) / scale
    if keep is not None:
        gaps = gaps[keep]
    return float(np.max(gaps))


def train_numbers(program: dict, reference: dict) -> Dict[str, float]:
    """Both hold `losses` (three steps), `grad_norms` (the first clipped
    gradient, by leaf) and `change_norms` (each leaf's move over the
    three steps). A control read over fewer steps holds fewer losses and
    no change: those numbers are then left out."""
    ref_grad = np.asarray(reference["grad_norms"], np.float64)
    alive = ref_grad >= DEAD_GRADIENT_SHARE * np.median(ref_grad)
    out = {f"loss{i + 1}_gap": abs(p - r) / abs(r) for i, (p, r) in
           enumerate(zip(program["losses"], reference["losses"]))}
    out["grad_gap"] = worst_leaf_gap(program["grad_norms"], ref_grad)
    if "change_norms" in program:
        out["change_gap"] = worst_leaf_gap(
            program["change_norms"], reference["change_norms"], alive)
    return out


def serve_numbers(answers: list, reference_logits: np.ndarray
                  ) -> Dict[str, float]:
    """`answers[i]` holds the served `ids` and `logits` (largest first);
    `reference_logits[i]` the reference's logits at that prompt's last
    position. token_gap: how far the served first token's logit lies
    under the reference's best, at worst. logit_err: the worst distance
    of a served logit from the reference's logit of the same id."""
    token_gap, logit_err = 0.0, 0.0
    for answer, ref in zip(answers, reference_logits):
        ids = np.asarray(answer["ids"], np.int64)
        token_gap = max(token_gap, float(np.max(ref) - ref[ids[0]]))
        logit_err = max(logit_err, float(np.max(np.abs(
            np.asarray(answer["logits"], np.float64) - ref[ids]))))
    return {"token_gap": token_gap, "logit_err": logit_err}


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          attempted: int, failed: int) -> dict:
    """-> {"correct": bool, "checks": {name: {"value", "limit"}}}. A
    number with no limit in the file is shown and not compared (PERF.md
    names those). Every operation has to have succeeded."""
    checks = {}
    correct = failed == 0 and attempted > 0
    for name, value in numbers.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value)
                                      and value <= limit):
            correct = False
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"the limits file names numbers this run did not "
                       f"produce: {missing}")
    checks["failed_operations"] = {"value": failed, "limit": 0}
    return {"correct": correct, "checks": checks}
