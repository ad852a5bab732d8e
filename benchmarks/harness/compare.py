"""The comparison that decides `correct`: each number compared beside its
limit.  Pure numpy; the drivers bring the program's readings and the
reference's."""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # a NaN must fail
        return bool(self.value <= self.limit)

    def as_dict(self) -> dict:
        return {"value": float(self.value), "limit": float(self.limit)}


def checks_from(readings: dict, limits: dict) -> list:
    """One Check per limit; a limit without a reading fails (value inf)."""
    return [Check(name, float(readings.get(name, float("inf"))), float(lim))
            for name, lim in limits.items()]


def served_logit_gap(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """By how much each served token's reference logit lies below the
    reference's best at that position; [n] >= 0."""
    ref_logits = np.asarray(ref_logits, np.float32)
    best = ref_logits.max(axis=-1)
    mine = np.take_along_axis(ref_logits, np.asarray(served)[:, None],
                              axis=-1)[:, 0]
    return best - mine


def rel_gap(got: float, ref: float) -> float:
    return abs(float(got) - float(ref)) / max(abs(float(ref)), 1e-30)


def worst_leaf_gap(got: dict, ref: dict, skip=()) -> tuple:
    """Over leaves: |norm_got - norm_ref| / max(norm_ref, median norm_ref).
    Returns (worst gap, its leaf).  `got` and `ref` map leaf -> norm."""
    keys = [k for k in ref if k not in skip]
    med = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, None
    for k in keys:
        g = abs(got[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not g <= worst:          # NaN lands here and stays
            worst, where = g, k
    return float(worst), where


def tiny_gradient_leaves(ref_grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is under `share` of the median
    leaf's: they move under Adam by round-off alone."""
    med = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < share * med}
