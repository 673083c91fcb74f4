"""The numbers that decide ``correct``, each worked out the same way for the
program, the controls and the planted faults.

Training (per level, over the first three steps): the relative gap of each
step's loss; the gap between the program's and the reference's norm of the
first (clipped) gradient, per leaf; the same for the parameters' change over
the three steps.  A leaf's gap is measured against the reference's norm of
that same leaf, and the worst leaf counts, so a small leaf (a LayerNorm
scale, a bias) left unmoved reads 1 like a large one.  Leaves whose reference
gradient is under a thousandth of the median leaf's (nought to rounding, as
a key bias under softmax) are left out of both.  Coalescing: per leaf, the
norm of the difference over the reference's norm, worst leaf.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

GRAD_FLOOR = 1e-3


def moved(ref_grad: Dict[str, float]):
    """The leaves whose reference gradient is at least GRAD_FLOOR of the
    median leaf's."""
    med = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v >= GRAD_FLOOR * med}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    return max(abs(prog[k] - ref[k]) / ref[k] for k in keep)


def training(prog: Dict, ref: Dict, level: str) -> List[Tuple[str, float]]:
    """``prog`` / ``ref``: {"loss": [..], "grad": {leaf: norm}, "update":
    {leaf: norm}} of one level."""
    keep = moved(ref["grad"])
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    return [(f"loss_gap.{level}", float(loss)),
            (f"grad_gap.{level}", float(norm_gap(prog["grad"], ref["grad"], keep))),
            (f"update_gap.{level}", float(norm_gap(prog["update"], ref["update"], keep)))]


def coalesce_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> float:
    return float(max(np.linalg.norm((np.asarray(prog[k], np.float64) - ref[k]).ravel())
                     / max(np.linalg.norm(np.asarray(ref[k], np.float64).ravel()), 1e-30)
                     for k in ref))
