"""Exact area under the ROC curve, ties at half weight, on the host."""

from __future__ import annotations

import numpy as np


def auc(score: np.ndarray, y: np.ndarray) -> float:
    """P(score of a positive > score of a negative) + half P(equal),
    over all pairs: each class sorted once, each positive placed among
    the negatives by bisection."""
    y = np.asarray(y).astype(bool)
    pos, neg = np.sort(score[y]), np.sort(score[~y])
    if not pos.size or not neg.size:
        return float("nan")
    below = np.searchsorted(neg, pos, side="left")
    upto = np.searchsorted(neg, pos, side="right")
    return float((below.sum(dtype=np.float64)
                  + 0.5 * (upto - below).sum(dtype=np.float64))
                 / (float(pos.size) * float(neg.size)))
