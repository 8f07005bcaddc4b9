"""What a finished GBM job hands to the comparison: the model it built,
as plain host arrays in the data's own units.

The program keeps its trees in bin space (``thresh`` is a bin id, a
categorical split a bit set over bin ids) beside the bin edges it chose.
This adapter is the only file that touches those objects; it rewrites
each split as a raw-value rule — numeric: go left iff ``x < value``;
categorical: go left iff the level code is in ``left_set`` — so the
reference needs nothing the program made but the model itself.
"""

from __future__ import annotations

import numpy as np

METRICS = ("logloss", "AUC", "MSE")


def read_outputs(model) -> dict:
    forest, bm = model.forest, model.bm
    feat = np.asarray(forest.feat)                  # [T, D, Lmax]
    thresh = np.asarray(forest.thresh)
    is_split = np.asarray(forest.is_split)
    cat_split = np.asarray(forest.cat_split)
    words = np.asarray(forest.left_words)           # [T, D, Lmax, W]
    edges = np.asarray(bm.edges, np.float32)        # [F, B-2], +inf padded
    edges = np.concatenate(
        [edges, np.full((edges.shape[0], 2), np.inf, np.float32)], axis=1)
    nlev = int(bm.nbins_total) - 1
    # bin <= t  <=>  (#edges <= x) <= t  <=>  x < edges[t]
    value = edges[feat, np.clip(thresh, 0, edges.shape[1] - 1)]
    bits = np.arange(nlev)
    left_set = ((words[..., bits // 32] >> (bits % 32).astype(np.uint32))
                & 1).astype(bool)                   # [T, D, Lmax, nlev]
    tm = model.training_metrics
    return {
        "f0": float(model.f0),
        "feat": feat.astype(np.int32),
        "is_split": is_split,
        "cat_split": cat_split & is_split,
        "value": np.where(is_split, value, np.inf).astype(np.float32),
        "left_set": left_set & cat_split[..., None],
        "na_left": np.asarray(forest.na_left),
        "leaf": np.asarray(forest.leaf, np.float32),        # [T, 2^D]
        "leaf_rows": np.asarray(forest.leaf_w, np.float64),
        "names": list(bm.names),
        "metrics": {k: float(tm[k]) for k in METRICS},
    }
