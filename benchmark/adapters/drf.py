"""What a finished DRF job hands to the comparison: the forest it built,
as plain host arrays in the data's own units, node by node.

The program keeps a tree as complete levels (``models/tree.py`` ``Tree``)
and, past them, as node tables a level (``models/frontier.py``
``DeepTree``), both in bin space beside the bin edges it chose. This
adapter is the only file that touches those objects. It writes every
tree as ONE list of its real nodes in level order — whatever layout held
them — and each split as a raw-value rule (numeric: go left iff
``x < value``; categorical: go left iff the level code's bit is set in
``left_words``), so the reference needs nothing the program made but the
model itself:

``trees["t<i>"]``: ``level``, ``path`` (the node's position in its
complete level: its heap id is ``2^level + path``), ``is_split``,
``feat``, ``cat_split``, ``value``, ``na_left``, ``left_words``
``[n, W]``, ``child`` (list index of the LEFT child, the right one next
to it; -1 at a leaf), ``leaf`` (the node's own value) and ``leaf_rows``
(its training weight; a leaf's are compared).
"""

from __future__ import annotations

import numpy as np

# a program without the frontier regime cannot run this configuration —
# it cuts every tree short of the stated depth — and says so here, at
# once, before any data is made
from h2o3_tpu.models import frontier
from h2o3_tpu.models.tree import Tree

METRICS = ("logloss", "AUC", "MSE")


def _levels(grown):
    """Per tree, per level: dict of the level's slot arrays plus
    ``live`` — from either layout. Leaf values of a node that stopped in
    the complete part sit where its rows went: leftmost below it."""
    split_names = ("feat", "thresh", "na_left", "is_split", "cat_split",
                   "left_words")
    if not isinstance(grown, frontier.DeepTree):
        top = {n: np.asarray(getattr(grown, n)) for n in Tree._fields}
        deep, K = None, top["feat"].shape[1]
        bottom_v, bottom_w = top["leaf"], top["leaf_w"]       # [T, 2^K]
    else:
        top = {n: np.asarray(getattr(grown.top, n)) for n in split_names}
        deep = {n: np.asarray(a) for n, a in grown.deep._asdict().items()}
        K = top["feat"].shape[1]
        bottom_v, bottom_w = deep["value"][:, 0], deep["weight"][:, 0]
    n_trees = top["feat"].shape[0]
    out = []
    for t in range(n_trees):
        levels = []
        alive = np.ones(1, bool)
        for d in range(K):
            L = 2 ** d
            lev = {n: top[n][t, d, :L] for n in split_names}
            lev["live"] = alive
            lev["path"] = np.arange(L, dtype=np.int32)
            lev["child"] = 2 * lev["path"]
            at = lev["path"].astype(np.int64) << (K - d)
            lev["value"], lev["weight"] = bottom_v[t, at], bottom_w[t, at]
            levels.append(lev)
            alive = np.repeat(lev["is_split"] & alive, 2)
        if deep is None:
            L = 2 ** K
            levels.append(dict(
                live=alive, path=np.arange(L, dtype=np.int32),
                is_split=np.zeros(L, bool), value=bottom_v[t],
                weight=bottom_w[t]))
        else:
            for j in range(deep["feat"].shape[1]):
                lev = {n: a[t, j] for n, a in deep.items()}
                lev["live"] = lev["path"] >= 0
                if j == 0:
                    # level K's slots are the complete level's: only
                    # those whose parent split are nodes of the tree
                    lev["live"] = np.zeros_like(lev["live"])
                    lev["live"][: alive.shape[0]] = alive
                levels.append(lev)
        out.append(levels)
    return out


def read_outputs(model) -> dict:
    bm = model.bm
    edges = np.asarray(bm.edges, np.float32)        # [F, B-2], +inf padded
    edges = np.concatenate(
        [edges, np.full((edges.shape[0], 2), np.inf, np.float32)], axis=1)
    trees = {}
    for t, levels in enumerate(_levels(model.grown)):
        # list index of every live slot, level by level
        counts = [int(lev["live"].sum()) for lev in levels]
        starts = np.concatenate([[0], np.cumsum(counts)])
        index = []
        for lev, s in zip(levels, starts):
            idx = np.full(lev["live"].shape[0], -1, np.int64)
            idx[lev["live"]] = s + np.arange(int(lev["live"].sum()))
            index.append(idx)
        n = int(starts[-1])
        W = levels[0]["left_words"].shape[-1]
        node = dict(level=np.zeros(n, np.int8), path=np.zeros(n, np.int32),
                    is_split=np.zeros(n, bool), feat=np.zeros(n, np.int16),
                    cat_split=np.zeros(n, bool),
                    value=np.full(n, np.inf, np.float32),
                    na_left=np.zeros(n, bool),
                    left_words=np.zeros((n, W), np.uint32),
                    child=np.full(n, -1, np.int32),
                    leaf=np.zeros(n, np.float32),
                    leaf_rows=np.zeros(n, np.float64))
        for d, (lev, s) in enumerate(zip(levels, starts)):
            live = lev["live"]
            sl = slice(int(s), int(s) + int(live.sum()))
            node["level"][sl] = d
            node["path"][sl] = lev["path"][live]
            node["leaf"][sl] = lev["value"][live]
            node["leaf_rows"][sl] = lev["weight"][live]
            sp = lev["is_split"][live]
            node["is_split"][sl] = sp
            if not sp.any():
                continue
            f = lev["feat"][live]
            th = np.clip(lev["thresh"][live], 0, edges.shape[1] - 1)
            # bin <= t  <=>  (#edges <= x) <= t  <=>  x < edges[t]
            node["value"][sl] = np.where(sp, edges[f, th], np.inf)
            node["feat"][sl] = f
            node["cat_split"][sl] = lev["cat_split"][live] & sp
            node["na_left"][sl] = lev["na_left"][live]
            node["left_words"][sl] = lev["left_words"][live]
            kids = index[d + 1][np.where(sp, lev["child"][live], 0)]
            node["child"][sl] = np.where(sp, kids, -1)
        trees[f"t{t}"] = node
    tm = model.training_metrics
    p = model.params
    return {
        "trees": trees,
        "names": list(bm.names),
        "rows_padded": int(bm.bins.shape[0]),
        "seed": int(p["seed"]),
        "metrics": {k: float(tm[k]) for k in METRICS},
    }
