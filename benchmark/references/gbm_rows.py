"""The plain reference of ``references/gbm.py`` for a frame whose rows do
not fit one chip in float32: the same functions and the same arithmetic,
with each feature's rows held on device 0 in the narrowest dtype that
holds every value exactly.

``gbm.grow`` keeps every feature on the device twice, as float32 values
and as int32 candidate bins: 80 B a row beside 32 B of row state, about
14.4 GB at the peak for 116M rows — all of one chip's memory, for a
reference that runs after the program has let go of it. Here a feature
whose values are whole numbers in range is held as int8 / int16 (else
float32) and its candidate bins as uint8 where they fit (else int32):
30 B a row at the airlines widths. ``gbm._level_pass`` and its siblings
promote them where they compare and add (an int16 enters a float32 sum
exactly, a uint8 bin is compared as the int32 it is), so every number is
the one ``gbm.check`` computes: ``benchmark/tests/test_gbm_mesh_cell.py``
holds the two equal.

It imports ``gbm.py``'s functions and repeats only its follow loop over
trees and levels (``check``); the lower-precision control is ``gbm.py``'s.
Nothing of the program, no collective: one device, as any plain
reference.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark.references import gbm
from benchmark.references.ranking import auc

NAMES = gbm.NAMES
control = gbm.control


def narrow(v: np.ndarray, npad: int):
    """``v`` zero-padded to ``npad`` rows in the narrowest of int8 / int16
    / float32 that holds every value exactly, on the device."""
    dt = np.float32
    if v.size and v.dtype.kind in "iub":
        lo, hi = int(v.min()), int(v.max())
        dt = next((t for t in (np.int8, np.int16)
                   if np.iinfo(t).min <= lo and hi <= np.iinfo(t).max),
                  np.float32)
    out = np.zeros((npad,), dt)
    out[:v.shape[0]] = v
    return jnp.asarray(out)


def check(data: dict, outputs: dict, params: dict,
          block: int = 131072) -> dict:
    """``gbm.check``: the numbers compared for one finished job."""
    p, follow = dict(params), outputs
    cols, resp = data["columns"], data["response"]
    names = [n for n in cols if n != resp]
    if list(follow["names"]) != names:
        raise ValueError(f"model features {follow['names']} != {names}")
    n = len(cols[resp])
    for name in cols:
        if cols[name].dtype.kind == "f" and np.isnan(cols[name]).any():
            raise ValueError(f"reference: missing values in {name!r}")
    is_cat = np.array([nm in data["domains"] for nm in names])
    nlevels = np.array([len(data["domains"][nm]) if c else 0
                        for nm, c in zip(names, is_cat)])
    cuts = [np.zeros((0,), np.float32) if c
            else gbm.quantile_cuts(cols[nm], int(p["nbins"]))
            for nm, c in zip(names, is_cat)]
    B = int(max([len(c) + 1 for c in cuts] + list(nlevels)))
    B = -(-B // 128) * 128
    C = int(max(nlevels.max(), 1))
    D, T = int(p["max_depth"]), int(follow["feat"].shape[0])
    LN, nleaf = 2 ** max(D - 1, 0), 2 ** D
    block = min(block, -(-n // 1024) * 1024)
    npad = -(-n // block) * block
    F = len(names)

    X = tuple(narrow(cols[nm], npad) for nm in names)
    bin_dt = jnp.uint8 if B <= 256 else jnp.int32
    RB = tuple(gbm._candidate_bins(X[f], jnp.asarray(cuts[f]),
                                   is_cat=bool(is_cat[f])).astype(bin_dt)
               for f in range(F))
    y = narrow(cols[resp], npad).astype(jnp.float32)
    w = jnp.asarray(np.arange(npad) < n, jnp.float32)

    mean_y = float(np.sum(cols[resp], dtype=np.float64)) / n
    f0_ref = float(np.log(mean_y / (1.0 - mean_y)))
    margin = jnp.where(w > 0, jnp.float32(follow["f0"]), 0.0)
    gain_gap = leaf_gap = rows_gap = 0.0
    keys = ("feat", "is_split", "cat_split", "value", "left_set")
    for t in range(T):
        stats = gbm._row_stats(margin, y, w)
        nid = jnp.zeros((npad,), jnp.int32)
        for d in range(D):
            tab = {k: np.asarray(follow[k][t, d]) for k in keys}
            ls = np.zeros((LN, C), bool)
            ls[:, : tab["left_set"].shape[1]] = tab["left_set"][:, :C]
            hist, left, nid = gbm._level_pass(
                X, RB, nid, stats, jnp.asarray(tab["feat"], jnp.int32),
                jnp.asarray(tab["is_split"]), jnp.asarray(tab["cat_split"]),
                jnp.asarray(tab["value"], jnp.float32), jnp.asarray(ls),
                block=block, B=B, bf16=False)
            hist = np.asarray(hist, np.float64).reshape(F, LN, 3, B) \
                .transpose(0, 2, 1, 3)                    # [F, 3, LN, B]
            best = gbm._best_splits(hist, cuts, is_cat, nlevels, 2 ** d, p)
            left = np.asarray(left, np.float64).reshape(LN, 3)
            tot = hist[0].sum(axis=2)                     # [3, LN]
            for l in range(2 ** d):
                ref_gain, msi = best[l][0], p["min_split_improvement"]
                if tot[0, l] <= 0 or ref_gain <= msi:
                    continue
                got = gbm._gain(*left[l], *tot[:, l], p) \
                    if tab["is_split"][l] else msi
                gain_gap = max(gain_gap, float((ref_gain - got) / ref_gain))
        sums = np.asarray(gbm._leaf_sums(nid, stats, block=block,
                                         nleaf=nleaf, bf16=False),
                          np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            ref_leaf = np.where(
                sums[:, 0] > 0,
                -p["learn_rate"] * sums[:, 1]
                / (sums[:, 2] + p["reg_lambda"]), 0.0)
        leaf = np.asarray(follow["leaf"][t], np.float64)
        live = sums[:, 0] > 0
        scale = np.maximum(np.abs(ref_leaf),
                           np.median(np.abs(ref_leaf[live])))
        leaf_gap = max(leaf_gap, float(np.max(
            np.abs(leaf - ref_leaf)[live] / scale[live])))
        rows_gap = max(rows_gap, float(np.max(
            np.abs(follow["leaf_rows"][t] - sums[:, 0])) / n))
        margin = gbm._add_leaves(margin, nid, jnp.asarray(leaf, jnp.float32),
                                 block=block)

    tot, ll, se = np.asarray(gbm._metrics(margin, y, w, block=block),
                             np.float64).sum(axis=1)
    got = follow["metrics"]
    logloss = ll / tot
    return {
        "f0_gap": abs(follow["f0"] - f0_ref),
        "gain_gap": gain_gap,
        "leaf_gap": leaf_gap,
        "leaf_rows_gap": rows_gap,
        "logloss_gap": abs(got["logloss"] - logloss) / logloss,
        "auc_gap": abs(got["AUC"] - auc(np.asarray(margin)[:n], cols[resp])),
    }
