"""Plain reference for the histogram GBM the configuration states.

Binomial gradient boosting, level-wise, depth ``max_depth``: per tree
``g = sigmoid(f) - y``, ``h = p (1 - p)``; per level and node a
histogram of ``{rows, g, h}`` over each feature's candidate bins; the
split with the largest Newton gain
``GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)`` whose children both keep
``min_rows`` rows and whose gain exceeds ``min_split_improvement``;
leaves ``-learn_rate * G / (H + lam)``; ``f0 = logit(mean y)``.
Candidates: numeric features offer ``nbins`` global quantile cuts, each
at the midpoint between two adjacent distinct values (from ALL rows);
categorical features offer every prefix of their levels ordered by
Newton value (the subset split of DTree.findBestSplitPoint).

Straightforward ``jax.numpy`` in float32 with every product at
``HIGHEST`` precision, computed in row blocks so that the same code fits
a chip at the timed size; sums over rows are carried in float32 per
block and the small per-node arithmetic runs in float64 on the host. It
imports nothing of the program. The data has no missing values and the
reference refuses any.

``grow`` either FOLLOWS a model (teacher forcing, as a served model's
tokens are followed: at every node it routes rows by the model's own
split, and reads how far that split's gain lies below the reference's
best, then how far the model's leaves and final metrics lie from its
own) or, given none, grows its own — which, with ``bf16=True`` (row
statistics rounded to bfloat16, one-pass products), is the
lower-precision control.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.ranking import auc

HI = jax.lax.Precision.HIGHEST
BIG = 1e30                      # stands for +inf inside one-hot products
NAMES = ("f0_gap", "gain_gap", "leaf_gap", "leaf_rows_gap",
         "logloss_gap", "auc_gap")


def quantile_cuts(col: np.ndarray, nbins: int) -> np.ndarray:
    """At most ``nbins - 1`` cuts: for q = k/nbins, the first distinct
    value whose cumulative share reaches q, cut at the midpoint to the
    next distinct value."""
    if col.dtype.kind in "iu":
        lo = int(col.min())
        cnt = np.bincount((col - lo).astype(np.int64))
        u = np.nonzero(cnt)[0] + lo
        cnt = cnt[cnt > 0]
    else:
        u, cnt = np.unique(col, return_counts=True)
    if u.size < 2:
        return np.zeros((0,), np.float32)
    cdf = np.cumsum(cnt, dtype=np.float64)
    cdf /= cdf[-1]
    idx = np.searchsorted(cdf, np.linspace(0.0, 1.0, nbins + 1)[1:-1],
                          side="left")
    idx = idx[idx < u.size - 1]
    mids = (u[idx].astype(np.float64) + u[idx + 1]) * 0.5
    return np.unique(mids.astype(np.float32))


@partial(jax.jit, static_argnames=("block", "B", "bf16"))
def _level_pass(X, RB, nid, stats, feat, is_split, cat_split, value,
                left_set, *, block: int, B: int, bf16: bool):
    """One pass over the rows at one tree level (``X``, ``RB`` and
    ``stats`` are tuples of ``[N]`` rows: a ``[10, N]`` array would be
    padded to 16 sublanes on the chip). Returns the histogram
    ``[F, 3*LN, B]`` of ``stats`` by (node, candidate bin), the
    ``[3*LN]`` sums over the rows the given splits send left, and the
    next level's node ids."""
    F, N = len(X), nid.shape[0]
    LN, C = left_set.shape
    tab = jnp.stack([feat.astype(jnp.float32),
                     is_split.astype(jnp.float32),
                     cat_split.astype(jnp.float32),
                     jnp.clip(value, -BIG, BIG)], axis=1)        # [LN, 4]
    sets = left_set.astype(jnp.float32)
    nodes = jnp.arange(LN, dtype=jnp.int32)
    bins = jnp.arange(B, dtype=jnp.int32)
    codes = jnp.arange(C, dtype=jnp.int32)
    dt = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else HI

    def body(carry, i):
        hist, left = carry
        at = i * block
        def cut(v):
            return jax.lax.dynamic_slice(v, (at,), (block,))

        xb, rb = [cut(v) for v in X], [cut(v) for v in RB]
        nb = cut(nid)
        sb = jnp.stack([cut(v) for v in stats], axis=1)          # [R, 3]
        noh = (nb[:, None] == nodes[None, :]).astype(jnp.float32)
        row = jnp.dot(noh, tab, precision=HI)                    # [R, 4]
        f_r = jnp.round(row[:, 0]).astype(jnp.int32)
        x_r = jnp.zeros((block,), jnp.float32)
        for f in range(F):
            x_r = x_r + jnp.where(f_r == f, xb[f], 0.0)
        in_set = jnp.sum(
            jnp.dot(noh, sets, precision=HI)
            * (x_r.astype(jnp.int32)[:, None] == codes[None, :]),
            axis=1) > 0.5
        go = jnp.where(row[:, 2] > 0.5, in_set, x_r < row[:, 3])
        go = jnp.where(row[:, 1] > 0.5, go, True)
        ns = (noh[:, :, None] * sb[:, None, :]).reshape(block, 3 * LN)
        ns = ns.astype(dt)
        h_blk = jnp.stack([
            jnp.dot(ns.T, (rb[f][:, None] == bins[None, :]).astype(dt),
                    precision=prec, preferred_element_type=jnp.float32)
            for f in range(F)])
        l_blk = jnp.dot(ns.T, go.astype(dt), precision=prec,
                        preferred_element_type=jnp.float32)
        nxt = 2 * nb + jnp.where(go, 0, 1)
        return (hist + h_blk, left + l_blk), nxt

    init = (jnp.zeros((F, 3 * LN, B), jnp.float32),
            jnp.zeros((3 * LN,), jnp.float32))
    (hist, left), nxt = jax.lax.scan(body, init, jnp.arange(N // block))
    return hist, left, nxt.reshape(N)


@partial(jax.jit, static_argnames=("block", "nleaf", "bf16"))
def _leaf_sums(nid, stats, *, block: int, nleaf: int, bf16: bool):
    """``[nleaf, 3]`` sums of ``stats`` by terminal node."""
    N = nid.shape[0]
    leaves = jnp.arange(nleaf, dtype=jnp.int32)
    dt = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else HI

    def body(acc, i):
        nb = jax.lax.dynamic_slice(nid, (i * block,), (block,))
        sb = jnp.stack([jax.lax.dynamic_slice(v, (i * block,), (block,))
                        for v in stats], axis=1)
        noh = (nb[:, None] == leaves[None, :]).astype(dt)
        return acc + jnp.dot(noh.T, sb.astype(dt), precision=prec,
                             preferred_element_type=jnp.float32), None

    out, _ = jax.lax.scan(body, jnp.zeros((nleaf, 3), jnp.float32),
                          jnp.arange(N // block))
    return out


@partial(jax.jit, static_argnames=("block",))
def _add_leaves(margin, nid, leaf, *, block: int):
    """``margin + leaf[nid]`` by one-hot products (no gather)."""
    N = nid.shape[0]
    leaves = jnp.arange(leaf.shape[0], dtype=jnp.int32)

    def body(_, i):
        nb = jax.lax.dynamic_slice(nid, (i * block,), (block,))
        noh = (nb[:, None] == leaves[None, :]).astype(jnp.float32)
        return None, jnp.dot(noh, leaf, precision=HI)

    _, add = jax.lax.scan(body, None, jnp.arange(N // block))
    return margin + add.reshape(N)


@partial(jax.jit, static_argnames=("is_cat",))
def _candidate_bins(x, cuts, *, is_cat: bool):
    """A feature's candidate bin per row: the level code, or the number
    of cuts at or below the value."""
    if is_cat:
        return x.astype(jnp.int32)
    return jnp.sum(x[None, :] >= cuts[:, None], axis=0, dtype=jnp.int32)


@jax.jit
def _row_stats(margin, y, w):
    p = 1.0 / (1.0 + jnp.exp(-margin))
    return w, w * (p - y), w * p * (1.0 - p)


@partial(jax.jit, static_argnames=("block",))
def _metrics(margin, y, w, *, block: int):
    """Per-block ``[rows, logloss sum, squared-error sum]``, added up in
    float64 on the host."""
    p = 1.0 / (1.0 + jnp.exp(-margin))
    ll = -(y * jnp.log(p) + (1.0 - y) * jnp.log1p(-p))
    return jnp.stack([w, w * ll, w * (p - y) ** 2]) \
        .reshape(3, -1, block).sum(axis=2)


def _best_splits(hist, cuts, is_cat, nlevels, n_nodes, p):
    """Per node the best candidate split: ``(gain, feature, value,
    left_set)``, float64 on the host. ``hist [F, 3, LN, B]``."""
    lam, min_rows = p["reg_lambda"], p["min_rows"]
    F = hist.shape[0]
    C = int(max(nlevels.max(), 1)) if len(nlevels) else 1
    out = []
    for l in range(n_nodes):
        best = (-np.inf, 0, np.inf, np.zeros(C, bool))
        for f in range(F):
            nb = int(nlevels[f]) if is_cat[f] else len(cuts[f]) + 1
            w, g, h = (hist[f, k, l, :nb] for k in range(3))
            order = np.arange(nb)
            if is_cat[f]:
                live = np.nonzero(w > 0)[0]
                order = live[np.argsort(-g[live] / (h[live] + lam + 1e-300),
                                        kind="stable")]
            if order.size < 2:
                continue
            cw, cg, ch = (np.cumsum(v[order])[:-1] for v in (w, g, h))
            tw, tg, th = w.sum(), g.sum(), h.sum()
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = (cg * cg / (ch + lam)
                        + (tg - cg) ** 2 / (th - ch + lam)
                        - tg * tg / (th + lam))
            gain = np.where((cw >= min_rows) & (tw - cw >= min_rows)
                            & np.isfinite(gain), gain, -np.inf)
            t = int(np.argmax(gain))
            if gain[t] > best[0]:
                if is_cat[f]:
                    ls = np.zeros(C, bool)
                    ls[order[: t + 1]] = True
                    best = (float(gain[t]), f, np.inf, ls)
                else:
                    best = (float(gain[t]), f, float(cuts[f][t]),
                            np.zeros(C, bool))
        out.append(best)
    return out


def _gain(wl, gl, hl, tw, tg, th, p):
    lam = p["reg_lambda"]
    if wl < p["min_rows"] or tw - wl < p["min_rows"]:
        return -np.inf
    return (gl * gl / (hl + lam) + (tg - gl) ** 2 / (th - hl + lam)
            - tg * tg / (th + lam))


def grow(data: dict, params: dict, follow: dict | None = None, *,
         bf16: bool = False, block: int = 131072):
    """Grow ``params["ntrees"]`` trees (``follow=None``) or follow the
    model ``follow`` (the adapter's dict). Returns ``(numbers, model)``:
    the numbers compared when following, the grown model otherwise, each
    ``None`` in the other mode."""
    p = dict(params)
    cols, resp = data["columns"], data["response"]
    names = [n for n in cols if n != resp]
    if follow is not None and list(follow["names"]) != names:
        raise ValueError(f"model features {follow['names']} != {names}")
    n = len(cols[resp])
    for name in cols:
        if cols[name].dtype.kind == "f" and np.isnan(cols[name]).any():
            raise ValueError(f"reference: missing values in {name!r}")
    is_cat = np.array([nm in data["domains"] for nm in names])
    nlevels = np.array([len(data["domains"][nm]) if c else 0
                        for nm, c in zip(names, is_cat)])
    cuts = [np.zeros((0,), np.float32) if c
            else quantile_cuts(cols[nm], int(p["nbins"]))
            for nm, c in zip(names, is_cat)]
    B = int(max([len(c) + 1 for c in cuts] + list(nlevels)))
    B = -(-B // 128) * 128
    C = int(max(nlevels.max(), 1))
    D = int(p["max_depth"])
    T = int(follow["feat"].shape[0] if follow is not None else p["ntrees"])
    LN, nleaf = 2 ** max(D - 1, 0), 2 ** D
    block = min(block, -(-n // 1024) * 1024)
    npad = -(-n // block) * block
    F = len(names)

    def padded(v, dtype):
        out = np.zeros((npad,), dtype)
        out[:n] = v
        return out

    X = tuple(jnp.asarray(padded(cols[nm], np.float32)) for nm in names)
    RB = tuple(_candidate_bins(X[f], jnp.asarray(cuts[f]),
                               is_cat=bool(is_cat[f])) for f in range(F))
    y = jnp.asarray(padded(cols[resp], np.float32))
    w = jnp.asarray(padded(np.ones(n, np.float32), np.float32))

    mean_y = float(np.sum(cols[resp], dtype=np.float64)) / n
    f0_ref = float(np.log(mean_y / (1.0 - mean_y)))
    if follow is not None:
        f0 = follow["f0"]
    elif bf16:
        import ml_dtypes
        b16 = ml_dtypes.bfloat16
        f0 = float(np.log(b16(b16(mean_y) / b16(1.0 - mean_y))).astype(b16))
    else:
        f0 = f0_ref
    margin = jnp.where(w > 0, jnp.float32(f0), 0.0)
    gain_gap = leaf_gap = rows_gap = 0.0
    model = {k: [] for k in ("feat", "is_split", "cat_split", "value",
                             "left_set", "leaf", "leaf_rows")}

    for t in range(T):
        stats = _row_stats(margin, y, w)
        nid = jnp.zeros((npad,), jnp.int32)
        lev = {k: [] for k in ("feat", "is_split", "cat_split", "value",
                               "left_set")}
        for d in range(D):
            L = 2 ** d
            if follow is not None:
                tab = {k: np.asarray(follow[k][t, d]) for k in lev}
            else:
                # own splits are not known before the histogram: pass 1
                # with no split, then route in pass 2
                tab = {"feat": np.zeros(LN, np.int32),
                       "is_split": np.zeros(LN, bool),
                       "cat_split": np.zeros(LN, bool),
                       "value": np.full(LN, np.inf, np.float32),
                       "left_set": np.zeros((LN, C), bool)}

            def run(tab):
                ls = np.zeros((LN, C), bool)
                ls[:, : tab["left_set"].shape[1]] = \
                    tab["left_set"][:, :C]
                return _level_pass(
                    X, RB, nid, stats, jnp.asarray(tab["feat"], jnp.int32),
                    jnp.asarray(tab["is_split"]),
                    jnp.asarray(tab["cat_split"]),
                    jnp.asarray(tab["value"], jnp.float32),
                    jnp.asarray(ls), block=block, B=B, bf16=bf16)

            hist, left, nxt = run(tab)
            hist = np.asarray(hist, np.float64).reshape(F, LN, 3, B) \
                .transpose(0, 2, 1, 3)                    # [F, 3, LN, B]
            best = _best_splits(hist, cuts, is_cat, nlevels, L, p)
            if follow is not None:
                left = np.asarray(left, np.float64).reshape(LN, 3)
                tot = hist[0].sum(axis=2)                 # [3, LN]
                for l in range(L):
                    if tot[0, l] <= 0:
                        continue
                    ref_gain, msi = best[l][0], p["min_split_improvement"]
                    if ref_gain <= msi:
                        continue            # nothing to gain at this node
                    got = _gain(*left[l], *tot[:, l], p) \
                        if tab["is_split"][l] else msi
                    gap = (ref_gain - got) / ref_gain
                    gain_gap = max(gain_gap, float(gap))
            else:
                for l in range(L):
                    g_l, f_l, v_l, s_l = best[l]
                    sp = g_l > p["min_split_improvement"]
                    tab["is_split"][l] = sp
                    tab["feat"][l] = f_l if sp else 0
                    tab["cat_split"][l] = sp and bool(is_cat[f_l])
                    tab["value"][l] = v_l if sp else np.inf
                    tab["left_set"][l] = s_l & sp
                _, _, nxt = run(tab)
            for k in lev:
                lev[k].append(np.array(tab[k]))
            nid = nxt
        sums = np.asarray(_leaf_sums(nid, stats, block=block, nleaf=nleaf,
                                     bf16=bf16), np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            ref_leaf = np.where(
                sums[:, 0] > 0,
                -p["learn_rate"] * sums[:, 1]
                / (sums[:, 2] + p["reg_lambda"]), 0.0)
        if follow is not None:
            leaf = np.asarray(follow["leaf"][t], np.float64)
            live = sums[:, 0] > 0
            scale = np.maximum(np.abs(ref_leaf),
                               np.median(np.abs(ref_leaf[live])))
            leaf_gap = max(leaf_gap, float(np.max(
                np.abs(leaf - ref_leaf)[live] / scale[live])))
            rows_gap = max(rows_gap, float(np.max(
                np.abs(follow["leaf_rows"][t] - sums[:, 0])) / n))
        else:
            leaf = ref_leaf
            for k in lev:
                model[k].append(np.stack(lev[k]))
            model["leaf"].append(leaf.astype(np.float32))
            model["leaf_rows"].append(sums[:, 0])
        margin = _add_leaves(margin, nid, jnp.asarray(leaf, jnp.float32),
                             block=block)

    tot, ll, se = np.asarray(_metrics(margin, y, w, block=block),
                             np.float64).sum(axis=1)
    metrics = {"logloss": ll / tot, "MSE": se / tot,
               "AUC": auc(np.asarray(margin)[:n], cols[resp])}
    if follow is None:
        out = {k: np.stack(v) for k, v in model.items()}
        out.update(f0=f0, names=names, metrics=metrics,
                   na_left=np.zeros_like(out["is_split"]))
        return None, out
    got = follow["metrics"]
    numbers = {
        "f0_gap": abs(follow["f0"] - f0_ref),
        "gain_gap": gain_gap,
        "leaf_gap": leaf_gap,
        "leaf_rows_gap": rows_gap,
        "logloss_gap": abs(got["logloss"] - metrics["logloss"])
        / metrics["logloss"],
        "auc_gap": abs(got["AUC"] - metrics["AUC"]),
    }
    return numbers, None


def check(data: dict, outputs: dict, params: dict) -> dict:
    """The numbers compared for one finished job: ``{name: value}``."""
    return grow(data, params, follow=outputs)[0]


def control(data: dict, params: dict) -> dict:
    """The lower-precision control, in the adapter's format: the
    reference's own model grown with bfloat16 row statistics."""
    return grow(data, params, follow=None, bf16=True)[1]
