"""Vectorized best-split scan over a level's (node, feature, bin) histogram.

Reference: hex/tree/DTree.java:619-697 ``findBestSplitPoint`` — cumulative
{w, wY, wYY} over bins, gain per threshold, NA-direction choice, and the
sorted-prefix categorical subset scan.

This is the single split-scan implementation shared by BOTH tree
backends: ``models/tree.py`` calls it from the XLA level loop, and
``ops/pallas/treekernel.py`` evaluates the very same function at the
fused kernel's histogram→partition boundary. One body ⇒ the two paths
are bit-exact by construction (the interpret-mode parity contract of
tests/test_tree_kernels.py) and can never drift apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def best_splits(hist, nb, col_mask, *, min_rows, reg_lambda,
                is_cat=None, constraints=None, lo=None, hi=None,
                cat_idx=None):
    """Vectorized DTree.findBestSplitPoint over all nodes of a level.

    hist: [L, F, B, 3] of {w, g, h}; col_mask [F] (per-tree sampling) or
    [L, F] (per-node mtries, DRF). With ``constraints`` ([F] in
    {-1,0,+1}) and per-node value bounds lo/hi ([L]), splits on
    constrained features must order their (bound-clipped) child Newton
    values per the constraint direction — the monotone-constraints
    contract of the reference GBM (hex/tree/DHistogram constraints +
    hex/tree/Constraints).

    Categorical features (``is_cat`` [F] bool; pass None for an
    all-numeric scan; ``cat_idx``, where the caller knows the schema
    statically, is the tuple of their indices and confines the
    re-ordering work to them): bins are re-ordered PER NODE by their Newton value
    -g/(h+λ) and the threshold scan runs over that order, so the best
    "prefix" is the best category SUBSET — the static-shape formulation
    of the reference's bitset splits (hex/tree/DTree.java:619-697
    findBestSplitPoint sorts by prediction then scans). Returns per-node
    best (gain, feat, thresh, na_left, left_val, right_val, leftmask)
    where leftmask [L, B-1] marks the ORIGINAL bin ids going left.
    """
    lam = reg_lambda
    B = hist.shape[2]
    w, g, h = hist[..., 0], hist[..., 1], hist[..., 2]
    wv = w[:, :, : B - 1]
    gv = g[:, :, : B - 1]
    hv = h[:, :, : B - 1]
    rank = None
    if is_cat is not None:
        # per-(node, feature) bin order: Newton value ascending for cats,
        # natural bin order for numerics (identity keeps the exact
        # numeric semantics). Empty bins key to +inf so they sort AFTER
        # every populated bin: the t <= nb-2 threshold-validity mask then
        # stays correct in sorted space (populated bins occupy a prefix
        # of it).
        # The order is a STABLE ascending sort of the keys, computed
        # without a sort: bin i's position is the number of bins that
        # precede it (smaller key, or equal key and smaller id), and the
        # re-ordered statistics are one-hot sums over those positions —
        # compares and adds the VPU fuses, where a sort along a 125-wide
        # axis and the gathers after it cost 58 us a node on a v5e
        # (PERF.md §6, PR 35). Keys compare in the sort's total order
        # (-0.0 before +0.0), as integers.
        pos = jnp.arange(B - 1, dtype=jnp.int32)

        def position(wc, gc, hc):
            """[L, C, B-1] statistics of categorical columns → each
            bin's position in its node's order."""
            val = jnp.where(wc > 0, -gc / (hc + lam + 1e-10), jnp.inf)
            bits = jax.lax.bitcast_convert_type(val, jnp.int32)
            ordered = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
            ki, kj = ordered[:, :, :, None], ordered[:, :, None, :]
            before = (kj < ki) | ((kj == ki) & (pos[None, :] < pos[:, None]))
            return jnp.sum(before, axis=3, dtype=jnp.int32)

        def reorder(x, r):
            at = r[:, :, :, None] == pos[None, None, None, :]
            return jnp.sum(jnp.where(at, x[:, :, :, None], 0.0), axis=2)

        if cat_idx is None:
            rank = jnp.where(is_cat[None, :, None], position(wv, gv, hv),
                             pos[None, None, :])             # [L, F, B-1]
            wv, gv, hv = (reorder(x, rank) for x in (wv, gv, hv))
        elif cat_idx:
            F = hist.shape[1]

            def take(x):
                return jnp.stack([x[:, i] for i in cat_idx], axis=1)

            def put(x, xc):
                cols = [x[:, f] for f in range(F)]
                for j, i in enumerate(cat_idx):
                    cols[i] = xc[:, j]
                return jnp.stack(cols, axis=1)

            rank_c = position(take(wv), take(gv), take(hv))
            rank = put(jnp.broadcast_to(pos[None, None, :], wv.shape),
                       rank_c)
            wv, gv, hv = (put(x, reorder(take(x), rank_c))
                          for x in (wv, gv, hv))
    # cumulative over (possibly re-ordered) value bins; NA bin is B-1
    cw = jnp.cumsum(wv, axis=2)
    cg = jnp.cumsum(gv, axis=2)
    ch = jnp.cumsum(hv, axis=2)
    naw, nag, nah = w[:, :, B - 1], g[:, :, B - 1], h[:, :, B - 1]
    tw = cw[:, :, -1] + naw
    tg = cg[:, :, -1] + nag
    th = ch[:, :, -1] + nah
    if lo is None:
        lo = jnp.full((hist.shape[0],), -jnp.inf, jnp.float32)
        hi = jnp.full((hist.shape[0],), jnp.inf, jnp.float32)

    def gain(gl, hl, gr, hr):
        return (gl * gl / (hl + lam) + gr * gr / (hr + lam)
                - tg[:, :, None] ** 2 / (th[:, :, None] + lam))

    def child_vals(gl, hl, gr, hr):
        lv = jnp.clip(-gl / (hl + lam), lo[:, None, None], hi[:, None, None])
        rv = jnp.clip(-gr / (hr + lam), lo[:, None, None], hi[:, None, None])
        return lv, rv

    def masked_gain(wl, gl, hl):
        wr = tw[:, :, None] - wl
        gr = tg[:, :, None] - gl
        hr = th[:, :, None] - hl
        ok = (wl >= min_rows) & (wr >= min_rows)
        lv, rv = child_vals(gl, hl, gr, hr)
        if constraints is not None:
            c = constraints[None, :, None].astype(jnp.float32)
            ok = ok & (c * (rv - lv) >= 0)
        return jnp.where(ok, gain(gl, hl, gr, hr), -jnp.inf), lv, rv

    g_nar, lv_nar, rv_nar = masked_gain(cw, cg, ch)         # NA → right
    g_nal, lv_nal, rv_nal = masked_gain(
        cw + naw[:, :, None], cg + nag[:, :, None],
        ch + nah[:, :, None])                               # NA → left
    # threshold validity: t <= nb[f]-2 (splitting at last real bin is void)
    t_ids = jnp.arange(B - 1, dtype=jnp.int32)
    valid_t = t_ids[None, :] <= (nb[:, None] - 2)           # [F, B-1]
    cm = col_mask if col_mask.ndim == 2 else col_mask[None, :]   # [L|1, F]
    mask = valid_t[None, :, :] & cm[:, :, None]
    g_nar = jnp.where(mask, g_nar, -jnp.inf)
    g_nal = jnp.where(mask, g_nal, -jnp.inf)

    stacked = jnp.stack([g_nar, g_nal], axis=-1)            # [L, F, B-1, 2]
    L = stacked.shape[0]
    flat = stacked.reshape(L, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    na_left = (best % 2).astype(bool)
    best_t = ((best // 2) % (B - 1)).astype(jnp.int32)
    best_f = (best // (2 * (B - 1))).astype(jnp.int32)
    lvals = jnp.stack([lv_nar, lv_nal], axis=-1).reshape(L, -1)
    rvals = jnp.stack([rv_nar, rv_nal], axis=-1).reshape(L, -1)
    best_lv = jnp.take_along_axis(lvals, best[:, None], axis=1)[:, 0]
    best_rv = jnp.take_along_axis(rvals, best[:, None], axis=1)[:, 0]
    if rank is not None:
        # original-bin-id membership of the winning prefix: position of
        # bin b within the winning feature's order <= t  ⇔  b goes left
        F = hist.shape[1]
        win = best_f[:, None] == jnp.arange(F, dtype=jnp.int32)[None, :]
        ranks = jnp.sum(jnp.where(win[:, :, None], rank, 0), axis=1)
        leftmask = ranks <= best_t[:, None]
    else:
        leftmask = (jnp.arange(B - 1, dtype=jnp.int32)[None, :]
                    <= best_t[:, None])
    return best_gain, best_f, best_t, na_left, best_lv, best_rv, leftmask
