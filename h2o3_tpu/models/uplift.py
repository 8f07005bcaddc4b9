"""Uplift DRF — treatment-effect random forests.

Reference: hex/tree/uplift/UpliftDRF.java:1 (~700 LoC) — binomial
response + 2-level treatment column; split criterion maximizes the
divergence gain between treatment and control response distributions
(KL / Euclidean / ChiSquared, Rzepakowski-Jaroszewicz), leaves predict
``uplift = P(y=1|treated) - P(y=1|control)``; metrics are AUUC/Qini
(hex/ModelMetricsBinomialUplift).

TPU redesign: per level the (leaf, col, bin) stats come from TWO calls
of the matmul histogram (ops/histogram.py) — one with treatment-masked
weights, one with control-masked weights ({count, positives} each); the
divergence gain scan is vectorized over all nodes exactly like
models/tree.py ``_best_splits``. Routing, mtries, bagging reuse the DRF
machinery.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.binning import BinnedMatrix, bin_frame, rebin_for_scoring
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import metrics as mm
from h2o3_tpu.models.model import Model, ModelBuilder, ModelCategory, adapt_domain
from h2o3_tpu.models.tree import (Tree, _level_mtries_mask, predict_forest,
                                  zero_catsplit,
                                  row_feature_values, stack_trees)
from h2o3_tpu.ops.histogram import histogram
from h2o3_tpu.ops.segments import segment_sum
from h2o3_tpu.parallel.mesh import get_mesh
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.uplift")


def _smooth_p(pos, n):
    return (pos + 1.0) / (n + 2.0)   # Laplace-smoothed response rate


def _divergence(pt, pc, metric: str):
    if metric == "euclidean":
        return 2.0 * (pt - pc) ** 2
    if metric == "chi_squared":
        pc_ = jnp.clip(pc, 1e-7, 1 - 1e-7)
        return (pt - pc) ** 2 / pc_ + (pt - pc) ** 2 / (1 - pc_)
    # KL (reference default)
    pt_ = jnp.clip(pt, 1e-7, 1 - 1e-7)
    pc_ = jnp.clip(pc, 1e-7, 1 - 1e-7)
    return (pt_ * jnp.log(pt_ / pc_)
            + (1 - pt_) * jnp.log((1 - pt_) / (1 - pc_)))


def _best_uplift_splits(ht, hc, nb, col_mask, min_rows: float, metric: str):
    """Vectorized divergence-gain scan over (node, feature, bin, NA-dir).

    ht/hc: [L, F, B, 3] {count, positives, _} for treatment / control.
    """
    B = ht.shape[2]
    nt, yt = ht[..., 0], ht[..., 1]
    nc, yc = hc[..., 0], hc[..., 1]
    cnt_t = jnp.cumsum(nt[:, :, : B - 1], axis=2)
    cyt = jnp.cumsum(yt[:, :, : B - 1], axis=2)
    cnt_c = jnp.cumsum(nc[:, :, : B - 1], axis=2)
    cyc = jnp.cumsum(yc[:, :, : B - 1], axis=2)
    na = (nt[:, :, B - 1], yt[:, :, B - 1], nc[:, :, B - 1], yc[:, :, B - 1])
    tot_t = cnt_t[:, :, -1] + na[0]
    tot_yt = cyt[:, :, -1] + na[1]
    tot_c = cnt_c[:, :, -1] + na[2]
    tot_yc = cyc[:, :, -1] + na[3]
    d_node = _divergence(_smooth_p(tot_yt, tot_t),
                         _smooth_p(tot_yc, tot_c), metric)
    n_all = tot_t + tot_c

    def gain_of(lt, lyt, lc, lyc):
        rt = tot_t[:, :, None] - lt
        ryt = tot_yt[:, :, None] - lyt
        rc = tot_c[:, :, None] - lc
        ryc = tot_yc[:, :, None] - lyc
        nl, nr = lt + lc, rt + rc
        dl = _divergence(_smooth_p(lyt, lt), _smooth_p(lyc, lc), metric)
        dr = _divergence(_smooth_p(ryt, rt), _smooth_p(ryc, rc), metric)
        g = (nl * dl + nr * dr) / jnp.maximum(n_all[:, :, None], 1.0) \
            - d_node[:, :, None]
        ok = (nl >= min_rows) & (nr >= min_rows) & (lt > 0) & (lc > 0) \
            & (rt > 0) & (rc > 0)
        return jnp.where(ok, g, -jnp.inf)

    g_nar = gain_of(cnt_t, cyt, cnt_c, cyc)
    g_nal = gain_of(cnt_t + na[0][:, :, None], cyt + na[1][:, :, None],
                    cnt_c + na[2][:, :, None], cyc + na[3][:, :, None])
    t_ids = jnp.arange(B - 1, dtype=jnp.int32)
    valid_t = t_ids[None, :] <= (nb[:, None] - 2)
    cm = col_mask if col_mask.ndim == 2 else col_mask[None, :]
    mask = valid_t[None, :, :] & cm[:, :, None]
    g_nar = jnp.where(mask, g_nar, -jnp.inf)
    g_nal = jnp.where(mask, g_nal, -jnp.inf)
    stacked = jnp.stack([g_nar, g_nal], axis=-1)
    L = stacked.shape[0]
    flat = stacked.reshape(L, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    na_left = (best % 2).astype(bool)
    best_t = ((best // 2) % (B - 1)).astype(jnp.int32)
    best_f = (best // (2 * (B - 1))).astype(jnp.int32)
    return best_gain, best_f, best_t, na_left


@partial(jax.jit, static_argnames=("depth", "B", "mtries", "metric",
                                   "min_rows"))
def _grow_uplift_tree(bins, nb, w, y, treat, key, *, depth: int, B: int,
                      mtries: int, metric: str, min_rows: float = 10.0):
    """One uplift tree fully on device; returns Tree (leaf=uplift) plus
    per-leaf treated/control response rates."""
    mesh = get_mesh()
    F = bins.shape[1]
    Lmax = 2 ** (depth - 1) if depth > 0 else 1
    N = bins.shape[0]
    nid = jnp.zeros((N,), jnp.int32)
    wt = w * treat
    wc = w * (1.0 - treat)
    feats = jnp.zeros((depth, Lmax), jnp.int32)
    threshs = jnp.full((depth, Lmax), B, jnp.int32)
    na_lefts = jnp.zeros((depth, Lmax), bool)
    is_splits = jnp.zeros((depth, Lmax), bool)
    ones = jnp.ones_like(y)
    for d in range(depth):
        L = 2 ** d
        ht = histogram(bins, nid, wt, y, ones, n_nodes=L, n_bins=B, mesh=mesh)
        hc = histogram(bins, nid, wc, y, ones, n_nodes=L, n_bins=B, mesh=mesh)
        key, sub = jax.random.split(key)
        cm = (_level_mtries_mask(sub, L, F, mtries) if 0 < mtries < F
              else jnp.ones((1, F), bool))
        bg, bf, bt, bnal = _best_uplift_splits(ht, hc, nb, cm, min_rows,
                                               metric)
        split = bg > 1e-9
        feats = feats.at[d, :L].set(jnp.where(split, bf, 0))
        threshs = threshs.at[d, :L].set(jnp.where(split, bt, B))
        na_lefts = na_lefts.at[d, :L].set(jnp.where(split, bnal, False))
        is_splits = is_splits.at[d, :L].set(split)
        f_r = feats[d][nid]
        t_r = threshs[d][nid]
        nal_r = na_lefts[d][nid]
        isp_r = is_splits[d][nid]
        b_r = row_feature_values(bins, f_r)
        isna = b_r == (B - 1)
        goleft = jnp.where(isp_r, jnp.where(isna, nal_r, b_r <= t_r), True)
        nid = 2 * nid + jnp.where(goleft, 0, 1)
    nleaf = 2 ** depth
    st_t = segment_sum(nid, jnp.stack([wt, wt * y], axis=1),
                       n_nodes=nleaf, mesh=mesh)
    st_c = segment_sum(nid, jnp.stack([wc, wc * y], axis=1),
                       n_nodes=nleaf, mesh=mesh)
    p_t = _smooth_p(st_t[:, 1], st_t[:, 0])
    p_c = _smooth_p(st_c[:, 1], st_c[:, 0])
    tree = Tree(feats, threshs, na_lefts, is_splits, p_t - p_c,
                st_t[:, 0] + st_c[:, 0],
                *zero_catsplit(feats.shape[0], feats.shape[1]))
    return tree, p_t, p_c


def auuc(uplift_pred: np.ndarray, y: np.ndarray, treat: np.ndarray,
         nbins: int = 1000, auuc_type: str = "qini") -> Dict[str, float]:
    """AUUC / Qini from the cumulative uplift curve
    (hex/AUUC.java semantics: rows sorted by predicted uplift desc;
    curve types qini / lift / gain per hex/AUUC.AUUCType)."""
    order = np.argsort(-uplift_pred, kind="stable")
    y, tr = y[order], treat[order]
    n = len(y)
    idx = np.linspace(0, n, min(nbins, n) + 1).astype(int)[1:]
    cy_t = np.cumsum(y * tr)
    cn_t = np.cumsum(tr)
    cy_c = np.cumsum(y * (1 - tr))
    cn_c = np.cumsum(1 - tr)

    def curve_at(k: int, kind: str) -> float:
        nt, nc = cn_t[k], cn_c[k]
        rt = cy_t[k] / nt if nt > 0 else 0.0
        rc = cy_c[k] / nc if nc > 0 else 0.0
        if kind == "qini":
            return cy_t[k] - (cy_c[k] * nt / nc if nc > 0 else 0.0)
        if kind == "lift":
            return rt - rc
        return (rt - rc) * (nt + nc)   # gain

    kind = auuc_type if auuc_type in ("qini", "lift", "gain") else "qini"
    vals = np.asarray([curve_at(k, kind) for k in idx - 1])
    qini = np.asarray([curve_at(k, "qini") for k in idx - 1])
    auuc_v = float(vals.mean())
    # random-targeting baseline endpoint (on the qini curve)
    q_final = curve_at(n - 1, "qini")
    qini_coef = float(qini.mean() - q_final / 2.0)
    return {"auuc": auuc_v, "qini": qini_coef, "auuc_type": kind,
            "uplift_top_decile": float(vals[max(len(vals) // 10 - 1, 0)])}


class UpliftDRFModel(Model):
    algo = "upliftdrf"

    def __init__(self, params, output, forest: Tree, leaf_pt, leaf_pc,
                 bm: BinnedMatrix):
        super().__init__(params, output)
        self.forest = forest
        self.leaf_pt = leaf_pt      # [T, 2^D]
        self.leaf_pc = leaf_pc
        self.bm = bm

    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        bm = rebin_for_scoring(self.bm, frame)
        B = self.bm.nbins_total
        T = self.forest.feat.shape[0]
        n = frame.nrows
        # tree leaves are p_t - p_c by construction, so uplift falls out
        # of the two class-rate scans without a third forest walk
        pt = np.asarray(predict_forest(
            self.forest._replace(leaf=self.leaf_pt), bm.bins, B))[:n] / T
        pc = np.asarray(predict_forest(
            self.forest._replace(leaf=self.leaf_pc), bm.bins, B))[:n] / T
        return {"uplift_predict": pt - pc, "p_y1_ct1": pt, "p_y1_ct0": pc}

    def model_performance(self, frame: Frame):
        raw = self._score_raw(frame)
        y = adapt_domain(frame.col(self.output["response"]),
                         self.output["domain"])[: frame.nrows]
        tr = adapt_domain(frame.col(self.params["treatment_column"]),
                          self.output["treatment_domain"])[: frame.nrows]
        ok = (y >= 0) & (tr >= 0)
        nbins = int(self.params.get("auuc_nbins") or -1)
        atype = str(self.params.get("auuc_type") or "auto").lower()
        a = auuc(raw["uplift_predict"][ok], y[ok].astype(float),
                 tr[ok].astype(float),
                 nbins=nbins if nbins > 0 else 1000,
                 auuc_type="qini" if atype == "auto" else atype)
        return mm.ModelMetrics("BinomialUplift", int(ok.sum()),
                               float(np.mean(raw["uplift_predict"] ** 2)),
                               **a)


class UpliftDRFEstimator(ModelBuilder):
    """h2o-py H2OUpliftRandomForestEstimator surface
    (h2o-py/h2o/estimators/uplift_random_forest.py)."""

    algo = "upliftdrf"

    DEFAULTS = dict(
        ntrees=50, max_depth=10, min_rows=10.0, nbins=64, nbins_cats=64,
        mtries=-2, sample_rate=0.632, seed=-1,
        treatment_column=None, uplift_metric="auto",
        auuc_type="auto", auuc_nbins=-1,
        ignored_columns=None, nfolds=0, fold_assignment="auto",
        weights_column=None, fold_column=None,
    )

    def __init__(self, **params):
        merged = dict(self.DEFAULTS)
        unknown = set(params) - set(merged)
        if unknown:
            raise ValueError(f"unknown UpliftDRF params: {sorted(unknown)}")
        merged.update(params)
        super().__init__(**merged)
        if not self.params.get("treatment_column"):
            raise ValueError("UpliftDRF requires treatment_column")

    def resolve_x(self, frame, x, y):
        x = super().resolve_x(frame, x, y)
        return [n for n in x if n != self.params["treatment_column"]]

    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job, validation_frame: Optional[Frame] = None) -> Model:
        p = self.params
        rc = frame.col(y)
        tc = frame.col(p["treatment_column"])
        if not (rc.is_categorical and rc.cardinality == 2):
            raise ValueError("UpliftDRF needs a 2-level categorical response")
        if not (tc.is_categorical and tc.cardinality == 2):
            raise ValueError("UpliftDRF needs a 2-level treatment column")
        metric = str(p["uplift_metric"]).lower().replace("chisquared",
                                                         "chi_squared")
        if metric == "auto":
            metric = "kl"
        if metric not in ("kl", "euclidean", "chi_squared"):
            raise ValueError(f"unknown uplift_metric '{p['uplift_metric']}'; "
                             "use KL, Euclidean or ChiSquared")
        n = frame.nrows
        w = frame.valid_weights()
        if p.get("weights_column") and p["weights_column"] in frame:
            wc_ = frame.col(p["weights_column"]).numeric_view()
            w = w * jnp.where(jnp.isnan(wc_), 0.0, wc_)
        from h2o3_tpu.parallel.mesh import fetch_replicated as _f
        bm = bin_frame(frame, x, nbins=p["nbins"], nbins_cats=p["nbins_cats"],
                       weights=_f(w)[:n])
        npad = bm.bins.shape[0]
        yv = adapt_domain(rc, rc.domain)
        trv = adapt_domain(tc, tc.domain)
        ok = (yv >= 0) & (trv >= 0)
        w = w * jnp.asarray(np.pad(ok.astype(np.float32), (0, npad - n)))
        y_dev = jnp.asarray(np.pad(np.maximum(yv, 0).astype(np.float32),
                                   (0, npad - n)))
        t_dev = jnp.asarray(np.pad(np.maximum(trv, 0).astype(np.float32),
                                   (0, npad - n)))

        F = len(x)
        mtries = int(p["mtries"])
        if mtries == -1:
            mtries = max(int(np.sqrt(F)), 1)
        elif mtries == -2:
            mtries = F   # all columns (reference UpliftDRF default -2)
        depth = int(p["max_depth"])
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 0xD00D
        key = jax.random.PRNGKey(seed)
        ntrees = int(p["ntrees"])
        trees, pts, pcs = [], [], []
        for t in range(ntrees):
            key, kb, kt = jax.random.split(key, 3)
            keep = jax.random.bernoulli(kb, float(p["sample_rate"]),
                                        shape=w.shape)
            tr_, pt_, pc_ = _grow_uplift_tree(
                bm.bins, bm.nbins, w * keep.astype(jnp.float32), y_dev,
                t_dev, kt, depth=depth, B=bm.nbins_total, mtries=mtries,
                metric=metric, min_rows=float(p["min_rows"]))
            trees.append(tr_)
            pts.append(pt_)
            pcs.append(pc_)
            job.update(1.0 / ntrees, f"tree {t + 1}/{ntrees}")
        forest = stack_trees(trees)
        output = {"category": "BinomialUplift", "response": y,
                  "names": list(x), "domain": rc.domain,
                  "treatment_domain": tc.domain, "nclasses": 2}
        model = UpliftDRFModel(p, output, forest, jnp.stack(pts),
                               jnp.stack(pcs), bm)
        model.training_metrics = model.model_performance(frame)
        if validation_frame is not None:
            model.validation_metrics = model.model_performance(validation_frame)
        return model
