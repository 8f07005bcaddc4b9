"""Model metrics — the hex.ModelMetrics* family.

Reference: one ModelMetrics class per problem type filled by incremental
MetricBuilders inside scoring MRTasks (h2o-core/src/main/java/hex/
ModelMetrics*.java); exact AUC from a 400-bin score histogram
(hex/AUC2.java:24, NBINS=400). Here the same shape: one device pass
builds weighted histograms/sums (psum over the mesh), host finishes the
scalar math.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.ops.segments import segment_sum
from h2o3_tpu.parallel.mesh import get_mesh

AUC_NBINS = 400  # hex/AUC2.java:24

# metric sums are float32 sums on every backend (ops/segments.py):
# served metrics must hit the reference pyunits' 1e-5 equality bars

# Every metric runs ONE jitted device pass (the MetricBuilder-inside-
# MRTask single sweep) and finishes scalars on host — un-jitted
# shard_maps would re-lower per call, which dominates wall time on a
# remote-attached chip.


@partial(jax.jit, static_argnames=("mesh",))
def _binomial_pass(p, y, w, *, mesh):
    pc = jnp.clip(p, 1e-7, 1 - 1e-7)
    sums = segment_sum(
        jnp.zeros_like(y, jnp.int32),
        jnp.stack([w,
                   w * (p - y) ** 2,
                   -w * (y * jnp.log(pc) + (1 - y) * jnp.log(1 - pc)),
                   w * y], axis=1),
        n_nodes=1, mesh=mesh)
    bins = jnp.clip((pc * AUC_NBINS).astype(jnp.int32), 0, AUC_NBINS - 1)
    hist = segment_sum(bins, jnp.stack([w * y, w * (1.0 - y)], axis=1),
                       n_nodes=AUC_NBINS, mesh=mesh)
    return sums[0], hist


def _auc_from_hist(pos: np.ndarray, neg: np.ndarray) -> Dict[str, float]:
    """AUC + AUCPR + max-F1 threshold from the bin histograms
    (hex/AUC2.java compute path)."""
    # sweep thresholds from high to low: cumulative TP/FP
    tp = np.cumsum(pos[::-1])[::-1]
    fp = np.cumsum(neg[::-1])[::-1]
    P, N = pos.sum(), neg.sum()
    if P == 0 or N == 0:
        return {"auc": 0.5, "pr_auc": 0.0, "max_f1": 0.0,
                "max_f1_threshold": 0.5, "gini": 0.0}
    tpr = np.concatenate([tp / P, [0.0]])
    fpr = np.concatenate([fp / N, [0.0]])
    auc = float(np.trapezoid(tpr[::-1], fpr[::-1]))
    prec = tp / np.maximum(tp + fp, 1e-12)
    rec = tp / P
    order = np.argsort(rec)
    pr_auc = float(np.trapezoid(np.concatenate([[prec[order][0]], prec[order]]),
                                np.concatenate([[0.0], rec[order]])))
    f1 = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
    k = int(np.argmax(f1))
    return {"auc": auc, "pr_auc": pr_auc, "max_f1": float(f1[k]),
            "max_f1_threshold": float(k / AUC_NBINS), "gini": 2 * auc - 1}


class ModelMetrics:
    """Base: shared scalar fields (hex/ModelMetrics.java)."""

    def __init__(self, kind: str, nobs: int, mse: float, **extra):
        self.kind = kind
        self.nobs = nobs
        self.mse = mse
        self.rmse = float(np.sqrt(mse))
        self.extra = extra

    def to_dict(self) -> dict:
        d = {"model_category": self.kind, "nobs": self.nobs,
             "MSE": self.mse, "RMSE": self.rmse}
        d.update(self.extra)
        return d

    def __getitem__(self, k):
        return self.to_dict()[k]

    def __repr__(self):
        items = ", ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                          for k, v in self.to_dict().items() if not isinstance(v, (list, dict)))
        return f"<ModelMetrics {items}>"


def binomial_metrics(p, y, w=None, mesh=None) -> ModelMetrics:
    """hex/ModelMetricsBinomial.java: AUC/logloss/Brier from one pass.

    p: P(class 1) [N]; y: 0/1 labels; w: weights (0 on padding rows).
    """
    mesh = mesh or get_mesh()
    p = jnp.asarray(p, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    w = jnp.ones_like(p) if w is None else jnp.asarray(w, jnp.float32)
    sums, hist = _binomial_pass(p, y, w, mesh=mesh)
    tot, sse, ll, pos = (float(x) for x in np.asarray(sums))
    hist = np.asarray(hist)
    pos_h, neg_h = hist[:, 0], hist[:, 1]
    roc = _auc_from_hist(pos_h, neg_h)
    t = roc["max_f1_threshold"]
    # confusion at max-F1 threshold (reference default criterion)
    idx = int(t * AUC_NBINS)
    tp = pos_h[idx:].sum(); fp = neg_h[idx:].sum()
    fn = pos_h[:idx].sum(); tn = neg_h[:idx].sum()
    err0 = fp / max(fp + tn, 1e-12)
    err1 = fn / max(fn + tp, 1e-12)
    mm = ModelMetrics(
        "Binomial", int(tot), sse / max(tot, 1e-12),
        logloss=ll / max(tot, 1e-12),
        AUC=roc["auc"], pr_auc=roc["pr_auc"], Gini=roc["gini"],
        max_f1=roc["max_f1"], max_f1_threshold=t,
        mean_per_class_error=float((err0 + err1) / 2),
        confusion_matrix=[[float(tn), float(fp)], [float(fn), float(tp)]],
        positive_fraction=pos / max(tot, 1e-12))
    # keep the 400-bin score histogram for the REST thresholds table
    # (hex/AUC2 serves per-threshold rows to the client)
    mm.hist = (pos_h, neg_h)
    return mm


@partial(jax.jit, static_argnames=("mesh",))
def _multinomial_pass(probs, y, w, *, mesh):
    K = probs.shape[1]
    py = jnp.clip(jnp.take_along_axis(probs, y[:, None], axis=1)[:, 0],
                  1e-7, 1.0)
    pred = jnp.argmax(probs, axis=1).astype(jnp.int32)
    onehot_err = (pred != y).astype(jnp.float32)
    sse = jnp.sum((probs - (jnp.arange(K)[None, :] == y[:, None])) ** 2,
                  axis=1)
    sums = segment_sum(
        jnp.zeros_like(y), jnp.stack([w, -w * jnp.log(py), w * onehot_err,
                                      w * sse], axis=1),
        n_nodes=1, mesh=mesh)
    cm = segment_sum((y * K + pred).astype(jnp.int32), w[:, None],
                     n_nodes=K * K, mesh=mesh)
    return sums[0], cm


@partial(jax.jit, static_argnames=("mesh",))
def _multinomial_score_hists(probs, y, w, *, mesh):
    """[K, K, AUC_NBINS] — weight of rows with TRUE class j landing in
    score bin b of class-k probability. One structure serves both
    one-vs-rest (pos = H[k,k], neg = Σ_{j≠k} H[k,j]) and one-vs-one
    (pos = H[i,i], neg = H[i,j]) AUCs — hex/MultinomialAUC.java."""
    K = probs.shape[1]
    out = []
    for k in range(K):
        b = jnp.clip((probs[:, k] * AUC_NBINS).astype(jnp.int32),
                     0, AUC_NBINS - 1)
        hk = segment_sum((y * AUC_NBINS + b).astype(jnp.int32), w[:, None],
                         n_nodes=K * AUC_NBINS, mesh=mesh)
        out.append(hk.reshape(K, AUC_NBINS))
    return jnp.stack(out)                    # [K(prob), K(true), B]


def multinomial_metrics(probs, y, w=None, mesh=None,
                        domain: Optional[List[str]] = None) -> ModelMetrics:
    """hex/ModelMetricsMultinomial.java: logloss, per-class error, CM."""
    mesh = mesh or get_mesh()
    K = probs.shape[1]
    y = jnp.asarray(y, jnp.int32)
    w = jnp.ones(probs.shape[0], jnp.float32) if w is None else jnp.asarray(w, jnp.float32)
    sums, cm = _multinomial_pass(probs, y, w, mesh=mesh)
    tot, ll, err, sse_t = (float(x) for x in np.asarray(sums))
    cm = np.asarray(cm).reshape(K, K)
    row = cm.sum(axis=1)
    per_class_err = np.where(row > 0, 1.0 - np.diag(cm) / np.maximum(row, 1e-12), 0.0)
    extra = {}
    if 2 <= K <= 30:
        # one-vs-rest + one-vs-one AUC/PR-AUC tables (PUBDEV-7269,
        # hex/MultinomialAUC.java; capped K bounds the K² histogram set)
        H = np.asarray(_multinomial_score_hists(probs, y, w, mesh=mesh),
                       np.float64)
        dom = domain or [f"class_{i}" for i in range(K)]
        frac = row / max(row.sum(), 1e-12)
        auc_rows, pr_rows = [], []
        ovr_auc, ovr_pr = np.zeros(K), np.zeros(K)
        for k in range(K):
            pos = H[k, k]
            neg = H[k].sum(axis=0) - pos
            r = _auc_from_hist(pos, neg)
            ovr_auc[k], ovr_pr[k] = r["auc"], r["pr_auc"]
            auc_rows.append([f"{dom[k]} vs Rest", dom[k], "",
                             float(r["auc"])])
            pr_rows.append([f"{dom[k]} vs Rest", dom[k], "",
                            float(r["pr_auc"])])
        auc_rows.append(["Macro OVR", "", "", float(ovr_auc.mean())])
        auc_rows.append(["Weighted OVR", "", "",
                         float((ovr_auc * frac).sum())])
        pr_rows.append(["Macro OVR", "", "", float(ovr_pr.mean())])
        pr_rows.append(["Weighted OVR", "", "",
                        float((ovr_pr * frac).sum())])
        ovo_auc, ovo_pr, ovo_w = [], [], []
        for i in range(K):
            for j in range(i + 1, K):
                # symmetric pairwise AUC: average of i-scored and
                # j-scored directions (PairwiseAUC semantics)
                ri = _auc_from_hist(H[i, i], H[i, j])
                rj = _auc_from_hist(H[j, j], H[j, i])
                a = 0.5 * (ri["auc"] + rj["auc"])
                pr = 0.5 * (ri["pr_auc"] + rj["pr_auc"])
                ovo_auc.append(a)
                ovo_pr.append(pr)
                ovo_w.append(frac[i] + frac[j])
                auc_rows.append([f"{dom[i]} vs {dom[j]}", dom[i], dom[j],
                                 float(a)])
                pr_rows.append([f"{dom[i]} vs {dom[j]}", dom[i], dom[j],
                                float(pr)])
        ow = np.asarray(ovo_w) / max(sum(ovo_w), 1e-12)
        auc_rows.append(["Macro OVO", "", "", float(np.mean(ovo_auc))])
        auc_rows.append(["Weighted OVO", "", "",
                         float((np.asarray(ovo_auc) * ow).sum())])
        pr_rows.append(["Macro OVO", "", "", float(np.mean(ovo_pr))])
        pr_rows.append(["Weighted OVO", "", "",
                        float((np.asarray(ovo_pr) * ow).sum())])
        extra = {"multinomial_auc_rows": auc_rows,
                 "multinomial_aucpr_rows": pr_rows,
                 # scalar AUC/PR = weighted OVR (the reference's
                 # default MultinomialAucType when computed)
                 "AUC": float((ovr_auc * frac).sum()),
                 "pr_auc": float((ovr_pr * frac).sum())}
    return ModelMetrics(
        "Multinomial", int(tot), sse_t / max(tot, 1e-12),
        logloss=ll / max(tot, 1e-12),
        mean_per_class_error=float(per_class_err[row > 0].mean()) if (row > 0).any() else 0.0,
        error_rate=err / max(tot, 1e-12),
        confusion_matrix=cm.tolist(),
        domain=domain, **extra)


@partial(jax.jit, static_argnames=("mesh",))
def _regression_pass(pred, y, w, dev, *, mesh):
    ok_log = (y > -1) & (pred > -1)
    rmsle_term = jnp.where(ok_log,
                           (jnp.log1p(jnp.maximum(pred, -1 + 1e-12))
                            - jnp.log1p(jnp.maximum(y, -1 + 1e-12))) ** 2, 0.0)
    sums = segment_sum(
        jnp.zeros(y.shape[0], jnp.int32),
        jnp.stack([w, w * (y - pred) ** 2, w * jnp.abs(y - pred),
                   w * rmsle_term, w * y, w * y * y, w * dev], axis=1),
        n_nodes=1, mesh=mesh)
    return sums[0]


def regression_metrics(pred, y, w=None, mesh=None,
                       deviance_fn=None) -> ModelMetrics:
    """hex/ModelMetricsRegression.java: MSE/MAE/RMSLE/deviance/R2."""
    mesh = mesh or get_mesh()
    pred = jnp.asarray(pred, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    w = jnp.ones_like(y) if w is None else jnp.asarray(w, jnp.float32)
    # deviance_fn is a fresh lambda per call — evaluate it outside the
    # jitted pass so the pass's trace cache never misses
    dev = deviance_fn(y, pred) if deviance_fn is not None else (y - pred) ** 2
    sums = _regression_pass(pred, y, w, jnp.asarray(dev, jnp.float32),
                            mesh=mesh)
    tot, sse, sae, sle, sy, syy, sdev = (float(x) for x in np.asarray(sums))
    mse = sse / max(tot, 1e-12)
    var_y = syy / max(tot, 1e-12) - (sy / max(tot, 1e-12)) ** 2
    return ModelMetrics(
        "Regression", int(tot), mse,
        mae=sae / max(tot, 1e-12),
        rmsle=float(np.sqrt(sle / max(tot, 1e-12))),
        mean_residual_deviance=sdev / max(tot, 1e-12),
        r2=1.0 - mse / max(var_y, 1e-12))
