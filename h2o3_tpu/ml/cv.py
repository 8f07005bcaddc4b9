"""n-fold cross-validation — the computeCrossValidation path.

Reference: hex/ModelBuilder.java:603 — build fold assignment, train
nfolds models on (N - fold) rows each (CVModelBuilder sweep at :819),
score each holdout, merge holdout predictions into one frame, compute CV
metrics from it, then train the final model on all data. Same here;
fold models run sequentially (parallel fold training over spare mesh
slices is the reference's parallelism #5, SURVEY §2.4).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np
from h2o3_tpu.parallel.mesh import padded_rows as _pad_rows
from h2o3_tpu.parallel import scheduler as _scheduler

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import metrics as mm
from h2o3_tpu.models.model import ModelCategory, adapt_domain, infer_category


def fold_assignment(n: int, nfolds: int, scheme: str = "modulo",
                    seed: int = 0xF01D, y: Optional[np.ndarray] = None) -> np.ndarray:
    """Fold ids per row (reference FoldAssignment / AstKFold schemes:
    AUTO→Random, Modulo, Stratified)."""
    if scheme in ("modulo",):
        return (np.arange(n) % nfolds).astype(np.int32)
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    if scheme == "stratified" and y is not None:
        folds = np.zeros(n, np.int32)
        for cls in np.unique(y):
            idx = np.where(y == cls)[0]
            rng.shuffle(idx)
            folds[idx] = np.arange(len(idx)) % nfolds
        return folds
    return rng.randint(0, nfolds, size=n).astype(np.int32)


def subset_frame(frame: Frame, keep: np.ndarray,
                 pad_to: Optional[int] = None) -> Frame:
    """Host-side row subset (reference uses fold-weight columns instead;
    a weights-based device path is the planned optimization). ``pad_to``
    pads the subset to a caller-chosen device shape — CV passes the
    parent frame's padded size so every fold (and the final full-data
    fit) compiles ONE program instead of one per fold size."""
    arrays, domains, cats = {}, {}, []
    for name in frame.names:
        c = frame.col(name)
        if c.type == "string":
            arrays[name] = c.strings[:frame.nrows][keep]
            continue
        v = _fetch_np(c.data)[: frame.nrows][keep]
        if c.is_categorical:
            v = v.astype(np.int32)
            v[_fetch_np(c.na_mask)[: frame.nrows][keep]] = -1
            domains[name] = c.domain
            cats.append(name)
            arrays[name] = v
        else:
            vv = v.astype(np.float64)
            vv[_fetch_np(c.na_mask)[: frame.nrows][keep]] = np.nan
            arrays[name] = vv
    return Frame.from_numpy(arrays, categorical=cats, domains=domains,
                            pad_to=pad_to)


def _glm_path_holdout_deviance(m, te: Frame, y: str, p: dict) -> np.ndarray:
    """Per-lambda deviance of a GLM fold model's coefficient path on
    its holdout frame — the statistic the reference's lambda-search CV
    minimizes (GLM.java xval deviance). Honors the user weights column
    and the offset column, like the CV metrics themselves."""
    import jax.numpy as jnp
    from h2o3_tpu.models.model import ModelCategory, adapt_domain
    X1 = m._design(te)                         # [n_pad, P+1]
    path = m._coef_path                        # [L, P+1]
    n = te.nrows
    w = np.asarray(te.valid_weights())[:n]
    if p.get("weights_column") and p["weights_column"] in te:
        wraw = te.col(p["weights_column"]).to_numpy()
        w = w * np.nan_to_num(wraw).astype(np.float32)
    yc = te.col(y)
    if m.output["category"] == ModelCategory.BINOMIAL:
        yv = adapt_domain(yc, m.output["domain"])
        w = w * (yv >= 0)
        yv = np.maximum(yv, 0).astype(np.float32)
    else:
        yraw = yc.to_numpy()
        w = w * (~np.isnan(yraw))
        yv = np.nan_to_num(yraw).astype(np.float32)
    yv = np.pad(yv, (0, X1.shape[0] - n))
    w = np.pad(w, (0, X1.shape[0] - n))
    etas = X1 @ jnp.asarray(path.T, jnp.float32)              # [n, L]
    off = m._frame_offset(te)
    if off is not None:
        etas = etas + off[:, None]
    fam = m.family
    mus = np.asarray(fam.linkinv(etas))
    devs = np.asarray(fam.deviance(jnp.asarray(yv)[:, None],
                                   jnp.asarray(mus)))
    return (w[:, None] * devs).sum(axis=0)


def train_with_cv(builder, frame: Frame, x: Sequence[str], y: str,
                  nfolds: int, job, validation_frame: Optional[Frame] = None):
    """Train nfolds+1 models; attach CV metrics to the final model.
    A validation_frame flows to the final (main) model only, like the
    reference (ModelBuilder.java cv_main model keeps _valid)."""
    p = dict(builder.params)
    scheme = str(p.get("fold_assignment", "auto") or "auto").lower()
    if scheme == "auto":
        # AUTO resolves to seeded Random (ModelBuilder.cv_AssignFold:
        # `case AUTO: case Random:` share the kfoldColumn branch) — a
        # modulo default made different seeds produce IDENTICAL CV
        # models (pyunit_glm_seed's seed-difference assertion)
        scheme = "random"
    raw_seed = p.get("seed")
    if raw_seed is None or int(raw_seed) < 0:
        # getOrMakeRealSeed: unset seed draws a REAL random one, so two
        # unseeded Random-fold runs genuinely differ (pyunit_cv_carsRF)
        seed = int(np.random.SeedSequence().entropy % (2 ** 31))
    else:
        seed = int(raw_seed)
    category = infer_category(frame, y)

    if p.get("fold_column"):
        folds = _fetch_np(frame.col(p["fold_column"]).data)[: frame.nrows].astype(np.int32)
        nfolds = int(folds.max()) + 1
    else:
        yv = None
        if scheme == "stratified":
            yv = _fetch_np(frame.col(y).data)[: frame.nrows]
        folds = fold_assignment(frame.nrows, nfolds, scheme, seed, yv)

    sub_params = {**p, "nfolds": 0, "fold_column": None}
    cap_total = float(p.get("max_runtime_secs") or 0.0)
    if cap_total > 0:
        # the cap covers the WHOLE train incl. CV (ModelBuilder
        # cv_computeAndSetOptimalParameters role): the MAIN model keeps
        # half the budget, folds share the other half — an even
        # (nfolds+1)-way split strangled the main model whenever the
        # masked-weight fold fits were cheap
        sub_params["max_runtime_secs"] = \
            cap_total / 2.0 / max(nfolds, 1)
    job._work = nfolds + 1.0  # nfolds CV fits + the final model

    if y is None:
        # unsupervised CV (KMeans nfolds, hex/ModelBuilder unsupervised
        # path): train per-fold models + the final model; CV metrics are
        # the final model's metrics minus centroid_stats (the reference
        # serves cv metrics with centroid_stats == null —
        # pyunit_kmeans_cv contract)
        cv_models = []
        for f in range(nfolds):
            # honor the computed fold assignment (fold_column / scheme /
            # seed) — the unsupervised branch must not silently fall back
            # to a modulo split
            mask_tr = folds != f
            tr = subset_frame(frame, mask_tr, pad_to=frame.nrows_padded)
            m = builder.__class__(**sub_params)._fit(tr, list(x), None, job)
            cv_models.append(m)
        final = builder.__class__(**sub_params)._fit(
            frame, list(x), None, job, validation_frame=validation_frame)
        import copy
        cvm = copy.copy(final.training_metrics)
        if cvm is not None and hasattr(cvm, "extra"):
            cvm.extra = dict(cvm.extra)
            cvm.extra["centroid_stats"] = None
        final.cross_validation_metrics = cvm
        from h2o3_tpu.core.kv import DKV
        cv_keys = []
        for i, m in enumerate(cv_models):
            new_key = f"{final.key}_cv_{i + 1}"
            DKV.remove(m.key)
            m.key = new_key
            DKV.put(new_key, m)
            cv_keys.append(new_key)
        final.output["cv_model_keys"] = cv_keys
        final.output["nfolds"] = nfolds
        final._cv_models = cv_models
        return final

    n = frame.nrows
    cv_models = []
    if category == ModelCategory.MULTINOMIAL:
        K = frame.col(y).cardinality
        holdout = np.zeros((n, K), np.float32)
    else:
        holdout = np.zeros((n,), np.float32)

    keep_preds = bool(p.get("keep_cross_validation_predictions"))
    cv_pred_keys = []
    fold_metric_dicts = []
    path_devs = []      # per-fold per-lambda holdout deviance (GLM search)
    dev_scores = []     # (holdout idx, device score) — light-mode async sweep

    # CV fast path (tree builders): fold models train on the PARENT
    # frame with held-out rows weight-masked and the main model's bin
    # edges shared, so the whole sweep reuses ONE compiled program and
    # never rebuilds frames — leave-one-out CV (nfolds == nrows,
    # pyunit_cv_cars_gbm boundary case) costs one dispatch per fold
    # instead of a frame rebuild + bin re-sketch per fold.
    fast = bool(getattr(builder, "cv_fold_masking", False)) \
        and not p.get("checkpoint")
    if fast and builder.algo == "glm" and (
            p.get("lambda_search") or
            (p.get("lambda_") not in (None, 0, 0.0))):
        # penalized GLM folds must standardize per fold (the penalty
        # couples to the sigma scaling), so the shared-design fast path
        # only covers unpenalized fits; regularized CV keeps the
        # subset-frame path with per-fold DataInfo like the reference
        fast = False
    final = None
    shared_bm = None
    main_params = dict(sub_params)
    if cap_total > 0:
        main_params["max_runtime_secs"] = cap_total / 2.0
    if fast:
        # main model FIRST: folds reuse its full-data binning (GLM has
        # no binned matrix — folds share the design implicitly, since
        # the masked rows ride the same parent frame)
        final = builder.__class__(**main_params)._fit(
            frame, list(x), y, job, validation_frame=validation_frame)
        shared_bm = getattr(final, "bm", None)

    # near-leave-one-out CV (the nfolds ≈ nrows boundary case,
    # pyunit_cv_cars_gbm) drops per-fold frills whose device syncs
    # dominate: fold training metrics, varimp, and per-fold holdout
    # metric dicts — the CV metric over the merged holdout (below) is
    # the contract that matters. Ordinary nfolds keep full fidelity.
    light = fast and nfolds >= max(100, 0.5 * frame.nrows)
    if light:
        from h2o3_tpu.utils.log import get_logger
        get_logger("h2o3_tpu.cv").info(
            "near-LOO CV (nfolds=%d on %d rows): skipping per-fold "
            "metric/varimp frills", nfolds, frame.nrows)

    # GLM lambda search under CV: train the MAIN model first to fix one
    # full-frame lambda path, have every fold walk that SAME path (so
    # per-lambda holdout deviances align index-wise), then re-fit the
    # main model at the CV-selected lambda (GLM.java xval-deviance
    # lambda selection).
    shared_lambda_path = None
    glm_search = (getattr(builder, "algo", "") == "glm"
                  and p.get("lambda_search") and not fast)
    if glm_search:
        probe = builder.__class__(**sub_params)._fit(frame, list(x), y, job)
        shared_lambda_path = getattr(probe, "_lambda_path_vals", None)
        from h2o3_tpu.core.kv import DKV as _DKV
        _DKV.remove(probe.key)
        del probe

    # ---- cluster-scheduled fold models (parallel/scheduler.py) -------
    # the subset-frame fold path is embarrassingly parallel: each fold
    # trains on its own rebuilt frame with no shared device state, so on
    # a multi-host cloud the folds fan out as work items (local mesh +
    # host frame copies) and come back as device-independent model bytes
    # every process installs identically. The fast path (shared binning
    # + fold masking on the parent frame) and GLM lambda-search CV keep
    # their single-program sweeps — scheduling would break the sharing
    # that makes them fast.
    sched_folds = None
    if (_scheduler.active() and not fast and not glm_search
            and not p.get("checkpoint") and nfolds >= 2):
        max_fold = int(np.max(np.bincount(folds, minlength=nfolds)))

        def _cv_execute(f):
            from h2o3_tpu.parallel import mesh as mesh_mod
            with mesh_mod.local_mesh_scope():
                lf = frame.local_copy()
                mask_tr = folds != f
                tr = subset_frame(lf, mask_tr, pad_to=lf.nrows_padded)
                te = subset_frame(lf, ~mask_tr,
                                  pad_to=_pad_rows(max_fold, block=8))
                sub = builder.__class__(**sub_params)
                m = sub._fit(tr, list(x), y, job)
                preds = {k: np.asarray(v)
                         for k, v in m._score_raw(te).items()}
                try:
                    fm = m.model_performance(te)
                    fmd = fm.to_dict() if hasattr(fm, "to_dict") else {}
                except Exception:    # noqa: BLE001 - summary-only data
                    fmd = {}
                return _scheduler.lower_to_bytes(
                    (_scheduler.detach_model(m), preds, fmd))

        res = _scheduler.run(f"cv:{builder.algo}:{nfolds}f", nfolds,
                             _cv_execute, job=job)
        sched_folds = {}
        for f in sorted(res):
            rec = res[f]
            if not rec["ok"]:
                # the owning host's training error — sequential CV
                # would have raised the same error out of its fold loop
                raise RuntimeError(rec["error"])
            m, preds_f, fmd = _scheduler.from_bytes(rec["data"])
            sched_folds[f] = (_scheduler.install_model(m), preds_f, fmd)

    for f in range(nfolds):
        mask_tr = folds != f
        idx = np.where(~mask_tr)[0]
        if fast:
            sub = builder.__class__(**sub_params)
            sub._cv_fold_mask = mask_tr
            sub._cv_shared_bm = shared_bm
            sub._cv_light = light
            m = sub._fit(frame, list(x), y, job)
            if light and not keep_preds and hasattr(m, "_score_dev"):
                # near-LOO async pipeline: keep every fold's holdout
                # score ON DEVICE and fetch the whole sweep in one
                # batched transfer after the loop — the per-fold
                # blocking fetch was a host round trip × nfolds
                # (pyunit_cv_carsRF's 583s). Periodic block bounds the
                # number of in-flight fold forests in HBM.
                dev_scores.append((idx, m._score_dev(frame)))
                if len(dev_scores) % 64 == 0:
                    dev_scores[-1][1].block_until_ready()
                from h2o3_tpu.core.kv import DKV as _DKV
                _DKV.remove(m.key)
                del m
                fold_metric_dicts.append({})
                continue
            full_preds = m._score_raw(frame)
            preds = {k: np.asarray(v)[idx] for k, v in full_preds.items()}
            if light:
                # near-LOO: fold models are NOT retained — hundreds of
                # padded complete-tree forests (~100MB each on device)
                # exhaust HBM long before the sweep ends; the merged
                # holdout predictions (the CV metric contract) are
                # already extracted above
                from h2o3_tpu.core.kv import DKV as _DKV
                _DKV.remove(m.key)
                del m
                fold_metric_dicts.append({})
            else:
                cv_models.append(m)
                hold_w = np.zeros(frame.nrows_padded, np.float32)
                hold_w[idx] = 1.0
                try:
                    fm = m.model_performance(frame, mask_weights=hold_w)
                    fold_metric_dicts.append(
                        fm.to_dict() if hasattr(fm, "to_dict") else {})
                except Exception:
                    fold_metric_dicts.append({})
        elif sched_folds is not None:
            m, preds, fmd = sched_folds.pop(f)
            cv_models.append(m)
            fold_metric_dicts.append(fmd)
        else:
            tr = subset_frame(frame, mask_tr, pad_to=frame.nrows_padded)
            # holdouts share one padded shape too (all ~n/nfolds rows;
            # max fold size keeps one scoring program across folds)
            te = subset_frame(frame, ~mask_tr,
                              pad_to=_pad_rows(int(np.max(
                                  np.bincount(folds, minlength=nfolds))),
                                  block=8))
            sub = builder.__class__(**sub_params)
            if shared_lambda_path:
                sub.params["_lambda_path_override"] = shared_lambda_path
            m = sub._fit(tr, list(x), y, job)
            cv_models.append(m)
            if shared_lambda_path and \
                    getattr(m, "_coef_path", None) is not None:
                path_devs.append(_glm_path_holdout_deviance(m, te, y, p))
            preds = m._score_raw(te)
            # per-fold holdout metrics feed
            # cross_validation_metrics_summary (reference cvModelBuilder
            # per-fold _validation metrics)
            try:
                fm = m.model_performance(te)
                fold_metric_dicts.append(fm.to_dict()
                                         if hasattr(fm, "to_dict") else {})
            except Exception:
                fold_metric_dicts.append({})
        if category == ModelCategory.BINOMIAL:
            holdout[idx] = preds["p1"]
        elif category == ModelCategory.MULTINOMIAL:
            for k in range(K):
                holdout[idx, k] = preds[f"p{k}"]
        else:
            holdout[idx] = preds["predict"]
        if keep_preds:
            # per-fold holdout prediction frame: full nrows, zeros off-fold
            # (reference keep_cross_validation_predictions contract)
            cols = {}
            for name, arr in preds.items():
                a = np.asarray(arr, np.float64)
                if a.dtype.kind not in "fiu":
                    continue
                fullcol = np.zeros(n, np.float64)
                fullcol[idx] = a[: len(idx)]
                cols[name] = fullcol
            pf = Frame.from_numpy(cols)
            cv_pred_keys.append(pf.key)

    if dev_scores:
        # ONE batched device→host transfer merges the whole light sweep
        fetched = _fetch_np([a for _, a in dev_scores])
        for (idx2, _), arr in zip(dev_scores, fetched):
            holdout[idx2] = np.asarray(arr)[idx2]
        dev_scores.clear()

    # final model on all data (ModelBuilder.java "main model") — the
    # fast path trained it up front to share its binning with the folds
    if final is None:
        fb = builder.__class__(**main_params)
        if path_devs:
            # GLM lambda search under CV selects the lambda minimizing
            # the SUMMED holdout deviance over the folds' SHARED path
            # (the reference's xval-deviance selection) — this is why
            # two different CV seeds legitimately yield different final
            # coefficients (pyunit_glm_seed h2oglm_3 != h2oglm_4)
            tot = np.sum(np.stack(path_devs), axis=0)
            lam_best = shared_lambda_path[int(np.argmin(tot))]
            fb.params["_lambda_path_override"] = shared_lambda_path
            fb.params["_cv_selected_lambda"] = float(lam_best)
        final = fb._fit(
            frame, list(x), y, job, validation_frame=validation_frame)

    # CV metrics: NA-response rows excluded, user weights applied — same
    # weighting contract as training metrics
    yc = frame.col(y)
    wv = np.ones(n, np.float32)
    if p.get("weights_column") and p["weights_column"] in frame:
        wraw = frame.col(p["weights_column"]).to_numpy()
        wv = np.nan_to_num(wraw).astype(np.float32)
    if category in (ModelCategory.BINOMIAL, ModelCategory.MULTINOMIAL):
        yv = adapt_domain(yc, yc.domain)
        wv = wv * (yv >= 0)
        yv = np.maximum(yv, 0)
        if category == ModelCategory.BINOMIAL:
            final.cross_validation_metrics = mm.binomial_metrics(
                holdout, yv.astype(np.float32), wv)
        else:
            final.cross_validation_metrics = mm.multinomial_metrics(
                holdout, yv, wv, domain=yc.domain)
    else:
        yraw = yc.to_numpy()
        wv = wv * (~np.isnan(yraw)).astype(np.float32)
        yv = np.nan_to_num(yraw).astype(np.float32)
        final.cross_validation_metrics = mm.regression_metrics(holdout, yv, wv)
    # combined holdout-prediction frame + fold-assignment frame
    # (reference cross_validation_holdout_predictions_frame_id /
    # cross_validation_fold_assignment_frame_id outputs)
    if keep_preds:
        if category == ModelCategory.MULTINOMIAL:
            hcols = {f"p{k}": holdout[:, k].astype(np.float64)
                     for k in range(holdout.shape[1])}
            hcols = {"predict": holdout.argmax(axis=1).astype(np.float64),
                     **hcols}
        elif category == ModelCategory.BINOMIAL:
            t = final.output.get("default_threshold", 0.5)
            hcols = {"predict": (holdout >= t).astype(np.float64),
                     "p0": (1.0 - holdout).astype(np.float64),
                     "p1": holdout.astype(np.float64)}
        else:
            hcols = {"predict": holdout.astype(np.float64)}
        hf = Frame.from_numpy(hcols)
        final.output["cv_holdout_frame_key"] = hf.key
    else:
        final.output["cv_holdout_frame_key"] = None
    if p.get("keep_cross_validation_fold_assignment"):
        faf = Frame.from_numpy({"fold_assignment":
                                folds.astype(np.float64)})
        final.output["cv_fold_assignment_key"] = faf.key
    else:
        final.output["cv_fold_assignment_key"] = None
    final.output["cv_holdout_predictions"] = None
    final.output["cv_predictions_keys"] = cv_pred_keys or None
    final.output["nfolds"] = nfolds
    # expose CV models to clients like the reference does: keys named
    # {main}_cv_{i}, listed under output.cross_validation_models
    # (hex/ModelBuilder.java:819 cv-model naming)
    from h2o3_tpu.core.kv import DKV
    cv_keys = []
    for i, m in enumerate(cv_models):
        new_key = f"{final.key}_cv_{i + 1}"
        DKV.remove(m.key)
        m.key = new_key
        DKV.put(new_key, m)
        cv_keys.append(new_key)
    final.output["cv_model_keys"] = cv_keys
    # mean/sd/per-fold summary rows (client
    # cross_validation_metrics_summary)
    keys_union = sorted({k for d in fold_metric_dicts for k, v in d.items()
                         if isinstance(v, (int, float))})
    summary_rows = []
    for kname in keys_union:
        # keep one slot per fold (None where the metric is absent or the
        # fold's scoring failed): twodim transposes these rows against a
        # fixed 2+nfolds column set, so a short row 500s GET /3/Models
        per_fold = [float(d[kname])
                    if isinstance(d.get(kname), (int, float)) else None
                    for d in fold_metric_dicts]
        vals = [v for v in per_fold if v is not None]
        if not vals:
            continue
        summary_rows.append(
            [kname, float(np.mean(vals)), float(np.std(vals))] + per_fold)
    final.output["cv_summary_rows"] = summary_rows
    final.output["cv_summary_nfolds"] = nfolds
    final._cv_holdout = holdout
    final._cv_models = cv_models
    final._cv_folds = folds
    return final
