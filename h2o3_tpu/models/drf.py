"""DRF — distributed random forest on the shared tree machinery.

Reference: hex/tree/drf/DRF.java:30 on the SharedTree skeleton.
Differences from GBM that this file reproduces:
- each tree is an independent regression tree on the raw response
  (indicator per class for classification), trained on a bagged row
  sample (sample_rate, default 0.632) — no shrinkage, no margins;
- per-NODE column subsampling of exactly `mtries` columns
  (DRF.java mtries: -1 → sqrt(p) classification / p/3 regression);
- prediction = average of per-tree leaf means (votes);
- training metrics are OOB: every row is scored only by the trees whose
  bag excluded it (DRF.java OOB scoring via Sample/Score).

TPU redesign: the whole forest is ONE compiled ``lax.scan`` over trees
(`_bag_scan`) — per tree: bag mask, grow_tree with (g=-y, h=1) so the
Newton leaf value is the bag-weighted mean of y, and OOB accumulator
updates — all on device; rows stay sharded on the mesh 'data' axis
throughout, and one model costs one dispatch.
"""

from __future__ import annotations

import dataclasses
import time

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np

from h2o3_tpu.frame.binning import BinnedMatrix, rebin_for_scoring
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import metrics as mm
from h2o3_tpu.models.model import (Model, ModelBuilder, ModelCategory,
                                   adapt_domain, checkpoint_error,
                                   infer_category, resolve_checkpoint_model,
                                   validate_checkpoint_params)
from h2o3_tpu import telemetry
from h2o3_tpu.models import frontier
from h2o3_tpu.models.tree import (Tree, TreeParams, bucket_depth,
                                  concat_forests, frontier_start, grow_tree,
                                  kernel_levels, leaf_values, predict_forest,
                                  scalars_of, stack_trees, trees_per_chunk)
from h2o3_tpu.ops import pallas as pallas_ops
from h2o3_tpu.parallel.mesh import get_mesh
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.drf")

# what the readers of the complete ``Tree`` layout (TreeSHAP, leaf
# assignment, path counts, MOJO/POJO export) are handed: a forest whose
# REALIZED depth is at most this is converted for them after the fit
# (DRFModel.forest); a deeper one raises frontier.DeepForestError there.
# Growth itself is not capped by it.
MAX_COMPLETE_DEPTH = 14


@partial(jax.jit,
         static_argnames=("tp", "sample_rate", "mtries", "n_class",
                          "ntrees"))
def _bag_scan(bins, nb, ys, w, key, depth_limit, *, tp: TreeParams,
              sample_rate: float, mtries: int, n_class: int, ntrees: int):
    """The whole forest as ONE compiled ``lax.scan`` over trees.

    The per-tree Python loop cost one dispatch + one host gains sync per
    tree — leave-one-out CV (pyunit_cv_carsRF boundary: nfolds == nrows)
    multiplied that into 20K host round trips and a 600s timeout. The
    scan leaves one dispatch per MODEL. The key chain reproduces the
    sequential `key, sub = split(key)` of the loop exactly, so forests
    are bit-identical to the unfused path."""
    N = w.shape[0]
    oob_sum = jnp.zeros((N, n_class), jnp.float32)
    oob_cnt = jnp.zeros((N,), jnp.float32)

    def gen(carry, _):
        k, s = jax.random.split(carry)
        return k, s

    key_out, subs = jax.lax.scan(gen, key, None, length=ntrees)

    def step(carry, sub):
        osum, ocnt = carry
        tr, osum, ocnt, gains = _bag_body(
            bins, nb, ys, w, osum, ocnt, sub, depth_limit, tp=tp,
            sample_rate=sample_rate, mtries=mtries, n_class=n_class)
        return (osum, ocnt), (tr, gains)

    (oob_sum, oob_cnt), (trees, gains) = jax.lax.scan(
        step, (oob_sum, oob_cnt), subs)
    # [T, K, ...] per-scan-step stacked class trees → flat [T*K, ...]
    forest = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), trees)
    # key_out: the evolved key chain — the chunked capped path threads
    # it so chunked and single-scan forests are bit-identical for the
    # same seed (a NON-binding max_runtime_secs must not change results)
    return forest, oob_sum, oob_cnt, jnp.sum(gains, axis=0), key_out


def _bag_body(bins, nb, ys, w, oob_sum, oob_cnt, key, depth_limit, *,
              tp: TreeParams, sample_rate: float, mtries: int,
              n_class: int):
    mesh = get_mesh()
    sc = scalars_of(tp)._replace(depth_limit=depth_limit)
    kb, kc1, kc2, kt = jax.random.split(key, 4)
    keep = jax.random.bernoulli(kb, sample_rate, shape=w.shape)
    wbag = w * keep.astype(jnp.float32)
    oob = (w > 0) & ~keep
    F = bins.shape[1]
    # per-tree column sampling (col_sample_rate_per_tree), one col forced
    if tp.col_sample_rate < 1.0:
        col_mask = (jax.random.bernoulli(kc1, tp.col_sample_rate, (F,))
                    | (jnp.arange(F) == jax.random.randint(kc2, (), 0, F)))
    else:
        col_mask = jnp.ones((F,), bool)
    trees = []
    gains_tot = jnp.zeros((F,), jnp.float32)
    for k in range(n_class):
        kt, sub = jax.random.split(kt)
        yk = ys[:, k]
        # g=-y, h=1 ⇒ leaf value = Σ w·y / (Σ w + λ): the bagged leaf mean
        tree, nid, gains = grow_tree(bins, nb, wbag, -yk, None,
                                     col_mask, params=tp, mesh=mesh,
                                     mtries=mtries, key=sub, scalars=sc)
        trees.append(tree)
        gains_tot = gains_tot + gains
        pred = leaf_values(tree)[nid]  # routing nid is bag-independent
        oob_sum = oob_sum.at[:, k].add(jnp.where(oob, pred, 0.0))
    oob_cnt = oob_cnt + oob.astype(jnp.float32)
    return stack_trees(trees), oob_sum, oob_cnt, gains_tot


@partial(jax.jit, static_argnames=("B",))
def _predict_deep_forest(stacked, bins, B: int):
    """``predict_forest`` for a stacked ``DeepTree`` forest."""
    def step(acc, tree):
        return acc + frontier.predict_deep(tree, bins, B), None

    total, _ = jax.lax.scan(
        step, jnp.zeros((bins.shape[0],), jnp.float32), stacked)
    return total


def _n_trees(forest) -> int:
    return (forest if isinstance(forest, Tree)
            else forest.top).feat.shape[0]


class DRFModel(Model):
    algo = "drf"

    def __init__(self, params, output, forest, bm: BinnedMatrix,
                 ntrees: int):
        super().__init__(params, output)
        # as grown: a complete ``Tree`` [T*K, D, Lmax], or a
        # ``frontier.DeepTree`` where the depth passed the complete
        # layout (checkpoint restarts append to this form)
        self.grown = forest
        self._complete = forest if isinstance(forest, Tree) else None
        self.bm = bm
        self.ntrees = ntrees

    @property
    def forest(self) -> Tree:
        """The forest as the complete ``Tree`` its readers know. One
        grown past that layout is converted if its realized depth fits
        MAX_COMPLETE_DEPTH, and is a named error otherwise."""
        if self._complete is None:
            depth = int(self.output.get("depth_reached")
                        or frontier.realized_depth(self.grown))
            if depth > MAX_COMPLETE_DEPTH:
                raise frontier.DeepForestError(
                    f"this forest has splits down to level {depth - 1}; "
                    f"the complete tree layout holds {MAX_COMPLETE_DEPTH} "
                    "levels (predict, metrics, varimp and checkpoint "
                    "restart work on it; TreeSHAP, leaf assignment, "
                    "feature frequencies and MOJO/POJO export do not yet)")
            self._complete = frontier.to_complete(
                self.grown, bucket_depth(max(depth, 1)),
                self.bm.nbins_total)
        return self._complete

    def _scoring_forest(self):
        try:
            return self.forest
        except frontier.DeepForestError:
            return self.grown

    def _mean_votes(self, bm: BinnedMatrix):
        """Per-class average tree output [N, K]."""
        B = self.bm.nbins_total
        K = max(1, self.output.get("nclasses", 1)
                if self.output["category"] != ModelCategory.REGRESSION else 1)
        if self.output["category"] == ModelCategory.BINOMIAL:
            K = 1
        forest = self._scoring_forest()
        T = _n_trees(forest) // K
        score = predict_forest if isinstance(forest, Tree) \
            else _predict_deep_forest
        # explicit reciprocal multiply, NOT division: XLA rewrites
        # x / <constant> into x * reciprocal inside a jitted program
        # but keeps true division in eager mode, a 1-ULP drift that
        # breaks the serving bit-identity contract (README §Serving) —
        # with the multiply spelled out, both paths run the same op
        inv_t = jnp.float32(1.0 / T)
        outs = []
        for k in range(K):
            f = jax.tree.map(
                lambda a: a.reshape((T, K) + a.shape[1:])[:, k], forest)
            outs.append(score(f, bm.bins, B) * inv_t)
        return jnp.stack(outs, axis=1)

    def _probs(self, bm: BinnedMatrix):
        cat = self.output["category"]
        votes = self._mean_votes(bm)
        if cat == ModelCategory.BINOMIAL:
            p1 = jnp.clip(votes[:, 0], 0.0, 1.0)
            return jnp.stack([1.0 - p1, p1], axis=1)
        s = jnp.sum(votes, axis=1, keepdims=True)
        return jnp.clip(votes, 0.0, 1.0) / jnp.maximum(s, 1e-12)

    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        bm = rebin_for_scoring(self.bm, frame)
        # the model's ONE compiled scoring program — the same
        # executable the serving tier dispatches, so row-payload
        # predictions match bit-for-bit (Model._serve_jit)
        return self._serve_finish(np.asarray(self._serve_jit()(bm.bins)),
                                  frame.nrows)

    def _score_dev(self, frame: Frame):
        """Device-resident holdout scoring for ml/cv.py light mode —
        see GBMModel._score_dev (one batched fetch per CV sweep instead
        of a blocking host sync per fold)."""
        bm = rebin_for_scoring(self.bm, frame)
        cat = self.output["category"]
        if cat == ModelCategory.REGRESSION:
            return self._mean_votes(bm)[:, 0]
        p = self._probs(bm)
        if cat == ModelCategory.BINOMIAL:
            return p[:, 1]
        return p

    def _serve_dev(self, bins):
        """Device half of the serving fast path (serving/engine.py jits
        this per row bucket): EXACTLY the device math of ``_score_raw``
        on a pre-binned matrix."""
        import types
        bm = types.SimpleNamespace(bins=bins)
        if self.output["category"] == ModelCategory.REGRESSION:
            return self._mean_votes(bm)
        return self._probs(bm)

    def _serve_finish(self, fetched: np.ndarray, n: int) -> Dict[str, np.ndarray]:
        """Host half of the serving fast path: the exact host tail of
        ``_score_raw`` applied to the fetched device output."""
        cat = self.output["category"]
        if cat == ModelCategory.REGRESSION:
            return {"predict": fetched[:n, 0]}
        p = fetched[:n]
        if cat == ModelCategory.BINOMIAL:
            t = self.output.get("default_threshold", 0.5)
            return {"predict": (p[:, 1] >= t).astype(np.int32),
                    "p0": p[:, 0], "p1": p[:, 1]}
        out = {"predict": p.argmax(axis=1).astype(np.int32)}
        for k in range(p.shape[1]):
            out[f"p{k}"] = p[:, k]
        return out

    def predict_leaf_node_assignment(self, frame: Frame) -> Frame:
        """Per-tree terminal node ids (h2o-py predict_leaf_node_assignment
        with type=Node_ID); per-class columns T{t}.C{k} for multinomial."""
        from h2o3_tpu.models.tree import leaf_assignment_frame
        return leaf_assignment_frame(self, frame)

    def feature_frequencies(self, frame: Frame) -> Frame:
        """Per-row feature usage counts on decision paths
        (h2o-py model.feature_frequencies / SharedTreeModel)."""
        from h2o3_tpu.models.tree import feature_frequencies_frame
        return feature_frequencies_frame(self, frame)

    def predict_contributions(self, frame: Frame) -> Frame:
        """TreeSHAP contributions; rows sum to the (unclipped) averaged
        vote — the reference DRF contributions contract."""
        from h2o3_tpu.ml.shap import contributions_frame
        return contributions_frame(self, frame, scale=1.0 / self.ntrees)

    def model_performance(self, frame: Frame, mask_weights=None):
        """``mask_weights``: see GBMModel.model_performance (CV fast
        path holdout metrics on the parent frame)."""
        y = self.output["response"]
        bm = rebin_for_scoring(self.bm, frame)
        w = frame.valid_weights()
        wc = self.params.get("weights_column")
        if wc and wc in frame:
            v = frame.col(wc).numeric_view()
            w = w * jnp.where(jnp.isnan(v), 0.0, v)
        if mask_weights is not None:
            w = w * jnp.asarray(mask_weights, jnp.float32)
        cat = self.output["category"]
        if cat == ModelCategory.REGRESSION:
            yv = frame.col(y).numeric_view()
            w = w * jnp.where(jnp.isnan(yv), 0.0, 1.0)
            yv = jnp.where(jnp.isnan(yv), 0.0, yv)
            return mm.regression_metrics(self._mean_votes(bm)[:, 0], yv, w)
        yv = adapt_domain(frame.col(y), self.output["domain"])
        yv = np.pad(yv, (0, bm.bins.shape[0] - frame.nrows), constant_values=-1)
        w = w * jnp.asarray((yv >= 0).astype(np.float32))
        yv = np.maximum(yv, 0)
        p = self._probs(bm)
        if cat == ModelCategory.BINOMIAL:
            return mm.binomial_metrics(p[:, 1], jnp.asarray(yv.astype(np.float32)), w)
        return mm.multinomial_metrics(p, jnp.asarray(yv), w,
                                      domain=self.output["domain"])

    @property
    def varimp_table(self) -> List:
        return self.output.get("varimp") or []


class DRFEstimator(ModelBuilder):
    """h2o-py H2ORandomForestEstimator-compatible surface."""

    cv_fold_masking = True   # ml/cv.py fast path: folds = masked weights

    algo = "drf"

    DEFAULTS = dict(
        max_runtime_secs=0.0,
        ntrees=50, max_depth=20, min_rows=1.0, nbins=20, nbins_cats=1024,
        mtries=-1, sample_rate=0.632, col_sample_rate_per_tree=1.0,
        min_split_improvement=1e-5, seed=-1, nfolds=0,
        weights_column=None, fold_column=None, fold_assignment="auto",
        keep_cross_validation_models=True,
        keep_cross_validation_predictions=False,
        keep_cross_validation_fold_assignment=False,
        ignored_columns=None, stopping_rounds=0, stopping_metric="auto",
        stopping_tolerance=1e-3, binomial_double_trees=False,
        distribution="auto", calibrate_model=False,
        calibration_frame=None, calibration_method="PlattScaling",
        histogram_type="auto", checkpoint=None,
    )

    # SharedTree checkpoint-non-modifiable parameters (hex/tree/
    # SharedTree CHECKPOINT_NON_MODIFIABLE_FIELDS + DRF's own knobs)
    CHECKPOINT_NON_MODIFIABLE = (
        "max_depth", "min_rows", "nbins", "nbins_cats", "sample_rate",
        "mtries", "histogram_type", "binomial_double_trees")

    def __init__(self, **params):
        merged = dict(self.DEFAULTS)
        unknown = set(params) - set(merged)
        if unknown:
            raise ValueError(f"unknown DRF params: {sorted(unknown)}")
        merged.update(params)
        super().__init__(**merged)

    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job, validation_frame: Optional[Frame] = None) -> Model:
        p = self.params
        category = infer_category(frame, y)
        ht = str(p.get("histogram_type", "auto")).lower()
        ht = {"auto": "quantiles", "quantilesglobal": "quantiles",
              "uniformadaptive": "uniform"}.get(ht, ht)
        rc = frame.col(y)
        w, y_dev, rows = self._training_weights(frame, y)
        # checkpoint restart (SharedTree _checkpoint semantics): reuse
        # the donor's bin edges so its trees stay valid, continue the
        # PRNG key chain, and append trees up to the new ntrees
        ckpt = None
        ck = p.get("checkpoint")
        if ck is not None:
            ckpt = resolve_checkpoint_model("drf", ck, DRFModel)
            if ckpt.output["response"] != y:
                raise checkpoint_error(
                    "drf", "response_column",
                    "Field _response_column cannot be modified if "
                    "checkpoint is provided (checkpoint response "
                    f"mismatch: {ckpt.output['response']!r} vs {y!r})")
            if list(ckpt.bm.names) != list(x):
                raise checkpoint_error(
                    "drf", "ignored_columns",
                    "The predictor set cannot be modified if checkpoint "
                    "is provided (checkpoint feature set mismatch)")
            if ckpt.output["category"] != category:
                raise checkpoint_error(
                    "drf", "response_column",
                    "checkpoint model category mismatch "
                    f"({ckpt.output['category']} vs {category})")
            validate_checkpoint_params("drf", ckpt.params, p,
                                       self.CHECKPOINT_NON_MODIFIABLE)

        shared_bm = getattr(self, "_cv_shared_bm", None)
        with telemetry.span("drf.bin") as sp:
            if ckpt is not None:
                bm, how = rebin_for_scoring(ckpt.bm, frame), "rebin"
            elif shared_bm is not None:
                bm, how = shared_bm, "shared"
            else:
                bm, how = self._binned(frame, x, y, nbins=p["nbins"],
                                       nbins_cats=p["nbins_cats"],
                                       histogram_type=ht)
            sp.annotate(cache=how)

        # trees grow to the depth asked for: the complete layout and its
        # kernels for the shallow levels, the frontier regime
        # (models/frontier.py) below them. The program compiles at the
        # depth BUCKET and masks splits beyond the actual depth —
        # candidates of nearby depths share one compiled forest program
        # (tree.py DEPTH_BUCKETS)
        depth = int(p["max_depth"])
        F = len(x)
        mtries = int(p["mtries"])
        if mtries == -1:
            mtries = (max(1, int(np.sqrt(F)))
                      if category != ModelCategory.REGRESSION
                      else max(1, F // 3))
        elif mtries <= 0:
            mtries = F
        w_scale = rows.w_scale

        tp = TreeParams(
            max_depth=bucket_depth(depth),
            min_rows=float(p["min_rows"]) / w_scale,
            learn_rate=1.0, reg_lambda=0.0,
            min_split_improvement=float(p["min_split_improvement"])
            / w_scale,
            col_sample_rate=float(p["col_sample_rate_per_tree"]),
            nbins_total=bm.nbins_total,
            cat_feats=tuple(bool(v) for v in bm.is_cat),
            pallas=pallas_ops.resolve_tree_mode(),
            # a class indicator under weights of 0 or 1: a row's (w, w·y)
            # are 0 or ±1, whatever the bag keeps of it
            whole_stats=rows.w_whole
            and category != ModelCategory.REGRESSION)
        tp = dataclasses.replace(
            tp, frontier_from=frontier_start(tp, F),
            frontier_sort_every=frontier.SORT_PERIOD)
        N = bm.bins.shape[0]
        n_complete = frontier.complete_levels(N, tp.max_depth,
                                              tp.frontier_from)
        kl = kernel_levels(tp, F)[:n_complete]
        # a forest's trees have a hessian of 1: two statistics a row,
        # in the complete levels' kernel passes and the frontier's alike
        tile, ns, pieces = frontier.hist_plan(tp, N, F, unit_h=True)
        paths = {"levels_kernel": sum(kl),
                 "levels_xla": n_complete - sum(kl),
                 "levels_frontier": tp.max_depth - n_complete,
                 "levels_sorted": frontier.sort_levels(
                     tp.max_depth - n_complete, tp.frontier_sort_every),
                 "frontier_hist": "kernel" if tile else "xla",
                 "hist_operand_rows": ns * pieces}

        # target matrix ys [Npad, K] from the device response (weighted-
        # out rows read 0): the values, or indicators for classification
        with telemetry.span("drf.init", on_device=True, host_bytes=0,
                            rows_out=rows.rows_out):
            if category == ModelCategory.REGRESSION \
                    or category == ModelCategory.BINOMIAL:
                K = 1
                ys = y_dev.astype(jnp.float32)[:, None]
            else:
                K = rc.cardinality
                ys = (y_dev[:, None] == jnp.arange(K, dtype=y_dev.dtype)
                      [None, :]).astype(jnp.float32)

            seed = int(p["seed"]) if int(p["seed"]) >= 0 else 0xD2F
            key = jax.random.PRNGKey(seed)
            ntrees = int(p["ntrees"])
            prior_T = 0
            if ckpt is not None:
                prior_T = _n_trees(ckpt.grown) // max(K, 1)
                if ntrees <= prior_T:
                    raise checkpoint_error(
                        "drf", "ntrees",
                        f"If checkpoint is provided, ntrees ({ntrees}) "
                        f"must exceed the checkpoint model's tree count "
                        f"({prior_T})")
                # _bag_scan's key carry is split once per tree, so
                # prior_T host-side splits reproduce the evolved chain
                # exactly: the appended trees are bit-equal to trees
                # prior_T.. of a single longer run with the same seed
                for _ in range(prior_T):
                    key, _sub = jax.random.split(key)
                ntrees = ntrees - prior_T
        output = {"category": category, "response": y, "names": list(x),
                  "nclasses": rc.cardinality if rc.is_categorical else 1,
                  "domain": rc.domain}
        # max_runtime_secs (Model.Parameters): graceful stop at a chunk
        # boundary, keeping the forest built so far — without a cap the
        # forest trains as ONE fused scan (the LOO-CV fast path needs
        # exactly one dispatch per fold model)
        _cap = float(p.get("max_runtime_secs") or 0.0)
        _deadline = time.time() + _cap
        # the chunk shrinks with per-tree cost so the deadline can bind
        _chunk = trees_per_chunk(tp, N, capped=True) if _cap > 0 else ntrees
        light = getattr(self, "_cv_light", False)
        chunks, oob_sum, oob_cnt, gains_dev = [], 0.0, 0.0, 0.0
        depth_reached = capped = 0
        done = 0
        while done < ntrees:
            kk = min(_chunk, ntrees - done)
            with telemetry.span("drf.chunk", trees=kk, **paths) as sp:
                tr_c, osum, ocnt, g_c, key = _bag_scan(
                    bm.bins, bm.nbins, ys, w, key, jnp.int32(depth),
                    tp=tp, sample_rate=float(p["sample_rate"]),
                    mtries=mtries, n_class=K, ntrees=kk)
                if not light:
                    # one fetch a chunk: what the trees came to
                    got = _fetch_np(frontier.forest_facts(tr_c))
                    depth_reached = max(depth_reached, int(got["depth"]))
                    capped += int(got["capped"])
                    sp.annotate(depth_reached=int(got["depth"]),
                                leaves=int(got["leaves"]),
                                frontier_nodes_max=int(got["nodes_max"]),
                                frontier_rescan_pct=float(
                                    got["rescan_pct"]))
            telemetry.counter("train_iterations_total", algo="drf").inc(kk)
            chunks.append(tr_c)
            oob_sum, oob_cnt = oob_sum + osum, oob_cnt + ocnt
            gains_dev = gains_dev + g_c
            done += kk
            job.update(kk / ntrees, f"tree {done}/{ntrees}")
            if _cap > 0 and time.time() > _deadline and done < ntrees:
                log.info("max_runtime_secs: DRF stopping at %d/%d trees",
                         done, ntrees)
                break
        forest = concat_forests(chunks)
        ntrees = done
        # stays 0: the frontier's capacity comes from the rows, so no
        # tree is stopped by the layout (frontier.frontier_capacity)
        telemetry.counter("drf_depth_capped_total").inc(capped)
        if ckpt is not None:
            shapes = [tuple(a.shape[1:]) for a in jax.tree.leaves(forest)]
            donor = [tuple(a.shape[1:])
                     for a in jax.tree.leaves(ckpt.grown)]
            if type(ckpt.grown) is not type(forest) or donor != shapes:
                raise checkpoint_error(
                    "drf", "training_frame",
                    "checkpoint restart requires a compatible training "
                    f"frame (donor tree layout {donor[0]} vs {shapes[0]})")
            forest = concat_forests([ckpt.grown, forest])
            depth_reached = max(depth_reached,
                                int(ckpt.output.get("depth_reached") or 0))
            prior_oob = getattr(ckpt, "_oob", None)
            if prior_oob is not None and \
                    tuple(prior_oob[0].shape) == tuple(oob_sum.shape):
                # OOB accumulators continue: training metrics of the
                # combined forest are what one longer run would report
                oob_sum = oob_sum + jnp.asarray(prior_oob[0])
                oob_cnt = oob_cnt + jnp.asarray(prior_oob[1])
            else:
                log.warning("drf checkpoint: donor carries no matching "
                            "OOB accumulators; OOB training metrics "
                            "reflect only the appended trees")
            ntrees = ntrees + prior_T
        model = DRFModel(p, output, forest, bm, ntrees)
        if light:
            # near-LOO CV fold fit (ml/cv.py): skip OOB metrics / varimp
            # / calibration — hundreds of folds of those frills (several
            # blocking device syncs each) were the pyunit_cv_carsRF
            # timeout; the fold model itself is discarded right after
            # its holdout scoring (its padded forest would otherwise
            # accumulate into ResourceExhausted). The merged-holdout CV
            # metric is the contract.
            model.output["default_threshold"] = 0.5
            model.output["varimp"] = []
            return model
        model.output["depth_reached"] = depth_reached
        gains_total = np.asarray(gains_dev)
        with telemetry.span("drf.oob"):
            # host-lowered OOB accumulators ride the model so a
            # checkpoint= restart can CONTINUE them (pickled
            # device-independent)
            model._oob = (np.asarray(oob_sum), np.asarray(oob_cnt))
            # rows never out-of-bag drop out via weight
            w_oob = w * (oob_cnt > 0).astype(jnp.float32)
            mean_oob = oob_sum / jnp.maximum(oob_cnt[:, None], 1.0)

        with telemetry.span("drf.metrics"):
            if category == ModelCategory.REGRESSION:
                model.training_metrics = mm.regression_metrics(
                    mean_oob[:, 0], ys[:, 0], w_oob)
            elif category == ModelCategory.BINOMIAL:
                p1 = jnp.clip(mean_oob[:, 0], 0.0, 1.0)
                model.training_metrics = mm.binomial_metrics(
                    p1, ys[:, 0], w_oob)
                model.output["default_threshold"] = \
                    model.training_metrics["max_f1_threshold"]
            else:
                s = jnp.sum(mean_oob, axis=1, keepdims=True)
                probs = jnp.clip(mean_oob, 0.0, 1.0) / jnp.maximum(s, 1e-12)
                model.training_metrics = mm.multinomial_metrics(
                    probs, y_dev, w_oob, domain=rc.domain)

        vi = gains_total
        order = np.argsort(-vi)
        tot = vi.sum() or 1.0
        model.output["varimp"] = [
            (x[i], float(vi[i]), float(vi[i] / max(vi.max(), 1e-12)),
             float(vi[i] / tot)) for i in order]
        if validation_frame is not None:
            model.validation_metrics = model.model_performance(validation_frame)
        from h2o3_tpu.ml.calibration import maybe_calibrate
        maybe_calibrate(model, p, category)
        return model
