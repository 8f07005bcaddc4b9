"""GBM — gradient boosting machine, the flagship TPU algorithm.

Reference: hex/tree/gbm/GBM.java:32 (buildNextKTrees at :464) on the
SharedTree skeleton (hex/tree/SharedTree.java:481 scoreAndBuildTrees):
per iteration compute residuals (ComputePredAndRes), grow K trees via
histogram MRTasks, set leaf gammas (GammaPass), update margins.

TPU redesign: the whole per-iteration pipeline — gradients → D histogram
levels → splits → routing → leaf values → margin update — is the body of
ONE compiled scan over a chunk of trees (`_boost_scan`); the Python loop
(`_run_chunks`) just feeds it chunk after chunk. Rows stay sharded over
the mesh 'data' axis; the only collectives are the psums inside
ops/histogram.py. Nothing leaves the device between trees.

Multinomial: K margin columns, K trees per iteration, softmax gradients —
the reference's per-class tree loop (GBM.java buildNextKTrees "ktrees").
"""

from __future__ import annotations

import time

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np

from h2o3_tpu.core import recovery as _recovery
from h2o3_tpu.core.watchdog import maybe_fail
from h2o3_tpu.frame.binning import BinnedMatrix, rebin_for_scoring
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import metrics as mm
from h2o3_tpu.models.distribution import Distribution, get_distribution
from h2o3_tpu.ml.calibration import maybe_calibrate
from h2o3_tpu.models.model import (EarlyStopper, Model, ModelBuilder,
                                   ModelCategory, RowSummary, adapt_domain,
                                   checkpoint_error, infer_category,
                                   resolve_checkpoint_model,
                                   validate_checkpoint_params)
from h2o3_tpu.models.tree import (Tree, TreeParams, TreeScalars,
                                  bucket_depth, concat_forests,
                                  grow_tree, kernel_levels,
                                  predict_forest, predict_tree,
                                  psum_bytes, select_levels, stack_trees,
                                  stats_plan, trees_per_chunk,
                                  unstack_model_trees)
from h2o3_tpu.ops import pallas as pallas_ops
from h2o3_tpu.parallel.mesh import (data_size, get_mesh, put_sharded,
                                    row_sharding)
from h2o3_tpu import telemetry
from h2o3_tpu.telemetry import observed_jit, stepprof
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.gbm")

# SharedTree checkpoint-non-modifiable parameters (hex/tree/SharedTree
# CHECKPOINT_NON_MODIFIABLE_FIELDS): structural knobs a restart cannot
# change without invalidating the donor model's trees/bin edges
CHECKPOINT_NON_MODIFIABLE = ("max_depth", "min_rows", "nbins",
                             "nbins_cats", "sample_rate")


def _tree_host(t: Tree) -> dict:
    """Device-independent (numpy) image of a stacked forest — the
    FitCheckpointer snapshot payload."""
    return {f: np.asarray(getattr(t, f)) for f in Tree._fields}


def _tree_dev(d: dict) -> Tree:
    return Tree(*(jnp.asarray(d[f]) for f in Tree._fields))


def _tree_keys(key, tree0, ntrees: int):
    """Per-tree PRNG keys derived from the GLOBAL tree index
    (fold_in(key, tree0+i)), not from a per-chunk split: chunk size is a
    scheduling artifact (max_runtime_secs shrinks it, row scale shrinks
    it) and must never change seeded sampling results. ``tree0`` rides
    as a traced scalar so every chunk boundary shares one program."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        tree0 + jnp.arange(ntrees, dtype=jnp.int32))


def _sample_columns(k1, k2, F: int, rate):
    """Per-tree column sampling mask (col_sample_rate_per_tree), with one
    column always forced in so a tree can never go featureless. ``rate``
    is a TRACED scalar (rate >= 1 keeps every column: bernoulli(1) is
    always True) so grid/AutoML candidates share one compilation."""
    mask = jax.random.bernoulli(k1, jnp.clip(rate, 0.0, 1.0), shape=(F,))
    return mask | (jnp.arange(F) == jax.random.randint(k2, (), 0, F))


def _boost_scan(bins, nb, y, w, carry, key, val=None, constraints=None,
                interaction_sets=None, *, tp, sample_rate, ntrees: int,
                tree0: int = 0, dist: Optional[Distribution] = None,
                score: str = "none", n_class: int = 0):
    """``ntrees`` boosting iterations from the global tree index
    ``tree0`` as ONE compiled program — the one un-jitted entry to the
    boosting programs, and the one place where a TreeParams becomes
    traced knobs (_knobs_of) plus a structural static key (_neutral_tp).

    ``carry`` is (margin, validation margin or None); ``val`` is
    (vbins, vy, vw) when ``score`` is "validation". ``n_class`` > 0 grows
    K class trees an iteration on the [N, K] margins. A LIST of
    TreeParams (with as many sample rates, ``key`` [M, 2], margins
    [M, N]) trains M models in the model-batched program.

    Returns (trees, carry, gains, devs): the stacked trees, the advanced
    carry, the split gains ([T, F] a tree; summed to [F] when nothing is
    scored) and the per-tree deviances (None when nothing is scored)."""
    if not isinstance(tp, TreeParams):
        knobs_b = jnp.stack([_knobs_of(t, s)
                             for t, s in zip(tp, sample_rate)])
        trees, margins, gains, devs = _boost_scan_batched_jit(
            bins, nb, y, w, carry[0], key, tree0, knobs_b, constraints,
            interaction_sets, tp=_neutral_tp(tp[0]), dist=dist,
            ntrees=ntrees)
        return trees, (margins, None), gains, devs
    knobs, tp = _knobs_of(tp, sample_rate), _neutral_tp(tp)
    if n_class:
        return _boost_scan_multi_jit(
            bins, nb, y, w, carry, key, tree0, knobs, val,
            interaction_sets, tp=tp, n_class=n_class, ntrees=ntrees,
            score=score)
    return _boost_scan_jit(
        bins, nb, y, w, carry, key, tree0, knobs, val, constraints,
        interaction_sets, tp=tp, dist=dist, ntrees=ntrees, score=score)


def _scan_trees(step, deviance, y, w, carry, keys, val, score: str):
    """The one scan shell of the boosting programs: ``lax.scan`` of
    ``step(margin, vmargin, key) -> (trees, margin, vmargin, gains)``
    over per-tree PRNG keys. Static tree shapes make the stacked Tree
    output exactly what predict_forest consumes; nothing leaves the
    device between trees.

    ``score`` (static) says what every step scores next to the tree it
    grew: "none", "train" or "validation". This is how early stopping
    stays on the fused path: deviance is a cheap elementwise+reduce next
    to histogram tree growth, the host reads back one small vector per
    chunk, applies the score_tree_interval/stopping_rounds policy, and
    truncates the stacked forest at the stop point (the reference scores
    between trees on the driver node, hex/tree/SharedTree.java:481 —
    here the scores ride inside the compiled program)."""

    def body(carry, k):
        trees, margin, vmargin, gains = step(*carry, k)
        if score == "none":
            return (margin, vmargin), (trees, gains)
        if score == "validation":
            dev = deviance(val[1], val[2], vmargin)
        else:
            dev = deviance(y, w, margin)
        return (margin, vmargin), (trees, gains, dev)

    carry, out = jax.lax.scan(body, carry, keys)
    if score == "none":
        # nothing scored means no tree is ever cut from the chunk, so
        # the gains leave the program already summed over its trees
        return out[0], carry, jnp.sum(out[1], axis=0), None
    return out[0], carry, out[1], out[2]


def _scan_single(bins, nb, y, w, carry, keys, knobs, val, constraints,
                 interaction_sets, *, tp: TreeParams, dist: Distribution,
                 score: str):
    """The scan of the single-vector families (one tree an iteration),
    shared by the sequential program and each lane of the batched one.
    With ``score`` "validation" the validation margin is carried
    through the scan too."""

    def step(margin, vmargin, k):
        tree, margin, gains = _boost_step_impl(
            bins, nb, y, w, margin, k, knobs, tp=tp, dist=dist,
            constraints=constraints, interaction_sets=interaction_sets)
        if score == "validation":
            vmargin = vmargin + predict_tree(tree, val[0], tp.nbins_total)
        return tree, margin, vmargin, gains

    def deviance(y_, w_, m):
        return jnp.sum(w_ * dist.deviance(y_, m)) \
            / jnp.maximum(jnp.sum(w_), 1e-12)

    return _scan_trees(step, deviance, y, w, carry, keys, val, score)


@observed_jit("gbm.boost_scan")
@partial(jax.jit, static_argnames=("tp", "dist", "ntrees", "score"))
def _boost_scan_jit(bins, nb, y, w, carry, key, tree0, knobs, val=None,
                    constraints=None, interaction_sets=None, *,
                    tp: TreeParams, dist: Distribution, ntrees: int,
                    score: str):
    return _scan_single(bins, nb, y, w, carry,
                        _tree_keys(key, tree0, ntrees), knobs, val,
                        constraints, interaction_sets, tp=tp, dist=dist,
                        score=score)


@observed_jit("gbm.boost_scan_batched")
@partial(jax.jit, static_argnames=("tp", "dist", "ntrees"))
def _boost_scan_batched_jit(bins, nb, y, w, margins, keys, tree0, knobs_b,
                            constraints=None, interaction_sets=None, *,
                            tp: TreeParams, dist: Distribution,
                            ntrees: int):
    """Model-batched boosting: ``vmap`` over the MODEL axis of a whole
    grid/AutoML shape bucket — ``knobs_b`` [M, 7] numeric knob vectors,
    ``keys`` [M, 2] per-model PRNG keys, ``margins`` [M, Npad] — with
    ``bins``/``y``/``w`` broadcast (shared, un-vmapped). One compiled
    program trains M models where the sequential walk paid M dispatch/
    readback round trips (the driver-bound outer loop of ml/grid.py).

    Every step also emits the training deviance so the host can apply
    per-model early-stop MASKS (truncate each model's stacked forest at
    its stop point) instead of the sequential path's Python breaks.
    Returns ([M, T, ...] stacked trees, [M, Npad] margins, [M, T, F]
    gains, [M, T] deviances)."""
    keys_t = jax.vmap(lambda k: _tree_keys(k, tree0, ntrees))(keys)

    def one(margin, tkeys, knobs):
        trees, (margin, _), gains, devs = _scan_single(
            bins, nb, y, w, (margin, None), tkeys, knobs, None,
            constraints, interaction_sets, tp=tp, dist=dist,
            score="train")
        return trees, margin, gains, devs

    return jax.vmap(one)(margins, keys_t, knobs_b)


@observed_jit("gbm.boost_scan_multi")
@partial(jax.jit, static_argnames=("tp", "n_class", "ntrees", "score"))
def _boost_scan_multi_jit(bins, nb, y_int, w, carry, key, tree0, knobs,
                          val=None, interaction_sets=None, *,
                          tp: TreeParams, n_class: int, ntrees: int,
                          score: str):
    """Fused multinomial boosting: ``ntrees`` iterations x K class trees
    in one compiled scan + device-side multinomial deviance."""

    def step(margins, vmargins, k):
        return _boost_step_multi_impl(
            bins, nb, y_int, w, margins, k, knobs, tp=tp, n_class=n_class,
            interaction_sets=interaction_sets,
            vbins=val[0] if score == "validation" else None,
            vmargins=vmargins)

    def deviance(y_, w_, m):
        py = jnp.take_along_axis(jax.nn.softmax(m, axis=1),
                                 y_[:, None], axis=1)[:, 0]
        return jnp.sum(-2.0 * w_ * jnp.log(jnp.clip(py, 1e-7, 1.0))) \
            / jnp.maximum(jnp.sum(w_), 1e-12)

    return _scan_trees(step, deviance, y_int, w, carry,
                       _tree_keys(key, tree0, ntrees), val, score)


def _knobs_of(tp: TreeParams, sample_rate: float):
    """Traced training knobs: [sample_rate, col_sample_rate, learn_rate,
    min_rows, reg_lambda, min_split_improvement, max_depth]. Keeping
    these OUT of the static jit key means one compiled boosting program
    serves every grid/AutoML candidate of the same depth-BUCKET/nbins
    (max_depth rides as the traced depth_limit; the program compiles at
    bucket_depth(max_depth))."""
    return jnp.asarray([sample_rate, tp.col_sample_rate, tp.learn_rate,
                        tp.min_rows, tp.reg_lambda,
                        tp.min_split_improvement,
                        float(tp.max_depth)], jnp.float32)


def _neutral_tp(tp: TreeParams) -> TreeParams:
    """Structural-only TreeParams for the jit static key (numeric knobs
    travel as traced values; depth compiles at its bucket)."""
    return TreeParams(max_depth=bucket_depth(tp.max_depth), min_rows=0.0,
                      learn_rate=0.0, reg_lambda=0.0,
                      min_split_improvement=0.0, col_sample_rate=1.0,
                      nbins_total=tp.nbins_total,
                      block_rows=tp.block_rows,
                      cat_feats=tp.cat_feats,
                      pallas=tp.pallas)         # static: kernel backend


def _level_paths(tp: TreeParams, n_features: int) -> dict:
    """What a ``gbm.chunk`` span says of the level passes: how many of a
    tree's levels (at its compile depth) ran through the Pallas kernels
    and how many through the XLA sequence (models/tree.kernel_levels),
    and the kernels' histogram operand rows a node
    (models/tree.stats_plan: a boosted tree carries its hessian)."""
    fused = kernel_levels(tp, n_features)
    n_stats, n_pieces = stats_plan(tp, unit_h=False)
    return {"levels_kernel": sum(fused),
            "levels_xla": len(fused) - sum(fused),
            "hist_operand_rows": n_stats * n_pieces}


def _mesh_split(tp: TreeParams, n_rows: int, n_features: int,
                trees: int) -> dict:
    """What a ``gbm.chunk`` span says of how a chunk of ``trees`` trees
    was split over the mesh: the ``data`` shards, the padded rows a
    shard, and the bytes all-reduced over them (models/tree.psum_bytes;
    0 on one shard, where a psum moves nothing). From static shapes: no
    fetch."""
    shards = data_size()
    return {"data_shards": shards, "rows_per_shard": n_rows // shards,
            "psum_bytes": trees * psum_bytes(tp, n_features)
            if shards > 1 else 0}


def _route_paths(forest: Tree) -> dict:
    """What a ``gbm.rescore`` span says of the routing: how many of a
    tree's levels cost selects alone and how many pay real gathers
    (models/tree.select_levels)."""
    sel = select_levels(forest.feat.shape[-2])
    return {"levels_select": sum(sel), "levels_gather": len(sel) - sum(sel)}


def _boost_step_impl(bins, nb, y, w, margin, key, knobs, *, tp, dist,
                     constraints=None, interaction_sets=None):
    """One boosting iteration of a single-vector family — the step the
    scan programs trace."""
    mesh = get_mesh()
    g = dist.grad(y, margin)
    h = dist.hess(y, margin)
    kr, kc1, kc2 = jax.random.split(key, 3)
    keep = jax.random.bernoulli(kr, jnp.clip(knobs[0], 0.0, 1.0),
                                shape=w.shape)
    ws = w * keep.astype(jnp.float32)
    F = bins.shape[1]
    col_mask = _sample_columns(kc1, kc2, F, knobs[1])
    sc = TreeScalars(knobs[3], knobs[4], knobs[5],
                     knobs[6].astype(jnp.int32))
    tree, nid, gains = grow_tree(bins, nb, ws, g, h, col_mask,
                                 params=tp, mesh=mesh,
                                 constraints=constraints,
                                 interaction_sets=interaction_sets,
                                 scalars=sc)
    tree = tree._replace(leaf=knobs[2] * tree.leaf)
    margin = margin + tree.leaf[nid]
    return tree, margin, gains


def _boost_step_multi_impl(bins, nb, y_int, w, margins, key, knobs, *,
                           tp: TreeParams, n_class: int,
                           interaction_sets=None,
                           vbins=None, vmargins=None):
    """One multinomial iteration (K class trees on softmax gradients) —
    the step the K-class scan traces; when ``vbins`` is given the
    validation margins are advanced too."""
    mesh = get_mesh()
    p = jax.nn.softmax(margins, axis=1)
    kr, kc1, kc2 = jax.random.split(key, 3)
    keep = jax.random.bernoulli(kr, jnp.clip(knobs[0], 0.0, 1.0),
                                shape=w.shape)
    ws = w * keep.astype(jnp.float32)
    F = bins.shape[1]
    col_mask = _sample_columns(kc1, kc2, F, knobs[1])
    sc = TreeScalars(knobs[3], knobs[4], knobs[5],
                     knobs[6].astype(jnp.int32))
    trees = []
    gains_tot = jnp.zeros((F,), jnp.float32)
    new_margins = margins
    for k in range(n_class):
        yk = (y_int == k).astype(jnp.float32)
        gk = p[:, k] - yk
        hk = p[:, k] * (1.0 - p[:, k])
        tree, nid, gains = grow_tree(bins, nb, ws, gk, hk, col_mask,
                                     params=tp, mesh=mesh,
                                     interaction_sets=interaction_sets,
                                     scalars=sc)
        tree = tree._replace(leaf=knobs[2] * tree.leaf)
        new_margins = new_margins.at[:, k].add(tree.leaf[nid])
        if vbins is not None:
            vmargins = vmargins.at[:, k].add(
                predict_tree(tree, vbins, tp.nbins_total))
        trees.append(tree)
        gains_tot = gains_tot + gains
    return stack_trees(trees), new_margins, vmargins, gains_tot


def _stop_point(devs, done, k, score_interval, stopper,
                scoring_history) -> int:
    """Apply the interval/stopping policy to a chunk's per-tree
    deviances; returns how many of the chunk's trees to keep."""
    for t_local in range(k):
        t_glob = done + t_local + 1
        if t_glob % score_interval == 0:
            devf = float(devs[t_local])
            scoring_history.append({"ntrees": t_glob, "deviance": devf})
            if stopper.should_stop(devf):
                return t_local + 1
    return k


def _offset_of(p: dict, frame: Frame, npad: int):
    """Per-row margin offset [npad] from the frame's offset_column, or
    None (GBM.java offset handling: a per-row base margin in training;
    hex/Model scoring applies it at predict time too)."""
    oc = p.get("offset_column")
    if not oc or oc not in frame:
        return None
    o = np.nan_to_num(frame.col(oc).to_numpy()).astype(np.float32)
    return jnp.asarray(np.pad(o, (0, npad - len(o))))


def _build_constraints(p, x, frame, category):
    """Monotone constraints vector (GBM.java monotone_constraints;
    numeric features only, like the reference's validation)."""
    mc = p.get("monotone_constraints") or {}
    if isinstance(mc, (list, tuple)):
        # h2o-py serializes this as KeyValue pairs
        # ([{'key': col, 'value': ±1}, ...], water/api/schemas3/KeyValueV3)
        mc = {kv["key"]: kv["value"] for kv in mc}
    if not mc:
        return None
    unknown_cols = set(mc) - set(x)
    if unknown_cols:
        raise ValueError(f"monotone_constraints columns not in "
                         f"predictors: {sorted(unknown_cols)}")
    bad = [c for c in mc if frame.col(c).is_categorical]
    if bad:
        raise ValueError("monotone_constraints require numeric "
                         f"columns; categorical: {sorted(bad)}")
    if category == ModelCategory.MULTINOMIAL:
        raise ValueError("monotone_constraints are not supported "
                         "for multinomial distributions")
    arr = np.zeros(len(x), np.int8)
    for c, d in mc.items():
        arr[x.index(c)] = int(np.sign(d))
    return jnp.asarray(arr)


def _build_interaction_sets(p, x):
    """Interaction-constraint set matrix (GBM interaction_constraints;
    hex/tree/GlobalInteractionConstraints): listed groups may interact
    internally; unlisted features become singleton sets."""
    ic = p.get("interaction_constraints")
    if not ic:
        return None
    unknown_cols = {c for grp in ic for c in grp} - set(x)
    if unknown_cols:
        raise ValueError("interaction_constraints columns not in "
                         f"predictors: {sorted(unknown_cols)}")
    listed = {c for grp in ic for c in grp}
    groups = [list(grp) for grp in ic]
    groups += [[c] for c in x if c not in listed]
    S = np.zeros((len(groups), len(x)), bool)
    for si, grp in enumerate(groups):
        for c in grp:
            S[si, x.index(c)] = True
    return jnp.asarray(S)


class GBMModel(Model):
    algo = "gbm"

    def __init__(self, params, output, forest: Tree, bm: BinnedMatrix,
                 f0: np.ndarray, dist_name: str):
        super().__init__(params, output)
        self.forest = forest          # [T(*K), D, Lmax] stacked
        self.bm = bm                  # training binning spec (edges reused to score)
        self.f0 = f0
        self.dist_name = dist_name

    # margin(s) on a binned matrix
    def _margins(self, bm: BinnedMatrix, offset=None):
        B = bm.nbins_total
        K = self.output.get("nclasses", 2)
        if self.output["category"] == ModelCategory.MULTINOMIAL:
            T = self.forest.feat.shape[0] // K
            outs = []
            for k in range(K):
                f = Tree(*(a.reshape((T, K) + a.shape[1:])[:, k]
                           for a in self.forest))
                outs.append(predict_forest(f, bm.bins, B))
            m = self.f0[None, :] + jnp.stack(outs, axis=1)
            return m if offset is None else m + offset[:, None]
        m = self.f0 + predict_forest(self.forest, bm.bins, B)
        return m if offset is None else m + offset

    def _frame_offset(self, frame: Frame, npad: int):
        return _offset_of(self.params, frame, npad)

    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        bm = rebin_for_scoring(self.bm, frame)
        n = frame.nrows
        off = self._frame_offset(frame, bm.bins.shape[0])
        if off is None:
            # the model's ONE compiled scoring program — the same
            # executable the serving tier dispatches, so row-payload
            # predictions match bit-for-bit (Model._serve_jit)
            return self._serve_finish(_fetch_np(self._serve_jit()(bm.bins)),
                                      n)
        return self._serve_finish(
            _fetch_np(self._link_inv(self._margins(bm, off))), n)

    def _link_inv(self, marg):
        """Margins → p1 / [N, K] class probabilities / prediction."""
        cat = self.output["category"]
        if cat == ModelCategory.BINOMIAL:
            return get_distribution("bernoulli").link_inv(marg)
        if cat == ModelCategory.MULTINOMIAL:
            return jax.nn.softmax(marg, axis=1)
        return get_distribution(self.dist_name, **self.params).link_inv(marg)

    def _score_dev(self, frame: Frame):
        """Device-resident holdout scoring for the near-LOO CV sweep
        (ml/cv.py light mode): the padded device array the CV merge
        needs (p1 / [N,K] probs / prediction) with NO host sync, so
        hundreds of fold scores pipeline through the async dispatch
        queue and the sweep pays one batched fetch at the end."""
        bm = rebin_for_scoring(self.bm, frame)
        return self._link_inv(self._margins(
            bm, self._frame_offset(frame, bm.bins.shape[0])))

    def _serve_dev(self, bins):
        """Device half of the serving fast path (serving/engine.py jits
        this per row bucket): EXACTLY the device math of ``_score_raw``
        on a pre-binned matrix. Offset models take the engine's eager
        fallback, so no offset input rides here."""
        import types
        bm = types.SimpleNamespace(bins=bins,
                                   nbins_total=self.bm.nbins_total)
        return self._link_inv(self._margins(bm))

    def _serve_finish(self, fetched: np.ndarray, n: int) -> Dict[str, np.ndarray]:
        """Host half of the serving fast path: the exact host tail of
        ``_score_raw`` applied to the fetched device output."""
        cat = self.output["category"]
        if cat == ModelCategory.BINOMIAL:
            p1 = fetched[:n]
            t = self.output.get("default_threshold", 0.5)
            return {"predict": (p1 >= t).astype(np.int32),
                    "p0": 1.0 - p1, "p1": p1}
        if cat == ModelCategory.MULTINOMIAL:
            p = fetched[:n]
            out = {"predict": p.argmax(axis=1).astype(np.int32)}
            for k in range(p.shape[1]):
                out[f"p{k}"] = p[:, k]
            return out
        return {"predict": fetched[:n]}

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

    def staged_predict_proba(self, frame: Frame) -> Frame:
        """Cumulative per-stage probabilities (h2o-py
        staged_predict_proba; SharedTreeModel staged scoring): column
        T{t}.C1 after t trees for binomial (p0, matching the reference's
        first-class convention), T{t} for regression."""
        bm = rebin_for_scoring(self.bm, frame)
        n = frame.nrows
        cat = self.output["category"]
        B = bm.nbins_total
        cols = {}
        # stage margins accumulate on device; ONE host fetch at the end
        # (a per-tree fetch costs a host round trip each)
        if cat == ModelCategory.MULTINOMIAL:
            K = self.output.get("nclasses", 2)
            T = self.forest.feat.shape[0] // K
            margins = jnp.broadcast_to(
                jnp.asarray(self.f0)[None, :],
                (bm.bins.shape[0], K)).astype(jnp.float32)
            stages = []
            for t in range(T):
                for k in range(K):
                    tr = Tree(*(a[t * K + k] for a in self.forest))
                    margins = margins.at[:, k].add(
                        predict_tree(tr, bm.bins, B))
                stages.append(jax.nn.softmax(margins, axis=1))
            probs = _fetch_np(jnp.stack(stages))[:, :n]     # [T, n, K]
            for t in range(T):
                for k in range(K):
                    cols[f"T{t + 1}.C{k + 1}"] = probs[t, :, k]
            return Frame.from_numpy(cols)
        T = self.forest.feat.shape[0]
        margin = jnp.full((bm.bins.shape[0],), self.f0, jnp.float32)
        dist = get_distribution(
            "bernoulli" if cat == ModelCategory.BINOMIAL else
            self.dist_name, **self.params)
        off = self._frame_offset(frame, bm.bins.shape[0])
        if off is not None:
            margin = margin + off
        stages = []
        for t in range(T):
            tr = Tree(*(a[t] for a in self.forest))
            margin = margin + predict_tree(tr, bm.bins, B)
            stages.append(dist.link_inv(margin))
        mus = _fetch_np(jnp.stack(stages))[:, :n]           # [T, n]
        for t in range(T):
            if cat == ModelCategory.BINOMIAL:
                cols[f"T{t + 1}.C1"] = 1.0 - mus[t]         # p0 convention
            else:
                cols[f"T{t + 1}"] = mus[t]
        return Frame.from_numpy(cols)

    def predict_contributions(self, frame: Frame) -> Frame:
        """TreeSHAP contributions (h2o-py predict_contributions): feature
        columns + BiasTerm, summing to the raw link-space margin."""
        from h2o3_tpu.ml.shap import contributions_frame
        if self.output["category"] == ModelCategory.MULTINOMIAL:
            raise ValueError("predict_contributions supports only "
                             "regression and binomial models "
                             "(got Multinomial)")
        return contributions_frame(self, frame, bias_offset=float(self.f0))

    def model_performance(self, frame: Frame, mask_weights=None):
        """``mask_weights`` (padded [nrows_padded] float) restricts the
        metric pass to a row subset — the CV fast path scores fold
        holdouts on the parent frame without building a subset frame."""
        y = self.output["response"]
        bm = rebin_for_scoring(self.bm, frame)
        marg = self._margins(bm, self._frame_offset(frame,
                                                    bm.bins.shape[0]))
        w = frame.valid_weights()
        wc_name = self.params.get("weights_column")
        if wc_name and wc_name in frame:
            wc = frame.col(wc_name).numeric_view()
            w = w * jnp.where(jnp.isnan(wc), 0.0, wc)
        if mask_weights is not None:
            w = w * jnp.asarray(mask_weights, jnp.float32)
        cat = self.output["category"]
        if cat in (ModelCategory.BINOMIAL, ModelCategory.MULTINOMIAL):
            yv = adapt_domain(frame.col(y), self.output["domain"])
            yv = np.pad(yv, (0, bm.bins.shape[0] - frame.nrows),
                        constant_values=-1)
            w = w * jnp.asarray((yv >= 0).astype(np.float32))  # NA response out
            yv = np.maximum(yv, 0)
            if cat == ModelCategory.BINOMIAL:
                p = get_distribution("bernoulli").link_inv(marg)
                return mm.binomial_metrics(p, jnp.asarray(yv.astype(np.float32)), w)
            p = jax.nn.softmax(marg, axis=1)
            return mm.multinomial_metrics(p, jnp.asarray(yv), w,
                                          domain=self.output["domain"])
        dist = get_distribution(self.dist_name, **self.params)
        yv = frame.col(y).numeric_view()
        w = w * jnp.where(jnp.isnan(yv), 0.0, 1.0)
        yv = jnp.where(jnp.isnan(yv), 0.0, yv)
        return mm.regression_metrics(dist.link_inv(marg), yv, w,
                                     deviance_fn=lambda yy, pp: dist.deviance(yy, marg))

    @property
    def varimp_table(self) -> List:
        vi = self.output.get("varimp") or []
        return vi


# ---- the pieces of one fit (GBMEstimator._fit and fit_gbm_batched) -------


def _prepare(builder, frame: Frame, x: Sequence[str], y: str, ckpt=None):
    """The preamble of a fit: (w, y_dev, rows, bm) — the device row
    weights and response, the host's summary of both (one fetch; no
    array with a row dimension is made on the host) and the binned
    matrix."""
    p = builder.params
    w, y_dev, rows = builder._training_weights(frame, y)
    if p.get("check_constant_response", True) \
            and rows.y_min is not None and rows.y_min == rows.y_max:
        raise ValueError(
            "Response cannot be constant - check your response "
            "column, or set check_constant_response=False")

    shared_bm = getattr(builder, "_cv_shared_bm", None)
    with telemetry.span("gbm.bin") as sp:
        if ckpt is not None:
            bm, how = rebin_for_scoring(ckpt.bm, frame), "rebin"
        elif shared_bm is not None:
            # CV fold models reuse the main model's full-data bin edges
            # (deliberate: per-fold edge re-sketches cost more than the
            # sketch approximation is worth; the histogram is adaptive
            # per node anyway)
            bm, how = shared_bm, "shared"
        else:
            bm, how = builder._binned(frame, x, y, nbins=p["nbins"],
                                      nbins_cats=p["nbins_cats"])
        sp.annotate(cache=how)
    return w, y_dev, rows, bm


def _tp_of(p: dict, bm: BinnedMatrix, w_scale: float) -> TreeParams:
    return TreeParams(
        max_depth=int(p["max_depth"]),
        min_rows=float(p["min_rows"]) / w_scale,
        learn_rate=float(p["learn_rate"]),
        reg_lambda=float(p["reg_lambda"]) / w_scale,
        min_split_improvement=float(p["min_split_improvement"]) / w_scale,
        col_sample_rate=float(p["col_sample_rate_per_tree"]),
        nbins_total=bm.nbins_total,
        cat_feats=tuple(bool(v) for v in bm.is_cat),
        # 10M+ rows: bigger histogram row blocks — 4096-row blocks
        # put a 12K-iteration inner scan in every tree at 50M and
        # underfeed the MXU contraction
        block_rows=16384 if bm.bins.shape[0] > 8_388_608 else 4096,
        pallas=pallas_ops.resolve_tree_mode())


def _prng_key(p: dict):
    seed = int(p["seed"])
    return jax.random.PRNGKey(seed if seed >= 0 else 0xDEC0DE)


def _init_single(p: dict, frame: Frame, bm: BinnedMatrix, w, y_dev,
                 rows: RowSummary, dist: Distribution, ckpt=None):
    """Where a single-vector fit starts: (off, f0, margin) — the offset
    column on the device, the initial margin value (from the weighted
    mean of the response, by the row summary's sums) and the [Npad]
    start margin."""
    mesh = get_mesh()
    with telemetry.span("gbm.init", on_device=True, host_bytes=0,
                        rows_out=rows.rows_out) as sp:
        mean_y = rows.sum_wy / max(rows.sum_w, 1e-12)
        # init_f is solved WITH the offset in place
        off = _offset_of(p, frame, bm.bins.shape[0])
        if off is not None:
            sp.annotate(host_bytes=int(off.nbytes))
            off = put_sharded(off, row_sharding(mesh))
        if ckpt is not None:
            f0 = ckpt.f0
            margin = put_sharded(ckpt._margins(bm).astype(jnp.float32),
                                 row_sharding(mesh))
            if off is not None:
                margin = margin + off
        elif off is None:
            f0 = np.float32(dist.init_margin(mean_y))
            margin = _start_margin(f0, bm.bins.shape[0], row_sharding(mesh))
        else:
            # Newton solve of the offset-adjusted init
            # (DistributionFactory init task role)
            c = jnp.float32(dist.init_margin(mean_y))
            for _ in range(25):
                gsum = jnp.sum(w * dist.grad(y_dev, off + c))
                hsum = jnp.sum(w * dist.hess(y_dev, off + c))
                c = c - gsum / jnp.maximum(hsum, 1e-12)
            f0 = np.float32(c)
            margin = off + f0
    return off, f0, margin


def _init_multi(bm: BinnedMatrix, rows: RowSummary, ckpt=None):
    """Where a K-class fit starts: (f0, margins) — the [K] log priors,
    from the row summary's weighted class counts over the rows that
    train, and the [Npad, K] start margins."""
    mesh = get_mesh()
    with telemetry.span("gbm.init", on_device=True, host_bytes=0,
                        rows_out=rows.rows_out):
        counts = rows.sum_wy
        pri = np.clip(counts / max(counts.sum(), 1e-12), 1e-10, 1.0)
        if ckpt is not None:
            f0 = ckpt.f0
            margins = jax.device_put(ckpt._margins(bm).astype(jnp.float32),
                                     row_sharding(mesh))
        else:
            f0 = np.log(pri).astype(np.float32)
            margins = _start_margin(f0, bm.bins.shape[0], row_sharding(mesh))
    return f0, margins


def _start_margin(f0, n_rows: int, sharding):
    """[n_rows] (or [n_rows, K]) float32 margins at the initial value,
    made where ``sharding`` puts them: each chip fills its own rows, no
    row-sized array is made on one device first."""
    return jnp.full((n_rows,) + np.shape(f0), f0, jnp.float32,
                    device=sharding)


def _cut_trees(trees: Tree, keep: int, n_class: int = 0) -> Tree:
    """A chunk's stacked trees cut to its first ``keep`` iterations; the
    K-class scan stacks per-iteration [K, ...] trees, [k, K, ...] →
    [keep*K, ...]."""
    if n_class:
        return Tree(*(a[:keep].reshape((keep * n_class,) + a.shape[2:])
                      for a in trees))
    if keep == trees.feat.shape[0]:
        return trees
    return Tree(*(a[:keep] for a in trees))


def _run_chunks(scan, carry, cut, *, tp: TreeParams, n_features: int,
                ntrees: int, tree0: int, stopper: EarlyStopper,
                score_interval: int, job, fc, deadline, light: bool,
                n_class: int = 0):
    """The chunk loop of a sequential fit: the boosting loop as compiled
    scans over tree chunks — the per-tree host round trip (dominant on a
    remote chip) amortizes over a chunk's trees, while the inter-chunk
    host boundary keeps progress reporting, cancellation, the in-fit
    checkpoint, the fault points and the max_runtime_secs deadline live.

    What varies between fits comes in as data: ``scan(carry, ntrees=,
    tree0=)`` (_boost_scan with the fit's arrays bound), the ``carry``
    (margin, validation margin or None) and ``cut(trees, keep)``; a
    K-class scan (``n_class``) grows K trees an iteration.
    Returns (forest, gains_total, scoring_history)."""
    chunk = trees_per_chunk(tp, carry[0].shape[0], deadline is not None)
    paths = _level_paths(tp, n_features)
    chunks: List[Tree] = []
    gains_total = np.zeros(n_features, np.float32)
    scoring_history: List[dict] = []
    done = 0
    if fc is not None:
        needs = {"done", "trees", "margin", "gains_total"}
        if carry[1] is not None:
            needs.add("vm")
        if stopper.enabled:
            needs.update(("stop_hist", "scoring_history"))
        loaded = fc.load(needs)
        if loaded is not None:
            st = loaded[1]
            done = int(st["done"])
            if st["trees"] is not None:
                chunks.append(_tree_dev(st["trees"]))
            carry = (put_sharded(jnp.asarray(st["margin"]),
                                 row_sharding(get_mesh())),
                     jnp.asarray(st["vm"]) if "vm" in needs else None)
            gains_total = st["gains_total"].copy()
            if stopper.enabled:
                stopper.history = list(st["stop_hist"])
                scoring_history = list(st["scoring_history"])

    def snapshot():
        st = {"done": done,
              "trees": _tree_host(concat_forests(chunks)) if chunks else None,
              "margin": _recovery.snapshot_host(carry[0]),
              "gains_total": gains_total.copy(),
              "stop_hist": list(stopper.history),
              "scoring_history": list(scoring_history)}
        if carry[1] is not None:
            st["vm"] = _recovery.snapshot_host(carry[1])
        return st

    while done < ntrees:
        k = min(chunk, ntrees - done)
        stepprof.chunk_begin()
        with telemetry.span("gbm.chunk", trees=k, **paths,
                            **_mesh_split(tp, carry[0].shape[0], n_features,
                                          k * max(n_class, 1))):
            trees, carry, gains, devs = scan(carry, ntrees=k,
                                             tree0=tree0 + done)
            stepprof.compute_done((carry, gains, devs))
        telemetry.counter("train_iterations_total", algo="gbm").inc(k)
        stepprof.chunk_end(trees=k)
        keep = (_stop_point(np.asarray(devs), done, k, score_interval,
                            stopper, scoring_history)
                if stopper.enabled else k)
        chunks.append(cut(trees, keep))
        if not light:
            g = np.asarray(gains)
            gains_total += g if g.ndim == 1 else g[:keep].sum(axis=0)
        done += keep
        job.update(k / ntrees, f"tree {done}/{ntrees}")
        if keep < k:
            # early stop: the fit completes right after; a crash
            # past this point replays from the last boundary and
            # stops at the same tree (deterministic stopper)
            break
        if fc is not None:
            fc.maybe_save(done, snapshot)
        maybe_fail("fit_chunk")
        maybe_fail("device_oom")
        if deadline and time.time() > deadline:
            log.info("max_runtime_secs: GBM stopping at %d/%d trees",
                     done, ntrees)
            break
    return concat_forests(chunks), gains_total, scoring_history


def _finish(model: "GBMModel", bm: BinnedMatrix, y_dev, w, off, dist,
            gains_total: np.ndarray, scoring_history: List[dict],
            validation_frame: Optional[Frame], light: bool = False,
            fc=None) -> "GBMModel":
    """What follows the last tree: the training metrics on the forest
    as kept, the threshold, the varimp table, validation metrics and
    calibration. ``light`` (ml/cv.py large-nfolds folds) skips the
    varimp/metric device syncs — leave-one-out CV pays per-fold for
    each one."""
    p, category = model.params, model.output["category"]
    multinomial = category == ModelCategory.MULTINOMIAL
    if light:
        if not multinomial:
            model.output["default_threshold"] = 0.5
    else:
        # recompute margins from the (possibly stop-truncated) forest —
        # the scan's margin may include discarded trees. The span ends
        # when the device has scored (the metrics below would wait for
        # it anyway)
        with telemetry.span("gbm.rescore", **_route_paths(model.forest)):
            mfin = model._margins(bm, off)
            if multinomial:
                mfin = jax.nn.softmax(mfin, axis=1)
            mfin = jax.block_until_ready(mfin)
        with telemetry.span("gbm.metrics"):
            if multinomial:
                model.training_metrics = mm.multinomial_metrics(
                    mfin, y_dev, w, domain=model.output["domain"])
            elif category == ModelCategory.BINOMIAL:
                model.training_metrics = mm.binomial_metrics(
                    dist.link_inv(mfin), y_dev, w)
                model.output["default_threshold"] = \
                    model.training_metrics["max_f1_threshold"]
            else:
                model.training_metrics = mm.regression_metrics(
                    dist.link_inv(mfin), y_dev, w,
                    deviance_fn=lambda yy, pp: dist.deviance(yy, mfin))
    if fc is not None:
        # training finished: a completed model must never resume
        fc.clear()
    model.output["scoring_history"] = scoring_history
    if light:
        model.output["varimp"] = None
    else:
        # scaled relative importance (hex/VarImp semantics)
        vi, x = gains_total, model.output["names"]
        tot = vi.sum() or 1.0
        model.output["varimp"] = [
            (x[i], float(vi[i]), float(vi[i] / max(vi.max(), 1e-12)),
             float(vi[i] / tot)) for i in np.argsort(-vi)]
    if validation_frame is not None:
        model.validation_metrics = model.model_performance(validation_frame)
    maybe_calibrate(model, p, category)
    return model


class GBMEstimator(ModelBuilder):
    """h2o-py H2OGradientBoostingEstimator-compatible surface
    (h2o-py/h2o/estimators/gbm.py)."""

    algo = "gbm"
    cv_fold_masking = True   # ml/cv.py fast path: folds = masked weights

    DEFAULTS = dict(
        max_runtime_secs=0.0,
        ntrees=50, max_depth=5, min_rows=10.0, learn_rate=0.1,
        sample_rate=1.0, col_sample_rate_per_tree=1.0,
        nbins=64, nbins_cats=1024, distribution="auto",
        custom_distribution_func=None,
        # reg_lambda=0: the reference GammaPass has no ridge term
        # (hex/tree/gbm/GBM.java leaf gamma = sum g / sum h); the
        # xgboost facade passes its own lambda
        min_split_improvement=1e-5, seed=-1, reg_lambda=0.0,
        nfolds=0, weights_column=None, fold_column=None,
        offset_column=None, fold_assignment="auto",
        keep_cross_validation_models=True,
        keep_cross_validation_predictions=False,
        keep_cross_validation_fold_assignment=False,
        ignored_columns=None, tweedie_power=1.5, quantile_alpha=0.5,
        huber_alpha=0.9, stopping_rounds=0, stopping_metric="auto",
        stopping_tolerance=1e-3, score_tree_interval=0, checkpoint=None,
        monotone_constraints=None, interaction_constraints=None,
        calibrate_model=False, calibration_frame=None,
        calibration_method="PlattScaling",
        check_constant_response=True,
    )

    def __init__(self, **params):
        merged = dict(self.DEFAULTS)
        unknown = set(params) - set(merged)
        if unknown:
            raise ValueError(f"unknown GBM params: {sorted(unknown)}")
        merged.update(params)
        super().__init__(**merged)

    def _resolve_distribution(self, category: str) -> str:
        d = self.params["distribution"]
        if d != "auto":
            return d
        return {"Binomial": "bernoulli", "Multinomial": "multinomial",
                "Regression": "gaussian"}[category]

    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job, validation_frame: Optional[Frame] = None) -> Model:
        p = self.params
        category = infer_category(frame, y)
        multinomial = category == ModelCategory.MULTINOMIAL
        dist_name = self._resolve_distribution(category)
        # light mode (ml/cv.py large-nfolds folds): skip varimp/metric
        # device syncs — leave-one-out CV pays per-fold for each one
        light = bool(getattr(self, "_cv_light", False))

        # checkpoint restart (SharedTree _checkpoint via
        # hex/util/CheckpointUtils + ReconstructTreeState): reuse the
        # prior model's binning so its trees stay valid, resume margins
        # from its predictions, and append trees up to the new ntrees.
        ckpt: Optional[GBMModel] = None
        ck = p.get("checkpoint")
        if ck is not None:
            ckpt = resolve_checkpoint_model("gbm", ck, GBMModel)
            if ckpt.output["response"] != y:
                raise checkpoint_error(
                    "gbm", "response_column",
                    "Field _response_column cannot be modified if "
                    "checkpoint is provided (checkpoint response "
                    f"mismatch: {ckpt.output['response']!r} vs {y!r})")
            if list(ckpt.bm.names) != list(x):
                raise checkpoint_error(
                    "gbm", "ignored_columns",
                    "The predictor set cannot be modified if checkpoint "
                    "is provided (checkpoint feature set mismatch)")
            if ckpt.output["category"] != category:
                raise checkpoint_error(
                    "gbm", "response_column",
                    "checkpoint model category mismatch "
                    f"({ckpt.output['category']} vs {category})")
            if ckpt.dist_name != dist_name:
                raise checkpoint_error(
                    "gbm", "distribution",
                    "Field _distribution cannot be modified if "
                    "checkpoint is provided: distribution cannot change "
                    f"across checkpoint restart ({ckpt.dist_name} vs "
                    f"{dist_name})")
            validate_checkpoint_params("gbm", ckpt.params, p,
                                       CHECKPOINT_NON_MODIFIABLE)

        w, y_dev, rows, bm = _prepare(self, frame, x, y, ckpt)
        tp = _tp_of(p, bm, rows.w_scale)
        constraints = _build_constraints(p, x, frame, category)
        interaction_sets = _build_interaction_sets(p, x)

        ntrees = int(p["ntrees"])
        # max_runtime_secs (Model.Parameters._max_runtime_secs): a
        # GRACEFUL stop at the next chunk boundary keeping the trees
        # built so far — the reference returns the partial model, it
        # does not discard it
        cap = float(p.get("max_runtime_secs") or 0.0)
        deadline = (time.time() + cap) if cap > 0 else None
        rc = frame.col(y)
        K = rc.cardinality if multinomial else 0
        prior_T = 0
        if ckpt is not None:
            prior_T = ckpt.forest.feat.shape[0] // max(K, 1)
            if ntrees <= prior_T:
                raise checkpoint_error(
                    "gbm", "ntrees",
                    f"If checkpoint is provided, ntrees ({ntrees}) must "
                    f"exceed the checkpoint model's tree count "
                    f"({prior_T})")
            ntrees = ntrees - prior_T
        output = {"category": category, "response": y, "names": list(x),
                  "nclasses": rc.cardinality if rc.is_categorical else 1,
                  "domain": rc.domain}
        stopper = EarlyStopper(int(p["stopping_rounds"]),
                               float(p["stopping_tolerance"]))
        # in-fit checkpointer (core/recovery.py): every K trees the
        # chunk host boundary persists device-independent partial state
        # (forest so far, margins, PRNG-independent counters, early-stop
        # + scoring history) so a killed fit resumes bit-identically.
        # CV fold fits skip it — their params fingerprint would collide
        # and fold models are discarded after holdout scoring anyway.
        fc = None
        if not light and getattr(self, "_cv_fold_mask", None) is None:
            fc = _recovery.fit_checkpointer("gbm", p, y, x, frame.nrows,
                                            default_every=25)

        if multinomial:
            dist, off = None, None
            f0, margin = _init_multi(bm, rows, ckpt)
        else:
            dist = (get_distribution("bernoulli")
                    if category == ModelCategory.BINOMIAL
                    else get_distribution(dist_name, **p))
            off, f0, margin = _init_single(p, frame, bm, w, y_dev, rows,
                                           dist, ckpt)
            output["init_f"] = float(f0)

        # early stopping watches the validation set when given, else
        # training (reference ScoreKeeper semantics,
        # hex/tree/SharedTree.java); without a stopper nothing is scored
        val, val_margin, score = None, None, "none"
        if stopper.enabled or multinomial:
            score = "train"
        if validation_frame is not None and stopper.enabled:
            score = "validation"
            vbm = rebin_for_scoring(bm, validation_frame)
            n_vpad = vbm.bins.shape[0] - validation_frame.nrows
            val_w = validation_frame.valid_weights()
            vc = validation_frame.col(y)
            if vc.is_categorical:
                vy = np.pad(adapt_domain(vc, rc.domain), (0, n_vpad),
                            constant_values=-1)
                val_w = val_w * jnp.asarray((vy >= 0).astype(np.float32))
                val_y = jnp.asarray(np.maximum(vy, 0).astype(np.float32))
            else:
                vy = vc.numeric_view()
                val_w = val_w * jnp.where(jnp.isnan(vy), 0.0, 1.0)
                val_y = jnp.where(jnp.isnan(vy), 0.0, vy)
            val = (vbm.bins, val_y.astype(jnp.int32) if multinomial
                   else val_y, val_w)
            if ckpt is not None:   # resume incl. the prior forest's part
                val_margin = ckpt._margins(vbm).astype(jnp.float32)
            else:
                val_margin = _start_margin(f0, vbm.bins.shape[0],
                                           row_sharding(get_mesh()))
            voff = None if multinomial else _offset_of(
                p, validation_frame, vbm.bins.shape[0])
            if voff is not None:
                val_margin = val_margin + voff

        forest, gains_total, scoring_history = _run_chunks(
            partial(_boost_scan, bm.bins, bm.nbins, y_dev, w,
                    key=_prng_key(p), val=val, constraints=constraints,
                    interaction_sets=interaction_sets, tp=tp,
                    sample_rate=float(p["sample_rate"]), dist=dist,
                    score=score, n_class=K),
            (margin, val_margin), partial(_cut_trees, n_class=K),
            tp=tp, n_features=len(x), ntrees=ntrees, tree0=prior_T,
            stopper=stopper, score_interval=int(p["score_tree_interval"]) or 5,
            job=job, fc=fc, deadline=deadline, light=light, n_class=K)
        if ckpt is not None:
            forest = Tree(*(jnp.concatenate([getattr(ckpt.forest, f),
                                             getattr(forest, f)])
                            for f in Tree._fields))
        model = GBMModel(p, output, forest, bm, f0,
                         "multinomial" if multinomial else dist_name)
        return _finish(model, bm, y_dev, w, off, dist, gains_total,
                       scoring_history, validation_frame, light, fc)


# ---- model-batched training (parallel/model_batch.py trainer) ----------


def fit_gbm_batched(builder_cls, params_list: List[dict], frame: Frame,
                    y: Optional[str] = None, x: Optional[Sequence[str]] = None,
                    validation_frame: Optional[Frame] = None) -> List[Model]:
    """Train a whole shape bucket of GBM hyperparameter combos as ONE
    vmapped boosting program (_boost_scan_batched_jit): the shared
    preamble (binning, weights, init margin) runs once, per-model numeric
    knobs stack into a [M, 7] matrix, and the host touches the device
    once per tree CHUNK for the whole bucket instead of once per model
    per chunk.

    Raises parallel.model_batch.BatchIneligible for anything the vmapped
    program cannot express (CV, checkpoints, multinomial, runtime caps,
    validation-frame early stopping) — the caller falls back to the
    sequential per-combo path, so semantics are always preserved.
    Models return in ``params_list`` order with the same outputs the
    sequential path produces (metrics, varimp, scoring history,
    threshold), matching it within float tolerance."""
    from h2o3_tpu.parallel.model_batch import BATCHABLE_KNOBS, BatchIneligible

    builders = [builder_cls(**p) for p in params_list]
    M = len(builders)
    b0 = builders[0]
    p0 = b0.params
    batchable = BATCHABLE_KNOBS["gbm"]
    for b in builders[1:]:
        for k, v in b.params.items():
            if k not in batchable and v != p0.get(k):
                raise BatchIneligible(f"structural param '{k}' varies")
    for b in builders:
        p = b.params
        if int(p.get("nfolds") or 0) >= 2 or p.get("fold_column"):
            raise BatchIneligible("cross-validation")
        if p.get("checkpoint") is not None:
            raise BatchIneligible("checkpoint restart")
        if p.get("custom_distribution_func"):
            raise BatchIneligible("custom distribution")
        if float(p.get("max_runtime_secs") or 0.0) > 0:
            raise BatchIneligible("per-model runtime cap")
    depths = [int(b.params["max_depth"]) for b in builders]
    if len({bucket_depth(d) for d in depths}) != 1:
        raise BatchIneligible("max_depth spans compile depth buckets")

    x = b0.resolve_x(frame, x, y)
    category = infer_category(frame, y)
    if category == ModelCategory.MULTINOMIAL:
        raise BatchIneligible("multinomial (per-class tree loop)")
    dist_name = b0._resolve_distribution(category)
    stopper_on = int(p0["stopping_rounds"]) > 0
    if stopper_on and validation_frame is not None:
        # validation-side stopping carries a second margin through the
        # scan — sequential path handles it; not vmapped (yet)
        raise BatchIneligible("validation-frame early stopping")

    w, y_dev, rows, bm = _prepare(b0, frame, x, y)
    tps = [_tp_of(b.params, bm, rows.w_scale) for b in builders]
    rates = [float(b.params["sample_rate"]) for b in builders]
    keys = jnp.stack([_prng_key(b.params) for b in builders])
    constraints = _build_constraints(p0, x, frame, category)
    interaction_sets = _build_interaction_sets(p0, x)
    ntrees = int(p0["ntrees"])
    score_interval = int(p0["score_tree_interval"]) or 5
    stoppers = [EarlyStopper(int(p0["stopping_rounds"]),
                             float(p0["stopping_tolerance"]))
                for _ in range(M)]
    histories: List[List[dict]] = [[] for _ in range(M)]

    dist = (get_distribution("bernoulli")
            if category == ModelCategory.BINOMIAL
            else get_distribution(dist_name, **p0))
    off, f0, margin1 = _init_single(p0, frame, bm, w, y_dev, rows, dist)
    margins = jnp.broadcast_to(margin1, (M,) + margin1.shape)

    # The batched chunk loop stays its own: a model that stops is MASKED
    # (its lane keeps running, its later trees are discarded) where the
    # sequential loop breaks, and there is no job, checkpointer or
    # deadline here. It shares the chunk-size rule (no deadline —
    # runtime-capped fits are ineligible above).
    chunk = trees_per_chunk(tps[0], bm.bins.shape[0], capped=False)
    paths = _level_paths(tps[0], bm.bins.shape[1])
    chunk_trees: List[List[Tree]] = [[] for _ in range(M)]
    gains_tot = np.zeros((M, len(x)), np.float32)
    stopped = [False] * M
    done = 0
    while done < ntrees and not all(stopped):
        k = min(chunk, ntrees - done)
        alive = M - sum(stopped)
        stepprof.chunk_begin()
        with telemetry.span("gbm.chunk", trees=k, batch=M, **paths,
                            **_mesh_split(tps[0], bm.bins.shape[0], len(x),
                                          k * M)):
            tr_b, (margins, _), gains_b, devs_b = _boost_scan(
                bm.bins, bm.nbins, y_dev, w, (margins, None), keys, None,
                constraints, interaction_sets, tp=tps, sample_rate=rates,
                dist=dist, ntrees=k, tree0=done)
            stepprof.compute_done((margins, devs_b))
        telemetry.counter("train_iterations_total",
                          algo="gbm").inc(k * alive)
        stepprof.chunk_end(trees=k, batch=M)
        devs_h = np.asarray(devs_b) if stopper_on else None
        gains_h = np.asarray(gains_b)
        for m in range(M):
            if stopped[m]:
                continue
            keep = (_stop_point(devs_h[m], done, k, score_interval,
                                stoppers[m], histories[m])
                    if stopper_on else k)
            chunk_trees[m].append(unstack_model_trees(tr_b, m, keep))
            gains_tot[m] += gains_h[m, :keep].sum(axis=0)
            if keep < k:
                stopped[m] = True
        done += k

    # ---- per-model unstack into ordinary Model objects ---------------
    rc = frame.col(y)
    output_base = {"category": category, "response": y, "names": list(x),
                   "nclasses": rc.cardinality if rc.is_categorical else 1,
                   "domain": rc.domain, "init_f": float(f0)}
    models: List[Model] = []
    t_done = time.time()
    for m in range(M):
        model = GBMModel(builders[m].params, dict(output_base),
                         concat_forests(chunk_trees[m]), bm, f0, dist_name)
        _finish(model, bm, y_dev, w, off, dist, gains_tot[m], histories[m],
                validation_frame)
        model.output["run_time"] = time.time() - t_done
        models.append(model)
    return models
