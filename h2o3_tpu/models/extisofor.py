"""Extended Isolation Forest — random-hyperplane isolation trees.

Reference: hex/tree/isoforextended/ (~800 LoC) — like IsolationForest
but each split is a random oblique hyperplane ``x·w < b`` with
``extension_level + 1`` nonzero components in w (extension_level = 0
reduces to axis-parallel splits), removing the axis-aligned scoring
bias (Hariri et al.). Scores share the c(n) normalization with
IsolationForest.

TPU redesign: a tree level is one [N, F]·[F] contraction per node batch
— node normals are gathered by the row's node id and the projection is
a masked elementwise product-sum, so the whole forest is dense f32 math
with no gathers over data. The split offset b is drawn uniformly inside
the node sample's projection range, approximated by the global
projection range per node normal (host-free, one pass).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.model import Model, ModelBuilder, ModelCategory
from h2o3_tpu.ops.segments import segment_sum
from h2o3_tpu.parallel.mesh import get_mesh


class ExtTree(NamedTuple):
    normals: jax.Array    # [D, Lmax, F]
    offsets: jax.Array    # [D, Lmax]
    is_split: jax.Array   # [D, Lmax] bool
    leaf: jax.Array       # [2^D] c(count) correction


from h2o3_tpu.models.isofor import _avg_path_correction  # noqa: E402 (shared c(n))


@partial(jax.jit, static_argnames=("depth", "ext"))
def _grow_ext_tree(X, lo, hi, w, key, *, depth: int, ext: int):
    """One extended isolation tree. X: [N, F] standardized; lo/hi: [F]
    per-feature value ranges (split-offset support)."""
    mesh = get_mesh()
    N, F = X.shape
    Lmax = 2 ** (depth - 1) if depth > 0 else 1
    nid = jnp.zeros((N,), jnp.int32)
    normals = jnp.zeros((depth, Lmax, F), jnp.float32)
    offsets = jnp.zeros((depth, Lmax), jnp.float32)
    is_splits = jnp.zeros((depth, Lmax), bool)
    k = min(ext + 1, F)
    for d in range(depth):
        L = 2 ** d
        key, kn, km, kb = jax.random.split(key, 4)
        from h2o3_tpu.models.tree import _level_mtries_mask
        Wn = jax.random.normal(kn, (L, F))
        # keep exactly ext+1 random components per node
        Wn = jnp.where(_level_mtries_mask(km, L, F, k), Wn, 0.0)
        # offset b = w·p for a random point p in the value box
        pu = jax.random.uniform(kb, (L, F))
        pnt = lo[None, :] + pu * (hi - lo)[None, :]
        b = jnp.sum(Wn * pnt, axis=1)
        cnt = segment_sum(nid, w[:, None], n_nodes=L, mesh=mesh)[:, 0]
        split = cnt > 1.0
        normals = normals.at[d, :L].set(Wn)
        offsets = offsets.at[d, :L].set(b)
        is_splits = is_splits.at[d, :L].set(split)
        Wr = normals[d][nid]                     # [N, F]
        proj = jnp.sum(X * Wr, axis=1)
        goleft = jnp.where(is_splits[d][nid], proj < offsets[d][nid], True)
        nid = 2 * nid + jnp.where(goleft, 0, 1)
    leaf_cnt = segment_sum(nid, w[:, None], n_nodes=2 ** depth, mesh=mesh)[:, 0]
    return ExtTree(normals, offsets, is_splits,
                   _avg_path_correction(leaf_cnt))


def _ext_path_length(tree: ExtTree, X):
    N = X.shape[0]
    D = tree.normals.shape[0]
    nid = jnp.zeros((N,), jnp.int32)
    plen = jnp.zeros((N,), jnp.float32)
    for d in range(D):
        isp = tree.is_split[d][nid]
        plen = plen + isp.astype(jnp.float32)
        Wr = tree.normals[d][nid]
        proj = jnp.sum(X * Wr, axis=1)
        goleft = jnp.where(isp, proj < tree.offsets[d][nid], True)
        nid = 2 * nid + jnp.where(goleft, 0, 1)
    return plen + tree.leaf[nid]


@jax.jit
def _ext_forest_mean_length(stacked: ExtTree, X):
    def step(acc, tree):
        return acc + _ext_path_length(tree, X), None
    tot, _ = jax.lax.scan(step, jnp.zeros((X.shape[0],), jnp.float32), stacked)
    return tot / stacked.normals.shape[0]


def _feature_matrix(frame: Frame, names, means=None):
    """Dense [Npad, F] with NA → column-mean imputation."""
    cols = []
    out_means = []
    for i, n in enumerate(names):
        c = frame.col(n)
        v = c.numeric_view()
        if means is None:
            from h2o3_tpu.frame.rollups import rollups
            mu = rollups(c)["mean"] or 0.0
        else:
            mu = means[i]
        out_means.append(mu)
        cols.append(jnp.where(jnp.isnan(v), mu, v))
    return jnp.stack(cols, axis=1), out_means


class ExtendedIsolationForestModel(Model):
    algo = "extendedisolationforest"

    def __init__(self, params, output, forest: ExtTree, c_norm: float,
                 means, features):
        super().__init__(params, output)
        self.forest = forest
        self.c_norm = c_norm
        self.means = means
        self.features = features

    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        X, _ = _feature_matrix(frame, self.features, self.means)
        ml = np.asarray(_ext_forest_mean_length(self.forest, X))[: frame.nrows]
        score = 2.0 ** (-ml / max(self.c_norm, 1e-12))
        return {"anomaly_score": score, "mean_length": ml}

    def model_performance(self, frame: Frame):
        raw = self._score_raw(frame)
        return {"mean_score": float(raw["anomaly_score"].mean()),
                "mean_length": float(raw["mean_length"].mean())}


class ExtendedIsolationForestEstimator(ModelBuilder):
    """h2o-py H2OExtendedIsolationForestEstimator surface
    (h2o-py/h2o/estimators/extended_isolation_forest.py)."""

    algo = "extendedisolationforest"
    supervised = False

    DEFAULTS = dict(
        ntrees=100, sample_size=256, extension_level=0, seed=-1,
        ignored_columns=None, score_tree_interval=0,
    )

    def __init__(self, **params):
        merged = dict(self.DEFAULTS)
        unknown = set(params) - set(merged)
        if unknown:
            raise ValueError(
                f"unknown ExtendedIsolationForest params: {sorted(unknown)}")
        merged.update(params)
        super().__init__(**merged)

    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job, validation_frame: Optional[Frame] = None) -> Model:
        p = self.params
        x = [n for n in x if not frame.col(n).is_categorical] or list(x)
        X, means = _feature_matrix(frame, x)
        ext = int(p["extension_level"])
        if not 0 <= ext <= len(x) - 1:
            raise ValueError(
                f"extension_level must be in [0, {len(x) - 1}]")
        lo = jnp.min(X, axis=0)
        hi = jnp.max(X, axis=0)
        w = frame.valid_weights()
        n = frame.nrows
        psi = int(p["sample_size"])
        bag_rate = min(1.0, psi / max(n, 1))
        depth = int(np.ceil(np.log2(max(psi, 2))))
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 0xE1F
        key = jax.random.PRNGKey(seed)
        ntrees = int(p["ntrees"])
        trees = []
        for t in range(ntrees):
            key, kb, kt = jax.random.split(key, 3)
            keep = jax.random.bernoulli(kb, bag_rate, shape=w.shape)
            trees.append(_grow_ext_tree(X, lo, hi,
                                        w * keep.astype(jnp.float32), kt,
                                        depth=depth, ext=ext))
            job.update(1.0 / ntrees, f"tree {t + 1}/{ntrees}")
        forest = ExtTree(*(jnp.stack([getattr(t, f) for t in trees])
                           for f in ExtTree._fields))
        c_norm = float(_avg_path_correction(jnp.asarray(float(psi))))
        output = {"category": ModelCategory.ANOMALY, "response": None,
                  "names": list(x), "domain": None}
        model = ExtendedIsolationForestModel(p, output, forest, c_norm,
                                             means, list(x))
        model.training_metrics = model.model_performance(frame)
        return model
