"""DataInfo — row-wise design-matrix view with one-hot + standardization.

Reference: hex/DataInfo.java:16 — GLM/DeepLearning/GLRM iterate rows
through a view that expands categoricals to indicator columns (skipping
the first level unless useAllFactorLevels), imputes NAs (mean imputation
default) and standardizes numerics. TPU-native, two layouts:

- dense: the expansion materialized once into an [Npad, P] f32 device
  matrix, row-sharded — what the dense layers, GAM, PCA, GLRM and an
  all-numeric GLM read;
- codes (``CodesDesign``): the factor columns kept as the frame holds
  them, integer codes with each column's offset into the coefficients,
  and only the numerics as a dense matrix — what hex/DataInfo itself
  keeps (categoricals as level indices) and what a GLM with factor
  predictors reads (``ops/gram.py`` forms X'WX from the codes). An
  [Npad, P] matrix of 756 indicator columns at 116M rows is 351 GB.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.rollups import rollups
from h2o3_tpu.parallel.mesh import row_sharding
from h2o3_tpu.telemetry import annotate


@partial(jax.jit, static_argnames=("spec", "standardize"))
def _design_device(datas, nas, stats, *, spec: tuple, standardize: bool):
    """All columns → the dense [Npad, P] design matrix in ONE compiled
    program. ``spec`` per column: ("cat", first_level, cardinality) or
    ("num",); ``stats``: ONE ``[cols, 2]`` float32 array, row ``i`` the
    (mu, sd) of column ``i`` ((0, 1) and unread for cats).
    """
    blocks = []
    for i, sp in enumerate(spec):
        na = nas[i]
        if sp[0] == "cat":
            _, first, card = sp
            code = datas[i].astype(jnp.int32)
            levels = jnp.arange(first, card, dtype=jnp.int32)
            oh = (code[:, None] == levels[None, :]).astype(jnp.float32)
            blocks.append(jnp.where(na[:, None], 0.0, oh))
        else:
            mu, sd = stats[i, 0], stats[i, 1]
            x = datas[i].astype(jnp.float32)
            x = jnp.where(na | jnp.isnan(x), mu, x)   # mean imputation
            if standardize:
                x = (x - mu) / sd
            blocks.append(x[:, None])
    return jnp.concatenate(blocks, axis=1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CodesDesign:
    """The design matrix as codes: what ``X`` is, never materialized.

    Row ``i`` of the matrix it stands for has, for factor ``f`` with
    ``factors[f] = (offset, first, card)``, a 1 in column
    ``offset + code - first`` where ``first <= code < card`` and the row
    is not NA in that column (no 1 at all otherwise: the dropped first
    level, or an NA row — the dense view's all-zero indicator block);
    and ``dense[i, j]`` in column ``dense_cols[j]``. ``codes`` / ``nas``
    are the frame's own row-sharded arrays, not copies.

    A pytree: the arrays are leaves, the layout is static, so a jitted
    function compiles once a layout and the rows ride the data axis.
    ``gram_kernel`` is how ``ops/gram.py`` forms the factor Gram — the
    ``ops/pallas`` mode a fit resolved (``gram.with_gram_kernel``), static
    like the layout, so that another mode compiles another program.
    """
    codes: Tuple[jax.Array, ...]     # per factor [Npad] integer codes
    nas: Tuple[jax.Array, ...]       # per factor [Npad] bool
    dense: jax.Array                 # [Npad, len(dense_cols)] float32
    factors: Tuple[Tuple[int, int, int], ...] = ()
    dense_cols: Tuple[int, ...] = ()
    p: int = 0                       # columns of the matrix it stands for
    gram_kernel: str = "off"         # off | native | interpret

    def tree_flatten(self):
        return ((self.codes, self.nas, self.dense),
                (self.factors, self.dense_cols, self.p, self.gram_kernel))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.dense.shape[0], self.p)

    @property
    def nbytes(self) -> int:
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in jax.tree_util.tree_leaves(self))

    @property
    def cat_levels(self) -> int:
        """Indicator columns the factors stand for."""
        return sum(card - first for _, first, card in self.factors)

    def with_intercept(self) -> "CodesDesign":
        """The same design with a last column of ones (GLM's ``X1``)."""
        ones = jnp.ones((self.dense.shape[0], 1), jnp.float32)
        return dataclasses.replace(
            self, dense=jnp.concatenate([self.dense, ones], axis=1),
            dense_cols=self.dense_cols + (self.p,), p=self.p + 1)


@dataclasses.dataclass
class DataInfo:
    names: List[str]                 # source columns
    coef_names: List[str]            # expanded coefficient names
    X: Union[jax.Array, CodesDesign]  # [Npad, P] (row-sharded)
    is_cat: np.ndarray
    cat_offsets: np.ndarray          # start index of each cat block
    num_means: np.ndarray            # imputation means of numeric cols
    num_sigmas: np.ndarray
    domains: List[Optional[List[str]]]
    standardize: bool
    use_all_factor_levels: bool
    nrows: int

    @property
    def P(self) -> int:
        return self.X.shape[1]


def build_datainfo(frame: Frame, features: Sequence[str],
                   standardize: bool = True,
                   use_all_factor_levels: bool = False,
                   missing_values_handling: str = "mean_imputation",
                   stats_override: Optional[dict] = None,
                   codes: bool = False) -> DataInfo:
    """Expand ``features`` into the design matrix.

    ``stats_override`` carries training-time means/sigmas/domains when
    adapting a scoring frame (adaptTestForTrain role). ``codes``: ``X``
    is a ``CodesDesign`` where a feature is categorical (the numerics
    alone are built dense); names, offsets and statistics are the dense
    view's either way.
    """
    cols = [frame.col(n) for n in features]
    is_cat = np.array([c.is_categorical for c in cols], dtype=bool)
    coef_names: List[str] = []
    cat_offsets = []
    num_means, num_sigmas, num_coefs = [], [], []
    domains: List[Optional[List[str]]] = []
    shard = row_sharding()

    # host pass: names/domains, the columns' resident device arrays and
    # every column's (mu, sd) in ONE host array, which reaches the chip
    # as one argument of the ONE jitted program (_design_device). No
    # device operation per column: an eager op, or a scalar converted on
    # its own, is a transfer of its own — 0.4 ms each on a
    # remote-attached chip, 0.61 s of a 0.64 s build at 784 columns
    datas, nas, spec = [], [], []
    stats = np.tile(np.float32([0.0, 1.0]), (len(cols), 1))
    host_arrays, host_bytes = (1, stats.nbytes) if cols else (0, 0)
    for i, c in enumerate(cols):
        if is_cat[i]:
            if stats_override is not None:
                dom = stats_override["domains"][i]
                from h2o3_tpu.models.model import adapt_domain
                adapted = np.pad(adapt_domain(c, dom),
                                 (0, frame.nrows_padded - frame.nrows),
                                 constant_values=-1)
                code, na = np.maximum(adapted, 0).astype(np.int32), \
                    adapted < 0
                datas.append(jax.device_put(code, shard))
                nas.append(jax.device_put(na, shard))
                host_arrays += 2
                host_bytes += code.nbytes + na.nbytes
            else:
                dom = c.domain or []
                datas.append(c.data)
                nas.append(c.na_mask)
            domains.append(dom)
            first = 0 if use_all_factor_levels else 1
            card = max(len(dom), 1)
            cat_offsets.append(len(coef_names))
            # NA row: all-zero indicator block (majority-level impute would
            # also be valid; the reference's default is mean imputation which
            # for indicators is the level frequency — zero is the simple,
            # consistent choice and is masked by skip rows when requested)
            spec.append(("cat", first, card))
            coef_names += [f"{c.name}.{dom[l]}" for l in range(first, card)]
        else:
            domains.append(None)
            if stats_override is not None:
                mu = stats_override["num_means"][len(num_means)]
                sd = stats_override["num_sigmas"][len(num_sigmas)]
            else:
                r = rollups(c)
                mu, sd = r["mean"], (r["sigma"] or 1.0)
            sd = sd if sd > 0 else 1.0
            num_means.append(mu)
            num_sigmas.append(sd)
            spec.append(("num",))
            stats[i] = (float(mu), float(sd))
            datas.append(c.data)
            nas.append(c.na_mask)
            num_coefs.append(len(coef_names))
            coef_names.append(c.name)

    # a factor whose only level is the dropped one has no column
    factors = [(int(off), i) for off, i in
               zip(cat_offsets, np.flatnonzero(is_cat))
               if spec[i][2] > spec[i][1]]
    if codes and factors:
        num = np.flatnonzero(~is_cat)
        dense = (_design_device(tuple(datas[i] for i in num),
                                tuple(nas[i] for i in num), stats[num],
                                spec=tuple(spec[i] for i in num),
                                standardize=bool(standardize))
                 if num.size else
                 jnp.zeros((frame.nrows_padded, 0), jnp.float32))
        X = CodesDesign(
            codes=tuple(datas[i] for _, i in factors),
            nas=tuple(nas[i] for _, i in factors),
            dense=jax.device_put(dense, shard),
            factors=tuple((off, spec[i][1], spec[i][2])
                          for off, i in factors),
            dense_cols=tuple(num_coefs),
            p=len(coef_names))
    else:
        X = (_design_device(tuple(datas), tuple(nas), stats,
                            spec=tuple(spec), standardize=bool(standardize))
             if cols else jnp.zeros((frame.nrows_padded, 0), jnp.float32))
        X = jax.device_put(X, shard)
    annotate(columns=len(cols), host_arrays=host_arrays,
             host_bytes=host_bytes)
    return DataInfo(
        names=list(features), coef_names=coef_names, X=X, is_cat=is_cat,
        cat_offsets=np.asarray(cat_offsets, np.int64),
        num_means=np.asarray(num_means), num_sigmas=np.asarray(num_sigmas),
        domains=domains, standardize=standardize,
        use_all_factor_levels=use_all_factor_levels, nrows=frame.nrows)


def design_row_bytes(frame: Frame, features: Sequence[str],
                     use_all_factor_levels: bool = False,
                     codes: bool = False) -> int:
    """Device bytes a row of the ``X`` that ``build_datainfo`` builds
    with these arguments: 4 B a column of the dense matrix — every
    indicator and numeric, or with ``codes`` where a factor has a column
    the numerics alone (the codes are the frame's own arrays)."""
    cols = [frame.col(n) for n in features]
    first = 0 if use_all_factor_levels else 1
    levels = sum(max(len(c.domain or []), 1) - first
                 for c in cols if c.is_categorical)
    nums = sum(not c.is_categorical for c in cols)
    return 4 * (nums if codes and levels > 0 else nums + levels)


def stats_of(di: DataInfo) -> dict:
    """Training stats needed to rebuild the view on a scoring frame."""
    return {"num_means": di.num_means, "num_sigmas": di.num_sigmas,
            "domains": di.domains}


def coef_stats(di: DataInfo):
    """Per-coefficient (mean, sd) aligned with coef_names — identity
    (0, 1) for one-hot indicator coefs, the standardization stats for
    numerics. Lets GLM report both standardized and de-standardized
    coefficients (hex/glm GLMModel coefficients_table)."""
    mus, sds = [], []
    ni = 0
    for i, cat in enumerate(di.is_cat):
        if cat:
            dom = di.domains[i] or []
            first = 0 if di.use_all_factor_levels else 1
            k = max(len(dom), 1) - first
            mus += [0.0] * k
            sds += [1.0] * k
        else:
            mus.append(float(di.num_means[ni]))
            sds.append(float(di.num_sigmas[ni]))
            ni += 1
    return np.asarray(mus), np.asarray(sds)
