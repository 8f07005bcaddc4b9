"""Model / ModelBuilder abstractions — the hex.Model / hex.ModelBuilder layer.

Reference: hex/Model.java (parameters/output/scoring, adaptTestForTrain at
Model.java:1850, BigScore bulk scorer at Model.java:2085) and
hex/ModelBuilder.java:25 (trainModel at :374 launches a Driver Job;
cross-validation orchestration at :603). Here the same lifecycle:

    builder = GBMEstimator(**params)
    model   = builder.train(frame, y="col", x=[...])   # Job-wrapped
    preds   = model.predict(frame)                      # Frame of predictions
    mm      = model.model_performance(frame)            # ModelMetrics

Categorical response/feature adaptation follows adaptTestForTrain: test
categorical codes are remapped into training domains (unseen level → NA).
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from functools import partial
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np
from h2o3_tpu.parallel.mesh import real_rows, row_sharding

from h2o3_tpu.core.job import Job
from h2o3_tpu.core.kv import DKV, make_key
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.model")

# per-model compiled scoring programs (Model._serve_jit) — weak-keyed
# so an evicted/deleted model releases its executables
_SERVE_JIT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


class ModelCategory:
    BINOMIAL = "Binomial"
    MULTINOMIAL = "Multinomial"
    REGRESSION = "Regression"
    CLUSTERING = "Clustering"
    DIMREDUCTION = "DimReduction"
    ANOMALY = "AnomalyDetection"


def infer_category(frame: Frame, y: Optional[str]) -> str:
    """Response-type sniffing (reference ModelBuilder.init distribution
    inference)."""
    if y is None:
        return ModelCategory.CLUSTERING
    c = frame.col(y)
    if c.is_categorical:
        return (ModelCategory.BINOMIAL if c.cardinality == 2
                else ModelCategory.MULTINOMIAL)
    return ModelCategory.REGRESSION


def adapt_domain(test_col, train_domain: List[str]) -> np.ndarray:
    """Map test categorical codes into the training domain; unseen → -1
    (NA). The adaptTestForTrain domain-mapping pass (hex/Model.java:1850).
    """
    if test_col.domain == train_domain:
        codes = _fetch_np(test_col.data)[: test_col.nrows].copy()
        codes[_fetch_np(test_col.na_mask)[: test_col.nrows]] = -1
        return codes
    lut = {lvl: i for i, lvl in enumerate(train_domain)}
    mapping = np.array([lut.get(lvl, -1) for lvl in (test_col.domain or [])],
                       dtype=np.int32)
    codes = _fetch_np(test_col.data)[: test_col.nrows]
    out = mapping[codes] if len(mapping) else np.full(test_col.nrows, -1, np.int32)
    out = out.copy()
    out[_fetch_np(test_col.na_mask)[: test_col.nrows]] = -1
    return out


def _response_of(data, na_mask, w, categorical: bool, dtype: str):
    """Traced body of the response programs: (y as ``dtype``, w with the
    rows of a missing response weighted out)."""
    if categorical:
        yraw = jnp.where(na_mask, -1, data)
        present = yraw >= 0
        y = jnp.maximum(yraw, 0)
    else:
        present = ~na_mask
        y = jnp.where(na_mask, 0, data)
    return y.astype(dtype), w * present.astype(w.dtype)


@partial(jax.jit, static_argnames=("categorical", "dtype"))
def _response_program(data, na_mask, w, *, categorical: bool, dtype: str):
    y, w = _response_of(data, na_mask, w, categorical, dtype)
    row = row_sharding()
    return (jax.lax.with_sharding_constraint(y, row),
            jax.lax.with_sharding_constraint(w, row))


def response_on_device(col, w, *, categorical: bool, dtype: str = "float32"):
    """(y, w') for TRAINING on ``col``, built on the device from the
    column's resident ``data`` and ``na_mask``: a missing response gets
    y = 0 and weight 0 — the mesh-padding rows are NA by construction,
    so they need no padding here. A categorical response gives its codes
    (the training domain is the column's own, so there is nothing to map
    — scoring another frame goes through adapt_domain), a numeric one its
    values, as ``dtype``. One program, no host copy."""
    if col.data is None:
        raise ValueError(f"response column {col.name!r} is of type "
                         f"{col.type}; it has to be numeric or categorical")
    assert col.data.shape == w.shape, (col.data.shape, w.shape)
    return _response_program(col.data, col.na_mask, w,
                             categorical=categorical, dtype=dtype)


# rows a float32 partial sum covers — numpy's own pairwise base case.
# 0/1 codes and whole weights add up exactly inside a block, a float's
# rounding stays at the size of a block's sum, and the partials (1.5 MB
# a 48M-row vector) are finished in float64 on the host
SUM_BLOCK_ROWS = 128


def _block_sums(v):
    """[N] → [ceil(N / SUM_BLOCK_ROWS)] float32 sums of blocks of rows."""
    n = v.shape[0]
    nb = -(-n // SUM_BLOCK_ROWS)
    if nb * SUM_BLOCK_ROWS != n:
        v = jnp.pad(v, (0, nb * SUM_BLOCK_ROWS - n))
    return v.reshape(nb, SUM_BLOCK_ROWS).sum(axis=1)


@partial(jax.jit, static_argnames=("categorical", "nclass"))
def _row_state_program(nrows, data, na_mask, wdata, wna, fold, *,
                       categorical: bool, nclass: int):
    """A tree fit's per-row state from the resident columns, and its
    scalar summary: (w, y, summary). ``wdata`` / ``wna`` (the weights
    column) and ``fold`` (the CV fold's 1/0 vector) may be None."""
    real = real_rows(nrows, data.shape[0])
    w = real.astype(jnp.float32)
    if wdata is not None:
        w = w * jnp.where(wna, 0.0, wdata.astype(jnp.float32))
    if fold is not None:
        w = w * fold
    y, w = _response_of(data, na_mask, w, categorical,
                        "int32" if nclass else "float32")
    # a constant weight column rescales to exactly 1.0 (a select, not a
    # division: x / x need not round to 1 on every backend)
    pos = w > 0
    w_min = jnp.min(jnp.where(pos, w, jnp.inf))
    w_max = jnp.max(jnp.where(pos, w, -jnp.inf))
    uniform = (w_min == w_max) & (w_min != 1.0)
    w = jnp.where(uniform & pos, 1.0, w)
    summary = {
        "w_scale": jnp.where(uniform, w_min, 1.0),
        # every weight that trains is 1.0 now: w is 0 or 1 on every row
        "w_whole": w_min == w_max,
        "rows_out": jnp.sum(real & ~pos, dtype=jnp.int32),
        "w": _block_sums(w),
    }
    if nclass:
        # [K, blocks] weighted class counts, a class a pass
        summary["wy"] = jax.lax.map(
            lambda k: _block_sums(jnp.where(y == k, w, 0.0)),
            jnp.arange(nclass, dtype=jnp.int32))
    else:
        summary["wy"] = _block_sums(w * y)
    if not categorical:
        summary["y_min"] = jnp.min(jnp.where(na_mask, jnp.inf, y))
        summary["y_max"] = jnp.max(jnp.where(na_mask, -jnp.inf, y))
    row = row_sharding()
    return (jax.lax.with_sharding_constraint(w, row),
            jax.lax.with_sharding_constraint(y, row), summary)


@dataclasses.dataclass(frozen=True)
class RowSummary:
    """What the host keeps of a tree fit's row state: sums over the rows
    that train, of the weights as the fit uses them (a constant weight
    column already rescaled to 1.0, ``w_scale`` saying by what)."""
    sum_w: float
    sum_wy: Union[float, np.ndarray]    # sum(w*y); [K] class weights
    w_scale: float
    rows_out: int           # real rows weighted out (NA or zero weight)
    w_whole: bool = False   # every row's weight is 0 or (rescaled) 1
    y_min: Optional[float] = None   # numeric response: over its non-NA
    y_max: Optional[float] = None


def row_state_on_device(col, nrows: int, weights_col=None, fold=None):
    """(w, y_dev, RowSummary) for a tree fit on response ``col``: the
    device row weights — real rows x weights column (NA → 0) x CV fold
    vector, rows with a missing response weighted out, a constant weight
    column rescaled to exactly 1.0 — the response beside them (float32
    values or 0/1 codes; int32 codes for a K-class response, K != 2) and
    the scalars the host needs of both, in ONE program and ONE fetch.
    No array with a row dimension is made on the host.

    Sums: float32 over SUM_BLOCK_ROWS rows on the device, the partials
    in float64 here — exact for 0/1 codes under whole weights however
    many rows (a plain float32 sum stalls past 2^24), and within a
    float32 ulp of the float64 sum for values of one sign."""
    if col.data is None:
        raise ValueError(f"response column {col.name!r} is of type "
                         f"{col.type}; it has to be numeric or categorical")
    nclass = col.cardinality if (col.is_categorical
                                 and col.cardinality != 2) else 0
    w, y_dev, s = _row_state_program(
        np.int32(nrows), col.data, col.na_mask,
        None if weights_col is None else weights_col.data,
        None if weights_col is None else weights_col.na_mask, fold,
        categorical=col.is_categorical, nclass=nclass)
    s = _fetch_np(s)
    wy = np.sum(s["wy"], axis=-1, dtype=np.float64)
    return w, y_dev, RowSummary(
        sum_w=float(np.sum(s["w"], dtype=np.float64)),
        sum_wy=wy if nclass else float(wy),
        w_scale=float(s["w_scale"]), rows_out=int(s["rows_out"]),
        w_whole=bool(s["w_whole"]),
        y_min=float(s["y_min"]) if "y_min" in s else None,
        y_max=float(s["y_max"]) if "y_max" in s else None)


def checkpoint_error(algo: str, field: str, message: str) -> ValueError:
    """H2O-shaped checkpoint validation error
    (water.exceptions.H2OModelBuilderIllegalArgumentException as
    h2o-py surfaces it: ``Illegal argument(s) for <ALGO> model ...
    Details: ERRR on field: _<field>: <message>``)."""
    return ValueError(
        f"Illegal argument(s) for {algo.upper()} model: "
        f"Details: ERRR on field: _{field}: {message}")


def validate_checkpoint_params(algo: str, donor_params: Dict,
                               params: Dict, fields) -> None:
    """Reject changes to checkpoint-non-modifiable parameters with the
    reference's error shape (hex/util/CheckpointUtils
    getAndValidateCheckpointModel: "Field _x cannot be modified if
    checkpoint is provided!")."""
    for f in fields:
        old = donor_params.get(f)
        new = params.get(f)
        if old != new:
            raise checkpoint_error(
                algo, f,
                f"Field _{f} cannot be modified if checkpoint is "
                f"provided (checkpoint model: {old!r}, request: {new!r})")


def resolve_checkpoint_model(algo: str, ck, model_cls):
    """Fetch + type-check the donor model behind ``checkpoint=`` (a
    Model instance or its DKV key)."""
    from h2o3_tpu.core.kv import DKV
    donor = ck if isinstance(ck, model_cls) else DKV.get(str(ck))
    if donor is None or getattr(donor, "algo", None) != algo:
        raise checkpoint_error(
            algo, "checkpoint",
            f"Checkpoint model '{getattr(ck, 'key', ck)}' not found "
            f"or not a {algo} model")
    return donor


class EarlyStopper:
    """Metric-based early stopping (reference hex/ScoreKeeper.stopEarly +
    the stopping_rounds/stopping_tolerance contract of SharedTree).

    Lower-is-better metric; stops when the best of the last ``rounds``
    scoring events fails to improve on the prior best by a relative
    ``tol``.
    """

    def __init__(self, rounds: int, tol: float = 1e-3):
        self.rounds = int(rounds)
        self.tol = float(tol)
        self.history: List[float] = []

    @property
    def enabled(self) -> bool:
        return self.rounds > 0

    def should_stop(self, value: float) -> bool:
        self.history.append(float(value))
        if not self.enabled or len(self.history) <= self.rounds:
            return False
        recent = min(self.history[-self.rounds:])
        before = min(self.history[: -self.rounds])
        denom = abs(before) if before else 1.0
        return (before - recent) / denom < self.tol


class Model:
    """Trained-model base (hex/Model.java)."""

    algo: str = "base"

    def __init__(self, params: dict, output: dict, key: Optional[str] = None):
        self.key = key or make_key(f"model_{self.algo}")
        self.params = params
        self.output = output           # domains, names, varimp, history...
        self.training_metrics = None
        self.validation_metrics = None
        self.cross_validation_metrics = None
        DKV.put(self.key, self)

    # subclasses implement raw scoring on a Frame
    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def _serve_jit(self):
        """The model's ONE compiled scoring program: ``jax.jit`` of
        ``_serve_dev``, cached per model instance. Both ``_score_raw``
        (on its no-offset path) and the serving tier score through THIS
        object, so row-payload predictions are bit-identical to
        ``Model.predict`` by construction — identical traced program,
        identical XLA fusions — rather than by hoping eager op-by-op
        execution matches a fused program (it does not: XLA rewrites
        e.g. divide-by-constant into reciprocal multiplies only inside
        a jitted program). Cached OUTSIDE the instance dict (weak-keyed
        module map) so models stay picklable for checkpoints."""
        fn = _SERVE_JIT_CACHE.get(self)
        if fn is None:
            fn = jax.jit(self._serve_dev)
            _SERVE_JIT_CACHE[self] = fn
        return fn

    def _finish_predict(self, cols: Dict[str, np.ndarray]):
        """Shared post-processing of raw score columns: predict-column
        domain labeling and calibrated probabilities. ONE implementation
        for ``predict``, the chunked bulk path, and the serving tier —
        the bit-identity contract of README §Serving rides on all three
        funneling through here. Returns ``(out, domains)``."""
        out: Dict[str, np.ndarray] = {}
        domains: Dict[str, List[str]] = {}
        for name, arr in cols.items():
            out[name] = arr
            if name == "predict" and self.output.get("domain"):
                domains[name] = self.output["domain"]
        cal = getattr(self, "calibrator", None)
        if cal is not None and "p1" in out:
            # calibrated probability columns (CalibrationHelper scoring)
            cp1 = cal.apply(np.asarray(out["p1"], dtype=np.float64))
            out["cal_p0"] = 1.0 - cp1
            out["cal_p1"] = cp1
        return out, domains

    def predict(self, frame: Frame) -> Frame:
        """Bulk scoring → prediction Frame (BigScore, hex/Model.java:2085)."""
        out, domains = self._finish_predict(self._score_raw(frame))
        return Frame.from_numpy(out, domains=domains)

    def predict_in_chunks(self, frame: Frame, job=None,
                          chunk_rows: Optional[int] = None) -> Frame:
        """Bulk scoring with chunk-boundary cancellation — the BigScore
        MRTask contract (water/Job.java stop_requested() polled per
        chunk): a cancelled or deadline-expired bulk predict frees its
        worker within one chunk instead of after the full frame. Used
        by the async ``/4/Predictions`` job path; bit-identical to
        ``predict`` (every per-chunk op is row-local, and the shared
        ``_finish_predict`` tail runs once over the reassembled
        columns)."""
        import os as _os
        from h2o3_tpu.core import request_ctx
        if chunk_rows is None:
            chunk_rows = int(_os.environ.get(
                "H2O3TPU_PREDICT_CHUNK_ROWS", 262144))
        n = frame.nrows
        if chunk_rows <= 0 or n <= chunk_rows:
            request_ctx.cancel_point("predict.chunk")
            if job is not None:
                job.update(0.9)
            return self.predict(frame)
        parts: List[Dict[str, np.ndarray]] = []
        for lo in range(0, n, chunk_rows):
            request_ctx.cancel_point("predict.chunk")
            hi = min(lo + chunk_rows, n)
            sub = frame.row_slice(lo, hi)
            try:
                parts.append(self._score_raw(sub))
            finally:
                sub.drop_device_caches()
            if job is not None:
                job.update(0.05 + 0.85 * (hi / n))
        merged = {nm: np.concatenate([p[nm] for p in parts])
                  for nm in parts[0]}
        out, domains = self._finish_predict(merged)
        return Frame.from_numpy(out, domains=domains)

    def model_performance(self, frame: Frame):
        raise NotImplementedError

    def download_mojo(self, path: str, format: str = "native") -> str:
        """Export this model as a MOJO zip for offline scoring
        (Model.getMojo + hex/genmodel readers; see h2o3_tpu/genmodel/).

        format="native" (default): the npz fast path our offline
        readers consume. format="reference": the reference MOJO zip
        layout (model.ini + domains/ + SharedTreeMojoModel v1.40 tree
        blobs; GlmMojoReader v1.00 kv block for GLM) so the reference
        genmodel runtime can score the model — GBM/DRF/GLM.
        """
        if format == "reference":
            from h2o3_tpu.genmodel import refmojo
            writers = {
                "glm": refmojo.write_reference_glm_mojo,
                "kmeans": refmojo.write_reference_kmeans_mojo,
                "deeplearning": refmojo.write_reference_dl_mojo,
                "isolationforest": refmojo.write_reference_isofor_mojo,
                "word2vec": refmojo.write_reference_word2vec_mojo,
                "coxph": refmojo.write_reference_coxph_mojo,
                "glrm": refmojo.write_reference_glrm_mojo,
                "pca": refmojo.write_reference_pca_mojo,
                "targetencoder": refmojo.write_reference_te_mojo,
                "gbm": refmojo.write_reference_mojo,
                "drf": refmojo.write_reference_mojo,
            }
            w = writers.get(self.algo)
            if w is None:
                raise ValueError(
                    "reference-format MOJO export supports "
                    f"{sorted(writers)} (got {self.algo})")
            return w(self, path)
        from h2o3_tpu.genmodel.export import mojo_artifacts
        from h2o3_tpu.genmodel.mojo import write_mojo
        meta, arrays = mojo_artifacts(self)
        return write_mojo(path, meta, arrays)

    def download_pojo(self, path: str) -> str:
        """Export a standalone source-code scorer (Model.toJava POJO
        role; a stdlib-only Python module here — see genmodel/pojo.py)."""
        from h2o3_tpu.genmodel.pojo import export_pojo
        return export_pojo(self, path)

    @property
    def default_metrics(self):
        return (self.cross_validation_metrics or self.validation_metrics
                or self.training_metrics)

    def to_dict(self) -> dict:
        return {
            "model_id": self.key,
            "algo": self.algo,
            "params": {k: v for k, v in self.params.items()
                       if isinstance(v, (int, float, str, bool, list, type(None)))},
            "output": {k: v for k, v in self.output.items()
                       if isinstance(v, (int, float, str, bool, list, dict, type(None)))},
            "training_metrics": self.training_metrics.to_dict() if self.training_metrics else None,
            "validation_metrics": self.validation_metrics.to_dict() if self.validation_metrics else None,
            "cross_validation_metrics": (self.cross_validation_metrics.to_dict()
                                         if self.cross_validation_metrics else None),
        }


class ModelBuilder:
    """Training lifecycle base (hex/ModelBuilder.java:25).

    ``train`` = trainModel (ModelBuilder.java:374): wraps ``_fit`` in a Job
    with progress; n-fold CV (computeCrossValidation, ModelBuilder.java:603)
    is implemented generically in ml/cv.py and invoked when nfolds >= 2.
    """

    algo: str = "base"
    supervised: bool = True
    # fold_column implies CV for normal builders; encoders use the fold
    # column for leakage handling instead (TargetEncoder)
    cv_from_fold_column: bool = True

    def __init__(self, **params):
        self.params = params
        self._job: Optional[Job] = None

    @classmethod
    def accepted_params(cls) -> set:
        """Parameter names this builder accepts (REST schema filter);
        DEFAULTS-based by convention, overridable by facades."""
        return set(getattr(cls, "DEFAULTS", {}))

    def set_max_runtime(self, secs: float) -> None:
        """Install a wallclock cap when the builder accepts one (the
        AutoML executor's time slicing; facades forward to their inner
        builder, which __init__ constructed before the cap existed)."""
        if "max_runtime_secs" in self.accepted_params():
            self.params["max_runtime_secs"] = float(secs)

    # -- subclass contract --------------------------------------------
    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job: Job, validation_frame: Optional[Frame] = None) -> Model:
        raise NotImplementedError

    # -- shared weight plumbing (one impl; GBM/DRF/GLM all use these) --
    def _cv_fold_weights(self, frame: Frame):
        """The CV fold's 1/0 row vector on the device, [nrows_padded]
        float32, or None outside the CV fast path."""
        fold_mask = getattr(self, "_cv_fold_mask", None)
        if fold_mask is None:
            return None
        fm = np.zeros(frame.nrows_padded, np.float32)
        fm[: frame.nrows] = fold_mask.astype(np.float32)
        return jnp.asarray(fm)

    def _cv_masked_weights(self, w, frame: Frame):
        """CV fast path (ml/cv.py): fold models train on the PARENT
        frame with held-out rows weight-masked — no per-fold frame or
        bin rebuild, one compiled program across folds."""
        fold = self._cv_fold_weights(frame)
        return w if fold is None else w * fold

    def _training_weights(self, frame: Frame, y: str):
        """(w, y_dev, rows) for a tree fit, derived on the device from
        the frame's resident columns (row_state_on_device): the row
        weights — padding mask x user weight column x CV fold mask, with
        the rows whose response is NA weighted out (the reference's
        ModelBuilder drops them from training and from the training
        metrics) and a constant weight column rescaled to 1.0 — the
        response, and their RowSummary. The host reads the summary's
        scalars in one fetch (the one device sync of a fit's preamble);
        callers divide every ABSOLUTE training threshold (min_rows,
        min_split_improvement, reg_lambda) by ``rows.w_scale``, which
        reproduces raw-weight reference semantics exactly in real
        arithmetic while 'uniform weights ≡ no weights' holds bit for
        bit (pyunit_weights_gbm)."""
        wc_name = self.params.get("weights_column")
        return row_state_on_device(
            frame.col(y), frame.nrows,
            weights_col=frame.col(wc_name) if wc_name else None,
            fold=self._cv_fold_weights(frame))

    def _host_weights(self, frame: Frame, y: Optional[str]) -> np.ndarray:
        """HOST copy of the training weights before any rescaling: user
        weight column × CV fold mask × response-NA exclusion,
        [frame.nrows] float32, equal to _training_weights' device vector
        row for row. Built for ONE reader: the weighted quantile sketch
        of a frame's first binning (_binned), which runs on the host."""
        wc_name = self.params.get("weights_column")
        if wc_name and wc_name in frame:
            wh = np.nan_to_num(
                frame.col(wc_name).to_numpy()).astype(np.float32)
        else:
            wh = np.ones(frame.nrows, np.float32)
        fold_mask = getattr(self, "_cv_fold_mask", None)
        if fold_mask is not None:
            wh = wh * fold_mask.astype(np.float32)
        if y is not None and y in frame and \
                frame.col(y).type not in ("string", "uuid"):
            wh = wh * (~np.isnan(frame.col(y).to_numpy())).astype(np.float32)
        return wh

    def _binned(self, frame: Frame, x: Sequence[str], y: str, **config):
        """(bm, "hit" | "miss"): the frame's binned training matrix from
        bin_frame's cache, its slot named by what the training weights
        are made from — the weights column and the response (columns
        are immutable and a frame drops its bins when one is replaced)
        — so a warm fit hashes no rows. The host weight vector is built
        on a miss alone: the weighted edges of the row-weight ≡
        row-multiplicity contract (pyunit_weights_gbm) are cut on the
        host. A CV fold's weights have no such name: they go by content
        and read "miss" (ml/cv.py hands fold fits the main model's bins,
        so none comes here)."""
        from h2o3_tpu.frame.binning import bin_frame
        built = []

        def host_weights():
            built.append(True)
            return self._host_weights(frame, y)

        named = getattr(self, "_cv_fold_mask", None) is None
        bm = bin_frame(
            frame, x, weights=host_weights, weights_key=(
                self.params.get("weights_column"), y) if named else None,
            **config)
        return bm, "miss" if built else "hit"

    def design_row_bytes(self, frame: Frame,
                         x: Sequence[str]) -> Optional[int]:
        """Device bytes a row of the design and row state this fit
        builds, for admission (``core/memgov.estimate_fit_bytes``); None:
        4 B a feature."""
        return None

    # -- public train --------------------------------------------------
    def resolve_x(self, frame: Frame, x: Optional[Sequence[str]],
                  y: Optional[str]) -> List[str]:
        ignored = set(self.params.get("ignored_columns") or [])
        drop = ignored | ({y} if y else set())
        drop |= {self.params.get("weights_column"),
                 self.params.get("fold_column"),
                 self.params.get("offset_column")}
        if x is None:
            x = [n for n in frame.names if n not in drop]
        else:
            x = [n if isinstance(n, str) else frame.names[n] for n in x]
            x = [n for n in x if n not in drop]
        # strings can't enter math paths (reference drops them with a warning)
        return [n for n in x if frame.col(n).type != "string"]

    def train(self, training_frame: Frame, y: Optional[str] = None,
              x: Optional[Sequence[str]] = None,
              validation_frame: Optional[Frame] = None,
              background: bool = False,
              dest_key: Optional[str] = None,
              custom_metric_func=None) -> Model:
        """``custom_metric_func`` is the water/udf CFunc role: a callable
        ``fn(y_values, preds_dict, weights) -> float`` evaluated on the
        training frame and attached to training_metrics as 'custom'."""
        from h2o3_tpu import telemetry
        # admission is a span of its own: it ends before the job's opens
        with telemetry.span("fit.admit", algo=self.algo):
            x = self.resolve_x(training_frame, x, y)
            nfolds = int(self.params.get("nfolds") or 0)
            # an explicit fold column triggers CV regardless of nfolds
            # (hex/ModelBuilder.java computeCrossValidation entry
            # conditions)
            if self.params.get("fold_column") and nfolds < 2 \
                    and self.cv_from_fold_column:
                nfolds = 2      # actual count comes from the fold column
            # predictive admission (core/memgov.py): estimate the fit's
            # device footprint and reserve it BEFORE the job dispatches —
            # an over-budget fit first spills cold frames, then rejects
            # here with an actionable error naming projected vs
            # available bytes (never an opaque XLA RESOURCE_EXHAUSTED
            # minutes in). The reservation releases when the job ends,
            # whatever status.
            from h2o3_tpu.core import memgov as _memgov
            _rsv = _memgov.governor.admit_fit(
                self.algo, self.params, training_frame, x, validation_frame,
                self.design_row_bytes(training_frame, x))
        # the model key must exist BEFORE training starts: the real h2o-py
        # captures job.dest at submission time (h2o-py/h2o/job.py:48).
        # The job is made outside the admission span: it parents under
        # the caller's span (a REST request's), not under admission
        if not dest_key:
            dest_key = make_key(f"model_{self.algo}")
        try:
            job = Job(f"{self.algo} train", work=1.0, dest=dest_key)
        except BaseException:
            _memgov.governor.release(_rsv)
            raise
        job.add_finalizer(lambda: _memgov.governor.release(_rsv))
        self._job = job
        # capture the in-fit checkpoint directory on the CALLER thread:
        # a background job runs on a fresh thread whose context would
        # not inherit the grid/AutoML fit_checkpoint_scope contextvar
        from h2o3_tpu.core import recovery as _recovery
        _fit_ckpt_dir = _recovery.fit_checkpoint_dir()

        def _run(j: Job) -> Model:
            t0 = time.time()
            # CV-contract validation errors surface as FAILED jobs so
            # clients see them while polling (hex/ModelBuilder error
            # handling; pyunit_cv_cars_* expect EnvironmentError from
            # H2OJob.poll)
            if nfolds == 1 or nfolds < 0:
                raise ValueError(
                    "nfolds must be either 0 or >1 (got %d)" % nfolds)
            if nfolds > training_frame.nrows:
                raise ValueError(
                    "nfolds (%d) cannot exceed the number of rows (%d)"
                    % (nfolds, training_frame.nrows))
            if self.params.get("fold_column") and \
                    int(self.params.get("nfolds") or 0) > 0:
                raise ValueError(
                    "only one of nfolds or fold_column may be specified")
            if self.params.get("fold_column") and \
                    str(self.params.get("fold_assignment", "auto")
                        or "auto").lower() != "auto":
                raise ValueError(
                    "fold_assignment is incompatible with fold_column "
                    "(hex/ModelBuilder fold-spec validation)")
            from h2o3_tpu.telemetry import roofline, stepprof
            with telemetry.span(f"{self.algo}.fit", algo=self.algo,
                                nfolds=nfolds) as fit_span, \
                    _recovery.fit_checkpoint_scope(_fit_ckpt_dir):
                rf_probe = roofline.fit_probe(self.algo)
                # step profiler: the chunk loops charge their phase
                # windows against this profile; finish registers the
                # per-fit ledger for /3/Models/{id}/profile, the
                # capsule, and the perf-regression baseline
                _sp = stepprof.start(self.algo,
                                     nrows=training_frame.nrows)
                t_fit = time.time()
                try:
                    if nfolds >= 2:
                        from h2o3_tpu.ml.cv import train_with_cv
                        model = train_with_cv(
                            self, training_frame, x, y, nfolds, j,
                            validation_frame=validation_frame)
                    else:
                        model = self._fit(
                            training_frame, x, y, j,
                            validation_frame=validation_frame)
                except BaseException:
                    stepprof.finish(_sp)   # never leave a live profile
                    raise
                # roofline accounting INSIDE the span: the MFU/HBM
                # numbers annotate the fit span and therefore land in
                # the job's flight-recorder capsule (never raises)
                with telemetry.span("fit.account"):
                    _rf = roofline.record_model_fit(
                        self, model, training_frame, x,
                        seconds=time.time() - t_fit, probe=rf_probe,
                        span=fit_span)
                    stepprof.finish(_sp, model_key=dest_key,
                                    seconds=time.time() - t_fit,
                                    mfu=(_rf or {}).get("mfu"))
                    telemetry.histogram(
                        "model_fit_seconds",
                        algo=self.algo).observe(time.time() - t0)
            if custom_metric_func is not None and y is not None:
                # "python:key" CFunc references (water/udf/CFuncRef)
                from h2o3_tpu.core.udf import resolve_udf
                cmf = resolve_udf(custom_metric_func)
                yv = training_frame.col(y).to_numpy()   # enum → float codes
                preds = model._score_raw(training_frame)
                wv = np.ones(training_frame.nrows)
                wc = self.params.get("weights_column")
                if wc and wc in training_frame:
                    wv = np.nan_to_num(training_frame.col(wc).to_numpy())
                val = float(cmf(yv, preds, wv))
                if model.training_metrics is not None and \
                        hasattr(model.training_metrics, "extra"):
                    model.training_metrics.extra["custom"] = val
                model.output["custom_metric"] = val
            model.output["run_time"] = time.time() - t0
            if dest_key and model.key != dest_key:
                # rename into the pre-announced job dest key
                DKV.remove(model.key)
                model.key = dest_key
                DKV.put(dest_key, model)
            log.info("%s trained in %.2fs -> %s", self.algo,
                     time.time() - t0, model.key)
            return model

        job.start(_run, background=background)
        if background:
            return job  # poll via /3/Jobs
        if job.status == "FAILED":
            raise RuntimeError(job.exception)
        return job.result
