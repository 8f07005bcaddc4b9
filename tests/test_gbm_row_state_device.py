"""A tree fit's row weights, response and start-of-fit sums are built on
the device from the frame's resident columns (models/model.py
``row_state_on_device``): equal to the host expressions they replaced,
and a warm fit makes no row-sized array on the host."""

import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.frame.column import Column, column_from_numpy
from h2o3_tpu.models.drf import DRFEstimator
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.model import (SUM_BLOCK_ROWS, ModelBuilder,
                                   row_state_on_device)
from h2o3_tpu.parallel import mesh as mesh_mod

N = 20003         # pads to 20480 on the 8-device test mesh
X = ["x0", "x1", "c"]
KINDS = ("binomial", "regression", "multinomial")
WEIGHTS = ("none", "column_na_zero", "constant_2", "na_response")


def _frame(kind, weights, n=N, seed=32):
    r = np.random.RandomState(seed)
    x = r.randn(n, 2).astype(np.float32)
    cols = {"x0": x[:, 0], "x1": x[:, 1],
            "c": np.array(list("abcd"), object)[r.randint(0, 4, n)]}
    eta = x[:, 0] - 0.5 * x[:, 1]
    if kind == "binomial":
        y = np.array(["N", "Y"], object)[(eta + r.randn(n) > 0).astype(int)]
    elif kind == "regression":
        y = (5e3 + eta * 1e3 + 1 / 3 + r.randn(n)).astype(np.float64)
    else:
        y = np.array(["lo", "mid", "hi"], object)[
            np.digitize(eta + r.randn(n), [-1.0, 1.0])]
    if weights == "na_response":
        y[::17] = np.nan if kind == "regression" else None
    cols["y"] = y
    wc = None
    if weights == "column_na_zero":
        wt = (r.randint(0, 5, n) * 0.75).astype(np.float64)   # zeros inside
        wt[::11] = np.nan
        cols["wt"], wc = wt, "wt"
    elif weights == "constant_2":
        cols["wt"], wc = np.full(n, 2.0), "wt"
    cats = ["c"] + ([] if kind == "regression" else ["y"])
    return h2o3_tpu.Frame.from_numpy(cols, categorical=cats), wc


class _Builder(ModelBuilder):
    """ModelBuilder's weight plumbing with nothing else around it."""


def _ulp32(x):
    return float(np.spacing(np.float32(abs(x))))


@pytest.mark.parametrize("weights", WEIGHTS)
@pytest.mark.parametrize("kind", KINDS)
def test_row_state_equals_the_host_path(kind, weights):
    fr, wc = _frame(kind, weights)
    n, npad = fr.nrows, fr.nrows_padded
    assert npad > n and npad // SUM_BLOCK_ROWS > 100
    b = _Builder(weights_column=wc)
    # what the host path gave (PR 31's _training_weights, _init_single /
    # _init_multi, _normalize_uniform_weights, _prepare's response check)
    wh = b._host_weights(fr, "y")
    pos = wh[wh > 0]
    scale = float(pos[0]) if (pos.min() == pos.max()
                              and float(pos[0]) != 1.0) else 1.0
    wh = wh / np.float32(scale)
    yh = fr.col("y").to_numpy()
    y_ref = np.nan_to_num(yh).astype(
        np.int32 if kind == "multinomial" else np.float32)

    fetches = mesh_mod.FETCH_CALLS
    w, y_dev, rows = b._training_weights(fr, "y")
    assert mesh_mod.FETCH_CALLS == fetches + 1          # the one fetch
    for a in (w, y_dev):
        assert a.shape == (npad,)
        assert a.sharding.is_equivalent_to(mesh_mod.row_sharding(), 1)
    w_got, y_got = np.asarray(w), np.asarray(y_dev)
    assert w_got.dtype == np.float32 and y_got.dtype == y_ref.dtype
    assert w_got[:n].tobytes() == wh.tobytes()
    assert y_got[:n].tobytes() == y_ref.tobytes()
    assert not w_got[n:].any() and not y_got[n:].any()

    assert rows.w_scale == scale
    assert (scale == 2.0) == (weights == "constant_2")
    assert rows.rows_out == int((wh == 0).sum())
    assert (rows.rows_out > 0) == (weights in ("column_na_zero",
                                               "na_response"))
    sum_w = float(np.sum(wh, dtype=np.float64))
    assert abs(rows.sum_w - sum_w) <= _ulp32(sum_w)
    if kind == "multinomial":
        counts = np.bincount(y_ref, weights=wh, minlength=3)
        assert rows.sum_wy.shape == (3,) and rows.sum_wy.dtype == np.float64
        for got, ref in zip(rows.sum_wy, counts):
            assert abs(got - ref) <= _ulp32(ref)
    else:
        terms = y_ref.astype(np.float64) * wh
        ref = float(np.sum(terms))
        # a float32 ulp of the sum of magnitudes (the sum itself where
        # the terms have one sign, as all but a few have here)
        assert abs(rows.sum_wy - ref) <= _ulp32(np.sum(np.abs(terms)))
        assert _ulp32(np.sum(np.abs(terms))) == _ulp32(ref)
    if kind == "regression":
        vals = yh[~np.isnan(yh)]
        assert rows.y_min == float(np.float32(vals.min()))
        assert rows.y_max == float(np.float32(vals.max()))
    else:
        assert rows.y_min is None and rows.y_max is None


@pytest.mark.parametrize("weights,whole", [
    ("none", True), ("na_response", True), ("constant_2", True),
    ("zeros_and_threes", True), ("column_na_zero", False)])
def test_the_summary_says_whether_the_weights_are_whole(weights, whole):
    """``w_whole``: every row's weight is 0 or 1 once a constant column
    is rescaled — what lets a forest's histogram operand carry one
    bfloat16 piece a statistic (models/drf.py)."""
    fr, wc = _frame("binomial", "none" if weights == "zeros_and_threes"
                    else weights, n=3000)
    wcol = fr.col(wc) if wc else None
    if weights == "zeros_and_threes":
        wt = 3.0 * (np.arange(3000) % 4 > 0)
        wcol = h2o3_tpu.Frame.from_numpy({"wt": wt}).col("wt")
    w, _, rows = row_state_on_device(fr.col("y"), fr.nrows, weights_col=wcol)
    assert rows.w_whole is whole
    assert set(np.unique(np.asarray(w))) <= {0.0, 1.0} or not whole
    if weights == "zeros_and_threes":
        assert rows.w_scale == 3.0 and rows.sum_w == 2250.0


def test_counts_stay_exact_past_the_float32_integers():
    """A plain float32 sum of 0/1 codes stalls at 2^24; block partials
    finished in float64 do not. 17M rows is past it and still small
    enough for the CPU mesh (a bool and an int8 vector)."""
    n = (1 << 24) + 99_999
    npad = mesh_mod.padded_rows(n)
    row = mesh_mod.row_sharding()
    ones = mesh_mod.put_sharded(np.ones(npad, np.int8), row)
    col = Column(name="y", type="categorical", data=ones,
                 na_mask=mesh_mod.valid_mask(n, npad) == 0, nrows=n,
                 domain=["N", "Y"])
    _, _, rows = row_state_on_device(col, n)
    assert rows.sum_w == n and rows.sum_wy == n and rows.rows_out == 0


def test_valid_mask_is_made_on_the_device():
    for n, npad in ((1003, 1024), (1024, 1024), (0, 8)):
        m = mesh_mod.valid_mask(n, npad)
        assert m.dtype == jnp.float32 and m.shape == (npad,)
        assert m.sharding.is_equivalent_to(mesh_mod.row_sharding(), 1)
        ref = np.zeros(npad, np.float32)
        ref[:n] = 1.0
        assert np.asarray(m).tobytes() == ref.tobytes()


def _spans_of(run, names=("gbm.bin", "gbm.init")):
    before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
    out = run()
    return out, {s["name"]: s["meta"]
                 for s in telemetry.spans_snapshot(1 << 20)
                 if s["id"] not in before and s["name"] in names}


@pytest.fixture
def host_reads(monkeypatch):
    """Names of the columns whose host copy was read, in order."""
    seen = []
    for name in ("to_numpy", "host_view"):
        real = getattr(Column, name)

        def spy(self, _real=real):
            seen.append(self.name)
            return _real(self)
        monkeypatch.setattr(Column, name, spy)
    return seen


@pytest.mark.parametrize("kind", KINDS)
def test_warm_fit_reads_no_host_copy(kind, host_reads):
    fr, wc = _frame(kind, "column_na_zero", n=3001)
    est = dict(ntrees=2, max_depth=3, seed=1, weights_column=wc)
    _, cold = _spans_of(lambda: GBMEstimator(**est).train(fr, y="y", x=X))
    assert cold["gbm.bin"]["cache"] == "miss"
    assert {"y", "wt"} <= set(host_reads)       # the sketch's weights
    del host_reads[:]
    fetches = mesh_mod.FETCH_CALLS
    _, warm = _spans_of(lambda: GBMEstimator(**est).train(fr, y="y", x=X))
    assert warm["gbm.bin"]["cache"] == "hit"
    assert warm["gbm.init"]["on_device"] is True
    assert warm["gbm.init"]["host_bytes"] == 0
    assert warm["gbm.init"]["rows_out"] > 0
    assert not {"y", "wt"} & set(host_reads), host_reads
    # the summary is the preamble's one fetch (metrics fetch their own)
    assert mesh_mod.FETCH_CALLS > fetches


def _edges(model):
    return np.asarray(model.bm.edges).tobytes()


def test_other_weights_or_a_replaced_column_miss_the_cache():
    fr, _ = _frame("binomial", "column_na_zero", n=3001)
    r = np.random.RandomState(4)
    fr.add_column(column_from_numpy(
        "w2", r.randint(0, 3, fr.nrows).astype(np.float64), fr.nrows_padded,
        mesh_mod.row_sharding()))
    est = dict(ntrees=2, max_depth=3, seed=1)

    def fit(frame, **kw):
        return _spans_of(lambda: GBMEstimator(**est, **kw).train(
            frame, y="y", x=X))

    m_wt, sp = fit(fr, weights_column="wt")
    assert sp["gbm.bin"]["cache"] == "miss"
    m_w2, sp = fit(fr, weights_column="w2")
    assert sp["gbm.bin"]["cache"] == "miss"
    m_none, sp = fit(fr)
    assert sp["gbm.bin"]["cache"] == "miss"
    assert fit(fr, weights_column="w2")[1]["gbm.bin"]["cache"] == "hit"
    # DRF names the slot the same way and finds GBM's bins
    drf = DRFEstimator(ntrees=2, max_depth=3, seed=1, nbins=64,
                       nbins_cats=1024, weights_column="w2").train(
                           fr, y="y", x=X)
    assert drf.bm is m_w2.bm
    # the edges are a fresh frame's, value for value
    fresh, _ = _frame("binomial", "column_na_zero", n=3001)
    fresh.add_column(column_from_numpy(
        "w2", np.asarray(fr.col("w2").to_numpy()), fresh.nrows_padded,
        mesh_mod.row_sharding()))
    assert _edges(fit(fresh, weights_column="w2")[0]) == _edges(m_w2)
    assert _edges(m_w2) != _edges(m_wt)
    # a replaced column drops the frame's bins: the same name, new values
    x0 = np.asarray(fr.col("x0").to_numpy())
    fr.add_column(column_from_numpy(
        "x0", x0 * 2.0 + 1.0, fr.nrows_padded, mesh_mod.row_sharding()))
    m_new, sp = fit(fr, weights_column="w2")
    assert sp["gbm.bin"]["cache"] == "miss"
    assert m_new.bm is not m_w2.bm and _edges(m_new) != _edges(m_w2)
    fresh.add_column(column_from_numpy(
        "x0", x0 * 2.0 + 1.0, fresh.nrows_padded, mesh_mod.row_sharding()))
    assert _edges(fit(fresh, weights_column="w2")[0]) == _edges(m_new)


@pytest.mark.parametrize("how", ["checkpoint", "cv_fold"])
def test_bins_that_are_not_looked_up_say_so(how):
    fr, _ = _frame("binomial", "none", n=3001)
    m0 = GBMEstimator(ntrees=2, max_depth=3, seed=1).train(fr, y="y", x=X)
    if how == "checkpoint":
        _, sp = _spans_of(lambda: GBMEstimator(
            ntrees=3, max_depth=3, seed=1, checkpoint=m0).train(
                fr, y="y", x=X))
        assert sp["gbm.bin"]["cache"] == "rebin"
    else:
        before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
        GBMEstimator(ntrees=2, max_depth=3, seed=1, nfolds=2).train(
            fr, y="y", x=X)
        got = [s["meta"]["cache"] for s in telemetry.spans_snapshot(1 << 20)
               if s["id"] not in before and s["name"] == "gbm.bin"]
        assert got == ["hit", "shared", "shared"]


@pytest.mark.parametrize("with_na", [False, True])
def test_constant_numeric_response_is_refused(with_na):
    r = np.random.RandomState(1)
    y = np.full(500, 3.25)
    if with_na:
        y[::7] = np.nan
    fr = h2o3_tpu.Frame.from_numpy({"x": r.randn(500), "y": y})
    with pytest.raises(ValueError, match="Response cannot be constant - "
                       "check your response column, or set "
                       "check_constant_response=False"):
        GBMEstimator(ntrees=2, max_depth=3, seed=1).train(fr, y="y")
    m = GBMEstimator(ntrees=2, max_depth=3, seed=1,
                     check_constant_response=False).train(fr, y="y")
    assert m.output["init_f"] == 3.25
