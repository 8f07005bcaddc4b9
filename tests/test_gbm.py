"""GBM end-to-end tests — the pyunit_gbm* role
(h2o-py/tests/testdir_algos/gbm/)."""

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu.models.gbm import GBMEstimator
from tests.conftest import make_classification, make_regression


def test_gbm_binomial_learns(classif_frame):
    m = GBMEstimator(ntrees=20, max_depth=4, learn_rate=0.2, seed=42)
    model = m.train(classif_frame, y="y")
    tm = model.training_metrics
    assert tm["AUC"] > 0.80, tm.to_dict()
    assert tm["logloss"] < 0.60


def test_gbm_predictions_shape(classif_frame):
    m = GBMEstimator(ntrees=5, max_depth=3, seed=1)
    model = m.train(classif_frame, y="y")
    preds = model.predict(classif_frame)
    assert preds.names == ["predict", "p0", "p1"]
    assert preds.nrows == classif_frame.nrows
    p = preds.to_pandas()
    assert ((p["p0"] + p["p1"]).round(4) == 1.0).all()


def test_gbm_regression(regress_frame):
    m = GBMEstimator(ntrees=30, max_depth=5, learn_rate=0.2, seed=3)
    model = m.train(regress_frame, y="y")
    tm = model.training_metrics
    y = regress_frame.col("y").to_numpy()
    base_mse = float(np.var(y))
    assert tm["MSE"] < 0.3 * base_mse, (tm["MSE"], base_mse)


def test_gbm_multinomial():
    r = np.random.RandomState(7)
    n = 3000
    X = r.randn(n, 5)
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
    fr = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(5)},
         "y": np.array(["a", "b", "c"], object)[y]},
        categorical=["y"])
    m = GBMEstimator(ntrees=10, max_depth=4, learn_rate=0.3, seed=5)
    model = m.train(fr, y="y")
    tm = model.training_metrics
    assert tm["logloss"] < 0.5
    preds = model.predict(fr)
    p = preds.to_pandas()
    acc = (p["predict"].to_numpy() == np.array(["a", "b", "c"], object)[y]).mean()
    assert acc > 0.85


def test_gbm_multinomial_opens_the_fit_phases():
    """A K-class job charges its start (class codes to the device, the
    priors, the [N, K] margins) to ``gbm.init`` like every other fit,
    not to ``gbm.fit``'s own time."""
    from h2o3_tpu import telemetry
    r = np.random.RandomState(3)
    n = 600
    X = r.randn(n, 3)
    y = np.digitize(X[:, 0], [-0.4, 0.4])
    fr = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(3)},
         "y": np.array(["a", "b", "c"], object)[y]}, categorical=["y"])
    before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
    GBMEstimator(ntrees=3, max_depth=3, seed=5).train(fr, y="y")
    spans = {s["name"]: s["meta"] for s in telemetry.spans_snapshot(1 << 20)
             if s["id"] not in before}
    assert {"gbm.bin", "gbm.init", "gbm.chunk", "gbm.rescore",
            "gbm.metrics"} <= set(spans)
    assert spans["gbm.chunk"]["trees"] == 3
    assert {"levels_kernel", "levels_xla"} <= set(spans["gbm.chunk"])
    assert {"levels_select", "levels_gather"} <= set(spans["gbm.rescore"])


def test_gbm_with_categorical_features():
    r = np.random.RandomState(11)
    n = 2000
    cat = r.randint(0, 4, n)
    x1 = r.randn(n)
    y = (cat >= 2).astype(int) ^ (x1 > 0).astype(int)
    fr = h2o3_tpu.Frame.from_numpy(
        {"c": np.array(["p", "q", "r", "s"], object)[cat], "x1": x1,
         "y": np.array(["n", "y"], object)[y]},
        categorical=["y"])
    model = GBMEstimator(ntrees=20, max_depth=4, learn_rate=0.3, seed=2).train(fr, y="y")
    assert model.training_metrics["AUC"] > 0.9


def test_gbm_nas_in_features():
    r = np.random.RandomState(13)
    n = 2000
    x = r.randn(n)
    y = (x > 0).astype(int)
    x_na = x.copy()
    x_na[r.rand(n) < 0.3] = np.nan  # NAs uncorrelated with y
    fr = h2o3_tpu.Frame.from_numpy(
        {"x": x_na, "y": np.array(["n", "y"], object)[y]}, categorical=["y"])
    model = GBMEstimator(ntrees=10, max_depth=3, seed=2).train(fr, y="y")
    assert model.training_metrics["AUC"] > 0.8


def test_gbm_varimp(classif_frame):
    model = GBMEstimator(ntrees=10, max_depth=4, seed=9).train(classif_frame, y="y")
    vi = model.output["varimp"]
    assert len(vi) == 8
    names = [v[0] for v in vi]
    # informative features x0..x3 should dominate
    assert set(names[:3]).issubset({"x0", "x1", "x2", "x3"})


def test_gbm_validation_frame():
    X, y = make_classification(n=2000, seed=21)
    Xv, yv = make_classification(n=1000, seed=22)
    tr = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(8)},
         "y": np.array(["a", "b"], object)[y]}, categorical=["y"])
    va = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": Xv[:, i] for i in range(8)},
         "y": np.array(["a", "b"], object)[yv]}, categorical=["y"])
    model = GBMEstimator(ntrees=15, max_depth=4, seed=4).train(tr, y="y",
                                                               validation_frame=va)
    assert model.validation_metrics is not None
    assert model.validation_metrics["AUC"] > 0.75


def test_gbm_cv():
    X, y = make_classification(n=1500, seed=31)
    fr = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(8)},
         "y": np.array(["a", "b"], object)[y]}, categorical=["y"])
    model = GBMEstimator(ntrees=10, max_depth=3, nfolds=3, seed=6).train(fr, y="y")
    assert model.cross_validation_metrics is not None
    assert model.cross_validation_metrics["AUC"] > 0.7


def test_gbm_scoring_adapts_test_domains():
    """Unseen/reordered test-time categorical levels must map into the
    training domain (adaptTestForTrain, hex/Model.java:1850)."""
    r = np.random.RandomState(17)
    n = 2000
    lv = np.array(["a", "b", "c"], object)
    cat = r.randint(0, 3, n)
    y = (cat == 2).astype(int)
    tr = h2o3_tpu.Frame.from_numpy(
        {"c": lv[cat], "y": np.array(["n", "y"], object)[y]}, categorical=["y"])
    model = GBMEstimator(ntrees=5, max_depth=2, min_rows=5.0, seed=3).train(tr, y="y")
    # test frame whose domain is a reordered superset: codes differ from train
    te_cat = np.array(["zz_new", "c", "a", "c"], object)
    te = h2o3_tpu.Frame.from_numpy({"c": te_cat})
    p = model.predict(te).to_pandas()
    # rows with level "c" must score high, "a" low, unseen level ~ NA path
    assert p["p1"][1] > 0.55 and p["p1"][3] > 0.55
    assert p["p1"][2] < 0.35
    assert p["p1"][1] == p["p1"][3]


def test_gbm_missing_response_rows_excluded():
    r = np.random.RandomState(5)
    n = 1000
    x = r.randn(n)
    y = np.array(["n", "y"], object)[(x > 0).astype(int)]
    y[:100] = ""  # blank -> NA after interning? use explicit None-ish level
    fr = h2o3_tpu.Frame.from_numpy({"x": x, "y": y}, categorical=["y"])
    # force NA: blank string becomes its own level; instead use numeric resp
    yr = x * 2
    yr[:100] = np.nan
    fr2 = h2o3_tpu.Frame.from_numpy({"x": x, "yr": yr})
    model = GBMEstimator(ntrees=5, max_depth=3, seed=1).train(fr2, y="yr")
    assert model.training_metrics["nobs"] == 900


def test_gbm_early_stopping():
    X, y = make_classification(n=2000, seed=41)
    Xv, yv = make_classification(n=1000, seed=42)
    tr = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(8)},
         "y": np.array(["a", "b"], object)[y]}, categorical=["y"])
    va = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": Xv[:, i] for i in range(8)},
         "y": np.array(["a", "b"], object)[yv]}, categorical=["y"])
    model = GBMEstimator(ntrees=200, max_depth=3, learn_rate=0.5,
                         stopping_rounds=2, stopping_tolerance=0.01,
                         score_tree_interval=5, seed=8).train(
        tr, y="y", validation_frame=va)
    ntrees_built = model.forest.feat.shape[0]
    assert ntrees_built < 200, "early stopping never fired"
    assert len(model.output["scoring_history"]) >= 3


def test_gbm_fold_assignment_param_accepted():
    X, y = make_classification(n=800, seed=51)
    fr = h2o3_tpu.Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(8)},
         "y": np.array(["a", "b"], object)[y]}, categorical=["y"])
    model = GBMEstimator(ntrees=5, max_depth=3, nfolds=3, seed=6,
                         fold_assignment="random").train(fr, y="y")
    assert model.cross_validation_metrics is not None
