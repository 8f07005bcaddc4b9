"""Model-batched training (parallel/model_batch.py): vmap hyperparameter
combos into ONE compiled program for grid search, AutoML and the GLM
(alpha, lambda) product.

Acceptance contract (ISSUE 4): a numeric-only GBM grid of >= 8 combos
trains through the batched path with exactly one boost-program compile
per shape bucket (asserted via the compile observer), and batched
results match the sequential path's metrics within 1e-5 under fixed
seeds. Satellite regressions ride along: per-model early-stop masks,
canonical-key resume filtering, the Frame.device_matrix cache and the
device-resident ordinal GLM predict path.
"""

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.ml.grid import GridSearch
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.glm import GLMEstimator
from h2o3_tpu.parallel import model_batch


def _class_frame(n=400, seed=1, noise=False):
    r = np.random.RandomState(seed)
    a, b, c = r.randn(n), r.randn(n), r.randn(n)
    if noise:
        yv = r.randint(0, 2, n)
    else:
        yv = (a + 0.5 * b + 0.3 * r.randn(n) > 0).astype(int)
    return h2o3_tpu.Frame.from_numpy(
        {"a": a, "b": b, "c": c,
         "y": np.array(["N", "Y"], object)[yv]}, categorical=["y"])


def _misses(fn: str) -> float:
    """Total jit-cache misses recorded for an observed_jit fn across its
    shape-bucket label sets (telemetry/compile_observer.py)."""
    tot = 0.0
    for (nm, lbl), m in list(telemetry.REGISTRY._metrics.items()):
        if nm.endswith("jit_cache_miss_total") and dict(lbl).get("fn") == fn:
            tot += m.value
    return tot


def _by_combo(grid):
    return {tuple(sorted(m.output["grid_params"].items())): m
            for m in grid.models}


def _metric_diff(m1, m2, keys=("AUC", "logloss", "RMSE")):
    d1, d2 = m1.training_metrics.to_dict(), m2.training_metrics.to_dict()
    return max(abs(d1[k] - d2[k]) for k in keys if k in d1 and k in d2)


# ------------------------------------------------- GBM batched tentpole


def test_gbm_numeric_grid_one_compile_per_bucket_and_parity(monkeypatch):
    """The acceptance criterion: 8 numeric-only combos -> ONE
    gbm.boost_scan_batched compile, sequential-equal metrics,
    leaderboard order preserved."""
    fr = _class_frame()
    hyper = {"learn_rate": [0.05, 0.1], "sample_rate": [0.7, 1.0],
             "min_rows": [1.0, 10.0]}          # 8 combos, one shape bucket
    fixed = dict(ntrees=10, max_depth=3, seed=7)

    m0 = _misses("gbm.boost_scan_batched")
    b0 = telemetry.REGISTRY.value("batched_train_batches_total", algo="gbm")
    g_bat = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
    assert len(g_bat.models) == 8
    assert telemetry.REGISTRY.value("batched_train_batches_total",
                                    algo="gbm") == b0 + 1
    assert _misses("gbm.boost_scan_batched") - m0 == 1, \
        "expected exactly ONE boost-program compile for the bucket"
    assert telemetry.REGISTRY.value("batched_train_width", algo="gbm") >= 1

    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    g_seq = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
    by = _by_combo(g_seq)
    for m in g_bat.models:
        m2 = by[tuple(sorted(m.output["grid_params"].items()))]
        assert _metric_diff(m, m2) < 1e-5
        assert m.forest.feat.shape[0] == m2.forest.feat.shape[0]
        # varimp ordering agrees too (same trees -> same gains)
        assert [v[0] for v in m.output["varimp"]] == \
            [v[0] for v in m2.output["varimp"]]
    # leaderboard order: identical combos in identical order
    assert [m.output["grid_params"] for m in g_bat.sorted_models()] == \
        [m.output["grid_params"] for m in g_seq.sorted_models()]


def test_gbm_batched_early_stop_masks_match_sequential(monkeypatch):
    """Per-model early-stop MASKS (host-side truncation of the stacked
    forest) reproduce the sequential walk's per-model stop points and
    scoring histories exactly."""
    fr = _class_frame(n=200, seed=3, noise=True)   # flat deviance: stops
    hyper = {"learn_rate": [0.5, 0.01], "min_rows": [5.0, 20.0]}
    fixed = dict(ntrees=40, max_depth=3, seed=7, stopping_rounds=2,
                 score_tree_interval=1, stopping_tolerance=1e-2)
    g_bat = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    g_seq = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
    by = _by_combo(g_seq)
    stopped_any = False
    for m in g_bat.models:
        m2 = by[tuple(sorted(m.output["grid_params"].items()))]
        assert m.forest.feat.shape[0] == m2.forest.feat.shape[0]
        assert m.output["scoring_history"] == m2.output["scoring_history"]
        assert _metric_diff(m, m2) < 1e-5
        stopped_any |= m.forest.feat.shape[0] < 40
    assert stopped_any, "no model early-stopped; test lost its teeth"


def _gbm_spans_of(fit):
    """Names and meta of the ``gbm.*`` spans that ``fit()`` opens."""
    before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
    fit()
    return [(s["name"], s["meta"]) for s in telemetry.spans_snapshot(1 << 20)
            if s["name"].startswith("gbm.") and s["id"] not in before]


def test_gbm_batched_fit_opens_the_sequential_fits_spans():
    """A batched and a sequential fit of the same combo run the same
    preamble, init and finish (models/gbm.py), so a trace of a grid
    search shows the phases a trace of one fit shows."""
    from h2o3_tpu.models.gbm import fit_gbm_batched
    fr = _class_frame()
    combo = dict(ntrees=6, max_depth=3, seed=7, learn_rate=0.2)
    phases = {"gbm.bin", "gbm.init", "gbm.chunk", "gbm.rescore",
              "gbm.metrics"}
    seq = _gbm_spans_of(lambda: GBMEstimator(**combo).train(fr, y="y"))
    bat = _gbm_spans_of(
        lambda: fit_gbm_batched(GBMEstimator, [combo], fr, y="y"))
    assert {n for n, _ in seq} - {"gbm.fit"} == phases
    assert {n for n, _ in bat} == phases
    for spans in (seq, bat):
        meta = {n: m for n, m in spans}
        assert {"trees", "levels_kernel", "levels_xla"} <= set(meta["gbm.chunk"])
        assert {"levels_select", "levels_gather"} <= set(meta["gbm.rescore"])


def test_gbm_batched_max_models_cap_discards_extras():
    """max_models caps the grid exactly like the sequential walk; pre-
    trained extras are discarded from the DKV, not leaked."""
    from h2o3_tpu.core.kv import DKV
    fr = _class_frame()
    before = {k for k in DKV.keys() if k.startswith("model_gbm")}
    hyper = {"learn_rate": [0.05, 0.1, 0.15, 0.2]}
    g = GridSearch(GBMEstimator, hyper,
                   search_criteria={"strategy": "Cartesian",
                                    "max_models": 2},
                   ntrees=5, max_depth=3, seed=7).train(fr, y="y")
    assert len(g.models) == 2
    new = {k for k in DKV.keys()
           if k.startswith("model_gbm")} - before
    assert new == {m.key for m in g.models}, \
        "discarded pre-trained models must leave the DKV"


# ------------------------------------------------- GLM batched tentpole


def test_glm_alpha_lambda_product_parity(monkeypatch):
    """The (alpha, lambda) product of a GLM grid solves as one vmapped
    IRLS program per use_l1 partition; metrics match sequential within
    1e-5 (coefs within ADMM jitter)."""
    fr = _class_frame(n=300, seed=2)
    hyper = {"alpha": [0.0, 0.5], "lambda_": [1e-2, 1e-3, 1e-4, 0.0]}
    b0 = telemetry.REGISTRY.value("batched_train_batches_total", algo="glm")
    g_bat = GridSearch(GLMEstimator, hyper,
                       family="binomial").train(fr, y="y")
    assert len(g_bat.models) == 8
    assert telemetry.REGISTRY.value("batched_train_batches_total",
                                    algo="glm") == b0 + 1
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    g_seq = GridSearch(GLMEstimator, hyper,
                       family="binomial").train(fr, y="y")
    by = _by_combo(g_seq)
    for m in g_bat.models:
        m2 = by[tuple(sorted(m.output["grid_params"].items()))]
        assert _metric_diff(m, m2, keys=("AUC", "logloss")) < 1e-5
        # ADMM's inexact inner solves jitter coefs slightly more than
        # the metric surface moves; bound them loosely
        assert float(np.max(np.abs(np.asarray(m.coef)
                                   - np.asarray(m2.coef)))) < 5e-4
        assert m.output["lambda_best"] == m2.output["lambda_best"]


# -------------------------------------------- planner / fallback layer


def test_bucket_planning_structural_knobs_split():
    # same depth bucket (3..6) batches; 12 lands in the 7..10 bucket...
    # (tree.py DEPTH_BUCKETS = (6, 10, 14)): 3,5 -> 6 | 12 -> 14
    combos = [{"max_depth": 3, "learn_rate": 0.1},
              {"max_depth": 5, "learn_rate": 0.2},
              {"max_depth": 12, "learn_rate": 0.1}]
    buckets = model_batch.plan_buckets("gbm", combos)
    assert sorted(b.width for b in buckets) == [1, 2]
    # a structural knob (ntrees) always splits
    combos = [{"ntrees": 10, "learn_rate": 0.1},
              {"ntrees": 20, "learn_rate": 0.1},
              {"ntrees": 10, "learn_rate": 0.2}]
    buckets = model_batch.plan_buckets("gbm", combos)
    assert sorted(b.width for b in buckets) == [1, 2]
    # glm: only alpha/lambda batch
    combos = [{"alpha": 0.1, "lambda_": 0.0},
              {"alpha": 0.9, "lambda_": 1e-3}]
    assert model_batch.plan_buckets("glm", combos)[0].width == 2


def test_combo_key_canonicalizes_json_round_trips():
    # JSON round trips tuples to lists; the resume filter must not care
    a = {"hidden": [200, 200], "rate": 0.1}
    b = {"rate": 0.1, "hidden": (200, 200)}
    assert model_batch.combo_key(a) == model_batch.combo_key(b)
    assert model_batch.combo_key(a) != model_batch.combo_key(
        {"hidden": [200, 100], "rate": 0.1})


def test_resume_skip_done_filter_set_semantics():
    """_skip_done filtering keys combos on canonical tuples — same
    result as the old O(n·m) dict-equality scan."""
    fr = _class_frame(n=200, seed=5)
    hyper = {"alpha": [0.1, 0.5], "lambda_": [1e-3, 1e-4]}
    gs = GridSearch(GLMEstimator, hyper, family="binomial")
    done = [{"alpha": 0.1, "lambda_": 1e-3}, {"alpha": 0.5, "lambda_": 1e-4}]
    grid = gs.train(fr, y="y", _skip_done=done)
    trained = {tuple(sorted(m.output["grid_params"].items()))
               for m in grid.models}
    assert len(grid.models) == 2
    assert trained == {(("alpha", 0.1), ("lambda_", 1e-4)),
                       (("alpha", 0.5), ("lambda_", 1e-3))}


def test_cv_combos_fall_back_sequential():
    """nfolds >= 2 is batch-ineligible; the grid walk falls back and
    still delivers CV'd models."""
    fr = _class_frame(n=200)
    b0 = telemetry.REGISTRY.value("batched_train_batches_total", algo="gbm")
    g = GridSearch(GBMEstimator, {"learn_rate": [0.1, 0.2]}, ntrees=5,
                   max_depth=3, seed=7, nfolds=2).train(fr, y="y")
    assert len(g.models) == 2
    assert all(m.cross_validation_metrics is not None for m in g.models)
    assert telemetry.REGISTRY.value("batched_train_batches_total",
                                    algo="gbm") == b0


def test_unsupported_algo_falls_back_sequential():
    from h2o3_tpu.models.drf import DRFEstimator
    fr = _class_frame(n=200)
    g = GridSearch(DRFEstimator, {"ntrees": [4, 6]}, max_depth=3,
                   seed=7).train(fr, y="y")
    assert len(g.models) == 2


def test_batch_models_knob_off_disables(monkeypatch):
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    assert not model_batch.enabled()
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "auto")
    assert model_batch.enabled()


# ------------------------------------------------------ satellites


def test_frame_device_matrix_cached_and_invalidated():
    fr = _class_frame(n=64)
    m1 = fr.device_matrix(["a", "b"])
    assert fr.device_matrix(["a", "b"]) is m1          # cache hit
    assert fr.device_matrix(["b", "a"]) is not m1      # order is identity
    assert fr.matrix(["a", "b"]) is m1                 # matrix() delegates
    from h2o3_tpu.frame.column import column_from_numpy
    from h2o3_tpu.parallel import mesh as mesh_mod
    col = column_from_numpy("z", np.zeros(64), fr.nrows_padded,
                            mesh_mod.row_sharding())
    fr.add_column(col)                                 # mutation invalidates
    assert fr.device_matrix(["a", "b"]) is not m1


def test_ordinal_predict_stays_on_device():
    """Ordinal GLM scoring computes the cumulative-logit pipeline on
    device with ONE host fetch; probabilities match the closed form."""
    r = np.random.RandomState(11)
    n = 3000
    x = r.randn(n)
    lat = 1.4 * x + r.logistic(size=n)
    y = np.where(lat < -0.8, "l0", np.where(lat < 0.9, "l1", "l2"))
    fr = h2o3_tpu.Frame.from_numpy({"x": x, "y": y}, categorical=["y"])
    m = GLMEstimator(family="ordinal", lambda_=0.0,
                     standardize=False).train(fr, y="y")
    from h2o3_tpu.parallel import mesh as mesh_mod
    f0 = mesh_mod.FETCH_CALLS
    raw = m._score_raw(fr)
    assert mesh_mod.FETCH_CALLS - f0 <= 1, \
        "ordinal predict must fetch ONCE (device-resident pipeline)"
    probs = np.stack([raw[f"p{k}"] for k in range(3)], axis=1)
    assert np.allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    # closed-form check against the model's own coefficients
    import jax
    X1 = np.asarray(m._design(fr))[:n]
    eta = X1[:, :-1] @ np.asarray(m.coef[:-1])
    alphas = np.asarray(m.output["ordinal_alphas"])
    cum = 1.0 / (1.0 + np.exp(-(alphas[None, :] - eta[:, None])))
    cum = np.concatenate([np.zeros((n, 1)), cum, np.ones((n, 1))], axis=1)
    assert np.allclose(probs, np.diff(cum, axis=1), atol=1e-5)


def test_grid_models_total_counts_both_paths(monkeypatch):
    fr = _class_frame(n=200)
    c0 = telemetry.REGISTRY.value("grid_models_total", algo="glm")
    GridSearch(GLMEstimator, {"alpha": [0.1, 0.5]}, family="binomial",
               lambda_=1e-4).train(fr, y="y")
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    GridSearch(GLMEstimator, {"alpha": [0.1, 0.5]}, family="binomial",
               lambda_=1e-4).train(fr, y="y")
    assert telemetry.REGISTRY.value("grid_models_total",
                                    algo="glm") == c0 + 4
