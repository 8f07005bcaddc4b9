"""DRF at the depth the user asked for (PR 35): trees grow past the
complete layout in the frontier regime (models/frontier.py) — whole, to
``max_depth``, bit-equal to the complete layout where both can grow a
tree, chunk after chunk and restart after restart — and the readers of
the complete ``Tree`` that were not carried over raise a named error on a
forest that is really deeper than that layout holds. Small frames only:
no test here grows a tree on more than a few thousand rows."""

import dataclasses

import jax
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.models import frontier, tree as tree_mod
from h2o3_tpu.models.drf import MAX_COMPLETE_DEPTH, DRFEstimator

pytestmark = pytest.mark.allow_key_leak     # module-scoped fits below

GROUPS = 21


def _same(a, b) -> bool:
    return all(jax.tree.leaves(jax.tree.map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)))


@pytest.fixture(scope="module")
def mixed_frame():
    """3,000 rows: two numeric columns (one with missing values), a
    12-level categorical, a noisy binary response."""
    r = np.random.default_rng(0)
    n = 3000
    x1, x2 = r.normal(size=n), r.normal(size=n)
    c = r.integers(0, 12, n)
    y = ((x1 + 0.5 * x2 + (c % 3) + r.normal(size=n)) > 1).astype(np.int32)
    x1[r.random(n) < 0.05] = np.nan
    return h2o3_tpu.Frame.from_numpy(
        {"x1": x1, "x2": x2, "c": c.astype(np.int32), "y": y},
        domains={"c": [f"l{i}" for i in range(12)], "y": ["n", "y"]})


@pytest.fixture(scope="module")
def spine():
    """A frame whose trees are one spine: 21 one-hot groups whose
    responses are 2^group, so the largest group left is always the best
    one to split off — 20 levels — and a 20-bin column of small noise
    that splits each group a little further. (model, frame)."""
    r = np.random.default_rng(1)
    per = 48
    g = np.repeat(np.arange(GROUPS), per)
    z = r.integers(0, 20, g.shape[0])
    y = 2.0 ** g + 0.05 * z
    cols = {f"g{j}": (g == j).astype(np.float64) for j in range(GROUPS)}
    cols["z"] = z.astype(np.float64)
    cols["y"] = y
    fr = h2o3_tpu.Frame.from_numpy(cols)
    before = telemetry.REGISTRY.counter_totals().get(
        "drf_depth_capped_total", 0.0)
    model = DRFEstimator(ntrees=2, seed=3, mtries=GROUPS + 1,
                         sample_rate=1.0).train(fr, y="y")
    capped = telemetry.REGISTRY.counter_totals().get(
        "drf_depth_capped_total", 0.0) - before
    return model, fr, capped


def test_a_spine_is_grown_whole(spine):
    model, fr, capped = spine
    assert isinstance(model.grown, frontier.DeepTree)
    assert model.output["depth_reached"] == 20
    chunk = [s for s in telemetry.spans_snapshot(last=1 << 16)
             if s["name"] == "drf.chunk"
             and s["meta"].get("depth_reached") == 20]
    assert chunk, "no drf.chunk span reports the depth"
    meta = chunk[-1]["meta"]
    assert meta["levels_frontier"] == 20 - (
        meta["levels_kernel"] + meta["levels_xla"]) > 0
    assert 100 <= meta["leaves"] <= 2 * 2 * GROUPS * 20
    assert 0 < meta["frontier_nodes_max"] <= 64
    assert capped == 0
    # every group is told apart: a row's prediction lies in its own
    # group's range [2^g, 2^g + 0.95]
    p = model.predict(fr).to_pandas()["predict"].to_numpy()
    base = 2.0 ** np.floor(np.log2(fr.col("y").to_numpy()))
    assert np.max(np.abs(p - base - 0.475)) <= 0.6


@pytest.mark.parametrize("reader", [
    "forest", "predict_contributions", "predict_leaf_node_assignment",
    "feature_frequencies", "download_mojo", "download_pojo"])
def test_a_reader_of_the_complete_tree_raises_its_named_error(
        spine, reader, tmp_path):
    model, fr, _ = spine
    assert model.output["depth_reached"] > MAX_COMPLETE_DEPTH
    with pytest.raises(frontier.DeepForestError, match="level 19"):
        if reader == "forest":
            model.forest
        elif reader.startswith("download"):
            getattr(model, reader)(str(tmp_path / "m"))
        else:
            getattr(model, reader)(fr)


def test_what_a_deep_forest_still_does(spine):
    model, fr, _ = spine
    assert model.training_metrics["MSE"] >= 0          # OOB metrics
    assert len(model.varimp_table) == GROUPS + 1
    perf = model.model_performance(fr)
    assert perf["MSE"] < 1e-3 * float(np.var(fr.col("y").to_numpy()))


@pytest.fixture(scope="module", params=["off", "interpret"])
def two_regimes(mixed_frame, request):
    """The same depth-8 forest grown in the complete layout all the way
    (in XLA) and with the frontier regime forced on from level 3 — in
    XLA too, or with the Pallas kernels in interpret mode: the level
    kernels above level 3, ``tree_frontier_hist`` from there."""
    fits = {}
    was = tree_mod.FRONTIER_FROM
    mp = pytest.MonkeyPatch()
    try:
        for start in (0, 3):
            tree_mod.FRONTIER_FROM = start
            mp.setenv("H2O3TPU_PALLAS", request.param if start else "off")
            fits[start] = DRFEstimator(ntrees=3, seed=1, max_depth=8).train(
                mixed_frame, y="y")
    finally:
        tree_mod.FRONTIER_FROM = was
        mp.undo()
    chunk = [s for s in telemetry.spans_snapshot(last=1 << 16)
             if s["name"] == "drf.chunk"][-1]["meta"]
    assert chunk["frontier_hist"] == (
        "kernel" if request.param == "interpret" else "xla")
    assert chunk["levels_frontier"] == 7 and chunk["hist_operand_rows"] == 2
    return fits[0], fits[3]


def test_the_two_regimes_grow_the_same_forest(two_regimes):
    complete, forced = two_regimes
    assert isinstance(complete.grown, tree_mod.Tree)
    assert isinstance(forced.grown, frontier.DeepTree)
    assert forced.grown.top.feat.shape[1] == 3
    assert _same(complete.forest, forced.forest)
    assert complete.training_metrics["logloss"] == \
        forced.training_metrics["logloss"]
    assert complete.varimp_table == forced.varimp_table


def test_the_node_tables_route_as_the_complete_tree(two_regimes,
                                                    mixed_frame):
    from h2o3_tpu.models.drf import _predict_deep_forest
    _, forced = two_regimes
    bm = forced.bm
    deep = np.asarray(_predict_deep_forest(forced.grown, bm.bins,
                                           bm.nbins_total))
    flat = np.asarray(tree_mod.predict_forest(forced.forest, bm.bins,
                                              bm.nbins_total))
    assert np.array_equal(deep, flat)


@pytest.mark.parametrize("reader", ["predict_contributions",
                                    "predict_leaf_node_assignment",
                                    "feature_frequencies"])
def test_a_shallow_forest_is_handed_over_as_the_complete_tree(
        two_regimes, mixed_frame, reader):
    complete, forced = two_regimes
    a = getattr(complete, reader)(mixed_frame).to_pandas()
    b = getattr(forced, reader)(mixed_frame).to_pandas()
    assert a.equals(b)


@pytest.fixture(scope="module")
def default_forest(mixed_frame):
    return DRFEstimator(ntrees=4, seed=7).train(mixed_frame, y="y")


def test_a_default_forest_grows_past_the_old_cap(default_forest):
    # max_depth 20, min_rows 1: the old complete layout stopped every
    # tree at level 14; 3,000 noisy rows go deeper
    assert isinstance(default_forest.grown, frontier.DeepTree)
    assert default_forest.output["depth_reached"] > MAX_COMPLETE_DEPTH
    assert default_forest.training_metrics["AUC"] > 0.7


def _operand_rows(**fit):
    frame, x, y = fit.pop("frame"), fit.pop("x"), fit.pop("y", "y")
    model = DRFEstimator(ntrees=2, seed=7, max_depth=12, **fit).train(
        frame, y=y, x=x)
    chunk = [s for s in telemetry.spans_snapshot(last=1 << 16)
             if s["name"] == "drf.chunk"][-1]["meta"]
    assert chunk["frontier_hist"] == "xla" and chunk["levels_frontier"] == 5
    return model, chunk["hist_operand_rows"]


@pytest.fixture(scope="module")
def weighted_frame(mixed_frame):
    """``mixed_frame`` with a weights column of 0 and 3 (whole once
    rescaled), one of 0.5 … 2 (fractional) and a numeric response."""
    r = np.random.default_rng(5)
    cols = {n: mixed_frame.col(n).to_numpy() for n in ("x1", "x2")}
    cols["c"] = mixed_frame.col("c").to_numpy().astype(np.int32)
    cols["y"] = mixed_frame.col("y").to_numpy().astype(np.int32)
    n = len(cols["y"])
    cols["w03"] = 3.0 * (r.random(n) < 0.8)
    cols["wfrac"] = r.choice([0.5, 1.0, 2.0], n)
    cols["z"] = cols["x2"] + 0.1 * r.normal(size=n)
    return h2o3_tpu.Frame.from_numpy(
        cols, domains={"c": [f"l{i}" for i in range(12)], "y": ["n", "y"]})


def test_the_histogram_operand_is_as_short_as_the_statistics(
        weighted_frame, monkeypatch):
    """Two statistics a forest (a hessian of 1), and one bfloat16 piece
    each where they are 0 or ±1: a class response under whole weights."""
    x = ["x1", "x2", "c"]
    plain, rows = _operand_rows(frame=weighted_frame, x=x)
    assert rows == 2
    whole, rows = _operand_rows(frame=weighted_frame, x=x,
                                weights_column="w03")
    assert rows == 2
    assert _operand_rows(frame=weighted_frame, x=x,
                         weights_column="wfrac")[1] == 6
    assert _operand_rows(frame=weighted_frame, x=x, y="z")[1] == 6
    # forced to three pieces, the same forests: the two pieces left out
    # are identically zero
    real = DRFEstimator._training_weights

    def three_pieces(self, frame, y):
        w, y_dev, rows = real(self, frame, y)
        return w, y_dev, dataclasses.replace(rows, w_whole=False)
    monkeypatch.setattr(DRFEstimator, "_training_weights", three_pieces)
    for model, fit in ((plain, {}), (whole, {"weights_column": "w03"})):
        forced, rows = _operand_rows(frame=weighted_frame, x=x, **fit)
        assert rows == 6
        assert _same(model.grown, forced.grown)
        assert model.training_metrics["logloss"] == \
            forced.training_metrics["logloss"]


def test_chunked_is_single_scan(default_forest, mixed_frame):
    chunked = DRFEstimator(ntrees=4, seed=7, max_runtime_secs=1e6).train(
        mixed_frame, y="y")
    chunks = [s for s in telemetry.spans_snapshot(last=1 << 16)
              if s["name"] == "drf.chunk"][-4:]
    assert [s["meta"]["trees"] for s in chunks] == [1, 1, 1, 1]
    assert _same(default_forest.grown, chunked.grown)
    assert default_forest.training_metrics["logloss"] == \
        chunked.training_metrics["logloss"]


def test_a_checkpoint_restart_appends_bit_equal_trees(default_forest,
                                                     mixed_frame):
    first = DRFEstimator(ntrees=2, seed=7).train(mixed_frame, y="y")
    more = DRFEstimator(ntrees=4, seed=7, checkpoint=first).train(
        mixed_frame, y="y")
    assert more.ntrees == 4
    assert _same(default_forest.grown, more.grown)
    assert np.allclose(more.training_metrics["logloss"],
                       default_forest.training_metrics["logloss"],
                       rtol=1e-6)
    a = default_forest.predict(mixed_frame).to_pandas()
    assert a.equals(more.predict(mixed_frame).to_pandas())


def test_the_column_draw_is_the_nodes_own():
    """A node's columns depend on (tree key, heap id) alone: the same in
    a level of any width, in any order."""
    key = jax.random.PRNGKey(5)
    heap = np.arange(64, 128, dtype=np.int32)
    whole = np.asarray(tree_mod._mtries_mask(key, heap, 10, 3))
    assert (whole.sum(axis=1) == 3).all()
    some = np.asarray(tree_mod._mtries_mask(key, heap[[40, 3, 17]], 10, 3))
    assert np.array_equal(some, whole[[40, 3, 17]])
    assert len({tuple(r) for r in whole}) > 20


def test_frontier_capacity_comes_from_rows_and_depth():
    assert frontier.frontier_capacity(48_234_496, 20) == 2 ** 19
    assert frontier.frontier_capacity(3000, 20) == 4096
    assert frontier.frontier_capacity(3000, 6) == 32
    assert frontier.complete_levels(3000, 20, 9) == 9
    assert frontier.complete_levels(100, 20, 9) == 7
    assert frontier.complete_levels(3000, 6, 9) == 6
    assert frontier.complete_levels(3000, 20, 0) == 20
