"""DRF at the depth the user asked for (PR 35): trees grow past the
complete layout in the frontier regime (models/frontier.py) — whole, to
``max_depth``, bit-equal to the complete layout where both can grow a
tree, chunk after chunk and restart after restart — and the readers of
the complete ``Tree`` that were not carried over raise a named error on a
forest that is really deeper than that layout holds. The rows are
ordered by node once every few frontier levels (PR 38): the trees and
every row's final node are those of a sort at every level. Small frames
only: no test here grows a tree on more than a few thousand rows."""

import dataclasses
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.models import drf as drf_mod, frontier, tree as tree_mod
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
    # how often the rows were ordered, and what the order in between cost
    assert chunk["levels_sorted"] == frontier.sort_levels(
        7, frontier.SORT_PERIOD) < 7
    assert 0 < chunk["frontier_rescan_pct"] < 100
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


@pytest.fixture
def frontier_from_3(monkeypatch):
    """The frontier regime from level 3, as ``two_regimes`` forces it: the
    complete levels above it are the costliest part of a small forest's
    program to compile, and a fit reads the constant when it starts."""
    monkeypatch.setattr(tree_mod, "FRONTIER_FROM", 3)


@pytest.fixture(scope="module")
def default_forest(mixed_frame):
    """Four trees at the default depth, 20, from level 3 in the frontier
    regime (``frontier_from_3``)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tree_mod, "FRONTIER_FROM", 3)
        return DRFEstimator(ntrees=4, seed=7).train(mixed_frame, y="y")


def test_a_default_forest_grows_past_the_old_cap(default_forest):
    # max_depth 20, min_rows 1: the old complete layout stopped every
    # tree at level 14; 3,000 noisy rows go deeper
    assert isinstance(default_forest.grown, frontier.DeepTree)
    assert default_forest.output["depth_reached"] > MAX_COMPLETE_DEPTH
    assert default_forest.training_metrics["AUC"] > 0.7


def _operand_rows(**fit):
    """A depth-6 forest, the frontier regime from level 3
    (``frontier_from_3``)."""
    frame, x, y = fit.pop("frame"), fit.pop("x"), fit.pop("y", "y")
    model = DRFEstimator(ntrees=2, seed=7, max_depth=6, **fit).train(
        frame, y=y, x=x)
    chunk = [s for s in telemetry.spans_snapshot(last=1 << 16)
             if s["name"] == "drf.chunk"][-1]["meta"]
    assert chunk["frontier_hist"] == "xla" and chunk["levels_frontier"] == 3
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
        weighted_frame, frontier_from_3, monkeypatch):
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


@pytest.mark.parametrize("pallas", ["off", "interpret"])
@pytest.mark.parametrize("whole", [False, True])
@pytest.mark.parametrize("unit_h", [False, True])
def test_one_plan_sizes_both_regimes_operands(unit_h, whole, pallas):
    """``tree.stats_plan`` is the one decision: the frontier's plan is
    its (statistics, pieces), whichever histogram pass runs there."""
    tp = tree_mod.TreeParams(max_depth=20, nbins_total=126,
                             cat_feats=(False,) * 10, whole_stats=whole,
                             pallas=pallas)
    plan = tree_mod.stats_plan(tp, unit_h)
    assert plan == (2 if unit_h else 3, 1 if whole else 3)
    assert frontier.hist_plan(tp, 48_000_000, 10, unit_h)[1:] == plan


def test_the_complete_levels_and_the_frontier_ask_the_same_plan(
        mixed_frame, monkeypatch):
    """A forest grown through the level kernels (interpret mode) above
    the frontier and the frontier's kernel below it: both regimes take
    their operand from ``stats_plan``, with the forest's statistics."""
    asked = []
    real = tree_mod.stats_plan

    def spy(params, unit_h):
        asked.append((sys._getframe(1).f_code.co_name, unit_h,
                      params.whole_stats))
        return real(params, unit_h)
    monkeypatch.setattr(tree_mod, "stats_plan", spy)
    monkeypatch.setattr(tree_mod, "FRONTIER_FROM", 3)
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    drf_mod._bag_scan.clear_cache()     # grow_tree is traced anew
    DRFEstimator(ntrees=1, seed=1, max_depth=6).train(mixed_frame, y="y")
    chunk = [s for s in telemetry.spans_snapshot(last=1 << 16)
             if s["name"] == "drf.chunk"][-1]["meta"]
    assert chunk["levels_kernel"] == 3 and chunk["levels_frontier"] == 3
    assert chunk["hist_operand_rows"] == 2
    assert {c for c, *_ in asked} == {"grow_tree", "hist_plan"}
    assert {(u, w) for _, u, w in asked} == {(True, True)}


def test_chunked_is_single_scan(default_forest, mixed_frame,
                               frontier_from_3):
    chunked = DRFEstimator(ntrees=4, seed=7, max_runtime_secs=1e6).train(
        mixed_frame, y="y")
    chunks = [s for s in telemetry.spans_snapshot(last=1 << 16)
              if s["name"] == "drf.chunk"][-4:]
    assert [s["meta"]["trees"] for s in chunks] == [1, 1, 1, 1]
    assert _same(default_forest.grown, chunked.grown)
    assert default_forest.training_metrics["logloss"] == \
        chunked.training_metrics["logloss"]


def test_a_checkpoint_restart_appends_bit_equal_trees(default_forest,
                                                     mixed_frame,
                                                     frontier_from_3):
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


# ------------------------------------------ a sort every few levels (PR 38)

FROM, DEPTH = 3, 14             # 11 frontier levels: 2 * 4 + 1 and two more


def _grow(bm, y, *, period, pallas="off", whole=True, seed=0):
    """One bagged tree of ``models/drf._bag_body``'s kind straight from
    ``grow_tree`` → (DeepTree, ref): the frontier regime from level 3,
    a sort every ``period`` levels."""
    from h2o3_tpu.parallel.mesh import get_mesh
    tp = tree_mod.TreeParams(
        max_depth=DEPTH, min_rows=1.0, learn_rate=1.0, reg_lambda=0.0,
        min_split_improvement=1e-5, nbins_total=bm.nbins_total,
        cat_feats=tuple(bool(v) for v in bm.is_cat), pallas=pallas,
        frontier_from=FROM, frontier_sort_every=period, whole_stats=whole)
    kb, kt = jax.random.split(jax.random.PRNGKey(seed))
    n = bm.bins.shape[0]
    w = jax.random.bernoulli(kb, 0.632, (n,)).astype(jnp.float32) \
        * (jnp.arange(n) < len(y))
    g = -jnp.zeros((n,), jnp.float32).at[: len(y)].set(
        jnp.asarray(y, jnp.float32))
    grown, ref, _ = jax.jit(lambda: tree_mod.grow_tree(
        bm.bins, bm.nbins, w, g, None, jnp.ones((bm.bins.shape[1],), bool),
        params=tp, mesh=get_mesh(), mtries=2, key=kt))()
    return grown, np.asarray(ref)


@pytest.mark.parametrize("pallas", ["off", "interpret"])
def test_a_sort_every_few_levels_grows_the_same_tree(default_forest,
                                                     mixed_frame, pallas):
    assert frontier.SORT_PERIOD > 1
    assert DEPTH - FROM >= 2 * frontier.SORT_PERIOD + 1
    bm, y = default_forest.bm, mixed_frame.col("y").to_numpy()
    each, ref_each = _grow(bm, y, period=1, pallas=pallas)
    some, ref_some = _grow(bm, y, period=frontier.SORT_PERIOD,
                           pallas=pallas)
    assert int(np.asarray(each.deep.is_split).any(axis=1).sum()) \
        == DEPTH - FROM
    assert _same(each.top, some.top) and _same(each.deep, some.deep)
    assert np.array_equal(ref_each, ref_some)
    # a sort at every level reads the live rows, once
    assert each.scanned[0] == each.scanned[1] > 0
    assert some.scanned[0] > some.scanned[1] == each.scanned[1]


def test_a_shared_ancestor_across_a_super_batch_boundary(
        default_forest, mixed_frame, monkeypatch):
    """Blocks of 4 nodes, super-batches of 2 blocks, chunks of 64 rows:
    3,000 rows cross dozens of super-batches a level, and three levels
    after a sort an ancestor's 8 descendants lie in up to three blocks —
    the rows a super-batch routes lie in the ranges of the next one's
    blocks too."""
    monkeypatch.setattr(frontier, "NODE_BLOCK", 4)
    monkeypatch.setattr(frontier, "SUPER_BLOCKS", 2)
    monkeypatch.setattr(frontier, "CHUNK_ROWS", 64)
    assert frontier.range_blocks(4, 4) == 3
    bm, y = default_forest.bm, mixed_frame.col("y").to_numpy()
    for pallas in ("off", "interpret"):
        each, ref_each = _grow(bm, y, period=1, pallas=pallas, seed=1)
        some, ref_some = _grow(bm, y, period=4, pallas=pallas, seed=1)
        live = np.asarray(each.deep.path >= 0).sum(axis=1)
        assert live.max() > 6 * 8       # super-batches of 8 nodes
        assert _same(each.deep, some.deep)
        assert np.array_equal(ref_each, ref_some)
        assert some.scanned[0] > 1.1 * some.scanned[1]


def test_a_regression_tree_within_the_references_limits(default_forest,
                                                        mixed_frame):
    """Real statistics: three pieces a statistic, float32 sums that
    depend on the order of a block's rows — the same splits, values and
    weights within ``benchmark/references/drf.py``'s ``leaf_gap``."""
    bm = default_forest.bm
    z = mixed_frame.col("x2").to_numpy() + mixed_frame.col("y").to_numpy()
    each, ref_each = _grow(bm, z, period=1, whole=False)
    some, ref_some = _grow(bm, z, period=frontier.SORT_PERIOD, whole=False)
    for name in ("feat", "thresh", "na_left", "is_split", "cat_split",
                 "left_words", "child", "path"):
        assert np.array_equal(np.asarray(getattr(each.deep, name)),
                              np.asarray(getattr(some.deep, name))), name
    assert np.array_equal(ref_each, ref_some)
    for name in ("value", "weight"):
        a, b = (np.asarray(getattr(t.deep, name)) for t in (each, some))
        assert np.max(np.abs(a - b) / np.maximum(np.abs(a), 1.0)) <= 1e-5


def test_the_lowered_frontier_holds_one_sort_of_the_rows(default_forest,
                                                         mixed_frame):
    """Lowered, not compiled: the sort of the rows — seven operands at
    the airlines widths, minutes of compiling on the chip — is in the
    program once, whatever the period."""
    from h2o3_tpu.models.tree import scalars_of
    bm = default_forest.bm
    n, F = bm.bins.shape
    tp = tree_mod.TreeParams(
        max_depth=DEPTH, nbins_total=bm.nbins_total,
        cat_feats=tuple(bool(v) for v in bm.is_cat), frontier_from=FROM,
        frontier_sort_every=frontier.SORT_PERIOD, whole_stats=True)
    is_cat = jnp.asarray(np.asarray(tp.cat_feats))

    def frontier_levels(bins, nb, nid, w, wg, key):
        return frontier.grow_frontier(
            bins, nb, nid, (w, wg), jnp.ones((2 ** FROM,), bool), key,
            jnp.ones((F,), bool), params=tp, K=FROM, sc=scalars_of(tp),
            mtries=2, is_cat=is_cat)
    text = jax.jit(frontier_levels).lower(
        bm.bins, bm.nbins, jnp.zeros((n,), jnp.int32),
        jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        jax.random.PRNGKey(0)).as_text()
    sorts = [len(re.findall(r"%", m.group(1))) for m in re.finditer(
        r'"?stablehlo\.sort"?\(([^)]*)\)', text)]
    assert sorts, "no sort in the lowered text"
    # (key, row id, the packed bin words, two statistics)
    assert [k for k in sorts if k > 2] == [2 + -(-F // 4) + 2]
