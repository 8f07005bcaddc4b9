"""Fused Pallas tree kernels (ops/pallas/treekernel.py) — ISSUE 6.

Acceptance contract: in interpret mode on CPU the fused histogram +
split + partition level pass is BIT-EXACT against the XLA path on the
same mesh (f32 accumulation with the same row-block structure, shared
split-scan code, integer routing), across the binning edge-case sweep;
a seeded GBM forest trained with the kernels equals the XLA forest
tree-for-tree; the batched-grid compile discipline (one boost-program
compile per shape bucket) holds with the kernel layer active.
Satellites ride along: the H2O3TPU_PALLAS knob + import guard with a
single logged fallback, the pallas_* telemetry counters (and their
flight-recorder capture), the bin-major tile view, and the bin_frame
cache."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.frame.binning import bin_frame, rebin_for_scoring
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.tree import Tree, TreeScalars
from h2o3_tpu.ops import pallas as plx
from h2o3_tpu.ops.pallas import treekernel as tk
from h2o3_tpu.parallel.mesh import get_mesh, padded_rows, put_sharded, \
    row_sharding

OUT_NAMES = ("hist", "gain", "feat", "thresh", "na_left", "left_val",
             "right_val", "leftmask", "split", "new_nid")


def _scalars(min_rows=3.0, lam=1.0, msi=1e-5, depth_limit=30):
    return TreeScalars(jnp.float32(min_rows), jnp.float32(lam),
                       jnp.float32(msi), jnp.int32(depth_limit))


def _assert_level_parity(bins, w, g, h, cm, nb, is_cat, constraints,
                         lo, hi, sc, *, depth, L, B, mesh=None,
                         block_rows=256):
    """Run levels 0..depth via BOTH paths (each path routes with its own
    nids) and assert every output of every level is bit-identical. Each
    path's whole sweep is ONE jitted program — eager shard_map dispatch
    per level would dominate the suite's wall clock."""
    mesh = mesh or get_mesh()

    @jax.jit
    def sweep_xla(bins, w, g, h, cm, nb, lo, hi):
        outs, prev = [], None
        nid = jnp.zeros((bins.shape[0],), jnp.int32)
        for d in range(depth + 1):
            out = tk.xla_level(
                bins, nid, w, g, h, prev, cm, nb, is_cat, constraints,
                lo, hi, sc, d=d, n_nodes=2 ** d, n_bins=B,
                block_rows=block_rows, mesh=mesh)
            outs.append(out)
            prev, nid = out[0], out[-1]
        return outs

    @jax.jit
    def sweep_fused(bins, w, g, h, cm, nb, lo, hi):
        stats = jnp.stack([w, w * g, w * h]).astype(jnp.float32)
        outs, prev = [], None
        nid = jnp.zeros((bins.shape[0],), jnp.int32)
        for d in range(depth + 1):
            out = tk.fused_level(
                bins, nid, stats, prev, cm, nb, is_cat, constraints,
                lo, hi, sc, d=d, n_nodes=2 ** d, n_bins=B,
                block_rows=block_rows, mesh=mesh, interpret=True)
            outs.append(out)
            prev, nid = out[0], out[-1]
        return outs

    all_x = sweep_xla(bins, w, g, h, cm, nb, lo, hi)
    all_p = sweep_fused(bins, w, g, h, cm, nb, lo, hi)
    for d, (out_x, out_p) in enumerate(zip(all_x, all_p)):
        for name, a, b in zip(OUT_NAMES, out_x, out_p):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"level {d} output '{name}' diverged")


def _level_inputs(n=600, F=4, B=17, seed=0, na_frac=0.1):
    r = np.random.RandomState(seed)
    npad = padded_rows(n)
    bins_np = r.randint(0, B - 1, (npad, F))
    bins_np[r.rand(npad, F) < na_frac] = B - 1          # NA lane
    bins = put_sharded(jnp.asarray(bins_np.astype(np.int8)),
                       row_sharding())
    w = np.zeros(npad, np.float32)
    w[:n] = (r.rand(n) > 0.05).astype(np.float32)
    g = r.randn(npad).astype(np.float32)
    h = r.rand(npad).astype(np.float32) + 0.1
    nb = jnp.full((F,), B - 1, jnp.int32)
    return (bins, jnp.asarray(w), jnp.asarray(g), jnp.asarray(h), nb,
            r)


# ------------------------------------------------ kernel-level parity


def test_parity_numeric_multilevel():
    bins, w, g, h, nb, _ = _level_inputs()
    F = bins.shape[1]
    _assert_level_parity(
        bins, w, g, h, jnp.ones((F,), bool), nb, None, None,
        jnp.full((1,), -jnp.inf, jnp.float32),
        jnp.full((1,), jnp.inf, jnp.float32),
        _scalars(), depth=2, L=4, B=17)


def test_parity_categorical_subset_splits():
    bins, w, g, h, nb, r = _level_inputs(seed=3, B=9)
    F = bins.shape[1]
    is_cat = jnp.asarray(np.array([True, False, True, False]))
    _assert_level_parity(
        bins, w, g, h, jnp.ones((F,), bool), nb, is_cat, None,
        jnp.full((1,), -jnp.inf, jnp.float32),
        jnp.full((1,), jnp.inf, jnp.float32),
        _scalars(), depth=2, L=4, B=9)


def test_parity_constraints_and_depth_limit():
    bins, w, g, h, nb, _ = _level_inputs(seed=5)
    F = bins.shape[1]
    cons = jnp.asarray(np.array([1, -1, 0, 0], np.int8))
    # [1]-shaped bounds broadcast at every level (grow_tree only grows
    # them alongside its own constraint propagation)
    lo = jnp.full((1,), -0.5, jnp.float32)
    hi = jnp.full((1,), 0.5, jnp.float32)
    # depth_limit=2 masks the d=2 level's splits in BOTH paths
    _assert_level_parity(
        bins, w, g, h, jnp.ones((F,), bool), nb, None, cons, lo, hi,
        _scalars(depth_limit=2), depth=2, L=4, B=17)


def test_parity_per_node_col_mask():
    """DRF's [L, F] mtries mask flows through both split scans."""
    bins, w, g, h, nb, r = _level_inputs(seed=7)
    F = bins.shape[1]
    L = 4
    cm = jnp.asarray(r.rand(L, F) > 0.4) | (
        jnp.arange(F)[None, :] == 0)     # never fully featureless
    sc = _scalars()
    mesh = get_mesh()
    lo = jnp.full((1,), -jnp.inf, jnp.float32)
    hi = jnp.full((1,), jnp.inf, jnp.float32)
    cm1 = jnp.ones((F,), bool)

    @jax.jit
    def run(bins, w, g, h, cm):
        # two shared warmup levels, then a d=2 level through BOTH
        # paths with the per-node mask
        stats = jnp.stack([w, w * g, w * h]).astype(jnp.float32)
        nid = jnp.zeros((bins.shape[0],), jnp.int32)
        prev = None
        for d in range(2):
            out = tk.xla_level(bins, nid, w, g, h, prev, cm1, nb, None,
                               None, lo, hi, sc, d=d, n_nodes=2 ** d,
                               n_bins=17, block_rows=256, mesh=mesh)
            prev, nid = out[0], out[-1]
        kw = dict(d=2, n_nodes=L, n_bins=17, block_rows=256, mesh=mesh)
        out_x = tk.xla_level(bins, nid, w, g, h, prev, cm, nb, None,
                             None, lo, hi, sc, **kw)
        out_p = tk.fused_level(bins, nid, stats, prev, cm, nb, None,
                               None, lo, hi, sc, interpret=True, **kw)
        return out_x, out_p

    out_x, out_p = run(bins, w, g, h, cm)
    for name, a, b in zip(OUT_NAMES, out_x, out_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"output '{name}'")


def test_parity_single_device_fully_fused():
    """On a 1-shard mesh the whole level is ONE pallas_call (the
    tentpole kernel); same bitwise contract."""
    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                 ("data", "model"))
    r = np.random.RandomState(11)
    n, F, B = 512, 3, 9
    bins = jnp.asarray(r.randint(0, B, (n, F)).astype(np.int8))
    w = jnp.asarray((r.rand(n) > 0.1).astype(np.float32))
    g = jnp.asarray(r.randn(n).astype(np.float32))
    h = jnp.asarray((r.rand(n) + 0.1).astype(np.float32))
    nb = jnp.full((F,), B - 1, jnp.int32)
    _assert_level_parity(
        bins, w, g, h, jnp.ones((F,), bool), nb, None, None,
        jnp.full((1,), -jnp.inf, jnp.float32),
        jnp.full((1,), jnp.inf, jnp.float32),
        _scalars(), depth=2, L=4, B=B, mesh=mesh1, block_rows=128)


# ---------------------------------- a shorter statistics operand, same level


def _assert_plan_parity(bins, w, g, nb, is_cat, cms, *, n_stats, n_pieces,
                        B, block_rows=256):
    """Levels 0..len(cms)-1 through the kernels twice — with the 3 x 3
    operand of {w, w·g, w·1} and with the ``n_stats`` x ``n_pieces`` one
    of models/tree.stats_plan — each routing with its own node ids:
    every output of every level is bit-identical. ``cms[d]`` is level
    d's per-node column mask [2^d, F]."""
    mesh = get_mesh()
    lo = jnp.full((1,), -jnp.inf, jnp.float32)
    hi = jnp.full((1,), jnp.inf, jnp.float32)
    sc = _scalars(min_rows=1.0)

    def sweep(stats, pieces):
        outs, prev = [], None
        nid = jnp.zeros((bins.shape[0],), jnp.int32)
        for d, cm in enumerate(cms):
            out = tk.fused_level(
                bins, nid, stats, prev, cm, nb, is_cat, None, lo, hi, sc,
                d=d, n_nodes=2 ** d, n_bins=B, block_rows=block_rows,
                mesh=mesh, interpret=True, n_pieces=pieces)
            outs.append(out)
            prev, nid = out[0], out[-1]
        return outs

    @jax.jit
    def both(w, g):
        stats = jnp.stack([w, w * g, w]).astype(jnp.float32)
        return sweep(stats, 3), sweep(stats[:n_stats], n_pieces)

    nine, short = both(w, g)
    for d, (out_9, out_s) in enumerate(zip(nine, short)):
        for name, a, b in zip(OUT_NAMES, out_9, out_s):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"level {d} output '{name}' diverged")
    # the levels split: the parity is not one of empty trees
    assert np.asarray(nine[-1][8]).any()


@pytest.mark.parametrize("n_stats,n_pieces", [(2, 1), (2, 3)])
def test_a_shorter_operand_grows_the_same_levels(n_stats, n_pieces):
    """A forest's operand: two statistics under a hessian of 1, in one
    bfloat16 piece where they are 0 or ±1 (a class indicator under
    whole weights: 2 rows a node) and in three where w·g is any float
    (a regression forest: 6) — against the 9-row operand, over four
    levels with categorical features and a per-node column mask."""
    bins, w, g, h, nb, r = _level_inputs(n=900, seed=13, B=9)
    F = bins.shape[1]
    if n_pieces == 1:
        g = jnp.asarray(r.randint(-1, 2, g.shape[0]).astype(np.float32))
    is_cat = jnp.asarray(np.array([True, False, True, False]))
    cms = [jnp.asarray(r.rand(2 ** d, F) > 0.3)
           | (jnp.arange(F)[None, :] == d % F) for d in range(4)]
    _assert_plan_parity(bins, w, g, nb, is_cat, cms, n_stats=n_stats,
                        n_pieces=n_pieces, B=9)


AIR_F, AIR_B = 10, 126                # the airlines frame, binned


def test_the_nine_row_operand_keeps_its_tiles():
    """The GBM cells' levels (3 statistics x 3 pieces) get the tiles
    they had before the operand's height became an argument."""
    today = [2048] * 6 + [1024, 1024, 256, 0]
    assert [plx.tile_rows(AIR_F, AIR_B, 2 ** d) for d in range(10)] == today
    assert [plx.tile_rows(AIR_F, AIR_B, 2 ** d, 9)
            for d in range(10)] == today


@pytest.mark.parametrize("node_rows", [2, 6])
def test_a_shorter_operand_gets_a_tile_as_large(node_rows):
    for d in range(9):
        nine = plx.tile_rows(AIR_F, AIR_B, 2 ** d)
        assert plx.tile_rows(AIR_F, AIR_B, 2 ** d, node_rows) >= nine > 0
    # the forest's level 8: 256 operand rows in place of 1,152
    assert plx.tile_rows(AIR_F, AIR_B, 256, 2) == 1024


# --------------------------------------- binning edge-case sweep parity


def _edge_case_bm(case):
    if case == "nbins1":
        fr = h2o3_tpu.Frame.from_numpy(
            {"a": np.random.RandomState(0).randn(64),
             "b": np.arange(64, dtype=float)})
        return bin_frame(fr, ["a", "b"], nbins=1)
    if case == "single_row":
        fr = h2o3_tpu.Frame.from_numpy({"a": np.array([1.5]),
                                        "b": np.array([-2.0])})
        return bin_frame(fr, ["a", "b"], nbins=8)
    if case == "all_na":
        fr = h2o3_tpu.Frame.from_numpy(
            {"a": np.full(50, np.nan),
             "b": np.random.RandomState(1).randn(50)})
        return bin_frame(fr, ["a", "b"], nbins=8)
    if case == "constant":
        fr = h2o3_tpu.Frame.from_numpy(
            {"a": np.full(50, 3.25),
             "b": np.random.RandomState(2).randn(50)})
        return bin_frame(fr, ["a", "b"], nbins=8)
    if case == "unseen_levels":
        tr = h2o3_tpu.Frame.from_numpy(
            {"c": np.random.RandomState(3).choice(["a", "b"], 60),
             "x": np.random.RandomState(4).randn(60)},
            categorical=["c"])
        bm = bin_frame(tr, ["c", "x"], nbins=8)
        sc_fr = h2o3_tpu.Frame.from_numpy(
            {"c": np.random.RandomState(5).choice(["a", "b", "c", "d"],
                                                  40),
             "x": np.random.RandomState(6).randn(40)},
            categorical=["c"])
        return rebin_for_scoring(bm, sc_fr)    # unseen levels → NA bin
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["nbins1", "single_row", "all_na",
                                  "constant", "unseen_levels"])
def test_binning_edge_case_parity(case):
    bm = _edge_case_bm(case)
    r = np.random.RandomState(42)
    npad = bm.bins.shape[0]
    w = np.zeros(npad, np.float32)
    w[: bm.nrows] = 1.0
    g = jnp.asarray(r.randn(npad).astype(np.float32))
    h = jnp.asarray(np.ones(npad, np.float32))
    is_cat = (jnp.asarray(np.asarray(bm.is_cat, bool))
              if bm.is_cat.any() else None)
    F = bm.nfeatures
    _assert_level_parity(
        bm.bins, jnp.asarray(w), g, h, jnp.ones((F,), bool), bm.nbins,
        is_cat, None, jnp.full((1,), -jnp.inf, jnp.float32),
        jnp.full((1,), jnp.inf, jnp.float32),
        _scalars(min_rows=1.0), depth=1, L=2, B=bm.nbins_total)


# ------------------------------------------------- seeded forest parity


def _mixed_frame(n=700, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, 4)
    X[r.rand(n) < 0.05, 0] = np.nan
    cat = r.choice(["a", "b", "c", "d"], n)
    y = (X[:, 1] + (cat == "a") * 1.5 + 0.3 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(4)}
    cols["c"] = cat
    cols["y"] = np.array(["N", "Y"], object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["c", "y"])


def _forests_equal(m1, m2):
    for f in Tree._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(m1.forest, f)),
            np.asarray(getattr(m2.forest, f)),
            err_msg=f"forest field '{f}' diverged")


def test_seeded_gbm_forest_parity_interpret(monkeypatch):
    """Acceptance: the fused-kernel GBM forest equals the XLA forest
    tree-for-tree (NAs + categorical subset splits included)."""
    fr = _mixed_frame()
    params = dict(ntrees=4, max_depth=4, seed=11)
    monkeypatch.setenv("H2O3TPU_PALLAS", "off")
    m_x = GBMEstimator(**params).train(fr, y="y")
    launches0 = telemetry.REGISTRY.total("pallas_kernel_launches_total")
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    m_p = GBMEstimator(**params).train(fr, y="y")
    _forests_equal(m_x, m_p)
    assert m_x.training_metrics["AUC"] == m_p.training_metrics["AUC"]
    # satellite: launch counter moved while the kernels were active
    assert telemetry.REGISTRY.total(
        "pallas_kernel_launches_total") > launches0


def test_seeded_drf_forest_parity_interpret(monkeypatch):
    """The mtries (per-node column mask) path through the shared
    grow_tree, kernels vs XLA."""
    from h2o3_tpu.models.drf import DRFEstimator
    fr = _mixed_frame(n=400, seed=2)
    params = dict(ntrees=3, max_depth=4, seed=5)
    monkeypatch.setenv("H2O3TPU_PALLAS", "off")
    m_x = DRFEstimator(**params).train(fr, y="y")
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    m_p = DRFEstimator(**params).train(fr, y="y")
    _forests_equal(m_x, m_p)
    # the kernels took every level (of the compiled depth bucket) on the
    # 2-row operand: two whole statistics, one bfloat16 piece each
    chunk = [s for s in telemetry.spans_snapshot(last=1 << 16)
             if s["name"] == "drf.chunk"][-1]["meta"]
    assert chunk["levels_kernel"] >= 4 and chunk["levels_xla"] == 0
    assert chunk["hist_operand_rows"] == 2


# --------------------------------------------- knob + import guard


def test_decide_table():
    assert plx.decide("auto", "tpu", 1, True) == ("native", None)
    assert plx.decide("auto", "cpu", 8, True) == ("off",
                                                  "non_tpu_backend")
    assert plx.decide("off", "tpu", 1, True) == ("off", "knob_off")
    assert plx.decide("interpret", "cpu", 8, True) == ("interpret", None)
    assert plx.decide("on", "cpu", 1, True) == ("native", None)
    # unavailable pallas wins over every knob except explicit off
    assert plx.decide("auto", "tpu", 1, False) == \
        ("off", "pallas_unavailable")
    assert plx.decide("interpret", "cpu", 1, False) == \
        ("off", "pallas_unavailable")
    assert plx.decide("bogus", "tpu", 1, True) == ("off", "unknown_knob")


def test_knob_off_single_logged_fallback(monkeypatch):
    """off → XLA with ONE logged fallback (no per-tree/per-fit spam);
    every decision still counts in pallas_fallbacks_total{reason}."""
    from h2o3_tpu.utils.log import log_buffer
    monkeypatch.setenv("H2O3TPU_PALLAS", "off")
    plx._LOGGED_REASONS.clear()
    c0 = telemetry.REGISTRY.value("pallas_fallbacks_total",
                                  reason="knob_off")
    n_logged0 = sum("falling back to XLA" in ln for ln in log_buffer())
    assert plx.resolve_tree_mode() == "off"
    assert plx.resolve_tree_mode() == "off"
    assert telemetry.REGISTRY.value("pallas_fallbacks_total",
                                    reason="knob_off") == c0 + 2
    n_logged = sum("falling back to XLA" in ln for ln in log_buffer())
    assert n_logged - n_logged0 == 1, "fallback must log exactly once"


def test_knob_off_zero_behavior_change(monkeypatch):
    """off and auto (non-TPU backend) are the SAME XLA program — forests
    bit-identical."""
    fr = _mixed_frame(n=300, seed=9)
    params = dict(ntrees=3, max_depth=3, seed=3)
    monkeypatch.setenv("H2O3TPU_PALLAS", "off")
    m_off = GBMEstimator(**params).train(fr, y="y")
    monkeypatch.setenv("H2O3TPU_PALLAS", "auto")
    m_auto = GBMEstimator(**params).train(fr, y="y")
    _forests_equal(m_off, m_auto)


def test_import_guard_unavailable(monkeypatch):
    """A missing jax.experimental.pallas resolves to the XLA path with a
    counted fallback — never an ImportError."""
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    monkeypatch.setattr(plx, "available", lambda: False)
    c0 = telemetry.REGISTRY.value("pallas_fallbacks_total",
                                  reason="pallas_unavailable")
    assert plx.resolve_tree_mode() == "off"
    assert telemetry.REGISTRY.value(
        "pallas_fallbacks_total",
        reason="pallas_unavailable") == c0 + 1


def test_flight_recorder_captures_pallas_counters(monkeypatch):
    """Satellite: the pallas_* counters flow into the job capsule's
    start→end metric deltas like every other counter."""
    from h2o3_tpu.core.job import Job
    from h2o3_tpu.telemetry import flight_recorder
    fr = _mixed_frame(n=200, seed=13)
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")

    def work(job):
        GBMEstimator(ntrees=2, max_depth=3, seed=1).train(fr, y="y")
        return "ok"

    j = Job("pallas capsule probe").start(work)
    cap = flight_recorder.get_capsule(j.key).to_dict()
    assert any("pallas_kernel_launches_total" in k
               for k in cap["metric_deltas"]), cap["metric_deltas"]


# ------------------------------------- batched-grid compile discipline


def test_batched_grid_one_compile_with_kernels_active(monkeypatch):
    """ISSUE 6 acceptance: the vmapped shape-bucket trainer composes
    with the kernel layer — one boost-program compile for the bucket,
    results matching the sequential walk (both interpret)."""
    from h2o3_tpu.ml.grid import GridSearch
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    r = np.random.RandomState(1)
    n = 300
    a, b = r.randn(n), r.randn(n)
    yv = (a + 0.5 * b + 0.3 * r.randn(n) > 0).astype(int)
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": a, "b": b, "y": np.array(["N", "Y"], object)[yv]},
        categorical=["y"])
    hyper = {"learn_rate": [0.05, 0.1], "min_rows": [1.0, 10.0]}
    fixed = dict(ntrees=4, max_depth=3, seed=7)

    def _misses():
        tot = 0.0
        for (nm, lbl), m in list(telemetry.REGISTRY._metrics.items()):
            if nm.endswith("jit_cache_miss_total") and \
                    dict(lbl).get("fn") == "gbm.boost_scan_batched":
                tot += m.value
        return tot

    m0 = _misses()
    g_bat = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
    assert len(g_bat.models) == 4
    assert _misses() - m0 == 1, \
        "one compile per shape bucket, kernels active"
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    g_seq = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
    by = {tuple(sorted(m.output["grid_params"].items())): m
          for m in g_seq.models}
    for m in g_bat.models:
        m2 = by[tuple(sorted(m.output["grid_params"].items()))]
        d1 = m.training_metrics.to_dict()
        d2 = m2.training_metrics.to_dict()
        for k in ("AUC", "logloss"):
            assert abs(d1[k] - d2[k]) < 1e-5


# --------------------------------------------------- layout + caches


def test_tile_view_geometry_and_cache():
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": np.random.RandomState(0).randn(100),
         "b": np.random.RandomState(1).randn(100)})
    bm = bin_frame(fr, ["a", "b"], nbins=8)
    tv = bm.tile_view(64)
    assert tv.rows == 64
    assert tv.bins.shape[0] == tv.ntiles * 64
    assert tv.bins.shape[0] >= bm.bins.shape[0]
    assert tv.tile_shape == (64, 2)
    assert tv.nbins_total == bm.nbins_total     # NA lane folded in
    assert bm.tile_view(64) is tv               # cached per rows
    auto = bm.tile_view()                       # VMEM-sized default
    assert auto.rows % 8 == 0 or auto.rows == bm.bins.shape[0]
    # pickling drops the cache, not the matrix
    import pickle
    bm2 = pickle.loads(pickle.dumps(bm))
    assert bm2._tile_cache == {}


def test_bin_frame_cached_per_config_and_invalidated():
    r = np.random.RandomState(3)
    fr = h2o3_tpu.Frame.from_numpy({"a": r.randn(120), "b": r.randn(120)})
    bm1 = bin_frame(fr, ["a", "b"], nbins=8)
    assert bin_frame(fr, ["a", "b"], nbins=8) is bm1       # cache hit
    assert bin_frame(fr, ["a", "b"], nbins=16) is not bm1  # config keyed
    assert bin_frame(fr, ["a"], nbins=8) is not bm1
    # weights key by CONTENT (each fit rebuilds the host mirror array)
    wts = np.ones(120)
    bmw = bin_frame(fr, ["a", "b"], nbins=8, weights=wts)
    assert bmw is not bm1
    assert bin_frame(fr, ["a", "b"], nbins=8,
                     weights=np.ones(120)) is bmw
    assert bin_frame(fr, ["a", "b"], nbins=8,
                     weights=np.full(120, 2.0)) is not bmw
    # column mutation invalidates, like the device_matrix cache
    from h2o3_tpu.frame.column import column_from_numpy
    from h2o3_tpu.parallel import mesh as mesh_mod
    fr.add_column(column_from_numpy("z", np.zeros(120), fr.nrows_padded,
                                    mesh_mod.row_sharding()))
    assert bin_frame(fr, ["a", "b"], nbins=8) is not bm1
    # scoring rebins bypass the cache (train-matrix keyed, not frame)
    fr2 = h2o3_tpu.Frame.from_numpy({"a": r.randn(50), "b": r.randn(50)})
    bm_s1 = rebin_for_scoring(bm1, fr2)
    bm_s2 = rebin_for_scoring(bm1, fr2)
    assert bm_s1 is not bm_s2
