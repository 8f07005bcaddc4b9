"""Row routing (`models/tree.py` `_level_goleft` / `_route`) against the
independent numpy walk of `genmodel/mojo.py`, bit for bit.

Routing is integer and boolean work: whatever way the device finds a
row's left-set word, the node ids have to equal the host walk's exactly
— at one bitset word and at many, at levels narrow enough for the
compiler's selects and wide enough for a real gather, with NA rows
going both ways and unsplit nodes routing left.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.genmodel.mojo import route_tree_nids
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.tree import (SELECT_NODES, Tree, _level_goleft, _route,
                                  select_levels, zero_catsplit)

F, ROWS = 7, 3001
CAT_FEATS = np.array([False, True, False, True, True, False, False])


def _random_tree(rng, D, B, cats):
    """A complete depth-D tree in the Tree layout: ~1 node in 8 unsplit,
    NA direction a coin, categorical features split on a random bit
    set (all words used), numeric ones on a random bin."""
    Lmax = 2 ** (D - 1)
    feat = rng.integers(0, F, (D, Lmax)).astype(np.int32)
    isp = rng.random((D, Lmax)) < 0.875
    isp[0, 0] = True                                   # the root splits
    feat = np.where(isp, feat, 0).astype(np.int32)
    thresh = np.where(isp, rng.integers(0, B - 1, (D, Lmax)), B).astype(
        np.int32)
    nal = (rng.random((D, Lmax)) < 0.5) & isp
    leaf = rng.standard_normal(2 ** D).astype(np.float32)
    if not cats:
        return Tree(*(jnp.asarray(a) for a in (feat, thresh, nal, isp, leaf,
                                               np.abs(leaf))),
                    *zero_catsplit(D, Lmax))
    W = (B - 1 + 31) // 32
    cs = CAT_FEATS[feat] & isp
    mask = rng.random((D, Lmax, B - 1)) < 0.5          # bin → goes left
    words = np.zeros((D, Lmax, W), np.uint32)
    for b in range(B - 1):
        words[..., b >> 5] |= mask[..., b].astype(np.uint32) << np.uint32(
            b & 31)
    words = np.where(cs[..., None], words, 0).astype(np.uint32)
    return Tree(*(jnp.asarray(a) for a in (feat, thresh, nal, isp, leaf,
                                           np.abs(leaf), cs, words)))


def _host(tree, bins, B, cats):
    t = jax.tree_util.tree_map(np.asarray, tree)
    return route_tree_nids(
        t.feat, t.thresh, t.na_left, t.is_split, bins.astype(np.int64), B,
        t.cat_split if cats else None, t.left_words if cats else None)


CASES = [(B, D, cats, dt)
         for B in (33, 126, 341)                 # W = 1, 4, 11
         for D in (1, 6, 12)
         for cats in (True, False)
         for dt in (("int8", "int32") if B <= 127 else ("int32",))]


@pytest.mark.parametrize("B,D,cats,dtype", CASES)
def test_routing_equals_the_host_walk(B, D, cats, dtype):
    rng = np.random.default_rng(1000 * B + 10 * D + cats)
    tree = _random_tree(rng, D, B, cats)
    bins = rng.integers(0, B - 1, (ROWS, F))
    bins[rng.random((ROWS, F)) < 0.15] = B - 1          # the NA bin
    bins[:, 1] = np.arange(ROWS) % B                    # every bin of a cat
    dev_bins = jnp.asarray(bins.astype(dtype))
    assert (np.asarray(tree.left_words).shape[-1]
            == ((B - 1 + 31) // 32 if cats else 1))

    got = np.asarray(jax.jit(_route, static_argnums=2)(tree, dev_bins, B))
    want = _host(tree, bins, B, cats)
    np.testing.assert_array_equal(got, want)
    assert got.max() < 2 ** D and len(np.unique(got)) > 1

    # the deepest level alone, rows spread over all of its nodes (the
    # walk above leaves most of a deep level's nodes empty)
    d = D - 1
    nid = rng.integers(0, 2 ** d, ROWS).astype(np.int32)
    one = jax.jit(_level_goleft, static_argnums=(8, 9))(
        tree.feat[d], tree.thresh[d], tree.na_left[d], tree.is_split[d],
        tree.cat_split[d], tree.left_words[d], jnp.asarray(nid), dev_bins, B,
        d)
    t = jax.tree_util.tree_map(np.asarray, tree)
    f_r, isp_r = t.feat[d][nid], t.is_split[d][nid]
    b_r = bins[np.arange(ROWS), f_r]
    go = b_r <= t.thresh[d][nid]
    if cats:
        word = t.left_words[d][nid, np.minimum(b_r >> 5,
                                               t.left_words.shape[-1] - 1)]
        go = np.where(t.cat_split[d][nid], (word >> (b_r & 31)) & 1 == 1, go)
    goleft = np.where(isp_r, np.where(b_r == B - 1, t.na_left[d][nid], go),
                      True)
    np.testing.assert_array_equal(np.asarray(one),
                                  2 * nid + np.where(goleft, 0, 1))
    # both directions were taken by NA rows and by unsplit nodes' rows
    na_rows = isp_r & (b_r == B - 1)
    if d:                                   # a root sends its NAs one way
        assert goleft[na_rows].any() and not goleft[na_rows].all()
    if not isp_r.all():
        assert goleft[~isp_r].all()


def test_rescore_span_counts_the_levels_of_each_kind():
    """``gbm.rescore`` says how many of a tree's levels were routed by
    selects alone and how many paid gathers — models/tree.select_levels,
    the rule ``_left_word`` follows — at the depth the forest compiled
    at (depth 8 compiles at the bucket 10: levels of 128, 256 and 512
    nodes are the three that gather)."""
    assert SELECT_NODES == 64
    assert select_levels(6) == (True,) * 6
    assert select_levels(10) == (True,) * 7 + (False,) * 3
    rng = np.random.default_rng(8)
    n = 600
    cols = {"x": rng.standard_normal(n), "z": rng.standard_normal(n),
            "c": rng.integers(0, 40, n).astype(np.float64)}
    cols["y"] = (cols["x"] + (cols["c"] % 3 == 0) > 0.5).astype(np.float64)
    frame = h2o3_tpu.Frame.from_numpy(
        cols, domains={"c": [f"l{i}" for i in range(40)], "y": ["n", "p"]})
    before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
    model = GBMEstimator(ntrees=2, max_depth=8, min_rows=1.0, seed=3).train(
        frame, y="y")
    metas = [s["meta"] for s in telemetry.spans_snapshot(1 << 20)
             if s["name"] == "gbm.rescore" and s["id"] not in before]
    assert model.forest.feat.shape[1:] == (10, 512)
    assert [(m["levels_select"], m["levels_gather"]) for m in metas] == [(7, 3)]
    h2o3_tpu.DKV.remove(model.key)
    h2o3_tpu.DKV.remove(frame.key)


def test_feature_path_counts_follow_the_walk():
    """feature_path_counts makes its own two lookups a level beside
    _level_goleft's: per row, how often each feature decided on the way
    down, over a forest of two depth-8 trees (levels of 128 nodes too)."""
    from h2o3_tpu.models.tree import feature_path_counts, stack_trees
    B, D = 126, 8
    rng = np.random.default_rng(88)
    trees = [_random_tree(rng, D, B, True) for _ in range(2)]
    bins = rng.integers(0, B, (ROWS, F))
    got = np.asarray(feature_path_counts(
        stack_trees(trees), jnp.asarray(bins.astype(np.int8)), B, F))
    want = np.zeros((ROWS, F), np.int64)
    rows = np.arange(ROWS)
    for tree in trees:
        t = jax.tree_util.tree_map(np.asarray, tree)
        for d in range(D):
            nid = (_host(jax.tree_util.tree_map(lambda a: a[:d], tree),
                         bins, B, True) if d else np.zeros(ROWS, np.int64))
            np.add.at(want, (rows, t.feat[d][nid]), t.is_split[d][nid])
    np.testing.assert_array_equal(got, want)
