"""Shared tree machinery — level-wise histogram tree growing on TPU.

Reference: the SharedTree skeleton (hex/tree/SharedTree.java:29,481):
per level, ScoreBuildHistogram2 routes rows to leaves and fills
DHistograms, then DTree.findBestSplitPoint scans bins for best gain
(hex/tree/DTree.java:619-697), leaves get Newton values (GammaPass).

TPU-first redesign (SURVEY §7 hard part #1/#2):
- trees are COMPLETE binary trees of static depth D: level d has 2^d
  node slots (padded; empty nodes have zero histograms and never split).
  Static shapes ⇒ one compiled program for the whole tree.
- per level: matmul histogram (ops/histogram.py) → vectorized gain scan
  over (feature, threshold, NA-direction) → argmax → elementwise
  row-routing update of the node-id vector. No host roundtrips.
- split criterion is the Newton gain on (g, h) — the XGBoost-style
  generalization of the reference's {w,wY,wYY} SSE gain; with
  g = residual, h = 1 it reduces exactly to the reference's variance
  reduction.
- NA handling: NAs live in the last bin; both NA-left and NA-right are
  scored, best kept — mirroring DHistogram's NA bucket semantics.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.ops.histogram import histogram
from h2o3_tpu.ops.segments import segment_sum
from h2o3_tpu.ops.split_scan import best_splits


class TreeScalars(NamedTuple):
    """Traced per-call training knobs. These previously rode inside the
    static TreeParams, so every distinct (min_rows, reg_lambda, msi)
    combination — e.g. every AutoML/grid candidate — forced a fresh XLA
    compilation; as traced scalars one compiled program serves them all
    (structure-affecting fields stay static in TreeParams).

    ``depth_limit`` extends the trick to max_depth: programs compile at
    a BUCKETED static depth (DEPTH_BUCKETS) and mask splits past the
    traced actual depth, so AutoML/grid candidates of depths 3..6 (or
    7..10, 11..14) all share one compiled boosting program instead of
    paying a fresh 20-40s XLA compile each."""
    min_rows: jax.Array
    reg_lambda: jax.Array
    msi: jax.Array
    depth_limit: jax.Array = None


def scalars_of(params: "TreeParams") -> "TreeScalars":
    return TreeScalars(jnp.float32(params.min_rows),
                       jnp.float32(params.reg_lambda),
                       jnp.float32(params.min_split_improvement),
                       jnp.int32(params.max_depth))


# static compile-depth buckets: levels past the actual depth cost one
# masked row-pass each, so the padding overhead is bounded by
# bucket/actual while compile count drops from one-per-depth to
# one-per-bucket (AutoML trains depths {3..15} in one session)
DEPTH_BUCKETS = (6, 10, 14)


def bucket_depth(d: int) -> int:
    for b in DEPTH_BUCKETS:
        if d <= b:
            return b
    return d


def trees_per_chunk(tp: "TreeParams", n_rows: int, capped: bool) -> int:
    """Trees a compiled chunk of a boosting or bagging loop — the ONE
    rule for every loop (models/gbm.py, models/drf.py), so that the
    global-tree-index PRNG keys and the stop points of a batched and a
    sequential GBM fit line up exactly.

    Row scale bounds single-program runtime: a 25-tree fused scan at
    50M rows runs minutes inside ONE XLA program, between which no
    cancel point, checkpoint or progress update can fire — chunks
    shrink past ~5M padded rows so each program stays ~tens of seconds;
    <=5M rows keep 25 (the pyunit shapes are untouched).
    A fit under max_runtime_secs (``capped``) can only stop at a chunk
    boundary, so its chunk also shrinks as per-tree cost grows
    (complete-tree layout: ~2^depth * nbins per tree) — a 25-deep-tree
    chunk at depth bucket 10 runs ~20-80s, far past a ~30s AutoML
    slice. Uncapped fits keep 25 (no extra program shapes)."""
    cost = max(1.0, n_rows / 5_242_880.0)
    if capped:
        cost *= (2.0 ** tp.max_depth / 64.0) * (tp.nbins_total / 65.0)
    return max(1, min(25, int(round(25.0 / max(cost, 1.0)))))


class Tree(NamedTuple):
    """One complete tree; arrays padded to Lmax = 2^(D-1) internal slots."""
    feat: jax.Array       # [D, Lmax] int32 split feature
    thresh: jax.Array     # [D, Lmax] int32 split bin (go left if bin <= t)
    na_left: jax.Array    # [D, Lmax] bool
    is_split: jax.Array   # [D, Lmax] bool
    leaf: jax.Array       # [2^D] float32 leaf values
    leaf_w: jax.Array     # [2^D] float32 training row weight per leaf
                          # (node covers for TreeSHAP pool up from these;
                          # the reference stores them as node weights in
                          # hex/tree/CompressedTree for contributions)
    cat_split: jax.Array  # [D, Lmax] bool — split is a category SUBSET
                          # (bitset) split, not a bin-range split
    left_words: jax.Array  # [D, Lmax, W] uint32 — bit b of word k set ⇔
                          # bin 32k+b goes LEFT (DTree.java:619-697
                          # bitset splits, static-shape bit-packed)


def zero_catsplit(D: int, Lmax: int):
    """(cat_split, left_words) placeholders for builders that never make
    categorical subset splits (isolation forests, uplift)."""
    return (jnp.zeros((D, Lmax), bool),
            jnp.zeros((D, Lmax, 1), jnp.uint32))


@dataclasses.dataclass(frozen=True)
class TreeParams:
    max_depth: int = 5
    min_rows: float = 10.0
    learn_rate: float = 0.1
    reg_lambda: float = 1.0          # hessian regularization (reference min_rows+pred smoothing)
    min_split_improvement: float = 1e-5
    col_sample_rate: float = 1.0     # per-split column sampling is per-tree here
    nbins_total: int = 65            # B incl. NA bin
    block_rows: int = 4096
    cat_feats: tuple = ()            # per-feature is-categorical flags —
                                     # schema-static, activates the
                                     # sorted-prefix subset-split path
    pallas: str = "off"              # level-pass backend:
                                     # "off" = XLA, "native"/"interpret"
                                     # = ops/pallas/treekernel. STATIC
                                     # on purpose: the knob decision
                                     # must be part of the jit key so a
                                     # mid-process flip recompiles
                                     # instead of reusing a stale
                                     # program (ops/pallas.resolve_tree_mode)
    frontier_from: int = 0           # first level grown in the frontier
                                     # regime (models/frontier.py) where
                                     # the depth passes it; 0 = complete
                                     # layout all the way (GBM: its depths
                                     # fit the layout, ROADMAP R4)
    frontier_sort_every: int = 1     # the frontier levels between two
                                     # sorts of the rows by node, the
                                     # sorting one counted: the forest
                                     # fits hand on frontier.SORT_PERIOD
    whole_stats: bool = False        # the fit KNOWS every statistic of a
                                     # row is 0 or ±1 (whole weights on a
                                     # class indicator): the frontier's
                                     # histogram operand then carries one
                                     # bfloat16 piece a statistic

    @property
    def has_cats(self) -> bool:
        return any(self.cat_feats)


def kernel_levels(params: TreeParams, n_features: int) -> tuple:
    """Per level of ``params.max_depth``, whether grow_tree runs it
    through the Pallas level kernels (ops/pallas/treekernel.py) — the
    fit's STATIC ``params.pallas`` knob, and per LEVEL whether the
    level's shapes fit a VMEM tile (ops/pallas.tile_rows — deep levels'
    accumulators do not; they take the XLA sequence). The one place
    that decides it: grow_tree follows it and the fits report it on
    their ``*.chunk`` spans (``levels_kernel`` / ``levels_xla``)."""
    if params.pallas not in ("native", "interpret"):
        return (False,) * params.max_depth
    from h2o3_tpu.ops.pallas import tile_rows
    return tuple(tile_rows(n_features, params.nbins_total, 2 ** d) > 0
                 for d in range(params.max_depth))


# Where no kernel says otherwise, the first level grown in the frontier
# regime by the fits that ask for it (DRF): below it a complete level's
# XLA histogram is [2^d, F·B, 3] one-hot blocks that stop loading at
# scale (PERF.md §4), and from level 9 on the airlines widths fit no
# kernel tile either.
FRONTIER_FROM = 9


def frontier_start(params: TreeParams, n_features: int) -> int:
    """The level from which a fit that grows past the complete layout
    leaves it: the first level the kernels do not take, FRONTIER_FROM at
    most (and where no kernel runs)."""
    fits = kernel_levels(dataclasses.replace(params,
                                             max_depth=FRONTIER_FROM),
                         n_features)
    if not any(fits):
        return FRONTIER_FROM
    return next((d for d, ok in enumerate(fits) if not ok), FRONTIER_FROM)


def row_feature_values(bins, f_r):
    """``bins[i, f_r[i]]`` without a gather.

    On TPU ``take_along_axis`` lowers to a gather (~11 ms per call on 1M
    rows, v5e); the masked feature-sum is pure VPU broadcast work (<1 ms)
    — this select runs once per tree level, so it dominates routing cost.
    """
    iota = jnp.arange(bins.shape[1], dtype=jnp.int32)
    return jnp.sum(jnp.where(f_r[:, None] == iota[None, :], bins, 0), axis=1)


def _best_splits(hist, nb, col_mask, params: TreeParams,
                 constraints=None, lo=None, hi=None, scalars=None,
                 is_cat=None):
    """Vectorized DTree.findBestSplitPoint over all nodes of a level —
    thin adapter over the shared implementation (ops/split_scan.py),
    which the fused Pallas kernels evaluate too so both tree backends
    stay bit-exact by construction. See ops.split_scan.best_splits for
    the full contract."""
    sc = scalars if scalars is not None else scalars_of(params)
    cats = params.has_cats and is_cat is not None
    return best_splits(
        hist, nb, col_mask, min_rows=sc.min_rows,
        reg_lambda=sc.reg_lambda, is_cat=is_cat if cats else None,
        constraints=constraints, lo=lo, hi=hi,
        cat_idx=tuple(i for i, c in enumerate(params.cat_feats) if c)
        if cats else None)


def _pack_leftmask(leftmask, W: int):
    """[L, B-1] bool → [L, W] uint32 bitset words (bit b of word k ⇔
    bin 32k+b). One-hot matmul keeps it gather-free."""
    Bm1 = leftmask.shape[1]
    bpos = jnp.arange(Bm1, dtype=jnp.uint32)
    contrib = leftmask.astype(jnp.uint32) << (bpos % 32)[None, :]
    seg = (bpos // 32)[:, None] == jnp.arange(W, dtype=jnp.uint32)[None, :]
    return jnp.sum(contrib[:, :, None] * seg[None].astype(jnp.uint32),
                   axis=1)


# The widest table a per-row lookup ``table[nid]`` may read and still be
# a chain of selects: the chip's compiler takes a 1-D lookup from up to
# 64 entries that way (1.3-1.8 ms over 48M rows, v5e) and makes a real
# gather of it from 128 on (454 ms; 599 ms from a 2-D table — PERF.md
# §6, PR 30). tests/test_chip_compile.py holds the compiler to it.
SELECT_NODES = 64


def select_levels(depth: int) -> tuple:
    """Per level of a depth-``depth`` tree, whether routing rows through
    it costs selects alone (level d has 2^d live nodes; a level wider
    than SELECT_NODES pays real gathers, _left_word one of them). What
    the fits report on their ``*.rescore`` spans (``levels_select`` /
    ``levels_gather``)."""
    return tuple(2 ** d <= SELECT_NODES for d in range(depth))


def _left_word(lw_d, nid, widx):
    """``lw_d[nid, widx]``, the row's word of its node's packed left-set,
    without a per-row gather from the 2-D table (12.4 ns a row on a v5e,
    once per word: 27 s of a 35 s fit at 48M rows) and without an
    ``[N, W]`` intermediate (its minor dim is padded to 128 lanes:
    25.7 GB at 50M rows). A level of selects looks each of the W columns
    up as the level's other tables are and keeps the row's own; a wide
    level makes ONE gather from the flat table, where it made W."""
    L, W = lw_d.shape
    if L > SELECT_NODES:
        # the NA bin's word index is W when B-1 is a multiple of 32;
        # the isna branch decides those rows, any word will do
        return lw_d.reshape(-1)[nid * W + jnp.minimum(widx, W - 1)]
    word = lw_d[:, 0][nid]
    for k in range(1, W):
        word = jnp.where(widx == k, lw_d[:, k][nid], word)
    return word


def _level_goleft(feat_d, thresh_d, nal_d, isp_d, cat_d, lw_d, nid, bins,
                  B: int, d: int):
    """Row routing for level ``d`` of a tree — shared by training,
    scoring, leaf assignment and path counting (the DecidedNode
    assignment pass). Numeric splits compare bin <= t; categorical
    subset splits test the row's bin bit in the node's packed left-set.
    The level's tables are cut to its 2^d live nodes (``nid`` < 2^d),
    so that the narrow levels of a deep tree are narrow tables too."""
    feat_d, thresh_d, nal_d, isp_d, cat_d, lw_d = (
        t[:2 ** d] for t in (feat_d, thresh_d, nal_d, isp_d, cat_d, lw_d))
    f_r = feat_d[nid]
    t_r = thresh_d[nid]
    nal_r = nal_d[nid]
    isp_r = isp_d[nid]
    cs_r = cat_d[nid]
    b_r = row_feature_values(bins, f_r).astype(jnp.int32)
    isna = b_r == (B - 1)
    go_num = b_r <= t_r
    word = _left_word(lw_d, nid, b_r >> 5)
    inset = ((word >> (b_r & 31).astype(jnp.uint32)) & 1) == 1
    go_split = jnp.where(cs_r, inset, go_num)
    goleft = jnp.where(isp_r, jnp.where(isna, nal_r, go_split), True)
    return 2 * nid + jnp.where(goleft, 0, 1)


def _level_mtries_mask(key, L: int, F: int, mtries: int):
    """Exactly-mtries-per-node column mask [L, F] from ONE draw a level
    of L nodes — the uplift forest's and the extended isolation forest's
    (models/uplift.py, models/extisofor.py): their trees keep the
    complete layout and nothing replays them node by node."""
    u = jax.random.uniform(key, (L, F))
    rank = jnp.argsort(jnp.argsort(u, axis=1), axis=1)
    return rank < mtries


def _mtries_mask(key, heap_ids, F: int, mtries: int):
    """Exactly-mtries-per-node column mask [L, F] — the reference DRF
    per-split column subsample (hex/tree/DTree.java UndecidedNode scoreCols,
    mtries semantics of hex/tree/drf/DRF.java:30). A node's draw is a
    function of (tree key, its heap id 2^level + path) alone: the mtries
    columns that F uniforms from ``fold_in(key, heap id)`` rank lowest —
    whatever layout holds the node, and replayable node by node
    (benchmark/references/drf.py)."""
    u = jax.vmap(lambda h: jax.random.uniform(
        jax.random.fold_in(key, h), (F,)))(heap_ids)
    rank = jnp.argsort(jnp.argsort(u, axis=1), axis=1)
    return rank < mtries


def grow_tree(bins, nb, w, g, h, col_mask, *, params: TreeParams, mesh,
              mtries: int = 0, key=None, constraints=None,
              interaction_sets=None, scalars=None):
    """Grow one tree; returns (Tree, final_leaf_id_per_row).

    bins [Npad, F] int32 row-sharded; w zero on padding rows (``h`` None
    is a hessian of 1, as a forest of mean-valued leaves has it); col_mask [F]
    bool (per-tree column sampling, reference col_sample_rate_per_tree).
    mtries > 0 additionally samples exactly-mtries columns per NODE per
    level (DRF semantics) using `key`. ``constraints`` [F] in {-1,0,+1}
    activates monotone constraints: per-node value bounds propagate to
    children through the split midpoint and leaves are clipped into
    them (the reference's hex/tree/Constraints machinery).
    ``interaction_sets`` [S, F] bool activates interaction constraints
    (GBM interaction_constraints / hex/tree/GlobalInteractionConstraints):
    once a node splits on feature f, its subtree may only use features
    sharing an interaction set with every feature on the path — tracked
    as a per-node allowed mask.

    Where ``params.frontier_from`` is set and the depth passes it, the
    levels from there on are grown in the frontier regime
    (models/frontier.py) and the result is ``(DeepTree, ref, gains)``:
    ``ref`` indexes the node tables' flat values as ``nid`` indexes
    ``Tree.leaf`` (``leaf_values``).
    """
    from h2o3_tpu.models import frontier
    unit_h = h is None
    if unit_h:
        h = jnp.ones_like(g)
    sc = scalars if scalars is not None else scalars_of(params)
    B = params.nbins_total
    F = bins.shape[1]
    N = bins.shape[0]
    # the complete layout holds levels 0..D-1 of the whole tree, or the
    # levels above the frontier
    D = frontier.complete_levels(N, params.max_depth, params.frontier_from)
    Lmax = 2 ** (D - 1) if D > 0 else 1
    nid = jnp.zeros((N,), jnp.int32)

    feats = jnp.zeros((D, Lmax), jnp.int32)
    threshs = jnp.full((D, Lmax), B, jnp.int32)
    na_lefts = jnp.zeros((D, Lmax), bool)
    is_splits = jnp.zeros((D, Lmax), bool)
    is_cat = (jnp.asarray(np.asarray(params.cat_feats, dtype=bool))
              if params.has_cats else None)
    W = max(1, (B - 1 + 31) // 32) if params.has_cats else 1
    cat_splits = jnp.zeros((D, Lmax), bool)
    left_words = jnp.zeros((D, Lmax, W), jnp.uint32)
    gain_by_feat = jnp.zeros((F,), jnp.float32)  # relative varimp (hex/VarImp)
    lo = jnp.full((1,), -jnp.inf, jnp.float32)
    hi = jnp.full((1,), jnp.inf, jnp.float32)
    allowed = jnp.ones((1, F), bool)   # per-node feature set (interactions)
    pair_allow = None                  # lazy [F, F] compatibility matrix

    # Pallas level kernels (ops/pallas/treekernel.py): the histogram
    # and row-partition passes over the bin-major tiles, where
    # kernel_levels says so; the XLA sequence below elsewhere. Either
    # way every sum of {w, w·g, w·h} is a float32 sum (ops/histogram.py
    # split3). The stats block is level-invariant, so it is built once
    # here (the XLA path rebuilds the same values inside
    # ops/histogram.py).
    fused = kernel_levels(params, F)
    # asked for, which is more than any(fused): a level that then fits
    # no tile is a counted fallback, most of all when none fits
    use_kernels = params.pallas in ("native", "interpret")
    if use_kernels:
        from h2o3_tpu.ops import pallas as pallas_policy
        from h2o3_tpu.ops.pallas.treekernel import fused_level
        stats3 = jnp.stack([w, w * g, w * h]).astype(jnp.float32)  # [3, N]
    prev_hist = None
    # a node whose parent did not split is a leaf, not a second chance
    # with a fresh column draw: only mtries can tell the difference (a
    # node that found no split finds none in the same columns below)
    alive = jnp.ones((1,), bool)
    for d in range(D):
        L = 2 ** d
        cm = col_mask
        if mtries > 0 and mtries < F:
            heap = 2 ** d + jnp.arange(L, dtype=jnp.int32)
            cm = _mtries_mask(key, heap, F, mtries) & col_mask[None, :] \
                & alive[:, None]
        if interaction_sets is not None:
            cm = (cm if cm.ndim == 2 else cm[None, :]) & allowed
        use_fused = fused[d]
        if use_kernels and not use_fused:
            pallas_policy.record_fallback("level_fits_no_tile")
        if use_fused:
            (hist, bg, bf, bt, bnal, blv, brv, leftmask, split,
             nid_next) = fused_level(
                bins, nid, stats3, prev_hist, cm, nb, is_cat,
                constraints, lo, hi, sc, d=d, n_nodes=L, n_bins=B,
                block_rows=params.block_rows, mesh=mesh,
                interpret=(params.pallas == "interpret"))
        else:
            with jax.named_scope("tree.hist"):
                if prev_hist is None:
                    hist = histogram(bins, nid, w, g, h, n_nodes=L,
                                     n_bins=B, mesh=mesh,
                                     block_rows=params.block_rows)
                else:
                    # sibling subtraction: histogram only the LEFT
                    # children (even node slots), derive right = parent −
                    # left. Halves the histogram matmul at every level
                    # ≥ 1 (the LightGBM/XGBoost smaller-child trick, made
                    # static-shape by always picking left; the reference
                    # recomputes both children,
                    # hex/tree/ScoreBuildHistogram2.java).
                    even = (nid % 2 == 0).astype(jnp.float32)
                    lh = histogram(bins, nid >> 1, w * even, g, h,
                                   n_nodes=L // 2, n_bins=B, mesh=mesh,
                                   block_rows=params.block_rows)
                    rh = prev_hist - lh
                    # f32 cancellation guard: w and h are nonnegative
                    # sums, so clamp tiny negative residue (|err| ≲
                    # parent·2^-23); g may be legitimately negative and
                    # stays as computed
                    rh = rh.at[..., 0].set(jnp.maximum(rh[..., 0], 0.0))
                    rh = rh.at[..., 2].set(jnp.maximum(rh[..., 2], 0.0))
                    hist = jnp.stack([lh, rh], axis=1).reshape(
                        L, *lh.shape[1:])
            with jax.named_scope("tree.split_scan"):
                bg, bf, bt, bnal, blv, brv, leftmask = _best_splits(
                    hist, nb, cm, params, constraints=constraints, lo=lo,
                    hi=hi, scalars=sc, is_cat=is_cat)
                split = bg > sc.msi
                if sc.depth_limit is not None:
                    # depth-bucketed program: levels past the ACTUAL
                    # depth never split (one compiled program per
                    # DEPTH_BUCKET, not per depth)
                    split = split & (jnp.int32(d) < sc.depth_limit)
            nid_next = None
        prev_hist = hist
        alive = jnp.repeat(split, 2)
        feats = feats.at[d, :L].set(jnp.where(split, bf, 0))
        threshs = threshs.at[d, :L].set(jnp.where(split, bt, B))
        na_lefts = na_lefts.at[d, :L].set(jnp.where(split, bnal, False))
        is_splits = is_splits.at[d, :L].set(split)
        if params.has_cats and is_cat is not None:
            cs = is_cat[bf] & split
            cat_splits = cat_splits.at[d, :L].set(cs)
            words = _pack_leftmask(leftmask, W)
            left_words = left_words.at[d, :L].set(
                jnp.where(cs[:, None], words, 0))
        gain_by_feat = gain_by_feat + jnp.sum(
            jnp.where(split, jnp.maximum(bg, 0.0), 0.0)[:, None]
            * (bf[:, None] == jnp.arange(F, dtype=jnp.int32)[None, :]),
            axis=0)

        # interaction-set propagation (XGBoost/GlobalInteractionConstraints
        # rule): children may use any feature sharing a set with the
        # split feature, intersected with the path's allowance.
        # pair_allow[i, j] = features i and j share a set — one [F, F]
        # precompute, then a per-level [L, F] gather.
        if interaction_sets is not None:
            if pair_allow is None:
                pair_allow = jnp.einsum(
                    "sf,sg->fg", interaction_sets.astype(jnp.float32),
                    interaction_sets.astype(jnp.float32)) > 0
            child_allow = pair_allow[bf]                   # [L, F]
            child_allow = allowed & jnp.where(split[:, None], child_allow,
                                              True)
            allowed = jnp.repeat(child_allow, 2, axis=0)   # children 2l,2l+1

        # bound propagation (Constraints.childBounds role): on a
        # constrained split the midpoint of the child values caps the
        # low side / high side; unconstrained splits inherit
        if constraints is not None:
            c_split = constraints[bf].astype(jnp.float32) * split
            mid = 0.5 * (blv + brv)
            lo_l = lo
            hi_l = jnp.where(c_split > 0, jnp.minimum(hi, mid), hi)
            lo_l = jnp.where(c_split < 0, jnp.maximum(lo, mid), lo_l)
            lo_r = jnp.where(c_split > 0, jnp.maximum(lo, mid), lo)
            hi_r = jnp.where(c_split < 0, jnp.minimum(hi, mid), hi)
            # interleave children: node l → children 2l, 2l+1
            lo = jnp.stack([lo_l, lo_r], axis=1).reshape(-1)
            hi = jnp.stack([hi_l, hi_r], axis=1).reshape(-1)
        # route rows (the reference's DecidedNode assignment pass);
        # the partition kernel has already done it on the kernel path
        if nid_next is not None:
            nid = nid_next
        else:
            with jax.named_scope("tree.partition"):
                nid = _level_goleft(feats[d], threshs[d], na_lefts[d],
                                    is_splits[d], cat_splits[d],
                                    left_words[d], nid, bins, B, d)

    if D < params.max_depth:
        if constraints is not None or interaction_sets is not None:
            raise NotImplementedError(
                "monotone and interaction constraints stop at the "
                "complete layout (TreeParams.frontier_from)")
        empty = jnp.zeros((2 ** D,), jnp.float32)
        top = Tree(feats, threshs, na_lefts, is_splits, empty, empty,
                   cat_splits, left_words)
        deep, ref, gains, capped, scanned = frontier.grow_frontier(
            bins, nb, nid, (w, w * g) if unit_h else (w, w * g, w * h),
            alive, key, col_mask, params=params, K=D, sc=sc, mtries=mtries,
            is_cat=is_cat)
        return (frontier.DeepTree(top, deep, capped, scanned), ref,
                gain_by_feat + gains)

    # leaf Newton values from final assignment (GammaPass analogue)
    nleaf = 2 ** D
    with jax.named_scope("tree.leaf_sums"):
        stats = jnp.stack([w, w * g, w * h], axis=1)
        leaf_stats = segment_sum(nid, stats, n_nodes=nleaf, mesh=mesh,
                                 block_rows=params.block_rows)
    G, H = leaf_stats[:, 1], leaf_stats[:, 2]
    leaf = jnp.where(leaf_stats[:, 0] > 0,
                     -G / (H + sc.reg_lambda + 1e-10), 0.0)
    if constraints is not None:
        leaf = jnp.clip(leaf, lo, hi)   # leaves honor propagated bounds
    tree = Tree(feats, threshs, na_lefts, is_splits, leaf,
                leaf_stats[:, 0], cat_splits, left_words)
    return tree, nid, gain_by_feat


def predict_tree(tree: Tree, bins, B: int):
    """Route binned rows through one tree → leaf values [N]."""
    return tree.leaf[_route(tree, bins, B)]


def leaf_values(tree):
    """The flat per-node values that grow_tree's second result indexes:
    a complete tree's leaves, or a deep tree's node tables."""
    return tree.leaf if isinstance(tree, Tree) \
        else tree.deep.value.reshape(-1)


def stack_trees(trees):
    """Stack per-iteration trees (all ``Tree`` or all ``DeepTree``)
    into [T, ...] arrays for scan-predict."""
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def concat_forests(chunks):
    """Concatenate [T_i, ...] forest chunks (all ``Tree`` or all
    ``DeepTree``) along the tree axis — the chunked-scan and
    model-batched training paths both assemble their final forest
    through this."""
    chunks = list(chunks)
    if len(chunks) == 1:
        return chunks[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a), *chunks)


def unstack_model_trees(batched: Tree, m: int, keep=None) -> Tree:
    """Slice model ``m``'s forest out of a model-batched [M, T, ...]
    stacked Tree (parallel/model_batch vmap axis), optionally truncated
    to its first ``keep`` trees (per-model early stop)."""
    sl = slice(None) if keep is None else slice(int(keep))
    return Tree(*(a[m, sl] for a in batched))


def _route(tree: Tree, bins, B: int):
    """Terminal node id per row for one tree — the single routing
    implementation shared by scoring and leaf assignment."""
    N = bins.shape[0]
    D = tree.feat.shape[0]
    nid = jnp.zeros((N,), jnp.int32)
    for d in range(D):
        with jax.named_scope("forest.level"):
            nid = _level_goleft(tree.feat[d], tree.thresh[d],
                                tree.na_left[d], tree.is_split[d],
                                tree.cat_split[d], tree.left_words[d],
                                nid, bins, B, d)
    return nid


@partial(jax.jit, static_argnames=("B", "F"))
def feature_path_counts(stacked: Tree, bins, B: int, F: int):
    """Per-row counts of feature usage along decision paths, summed over
    all trees [N, F] — hex/tree SharedTreeModel feature_frequencies
    (h2o-py model.feature_frequencies)."""

    def step(counts, tree):
        N = bins.shape[0]
        D = tree.feat.shape[0]
        nid = jnp.zeros((N,), jnp.int32)
        for d in range(D):
            # cut as _level_goleft cuts them: the same lookups, made once
            f_r = tree.feat[d, :2 ** d][nid]
            isp_r = tree.is_split[d, :2 ** d][nid]
            onehot = (f_r[:, None] ==
                      jnp.arange(F, dtype=jnp.int32)[None, :])
            counts = counts + jnp.where(isp_r[:, None] & onehot, 1, 0)
            nid = _level_goleft(tree.feat[d], tree.thresh[d],
                                tree.na_left[d], tree.is_split[d],
                                tree.cat_split[d], tree.left_words[d],
                                nid, bins, B, d)
        return counts, None

    counts0 = jnp.zeros((bins.shape[0], F), jnp.int32)
    counts, _ = jax.lax.scan(step, counts0, stacked)
    return counts


def feature_frequencies_frame(model, frame):
    """Per-feature usage counts as a Frame (h2o-py feature_frequencies)."""
    from h2o3_tpu.frame.binning import rebin_for_scoring
    from h2o3_tpu.frame.frame import Frame
    bm = rebin_for_scoring(model.bm, frame)
    F = bm.bins.shape[1]
    counts = np.asarray(feature_path_counts(
        model.forest, bm.bins, model.bm.nbins_total, F))[: frame.nrows]
    return Frame.from_numpy({bm.names[j]: counts[:, j].astype(np.float64)
                             for j in range(F)})


@partial(jax.jit, static_argnames=("B",))
def leaf_assignments(stacked: Tree, bins, B: int):
    """Per-tree terminal leaf id for every row [N, T] — the
    predict_leaf_node_assignment path (hex/Model.java scoreLeafNode
    /h2o-py predict_leaf_node_assignment with type Node_ID)."""

    def step(_, tree):
        return None, _route(tree, bins, B)

    _, out = jax.lax.scan(step, None, stacked)
    return out.T          # [N, T]


def leaf_assignment_frame(model, frame):
    """Shared GBM/DRF predict_leaf_node_assignment: columns are T{t} for
    single-output forests and T{t}.C{k} per class for stacked per-class
    forests (h2o naming)."""
    from h2o3_tpu.frame.binning import rebin_for_scoring
    from h2o3_tpu.frame.frame import Frame
    bm = rebin_for_scoring(model.bm, frame)
    ids = np.asarray(leaf_assignments(model.forest, bm.bins,
                                      model.bm.nbins_total))[: frame.nrows]
    # forests compile at the DEPTH BUCKET (tree.py DEPTH_BUCKETS) with a
    # traced limit masking deeper splits; the walk therefore returns ids
    # at the bucket depth D — shift back to the REQUESTED depth's id
    # space (rows route left through masked levels, so the shift is an
    # exact inverse)
    D = int(model.forest.feat.shape[1])
    d_req = min(int(model.params.get("max_depth") or D), D)
    if d_req < D:
        ids = ids >> (D - d_req)
    category = model.output.get("category")
    K = (model.output.get("nclasses", 1)
         if category == "Multinomial" else 1)
    # classification columns carry a .C{k} suffix even for binomial
    # (SharedTreeModel.java:326 — suffix dropped only when the per-iter
    # tree-key array has a single entry, i.e. regression)
    suffixed = category in ("Binomial", "Multinomial")
    cols = {}
    for j in range(ids.shape[1]):
        name = (f"T{j // K + 1}.C{j % K + 1}" if suffixed
                else f"T{j + 1}")
        cols[name] = ids[:, j].astype(np.float64)
    return Frame.from_numpy(cols)


@partial(jax.jit, static_argnames=("B",))
def predict_forest(stacked: Tree, bins, B: int):
    """Sum of all trees' outputs via lax.scan over the tree axis.

    The compressed-forest scoring path (hex/tree/CompressedTree.java walk
    inside BigScore, hex/Model.java:2085) as one jitted program.
    """

    def step(acc, tree):
        return acc + predict_tree(tree, bins, B), None

    init = jnp.zeros((bins.shape[0],), jnp.float32)
    total, _ = jax.lax.scan(step, init, stacked)
    return total
