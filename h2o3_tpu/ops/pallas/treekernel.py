"""Pallas tree kernels: the two row passes of a tree level, on the chip.

The XLA level loop (models/tree.py grow_tree) touches the binned matrix
twice per depth level — one-hot matmul histograms (ops/histogram.py),
then ``_level_goleft`` re-reads the matrix to route rows — with the
split scan between them. This module runs the two row passes as Pallas
kernels over bin-major tiles (frame/binning.py tile layout: int8,
feature-major lanes, NA folded in as bin B-1), the way the GPU
tree-boosting systems do (Booster arxiv 2011.02022; XGBoost-GPU arxiv
1806.11248), on every mesh alike:

- a per-shard histogram kernel streams the tiles through VMEM and
  accumulates the histogram in a VMEM scratch on the MXU — both
  operands bfloat16, the float32 statistics as three pieces split on
  the tile in VMEM (ops/histogram.stat_rows), so every sum is a
  float32 sum; the three [3L, F·B] slabs are added outside;
- the cross-shard ``psum`` (the MRTask reduce tree,
  water/MRTask.java:891 — a no-op on one shard) and the level boundary
  in plain XLA: sibling subtraction against the parent level and the
  shared split scan (ops/split_scan.py — the SAME function the XLA path
  calls, so the two paths are bit-exact by construction). The scan is
  ``jax.numpy`` (cumsum, sort, scatter) and cannot lower inside a
  kernel, which is why there is no single fused ``pallas_call``;
- a per-shard partition kernel re-streams the tiles and routes every
  row to its child.

Numerics contract: with ``interpret=True`` (CPU tier-1) every output is
bit-exact vs the XLA path on the same mesh — f32 accumulation with the
XLA path's exact row-block structure, identical split tie-breaking
(shared code), integer routing. Native TPU runs use VMEM-sized tiles
(ops/pallas.tile_rows) and trade the bitwise match for fitting the
chip. A level whose shapes fit no tile (``tile_rows == 0``: deep
levels, bin ids past 256) is grown by grow_tree's XLA sequence instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from h2o3_tpu.ops import pallas as pallas_policy
from h2o3_tpu.ops.histogram import piece_rows, stat_rows, sum_pieces
from h2o3_tpu.ops.split_scan import best_splits
from h2o3_tpu.parallel.mesh import DATA_AXIS


# --------------------------------------------------------------- tile math


def _tile_geometry(n_rows: int, block_rows: int):
    """(C, nblk, n_pad): the XLA path's exact row-block structure
    (ops/histogram.py _local_histogram) — sharing it is what makes the
    f32 accumulation order, and therefore the histograms, bit-identical
    in interpret mode."""
    C = min(block_rows, n_rows)
    nblk = (n_rows + C - 1) // C
    return C, nblk, nblk * C


def _pad_lanes(arr, n_pad: int):
    n = arr.shape[1]
    if n == n_pad:
        return arr
    return jnp.pad(arr, ((0, 0), (0, n_pad - n)))


# ----------------------------------------------------- kernel block bodies
#
# Layout: PER-ROW VALUES RIDE THE LANES. Node ids reach a kernel as a
# [1, C] row, stats as a [3, C] block, and the partition kernel reads
# the bins transposed, [F, C] — the last (lane) axis is the row axis,
# so they are lane-dense in HBM and in VMEM. The row-major alternative,
# [N, 1] and [N, 3] operands, pads each to 128 lanes: 2.7 GB apiece in
# HBM at 5M rows (the boost scan's temporaries compiled to 14 GB). Both
# kernels compute in that orientation; only the histogram kernel's bins
# tile is row-major, [C, F], because its one-hot is the product's
# [C, F·B] right operand.


def _hist_block(bins, nid, stats, *, n_nodes_h: int, n_bins: int, d: int):
    """One tile's [piece_rows(Lh), F·B] partial products — VMEM one-hots
    feeding the MXU. ``bins`` [C, F], ``nid`` [1, C], ``stats`` [3, C]:
    the forms of ops/histogram._block_hist, whose values (not just
    sums) this matches — the one-hot indicators are exact 0/1, the
    stats operand is the same ``stat_rows`` and the contraction the
    same ``left @ right``, so the f32 accumulation sees identical
    operands in an identical order. At levels d >= 1 only LEFT-child
    rows accumulate, into their PARENT's slot (the sibling-subtraction
    trick of grow_tree, kept inside the kernel).

    The (feature, bin) indicator is built by EXPANDING the row's bins
    across the F·B lanes with a [C, F] x [F, F·B] 0/1 selection matmul
    and one compare — a per-feature compare-and-add loop costs Mosaic F
    times the VPU work and an amount of scoped VMEM that no formula
    predicted. The expansion runs in bf16 (one MXU pass, f32 result):
    exact for bin ids up to 256, which ``tile_rows`` guarantees."""
    C, F = bins.shape
    FB = F * n_bins
    assert n_bins <= pallas_policy.MAX_KERNEL_BINS, n_bins
    if d > 0:
        even = ((nid % 2) == 0).astype(jnp.float32)      # [1, C]
        stats = stats * even
        nid = nid >> 1
    lane_f = jax.lax.broadcasted_iota(jnp.int32, (F, FB), 1) // n_bins
    sel = lane_f == jax.lax.broadcasted_iota(jnp.int32, (F, FB), 0)
    row_bin = jax.lax.dot_general(                   # bins[c, lane's feature]
        bins.astype(jnp.bfloat16), sel.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [C, FB]
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, FB), 1) % n_bins
    right = (row_bin == lane_b.astype(jnp.float32)).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        stat_rows(nid, stats, n_nodes_h), right,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _level_boundary(lh, prev_hist, cm, nb, is_cat, constraints, lo, hi,
                    knobs, dl, *, d: int, n_nodes: int, n_bins: int,
                    n_features: int):
    """Histogram → split decisions, between the two row passes.

    Line-for-line the XLA level head of grow_tree: reshape the matmul
    accumulator to [Lh, F, B, 3], sibling-subtract against the parent
    level (with the f32 cancellation clamps), then the SHARED split scan
    (ops/split_scan.best_splits) and the split/categorical flags."""
    Lh = max(n_nodes // 2, 1)
    lh4 = lh.reshape(Lh, 3, n_features, n_bins).transpose(0, 2, 3, 1)
    if d == 0:
        hist = lh4
    else:
        rh = prev_hist - lh4
        rh = rh.at[..., 0].set(jnp.maximum(rh[..., 0], 0.0))
        rh = rh.at[..., 2].set(jnp.maximum(rh[..., 2], 0.0))
        hist = jnp.stack([lh4, rh], axis=1).reshape(n_nodes,
                                                    *lh4.shape[1:])
    bg, bf, bt, bnal, blv, brv, leftmask = best_splits(
        hist, nb, cm != 0, min_rows=knobs[0, 0], reg_lambda=knobs[0, 1],
        is_cat=is_cat, constraints=constraints, lo=lo, hi=hi)
    split = bg > knobs[0, 2]
    split = split & (jnp.int32(d) < dl[0, 0])
    if is_cat is not None:
        cs = is_cat[bf] & split
    else:
        cs = jnp.zeros_like(split)
    return hist, bg, bf, bt, bnal, blv, brv, leftmask, split, cs


def _partition_block(bins, nid, bf, bt, bnal, isp, cs, leftmask, *,
                     n_bins: int):
    """Route one tile's rows to their children — gather-free
    ``_level_goleft`` semantics (one-hot selects + a 0/1 matmul for the
    categorical left-set membership). Pure integer/boolean work ⇒
    bit-exact against the XLA routing by construction.

    ``bins`` [F, C], ``nid`` [1, C]; ``bf``/``bt``/``bnal``/``isp``/
    ``cs`` are [L, 1] int32 columns (flags 0/1) and ``leftmask`` a
    [B-1, L] f32 0/1 block. Written for what Mosaic lowers: every
    per-row value stays a [1, C] row (no 1-D vectors), flags stay int32
    until one final compare, and booleans combine through and/or/not —
    a ``select`` between two boolean operands is refused ("Unsupported
    target bitwidth for truncation")."""
    F, C = bins.shape
    L = bf.shape[0]
    bins = bins.astype(jnp.int32)
    noh = nid == jax.lax.broadcasted_iota(jnp.int32, (L, C), 0)  # [L, C]

    def of_node(col):                                            # [1, C]
        return jnp.sum(jnp.where(noh, col, 0), axis=0, keepdims=True)

    f_r = of_node(bf)
    t_r = of_node(bt)
    nal_r = of_node(bnal) > 0
    isp_r = of_node(isp) > 0
    cs_r = of_node(cs) > 0
    fio = jax.lax.broadcasted_iota(jnp.int32, (F, C), 0)
    b_r = jnp.sum(jnp.where(f_r == fio, bins, 0), axis=0, keepdims=True)
    isna = b_r == (n_bins - 1)
    go_num = b_r <= t_r
    # leftmask[nid, b_r] without a 2D gather: 0/1 matmul over nodes,
    # then a sublane select over bins. Both operands are indicators,
    # exact in the MXU's one bfloat16 pass — so that pass is NAMED: a
    # float32 product with no precision at all is what the precision
    # contract (tests/test_gbm_reference_parity.py) takes for a
    # forgotten one. (HIGHEST here costs the kernel 2.6x on a v5e; a
    # bfloat16 pair is a dot the CPU runtime refuses in this program.)
    row_mask = jax.lax.dot_general(
        leftmask, jnp.where(noh, 1.0, 0.0).astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)                      # [B-1, C]
    bio = jax.lax.broadcasted_iota(jnp.int32, (n_bins - 1, C), 0)
    inset = jnp.sum(jnp.where(bio == b_r, row_mask, 0.0), axis=0,
                    keepdims=True) > 0.5
    go_split = (cs_r & inset) | (~cs_r & go_num)
    goleft = ~isp_r | (isna & nal_r) | (~isna & go_split)
    return 2 * nid + jnp.where(goleft, 0, 1)


# ------------------------------------------------------ the two kernels


def _hist_kernel(bins_ref, nid_ref, stats_ref, out_ref, acc_ref, *,
                 d: int, n_nodes_h: int, n_bins: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += _hist_block(bins_ref[:], nid_ref[:], stats_ref[:],
                              n_nodes_h=n_nodes_h, n_bins=n_bins, d=d)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _lane_tile(k: int, C: int):
    return pl.BlockSpec((k, C), lambda i: (0, i))


def _hist_call(bins, nid, stats, *, d, n_nodes, n_bins, block_rows,
               interpret):
    """Per-shard histogram kernel over ``bins`` [N, F], ``nid`` [1, N],
    ``stats`` [3, N] → float32 sums [3Lh, F·B] (caller psums)."""
    N, F = bins.shape
    C, nblk, n_pad = _tile_geometry(N, block_rows)
    Lh = max(n_nodes // 2, 1)
    acc = (piece_rows(Lh), F * n_bins)
    pallas_policy.record_launch("tree_hist")
    pieces = pl.pallas_call(
        functools.partial(_hist_kernel, d=d, n_nodes_h=Lh, n_bins=n_bins),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((C, F), lambda i: (i, 0)),
                  _lane_tile(1, C), _lane_tile(3, C)],
        out_specs=pl.BlockSpec(acc, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(acc, jnp.float32),
        scratch_shapes=[pltpu.VMEM(acc, jnp.float32)],
        interpret=interpret, name="tree_hist",
    )(jnp.pad(bins, ((0, n_pad - N), (0, 0))), _pad_lanes(nid, n_pad),
      _pad_lanes(stats, n_pad))
    return sum_pieces(pieces, Lh)


def _partition_kernel(bins_ref, nid_ref, bf_ref, bt_ref, bnal_ref,
                      isp_ref, cs_ref, lmask_ref, newnid_ref, *,
                      n_bins: int):
    newnid_ref[:] = _partition_block(
        bins_ref[:], nid_ref[:], bf_ref[:], bt_ref[:], bnal_ref[:],
        isp_ref[:], cs_ref[:], lmask_ref[:], n_bins=n_bins)


def _partition_call(bins, nid, bf, bt, bnal, isp, cs, lmask, *, n_bins,
                    block_rows, interpret):
    """Per-shard partition kernel over ``bins`` [F, N], ``nid`` [1, N]
    and the level's [L] decisions → routed node ids [1, N]."""
    F, N = bins.shape
    C, nblk, n_pad = _tile_geometry(N, block_rows)
    L = bf.shape[0]
    pallas_policy.record_launch("tree_partition")
    full = lambda *shape: pl.BlockSpec(       # noqa: E731
        shape, lambda i: (0,) * len(shape))
    col = lambda v: v.astype(jnp.int32)[:, None]   # noqa: E731
    newnid = pl.pallas_call(
        functools.partial(_partition_kernel, n_bins=n_bins),
        grid=(nblk,),
        in_specs=[_lane_tile(F, C), _lane_tile(1, C),
                  full(L, 1), full(L, 1), full(L, 1), full(L, 1),
                  full(L, 1), full(n_bins - 1, L)],
        out_specs=_lane_tile(1, C),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret, name="tree_partition",
    )(_pad_lanes(bins, n_pad), _pad_lanes(nid, n_pad),
      col(bf), col(bt), col(bnal), col(isp), col(cs),
      lmask.astype(jnp.float32).T)
    return newnid[:, :N]


# ------------------------------------------- the frontier's histogram pass
#
# models/frontier.py keeps a level's rows ordered by node, or by an
# ancestor of their node, so the rows of a block of ``lb`` nodes lie in
# one row range (frontier.block_ranges), wherever it starts; between two
# sorts the range also holds final rows and may share rows with the
# neighbouring blocks'. The kernel streams ALIGNED row tiles and a
# schedule (frontier_schedule) says, for each grid step, which tile it
# reads and which block it adds to — the grouped-matmul pattern
# (jax.experimental.pallas.ops.tpu.megablox.gmm): a tile that straddles
# ranges is visited once a block, and a row counts for the block whose
# LOCAL node id it has (the keys are the level's own from its first
# super-batch to its last: routing writes the next level's elsewhere).


def frontier_schedule(lo, hi, tile: int, n_tiles: int, overlap: int = 1):
    """The level's steps from the blocks' row ranges [lo[k], hi[k])
    ([nblk] each, ascending; a row lies in at most ``overlap`` of them,
    frontier.range_blocks): ``(step0, blk, tid)`` — block k takes the
    steps [step0[k], step0[k+1]), one for each tile its range touches,
    and ONE where the range is empty (its histogram still has to be
    zeroed); step i adds tile ``tid[i]`` to block ``blk[i]``. The step
    arrays have the static length ``overlap * n_tiles + nblk`` that no
    level can pass (the blocks k, k + overlap, … share no row, so their
    steps are at most a step a tile and one more a block); the steps
    that exist are the first ``step0[nblk]``."""
    nblk = lo.shape[0]
    first = jnp.minimum(lo // tile, n_tiles - 1)
    count = jnp.where(hi > lo, (hi - 1) // tile - first + 1, 1)
    step0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(count, dtype=jnp.int32)])
    i = jnp.arange(overlap * n_tiles + nblk, dtype=jnp.int32)
    blk = jnp.clip(jnp.searchsorted(step0, i, side="right").astype(jnp.int32)
                   - 1, 0, nblk - 1)
    tid = jnp.minimum(first[blk] + i - step0[blk], n_tiles - 1)
    return step0, blk, tid


def _frontier_hist_kernel(meta_ref, blk_ref, tid_ref, lo_ref, hi_ref,
                          fid_ref, *refs, lb: int, n_features: int,
                          n_bins: int, bits: int, n_words: int, n_stats: int,
                          n_pieces: int):
    word_refs = refs[:n_words]
    stat_refs = refs[n_words:n_words + n_stats]
    out_ref = refs[n_words + n_stats]
    at = meta_ref[0] + pl.program_id(0)
    blk = blk_ref[at]

    @pl.when((pl.program_id(0) == 0)
             | (blk_ref[jnp.maximum(at - 1, 0)] != blk))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    T = fid_ref.shape[1]
    at_row = tid_ref[at] * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    mine = (at_row >= lo_ref[blk]) & (at_row < hi_ref[blk])
    lid = jnp.where(mine, fid_ref[...] - blk * lb, -1)           # [1, T]
    # the (feature, bin) indicator with the rows on the lanes: feature
    # f's bins are the sublanes [f·Bp, f·Bp + B) — one compare of the
    # row's bin against a sublane iota a feature, no expansion product
    Bp = pallas_policy.frontier_bin_rows(n_bins)
    per = 32 // bits
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (Bp, T), 0)
    words = [jax.lax.bitcast_convert_type(r[...], jnp.int32)
             for r in word_refs]                                 # [1, T]
    ind = jnp.concatenate(
        [(((words[f // per] >> (bits * (f % per))) & ((1 << bits) - 1))
          == bin_id).astype(jnp.float32).astype(jnp.bfloat16)
         for f in range(n_features)], axis=0)
    s_id = jax.lax.broadcasted_iota(jnp.int32, (n_stats, T), 0)
    stats = stat_refs[-1][...]
    for j in range(n_stats - 2, -1, -1):
        stats = jnp.where(s_id == j, stat_refs[j][...], stats)   # [S, T]
    # [M, T] x [F·Bp, T] over the rows, the lanes of both ("NT")
    out_ref[...] += jax.lax.dot_general(
        stat_rows(lid, stats, lb, n_stats, n_pieces), ind,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def frontier_hist(sched, lo, hi, s, fid, words, stats, *, lb: int, sb: int,
                  n_features: int, n_bins: int, bits: int, n_pieces: int,
                  tile: int, interpret: bool):
    """Super-batch ``s`` of a frontier level — the blocks s·sb ..
    (s+1)·sb - 1 of ``lb`` nodes — as float32 sums [sb·lb, F, B, S]:
    ``fid`` [N] the rows' node slots at this level, ``words`` their
    packed bin ids ([N] uint32 each, ``bits`` a bin), ``stats`` their S
    statistics ([N] float32 each); N a multiple of ``tile``. Block k's
    rows all lie in [lo[k], hi[k]) (frontier.block_ranges) and ``sched``
    is the level's ``frontier_schedule`` of those ranges; the grid is
    the super-batch's own steps, a traced count."""
    step0, blk, tid = sched
    N = fid.shape[0]
    assert N % tile == 0, (N, tile)
    S, F, B = len(stats), n_features, n_bins
    Bp = pallas_policy.frontier_bin_rows(B)
    M = piece_rows(lb, S, n_pieces)
    k0 = s * sb
    off = step0[k0]
    meta = jnp.stack([off, k0]).astype(jnp.int32)
    row = pl.BlockSpec((1, tile), lambda i, meta, blk, tid, lo, hi:
                       (0, tid[meta[0] + i]))
    rows = [fid, *words, *stats]
    pallas_policy.record_launch("tree_frontier_hist")
    acc = pl.pallas_call(
        functools.partial(
            _frontier_hist_kernel, lb=lb, n_features=F, n_bins=B,
            bits=bits, n_words=len(words), n_stats=S, n_pieces=n_pieces),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(step0[k0 + sb] - off,),
            in_specs=[row] * len(rows),
            out_specs=pl.BlockSpec(
                (None, M, F * Bp), lambda i, meta, blk, tid, lo, hi:
                (blk[meta[0] + i] - meta[1], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((sb, M, F * Bp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="tree_frontier_hist",
    )(meta, blk, tid, lo, hi, *(r[None, :] for r in rows))
    # [sb, M, F·Bp] rows piece·S·lb + S·node + stat → [nodes, F, B, S]
    sums = jax.vmap(lambda a: sum_pieces(a, lb, S, n_pieces))(acc)
    return sums.reshape(sb * lb, S, F, Bp)[..., :B].transpose(0, 2, 3, 1)


# ----------------------------------------------------------- entry points


def fused_level(bins, nid, stats, prev_hist, col_mask, nb, is_cat,
                constraints, lo, hi, scalars, *, d: int, n_nodes: int,
                n_bins: int, block_rows: int, mesh, interpret: bool):
    """One tree level through the kernels: returns (hist [L,F,B,3],
    gain, feat, thresh, na_left, left_val, right_val, leftmask, split,
    new_nid).

    Drop-in for grow_tree's per-level XLA sequence (histogram →
    _best_splits → _level_goleft), with identical semantics: ``stats``
    is the level-invariant [3, N] {w, w·g, w·h} block, ``prev_hist`` the
    previous level's histogram (None at the root — sibling subtraction
    starts at level 1), and the returned ``split`` already folds in the
    min-split-improvement and traced depth-limit masks. Rows must be
    pre-padded to the mesh (N %% data-shards == 0), as grow_tree's are.

    Native mode sizes the tile rows for VMEM (ops/pallas.tile_rows —
    the caller has checked that the level fits); interpret mode keeps
    the XLA path's exact block structure so tier-1 can assert bitwise
    parity.
    """
    knobs = jnp.stack([scalars.min_rows, scalars.reg_lambda,
                       scalars.msi]).astype(jnp.float32).reshape(1, 3)
    dl = (scalars.depth_limit if scalars.depth_limit is not None
          else jnp.int32(1 << 30))
    dl = jnp.asarray(dl, jnp.int32).reshape(1, 1)
    cm2 = (col_mask if col_mask.ndim == 2
           else col_mask[None, :]).astype(jnp.int8)
    nb2 = jnp.asarray(nb, jnp.int32)[None, :]
    iscat = None if is_cat is None else is_cat.astype(jnp.int8)[None, :]
    cons = (None if constraints is None
            else jnp.asarray(constraints, jnp.int8)[None, :])
    lo2 = jnp.asarray(lo, jnp.float32)[None, :]
    hi2 = jnp.asarray(hi, jnp.float32)[None, :]
    F = bins.shape[1]
    if not interpret:
        tile = pallas_policy.tile_rows(F, n_bins, n_nodes)
        assert tile > 0, (F, n_bins, n_nodes)
        block_rows = min(block_rows, tile)

    # per-shard hist kernel, psum barrier, boundary math in XLA,
    # per-shard partition kernel
    has_cats = iscat is not None
    has_cons = cons is not None
    Lh = max(n_nodes // 2, 1)
    prev = (prev_hist if prev_hist is not None
            else jnp.zeros((Lh, F, n_bins, 3), jnp.float32))
    iscat_in = iscat if has_cats else jnp.zeros((1, F), jnp.int8)
    cons_in = cons if has_cons else jnp.zeros((1, F), jnp.int8)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS))
        + (P(),) * 9,
        out_specs=(P(),) * 9 + (P(DATA_AXIS),), check_vma=False)
    def _task(bins_l, nid_l, stats_l, prev, cm2, nb2, iscat_a, cons_a,
              lo2, hi2, knobs, dl):
        # the scope names grow_tree's XLA sequence gives the same steps
        nid_l = nid_l[None, :]
        with jax.named_scope("tree.hist"):
            lh = _hist_call(bins_l, nid_l, stats_l, d=d, n_nodes=n_nodes,
                            n_bins=n_bins, block_rows=block_rows,
                            interpret=interpret)
            lh = jax.lax.psum(lh, DATA_AXIS)
        with jax.named_scope("tree.split_scan"):
            hist, bg, bf, bt, bnal, blv, brv, lmask, split, cs = \
                _level_boundary(
                    lh, prev if d > 0 else None, cm2, nb2[0],
                    iscat_a[0] != 0 if has_cats else None,
                    cons_a[0] if has_cons else None, lo2[0], hi2[0],
                    knobs, dl, d=d, n_nodes=n_nodes, n_bins=n_bins,
                    n_features=F)
        with jax.named_scope("tree.partition"):
            newnid_l = _partition_call(bins_l.T, nid_l, bf, bt, bnal,
                                       split, cs, lmask, n_bins=n_bins,
                                       block_rows=block_rows,
                                       interpret=interpret)[0]
        return (hist, bg, bf, bt, bnal, blv, brv, lmask, split, newnid_l)

    return _task(bins, nid, stats, prev, cm2, nb2, iscat_in, cons_in,
                 lo2, hi2, knobs, dl)


def xla_level(bins, nid, w, g, h, prev_hist, col_mask, nb, is_cat,
              constraints, lo, hi, scalars, *, d: int, n_nodes: int,
              n_bins: int, block_rows: int, mesh):
    """Reference composition — grow_tree's per-level XLA sequence as one
    callable, for the interpret-parity tests and the bench `treekernel`
    leg. Same return tuple as fused_level."""
    from h2o3_tpu.models.tree import _level_goleft, _pack_leftmask
    from h2o3_tpu.ops.histogram import histogram
    L, B = n_nodes, n_bins
    if d == 0 or prev_hist is None:
        hist = histogram(bins, nid, w, g, h, n_nodes=L, n_bins=B,
                         mesh=mesh, block_rows=block_rows)
    else:
        even = (nid % 2 == 0).astype(jnp.float32)
        lh = histogram(bins, nid >> 1, w * even, g, h, n_nodes=L // 2,
                       n_bins=B, mesh=mesh, block_rows=block_rows)
        rh = prev_hist - lh
        rh = rh.at[..., 0].set(jnp.maximum(rh[..., 0], 0.0))
        rh = rh.at[..., 2].set(jnp.maximum(rh[..., 2], 0.0))
        hist = jnp.stack([lh, rh], axis=1).reshape(L, *lh.shape[1:])
    bg, bf, bt, bnal, blv, brv, leftmask = best_splits(
        hist, nb, col_mask, min_rows=scalars.min_rows,
        reg_lambda=scalars.reg_lambda, is_cat=is_cat,
        constraints=constraints, lo=lo, hi=hi)
    split = bg > scalars.msi
    if scalars.depth_limit is not None:
        split = split & (jnp.int32(d) < scalars.depth_limit)
    feat_d = jnp.where(split, bf, 0)
    thresh_d = jnp.where(split, bt, B)
    nal_d = jnp.where(split, bnal, False)
    if is_cat is not None:
        cs = is_cat[bf] & split
        W = max(1, (B - 1 + 31) // 32)
        words = jnp.where(cs[:, None], _pack_leftmask(leftmask, W), 0)
    else:
        cs = jnp.zeros_like(split)
        words = jnp.zeros((L, 1), jnp.uint32)
    newnid = _level_goleft(feat_d, thresh_d, nal_d, split, cs, words,
                           nid, bins, B, d)
    return (hist, bg, bf, bt, bnal, blv, brv, leftmask, split, newnid)
