"""The frontier regime of tree growth — levels past the complete layout.

``models/tree.py`` keeps a tree as a COMPLETE binary tree: level d has
2^d node slots whether rows reach them or not, so a level's histogram is
``[2^d, F·B, 3]`` — 7.9 GB at level 19 of the airlines widths. That
layout and its kernels stay for the shallow levels they fit. Below them
(``TreeParams.frontier_from``) a tree continues here:

- **storage**: a node table a level (``DeepLevels``: split feature, bin,
  NA side, category words, the index of the left child in the next
  level's table, the node's own value and weight, its path id). A level
  holds its LIVE nodes, compact, in the order their parents split; its
  static capacity comes from the padded row count and the depth
  (``frontier_capacity``: a live node holds at least one row).
- **growth**: the rows are ordered by node on the first frontier level
  and on every ``TreeParams.frontier_sort_every``-th after it (one
  multi-operand sort: the node id is the key, the packed bin ids, the
  statistics and the row id ride along), not at every level. What a
  level's passes need of the order is that the rows of a block of
  ``NODE_BLOCK`` consecutive nodes lie in one row range that holds few
  rows of other blocks, and the slots are handed out so that this
  outlives a level: children are numbered in the order their parents
  split, so the descendants of a run of slots are a run of slots at
  every later level, and their rows are the rows that lay in the
  ancestors' range when the rows were last sorted. A block therefore
  reads the range of its slots' ancestors at that sort (``block_ranges``:
  two binary searches a block in the keys as the sort left them); on a
  level that sorted, that is the block's own rows, and between sorts a
  block shares at most the ancestor at each end of its range with its
  neighbour, whose rows both scan (a row's LOCAL node id says whose it
  is). The range is cut into
  chunks of ``CHUNK_ROWS`` rows; a chunk's histogram is the one-hot
  product of ``ops/histogram.py`` over the block's LOCAL node ids — the
  same ``stat_rows`` operand, three bfloat16 pieces a statistic, so
  every sum is a float32 sum. ``SUPER_BLOCKS`` blocks make a
  super-batch: its histogram ``[nodes, F, B, 3]`` goes through the ONE
  split rule (``tree._best_splits``) and is dropped, its rows are routed
  to their children — into the NEXT level's keys, a second array: the
  keys a level's passes read are the ones it started with — and the
  next super-batch reuses the memory. A level
  therefore costs ``rows / CHUNK_ROWS + live nodes / NODE_BLOCK`` chunk
  products and ``live nodes`` split scans, not 2^d of either.
- **the per-node column draw** is a function of (tree key, the node's
  heap id 2^d + path) in both regimes (``tree._mtries_mask``).

Rows whose node does not split are final: their sort key becomes the
capacity plus the table index of that node, so they sort behind the live
rows at the next sort (until then they lie among them, and no block
counts them) and the key still says where they ended.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.ops.histogram import piece_rows, stat_rows, sum_pieces

# rows of one chunk product; local nodes a chunk's statistics operand
# spans; blocks whose histograms are held at once (a super-batch:
# NODE_BLOCK * SUPER_BLOCKS nodes go through one split scan)
CHUNK_ROWS = 8192
NODE_BLOCK = 64
SUPER_BLOCKS = 32
# the forest fits sort the rows on every SORT_PERIOD-th frontier level
# (models/drf.py hands it on as TreeParams.frontier_sort_every). On the
# chip a 2-tree depth-20 job on 48M rows took 17.71 / 16.11 / 15.31 s at
# 2 / 3 / 4, a sort 0.42 s, with 0.2 / 1.2 / 2.8% of the rows read
# twice or final (PERF.md §6, PR 38); a regression forest's passes cost
# three times the cell's a row, so the period stays small
SORT_PERIOD = 4


class DeepLevels(NamedTuple):
    """Levels K..D of one tree, ``[D - K + 1, Lcap]`` each (the last
    level holds leaves only). Slot i of level d is live where
    ``path[d, i] >= 0``."""
    feat: jax.Array        # int32 split feature
    thresh: jax.Array      # int32 split bin (left if bin <= t)
    na_left: jax.Array     # bool
    is_split: jax.Array    # bool
    cat_split: jax.Array   # bool — category-subset split
    left_words: jax.Array  # [.., Lcap, W] uint32 packed left-set
    child: jax.Array       # int32 slot of the LEFT child one level down
    value: jax.Array       # float32 the node's own leaf value
    weight: jax.Array      # float32 training weight in the node
    path: jax.Array        # int32 position in the complete level, -1 dead


class DeepTree(NamedTuple):
    """A tree grown past the complete layout: ``top`` — the complete
    levels 0..K-1 (a ``tree.Tree`` whose leaf arrays are unused) — and
    the node tables below."""
    top: tuple
    deep: DeepLevels
    capped: jax.Array      # bool: a split was refused for want of slots
    scanned: jax.Array     # [2] float32, summed over the frontier levels:
                           # the rows the blocks' ranges held, the live rows


class DeepForestError(NotImplementedError):
    """A reader of the complete ``Tree`` layout was handed a forest
    that is really deeper than that layout holds."""


def frontier_capacity(n_rows: int, depth: int) -> int:
    """Static node slots of a frontier level: a live node holds a row,
    and level d of a depth-D tree has at most 2^d nodes."""
    rows_cap = 1 << max(int(n_rows) - 1, 1).bit_length()
    return max(2, min(2 ** max(depth - 1, 1), rows_cap))


def complete_levels(n_rows: int, depth: int, frontier_from: int) -> int:
    """K: how many levels keep the complete layout. Level K's 2^K slots
    have to fit the frontier's capacity."""
    if frontier_from <= 0 or depth <= frontier_from:
        return depth
    cap = frontier_capacity(n_rows, depth)
    return max(1, min(frontier_from, cap.bit_length() - 1))


def _geometry(n_rows: int, lcap: int):
    lb = min(NODE_BLOCK, lcap)
    sb = min(SUPER_BLOCKS, lcap // lb)
    return min(CHUNK_ROWS, n_rows), lb, sb


def sort_levels(n_levels: int, period: int) -> int:
    """How many of a tree's ``n_levels`` frontier levels order the rows:
    the first, and every ``period``-th after it."""
    return -(-n_levels // period)


def range_blocks(period: int, lb: int) -> int:
    """How many blocks' ranges a row can lie in. A sort's node has up to
    2^(period - 1) descendants before the next sort, a run of slots from
    an even one; a pair of children never straddles a block of an even
    ``lb``."""
    run = 2 ** (period - 1)
    return 1 if run <= 2 else (run - 3) // lb + 2


def block_ranges(key_at_sort, anc, n_live, *, lb: int):
    """``(lo, hi)`` [nblk] int32: block k — the live slots k·lb ..
    min((k+1)·lb, n_live) - 1 — reads the rows [lo[k], hi[k]), the rows
    of its first to its last slot's ancestor at the last sort:
    ``key_at_sort`` [N] the keys as that sort left them (ascending),
    ``anc`` [lcap] each live slot's slot at that sort (ascending over
    the live slots; the identity on a level that sorted, where the
    ranges are the blocks' own rows, end to end). A block with no live
    slot gets an empty range."""
    nblk = anc.shape[0] // lb
    a = jnp.arange(nblk, dtype=jnp.int32) * lb
    b = jnp.maximum(jnp.minimum(a + lb, n_live) - 1, 0)
    at = jnp.searchsorted(
        key_at_sort, jnp.concatenate([anc[a], anc[b] + 1]),
        side="left").astype(jnp.int32)
    lo, hi = at[:nblk], at[nblk:]
    return jnp.where(a < n_live, lo, hi), hi


def hist_plan(params, n_rows: int, n_features: int, n_stats: int):
    """(tile, pieces) of a frontier level's histogram pass: the row tile
    of the Pallas kernel ``tree_frontier_hist`` — 0 where the fit asked
    for no kernels or no tile fits, and the XLA chunk product runs — and
    the bfloat16 pieces a statistic enters the product in (1 where
    ``params.whole_stats``, else 3). ``n_stats * pieces`` is the operand's
    rows a node. The one place that decides it: ``grow_frontier`` follows
    it and the forest fit reports it on ``drf.chunk``."""
    pieces = 1 if params.whole_stats else 3
    if params.pallas not in ("native", "interpret"):
        return 0, pieces
    from h2o3_tpu.ops.pallas import frontier_tile_rows
    B = params.nbins_total
    lb = _geometry(n_rows, frontier_capacity(n_rows, params.max_depth))[1]
    n_words = -(-n_features // (32 // _bin_bits(B)))
    return frontier_tile_rows(n_features, B, piece_rows(lb, n_stats, pieces),
                              1 + n_words + n_stats), pieces


def _bin_bits(n_bins: int) -> int:
    return 8 if n_bins <= 256 else 16


def pack_bins(bins, n_bins: int):
    """``bins`` [N, F] → a tuple of [N] uint32 words holding the row's
    bin ids, 8 bits each (16 where a bin id passes 255): what a row
    carries through the level sorts."""
    bits = _bin_bits(n_bins)
    per = 32 // bits
    F = bins.shape[1]
    words = []
    for j in range(0, F, per):
        word = jnp.zeros((bins.shape[0],), jnp.uint32)
        for i in range(min(per, F - j)):
            word = word | ((bins[:, j + i].astype(jnp.uint32)
                            & jnp.uint32((1 << bits) - 1))
                           << jnp.uint32(bits * i))
        words.append(word)
    return tuple(words), bits


def _unpack(words, f: int, bits: int):
    per = 32 // bits
    return ((words[f // per] >> jnp.uint32(bits * (f % per)))
            & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)


def chunk_product_hist(lo, hi, s, fid, words, stats, *, lb: int, sb: int,
                       chunk: int, n_features: int, n_bins: int, bits: int,
                       n_pieces: int):
    """Super-batch ``s`` of a frontier level as float32 sums
    [sb·lb, F, B, S], in XLA: what ``treekernel.frontier_hist`` computes
    where no kernel runs, and the oracle it is held to. Block k's range
    [lo[k], hi[k]) of ``fid`` / ``words`` / ``stats`` (``block_ranges``:
    it holds every row of the block's nodes, and may hold rows of other
    blocks' and final rows) is cut into chunks of ``chunk`` rows from
    its first (the arrays end in ``chunk`` rows that belong to no node,
    so a chunk cut at any row stays inside); a chunk's histogram is the
    one-hot product of ops/histogram.py over the block's LOCAL node ids,
    which no row of another block has."""
    F, B, S = n_features, n_bins, len(stats)
    FB = F * B
    PR = piece_rows(lb, S, n_pieces)
    k0 = s * sb
    iota_b = jnp.arange(B, dtype=jnp.int32)
    lane_f = np.arange(FB) // B
    sel = jnp.asarray(lane_f[None, :] == np.arange(F)[:, None],
                      jnp.bfloat16)                           # [F, FB]
    lane_b = jnp.asarray((np.arange(FB) % B)[None, :], jnp.float32)

    def indicator(wd):
        """The chunk's 0/1 (feature, bin) indicator [C, FB], bfloat16.
        As the level kernel builds it (treekernel._hist_block): the
        row's bins expanded across the F·B lanes by a 0/1 selection
        product (exact in bfloat16 for 8-bit bin ids) and ONE compare —
        0.81 s a pass over 48M rows where a compare a feature and a
        concatenation took 1.16 (PERF.md §6, PR 35)."""
        if bits != 8:
            return jnp.concatenate(
                [_unpack(wd, f, bits)[:, None] == iota_b[None, :]
                 for f in range(F)], axis=1).astype(jnp.bfloat16)
        bins_t = jnp.stack([_unpack(wd, f, bits) for f in range(F)]) \
            .astype(jnp.bfloat16)                             # [F, C]
        row_bin = jax.lax.dot_general(
            bins_t, sel, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [C, FB]
        return (row_bin == lane_b).astype(jnp.bfloat16)

    def chunk_hist(at, k):
        def cut(v):
            return jax.lax.dynamic_slice(v, (at,), (chunk,))
        lid = (cut(fid) - k * lb)[None, :]
        return jax.lax.dot_general(
            stat_rows(lid, jnp.stack([cut(v) for v in stats]), lb, S,
                      n_pieces),
            indicator([cut(w) for w in words]),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def block(kk, hist):
        r0, r1 = lo[k0 + kk], hi[k0 + kk]
        a = jax.lax.fori_loop(
            0, (r1 - r0 + chunk - 1) // chunk,
            lambda c, a: a + chunk_hist(r0 + c * chunk, k0 + kk),
            jnp.zeros((PR, FB), jnp.float32))
        return jax.lax.dynamic_update_slice(
            hist, sum_pieces(a, lb, S, n_pieces)[None], (kk, 0, 0))
    hist = jax.lax.fori_loop(0, sb, block,
                             jnp.zeros((sb, S * lb, FB), jnp.float32))
    # [sb, S·lb, FB] rows node·S + stat → [nodes, F, B, S]
    return hist.reshape(sb * lb, S, F, B).transpose(0, 2, 3, 1)


def grow_frontier(bins, nb, nid, stats, alive, key, col_mask, *, params,
                  K: int, sc, mtries: int, is_cat):
    """Levels K..D-1 of one tree from the complete phase's level-K state
    (``nid`` in [0, 2^K); ``alive`` [2^K]: the nodes whose parent split;
    ``stats``: the rows' (w, w·g, w·h), or (w, w·g) where h is 1 and the
    third is the first: the histogram's third column is then a copy of
    its first, not a third of the product. ``params.whole_stats`` says
    every statistic is 0 or ±1, and the operand then carries one
    bfloat16 piece a statistic, not three (ops/histogram.stat_rows).

    A level's histogram pass is the Pallas kernel ``tree_frontier_hist``
    (ops/pallas/treekernel.frontier_hist) where ``params.pallas`` asks
    for kernels and a tile fits, and the XLA chunk product elsewhere.
    The rows are sorted by node on level K and on every
    ``params.frontier_sort_every``-th level after it: a scan over groups
    of that many levels, the sort at the head of a group.

    Returns ``(DeepLevels, ref, gains, capped, scanned)``: ``ref`` [N]
    the flat index (level - K) * Lcap + slot of every row's final node;
    ``capped`` whether a split was refused for want of node slots (never,
    by ``frontier_capacity``; counted all the same); ``scanned`` [2] the
    rows the blocks' ranges held and the live rows, summed over the
    levels (``DeepTree.scanned``)."""
    from h2o3_tpu.models import tree as T
    D, B = params.max_depth, params.nbins_total
    N, F = bins.shape
    lcap = frontier_capacity(N, D)
    C, LB, SB = _geometry(N, lcap)
    SBN = LB * SB
    nlev = D - K
    period = min(params.frontier_sort_every, nlev)
    ns = len(stats)
    tile, pieces = hist_plan(params, N, F, ns)
    W = max(1, (B - 1 + 31) // 32) if params.has_cats else 1
    lam = sc.reg_lambda

    words, bits = pack_bins(bins, B)
    nw = len(words)
    if tile:
        from h2o3_tpu.ops.pallas import treekernel
    elif params.pallas in ("native", "interpret"):
        from h2o3_tpu.ops.pallas import record_fallback
        record_fallback("frontier_fits_no_tile")
    # rows behind the frame's: a chunk cut at any row stays inside, and
    # the kernel's tiles divide the whole
    n_tail = C + (-(N + C)) % max(tile, 1)

    def tail(v, fill):
        return jnp.concatenate([v, jnp.full((n_tail,), fill, v.dtype)])

    # the sort key: a live row's slot in its level (< lcap), a final
    # row's lcap + flat table index of its node, the tail's rows last
    fid = tail(nid.astype(jnp.int32), jnp.iinfo(jnp.int32).max)
    rid = tail(jnp.arange(N, dtype=jnp.int32), N)
    words = tuple(tail(w, 0) for w in words)
    stats = tuple(tail(s.astype(jnp.float32), 0.0) for s in stats)
    slots = jnp.arange(lcap, dtype=jnp.int32)
    path0 = jnp.where(slots < 2 ** K, slots, -1)
    may0 = jnp.zeros((lcap,), bool).at[: 2 ** K].set(alive)
    iota_lb = jnp.arange(LB, dtype=jnp.int32)[:, None]

    def byte_rows(v, n):
        return [((v >> (8 * i)) & 255) for i in range(n)]

    def block_table(t_f, t_t, t_flags, t_ch, t_lw):
        """A block's split tables as byte-valued rows [k, LB], bfloat16
        (a byte is exact there): what a chunk looks its rows' nodes up
        in by ONE one-hot product, where a select chain a table cost
        1.2 s a pass over 48M rows."""
        rows = (byte_rows(t_f, 2) + byte_rows(t_t, 2) + [t_flags]
                + byte_rows(t_ch, 4))
        for j in range(W):
            rows += byte_rows(t_lw[:, j].astype(jnp.int32), 4)
        return jnp.stack(rows).astype(jnp.bfloat16)

    def lookup(table, lid):
        """[k, C] int32: each row's node's bytes (0 outside the block)."""
        onehot = (lid[None, :] == iota_lb).astype(jnp.bfloat16)  # [LB, C]
        return jax.lax.dot_general(
            table, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)

    def level(ordered, carry, d):
        """Level ``d`` on the rows as the group's sort left them:
        ``ordered`` = (the keys then, the bin words, the statistics)."""
        key_at_sort, words, stats = ordered
        fid, anc, path, may, n_live, last = carry
        # the last group may run past the depth: such a level has no
        # live node, its tables are dropped and it hands on what it got
        real = d < D
        n_live = jnp.where(real, n_live, 0)
        with jax.named_scope("tree.frontier.route"):
            lo, hi = block_ranges(key_at_sort, anc, n_live, lb=LB)
            scanned = jnp.stack([
                jnp.sum((hi - lo).astype(jnp.float32)),
                jnp.sum(fid < lcap, dtype=jnp.float32)])
            if tile:
                sched = treekernel.frontier_schedule(
                    lo, hi, tile, fid.shape[0] // tile,
                    range_blocks(period, LB))
        n_sb = (n_live + SBN - 1) // SBN
        heap0 = jnp.left_shift(jnp.int32(1), d)

        def superbatch(s, acc):
            fid_next, tabs, nsplit, gains, capped = acc
            k0 = s * SB
            n0 = k0 * LB

            with jax.named_scope("tree.frontier.hist"):
                if tile:
                    hist = treekernel.frontier_hist(
                        sched, lo, hi, s, fid, words, stats, lb=LB, sb=SB,
                        n_features=F, n_bins=B, bits=bits, n_pieces=pieces,
                        tile=tile, interpret=params.pallas == "interpret")
                else:
                    hist = chunk_product_hist(
                        lo, hi, s, fid, words, stats, lb=LB, sb=SB,
                        chunk=C, n_features=F, n_bins=B, bits=bits,
                        n_pieces=pieces)
                if ns == 2:
                    hist = jnp.concatenate([hist, hist[..., :1]], axis=-1)

            with jax.named_scope("tree.frontier.split"):
                p_sb = jax.lax.dynamic_slice(path, (n0,), (SBN,))
                ok = jax.lax.dynamic_slice(may, (n0,), (SBN,)) & (p_sb >= 0)
                cm = col_mask[None, :] & ok[:, None]
                if 0 < mtries < F:
                    cm = cm & T._mtries_mask(key, heap0 + p_sb, F, mtries)
                bg, bf, bt, bnal, _, _, leftmask = T._best_splits(
                    hist, nb, cm, params, scalars=sc, is_cat=is_cat)
                split = (bg > sc.msi) & ok
                if sc.depth_limit is not None:
                    split = split & (d < sc.depth_limit)
                rank = jnp.cumsum(split.astype(jnp.int32)) - split
                child = 2 * (nsplit + rank)
                fits = child + 1 < lcap
                capped = capped | jnp.any(split & ~fits)
                split = split & fits
                tot = jnp.sum(hist[:, 0], axis=1)                # [SBN, 3]
                hs = jnp.take_along_axis(
                    hist, bf[:, None, None, None], axis=1)[:, 0]  # [., B, 3]
                lsum = jnp.sum(jnp.where(leftmask[:, :, None],
                                         hs[:, : B - 1], 0.0), axis=1) \
                    + jnp.where(bnal[:, None], hs[:, B - 1], 0.0)
                rsum = tot - lsum

                def val(t):
                    return jnp.where(t[:, 0] > 0,
                                     -t[:, 1] / (t[:, 2] + lam + 1e-10), 0.0)
                cs = (is_cat[bf] & split) if is_cat is not None \
                    else jnp.zeros_like(split)
                lw = jnp.where(cs[:, None], T._pack_leftmask(leftmask, W), 0) \
                    if is_cat is not None \
                    else jnp.zeros((SBN, W), jnp.uint32)
                new = dict(
                    feat=jnp.where(split, bf, 0),
                    thresh=jnp.where(split, bt, B),
                    na_left=split & bnal, is_split=split, cat_split=cs,
                    left_words=lw, child=jnp.where(split, child, 0),
                    value=val(tot), weight=tot[:, 0],
                    lval=val(lsum), rval=val(rsum),
                    lwt=lsum[:, 0], rwt=jnp.maximum(rsum[:, 0], 0.0))
                tabs = {n: jax.lax.dynamic_update_slice(
                    tabs[n], new[n], (n0,) + (0,) * (new[n].ndim - 1))
                    for n in tabs}
                gains = gains + jnp.sum(
                    jnp.where(split, jnp.maximum(bg, 0.0), 0.0)[:, None]
                    * (bf[:, None] == jnp.arange(F, dtype=jnp.int32)[None]),
                    axis=0)
                nsplit = nsplit + jnp.sum(split, dtype=jnp.int32)

            with jax.named_scope("tree.frontier.route"):
                done = lcap + (d - K) * lcap     # + slot: a final key

                def block_route(kk, fid_next):
                    k = k0 + kk
                    r0, r1 = lo[k], hi[k]

                    def tab(n):
                        return jax.lax.dynamic_slice(
                            new[n], (kk * LB,) + (0,) * (new[n].ndim - 1),
                            (LB,) + new[n].shape[1:])
                    flags = (tab("is_split").astype(jnp.int32)
                             | (tab("cat_split").astype(jnp.int32) << 1)
                             | (tab("na_left").astype(jnp.int32) << 2))
                    table = block_table(tab("feat"), tab("thresh"), flags,
                                        tab("child"), tab("left_words"))

                    def chunk(c, fid_next):
                        at = r0 + c * C

                        def cut(v):
                            return jax.lax.dynamic_slice(v, (at,), (C,))
                        f_old = cut(fid)
                        lid = f_old - k * LB
                        mine = (lid >= 0) & (lid < LB)
                        got = lookup(table, lid)
                        f_r = got[0] | (got[1] << 8)
                        t_r = got[2] | (got[3] << 8)
                        fl = got[4]
                        ch = (got[5] | (got[6] << 8) | (got[7] << 16)
                              | (got[8] << 24))
                        wd = [cut(w) for w in words]
                        b_r = jnp.zeros((C,), jnp.int32)
                        for f in range(F):
                            b_r = jnp.where(f_r == f, _unpack(wd, f, bits),
                                            b_r)
                        word = jnp.zeros((C,), jnp.int32)
                        for j in range(W):
                            at_w = 9 + 4 * j
                            w_j = (got[at_w] | (got[at_w + 1] << 8)
                                   | (got[at_w + 2] << 16)
                                   | (got[at_w + 3] << 24))
                            word = jnp.where((b_r >> 5) == j, w_j, word)
                        inset = ((word >> (b_r & 31)) & 1) == 1
                        go = jnp.where((fl & 2) == 2, inset, b_r <= t_r)
                        go = jnp.where(b_r == B - 1, (fl & 4) == 4, go)
                        f_new = jnp.where(
                            (fl & 1) == 1, ch + jnp.where(go, 0, 1),
                            done + f_old)
                        # a row of another block's keeps what that
                        # block's pass wrote, or will
                        return jax.lax.dynamic_update_slice(
                            fid_next,
                            jnp.where(mine, f_new, cut(fid_next)), (at,))
                    return jax.lax.fori_loop(0, (r1 - r0 + C - 1) // C,
                                             chunk, fid_next)
                fid_next = jax.lax.fori_loop(0, SB, block_route, fid_next)
            return fid_next, tabs, nsplit, gains, capped

        tabs0 = dict(
            feat=jnp.zeros((lcap,), jnp.int32),
            thresh=jnp.full((lcap,), B, jnp.int32),
            na_left=jnp.zeros((lcap,), bool),
            is_split=jnp.zeros((lcap,), bool),
            cat_split=jnp.zeros((lcap,), bool),
            left_words=jnp.zeros((lcap, W), jnp.uint32),
            child=jnp.zeros((lcap,), jnp.int32),
            value=jnp.zeros((lcap,), jnp.float32),
            weight=jnp.zeros((lcap,), jnp.float32),
            lval=jnp.zeros((lcap,), jnp.float32),
            rval=jnp.zeros((lcap,), jnp.float32),
            lwt=jnp.zeros((lcap,), jnp.float32),
            rwt=jnp.zeros((lcap,), jnp.float32))
        # the next level's keys start as this level's: a final row
        # keeps its key
        fid_next, tabs, nsplit, gains, capped = jax.lax.fori_loop(
            0, n_sb, superbatch,
            (fid, tabs0, jnp.int32(0), jnp.zeros((F,), jnp.float32),
             jnp.bool_(False)))

        # the next level's nodes: children in the order their parents
        # split; a child's path id is its parent's with one more bit
        sp = tabs["is_split"]
        at_l = jnp.where(sp, tabs["child"], lcap)

        def to_children(left, right, fill):
            return jnp.full((lcap,), fill, left.dtype) \
                .at[at_l].set(left, mode="drop") \
                .at[at_l + 1].set(right, mode="drop")
        nxt_path = to_children(2 * path, 2 * path + 1, -1)
        nxt = dict(value=to_children(tabs["lval"], tabs["rval"], 0.0),
                   weight=to_children(tabs["lwt"], tabs["rwt"], 0.0))
        level_out = {n: tabs[n] for n in DeepLevels._fields if n != "path"}
        level_out["path"] = path
        nodes = jax.tree.map(
            lambda new, old: jnp.where(real, new, old),
            (to_children(anc, anc, 0), nxt_path, nxt_path >= 0, 2 * nsplit,
             nxt), (anc, path, may, n_live, last))
        return (fid_next,) + nodes, (level_out, gains, capped, scanned)

    def group(carry, d0):
        """``period`` levels from ``d0`` on ONE order of the rows: the
        program's one sort of the rows (it alone compiles for minutes)
        is the head of the group, the level's body its loop."""
        fid, rid, words, stats, *nodes = carry
        with jax.named_scope("tree.frontier.route"):
            rows = jax.lax.sort((fid, rid) + words + stats, num_keys=1,
                                is_stable=False)
        fid, rid = rows[:2]
        words, stats = tuple(rows[2:2 + nw]), tuple(rows[2 + nw:])
        (fid, _, *nodes), out = jax.lax.scan(
            partial(level, (fid, words, stats)), (fid, slots, *nodes),
            d0 + jnp.arange(period, dtype=jnp.int32))
        return (fid, rid, words, stats, *nodes), out

    last0 = dict(value=jnp.zeros((lcap,), jnp.float32),
                 weight=jnp.zeros((lcap,), jnp.float32))
    carry, out = jax.lax.scan(
        group, (fid, rid, words, stats, path0, may0, jnp.int32(2 ** K),
                last0),
        K + period * jnp.arange(sort_levels(nlev, period), dtype=jnp.int32))
    # [groups, period, ...] → the tree's levels
    levels, gains, capped, scanned = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:nlev], out)
    fid, rid, _, _, path_d, _, _, last = carry

    # level D: leaves alone, valued from their parents' split sums
    zeros = jnp.zeros((1, lcap), jnp.int32)
    deep = DeepLevels(
        feat=jnp.concatenate([levels["feat"], zeros]),
        thresh=jnp.concatenate([levels["thresh"], zeros + B]),
        na_left=jnp.concatenate([levels["na_left"], zeros > 0]),
        is_split=jnp.concatenate([levels["is_split"], zeros > 0]),
        cat_split=jnp.concatenate([levels["cat_split"], zeros > 0]),
        left_words=jnp.concatenate(
            [levels["left_words"], jnp.zeros((1, lcap, W), jnp.uint32)]),
        child=jnp.concatenate([levels["child"], zeros]),
        value=jnp.concatenate([levels["value"], last["value"][None]]),
        weight=jnp.concatenate([levels["weight"], last["weight"][None]]),
        path=jnp.concatenate([levels["path"], path_d[None]]))
    with jax.named_scope("tree.frontier.route"):
        ref = jnp.where(fid < lcap, nlev * lcap + fid, fid - lcap)
        # back to the frame's row order
        ref = jnp.zeros((N,), jnp.int32).at[rid].set(ref, mode="drop")
    return (deep, ref, jnp.sum(gains, axis=0), jnp.any(capped),
            jnp.sum(scanned, axis=0))


def route_deep(tree: DeepTree, bins, B: int):
    """Flat index (level - K) * Lcap + slot of every row's final node:
    the complete levels by ``tree._level_goleft``, the node tables by
    their child indices."""
    from h2o3_tpu.models import tree as T
    top, deep = tree.top, tree.deep
    K = top.feat.shape[0]
    nlev, lcap = deep.feat.shape[0] - 1, deep.feat.shape[1]
    W = deep.left_words.shape[2]
    nid = jnp.zeros((bins.shape[0],), jnp.int32)
    for d in range(K):
        with jax.named_scope("forest.level"):
            nid = T._level_goleft(top.feat[d], top.thresh[d], top.na_left[d],
                                  top.is_split[d], top.cat_split[d],
                                  top.left_words[d], nid, bins, B, d)
    done = jnp.zeros(nid.shape, bool)
    ref = nid
    for j in range(nlev):
        with jax.named_scope("forest.level"):
            f_r = deep.feat[j][nid]
            b_r = T.row_feature_values(bins, f_r).astype(jnp.int32)
            word = deep.left_words[j].reshape(-1)[
                nid * W + jnp.minimum(b_r >> 5, W - 1)]
            inset = ((word >> (b_r & 31).astype(jnp.uint32)) & 1) == 1
            go = jnp.where(deep.cat_split[j][nid], inset,
                           b_r <= deep.thresh[j][nid])
            go = jnp.where(b_r == B - 1, deep.na_left[j][nid], go)
            sp = deep.is_split[j][nid] & ~done
            nxt = deep.child[j][nid] + jnp.where(go, 0, 1)
            ref = jnp.where(sp, (j + 1) * lcap + nxt, ref)
            done = done | ~sp
            nid = jnp.where(sp, nxt, nid)
    return ref


def predict_deep(tree: DeepTree, bins, B: int):
    return tree.deep.value.reshape(-1)[route_deep(tree, bins, B)]


def forest_facts(forest) -> dict:
    """Counted facts of a stacked forest, on the device: ``depth`` (the
    deepest split's level + 1), ``leaves`` (all trees), ``nodes_max``
    (the widest frontier level's live nodes; 0 for a complete forest),
    ``capped`` (trees that were refused a split for want of slots),
    ``rescan_pct`` (of the rows the frontier levels' blocks read, the
    share beyond the live rows: rows that lie in two blocks' ranges
    between sorts, and final rows still lying among the live ones)."""
    def deepest(is_split):                  # [T, levels, L] → levels + 1
        lv = jnp.any(is_split, axis=(0, 2))
        return jnp.max(jnp.where(
            lv, jnp.arange(lv.shape[0], dtype=jnp.int32) + 1, 0))
    if not isinstance(forest, DeepTree):
        return dict(depth=deepest(forest.is_split),
                    leaves=jnp.sum(forest.leaf_w > 0, dtype=jnp.int32),
                    nodes_max=jnp.int32(0), capped=jnp.int32(0),
                    rescan_pct=jnp.float32(0))
    deep = forest.deep
    K = forest.top.feat.shape[1]
    live = (deep.path >= 0) & (deep.weight > 0)
    d_deep = deepest(deep.is_split)
    return dict(
        depth=jnp.where(d_deep > 0, K + d_deep,
                        deepest(forest.top.is_split)),
        leaves=jnp.sum(live & ~deep.is_split, dtype=jnp.int32),
        nodes_max=jnp.max(jnp.sum(live, axis=2, dtype=jnp.int32)),
        capped=jnp.sum(forest.capped, dtype=jnp.int32),
        rescan_pct=100.0 * jnp.sum(forest.scanned[:, 0])
        / jnp.maximum(jnp.sum(forest.scanned[:, 1]), 1.0) - 100.0)


def realized_depth(forest) -> int:
    """Levels of the deepest tree of a stacked ``DeepTree`` forest: its
    deepest split's level + 1."""
    K = forest.top.feat.shape[1]
    deep = np.asarray(forest.deep.is_split).any(axis=(0, 2))
    if deep.any():
        return K + int(np.nonzero(deep)[0].max()) + 1
    top = np.asarray(forest.top.is_split).any(axis=(0, 2))
    return int(np.nonzero(top)[0].max()) + 1 if top.any() else 0


def to_complete(forest, depth: int, n_bins: int):
    """A stacked ``DeepTree`` forest of realized depth ≤ ``depth`` as the
    complete ``tree.Tree`` its readers know ([T, depth, 2^(depth-1)]):
    every node goes to its path's slot, a leaf's value to the slot its
    rows reach by going left from there. On the host, in numpy."""
    from h2o3_tpu.models import tree as T
    top = [np.asarray(a) for a in forest.top]
    deep = DeepLevels(*(np.asarray(a) for a in forest.deep))
    n_trees, K = top[0].shape[:2]
    nlev = deep.feat.shape[1]
    Lmax = 2 ** max(depth - 1, 0)
    W = deep.left_words.shape[-1]
    out = dict(feat=np.zeros((n_trees, depth, Lmax), np.int32),
               thresh=np.full((n_trees, depth, Lmax), n_bins, np.int32),
               na_left=np.zeros((n_trees, depth, Lmax), bool),
               is_split=np.zeros((n_trees, depth, Lmax), bool),
               cat_split=np.zeros((n_trees, depth, Lmax), bool),
               left_words=np.zeros((n_trees, depth, Lmax, W), np.uint32),
               leaf=np.zeros((n_trees, 2 ** depth), np.float32),
               leaf_w=np.zeros((n_trees, 2 ** depth), np.float32))
    kt = min(K, depth)
    w_top = min(top[0].shape[2], Lmax)
    for name, a in zip(T.Tree._fields, top):
        if name not in ("leaf", "leaf_w"):
            out[name][:, :kt, :w_top] = a[:, :kt, :w_top]
    for j in range(nlev):
        d = K + j
        t_i, s_i = np.nonzero(deep.path[:, j] >= 0)
        p = deep.path[t_i, j, s_i]
        sp = deep.is_split[t_i, j, s_i]
        if d < depth:
            for name in ("feat", "thresh", "na_left", "is_split",
                         "cat_split", "left_words"):
                out[name][t_i, d, p] = getattr(deep, name)[t_i, j, s_i]
        elif sp.any():
            raise DeepForestError(
                f"a split at level {d} does not fit a complete tree of "
                f"depth {depth}")
        leaf = ~sp
        if d <= depth:
            slot = p[leaf] << (depth - d)
            out["leaf"][t_i[leaf], slot] = deep.value[t_i, j, s_i][leaf]
            out["leaf_w"][t_i[leaf], slot] = deep.weight[t_i, j, s_i][leaf]
    return T.Tree(**{n: jnp.asarray(out[n]) for n in T.Tree._fields})
