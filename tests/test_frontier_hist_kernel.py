"""The frontier levels' histogram pass as a Pallas kernel (PR 36):
``treekernel.frontier_hist`` against the XLA chunk product it replaces
(``frontier.chunk_product_hist``), in interpret mode, on a schedule as
ragged as a level's can be; the statistics operand at any number of
statistics and pieces; the tile's arithmetic. The blocks' row ranges on
a level that sorted its rows and on one that did not (PR 38:
``frontier.block_ranges``), and the kernel on ranges that overlap. Tiny
shapes only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.models import frontier
from h2o3_tpu.ops import histogram as H
from h2o3_tpu.ops import pallas as plx
from h2o3_tpu.ops.pallas import treekernel as tk

LB, SB, TILE, CHUNK = 8, 4, 128, 64
LCAP = 96                     # 12 blocks of 8 nodes: 3 super-batches
# rows a block. Block 0 is empty; block 1 spans the tiles 0, 1 and 2;
# tile 2 (rows 256..383) holds rows of the blocks 1 to 5; the blocks 10
# and 11 are not live, so the last super-batch is not full
SIZES = (0, 300, 20, 25, 15, 30, 10, 140, 60, 33, 0, 0)
N_FINAL = 200                 # rows that stopped above: keys >= LCAP


def _level(F, B, n_stats, whole, seed=0):
    """A sorted frontier level: (fid, words, bits, stats, blk_start,
    bins) with CHUNK rows of tail, a multiple of TILE in all."""
    r = np.random.default_rng(seed)
    fid = np.concatenate(
        [np.sort(r.integers(k * LB, (k + 1) * LB, size=n))
         for k, n in enumerate(SIZES)]
        + [np.sort(LCAP + r.integers(0, 50, N_FINAL))])
    n = len(fid) + CHUNK
    n += -n % TILE
    fid = np.concatenate([fid, np.full(n - len(fid), np.iinfo(np.int32).max)
                          ]).astype(np.int32)
    bins = r.integers(0, B, (n, F)).astype(np.int32)
    if whole:           # 0 or ±1: one bfloat16 piece holds a statistic
        stats = [r.integers(-1, 2, n).astype(np.float32)
                 for _ in range(n_stats)]
    else:               # 18 bits: three pieces, and every sum still exact
        stats = [r.integers(-(1 << 18), 1 << 18, n).astype(np.float32)
                 for _ in range(n_stats)]
    words, bits = frontier.pack_bins(jnp.asarray(bins), B)
    blk_start = np.searchsorted(
        fid, np.arange(LCAP // LB + 1) * LB).astype(np.int32)
    return (jnp.asarray(fid), words, bits,
            tuple(jnp.asarray(v) for v in stats), jnp.asarray(blk_start),
            bins)


def _by_hand(fid, bins, stats, s, B):
    out = np.zeros((SB * LB, bins.shape[1], B, len(stats)), np.float64)
    for i in np.nonzero((fid >= s * SB * LB) & (fid < (s + 1) * SB * LB))[0]:
        for f in range(bins.shape[1]):
            out[fid[i] - s * SB * LB, f, bins[i, f]] += [v[i] for v in stats]
    return out.astype(np.float32)


def test_the_schedule_is_as_ragged_as_meant():
    _, _, _, _, blk_start, _ = _level(3, 5, 2, True)
    step0, blk, tid = (np.asarray(a) for a in tk.frontier_schedule(
        blk_start[:-1], blk_start[1:], TILE, 1024 // TILE))
    steps = step0[-1]
    assert list(np.diff(step0)[:3]) == [1, 3, 1]      # empty; three tiles
    assert sorted(set(blk[:steps][tid[:steps] == 2])) == [1, 2, 3, 4, 5]
    assert list(np.diff(step0)[-2:]) == [1, 1]        # not live: zeroed
    # a block's steps are consecutive and its tiles ascend
    assert (np.diff(blk[:steps]) >= 0).all()
    assert all((np.diff(tid[:steps][blk[:steps] == k]) == 1).all()
               for k in range(12))
    # final rows get no step of their own: the last tile visited is the
    # one the last live row lies in
    assert tid[:steps].max() == (int(blk_start[-1]) - 1) // TILE


@pytest.mark.parametrize("n_pieces", [1, 3])
@pytest.mark.parametrize("n_stats", [2, 3])
def test_the_kernel_is_the_chunk_product_bit_for_bit(n_stats, n_pieces):
    F, B = 5, 21
    fid, words, bits, stats, blk_start, bins = _level(
        F, B, n_stats, whole=n_pieces == 1)
    lo, hi = blk_start[:-1], blk_start[1:]
    sched = tk.frontier_schedule(lo, hi, TILE, fid.shape[0] // TILE)
    geo = dict(lb=LB, sb=SB, n_features=F, n_bins=B, bits=bits,
               n_pieces=n_pieces)
    kernel = jax.jit(lambda s, fid: tk.frontier_hist(
        sched, lo, hi, s, fid, words, stats, tile=TILE, interpret=True,
        **geo))
    xla = jax.jit(lambda s, fid: frontier.chunk_product_hist(
        lo, hi, s, fid, words, stats, chunk=CHUNK, **geo))
    for s in range(3):
        # a row outside a block's range counts for nothing there,
        # whatever node its key names
        routed = jnp.where(jnp.arange(fid.shape[0]) < blk_start[s * SB],
                           s * SB * LB + 1, fid)
        got = np.asarray(kernel(jnp.int32(s), routed))
        assert got.shape == (SB * LB, F, B, n_stats)
        assert np.array_equal(got, np.asarray(xla(jnp.int32(s), routed)))
        assert np.array_equal(got, _by_hand(np.asarray(fid), bins,
                                            [np.asarray(v) for v in stats],
                                            s, B))
    assert np.asarray(kernel(jnp.int32(2), fid))[2 * LB:].max() == 0.0


def test_sixteen_bit_bin_ids():
    F, B = 3, 300
    fid, words, bits, stats, blk_start, bins = _level(F, B, 2, True, seed=1)
    assert bits == 16 and len(words) == 2
    lo, hi = blk_start[:-1], blk_start[1:]
    sched = tk.frontier_schedule(lo, hi, TILE, fid.shape[0] // TILE)
    got = jax.jit(lambda: tk.frontier_hist(
        sched, lo, hi, jnp.int32(1), fid, words, stats, lb=LB, sb=SB,
        n_features=F, n_bins=B, bits=bits, n_pieces=1, tile=TILE,
        interpret=True))()
    assert np.array_equal(np.asarray(got), _by_hand(
        np.asarray(fid), bins, [np.asarray(v) for v in stats], 1, B))


def _grown_on(fid, n_live, levels, seed=0):
    """``levels`` levels of growth from a sorted level without moving a
    row, in numpy: a slot splits nine times in ten, its rows go left
    or right by a coin, the children are numbered in the order their
    parents split (frontier.grow_frontier). → (fid, anc, n_live)."""
    r = np.random.default_rng(seed)
    fid, anc = fid.copy(), np.arange(LCAP, dtype=np.int32)
    for _ in range(levels):
        split = r.random(n_live) < 0.9
        child = 2 * (np.cumsum(split) - split)
        live = np.nonzero(fid < LCAP)[0]
        slot = fid[live]
        fid[live] = np.where(split[slot],
                             child[slot] + r.integers(0, 2, len(live)),
                             LCAP + slot)
        nxt = np.zeros(LCAP, np.int32)
        nxt[child[split]] = nxt[child[split] + 1] = anc[:n_live][split]
        anc, n_live = nxt, 2 * int(split.sum())
    return fid, anc, n_live


def test_block_ranges_on_a_level_that_sorted_are_the_blocks_own_rows():
    fid, _, _, _, blk_start, _ = _level(3, 5, 2, True)
    lo, hi = frontier.block_ranges(
        fid, jnp.arange(LCAP, dtype=jnp.int32), jnp.int32(10 * LB), lb=LB)
    assert np.array_equal(np.asarray(lo), np.asarray(blk_start[:-1]))
    assert np.array_equal(np.asarray(hi), np.asarray(blk_start[1:]))
    # a last live block that is not full ends with its last live slot
    lo, hi = frontier.block_ranges(
        fid, jnp.arange(LCAP, dtype=jnp.int32), jnp.int32(9 * LB + 3), lb=LB)
    assert int(hi[9]) == np.searchsorted(np.asarray(fid), 9 * LB + 3) \
        == int(lo[10]) == int(hi[11])


# twelve nodes at the sort (rows a node; the fifth holds none), grown
# three levels on without a sort: up to 96 slots, eight to an ancestor,
# so an ancestor's slots can lie in two blocks of eight
PARENTS = (40, 130, 7, 260, 0, 90, 55, 31, 170, 12, 66, 101)


def _stale_level(F, B, n_stats, whole, seed=0):
    r = np.random.default_rng(seed)
    key = np.concatenate(
        [np.repeat(np.arange(len(PARENTS)), PARENTS),
         np.sort(LCAP + r.integers(0, 50, N_FINAL))])
    n = len(key) + CHUNK
    n += -n % TILE
    key = np.concatenate([key, np.full(n - len(key), np.iinfo(np.int32).max)
                          ]).astype(np.int32)
    fid, anc, n_live = _grown_on(key, len(PARENTS), 3, seed)
    bins = r.integers(0, B, (n, F)).astype(np.int32)
    span = (-1, 2) if whole else (-(1 << 18), 1 << 18)
    stats = tuple(jnp.asarray(r.integers(*span, n).astype(np.float32))
                  for _ in range(n_stats))
    words, bits = frontier.pack_bins(jnp.asarray(bins), B)
    lo, hi = frontier.block_ranges(jnp.asarray(key), jnp.asarray(anc),
                                   jnp.int32(n_live), lb=LB)
    return key, fid, anc, n_live, lo, hi, words, bits, stats, bins


def test_block_ranges_between_sorts_hold_the_blocks_rows():
    key, fid, anc, n_live, lo, hi, *_ = _stale_level(3, 5, 2, True)
    lo, hi = np.asarray(lo), np.asarray(hi)
    live_blocks = -(-n_live // LB)
    assert 4 <= live_blocks < LCAP // LB
    shared = 0
    for k in range(live_blocks):
        at = np.nonzero((fid >= k * LB) & (fid < (k + 1) * LB))[0]
        assert len(at) and lo[k] <= at.min() and at.max() < hi[k]
        if k + 1 < live_blocks:
            last, first = anc[min((k + 1) * LB, n_live) - 1], anc[(k + 1) * LB]
            if last == first:       # one ancestor, slots in both blocks
                shared += 1
                assert hi[k] - lo[k + 1] == PARENTS[last] > 0
            else:                   # final ancestors' rows may lie between
                assert hi[k] <= lo[k + 1]
    assert shared >= 2
    # a block with no live slot: an empty range, and one step to zero it
    assert (lo[live_blocks:] == hi[live_blocks:]).all()
    n_tiles = len(fid) // TILE
    step0, blk, _ = (np.asarray(a) for a in tk.frontier_schedule(
        jnp.asarray(lo), jnp.asarray(hi), TILE, n_tiles,
        frontier.range_blocks(4, LB)))
    assert (np.diff(step0)[live_blocks:] == 1).all()
    assert frontier.range_blocks(4, LB) == 2 and step0[-1] <= len(blk)
    # rows lie in two ranges, final rows among them: more than the live
    assert (hi - lo).sum() > (fid < LCAP).sum()


@pytest.mark.parametrize("n_stats,n_pieces", [(2, 1), (3, 3)])
def test_the_kernel_is_the_chunk_product_on_ranges_that_overlap(
        n_stats, n_pieces):
    F, B = 5, 21
    _, fid, _, n_live, lo, hi, words, bits, stats, bins = _stale_level(
        F, B, n_stats, whole=n_pieces == 1, seed=2)
    fid_d = jnp.asarray(fid)
    sched = tk.frontier_schedule(lo, hi, TILE, len(fid) // TILE,
                                 frontier.range_blocks(4, LB))
    geo = dict(lb=LB, sb=SB, n_features=F, n_bins=B, bits=bits,
               n_pieces=n_pieces)
    kernel = jax.jit(lambda s: tk.frontier_hist(
        sched, lo, hi, s, fid_d, words, stats, tile=TILE, interpret=True,
        **geo))
    xla = jax.jit(lambda s: frontier.chunk_product_hist(
        lo, hi, s, fid_d, words, stats, chunk=CHUNK, **geo))
    assert n_live > 2 * SB * LB         # three super-batches hold nodes
    assert (np.asarray(hi)[:-1] > np.asarray(lo)[1:]).any()
    for s in range(3):
        got = np.asarray(kernel(jnp.int32(s)))
        assert np.array_equal(got, np.asarray(xla(jnp.int32(s))))
        assert np.array_equal(got, _by_hand(
            fid, bins, [np.asarray(v) for v in stats], s, B))


def _stat_rows_of_pr35(nid, stats, n_nodes):
    """ops/histogram.stat_rows as it stood before it took the numbers of
    statistics and pieces."""
    L3 = 3 * n_nodes
    k = jax.lax.broadcasted_iota(jnp.int32, (-(-9 * n_nodes // 16) * 16, 1), 0)
    piece = k // L3
    rem = k - piece * L3
    node = rem // 3
    stat = rem - 3 * node

    def of_stat(x):
        return jnp.where(stat == 0, x[0:1],
                         jnp.where(stat == 1, x[1:2], x[2:3]))
    hi, mid, lo = H.split3(stats)
    val = jnp.where(piece == 0, of_stat(hi),
                    jnp.where(piece == 1, of_stat(mid), of_stat(lo)))
    hit = (nid == node) & (piece < 3)
    return jnp.where(hit, val, 0.0).astype(jnp.bfloat16)


@pytest.mark.parametrize("n_nodes", [1, 5, 64])
def test_the_default_operand_is_todays(n_nodes):
    r = np.random.default_rng(n_nodes)
    nid = jnp.asarray(r.integers(-1, n_nodes + 1, (1, 200)), jnp.int32)
    stats = r.normal(size=(3, 200)).astype(np.float32)
    stats[1, 7] = np.nan
    was = np.asarray(_stat_rows_of_pr35(nid, jnp.asarray(stats), n_nodes)
                     ).view(np.uint16)
    now = np.asarray(H.stat_rows(nid, jnp.asarray(stats), n_nodes)
                     ).view(np.uint16)
    assert now.shape == (H.piece_rows(n_nodes), 200) and \
        H.piece_rows(n_nodes) == -(-9 * n_nodes // 16) * 16
    assert np.array_equal(was, now)
    acc = jnp.asarray(r.normal(size=(H.piece_rows(n_nodes), 7)), jnp.float32)
    L3 = 3 * n_nodes
    assert np.array_equal(
        np.asarray(H.sum_pieces(acc, n_nodes)),
        np.asarray(acc[:L3] + acc[L3:2 * L3] + acc[2 * L3:3 * L3]))


@pytest.mark.parametrize("n_stats,n_pieces", [(2, 3), (2, 1), (3, 1)])
def test_a_shorter_operand_drops_rows_and_nothing_else(n_stats, n_pieces):
    """Row p·S·L + S·node + s of the short operand is row p·3·L + 3·node
    + s of the full one."""
    L = 6
    r = np.random.default_rng(3)
    nid = jnp.asarray(r.integers(0, L, (1, 50)), jnp.int32)
    stats = jnp.asarray(r.normal(size=(3, 50)), jnp.float32)
    full = np.asarray(H.stat_rows(nid, stats, L)).astype(np.float32)
    short = np.asarray(H.stat_rows(nid, stats[:n_stats], L, n_stats,
                                   n_pieces)).astype(np.float32)
    assert short.shape[0] == H.piece_rows(L, n_stats, n_pieces)
    for p in range(n_pieces):
        for node in range(L):
            for st in range(n_stats):
                assert np.array_equal(
                    short[p * n_stats * L + n_stats * node + st],
                    full[p * 3 * L + 3 * node + st])
    assert not short[n_pieces * n_stats * L:].any()


@pytest.mark.parametrize("operand_rows", [576, 384, 128])
def test_the_tile_fits_the_budget_at_the_cells_widths(operand_rows):
    F, B = 10, 126                       # drf-airlines-d20, 64 nodes a block
    n_inputs = 1 + 3 + (3 if operand_rows == 576 else 2)
    assert operand_rows in (H.piece_rows(64), H.piece_rows(64, 2, 3),
                            H.piece_rows(64, 2, 1))
    tile = plx.frontier_tile_rows(F, B, operand_rows, n_inputs)
    fixed, row = plx.frontier_tile_bytes(F, B, operand_rows, n_inputs)
    assert tile in (1024, 2048) and tile & (tile - 1) == 0
    assert fixed + tile * row <= plx.VMEM_BUDGET_BYTES
    assert tile == 2048 or fixed + 2 * tile * row > plx.VMEM_BUDGET_BYTES
    # the accumulator alone can outgrow the budget: no tile, XLA runs
    assert plx.frontier_tile_rows(F, B, 1 << 13, n_inputs) == 0
    assert plx.frontier_tile_rows(F, B, operand_rows, n_inputs,
                                  budget_bytes=fixed + 127 * row) == 0
