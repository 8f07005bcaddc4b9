"""Distributed (node, feature, bin) histogram — THE hot loop of tree building.

Reference: hex/tree/DHistogram.java:585-674 ``updateHisto`` accumulates
{w, wY, wYY} per (leaf, col, bin) with scalar adds inside an MRTask;
reduce = elementwise histogram add up the thread/node trees
(hex/tree/ScoreBuildHistogram2.java:62).

TPU-native: scatter-add is MXU-hostile, so the accumulation is recast as
two matmuls per row-block (SURVEY §7 "hard parts" #1):

    left  [9L, C] = one_hot(node) ⊗ pieces([w, g, h])   (C = block rows)
    right [C, FB] = one_hot(feature-bin)                (0/1)
    acc  += left @ right                                → [9L, FB]

Both operands are bfloat16 and every sum is a float32 sum: the one-hot
is exact in bfloat16, and each float32 statistic enters as three
bfloat16 pieces that add up to it bit for bit (``split3``), so one MXU
pass a piece multiplies exactly and accumulates in float32; the three
[3L, FB] slabs are added at the end (``sum_pieces``). The same code runs
on every backend and at every size.

The contraction over C rows runs on the systolic array; ``lax.scan`` over
row blocks bounds memory (the F/J chunk loop analogue); ``psum`` over the
'data' mesh axis is the cross-node reduce tree (water/MRTask.java:891).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

# A standalone Pallas histogram kernel exists (ops/pallas_histogram) but
# measures ~2x slower than the XLA formulation on v5e (the one-hot
# construction is VPU-bound either way, and XLA fuses it into the matmul
# at larger row blocks than fit VMEM). Opt in with H2O3_TPU_PALLAS_HIST=1
# — read ONCE at import: histogram() only runs at trace time inside
# jit-cached programs, so a mid-process toggle could never take effect
# anyway. The FUSED tree kernels (ops/pallas/treekernel.py, knob
# H2O3TPU_PALLAS) supersede it for the grow_tree level loop by folding
# the split scan and row partition into the same pass — this module
# stays the always-available XLA fallback and the non-tree histogram
# entry point.
import os as _os
_USE_PALLAS_FLAG = _os.environ.get("H2O3_TPU_PALLAS_HIST") == "1"


def split3(v):
    """A float32 array as three float32 arrays that each hold a bfloat16
    value (8 significand bits) and add up to ``v`` bit for bit: the
    leading 8 bits, the next 8, the last 8. The MXU multiplies bfloat16
    operands exactly and adds in float32, so three one-pass products
    of the pieces with a 0/1 operand, added, are the float32 sum.

    The bits are masked, not rounded through ``astype``: a compiler
    that is allowed excess precision may drop a float32 → bfloat16 →
    float32 round trip, and the split would then silently be no split.
    Subnormal pieces may flush to zero; inf and NaN do not survive
    (they poison a one-hot product anyway: 0 * inf)."""
    def top(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                            jnp.float32)
    v = v.astype(jnp.float32)
    hi = top(v)
    rest = v - hi
    mid = top(rest)
    return hi, mid, rest - mid


def piece_rows(n_nodes: int, n_stats: int = 3, n_pieces: int = 3) -> int:
    """Rows of ``stat_rows``' block: ``n_pieces`` pieces x ``n_nodes`` x
    ``n_stats`` stats, up to the 16 sublanes of a bfloat16 tile."""
    return -(-n_pieces * n_stats * n_nodes // 16) * 16


def stat_rows(nid, stats, n_nodes: int, n_stats: int = 3, n_pieces: int = 3):
    """The statistics operand of the histogram product, rows on the
    lanes: ``nid`` [1, C] int32 and ``stats`` [n_stats, C] float32 ({w,
    w·g, w·h}) → bfloat16 [piece_rows(n_nodes, ..), C]. With S stats and
    L nodes, row ``p·S·L + S·node + s`` holds piece ``p`` (``split3``)
    of stat ``s`` where the row's node is ``node``, else 0. One function
    for the XLA path and for the body of the Pallas kernels (only what
    Mosaic lowers: iota, compare, select, bit masks), so both feed the
    MXU the same operand.

    ``n_stats`` 2 is for a caller whose third statistic IS its first (a
    hessian of 1) and who copies the finished column; ``n_pieces`` 1 for
    one who KNOWS every statistic is a bfloat16 value already (0, ±1:
    whole weights on a class indicator), so that the pieces left out are
    identically zero. Either way no sum changes.

    The stat is SELECTED into its rows (never a masked add): a NaN stat
    must not bleed into its siblings' rows the way 0*NaN would."""
    LS = n_stats * n_nodes
    k = jax.lax.broadcasted_iota(
        jnp.int32, (piece_rows(n_nodes, n_stats, n_pieces), 1), 0)
    piece = k // LS
    rem = k - piece * LS
    node = rem // n_stats
    stat = rem - n_stats * node

    def pick(which, rows):           # rows[j] where which == j, last else
        out = rows[-1]
        for j in range(len(rows) - 2, -1, -1):
            out = jnp.where(which == j, rows[j], out)
        return out

    def of_stat(x):                                          # [S, C] -> [M, C]
        return pick(stat, [x[s:s + 1] for s in range(n_stats)])

    val = pick(piece, [of_stat(p) for p in split3(stats)[:n_pieces]])
    hit = (nid == node) & (piece < n_pieces)
    return jnp.where(hit, val, 0.0).astype(jnp.bfloat16)


def sum_pieces(acc, n_nodes: int, n_stats: int = 3, n_pieces: int = 3):
    """[piece_rows(n_nodes, ..), FB] products of ``stat_rows`` → the
    float32 sums [n_stats · L, FB]."""
    LS = n_stats * n_nodes
    out = acc[:LS]
    for p in range(1, n_pieces):
        out = out + acc[p * LS:(p + 1) * LS]
    return out


def _block_hist(bins_blk, nid_blk, stats_blk, n_nodes: int, n_bins: int):
    """One row-block's [piece_rows, FB] partial products via MXU matmul:
    ``bins_blk`` [C, F], ``nid_blk`` [1, C], ``stats_blk`` [3, C]."""
    C, F = bins_blk.shape
    # right: 0/1 indicator of (feature, bin) per row — exact in bf16;
    # left: the float32 stats as bf16 pieces, so one pass is exact
    onehot_fb = (bins_blk[:, :, None] ==
                 jnp.arange(n_bins, dtype=jnp.int32)[None, None, :])
    right = onehot_fb.reshape(C, F * n_bins).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        stat_rows(nid_blk, stats_blk, n_nodes), right,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _local_histogram(bins, nid, stats, n_nodes: int, n_bins: int,
                     block_rows: int):
    """Scan row blocks of one shard (``bins`` [N, F], ``nid`` [N],
    ``stats`` [3, N]), accumulating the [L,F,B,3] histogram."""
    N, F = bins.shape
    C = min(block_rows, N)
    nblk = (N + C - 1) // C
    Npad = nblk * C
    if Npad != N:
        # padding rows carry zero stats so they contribute nothing
        bins = jnp.pad(bins, ((0, Npad - N), (0, 0)))
        nid = jnp.pad(nid, (0, Npad - N))
        stats = jnp.pad(stats, ((0, 0), (0, Npad - N)))
    bins_b = bins.reshape(nblk, C, F)
    nid_b = nid.reshape(nblk, 1, C)
    stats_b = stats.reshape(3, nblk, C).transpose(1, 0, 2)

    def step(acc, xs):
        b, n, s = xs
        return acc + _block_hist(b, n, s, n_nodes, n_bins), None

    init = jnp.zeros((piece_rows(n_nodes), F * n_bins), jnp.float32)
    acc, _ = jax.lax.scan(step, init, (bins_b, nid_b, stats_b))
    # [3L, FB] -> [L, F, B, 3]
    return sum_pieces(acc, n_nodes).reshape(
        n_nodes, 3, F, n_bins).transpose(0, 2, 3, 1)


def histogram(bins, nid, w, g, h, *, n_nodes: int, n_bins: int,
              mesh, block_rows: int = 16384):
    """All-reduced histogram [n_nodes, F, n_bins, {w,g,h}] over the mesh.

    Inputs are row-sharded over 'data'; output is replicated. Padding rows
    must have w == 0; stats accumulate {w, w·g, w·h} exactly as the
    reference accumulates {w, wY, wYY}: float32 sums, on any backend
    (``split3``).
    """
    stats = jnp.stack([w, w * g, w * h]).astype(jnp.float32)   # [3, N]
    ndata = mesh.shape[DATA_AXIS]
    N = bins.shape[0]
    if N % ndata != 0:
        pad = ndata - N % ndata
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        nid = jnp.pad(nid, (0, pad))
        stats = jnp.pad(stats, ((0, 0), (0, pad)))

    use_pallas = jax.default_backend() == "tpu" and _USE_PALLAS_FLAG

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS)),
        out_specs=P(), check_vma=False)
    def _task(bins_l, nid_l, stats_l):
        if use_pallas:
            from h2o3_tpu.ops.pallas_histogram import pallas_local_histogram
            hist = pallas_local_histogram(bins_l, nid_l, stats_l,
                                          n_nodes, n_bins,
                                          block_rows=min(block_rows, 512))
        else:
            hist = _local_histogram(bins_l, nid_l, stats_l, n_nodes, n_bins,
                                    block_rows)
        # psum over 'data' only: inputs are replicated over 'model', so
        # including it would scale every stat by the model-axis size;
        # a scope of its own, so that a trace prices the all-reduce
        with jax.named_scope("tree.psum"):
            return jax.lax.psum(hist, DATA_AXIS)

    return _task(bins, nid, stats)
