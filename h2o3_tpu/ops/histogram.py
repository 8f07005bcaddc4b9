"""Distributed (node, feature, bin) histogram — THE hot loop of tree building.

Reference: hex/tree/DHistogram.java:585-674 ``updateHisto`` accumulates
{w, wY, wYY} per (leaf, col, bin) with scalar adds inside an MRTask;
reduce = elementwise histogram add up the thread/node trees
(hex/tree/ScoreBuildHistogram2.java:62).

TPU-native: scatter-add is MXU-hostile, so the accumulation is recast as
two matmuls per row-block (SURVEY §7 "hard parts" #1):

    left  [3L, C] = (one_hot(node) ⊗ [w, g, h])ᵀ     (C = block rows)
    right [C, FB] = one_hot(feature-bin)             (0/1, bf16)
    hist += left @ right                             → [3L, FB]

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


def _block_hist(bins_blk, nid_blk, stats_blk, n_nodes: int, n_bins: int,
                precision=None):
    """One row-block's [3L, FB] partial histogram via MXU matmul."""
    C, F = bins_blk.shape
    # right: 0/1 indicator of (feature, bin) per row — exact in bf16
    onehot_fb = (bins_blk[:, :, None] ==
                 jnp.arange(n_bins, dtype=jnp.int32)[None, None, :])
    right = onehot_fb.reshape(C, F * n_bins).astype(jnp.float32)
    # left: stats routed to the row's node. f32 on both sides: the stats
    # side would lose ~0.4% in bf16, corrupting gains; XLA's bf16x3 pass
    # keeps the MXU busy for f32 contractions. ``precision=HIGHEST``
    # (small-problem mode) trades MXU rate for true-f32 accumulation —
    # the reference pyunits assert metric equality at 1e-5 relative,
    # which bf16x3 residue can miss (pyunit_weights_gbm, 1.9e-5 off).
    node_oh = (nid_blk[:, None] ==
               jnp.arange(n_nodes, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    left = (node_oh[:, :, None] * stats_blk[:, None, :])  # [C, L, 3]
    left = left.reshape(C, n_nodes * 3)
    return jax.lax.dot_general(
        left.T, right, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


def _local_histogram(bins, nid, stats, n_nodes: int, n_bins: int,
                     block_rows: int, precision=None):
    """Scan row blocks of one shard, accumulating the [L,F,B,3] histogram."""
    N, F = bins.shape
    C = min(block_rows, N)
    nblk = (N + C - 1) // C
    Npad = nblk * C
    if Npad != N:
        bins = jnp.pad(bins, ((0, Npad - N), (0, 0)))
        nid = jnp.pad(nid, (0, Npad - N))
        stats = jnp.pad(stats, ((0, Npad - N), (0, 0)))  # w=0 ⇒ no effect? see below
        # padding rows carry zero stats so they contribute nothing
    bins_b = bins.reshape(nblk, C, F)
    nid_b = nid.reshape(nblk, C)
    stats_b = stats.reshape(nblk, C, 3)

    def step(acc, xs):
        b, n, s = xs
        return acc + _block_hist(b, n, s, n_nodes, n_bins,
                                 precision=precision), None

    init = jnp.zeros((n_nodes * 3, F * n_bins), jnp.float32)
    acc, _ = jax.lax.scan(step, init, (bins_b, nid_b, stats_b))
    # [3L, FB] -> [L, F, B, 3]
    return acc.reshape(n_nodes, 3, F, n_bins).transpose(0, 2, 3, 1)


def histogram(bins, nid, w, g, h, *, n_nodes: int, n_bins: int,
              mesh, block_rows: int = 16384, precision=None):
    """All-reduced histogram [n_nodes, F, n_bins, {w,g,h}] over the mesh.

    Inputs are row-sharded over 'data'; output is replicated. Padding rows
    must have w == 0; stats accumulate {w, w·g, w·h} exactly as the
    reference accumulates {w, wY, wYY}.
    """
    stats = jnp.stack([w, w * g, w * h], axis=1).astype(jnp.float32)
    ndata = mesh.shape[DATA_AXIS]
    N = bins.shape[0]
    if N % ndata != 0:
        pad = ndata - N % ndata
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        nid = jnp.pad(nid, (0, pad))
        stats = jnp.pad(stats, ((0, pad), (0, 0)))

    use_pallas = jax.default_backend() == "tpu" and _USE_PALLAS_FLAG

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(), check_vma=False)
    def _task(bins_l, nid_l, stats_l):
        if use_pallas:
            from h2o3_tpu.ops.pallas_histogram import pallas_local_histogram
            hist = pallas_local_histogram(bins_l, nid_l, stats_l,
                                          n_nodes, n_bins,
                                          block_rows=min(block_rows, 512))
        else:
            hist = _local_histogram(bins_l, nid_l, stats_l, n_nodes, n_bins,
                                    block_rows, precision=precision)
        # psum over 'data' only: inputs are replicated over 'model', so
        # including it would scale every stat by the model-axis size
        return jax.lax.psum(hist, DATA_AXIS)

    return _task(bins, nid, stats)
