"""Pallas TPU kernel for the tree-building histogram.

The XLA path (ops/histogram.py) expresses the (node, feature, bin)
accumulation as one-hot × stats matmuls; XLA materializes the [C, F·B]
one-hot indicator between fusions, so every row block round-trips an
inflated intermediate through HBM. This kernel builds the indicators
in VMEM, feeds the MXU directly, and accumulates the histogram in a
VMEM scratch across the row-block grid — the whole hot loop of
ScoreBuildHistogram2 (hex/tree/DHistogram.java:585-674) stays on-chip.

Layout per grid step i over row blocks of C rows:
    bins_blk  [C, F] int32      (feature-bin ids; NA bin = B-1)
    nid_blk   [C, 1] int32      (current leaf per row)
    stats_blk [C, 3] f32        ({w, w·g, w·h}; 0 on padding rows)
    right     [C, F·B]  = one-hot(bins)       built in VMEM
    left      [C, 3L]   = one-hot(nid) ⊗ stats
    acc      += leftᵀ @ right                  (MXU, f32)
Final step writes acc → out [3L, F·B]; caller reshapes to [L, F, B, 3].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hist_kernel(bins_ref, nid_ref, stats_ref, out_ref, acc_ref, *,
                 n_nodes: int, n_bins: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bins = bins_ref[:]                     # [C, F]
    C, F = bins.shape
    FB = F * n_bins
    # combined (feature, bin) id per row/feature; one-hot built with an
    # unrolled per-feature compare against the lane iota — Mosaic has no
    # minor-dim reshape, so [C,F,B]→[C,FB] is constructed directly
    feat_off = jax.lax.broadcasted_iota(jnp.int32, (C, F), 1) * n_bins
    fb = bins + feat_off                   # [C, F] in [0, FB)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, FB), 1)
    right = (lane == fb[:, 0:1]).astype(jnp.float32)
    for f in range(1, F):
        right += (lane == fb[:, f:f + 1]).astype(jnp.float32)

    # left [C, 3L] with column k ↦ (node k//3, stat k%3), built without
    # any minor-dim reshape (Mosaic-unsupported): three masked
    # broadcast-multiplies against the lane iota
    nid = nid_ref[:]                       # [C, 1]
    stats = stats_ref[:]                   # [C, 3]
    lane3 = jax.lax.broadcasted_iota(jnp.int32, (C, n_nodes * 3), 1)
    node_of_k = lane3 // 3
    stat_of_k = lane3 - 3 * node_of_k
    node_hit = (nid == node_of_k).astype(jnp.float32)        # [C, 3L]
    left = jnp.zeros((C, n_nodes * 3), jnp.float32)
    for s in range(3):
        sel = (stat_of_k == s).astype(jnp.float32)
        left += sel * node_hit * stats[:, s:s + 1]

    acc_ref[:] += jax.lax.dot_general(
        left, right, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def pallas_local_histogram(bins, nid, stats, n_nodes: int, n_bins: int,
                           block_rows: int = 512, interpret: bool = False):
    """Single-shard histogram [L, F, B, 3] via the Pallas kernel.

    Drop-in replacement for ops/histogram._local_histogram on TPU
    backends (CPU tests run it with interpret=True).
    """
    from h2o3_tpu.ops import pallas as pallas_policy
    pallas_policy.record_launch("histogram")
    N, F = bins.shape
    C = min(block_rows, N)
    nblk = (N + C - 1) // C
    Npad = nblk * C
    if Npad != N:   # padding rows carry zero stats → no contribution
        bins = jnp.pad(bins, ((0, Npad - N), (0, 0)))
        nid = jnp.pad(nid, (0, Npad - N))
        stats = jnp.pad(stats, ((0, Npad - N), (0, 0)))

    kern = functools.partial(_hist_kernel, n_nodes=n_nodes, n_bins=n_bins)
    out = pl.pallas_call(
        kern,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((C, F), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, 3), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((n_nodes * 3, F * n_bins), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_nodes * 3, F * n_bins),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_nodes * 3, F * n_bins), jnp.float32)],
        interpret=interpret, name="histogram",
    )(bins, nid.reshape(-1, 1), stats)
    return out.reshape(n_nodes, 3, F, n_bins).transpose(0, 2, 3, 1)
