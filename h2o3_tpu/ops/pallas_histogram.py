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
    nid_blk   [1, C] int32      (current leaf per row, on the lanes)
    stats_blk [3, C] f32        ({w, w·g, w·h}; 0 on padding rows)
    right     [C, F·B]  = one-hot(bins)       built in VMEM, bf16
    left      [9L, C]   = one-hot(nid) ⊗ bf16 pieces of the stats
                          (ops/histogram.stat_rows: float32 sums)
    acc      += left @ right                   (MXU, f32)
Final step writes acc → out; the caller adds the three slabs and
reshapes to [L, F, B, 3].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.ops.histogram import piece_rows, stat_rows, sum_pieces


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

    acc_ref[:] += jax.lax.dot_general(
        stat_rows(nid_ref[:], stats_ref[:], n_nodes),
        right.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def pallas_local_histogram(bins, nid, stats, n_nodes: int, n_bins: int,
                           block_rows: int = 512, interpret: bool = False):
    """Single-shard histogram [L, F, B, 3] via the Pallas kernel, from
    ``bins`` [N, F], ``nid`` [N] and ``stats`` [3, N].

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
        stats = jnp.pad(stats, ((0, 0), (0, Npad - N)))

    kern = functools.partial(_hist_kernel, n_nodes=n_nodes, n_bins=n_bins)
    acc = (piece_rows(n_nodes), F * n_bins)
    out = pl.pallas_call(
        kern,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((C, F), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, C), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, C), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(acc, lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(acc, jnp.float32),
        scratch_shapes=[pltpu.VMEM(acc, jnp.float32)],
        interpret=interpret, name="histogram",
    )(bins, nid.reshape(1, -1), stats)
    return sum_pieces(out, n_nodes).reshape(
        n_nodes, 3, F, n_bins).transpose(0, 2, 3, 1)
