"""Segment (per-node) stat sums via the same matmul trick as histogram.

Reference: leaf-value passes like GammaPass (hex/tree/gbm/GBM.java:520)
accumulate per-leaf numerator/denominator with an MRTask. Here: one
one-hot matmul per row block, psum over the data axis. Every sum is a
float32 sum on any backend: the values enter the product as three
bfloat16 pieces (ops/histogram.split3) beside the exact 0/1 one-hot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu.ops.histogram import split3
from h2o3_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from h2o3_tpu.telemetry import observed_jit


def _local_segment_sum(nid, vals, n_nodes: int, block_rows: int):
    N = nid.shape[0]
    K = vals.shape[1]
    C = min(block_rows, N)
    nblk = (N + C - 1) // C
    Npad = nblk * C
    if Npad != N:
        nid = jnp.pad(nid, (0, Npad - N))
        vals = jnp.pad(vals, ((0, Npad - N), (0, 0)))
    nid_b = nid.reshape(nblk, C)
    vals_b = vals.reshape(nblk, C, K)

    def step(acc, xs):
        n, v = xs
        oh = (n[None, :] == jnp.arange(n_nodes, dtype=jnp.int32)[:, None])
        pieces = jnp.concatenate(split3(v), axis=1)            # [C, 3K]
        part = jax.lax.dot_general(
            oh.astype(jnp.bfloat16), pieces.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc + part, None

    init = jnp.zeros((n_nodes, 3 * K), jnp.float32)
    acc, _ = jax.lax.scan(step, init, (nid_b, vals_b))
    return acc[:, :K] + acc[:, K:2 * K] + acc[:, 2 * K:]


def segment_sum(nid, vals, *, n_nodes: int, mesh, block_rows: int = 16384):
    """All-reduced per-node sums: vals [N, K] → [n_nodes, K].

    Rows with all-zero vals (padding) contribute nothing; nid must be in
    [0, n_nodes).

    n_nodes is bucketed up to the next power of two internally (result
    sliced back): every distinct group count would otherwise compile its
    own XLA program — a group-by sweep over many cardinalities (the
    munging pyunits) pays 20-40s of TPU compile per distinct count.
    """
    want = n_nodes
    if n_nodes > 1:
        n_nodes = 1 << (n_nodes - 1).bit_length()
    ndata = mesh.shape[DATA_AXIS]
    N = nid.shape[0]
    if N % ndata != 0:
        pad = ndata - N % ndata
        nid = jnp.pad(nid, (0, pad))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    out = _segment_sum_jit(nid, vals, n_nodes=n_nodes,
                           block_rows=block_rows, mesh=mesh)
    return out if want == n_nodes else out[:want]


@observed_jit("ops.segment_sum")
@functools.partial(jax.jit, static_argnames=("n_nodes", "block_rows",
                                             "mesh"))
def _segment_sum_jit(nid, vals, *, n_nodes, block_rows, mesh):
    # module-level jit: eager callers (rapids group-by sweeps) hit the
    # trace cache across calls — a per-call closure would re-trace and
    # re-lower the shard_map every time
    task = functools.partial(_local_segment_sum, n_nodes=n_nodes,
                             block_rows=block_rows)

    def _body(nid_l, vals_l):
        part = task(nid_l, vals_l)
        with jax.named_scope("tree.leaf_psum"):
            return jax.lax.psum(part, DATA_AXIS)

    return shard_map(_body, mesh=mesh,
                     in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                     out_specs=P(), check_vma=False)(nid, vals)
