"""Distributed Gram matrix — X'WX / X'Wz as row contractions + psum.

Reference: hex/gram/Gram.java:15 — GLM's IRLS inner loop accumulates the
weighted Gram over an MRTask (GLMIterationTask, hex/glm/GLMTask.java) and
solves by Cholesky with collinear-column dropping (Gram.java:229,452).
TPU-native: each shard contracts its rows in one dot_general over the
row-sharded data axis, reading the design matrix in the layout it has;
`psum` replaces the reduce tree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu.parallel.mesh import DATA_AXIS


def _local_gram(X, wz):
    """[P, P] X'WX, [P] X'Wz and sum w over one shard, each ONE contraction
    over the shard's rows.

    X is not cut into row blocks: on the TPU it lies rows-minor, and a
    reshape into blocks makes XLA lay the whole matrix out again and
    slice it in sparsely filled tiles, with more temporaries than the
    matrix itself (tests/test_chip_compile.py ``test_glm_irls_solve``).

    wz: [N, 2] = (w, w*z) stacked. Returns (XtWX, XtWz, wsum).
    """
    # the scope name is what a device trace shows of this code
    with jax.named_scope("gram.accumulate"):
        rows = (((0,), (0,)), ((), ()))
        xtx = jax.lax.dot_general(X * wz[:, 0:1], X, rows,
                                  preferred_element_type=jnp.float32)
        xtz = jax.lax.dot_general(X, wz[:, 1], rows,
                                  preferred_element_type=jnp.float32)
        ws = jnp.sum(wz[:, 0])
    return xtx, xtz, ws


def gram(X, w, z, *, mesh):
    """All-reduced (X'WX, X'Wz, sum w) over the mesh.

    X [N, P] row-sharded design matrix (with intercept column appended by
    the caller); w weights (0 on padding rows); z working response.
    """
    wz = jnp.stack([w, w * z], axis=1)
    ndata = mesh.shape[DATA_AXIS]
    N = X.shape[0]
    if N % ndata != 0:
        pad = ndata - N % ndata
        X = jnp.pad(X, ((0, pad), (0, 0)))
        wz = jnp.pad(wz, ((0, pad), (0, 0)))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P()), check_vma=False)
    def _task(X_l, wz_l):
        xtx, xtz, ws = _local_gram(X_l, wz_l)
        with jax.named_scope("gram.psum"):
            return (jax.lax.psum(xtx, DATA_AXIS),
                    jax.lax.psum(xtz, DATA_AXIS),
                    jax.lax.psum(ws, DATA_AXIS))

    return _task(X, wz)


def gram_model_sharded(X, w, z, *, mesh):
    """Model-axis-sharded Gram: X columns sharded over 'model', rows over
    'data'; the X'X cross-block products stream around the model axis as
    a ppermute ring (the collective-matmul recipe — each device holds one
    column block, receives its neighbours' blocks one hop at a time, and
    never materializes the full-width matrix).

    This is the TP-like axis SURVEY §2.4 item 6 reserves for wide one-hot
    GLM feature spaces (the reference's sharded-Gram analogue of
    hex/gram/Gram.java over very wide DataInfo expansions).

    Returns (XtWX [P, P] sharded over columns, XtWz [P], wsum) — all
    psum-reduced over 'data'.
    """
    from h2o3_tpu.parallel.mesh import MODEL_AXIS
    nmodel = mesh.shape[MODEL_AXIS]
    ndata = mesh.shape[DATA_AXIS]
    N, Pdim = X.shape
    P0 = Pdim
    if nmodel == 1:
        return gram(X, w, z, mesh=mesh)
    if Pdim % nmodel != 0:
        padc = nmodel - Pdim % nmodel
        X = jnp.pad(X, ((0, 0), (0, padc)))
        Pdim += padc
    wz = jnp.stack([w, w * z], axis=1)
    if N % ndata != 0:
        pad = ndata - N % ndata
        X = jnp.pad(X, ((0, pad), (0, 0)))
        wz = jnp.pad(wz, ((0, pad), (0, 0)))
    Pm = Pdim // nmodel

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS)),
        out_specs=(P(None, MODEL_AXIS), P(MODEL_AXIS), P()),
        check_vma=False)
    def _task(X_l, wz_l):
        # X_l: [N/d, Pm] — this rank's column block; ring-stream the
        # other ranks' blocks to fill the [P, Pm] column slab of X'WX
        my = jax.lax.axis_index(MODEL_AXIS)
        wX = X_l * wz_l[:, 0:1]
        out = jnp.zeros((Pdim, Pm), jnp.float32)
        Y = X_l
        src = my
        perm = [(i, (i - 1) % nmodel) for i in range(nmodel)]
        for _hop in range(nmodel):
            # block (src, my) of the Gram: Y holds rank `src`'s columns
            blk = jax.lax.dot_general(
                Y.T, wX, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # [Pm, Pm]
            out = jax.lax.dynamic_update_slice(out, blk, (src * Pm, 0))
            Y = jax.lax.ppermute(Y, MODEL_AXIS, perm)
            src = (src + 1) % nmodel
        xtz = X_l.T @ wz_l[:, 1]
        ws = jnp.sum(wz_l[:, 0])
        return (jax.lax.psum(out, DATA_AXIS),
                jax.lax.psum(xtz, DATA_AXIS),
                jax.lax.psum(ws, (DATA_AXIS, MODEL_AXIS)) / nmodel)

    xtx, xtz, ws = _task(X, wz)
    # drop the nmodel-alignment padding: callers solve [P0, P0] normal
    # equations and a zero row/col would make them singular
    return xtx[:P0, :P0], xtz[:P0], ws
