"""Distributed Gram matrix — X'WX / X'Wz as row contractions + psum.

Reference: hex/gram/Gram.java:15 — GLM's IRLS inner loop accumulates the
weighted Gram over an MRTask (GLMIterationTask, hex/glm/GLMTask.java) and
solves by Cholesky with collinear-column dropping (Gram.java:229,452).
TPU-native: each shard contracts its rows in one dot_general over the
row-sharded data axis, reading the design matrix in the layout it has;
`psum` replaces the reduce tree.

A design held as codes (``frame/datainfo.CodesDesign``) never becomes a
matrix: as hex/gram/Gram accumulates its categorical block from level
indices, each shard walks its rows a chunk at a time, builds the chunk's
0/1 indicator rows on the chip and multiplies them with three bfloat16
pieces of every weight (``ops/histogram.split3``): products exact,
float32 sums, compensated from chunk to chunk (scope ``gram.cat``) — an
XLA scan, or where the fit's ``ops/pallas`` mode asks for kernels ONE
Pallas kernel a shard that keeps the sums in VMEM
(``ops/pallas/gramkernel.py``). The same chunk walk gives the linear
predictor ``X @ beta`` (coefficient lookups) and ``X' v``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu.frame.datainfo import CodesDesign
from h2o3_tpu.ops import pallas as pallas_policy
from h2o3_tpu.ops.histogram import split3
from h2o3_tpu.parallel.mesh import DATA_AXIS

# rows of a shard one step of the codes walk reads (a power of two that
# divides the shard's rows, at most this). A step is some twenty device
# ops whatever its rows: at 8,192 rows a 116M-row fit issued 870,000 ops
# a second on a v5e, more than a profiler trace holds
CAT_CHUNK = 65536
# rows one product sums in float32 on its own: a step's operands are
# blocks [CAT_CHUNK / CAT_SUM, rows, CAT_SUM], a product a block, the
# blocks' sums added in float32 and then, step to step, with
# compensation. Products over 65,536 rows read path_gap 9.9e-5 on a
# 131,072-row fit whose 8,192-row products read 8.3e-6
CAT_SUM = 8192
# a left operand of the factor pairs' products gathers factors up to the
# MXU's height; a wider factor is an operand of its own
CAT_GROUP_ROWS = 128
# rows a grid step of the factor Gram's Pallas kernel reads; its sums are
# added with compensation every gramkernel.SUB_ROWS rows. A pass at the
# airlines cell's rows on a v5e: 0.737 s at 4,096, 0.756 at 2,048, 0.807
# at 16,384, 0.860 at 8,192 (PERF.md §6, PR 42)
CAT_KERNEL_ROWS = 4096


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
    the caller), or a ``CodesDesign``; w weights (0 on padding rows); z
    working response.
    """
    wz = jnp.stack([w, w * z], axis=1)
    if isinstance(X, CodesDesign):
        return _codes_gram(X, wz, mesh=mesh)
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


# ---- the design as codes -------------------------------------------------

def _chunk(n: int) -> int:
    """Rows a step of the codes walk: a power of two dividing ``n``."""
    return max(1, min(CAT_CHUNK, n & -n))


def _blocks(v):
    """A step's ``[m, c]`` as ``[c / s, m, s]``: blocks of s =
    min(CAT_SUM, c) rows, the rows on the lanes."""
    m, c = v.shape
    sub = min(CAT_SUM, c)
    return v.reshape(m, c // sub, sub).transpose(1, 0, 2)


def _rows_dot(a, b, precision=None):
    """``a`` ``[k, m, s]`` against ``b`` ``[k, n, s]`` over the blocks'
    rows: ``[m, n]`` float32, a product a block and their sum."""
    return jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))), precision=precision,
        preferred_element_type=jnp.float32).sum(axis=0)


def _indicator(code, na, first, card, lo, c):
    """``[blocks, card - first, s]`` bfloat16 0/1 (``_blocks``): one
    factor's indicator rows of rows ``lo .. lo + c``. Each factor's block
    is its own operand: a concatenation of the blocks is written out to
    memory a chunk at a time (2.8 s a pass at 116M rows on a v5e, three
    quarters of it the concatenations)."""
    k = jax.lax.dynamic_slice_in_dim(code, lo, c).astype(jnp.int32)
    k = jnp.where(jax.lax.dynamic_slice_in_dim(na, lo, c), -1, k - first)
    k = _blocks(k[None, :])
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, card - first, 1), 1)
    return (k == iota).astype(jnp.bfloat16)


def _pieces(v):
    """``[k, c]`` float32 as ``[3k, c]`` bfloat16: three pieces a value
    that add up to it bit for bit (``split3``), piece-major."""
    return jnp.concatenate(split3(v), axis=0).astype(jnp.bfloat16)


def _kahan(total, comp, x):
    """``total + x`` as a compensated float32 sum: ``(sum, compensation)``.
    A shard's thousands of chunks added one after another in plain
    float32 lose up to that many roundings, and the Gram of a factor
    whose dropped first level is rare is a difference of such sums (its
    levels against the intercept): uncompensated, 14,336 chunks of 8,192
    rows made it indefinite on the chip."""
    y = x - comp
    t = total + y
    return t, (t - total) - y


def _scan_sum(step, zeros, steps):
    """``sum over i < steps of step(i)`` — a pytree shaped as ``zeros`` —
    each leaf added with compensation (``_kahan``)."""
    leaves, tree = jax.tree_util.tree_flatten(zeros)

    def body(acc, i):
        new = [_kahan(t, k, x) for t, k, x in zip(
            *acc, jax.tree_util.tree_leaves(step(i)))]
        return (tuple(t for t, _ in new), tuple(k for _, k in new)), None

    (sums, _), _ = jax.lax.scan(
        body, (tuple(leaves), tuple(jnp.zeros_like(a) for a in leaves)),
        jnp.arange(steps))
    return jax.tree_util.tree_unflatten(tree, sums)


def _cat_columns(factors) -> np.ndarray:
    return np.concatenate([np.arange(off, off + card - first)
                           for off, first, card in factors]).astype(np.int64)


def _cat_plan(factors):
    """Static plan of the factor pairs' products: the factors in
    ascending width, and the left operands ``(start, rows, members)``
    over that order — consecutive narrow factors gathered up to
    ``CAT_GROUP_ROWS`` rows, a wider factor alone. A left operand meets
    each factor after it (and its own members, where it gathers several:
    their pairs and their own diagonal blocks), so every pair of factors
    is multiplied once; a factor alone with itself is diagonal — its
    weighted level counts, which the statistics rows give."""
    order = sorted(range(len(factors)),
                   key=lambda f: (factors[f][2] - factors[f][1], f))
    groups, at = [], 0
    for i, f in enumerate(order):
        width = factors[f][2] - factors[f][1]
        if groups and groups[-1][1] + width <= CAT_GROUP_ROWS:
            s, m, members = groups[-1]
            groups[-1] = (s, m + width, members + (i,))
        else:
            groups.append((at, width, (i,)))
        at += width
    return order, groups


def _right_of(members, n_factors: int) -> range:
    """The factors a left operand meets: its own where it gathers
    several, and every factor after it."""
    first = members[0] if len(members) > 1 else members[-1] + 1
    return range(first, n_factors)


def _kernel_geometry(X):
    """The plan of ``X`` as ``ops/pallas/gramkernel.Geometry``: its left
    operands as groups of factors, a product for each that meets a
    factor (its right factors start at a group: a lone factor meets the
    next group on)."""
    from h2o3_tpu.ops.pallas.gramkernel import Geometry
    order, groups = _cat_plan(X.factors)
    F = len(order)
    first = [m[0] for _, _, m in groups]
    return Geometry(
        widths=tuple(X.factors[f][2] - X.factors[f][1] for f in order),
        groups=tuple((m[0], m[-1] + 1) for _, _, m in groups),
        products=tuple((g, first.index(_right_of(m, F).start))
                       for g, (_, _, m) in enumerate(groups)
                       if len(_right_of(m, F))),
        nd=X.dense.shape[1])


def with_gram_kernel(X):
    """``X`` with the factor Gram's mode for a fit, resolved once a fit as
    the trees' ``TreeParams.pallas`` is (``ops/pallas.resolve_tree_mode``):
    the Pallas kernel where the mode asks for kernels and the plan's sums
    and a block's operands fit ``VMEM_BUDGET_BYTES``, else the XLA scan —
    a plan that does not fit counted as the fallback ``cat_gram_vmem``.
    A dense design is returned as it is."""
    if not isinstance(X, CodesDesign):
        return X
    mode = pallas_policy.resolve_tree_mode()
    if mode != "off":
        from h2o3_tpu.ops.pallas import gramkernel
        if not gramkernel.fits(_kernel_geometry(X), CAT_KERNEL_ROWS):
            pallas_policy.record_fallback("cat_gram_vmem")
            mode = "off"
    return dataclasses.replace(X, gram_kernel=mode)


def gram_kernel_name(X) -> str:
    """What forms the Gram of ``X``: ``pallas`` or ``xla`` (span meta)."""
    return ("pallas" if isinstance(X, CodesDesign)
            and X.gram_kernel != "off" else "xla")


def _kernel_sums(X, wz, order, groups):
    """``_scan_sum``'s sums of the plan — the pairs' blocks, the
    statistics rows and the numeric block — from ONE Pallas kernel over
    the shard (``ops/pallas/gramkernel.cat_gram_sums``), its padded
    sums cut back to the plan's shapes."""
    from h2o3_tpu.ops.pallas import gramkernel
    geo = _kernel_geometry(X)
    ks = [jnp.where(X.nas[f], -1,
                    X.codes[f].astype(jnp.int32) - X.factors[f][1])
          for f in order]
    *prods, stats, num = gramkernel.cat_gram_sums(
        ks, wz[:, 0], wz[:, 1], X.dense, geo=geo, block=CAT_KERNEL_ROWS,
        interpret=X.gram_kernel == "interpret")
    # a factor's first row in the kernel's stacked tiles
    row = []
    for (a, b), start in zip(geo.groups, geo.starts):
        row += [start + sum(geo.widths[a:f]) for f in range(a, b)]
    out_of = dict(zip((g for g, _ in geo.products), prods))
    pairs, by_stats = [], []
    for g, (_, m, members) in enumerate(groups):
        right = _right_of(members, len(order))
        at = row[right.start] if len(right) else 0
        pairs.append([out_of[g][:m, row[f] - at:row[f] - at + geo.widths[f]]
                      for f in right])
        by_stats.append(stats[:3 * geo.nv, row[members[0]]:
                              row[members[0]] + m].T)
    nd = geo.nd
    q = num[:geo.nq, 0]
    return pairs, by_stats, (q[:nd * nd].reshape(nd, nd),
                             q[nd * nd:nd * nd + nd], q[-1])


def _local_codes_gram(X, wz):
    """One shard's (X'WX, X'Wz, sum w) of a ``CodesDesign``, in the
    coefficients' order, from one scan over chunks of rows whose terms
    are added with compensation (``_kahan``) — or from the Pallas kernel
    where ``X.gram_kernel`` asks for it (``_kernel_sums``). A chunk: each
    factor's indicator rows against three bfloat16 pieces of the weights
    (pairs of factors) and of the statistics rows ``w·dense_j``, ``w``,
    ``w·z`` (factor x numeric, the factors' diagonals, X'Wz) — exact
    products, float32 sums — and the numeric block at float32
    precision."""
    n = wz.shape[0]
    c = _chunk(n)
    order, groups = _cat_plan(X.factors)
    fac = [X.factors[f] for f in order]
    widths = [card - first for _, first, card in fac]
    starts = np.cumsum([0] + widths[:-1])
    pc = X.cat_levels
    nd = X.dense.shape[1]
    nv = nd + 2                     # w·dense_j, w, w·z
    hi = jax.lax.Precision.HIGHEST

    def right_of(members):
        return _right_of(members, len(fac))

    with jax.named_scope("gram.cat"):
        def step(i):
            lo = i * c
            H = [_indicator(X.codes[f], X.nas[f], first, card, lo, c)
                 for f, (_, first, card) in zip(order, fac)]
            wzc = jax.lax.dynamic_slice_in_dim(wz, lo, c).T
            Dt = jax.lax.dynamic_slice_in_dim(X.dense, lo, c).T
            stats = _blocks(_pieces(jnp.concatenate([Dt * wzc[0], wzc])))
            wp = [_blocks(p[None, :].astype(jnp.bfloat16))
                  for p in split3(wzc[0])]
            pairs, by_stats = [], []
            for _, _, members in groups:
                left = H[members[0]] if len(members) == 1 else \
                    jnp.concatenate([H[j] for j in members], axis=1)
                pairs.append([sum(_rows_dot(left, H[f] * p) for p in wp)
                              for f in right_of(members)])
                by_stats.append(_rows_dot(left, stats))
            Db, wb = _blocks(Dt), _blocks(wzc)
            dense = (_rows_dot(Db * wb[:, :1], Db, precision=hi),
                     _rows_dot(Db, wb[:, 1:], precision=hi)[:, 0],
                     jnp.sum(wzc[0]))
            return pairs, by_stats, dense

        zeros = ([[jnp.zeros((m, widths[f]), jnp.float32)
                   for f in right_of(members)] for _, m, members in groups],
                 [jnp.zeros((m, 3 * nv), jnp.float32) for _, m, _ in groups],
                 (jnp.zeros((nd, nd), jnp.float32),
                  jnp.zeros((nd,), jnp.float32), jnp.float32(0.0)))
        if X.gram_kernel == "off":
            sums = _scan_sum(step, zeros, n // c)
        else:
            sums = _kernel_sums(X, wz, order, groups)
        pairs, by_stats, (xtx_n, xtz_n, ws) = sums

        G = jnp.zeros((pc + nd, pc + nd), jnp.float32)
        xtz_c = jnp.zeros((pc,), jnp.float32)
        for (s, m, members), blocks, st in zip(groups, pairs, by_stats):
            for f, blk in zip(right_of(members), blocks):
                G = G.at[s:s + m, starts[f]:starts[f] + widths[f]].set(blk)
            cv = st.reshape(m, 3, nv).sum(axis=1)
            G = G.at[s:s + m, pc:].set(cv[:, :nd])
            if len(members) == 1:
                diag = jnp.arange(s, s + m)
                G = G.at[diag, diag].set(cv[:, nd])
            xtz_c = xtz_c.at[s:s + m].set(cv[:, nd + 1])
        G = G.at[pc:, pc:].set(xtx_n)
        G = jnp.triu(G) + jnp.triu(G, 1).T
        # internal position -> coefficient; read back in coefficient order
        inv = np.argsort(np.concatenate(
            [_cat_columns(fac), np.asarray(X.dense_cols, np.int64)]))
        return (G[inv][:, inv], jnp.concatenate([xtz_c, xtz_n])[inv], ws)


def _codes_gram(X, wz, *, mesh):
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P()), check_vma=False)
    def _task(X_l, wz_l):
        xtx, xtz, ws = _local_codes_gram(X_l, wz_l)
        with jax.named_scope("gram.psum"):
            return (jax.lax.psum(xtx, DATA_AXIS),
                    jax.lax.psum(xtz, DATA_AXIS),
                    jax.lax.psum(ws, DATA_AXIS))

    return _task(X, wz)


@functools.partial(jax.jit, static_argnames=("mesh",))
def codes_matvec(X, B, *, mesh):
    """``X @ B`` of a ``CodesDesign``: ``B`` ``[P]`` or ``[P, K]``. The
    factors' part is a coefficient lookup — each chunk's indicator rows
    of a factor against three bfloat16 pieces of its coefficients, so a
    row's coefficients enter exactly and add in float32; the numerics'
    part a float32-precision product."""
    vec = B.ndim == 1
    B2 = (B[:, None] if vec else B).astype(jnp.float32)
    K = B2.shape[1]
    fac = X.factors
    tables = tuple(_pieces(B2[off:off + card - first].T)   # [3K, levels]
                   for off, first, card in fac)
    Bd = B2[np.asarray(X.dense_cols, np.int64)]            # [nd, K]

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(DATA_AXIS), P(), P()),
        out_specs=P(DATA_AXIS), check_vma=False)
    def _task(X_l, tables, Bd):
        n = X_l.dense.shape[0]
        c = _chunk(n)

        def step(_, i):
            t = sum(jax.lax.dot_general(                # [3K, blocks, s]
                table, _indicator(code, na, first, card, i * c, c),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                for table, code, na, (_, first, card)
                in zip(tables, X_l.codes, X_l.nas, fac))
            return None, t.reshape(3, K, c).sum(axis=0)

        _, ys = jax.lax.scan(step, None, jnp.arange(n // c))
        cat = ys.transpose(0, 2, 1).reshape(n, K)
        return cat + jnp.dot(X_l.dense, Bd,
                             precision=jax.lax.Precision.HIGHEST)

    out = _task(X, tables, Bd)
    return out[:, 0] if vec else out


@functools.partial(jax.jit, static_argnames=("mesh",))
def codes_rmatvec(X, v, *, mesh):
    """``X' v`` of a ``CodesDesign``: ``v`` ``[N]`` or ``[N, K]``; the
    factors' sums from three bfloat16 pieces of ``v`` against each
    chunk's indicator rows, float32 with compensation."""
    vec = v.ndim == 1
    V2 = (v[:, None] if vec else v).astype(jnp.float32)
    K = V2.shape[1]
    fac = X.factors

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(), check_vma=False)
    def _task(X_l, V_l):
        n = V_l.shape[0]
        c = _chunk(n)

        def step(i):
            Vp = _blocks(_pieces(
                jax.lax.dynamic_slice_in_dim(V_l, i * c, c).T))
            return [_rows_dot(_indicator(code, na, first, card, i * c, c), Vp)
                    for code, na, (_, first, card)
                    in zip(X_l.codes, X_l.nas, fac)]

        sums = _scan_sum(step, [jnp.zeros((card - first, 3 * K), jnp.float32)
                                for _, first, card in fac], n // c)
        dense = jax.lax.dot_general(X_l.dense, V_l, (((0,), (0,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST)
        out = jnp.zeros((X.p, K), jnp.float32)
        for (off, first, card), acc in zip(fac, sums):
            out = out.at[off:off + card - first].set(
                acc.reshape(-1, 3, K).sum(axis=1))
        out = out.at[np.asarray(X.dense_cols, np.int64)].set(dense)
        return jax.lax.psum(out, DATA_AXIS)

    out = _task(X, V2)
    return out[:, 0] if vec else out


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
