"""The factor Gram of a design held as codes, as ONE Pallas kernel a shard.

``ops/gram.py`` ``_local_codes_gram`` forms one shard's X'WX, X'Wz and
sum w of a ``frame/datainfo.CodesDesign``. Its XLA form is a scan over
steps of rows that writes each step's block products to HBM, reads them
back to sum them and reads and writes the compensated accumulators,
some twenty device ops a step. This kernel walks the shard's rows in
blocks of ``gram.CAT_KERNEL_ROWS`` rows on a sequential grid, with the
compensated float32 sums held in VMEM for the whole grid and written
out once, at the last step:

- the rows ride the lanes, as in ``treekernel.frontier_hist``: each
  code and weight input is a ``[rows / 128, 128]`` view of its
  ``[rows]`` array, the dense part its ``[nd, rows]`` transpose (the
  same bytes on the chip), and a sub-block of ``SUB_ROWS`` rows is laid
  out on the lanes of one row vector; a left operand's 0/1 indicator
  tile ``[levels, SUB_ROWS]`` holds its factors' levels one after
  another, each factor one compare of its codes against a sublane iota
  (no indicator where the row is NA or at the dropped first level), the
  tile up to the 8 sublanes of a float32 tile; tiles are stacked and
  weighted in float32 and packed to bfloat16 once, so that the airlines
  factors' 753 levels take 768 rows — six MXU column tiles — where a
  tile a factor, up to 16 rows each, took 800;
- the products are the plan's (``gram._cat_plan``): a left operand (a
  factor, or narrow factors gathered) meets every factor after it,
  every pair of factors once, against three bfloat16 pieces of w
  (``ops/histogram.split3``) — the pieces stacked on the left
  operand's rows, so that the MXU streams three times the rows against
  the same indicator tiles — and the statistics rows ``w·dense_j``,
  ``w``, ``w·z`` in three pieces meet every factor. Operands exact,
  float32 sums over a sub-block inside the MXU;
- the numeric block (X'WX and X'Wz of the dense columns, sum w) is
  formed on the VPU in float32, a sum over the lanes a sub-block;
- every sub-block's sums are added into the accumulators with Kahan
  compensation (``gram._kahan``'s arithmetic).

``gram._local_codes_gram`` assembles the Gram from what this returns as
it does from its scan; the XLA scan stays the path where the fit's
``H2O3TPU_PALLAS`` mode is ``off`` and where the plan does not fit
VMEM (``fits``), and the reference the kernel is tested against
(``tests/test_glm_cat_gram_kernel.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.ops import pallas as pallas_policy
from h2o3_tpu.ops.histogram import split3

LANES = 128
# rows one sub-product sums on the MXU: its indicator tiles and weighted
# left operands are bfloat16 values of this many lanes (a pass at the
# airlines cell's rows on a v5e, 4,096-row blocks: 0.737 s at 512, 0.747
# at 1,024, 0.752 at 2,048)
SUB_ROWS = 512


def _up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


class Geometry(NamedTuple):
    """The kernel's static shapes. ``widths``: the factors' indicator
    columns in the plan's order; ``groups``: the factors ``first ..
    stop - 1`` each left operand gathers, consecutive and covering every
    factor; ``products``: ``(group, right)`` — the left operand of
    ``group`` meets every factor of the groups ``right ..``; ``nd``: the
    dense columns. A group is one indicator tile whose rows are its
    factors' levels one after another, up to the 8 sublanes of a
    float32 tile."""
    widths: Tuple[int, ...]
    groups: Tuple[Tuple[int, int], ...]
    products: Tuple[Tuple[int, int], ...]
    nd: int

    def levels(self, g) -> int:
        a, b = self.groups[g]
        return sum(self.widths[a:b])

    @property
    def rows(self) -> Tuple[int, ...]:
        return tuple(_up(self.levels(g), 8) for g in range(len(self.groups)))

    @property
    def starts(self) -> Tuple[int, ...]:
        return tuple(int(s) for s in np.cumsum((0,) + self.rows[:-1]))

    @property
    def total(self) -> int:
        return sum(self.rows)

    @property
    def nv(self) -> int:
        """Statistics rows: w·dense_j, w, w·z."""
        return self.nd + 2

    @property
    def stat_rows(self) -> int:
        return _up(3 * self.nv, 8)

    @property
    def nq(self) -> int:
        """Numeric quantities: X'WX and X'Wz of the dense columns, sum w."""
        return self.nd * self.nd + self.nd + 1

    @property
    def stats_with(self):
        """The product whose right operand is every factor, which takes
        the statistics rows on its left operand; None: a product of
        their own."""
        for i, (_, right) in enumerate(self.products):
            if right == 0:
                return i
        return None

    def out_shapes(self):
        """The sums the kernel keeps: a product's ``[left rows, right
        rows]`` (its three pieces added), the statistics rows against
        every factor, the numeric quantities (each on every lane of its
        row)."""
        return ([(self.rows[g], self.total - self.starts[r])
                 for g, r in self.products]
                + [(self.stat_rows, self.total), (_up(self.nq, 8), LANES)])


def block_rows(n: int, block: int) -> int:
    """Rows a grid step reads: ``block``, or all of a shard that holds
    fewer, in whole sub-blocks."""
    return min(block, _up(n, SUB_ROWS))


def vmem_bytes(geo: Geometry, block: int) -> int:
    """Scoped VMEM the kernel is counted at: each sum and its
    compensation, the inputs' blocks of ``block`` rows (two buffers
    each; the dense columns a block of 8 sublanes), and a sub-block's
    operands once — the indicator tiles and the largest left operand at
    3 B an element (the bfloat16 they are packed to and part of the
    float32 selects, which Mosaic does not hold whole), and the largest
    product at 2 B an element."""
    sums = sum(a * b for a, b in geo.out_shapes())
    n_in = len(geo.widths) + 2 + _up(geo.nd, 8) // 8
    left = max([3 * geo.rows[g] for g, _ in geo.products]
               + [0]) + geo.stat_rows
    product = max([(3 * geo.rows[g] + geo.stat_rows)
                   * (geo.total - geo.starts[r])
                   for g, r in geo.products] + [0])
    return (8 * sums + 2 * 4 * n_in * block
            + 3 * (geo.total + left) * SUB_ROWS + 2 * product)


def fits(geo: Geometry, block: int) -> bool:
    """Whether the kernel of ``geo`` fits ``VMEM_BUDGET_BYTES`` with a
    MiB to spare for the compiler's own scratch. ``vmem_bytes`` against
    the least limit at which Mosaic compiled the kernel for a v5e
    (libtpu 0.0.34; 4,096-row blocks, 512-row sub-blocks), by factor
    levels: 5.1 / 4.0 MiB at the airlines factors (12, 31, 7, 29, 340,
    340), 3.3 / 3.0 at (101, 201, 301), 7.1 / 3.7 at (5, 4000), 10.6 /
    5.4 at (5, 6000); (700, 800), 13.2 / 5.7, takes the scan — the count
    errs high, where a pair of wide factors' product is large."""
    return vmem_bytes(geo, block) + (1 << 20) <= \
        pallas_policy.VMEM_BUDGET_BYTES


def _kahan_into(s_ref, c_ref, x):
    """``s_ref += x`` with compensation (``gram._kahan``)."""
    s = s_ref[...]
    y = x - c_ref[...]
    t = s + y
    c_ref[...] = (t - s) - y
    s_ref[...] = t


def _flat(t):
    """A ``[k, 128]`` tile of rows as one ``[1, 128·k]`` row vector."""
    return jnp.concatenate([t[i:i + 1, :] for i in range(t.shape[0])],
                           axis=1)


def _stack(values, n_rows):
    """``[1, S]`` row vectors as the first rows of ``[n_rows, S]``."""
    at = jax.lax.broadcasted_iota(jnp.int32, (n_rows, values[0].shape[1]), 0)
    out = jnp.zeros(at.shape, jnp.float32)
    for i, v in enumerate(values):
        out = jnp.where(at == i, v, out)
    return out


def _kernel(*refs, geo: Geometry, n_sub: int):
    F, nd, nv = len(geo.widths), geo.nd, geo.nv
    n_out = len(geo.products) + 2
    n_in = F + 2 + (nd > 0)
    code_refs = refs[:F]
    w_ref, wz_ref, dense_ref = refs[F], refs[F + 1], refs[n_in - 1]
    outs = refs[n_in:n_in + n_out]
    comps = refs[n_in + n_out:]

    @pl.when(pl.program_id(0) == 0)
    def _():
        for r in outs + comps:
            r[...] = jnp.zeros_like(r)

    rows, stats_with = geo.rows, geo.stats_with
    sub = SUB_ROWS // LANES
    bf16 = jnp.bfloat16

    def indicator(ks, g):
        """Group ``g``'s ``[rows, S]`` 0/1 mask: its factors' levels one
        after another on the sublanes, each one compare of a factor's
        codes against the sublane iota."""
        a, b = geo.groups[g]
        level = jax.lax.broadcasted_iota(jnp.int32, (rows[g], SUB_ROWS), 0)
        mask, at = None, 0
        for f in range(a, b):
            hit = ks[f] + at == level
            if at:
                hit = hit & (ks[f] >= 0)
            mask = hit if mask is None else mask | hit
            at += geo.widths[f]
        return mask

    def sub_block(j):
        # the sub-block's rows j·S .. (j + 1)·S - 1, in order on the lanes
        ks = [_flat(r[j * sub:(j + 1) * sub, :]) for r in code_refs]
        w = _flat(w_ref[j * sub:(j + 1) * sub, :])
        wz = _flat(wz_ref[j * sub:(j + 1) * sub, :])
        dense = [dense_ref[a:a + 1, j * SUB_ROWS:(j + 1) * SUB_ROWS]
                 for a in range(nd)]

        # the numeric block at float32 on the VPU: a quantity's products
        # summed over the sub-block's lanes, every lane of its row
        qs = ([w * dense[a] * dense[b] for a in range(nd) for b in range(nd)]
              + [wz * d for d in dense] + [w])
        q = jnp.sum(_stack(qs, outs[-1].shape[0]), axis=1, keepdims=True)
        _kahan_into(outs[-1], comps[-1],
                    jnp.broadcast_to(q, outs[-1].shape))

        # the statistics rows, piece-major: w·dense_j, w, w·z
        vals = [split3(v) for v in [w * d for d in dense] + [w, wz]]
        srow = _stack([vals[v][p] for p in range(3) for v in range(nv)],
                      geo.stat_rows)
        pieces = split3(w)
        masks = [indicator(ks, g) for g in range(len(rows))]
        H = [jnp.where(m, jnp.float32(1.0), jnp.float32(0.0)) for m in masks]

        def product(left, r):
            right = jnp.concatenate(H[r:], axis=0) if r < len(H) - 1 \
                else H[r]
            return jax.lax.dot_general(
                left.astype(bf16), right.astype(bf16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

        for i, (g, r) in enumerate(geo.products):
            m = rows[g]
            left = [jnp.where(masks[g], p, jnp.float32(0.0)) for p in pieces]
            if i == stats_with:
                left.append(srow)
            d = product(jnp.concatenate(left, axis=0), r)
            _kahan_into(outs[i], comps[i],
                        d[:m] + d[m:2 * m] + d[2 * m:3 * m])
            if i == stats_with:
                _kahan_into(outs[-2], comps[-2], d[3 * m:])
        if stats_with is None:
            _kahan_into(outs[-2], comps[-2], product(srow, 0))

    for j in range(n_sub):
        sub_block(j)


def cat_gram_sums(ks, w, wz, dense, *, geo: Geometry, block: int,
                  interpret: bool, fuse_inputs: bool = True):
    """One shard's sums, as the kernel keeps them: ``ks`` each factor's
    ``[n]`` int32 level less its first kept one (-1: no indicator),
    ``w`` / ``wz`` ``[n]`` float32, ``dense`` ``[n, nd]``. Returns the
    ``geo.out_shapes()`` arrays. The inputs reach the kernel as views;
    their producers (the NA select, the slices of ``wz``, the transpose
    of ``dense``) are fused into its operands — the chip's compiler then
    places the kernel without holding it to ``vmem_limit_bytes``, so
    ``fuse_inputs=False`` is how a compile shows the kernel within
    ``VMEM_BUDGET_BYTES`` (``tests/test_chip_compile.py``)."""
    n = w.shape[0]
    T = block_rows(n, block)
    n_pad = _up(n, T)

    def tile(v, fill):
        if n_pad != n:
            v = jnp.pad(v, (0, n_pad - n), constant_values=fill)
        return v.reshape(n_pad // LANES, LANES)

    # the dense part lies rows-minor on the chip: its transpose is the
    # same bytes, a [nd, T] block a step
    dense_t = dense.T
    if n_pad != n:
        dense_t = jnp.pad(dense_t, ((0, 0), (0, n_pad - n)))
    args = ([tile(k, -1) for k in ks] + [tile(w, 0.0), tile(wz, 0.0)]
            + [dense_t] * (geo.nd > 0))
    shapes = geo.out_shapes()
    whole = lambda s: pl.BlockSpec(s, lambda i: (0, 0))   # noqa: E731
    pallas_policy.record_launch("glm_cat_gram")
    return pl.pallas_call(
        functools.partial(_kernel, geo=geo, n_sub=T // SUB_ROWS),
        grid=(n_pad // T,),
        in_specs=[pl.BlockSpec((T // LANES, LANES), lambda i: (i, 0))]
        * (len(ks) + 2)
        + [pl.BlockSpec((geo.nd, T), lambda i: (0, i))] * (geo.nd > 0),
        out_specs=[whole(s) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in shapes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            allow_input_fusion=[fuse_inputs] * len(args),
            vmem_limit_bytes=pallas_policy.VMEM_BUDGET_BYTES),
        interpret=interpret, name="glm_cat_gram",
    )(*args)
