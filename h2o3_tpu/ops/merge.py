"""Device sort-merge equi-join — the BinaryMerge.java role, TPU-native.

Reference contract: water/rapids/RadixOrder.java + BinaryMerge.java —
MSD-radix order both sides, then per-key binary search with per-row
match-range expansion. The TPU collapse keeps ALL the heavy work on
device in three compiled programs:

  1. ``_match_ranges``: one multi-key lexicographic sort of the
     CONCATENATED left+right keys (repeated stable argsort — the XLA
     sort network is the radix order), equal-key runs found with one
     shifted-compare, per-run right-row counts via ``segment_sum``.
     Multi-key equality needs no 64-bit key packing (x64 is off) —
     each key column is compared in its own dtype.
  2. ``_gather_out``: static-shape expansion of the per-left-row match
     ranges (searchsorted over the match-count prefix sum) + gathers of
     every output column, NA-masking unmatched right rows for left
     joins.

All three run on the frames' PADDED device arrays with the valid row
counts as TRACED scalars, so one compiled pipeline serves every frame
pair whose padded (bucketed) shapes match — the same compile economics
as mesh.padded_rows. The controller only touches ONE scalar (the total
match count, needed to size program 3). Host numpy remains the
tiny-frame path — sub-64K pyunit frames pay more in compile than they
save.

NA keys never match (Merge.java semantics). For all-float keys NA
folds to NaN: jnp.argsort orders finite < +inf < NaN and NaN != NaN
isolates every NA row in its own run, so genuine +inf keys still match
each other while NA rows match nothing — no sentinel collisions and no
extra sort pass. Mixed int keys keep an explicit NA ordering pass.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.column import Column
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.parallel import mesh as mesh_mod
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.merge")

DEVICE_MERGE_MIN_ROWS = 65536


def _merge_out_budget() -> int:
    """Max bytes the device join result may occupy: half the governor's
    HBM budget (core/memgov.py — device bytes_limit, else the
    H2O3TPU_HBM_BUDGET_MB knob). The CPU backend reports no memory
    statistics, so without a budget knob its meshes (the
    8-virtual-device test topology, usually on a small host) get a
    conservative 2GB. An accelerator that reports none is an error: no
    memory size is ever assumed for a device."""
    import os
    env = os.environ.get("H2O3TPU_MERGE_MAX_OUT_BYTES")
    if env:
        return int(env)
    from h2o3_tpu.core import memgov
    lim = memgov.governor.device_limit_bytes()
    if lim:
        return int(lim * 0.5)
    # the mesh's devices, NOT jax.devices(): init(backend="cpu") runs
    # the cloud (and this merge) on the CPU mesh beside an accelerator
    dev = mesh_mod.get_mesh().devices.flat[0]
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"memory statistics and H2O3TPU_HBM_BUDGET_MB is not set: "
            f"cannot size the device merge")
    return 2 << 30


def _all_float(keys) -> bool:
    return all(jnp.issubdtype(k.dtype, jnp.floating) for k in keys)


@partial(jax.jit, static_argnames=("n_keys",))
def _match_ranges(l_keys, l_nas, r_keys, r_nas, l_valid, r_valid, *,
                  n_keys: int):
    """Per-left-row [lo, lo+cnt) match ranges into right-sorted order.

    One combined sort of both (padded) sides; a run = maximal block of
    equal key tuples; each left row's matches are the right rows of its
    run. NA/padding rows never match: they fold to NaN (all-float keys,
    each NaN its own run) or sort into an explicitly-separated tail
    block (int keys) and left-NA counts are zeroed either way.
    """
    Lp = l_keys[0].shape[0]
    Rp = r_keys[0].shape[0]
    N = Lp + Rp
    l_pad = jnp.arange(Lp, dtype=jnp.int32) >= l_valid
    r_pad = jnp.arange(Rp, dtype=jnp.int32) >= r_valid
    comb, na_any = [], jnp.concatenate([l_pad, r_pad])
    for j in range(n_keys):
        k = jnp.concatenate([l_keys[j], r_keys[j]])
        na = jnp.concatenate([l_nas[j], r_nas[j]])
        if jnp.issubdtype(k.dtype, jnp.floating):
            na = na | jnp.isnan(k)
        na_any = na_any | na
        comb.append(k)
    fold_nan = _all_float(comb)
    if fold_nan:
        comb = [jnp.where(na_any, jnp.nan, k) for k in comb]
    else:
        comb = [jnp.where(na_any, jnp.zeros((), k.dtype), k) for k in comb]
    side = jnp.concatenate([jnp.zeros(Lp, jnp.int8), jnp.ones(Rp, jnp.int8)])

    order = jnp.arange(N, dtype=jnp.int32)
    for j in range(n_keys - 1, -1, -1):
        order = order[jnp.argsort(comb[j][order], stable=True)]
    if not fold_nan:
        order = order[jnp.argsort(na_any[order].astype(jnp.int8),
                                  stable=True)]

    s_na = na_any[order]
    s_side = side[order]
    pos = jnp.arange(N, dtype=jnp.int32)
    new_run = jnp.zeros(N, bool)
    for k in comb:
        sk = k[order]
        neq = sk != jnp.roll(sk, 1)
        if jnp.issubdtype(sk.dtype, jnp.floating):
            # NaN != NaN is True — exactly what isolates NA rows
            neq = neq | jnp.isnan(sk)
        new_run = new_run | neq
    new_run = new_run | (s_na != jnp.roll(s_na, 1))
    new_run = new_run.at[0].set(True)
    run_id = (jnp.cumsum(new_run.astype(jnp.int32)) - 1).astype(jnp.int32)
    seg_right = jax.ops.segment_sum(s_side.astype(jnp.int32), run_id,
                                    num_segments=N)
    cnt_at_pos = seg_right[run_id]
    rights_before = jnp.cumsum(s_side.astype(jnp.int32)) - s_side
    run_start = jax.lax.cummax(jnp.where(new_run, pos, 0))
    lo_at_pos = rights_before[run_start]
    cnt_at_pos = jnp.where(s_na, 0, cnt_at_pos)

    is_left = s_side == 0
    # left rows were concatenated first: their combined index IS the
    # original left row; rights scatter into the dump slot Lp
    l_orig = jnp.where(is_left, order, Lp)
    out_lo = jnp.zeros(Lp + 1, jnp.int32).at[l_orig].set(
        lo_at_pos.astype(jnp.int32))
    out_cnt = jnp.zeros(Lp + 1, jnp.int32).at[l_orig].set(
        cnt_at_pos.astype(jnp.int32))
    # right-sorted order falls out of the SAME sort (no second lexsort):
    # the right row at combined position p lands at rank rights_before[p]
    r_rank = jnp.where(is_left, Rp, rights_before)
    r_order = jnp.zeros(Rp + 1, jnp.int32).at[r_rank].set(
        jnp.where(is_left, 0, order - Lp).astype(jnp.int32))
    return out_lo[:Lp], out_cnt[:Lp], r_order[:Rp]


@jax.jit
def _total_rows(cnt, l_valid):
    """(left-join total, inner total) as device scalars."""
    valid = jnp.arange(cnt.shape[0], dtype=jnp.int32) < l_valid
    return jnp.sum(jnp.where(valid, jnp.maximum(cnt, 1), 0)), \
        jnp.sum(jnp.where(valid, cnt, 0))


@partial(jax.jit,
         static_argnames=("out_n", "left_join", "n_lcols", "n_rcols"))
def _gather_out(l_datas, l_masks, r_datas, r_masks, lo, cnt, r_order,
                l_valid, *, out_n: int, left_join: bool, n_lcols: int,
                n_rcols: int):
    """Expand match ranges and gather every output column, on device."""
    Lp = cnt.shape[0]
    valid_l = jnp.arange(Lp, dtype=jnp.int32) < l_valid
    if left_join:
        cnt_out = jnp.where(valid_l, jnp.maximum(cnt, 1), 0)
    else:
        cnt_out = jnp.where(valid_l, cnt, 0)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(cnt_out).astype(jnp.int32)])
    total = offs[-1]
    pos = jnp.arange(out_n, dtype=jnp.int32)
    # left-row-per-output-position via scatter-max + cummax: each
    # emitting left row marks its start offset with its index and the
    # running max fills the run. O(Lp + out_n) with ONE scatter — the
    # searchsorted formulation cost ~24 binary-search gather passes over
    # the offsets and dominated merge wall time on TPU.
    starts = jnp.where(cnt_out > 0, offs[:-1],
                       jnp.int32(out_n))          # silent rows → dump
    starts = jnp.minimum(starts, jnp.int32(out_n))
    marks = jnp.zeros(out_n + 1, jnp.int32).at[starts].max(
        jnp.arange(Lp, dtype=jnp.int32))
    li = jax.lax.cummax(marks[:out_n])
    within = pos - offs[li]
    matched = within < cnt[li]
    valid = pos < total
    rp = jnp.clip(lo[li] + within, 0, max(r_order.shape[0] - 1, 0))
    ri = r_order[rp]

    out_l, out_r = [], []
    for i in range(n_lcols):
        out_l.append((l_datas[i][li],
                      l_masks[i][li] | ~valid))
    for i in range(n_rcols):
        out_r.append((r_datas[i][ri],
                      r_masks[i][ri] | ~matched | ~valid))
    return tuple(out_l), tuple(out_r)


def _key_arrays(lc: Column, rc: Column, nrl: int, nrr: int):
    """Comparable (l, r) device key pairs in a common dtype, or None.

    Integer/categorical keys compare as int32 (exact); anything float
    compares as the stored f32. Categorical keys with differing domains
    remap the right codes into the left domain on the host (domains are
    small) before shipping.
    """
    if lc.data is None or rc.data is None:
        return None
    if lc.is_categorical != rc.is_categorical:
        return None
    if lc.is_categorical:
        # codes are domain indices → exact as f32 below 2^24; the
        # all-float NaN-fold path is both faster (one sort pass fewer)
        # and avoids a jaxlib CPU-mesh compile segfault observed on the
        # int32+int8 sort combination. Cardinalities at/above 2^24
        # would alias codes — host path instead of silent collisions.
        if max(len(lc.domain or []), len(rc.domain or [])) >= (1 << 24):
            return None
        ld = lc.data.astype(jnp.float32)
        if (lc.domain or []) == (rc.domain or []):
            rd = rc.data.astype(jnp.float32)
        else:
            lut = {lvl: i for i, lvl in enumerate(lc.domain or [])}
            rdom = rc.domain or []
            mp = np.asarray([lut.get(lvl, -1) for lvl in rdom], np.int32)
            codes = np.asarray(rc.data).astype(np.int64)
            na = np.asarray(rc.na_mask)
            remapped = mp[np.clip(codes, 0, max(len(rdom) - 1, 0))] \
                if len(rdom) else np.full(len(codes), -1, np.int32)
            # unseen right levels (-1) must never match: fold into NA.
            # Shard like every other column input — one unsharded
            # operand among sharded ones reproducibly segfaulted the
            # jaxlib CPU-mesh compiler
            rna = na | (remapped < 0)
            shard = mesh_mod.row_sharding()
            rd = mesh_mod.put_sharded(
                np.where(rna, 0, remapped).astype(np.float32), shard)
            return (ld, lc.na_mask, rd,
                    mesh_mod.put_sharded(rna, shard))
        return (ld, lc.na_mask, rd, rc.na_mask)
    l_int = jnp.issubdtype(lc.data.dtype, jnp.integer)
    r_int = jnp.issubdtype(rc.data.dtype, jnp.integer)
    if l_int and r_int:
        return (lc.data.astype(jnp.int32), lc.na_mask,
                rc.data.astype(jnp.int32), rc.na_mask)
    return (lc.data.astype(jnp.float32), lc.na_mask,
            rc.data.astype(jnp.float32), rc.na_mask)


def device_merge(lf: Frame, rf: Frame, key_names: List[str],
                 how: str) -> Optional[Frame]:
    """Multi-key equi-join with the whole pipeline on device; returns the
    joined Frame or None when the inputs need the host path (string/uuid
    columns, right/outer joins, tiny frames)."""
    if how not in ("inner", "left"):
        return None
    if not key_names:
        return None                      # host path raises a clear error
    if max(lf.nrows, rf.nrows) < DEVICE_MERGE_MIN_ROWS:
        return None
    if lf.nrows == 0 or rf.nrows == 0:
        return None
    l_keys, l_nas, r_keys, r_nas = [], [], [], []
    for k in key_names:
        pair = _key_arrays(lf.col(k), rf.col(k), lf.nrows, rf.nrows)
        if pair is None:
            return None
        lk, lna, rk, rna = pair
        l_keys.append(lk)
        l_nas.append(lna)
        r_keys.append(rk)
        r_nas.append(rna)
    l_cols = [lf.col(n) for n in lf.names]
    r_cols = [rf.col(n) for n in rf.names if n not in set(key_names)]
    if any(c.data is None for c in l_cols + r_cols):
        return None                      # string/uuid columns → host

    nk = len(key_names)
    lv = jnp.int32(lf.nrows)
    rv = jnp.int32(rf.nrows)
    lo, cnt, r_order = _match_ranges(tuple(l_keys), tuple(l_nas),
                                     tuple(r_keys), tuple(r_nas), lv, rv,
                                     n_keys=nk)

    left_join = how == "left"
    # ONE scalar crosses to the host — not the full cnt vector (40MB
    # at 10M rows)
    t_left, t_inner = _total_rows(cnt, lv)
    total = int(t_left) if left_join else int(t_inner)
    if total == 0:
        return _empty_like(lf, rf, key_names)
    # Low-cardinality keys make equi-joins quadratic (a 66K x 16K join
    # on a 4-level key is 208M output rows). Materializing that on the
    # device mesh starves XLA's CPU collective rendezvous (40s
    # termination timeout -> hard process abort, the round-4 crash) and
    # would OOM small HBM slices; size the output BEFORE allocating and
    # hand oversized joins to the host path, like BinaryMerge's
    # per-chunk result sizing (water/rapids/BinaryMerge.java).
    out_cells = total * (len(l_cols) + len(r_cols))
    if out_cells * 9 > _merge_out_budget():      # 8B data + 1B mask
        log.warning("device merge result %d rows x %d cols (%.1f GB) "
                    "exceeds device budget - host merge path",
                    total, len(l_cols) + len(r_cols), out_cells * 9 / 1e9)
        return None
    out_n = mesh_mod.padded_rows(total, block=8)

    out_l, out_r = _gather_out(
        tuple(c.data for c in l_cols), tuple(c.na_mask for c in l_cols),
        tuple(c.data for c in r_cols), tuple(c.na_mask for c in r_cols),
        lo, cnt, r_order, lv, out_n=out_n, left_join=left_join,
        n_lcols=len(l_cols), n_rcols=len(r_cols))

    shard = mesh_mod.row_sharding()
    collide = {c.name for c in r_cols if c.name in set(lf.names)}
    new_cols = []
    for c, (d, m) in zip(l_cols, out_l):
        nm = c.name + "_x" if c.name in collide else c.name
        new_cols.append(Column(
            name=nm, type=c.type, data=mesh_mod.put_sharded(d, shard),
            na_mask=mesh_mod.put_sharded(m, shard), nrows=total,
            domain=c.domain))
    for c, (d, m) in zip(r_cols, out_r):
        nm = c.name + "_y" if c.name in collide else c.name
        new_cols.append(Column(
            name=nm, type=c.type, data=mesh_mod.put_sharded(d, shard),
            na_mask=mesh_mod.put_sharded(m, shard), nrows=total,
            domain=c.domain))
    return Frame(new_cols, total)


def _empty_like(lf: Frame, rf: Frame, key_names: List[str]) -> Frame:
    arrays, cats, doms = {}, [], {}
    collide = {n for n in rf.names
               if n not in set(key_names) and n in set(lf.names)}
    for n in lf.names:
        c = lf.col(n)
        nm = n + "_x" if n in collide else n
        if c.is_categorical:
            arrays[nm] = np.zeros(0, np.int32)
            cats.append(nm)
            doms[nm] = c.domain
        else:
            arrays[nm] = np.zeros(0, np.float64)
    for n in rf.names:
        if n in set(key_names):
            continue
        c = rf.col(n)
        nm = n + "_y" if n in collide else n
        if c.is_categorical:
            arrays[nm] = np.zeros(0, np.int32)
            cats.append(nm)
            doms[nm] = c.domain
        else:
            arrays[nm] = np.zeros(0, np.float64)
    return Frame.from_numpy(arrays, categorical=cats, domains=doms)
