"""Device-side sort / join kernels for the Rapids munging surface.

Reference: water/rapids/RadixOrder.java + BinaryMerge.java — the
distributed MSD-radix order and the chunk-wise binary merge join. The
TPU-native collapse: XLA's sort IS the distributed sort primitive (jit
over row-sharded inputs lets SPMD partitioning insert the collectives),
so the controller never materializes the column data; it only touches
O(#matches) index metadata for joins. Host numpy remains the tiny-frame
path — sub-64K-row pyunit frames would pay more in compile+dispatch
than they save.
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

# below this many rows the host path wins (compile + device dispatch
# dominate); above it the device path also avoids a full host copy
DEVICE_SORT_MIN_ROWS = 65536


@partial(jax.jit, static_argnames=("n_keys", "valid_n"))
def _lexsort_device(keys, nas, *, n_keys: int, valid_n: int):
    """Stable ascending lexsort over ``keys`` (last key = primary is NOT
    the convention here — keys[0] is the PRIMARY key). NAs sort last
    (reference sort NA handling); padding rows sort after everything.
    Returns the [Npad] int32 permutation (valid rows first)."""
    N = keys[0].shape[0]
    order = jnp.arange(N, dtype=jnp.int32)
    # iterate minor→major keys with a stable argsort each round
    for i in range(n_keys - 1, -1, -1):
        k = keys[i]
        k = jnp.where(nas[i], jnp.inf, k)            # NA → last
        kk = k[order]
        order = order[jnp.argsort(kk, stable=True)]
    # padding rows (index >= valid_n) must land at the very end while
    # keeping the relative order of valid rows: one more stable pass
    order = order[jnp.argsort((order >= valid_n).astype(jnp.int32),
                              stable=True)]
    return order


@partial(jax.jit, static_argnames=("n_cols",))
def _gather_cols(datas, nas, order, *, n_cols: int):
    out_d, out_m = [], []
    for i in range(n_cols):
        out_d.append(datas[i][order])
        out_m.append(nas[i][order])
    return tuple(out_d), tuple(out_m)


def _f32_safe(c) -> bool:
    """True when the column's values survive a float32 cast EXACTLY, so
    the device compare order matches the host float64 path: float
    columns are already stored f32; integer columns qualify only within
    the f32-exact range ±2^24 (an int32 ID column of ~1e9 would collapse
    nearby keys into spurious ties/matches)."""
    if c.data is None:
        return False
    if jnp.issubdtype(c.data.dtype, jnp.floating):
        return True
    if c.data.dtype in (jnp.int8, jnp.int16, jnp.uint8, jnp.uint16):
        return True                              # always f32-exact
    from h2o3_tpu.frame.rollups import rollups
    try:
        stats = rollups(c)
        return max(abs(float(stats.get("min", 0))),
                   abs(float(stats.get("max", 0)))) < 2 ** 24
    except Exception:
        return False


def device_sort(frame: Frame, key_names: List[str],
                ascending: List[bool]) -> Optional[Frame]:
    """Sort ``frame`` by key columns entirely on device; returns the new
    Frame or None when the frame is not device-sortable (string columns
    ride along on the host, so their presence forces the host path)."""
    if frame.nrows < DEVICE_SORT_MIN_ROWS:
        return None
    cols = [frame.col(n) for n in frame.names]
    if any(c.data is None for c in cols):
        return None                       # string/uuid columns → host
    if any(not _f32_safe(frame.col(n)) for n in key_names):
        return None                       # f32-unsafe keys → host path
    keys, nas = [], []
    for n, asc in zip(key_names, ascending):
        c = frame.col(n)
        v = c.data.astype(jnp.float32)
        keys.append(v if asc else -v)
        nas.append(c.na_mask)
    order = _lexsort_device(tuple(keys), tuple(nas),
                            n_keys=len(keys), valid_n=frame.nrows)
    datas, masks = _gather_cols(tuple(c.data for c in cols),
                                tuple(c.na_mask for c in cols), order,
                                n_cols=len(cols))
    shard = mesh_mod.row_sharding()
    new_cols = []
    for c, d, m in zip(cols, datas, masks):
        new_cols.append(Column(
            name=c.name, type=c.type,
            data=mesh_mod.put_sharded(d, shard),
            na_mask=mesh_mod.put_sharded(m, shard),
            nrows=frame.nrows, domain=c.domain))
    return Frame(new_cols, frame.nrows)


@partial(jax.jit, static_argnames=("l_valid", "r_valid"))
def _join_core(l_key, r_key, *, l_valid: int, r_valid: int):
    """The whole device half of the join as ONE program: sort the right
    keys, binary-search every left key (BinaryMerge's per-key search,
    batched). One compiled call = one dispatch; the previous eager
    formulation paid a dispatch (and a host round trip) per op."""
    lk = jnp.where(jnp.isnan(l_key[:l_valid]), jnp.inf, l_key[:l_valid])
    rk = jnp.where(jnp.isnan(r_key[:r_valid]), jnp.inf, r_key[:r_valid])
    r_order = jnp.argsort(rk, stable=True)
    r_sorted = rk[r_order]
    lo = jnp.searchsorted(r_sorted, lk, side="left")
    hi = jnp.searchsorted(r_sorted, lk, side="right")
    return r_order.astype(jnp.int32), lo.astype(jnp.int32), \
        hi.astype(jnp.int32), jnp.isinf(lk)


def device_join_index(l_key: jax.Array, r_key: jax.Array,
                      l_valid: int, r_valid: int):
    """Single-key equi-join indices with the heavy work on device.

    Returns host arrays (l_idx, r_idx) of matching row pairs (inner
    join core; callers add unmatched rows for left/right/outer). The
    device does the O(N log N) sort + binary searches; the host only
    expands the per-row match ranges (O(#matches) memcpy).
    """
    r_order, lo, hi, nan_l = (np.asarray(a) for a in _join_core(
        l_key, r_key, l_valid=l_valid, r_valid=r_valid))
    lo_h, hi_h = lo, hi
    cnt = np.where(nan_l, 0, hi_h - lo_h)
    l_idx = np.repeat(np.arange(l_valid), cnt)
    # per-left-row runs lo..hi expanded into sorted-right positions
    starts = np.repeat(lo_h, cnt)
    within = np.arange(cnt.sum()) - np.repeat(
        np.concatenate([[0], np.cumsum(cnt)[:-1]]), cnt)
    r_pos = starts + within
    r_idx = r_order[r_pos]
    return l_idx, r_idx
