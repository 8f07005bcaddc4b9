"""Column — the Vec analogue: one typed, distributed column.

Reference: water/fvec/Vec.java (distributed compressed column split into
Chunks, ~20 codec classes picked per chunk by NewChunk.compress,
water/fvec/NewChunk.java:1133). TPU-native replacement per SURVEY §7:
chunk codecs collapse into dtype-narrowed dense device arrays + an NA
bitmask + a categorical dictionary. Rows shard over the mesh 'data' axis;
padding rows (mesh alignment) are marked NA so every reduction that
honours the mask is exact.

Types (reference Vec.T_NUM/T_CAT/T_TIME/T_STR/T_UUID, water/fvec/Vec.java):
- numeric:     float32/float64/int narrowed device array
- categorical: int32 codes + host-side ``domain`` list (water/parser/
               Categorical.java interning becomes pandas factorize)
- time:        int64 epoch-millis device array
- string:      host-side numpy object array (never on device; the
               reference likewise keeps CStrChunk out of math paths)
"""

from __future__ import annotations

import dataclasses
from functools import partial as _partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

T_NUM, T_CAT, T_TIME, T_STR = "numeric", "categorical", "time", "string"
T_UUID = "uuid"      # host-side 128-bit ids (C16Chunk role) — never in math


@dataclasses.dataclass
class Column:
    name: str
    type: str                        # T_NUM | T_CAT | T_TIME | T_STR
    data: Optional[jax.Array]        # device array, padded length; None for T_STR
    na_mask: Optional[jax.Array]     # bool device array, True = missing
    nrows: int                       # logical (unpadded) length
    domain: Optional[List[str]] = None   # categorical levels
    strings: Optional[np.ndarray] = None  # host strings for T_STR
    _rollups: Optional[dict] = None      # cached stats (RollupStats analogue)

    @property
    def is_numeric(self) -> bool:
        return self.type in (T_NUM, T_TIME)

    @property
    def is_categorical(self) -> bool:
        return self.type == T_CAT

    @property
    def is_uuid(self) -> bool:
        return self.type == T_UUID

    @property
    def cardinality(self) -> int:
        return len(self.domain) if self.domain else 0

    def numeric_view(self) -> jax.Array:
        """float32 view with NaN at NA positions — the math-path input.

        Analogue of Chunk.atd() returning NaN for missing
        (water/fvec/Chunk.java).
        """
        x = self.data.astype(jnp.float32)
        return jnp.where(self.na_mask, jnp.nan, x)

    def host_view(self) -> np.ndarray:
        """READ-ONLY cached host view, logical rows only, NaN/None NAs.

        Cached: columns are immutable (mutation makes new columns), and
        every device→host fetch costs a host round trip regardless of
        size — one batched fetch of (data, mask), then reuse. Callers
        must not mutate;
        use to_numpy() for an owned copy.
        """
        if self.type in (T_STR, T_UUID):
            return self.strings[: self.nrows]
        host = getattr(self, "_host_cache", None)
        if host is None:
            if getattr(self, "_part_cache", None) is not None:
                # host-partitioned columns get their host cache seeded
                # eagerly at ingest (seed_partitioned_host_caches — a
                # guaranteed collective point). Assembling it HERE would
                # require a cross-process device collective, and
                # host_view() runs in single-process contexts (REST
                # handlers, scheduled work items) whose contract forbids
                # collectives — so a missing cache is a bug, never
                # something to gather lazily.
                raise RuntimeError(
                    f"partitioned column {self.name!r} has no host cache;"
                    " it must be seeded at ingest"
                    " (seed_partitioned_host_caches)")
            from h2o3_tpu.parallel.mesh import fetch_replicated
            data, mask = fetch_replicated((self.data, self.na_mask))
            x = data[: self.nrows].astype(np.float64)
            x[mask[: self.nrows]] = np.nan
            host = x
            object.__setattr__(self, "_host_cache", host)
        return host

    def to_numpy(self) -> np.ndarray:
        """Host copy of host_view() — callers may mutate their copy."""
        if self.type in (T_STR, T_UUID):
            return self.strings[: self.nrows].copy()
        return self.host_view().copy()


def prefetch_host(cols: List["Column"]) -> None:
    """Fill the host caches of many columns with ONE device→host fetch.

    N sequential to_numpy calls cost N host round trips;
    jax.device_get on the whole pytree batches them into one transfer.
    """
    todo = [c for c in cols
            if c.type not in (T_STR, T_UUID)
            and getattr(c, "_host_cache", None) is None]
    if not todo:
        return
    stale = [c.name for c in todo
             if getattr(c, "_part_cache", None) is not None]
    if stale:
        # see host_view(): partitioned host caches are seeded at ingest;
        # prefetch_host may run in single-process contexts, so it must
        # never assemble them here (that would take a collective)
        raise RuntimeError(
            f"partitioned columns {stale} have no host cache; they must "
            "be seeded at ingest (seed_partitioned_host_caches)")
    from h2o3_tpu.parallel.mesh import fetch_replicated
    fetched = fetch_replicated([(c.data, c.na_mask) for c in todo])
    for c, (data, mask) in zip(todo, fetched):
        x = data[: c.nrows].astype(np.float64)
        x[mask[: c.nrows]] = np.nan
        object.__setattr__(c, "_host_cache", x)


def _int_codec(values: np.ndarray):
    """The narrowest of int8 / int16 / int32 that holds every value of an
    integer (or bool) array, as the float path below picks it; None
    where none does (that path then makes it float32)."""
    if values.size == 0:
        return np.int8
    lo, hi = int(values.min()), int(values.max())
    for dt in (np.int8, np.int16):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return dt
    return np.int32 if -2**31 < lo and hi < 2**31 else None


def column_from_numpy(name: str, values: np.ndarray, nrows_padded: int,
                      sharding, domain: Optional[List[str]] = None,
                      time: bool = False) -> Column:
    """Build a Column from host data, narrowing dtype (codec selection).

    The reference picks a Chunk codec per 1K-1M-element chunk
    (NewChunk.compress); here one dtype per column: int8/int16/int32 for
    integral ranges, float32 otherwise, int32 codes for categoricals.
    """
    from h2o3_tpu.telemetry.spans import span
    values = np.asarray(values)
    n = values.shape[0]
    pad = nrows_padded - n

    # the host passes that pick the codec and pad: one span a column
    with span("frame.encode", columns=1, host_bytes=int(values.nbytes)):
        if values.dtype == object or values.dtype.kind in "US":
            if domain is None:
                # categorical via interning, domain sorted lexicographically
                # like the reference parser (water/parser/Categorical.java)
                import pandas as pd
                codes, uniques = pd.factorize(values, sort=True)
                domain = [str(u) for u in uniques]
                values = codes.astype(np.int32)
            else:
                # explicit domain: map labels to codes, unseen/None → NA
                lut = {lvl: i for i, lvl in enumerate(domain)}
                values = np.asarray([lut.get(v, -1) if v is not None else -1
                                     for v in values], np.int32)
            na = values < 0
            data = np.where(na, 0, values).astype(np.int32)
            ctype = T_CAT
        elif domain is not None:
            na = values < 0
            if values.dtype.kind not in "iub":      # every integer is finite
                na |= ~np.isfinite(values.astype(np.float64))
            data = np.where(na, 0, values).astype(np.int32)
            ctype = T_CAT
        elif values.dtype.kind in "iub" \
                and (codec := _int_codec(values)) is not None:
            # integers need no float64 round trip: no NA, already whole,
            # and their range alone picks the codec (the passes below
            # made 44 ns a cell of set-up: PERF.md §5)
            data = values.astype(codec)
            na = np.zeros(n, bool)
            ctype = T_NUM
        else:
            vals64 = values.astype(np.float64)
            na = ~np.isfinite(vals64)
            clean = np.where(na, 0.0, vals64)
            if np.all(clean == np.round(clean)) and np.all(np.abs(clean) < 2**31):
                lo, hi = clean.min() if n else 0, clean.max() if n else 0
                if -128 <= lo and hi <= 127:
                    data = clean.astype(np.int8)
                elif -32768 <= lo and hi <= 32767:
                    data = clean.astype(np.int16)
                else:
                    data = clean.astype(np.int32)
            else:
                data = clean.astype(np.float32)
            ctype = T_NUM

        data = np.pad(data, (0, pad))
        na = np.pad(na, (0, pad), constant_values=True)  # padding rows are NA
    from h2o3_tpu.parallel.mesh import put_sharded
    if time and ctype == T_NUM:
        # Vec.T_TIME: epoch millis. Device storage remains f32 (x64 is
        # off under jit — int64 would silently truncate to int32), so
        # device math on times is ~65-131s-granular; all host paths
        # (rapids time ops, downloads) read the exact f64 cache below.
        ctype = T_TIME
    with span("frame.put", columns=1,
              host_bytes=int(data.nbytes + na.nbytes)):
        col = Column(
            name=name, type=ctype,
            data=put_sharded(data, sharding),
            na_mask=put_sharded(na, sharding),
            nrows=n, domain=domain)
    if ctype in (T_NUM, T_TIME) and data.dtype == np.float32:
        # seed the host cache with the ORIGINAL float64 values: the
        # munging/metadata path (rapids reducers, quantiles, mmult)
        # then matches f64 oracles exactly, while the device keeps the
        # f32 math-path copy. Same layout to_numpy would build.
        host64 = vals64.copy()
        host64[na[:n]] = np.nan
        object.__setattr__(col, "_host_cache", host64)
    elif not getattr(sharding, "is_fully_addressable", True):
        # multi-process cloud: every process holds the same full host
        # copy at ingest (the put_sharded contract), so retain the host
        # view NOW — host_view() would otherwise have to allgather the
        # cross-process shards, and scheduled work items
        # (parallel/scheduler.py) must never issue a collective. One f64
        # host copy per column, multi-process clouds only.
        host64 = data[:n].astype(np.float64)
        host64[na[:n]] = np.nan
        object.__setattr__(col, "_host_cache", host64)
    return col


def gather_partitioned_host(slabs):
    """Assemble full host arrays from per-process partitioned slabs
    (pytree in, matching pytree of full arrays out). Process order IS
    row order — asserted by Frame.from_numpy_partitioned at ingest.
    Single process: the slab already covers every row.

    COLLECTIVE: multihost_utils.process_allgather is an SPMD *device*
    collective — every process must reach this call at the same program
    point, or the pod wedges until the cloud timeout. The only caller is
    seed_partitioned_host_caches under Frame.from_numpy_partitioned,
    which is collective by contract; never call this from a
    single-process context (REST handlers, scheduled work items).

    Slabs travel as raw BYTES (uint8 views, reinterpreted on arrival):
    pushing the f64 host slabs through jax directly would silently
    truncate them to f32 (x64 is off under jit), breaking the exact-f64
    host-view contract every oracle test pins."""
    import jax
    if jax.process_count() == 1:
        return slabs
    from jax.experimental import multihost_utils
    leaves, treedef = jax.tree_util.tree_flatten(slabs)
    as_bytes = [np.ascontiguousarray(v).view(np.uint8) for v in leaves]
    gathered = jax.device_get(
        multihost_utils.process_allgather(as_bytes, tiled=True))
    out = [np.asarray(g).view(v.dtype)
           for g, v in zip(gathered, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def seed_partitioned_host_caches(cols: List["Column"]) -> None:
    """Fill the host caches of host-partitioned columns with ONE batched
    slab allgather (exact f64 — the device arrays may be narrowed to
    f32). Called by Frame.from_numpy_partitioned, a guaranteed
    collective point, so later host_view()/prefetch_host() calls from a
    SINGLE process (REST handlers, scheduled work items — contexts whose
    contract forbids cross-process collectives) hit the cache and never
    need peer participation — the partitioned analogue of
    column_from_numpy's eager multi-process host-cache seed. Each
    process ends up holding the full f64 host view (same host-memory
    footprint as the replicated ingest); device data stays partitioned.
    """
    todo = [c for c in cols
            if getattr(c, "_part_cache", None) is not None
            and getattr(c, "_host_cache", None) is None]
    if not todo:
        return
    gathered = gather_partitioned_host([c._part_cache for c in todo])
    for c, full in zip(todo, gathered):
        object.__setattr__(c, "_host_cache", np.asarray(full)[: c.nrows])


def column_from_partitioned(name: str, values: np.ndarray, *,
                            span, nrows: int, npad: int, sharding,
                            domain: Optional[List[str]] = None,
                            facts: Optional[dict] = None,
                            time: bool = False) -> Column:
    """Host-partitioned complement of ``column_from_numpy``: ``values``
    holds ONLY this process's logical rows (global rows ``[span[0],
    min(span[1], nrows))``), every codec decision comes from the
    globally-merged ``facts``/``domain`` (frame/partition.py) — never
    from local data, or peers would pick divergent dtypes — and
    placement goes through ``put_partitioned`` so no process ever
    materializes a peer's rows. Bit-identical to ``column_from_numpy``
    on a single process, where the local slab is the whole column.
    """
    from h2o3_tpu.parallel.mesh import put_partitioned
    values = np.asarray(values)
    lo, hi = span
    local_n = values.shape[0]
    pad = (hi - lo) - local_n        # mesh-padding rows homed here
    vals64 = None

    if values.dtype == object or values.dtype.kind in "US":
        assert domain is not None, (
            "partitioned string-typed ingest requires the merged domain")
        lut = {lvl: i for i, lvl in enumerate(domain)}
        # str-coerce before the lookup: the merged domain holds str(u)
        # levels (partition.local_str_levels), so non-str objects in an
        # object column (ints/floats mixed with strings) must code
        # through their str form like the replicated auto-factorize
        # path — not silently become NA
        codes = np.asarray(
            [lut.get(v if isinstance(v, str) else str(v), -1)
             if v is not None else -1
             for v in values], np.int32)
        na = codes < 0
        data = np.where(na, 0, codes).astype(np.int32)
        ctype = T_CAT
    elif domain is not None:
        na = (values < 0) | ~np.isfinite(values.astype(np.float64))
        data = np.where(na, 0, values).astype(np.int32)
        ctype = T_CAT
    else:
        vals64 = values.astype(np.float64)
        na = ~np.isfinite(vals64)
        clean = np.where(na, 0.0, vals64)
        if facts is None:
            from h2o3_tpu.frame.partition import (local_numeric_facts,
                                                  merge_numeric_facts)
            facts = merge_numeric_facts([local_numeric_facts(values)])
        if facts["integral"]:
            data = clean.astype(block_int_dtype(facts["lo"], facts["hi"]))
        else:
            data = clean.astype(np.float32)
        ctype = T_NUM

    data = np.pad(data, (0, pad))
    na = np.pad(na, (0, pad), constant_values=True)
    if time and ctype == T_NUM:
        ctype = T_TIME
    col = Column(
        name=name, type=ctype,
        data=put_partitioned(data, sharding, (npad,)),
        na_mask=put_partitioned(na, sharding, (npad,)),
        nrows=nrows, domain=domain)
    # exact-f64 host semantics: retain THIS process's padded f64 slab;
    # Frame.from_numpy_partitioned then assembles the full host view
    # from every process's slabs in one batched device collective
    # (seed_partitioned_host_caches) while all processes are still at
    # the same program point — host_view() itself must stay
    # collective-free
    slab = data.astype(np.float64)
    slab[na] = np.nan
    if vals64 is not None and data.dtype == np.float32:
        slab[:local_n] = np.where(na[:local_n], np.nan, vals64)
    object.__setattr__(col, "_part_cache", slab)
    return col


# ---------------------------------------------------------------------------
# Block assembly — the chunk-parallel ingest building blocks.
#
# Reference: water/fvec/NewChunk.compress picks a codec per chunk; here a
# NumericBlock carries one window's narrowed values + NA mask + the
# integrality/range facts, and a BlockAccumulator (per column) ships each
# block to HBM as an async device_put, interns categorical domains
# globally, and reconciles the per-block narrowing into the final column
# dtype. The tokenize stage (pure, runs on worker threads) builds blocks;
# the in-order merge stage (caller thread) owns the accumulator, so the
# parallel and sequential ingest paths are bit-identical by construction.
# ---------------------------------------------------------------------------


def block_int_dtype(lo: float, hi: float):
    """Narrowest int dtype holding [lo, hi] (int8/int16/int32)."""
    if -128 <= lo and hi <= 127:
        return np.int8
    if -32768 <= lo and hi <= 32767:
        return np.int16
    return np.int32


@dataclasses.dataclass
class NumericBlock:
    """One window's worth of a numeric column, already narrowed."""
    clean: np.ndarray           # NA positions zero-filled
    na: np.ndarray              # bool mask, True = missing
    dtype: object               # narrow storage dtype for this block
    lo: float                   # block min of clean (0.0 when empty)
    hi: float                   # block max of clean (0.0 when empty)
    is_int: bool                # every value integral and |v| < 2**31


def narrow_numeric_block(values: np.ndarray,
                         na: Optional[np.ndarray] = None) -> NumericBlock:
    """Per-chunk codec selection (the NewChunk.compress role).

    With na=None the mask is derived from non-finite values (the CSV
    tokenizer path); Arrow callers pass validity-derived masks explicitly
    so integer buffers narrow without a float round trip.
    """
    if na is None:
        na = ~np.isfinite(values)
    else:
        na = np.asarray(na, bool)
    # NA-free blocks keep their buffer (zero-copy from Arrow readers:
    # device_put then ships the original buffer when the narrow dtype
    # already matches); blocks never get mutated downstream
    clean = np.where(na, 0, values) if na.any() else values
    # range check in float64: np.abs on int64 extremes would overflow
    # and sneak past the < 2**31 gate (f64 is a no-op copy=False view
    # on the CSV path, which is already float64)
    clean64 = clean.astype(np.float64, copy=False)
    is_int = bool(np.all(clean == np.round(clean)) and
                  np.all(np.abs(clean64) < 2**31))
    lo = float(clean64.min()) if clean.size else 0.0
    hi = float(clean64.max()) if clean.size else 0.0
    if is_int and clean.size:
        bd = block_int_dtype(lo, hi)
    elif is_int:
        bd = np.int8
    else:
        bd = np.float32
    return NumericBlock(clean=clean, na=na, dtype=bd,
                        lo=lo, hi=hi, is_int=is_int)


def block_values_f64(nb: NumericBlock) -> np.ndarray:
    """Reconstruct the block's float64 values with NaN at NAs (the
    categorical-promotion input)."""
    vals = nb.clean.astype(np.float64)
    if nb.na.any():
        vals[nb.na] = np.nan
    return vals


@_partial(jax.jit, static_argnames=("npad", "dtype", "sizes"))
def _assemble_col(parts, bit_parts, *, npad: int, dtype: str,
                  sizes: tuple):
    """Concatenate the per-window device blocks, upcast to the column's
    final dtype, pad, and build the NA mask from per-block packed bits
    (None = block had no NAs) — all on device. One program per
    (file-window-shape, dtype) signature; the persistent XLA cache
    amortizes it across runs."""
    from h2o3_tpu.parallel import mesh as mesh_mod
    segs = [p.astype(dtype) for p in parts]
    x = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
    x = jnp.pad(x, (0, npad - x.shape[0]))
    x = jax.lax.with_sharding_constraint(x, mesh_mod.row_sharding())
    msegs = []
    for bits, sz in zip(bit_parts, sizes):
        if bits is None:
            msegs.append(jnp.zeros(sz, bool))
        else:
            idx = jnp.arange(sz, dtype=jnp.int32)
            b = bits[idx >> 3]
            msegs.append((
                (b >> (7 - (idx & 7)).astype(jnp.uint8)) & 1).astype(bool))
    m = msegs[0] if len(msegs) == 1 else jnp.concatenate(msegs)
    m = jnp.pad(m, (0, npad - m.shape[0]), constant_values=True)
    m = jax.lax.with_sharding_constraint(m, mesh_mod.row_sharding())
    return x, m


class BlockAccumulator:
    """Per-column accumulator: per-window NARROWED device blocks + the
    global categorical domain.

    Each window's slice ships immediately as an async device_put at the
    window-local narrow dtype (int8/int16 when the block's values fit —
    the NewChunk.compress codec role, applied per chunk like the
    reference), and NA masks ship as packed BITS only for blocks that
    have NAs. Host→device bytes are the budget (the transfer rate is
    not measured on the current chip set-up): narrowing + bit-masks +
    transfer/tokenize overlap together turn
    sum(tokenize, transfer-at-4B/cell) into ~max(tokenize,
    transfer-at-1-2B/cell).

    Order contract: add_* calls MUST arrive in window order (the merge
    stage serializes them) — domain interning is append-only and block
    codes are final the moment they are pushed.
    """

    def __init__(self, name: str, time: bool = False):
        self.name = name
        self.time = time                     # finish() → T_TIME column
        self.parts: List[jax.Array] = []     # device blocks (async put)
        self.bit_parts: List[Optional[jax.Array]] = []
        self.sizes: List[int] = []
        self.levels: Dict[str, int] = {}     # global categorical domain
        self.order: List[str] = []
        self.is_cat = False

    def _push(self, clean: np.ndarray, na: np.ndarray, dtype):
        self.parts.append(jax.device_put(clean.astype(dtype, copy=False)))
        self.bit_parts.append(
            jax.device_put(np.packbits(na)) if na.any() else None)
        self.sizes.append(len(clean))

    def add_numeric_block(self, nb: NumericBlock):
        """Merge one pre-narrowed window block (tokenize-stage output)."""
        if self.is_cat:
            # numeric window inside a categorical column: values become
            # their string levels (the reference re-types the column)
            self.add_categorical(np.zeros(0, np.int32), [],
                                 raw_numeric=block_values_f64(nb))
            return
        # per-chunk integrality/range tracking for the FINAL dtype
        if not hasattr(self, "_all_int"):
            self._all_int, self._lo, self._hi = True, np.inf, -np.inf
        if self._all_int and nb.is_int:
            if nb.clean.size:
                self._lo = min(self._lo, nb.lo)
                self._hi = max(self._hi, nb.hi)
        else:
            self._all_int = False
        self._push(nb.clean, nb.na, nb.dtype)

    def add_numeric(self, arr: np.ndarray):
        self.add_numeric_block(narrow_numeric_block(arr))

    def add_categorical(self, codes: np.ndarray, domain: List[str],
                        raw_numeric: Optional[np.ndarray] = None):
        if not self.is_cat and self.parts:
            # column promoted to categorical mid-stream: earlier numeric
            # blocks are fetched back and re-expressed as levels (rare
            # type-drift path; one host round trip per prior window —
            # the reference re-parses the column in the same situation)
            old = list(zip(self.parts, self.bit_parts, self.sizes))
            self.parts, self.bit_parts, self.sizes = [], [], []
            self.is_cat = True
            for part, bits, sz in old:
                vals = np.asarray(part, np.float64)
                if bits is not None:
                    na_old = np.unpackbits(
                        np.asarray(bits), count=sz).astype(bool)
                    vals[na_old] = np.nan
                self.add_categorical(np.zeros(0, np.int32), [],
                                     raw_numeric=vals)
        self.is_cat = True
        if raw_numeric is not None:
            strs = np.array([None if np.isnan(v) else
                             (f"{v:g}") for v in raw_numeric], object)
            codes = np.empty(len(strs), np.int32)
            for i, s in enumerate(strs):
                if s is None:
                    codes[i] = -1
                else:
                    k = self.levels.get(s)
                    if k is None:
                        k = self.levels[s] = len(self.order)
                        self.order.append(s)
                    codes[i] = k
            remapped = codes
        else:
            lut = np.empty(max(len(domain), 1), np.int32)
            for j, lvl in enumerate(domain):
                k = self.levels.get(lvl)
                if k is None:
                    k = self.levels[lvl] = len(self.order)
                    self.order.append(lvl)
                lut[j] = k
            remapped = np.where(codes >= 0, lut[np.maximum(codes, 0)], -1)
        na = remapped < 0
        clean = np.where(na, 0, remapped)
        # interning is append-only, so block codes are final; narrow by
        # the block's max level index (upcast to int32 at assembly)
        self._push(clean, na,
                   block_int_dtype(0, float(clean.max(initial=0))))

    def finish(self, n: int, npad: int) -> Column:
        dtype = np.float32
        if self.is_cat:
            dtype = np.int32
        elif getattr(self, "_all_int", False):
            dtype = block_int_dtype(self._lo, self._hi)
        data, na = _assemble_col(tuple(self.parts), tuple(self.bit_parts),
                                 npad=npad, dtype=np.dtype(dtype).name,
                                 sizes=tuple(self.sizes))
        self.parts, self.bit_parts, self.sizes = [], [], []
        if self.is_cat:
            return Column(name=self.name, type=T_CAT, data=data,
                          na_mask=na, nrows=n, domain=list(self.order))
        return Column(name=self.name, type=T_TIME if self.time else T_NUM,
                      data=data, na_mask=na, nrows=n)
