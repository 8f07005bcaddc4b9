"""Device-mesh management — the TPU-native replacement for H2O "clouding".

Reference: the cloud is N symmetric JVMs agreeing on membership via
heartbeat gossip (water/Paxos.java:27, water/HeartBeatThread.java:16) and
reducing over a binary node tree (water/MRTask.java:716-756). TPU-native:
membership is ``jax.distributed`` (control plane), the node tree is a
``jax.sharding.Mesh`` and every reduce is an XLA collective over ICI/DCN.

Axes:
- ``data``  — row-sharding axis; the analogue of H2O's chunk-to-node hash
  distribution (water/fvec/Vec.java chunk homing). All MRTask-style work
  shards rows over it and reduces with ``psum``.
- ``model`` — reserved width-sharding axis (wide Gram matrices for GLM with
  huge one-hot spaces; SURVEY §2.4 item 6). Size 1 on small meshes.

Multi-slice pods map as mesh shape (dcn_slices, ici_chips_per_slice)
flattened into ('data','model'); shardings are laid out so psum rides ICI
first (innermost axis varies fastest across a slice).
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_GLOBAL_MESH: Optional[Mesh] = None


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              data_axis: int = 0, model_axis: int = 1) -> Mesh:
    """Build the (data, model) mesh. data_axis=0 ⇒ use all devices."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if data_axis <= 0:
        data_axis = n // model_axis
    assert data_axis * model_axis <= n, (
        f"mesh {data_axis}x{model_axis} needs more than {n} devices")
    dev = np.array(devices[: data_axis * model_axis]).reshape(
        data_axis, model_axis)
    return Mesh(dev, (DATA_AXIS, MODEL_AXIS))


def set_global_mesh(mesh: Optional[Mesh]) -> None:
    """Install the process mesh; ``None`` resets so the next
    ``get_mesh()`` (or ``init()``) rebuilds from current devices —
    cloud.shutdown() must not leave a stale mesh behind."""
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


# per-thread mesh override (parallel/scheduler.py worker loops): scheduled
# work items train against a mesh over the process's LOCAL devices so a fit
# never issues a cross-process collective — a dead peer then cannot wedge
# it, and a single local device matches the single-process reference mesh
# bit-for-bit (the scheduler's determinism contract)
_MESH_OVERRIDE: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("h2o3tpu_mesh_override", default=None)


def get_mesh() -> Mesh:
    """The process mesh (analogue of the static H2O.CLOUD, water/H2O.java)."""
    global _GLOBAL_MESH
    override = _MESH_OVERRIDE.get()
    if override is not None:
        return override
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = make_mesh()
    return _GLOBAL_MESH


@contextlib.contextmanager
def local_mesh_scope(model_axis: int = 1):
    """Route every ``get_mesh()`` in this thread to a mesh over
    ``jax.local_devices()`` — the execution context for scheduled work
    items (each host trains its leased combos on its own chips while the
    global mesh stays reserved for collective-plane work)."""
    mesh = make_mesh(jax.local_devices(), model_axis=model_axis)
    token = _MESH_OVERRIDE.set(mesh)
    try:
        yield mesh
    finally:
        _MESH_OVERRIDE.reset(token)


def data_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape[DATA_AXIS]


def row_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Rows sharded over 'data', everything else replicated."""
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P())


def padded_rows(n: int, mesh: Optional[Mesh] = None, block: int = 1) -> int:
    """Rows padded so every data-shard holds an equal, block-aligned count,
    then rounded up to a shape BUCKET: at most 16 distinct padded sizes
    per power of two (≤6.25% padding waste).

    The alignment is the analogue of H2O chunk alignment
    (water/fvec/Vec.java ESPC layout); the bucketing is pure XLA
    economics — every distinct row count is a fresh compilation, and
    workflows like k-fold CV produce many near-identical sizes
    (n·(k-1)/k for k=2..10) that would otherwise each pay the 20-40s
    trace+compile. Padding rows carry weight 0 so reductions ignore
    them; all math paths already mask by weight.
    """
    d = data_size(mesh) * max(block, 1)
    aligned = ((n + d - 1) // d) * d
    if aligned <= 4 * d:
        return aligned
    # small frames: 4 buckets/octave (≤25% padding waste, trivial compute
    # at this scale) — k-fold CV on a small frame otherwise compiles a
    # fresh program per fold size; large frames: 16/octave (≤6.25%)
    shift = 3 if aligned < 65536 else 5
    q = 1 << (max(aligned.bit_length() - shift, 0))
    bucket = ((aligned + q - 1) // q) * q
    # keep mesh/block alignment after bucketing
    return ((bucket + d - 1) // d) * d


def global_fit_mode() -> str:
    """The ``H2O3TPU_GLOBAL_FIT`` knob: ``auto`` (default) | ``on`` |
    ``off``. Gates host-partitioned frame placement (each process homes
    only its own row shards) vs the legacy fully-replicated ingest where
    every process holds the complete host copy. ``auto`` and ``on`` are
    equivalent today (partitioned placement whenever the caller uses the
    partitioned ingest surface); ``off`` devolves partitioned ingest to
    the legacy replicated layout. The single-process path is bit-identical
    in every mode — partitioning one process's rows is the identity."""
    mode = os.environ.get("H2O3TPU_GLOBAL_FIT")
    if not mode:
        from h2o3_tpu.core.config import ARGS
        mode = getattr(ARGS, "global_fit", "auto") or "auto"
    mode = str(mode).lower()
    return mode if mode in ("auto", "on", "off") else "auto"


def global_fit_enabled() -> bool:
    """True when frames may keep host-partitioned device data."""
    return global_fit_mode() != "off"


def partition_bounds(npad: int, mesh: Optional[Mesh] = None) -> Tuple[int, int]:
    """This process's contiguous padded row range ``[lo, hi)`` under
    ``row_sharding(mesh)`` — the shard-homing contract: global row *i*
    lives on the process whose bounds contain it (the analogue of
    water/fvec/Vec.java chunk homing, ESPC layout). Raises if this
    process's addressable shards do not tile one contiguous interval
    (never the case for the process-major device order jax builds)."""
    mesh = mesh or get_mesh()
    sh = row_sharding(mesh)
    spans = set()
    for idx in sh.addressable_devices_indices_map((int(npad),)).values():
        s = idx[0]
        spans.add((s.start or 0, int(npad) if s.stop is None else s.stop))
    spans = sorted(spans)
    lo, hi = spans[0][0], spans[0][0]
    for start, stop in spans:
        if start > hi:
            raise ValueError(
                f"non-contiguous local row shards {spans} — partitioned "
                "ingest requires process-major device order")
        hi = max(hi, stop)
    return lo, hi


def owned_rows(nrows: int, mesh: Optional[Mesh] = None, block: int = 1,
               pad_to: Optional[int] = None) -> Tuple[int, int]:
    """The logical (unpadded) row range ``[lo, hi)`` this process must
    supply to a partitioned ingest of an ``nrows``-row frame — what a
    multi-host reader asks before loading its slice of the source (the
    PR 12 ingest chunk-boundary contract, io/chunking.py). Clipped to
    ``nrows``: a process whose shards are pure mesh padding gets an
    empty range."""
    npad = padded_rows(nrows, mesh, block)
    if pad_to is not None:
        npad = max(npad, int(pad_to))
    lo, hi = partition_bounds(npad, mesh)
    return min(lo, nrows), min(hi, nrows)


def put_partitioned(local_block, sharding, global_shape):
    """Assemble a global row-sharded array from ONLY this process's rows.

    ``local_block`` is the padded local slab covering this process's
    ``partition_bounds`` range; no process ever materializes (or ships)
    another process's rows — the host-partitioned complement of
    ``put_sharded``'s replicated-ingest contract. Single process: the
    slab IS the full array, so this degenerates to device_put (bit-
    identical to put_sharded)."""
    import numpy as _np
    local_block = _np.asarray(local_block)
    global_shape = tuple(int(s) for s in global_shape)
    if getattr(sharding, "is_fully_addressable", True):
        assert local_block.shape[0] == global_shape[0], (
            f"single-process slab {local_block.shape} != {global_shape}")
        return jax.device_put(local_block, sharding)
    imap = sharding.addressable_devices_indices_map(global_shape)
    lo = min((idx[0].start or 0) for idx in imap.values())
    shards = []
    for dev, idx in imap.items():
        s = idx[0]
        start = (s.start or 0) - lo
        stop = (global_shape[0] if s.stop is None else s.stop) - lo
        shards.append(jax.device_put(local_block[start:stop], dev))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards)


def put_sharded(host_array, sharding):
    """Place a host array onto a (possibly multi-process) sharding.

    Single process: plain device_put. Multi-process (jax.distributed
    cloud — the @CloudSize(n) tier): every process holds the SAME full
    host array (deterministic ingest), so each contributes its
    addressable shards via make_array_from_callback — the analogue of
    chunks parsing on their home nodes (water/parser/ParseDataset).
    When each process holds ONLY its own rows, use ``put_partitioned``
    (the H2O3TPU_GLOBAL_FIT host-partitioned ingest path)."""
    import numpy as _np
    import time as _time
    from h2o3_tpu.telemetry import stepprof as _sp
    _t0 = _time.perf_counter()
    try:
        if getattr(sharding, "is_fully_addressable", True):
            return jax.device_put(host_array, sharding)
        if isinstance(host_array, jax.Array):
            # already a global device array: reshard (device-to-device),
            # never pull through the host
            if host_array.sharding == sharding:
                return host_array
            return jax.device_put(host_array, sharding)
        host_array = _np.asarray(host_array)
        return jax.make_array_from_callback(
            host_array.shape, sharding, lambda idx: host_array[idx])
    finally:
        # wall-clock annotation on an active fit profile (stepprof
        # marks are NOT part of the phase partition — they say where
        # host time went, they don't re-charge it)
        _sp.mark("put_sharded_seconds", _time.perf_counter() - _t0)


FETCH_CALLS = 0      # observability: device→host fetches (tests assert
#                      device pipelines never materialize on controller)


def fetch_replicated(x):
    """Device→host fetch that works on cross-process sharded arrays.

    Single process: device_get. Multi-process: allgather the shards so
    every host sees the full array (water/MRTask postGlobal view)."""
    global FETCH_CALLS
    FETCH_CALLS += 1
    import time as _time
    from h2o3_tpu.telemetry import stepprof as _sp
    _t0 = _time.perf_counter()
    try:
        leaves = jax.tree_util.tree_leaves(x)
        if all(getattr(getattr(v, "sharding", None),
                       "is_fully_addressable", True) for v in leaves):
            return jax.device_get(x)
        from jax.experimental import multihost_utils
        return jax.device_get(multihost_utils.process_allgather(
            x, tiled=True))
    finally:
        _sp.mark("fetch_replicated_seconds",
                 _time.perf_counter() - _t0)


def shard_rows(x, mesh: Optional[Mesh] = None, block: int = 1,
               fill: float = 0.0):
    """Pad axis-0 to a shardable length and place with row_sharding.

    Placement goes through put_sharded: on a multi-process cloud a raw
    device_put onto a non-addressable sharding pays a cross-process
    assert_equal broadcast per call (and on CPU without collectives it
    simply fails — the old multiprocess-CPU standing failure)."""
    mesh = mesh or get_mesh()
    n = x.shape[0]
    npad = padded_rows(n, mesh, block)
    if npad != n:
        pad_widths = [(0, npad - n)] + [(0, 0)] * (x.ndim - 1)
        x = np.pad(np.asarray(x), pad_widths, constant_values=fill)
    return put_sharded(x, row_sharding(mesh))


def real_rows(n, npad: int):
    """[npad] bool under a trace: True for the ``n`` real rows. An iota
    compared with a scalar — nothing row-sized is fed or folded in."""
    return jax.lax.iota(jnp.int32, npad) < n


@partial(jax.jit, static_argnames=("npad", "sharding"))
def _valid_mask_program(n, *, npad: int, sharding):
    return jax.lax.with_sharding_constraint(
        real_rows(n, npad).astype(jnp.float32), sharding)


def valid_mask(n: int, npad: int, mesh: Optional[Mesh] = None):
    """float32 1/0 mask marking real rows among padded, made on the
    device (an iota compared with ``n``): no host vector, no upload.
    ``n`` is a traced scalar, so frames of one padded size share the
    program."""
    return _valid_mask_program(np.int32(n), npad=int(npad),
                               sharding=row_sharding(mesh))
