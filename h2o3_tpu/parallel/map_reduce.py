"""frame_map_reduce — the MRTask analogue, as one primitive.

Reference: water/MRTask.java:69 — serialize task to all nodes, split node
range as a binary tree (remote_compute, MRTask.java:716-756), split local
chunks over Fork/Join, ``map(Chunk...)`` per chunk, ``reduce`` pairwise up
both trees (MRTask.java:891). All of that machinery — RPC, ack/ackack,
F/J priorities — exists to make one thing safe: a distributed map + an
all-reduce.

TPU-native: ``shard_map`` over the 'data' mesh axis runs ``map_fn`` on each
row-shard; ``jax.lax.psum`` over the axis IS the reduce tree (XLA emits the
ICI ring/tree). Elementwise (map-only) tasks skip the psum and keep outputs
row-sharded. Local chunking (the F/J level) is either left to XLA fusion or
done with ``lax.scan`` over row blocks inside the shard when the map needs
bounded memory (see ops/histogram.py).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from h2o3_tpu import telemetry
from h2o3_tpu.core import request_ctx, watchdog
from h2o3_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, get_mesh


def _charge_reduce_payload(out, mesh) -> None:
    """MRTask telemetry: the reduce payload is the pytree the psum tree
    carries — the analogue of the reference's ack/ackack wire volume.
    Sizes come from avals (no device sync). A psum ring moves
    ~2·(n-1)/n of the payload over EACH of its n links, so the total
    collective estimate is 2·(n-1)·payload along the data axis.

    On a multi-host mesh the data-axis ring mixes link classes: a link
    whose endpoints share a process rides ICI (intra-host), one that
    crosses processes rides DCN. The counter is labeled by that scope —
    ``collective_bytes_total{scope=host|pod}`` — so the roofline/MFU
    gauges (fed the combined total via add_collective_bytes) and the
    DCN-bandwidth view stay honest when ONE fit spans the pod."""
    try:
        payload = sum(getattr(leaf, "nbytes", 0) or 0
                      for leaf in jax.tree_util.tree_leaves(out))
    except Exception:   # noqa: BLE001 - accounting must never fail the task
        return
    telemetry.histogram("frame_reduce_payload_bytes",
                        buckets=telemetry.BYTES_BUCKETS).observe(payload)
    n = mesh.shape[DATA_AXIS]
    est = 2.0 * max(n - 1, 0) * payload
    pod = 0.0
    if n > 1:
        try:
            # every model column rings over the same process layout —
            # classify the first column's n links (uniform traffic each)
            col = mesh.devices.reshape(mesh.shape[DATA_AXIS], -1)[:, 0]
            cross = sum(
                1 for i in range(n)
                if getattr(col[i], "process_index", 0)
                != getattr(col[(i + 1) % n], "process_index", 0))
            pod = est * cross / n
        except Exception:   # noqa: BLE001 - accounting must never fail
            pod = 0.0
    telemetry.counter("collective_bytes_total", scope="host").inc(est - pod)
    telemetry.counter("collective_bytes_total", scope="pod").inc(pod)
    telemetry.add_collective_bytes(est)


def frame_reduce(map_fn: Callable[..., Any], *arrays, mesh=None) -> Any:
    """All-reduce of ``map_fn`` applied per row-shard.

    ``map_fn(*local_arrays) -> pytree of stats``; every leaf is summed over
    the data axis. Equivalent of MRTask.doAll + reduce (water/MRTask.java).
    """
    mesh = mesh or get_mesh()
    # fault-injection site: a dispatch onto a wedged/restarted worker
    # dies here with INTERNAL/UNAVAILABLE — tier-1 tests plant that
    # failure (watchdog.inject_fault) to exercise the job-level retries
    watchdog.maybe_fail("frame_reduce")
    # chunk boundary: the one place a cancelled/expired request — or an
    # unhealthy cloud (core/heartbeat.py) — can be observed without
    # preempting compiled code (a scan only yields between dispatches).
    # A cancel or deadline frees this worker within one chunk; a
    # heartbeat-declared dead peer fails the job HERE with
    # CloudUnhealthyError instead of hanging forever inside the psum
    request_ctx.cancel_point("frame_reduce")
    telemetry.counter("frame_reduce_total").inc()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=tuple(P(DATA_AXIS) for _ in arrays),
        out_specs=P(),
        check_vma=False)
    def _task(*local):
        stats = map_fn(*local)
        return jax.tree_util.tree_map(
            lambda s: jax.lax.psum(s, DATA_AXIS), stats)

    from h2o3_tpu.telemetry import stepprof
    _t0 = stepprof.t_mark()
    with telemetry.span("mr.frame_reduce"):
        out = _task(*arrays)
    # charge the reduce wait to an active fit profile's collective
    # phase — this is where a fast host waits on a straggler's psum
    stepprof.collective_done(out, _t0)
    _charge_reduce_payload(out, mesh)
    return out


def frame_map(map_fn: Callable[..., Any], *arrays, mesh=None) -> Any:
    """Elementwise over rows; output stays row-sharded (map-only MRTask)."""
    mesh = mesh or get_mesh()
    watchdog.maybe_fail("frame_map")
    request_ctx.cancel_point("frame_map")
    telemetry.counter("frame_map_total").inc()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=tuple(P(DATA_AXIS) for _ in arrays),
        out_specs=P(DATA_AXIS),
        check_vma=False)
    def _task(*local):
        return map_fn(*local)

    with telemetry.span("mr.frame_map"):
        return _task(*arrays)
