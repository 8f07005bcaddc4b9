"""The all-reduces' share of a mesh job's device time: own device time of
the ops under the scopes ``tree.psum`` (a level histogram's reduce,
``ops/pallas/treekernel.fused_level``, ``ops/histogram.py``) and
``tree.leaf_psum`` (the leaf sums', ``ops/segments.py``), and of XLA's
own ``all-reduce*`` ops elsewhere in the job's programs (the row-state
program's and the metrics' sums), over device-busy time in the traced
window — each averaged over the device planes, as
``trace_reduce.device_seconds`` does. Nothing where the program names no
such scope (a program before it did)."""

from benchmark.layer_metrics import mesh_planes as mp


def read(r):
    planes = mp.of(r)
    busy = r.tr.busy_seconds(r.trace, *r.window_ns)
    if not planes or busy <= 0 or not mp.has_psum_scope(planes):
        return None
    part = mp.own_by_plane(
        planes, ".*", *r.window_ns,
        lambda o, path: mp.under_psum(o, path)
        or bool(mp.ALL_REDUCE.match(o.name)))
    return r.share_pct(sum(part.values()) / len(part) / 1e9, busy,
                       "mesh_psum_share_pct")
