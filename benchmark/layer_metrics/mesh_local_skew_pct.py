"""How unevenly the chips of a mesh work between two all-reduces: per
device plane, the own device time of the boosting scan's ops
(``jit__boost_scan*``) outside the scopes ``tree.psum`` /
``tree.leaf_psum``; then 100 x (max - min) / mean over the planes. A
chip that holds more rows, or more padding, than the others reads high
and holds them at every level's reduce. Nothing where the program names
no such scope."""

from benchmark.layer_metrics import mesh_planes as mp


def read(r):
    planes = mp.of(r)
    if not planes or not mp.has_psum_scope(planes):
        return None
    local = mp.own_by_plane(planes, mp.SCAN, *r.window_ns,
                            lambda o, path: not mp.under_psum(o, path))
    mean = sum(local.values()) / len(local)
    if mean <= 0:
        return None
    return 100.0 * (max(local.values()) - min(local.values())) / mean
