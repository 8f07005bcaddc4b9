"""The histogram kernel's share of its roofline on a mesh: the least
time one chip could take for ITS rows' level passes — ``ntrees`` x
``max_depth`` a job, each one read of every row's bin ids, statistics
and node id (``rooflines/tree-hist.py``) for the cell's rows over the
``data_shards`` of the window's ``gbm.chunk`` spans (padding rows are
no work the algorithm asks for: the spans' ``rows_per_shard`` counts
them) — over the device time of the kernel ``tree_hist`` a plane
(``trace_reduce.device_seconds``: averaged over the planes).
``hist_kernel_roofline``'s formula where ``data_shards`` is 1. Nothing
where no such op ran or the spans do not say how the job was split."""

from benchmark.layer_metrics import hist_kernel_roofline as one_chip
from benchmark.layer_metrics import mesh_planes as mp


def read(r):
    shards = mp.data_shards(r)
    lo, hi = r.window_ns
    spent = r.tr.device_seconds(
        r.trace, lambda e: bool(one_chip.KERNEL.match(e.name)), lo, hi)
    if not shards or spent <= 0 or not r.jobs:
        return None
    least = r.least_seconds(
        "tree-hist", {**r.shapes, "rows": r.shapes["rows"] / shards})
    if least is None:
        return None
    passes = r.shapes["ntrees"] * r.shapes["max_depth"] * len(r.jobs)
    return r.share_pct(least[0] * passes, spent, "mesh_hist_kernel_roofline")
