"""The histogram kernel's share of its roofline: the least time the chip
could take for the level passes the window's jobs ran — ``ntrees`` x
``max_depth`` a job, each one read of every row's bin ids, statistics
and node id (``rooflines/tree-hist.py``) — over the device time of the
kernel ``tree_hist`` (``ops/pallas/treekernel.py``: ops ``tree_hist``,
``tree_hist.N``) in the traced window. Nothing where no such op ran: a
level that fits no tile takes the XLA sequence, which has no kernel."""

import re

KERNEL = re.compile(r"^tree_hist(\.\d+)?$")


def read(r):
    lo, hi = r.window_ns
    spent = r.tr.device_seconds(r.trace, lambda e: bool(KERNEL.match(e.name)),
                                lo, hi)
    least = r.least_seconds("tree-hist", r.shapes)
    if spent <= 0 or least is None or not r.jobs:
        return None
    passes = r.shapes["ntrees"] * r.shapes["max_depth"] * len(r.jobs)
    return r.share_pct(least[0] * passes, spent, "hist_kernel_roofline")
