"""The frontier histogram's share of its roofline: the least time the
chip could take for the ``levels_frontier`` passes the window's jobs ran
— the program's count on its ``drf.chunk`` spans, ``ntrees`` trees a job,
each pass one read of every row's bin ids, statistics and node id
(``rooflines/tree-hist.py``, the same count whatever implements a pass)
— over the device time under the scope ``tree.frontier.hist`` of the
forest program in the traced window. Nothing where the trace names no
such scope or the spans carry no count."""

from benchmark.layer_metrics import (drf_frontier_share_pct,
                                     drf_levels_frontier_per_tree)

SCOPE = "tree.frontier.hist"


def read(r):
    by = drf_frontier_share_pct.by_scope(r)
    levels = drf_levels_frontier_per_tree.read(r)
    least = r.least_seconds("tree-hist", r.shapes)
    if by is None or not levels or least is None or not r.jobs \
            or by.get(SCOPE, 0.0) <= 0:
        return None
    passes = levels * r.shapes["ntrees"] * len(r.jobs)
    return r.share_pct(least[0] * passes, by[SCOPE] / 1e9,
                       "drf_frontier_roofline")
