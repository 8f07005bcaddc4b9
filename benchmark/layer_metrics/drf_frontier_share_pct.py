"""Device time of the frontier regime — the levels a tree grows past the
complete layout (``models/frontier.py``): own time of the forest
program's ops in the scopes ``tree.frontier.hist``,
``tree.frontier.split`` and ``tree.frontier.route`` — over device-busy
time in the traced window. Nothing where the trace names no such
scope."""

from benchmark import program_trace

MODULE = r"jit__bag_scan"
SCOPES = ("tree.frontier.hist", "tree.frontier.split",
          "tree.frontier.route")


def by_scope(r):
    """Device nanoseconds of the forest program by scope, or None where
    no frontier scope is named."""
    pt = program_trace.of(r)
    if pt is None:
        return None
    by = program_trace.device_by_scope(pt, MODULE, *r.window_ns)
    return by if any(s in by for s in SCOPES) else None


def read(r):
    by = by_scope(r)
    busy = r.tr.busy_seconds(r.trace, *r.window_ns)
    if by is None or busy <= 0:
        return None
    return r.share_pct(sum(by.get(s, 0.0) for s in SCOPES) / 1e9, busy,
                       "drf_frontier_share_pct")
