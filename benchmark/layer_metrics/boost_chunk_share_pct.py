"""Device time inside the compiled boosting scan (``_boost_scan*``: all
levels of all trees of a chunk) over device-busy time in the traced
window."""

MODULE = r"jit__boost_scan"


def read(r):
    lo, hi = r.window_ns
    busy = r.tr.busy_seconds(r.trace, lo, hi)
    part = r.tr.device_seconds(r.trace, r.tr.in_module(MODULE),
                               lo, hi)
    if busy <= 0 or part <= 0:
        return None
    return r.share_pct(part, busy, "boost_chunk_share_pct")
