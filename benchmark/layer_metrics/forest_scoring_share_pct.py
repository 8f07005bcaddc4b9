"""Device time inside the forest-scoring program (``predict_forest``:
the end-of-fit re-scoring of the training frame) over device-busy time
in the traced window."""

MODULE = r"jit_predict_forest$"


def read(r):
    lo, hi = r.window_ns
    busy = r.tr.busy_seconds(r.trace, lo, hi)
    part = r.tr.device_seconds(r.trace, r.tr.in_module(MODULE),
                               lo, hi)
    if busy <= 0 or part <= 0:
        return None
    return r.share_pct(part, busy, "forest_scoring_share_pct")
