"""Device time of the compiled training chunk (``_train_steps_fused``,
observed as ``dl.train_chunk``: every mini-batch step of a chunk, the
masked ones too) over device-busy time in the traced window."""

MODULE = r"jit__train_steps_fused"


def read(r):
    lo, hi = r.window_ns
    busy = r.tr.busy_seconds(r.trace, lo, hi)
    part = r.tr.device_seconds(r.trace, r.tr.in_module(MODULE), lo, hi)
    if busy <= 0 or part <= 0:
        return None
    return r.share_pct(part, busy, "dl_train_share_pct")
