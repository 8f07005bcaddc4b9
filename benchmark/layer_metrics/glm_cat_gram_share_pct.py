"""Device time of the factor Gram — the IRLS solve program's ops in the
scope ``gram.cat`` (``ops/gram.py``: a design held as codes, its
indicator rows built a chunk of rows at a time against three bfloat16
pieces of the weights) — over device-busy time in the traced window.
Nothing where the trace names no such scope (a design held dense)."""

from benchmark import program_trace

MODULE = r"jit__irls_solve"
SCOPE = "gram.cat"


def seconds(r):
    """Own device seconds under ``gram.cat`` in the window, or None."""
    pt = program_trace.of(r)
    if pt is None:
        return None
    by = program_trace.device_by_scope(pt, MODULE, *r.window_ns)
    return by[SCOPE] / 1e9 if by.get(SCOPE, 0.0) > 0 else None


def read(r):
    spent = seconds(r)
    busy = r.tr.busy_seconds(r.trace, *r.window_ns)
    if spent is None or busy <= 0:
        return None
    return r.share_pct(spent, busy, "glm_cat_gram_share_pct")
