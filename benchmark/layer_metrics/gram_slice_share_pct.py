"""Share of the IRLS solve program's device time that is no arithmetic:
own time of its ops in the scope ``gram.blocks`` (``ops/gram.py``: the
design matrix padded, reshaped into row blocks and sliced block by block
for the scan) over the device time of the program (``jit__irls_solve``)
in the traced window. Nothing where the trace names no scope."""

from benchmark import program_trace

MODULE = r"jit__irls_solve"
SCOPE = "gram.blocks"


def read(r):
    pt = program_trace.of(r)
    if pt is None:
        return None
    by = program_trace.device_by_scope(pt, MODULE, *r.window_ns)
    whole = sum(by.values())
    if whole <= 0 or set(by) <= {program_trace.UNSCOPED}:
        return None
    return r.share_pct(by.get(SCOPE, 0.0), whole, "gram_slice_share_pct")
