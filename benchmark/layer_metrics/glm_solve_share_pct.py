"""Device time of the IRLS solve program (``glm.irls_solve``) over the
wall time of the traced window's jobs."""

MODULE = r"jit__irls_solve"


def read(r):
    lo, hi = r.window_ns
    part = r.tr.device_seconds(r.trace, r.tr.in_module(MODULE),
                               lo, hi)
    if part <= 0:
        return None
    return r.share_pct(part, (hi - lo) / 1e9, "glm_solve_share_pct")
