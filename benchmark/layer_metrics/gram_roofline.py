"""The IRLS solve program's share of its roofline: the least time the
chip could take for the Gram passes the fit needs — each one read of the
float32 design matrix and its 2·rows·(cols+1)² operations
(``rooflines/gram-pass.py``) — over the device time of the solve program
(``glm.irls_solve``) in the traced window. The passes are the iterations
the stated ``beta_epsilon`` asks of a Newton iteration from zero, counted
by the plain reference on the same data (never ``max_iterations``, and
not the program's own counter, which adds ``max_iterations`` whatever
ran)."""

MODULE = r"jit__irls_solve"


def read(r):
    lo, hi = r.window_ns
    spent = r.tr.device_seconds(r.trace, r.tr.in_module(MODULE),
                                lo, hi)
    least = r.least_seconds("gram-pass", r.shapes)
    if spent <= 0 or least is None or "passes" not in r.shapes:
        return None
    return r.share_pct(least[0] * r.shapes["passes"] * len(r.jobs), spent,
                       "gram_roofline")
