"""The factor Gram's share of its roofline: the least time the chip
could take for the Gram passes the window's jobs need — each pass one
read of every row's codes, numerics, weight and weighted response and
one accumulation per pair of the row's non-zeros
(``rooflines/cat-gram-pass.py``, the same count whatever implements a
pass); the passes are the iterations the stated ``beta_epsilon`` asks of
a Newton iteration from zero, counted by the plain reference — over the
device time under the scope ``gram.cat`` in the traced window. Nothing
where the trace names no such scope."""

from benchmark.layer_metrics import glm_cat_gram_share_pct


def read(r):
    spent = glm_cat_gram_share_pct.seconds(r)
    least = r.least_seconds("cat-gram-pass", r.shapes)
    if spent is None or least is None or "passes" not in r.shapes \
            or not r.jobs:
        return None
    return r.share_pct(least[0] * r.shapes["passes"] * len(r.jobs), spent,
                       "glm_cat_gram_roofline")
