"""The training chunk's share of its roofline: the least time the chip
could take for the mini-batch steps that took effect in the window's
jobs — ``steps`` a job (floor(epochs · rows / batch), counted by the
plain reference), each the products, the batch's rows and the weights
with their ADADELTA moments once (``rooflines/mlp-step.py``) — over the
device time of the chunk's program (``jit__train_steps_fused``) in the
traced window. A step the program computes and masks away is in the
time and not in the work."""

MODULE = r"jit__train_steps_fused"


def read(r):
    lo, hi = r.window_ns
    spent = r.tr.device_seconds(r.trace, r.tr.in_module(MODULE), lo, hi)
    least = r.least_seconds("mlp-step", r.shapes)
    if spent <= 0 or least is None or "steps" not in r.shapes or not r.jobs:
        return None
    return r.share_pct(least[0] * r.shapes["steps"] * len(r.jobs), spent,
                       "dl_train_roofline")
