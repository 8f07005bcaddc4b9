"""What one whole DeepLearning fit needs by the algorithm, from shapes
alone: ``steps`` mini-batch steps (``rooflines/mlp-step.py``;
``steps`` = floor(epochs · rows / batch), counted by the plain
reference), one read of the frame's pixel columns (``pixel_bytes``
each) and one write of the float32 design matrix they are standardised
into, ``score_passes`` forward passes over every row (the loss a fit
reports at its end: 1), and one forward pass over the
``score_training_samples`` rows of its training metrics. The same count
whatever implements it."""

import importlib

step = importlib.import_module("benchmark.rooflines.mlp-step")


def work(s):
    z = step.sizes(s)
    forward = 2 * sum(a * b for a, b in zip(z[:-1], z[1:]))   # ops a row
    steps = s.get("steps", int(s["epochs"] * s["rows"] / s["batch"]))
    one = step.work(s)
    scored = s.get("score_passes", 1) * s["rows"] \
        + s.get("score_training_samples", 0)
    return {"flops": steps * one["flops"] + scored * forward,
            "bytes": steps * one["bytes"]
            + s["rows"] * s["inputs"] * (s["pixel_bytes"] + 4)
            + scored * s["inputs"] * 4}
