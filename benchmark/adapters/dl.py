"""What a finished DeepLearning job hands to the comparison: the network
it trained and what it reported while training, as plain host arrays.

The only file that touches the program's objects for this fit. It reads
each layer's weights ``[fan_in, fan_out]`` and biases, the losses the fit
scored with the steps it scored them at (``scoring_history``), the steps
that took effect, and the seed the job ran with (the estimator's own
parameter; the reference derives the initial weights from it itself).
The fixed block's logloss and error are the reference's to compute from
these weights: a job's model gets no second pass over the frame inside
the timed window.
"""

from __future__ import annotations

import numpy as np


def read_outputs(model) -> dict:
    history = model.output["scoring_history"]
    seed = int(model.params["seed"])
    return {
        "seed": seed if seed >= 0 else 0xD1,    # the program's default
        "steps": int(model._steps_trained),
        # by layer number: the harness compares a job's outputs with the
        # first job's key by key, array by array
        "weights": {str(i): np.asarray(l["W"], np.float32)
                    for i, l in enumerate(model.net)},
        "biases": {str(i): np.asarray(l["b"], np.float32)
                   for i, l in enumerate(model.net)},
        "score_steps": [int(h["step"]) for h in history],
        "score_losses": [float(h["loss"]) for h in history],
    }
