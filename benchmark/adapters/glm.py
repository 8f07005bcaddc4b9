"""What a finished GLM job hands to the comparison: the coefficients on
the data's own scale (de-standardised, intercept last) and the training
metrics, as plain host values."""

from __future__ import annotations

import numpy as np

METRICS = ("logloss", "AUC", "MSE")


def read_outputs(model) -> dict:
    coef = {k: float(v) for k, v in model.coefficients.items()}
    names = [k for k in coef if k != "Intercept"]
    tm = model.training_metrics
    return {
        "names": names,
        "coef": np.array([coef[k] for k in names] + [coef["Intercept"]],
                         np.float64),
        "metrics": {k: float(tm[k]) for k in METRICS},
    }
