"""Float64 reference for a GLM with factor predictors, for small frames.

The plain form of what ``models/glm.py`` fits on a design held as codes:
the factor columns expanded to a DENSE float64 indicator matrix on the
host, as H2O expands them (an indicator a level, each factor's first
level dropped, an NA row with no indicator), the numeric columns as they
are, an intercept last; unpenalised Newton from zero to convergence.
Imports nothing of the program. ``benchmark/references/glm_cat.py`` is
the benchmark's copy for 116M rows (float32 blocks on the device).
"""

from __future__ import annotations

import numpy as np


def design(columns: dict, domains: dict, response: str):
    """``(X [n, P+1] float64, coefficient names)``; a code below 0 is NA."""
    blocks, names = [], []
    for nm, v in columns.items():
        if nm == response:
            continue
        if nm in domains:
            v = np.asarray(v)
            blocks.append((v[:, None] == np.arange(1, len(domains[nm]))
                           [None, :]).astype(np.float64))
            names += [f"{nm}.{lvl}" for lvl in domains[nm][1:]]
        else:
            blocks.append(np.asarray(v, np.float64)[:, None])
            names.append(nm)
    n = len(columns[response])
    return np.concatenate(blocks + [np.ones((n, 1))], axis=1), names


def fit(columns: dict, domains: dict, response: str, family: str,
        iters: int = 30) -> dict:
    """Newton from zero: ``{"coef", "names", "deviance", "mu"}``."""
    X, names = design(columns, domains, response)
    y = np.asarray(columns[response], np.float64)
    beta = np.zeros(X.shape[1])
    for _ in range(iters):
        eta = X @ beta
        if family == "binomial":
            mu = 1.0 / (1.0 + np.exp(-eta))
            w = mu * (1.0 - mu)
        else:
            mu, w = eta, np.ones_like(eta)
        step = np.linalg.solve((X * w[:, None]).T @ X, X.T @ (y - mu))
        beta += step
        if np.max(np.abs(step)) < 1e-13:
            break
    eta = X @ beta
    if family == "binomial":
        mu = 1.0 / (1.0 + np.exp(-eta))
        dev = 2.0 * np.sum(np.logaddexp(0.0, eta) - y * eta)
    else:
        mu, dev = eta, np.sum((y - eta) ** 2)
    return {"coef": beta, "names": names, "deviance": float(dev), "mu": mu}
