"""Plain reference for the binomial GLM the configuration states.

Unpenalised logistic regression (``lambda = 0``) with an intercept: the
maximum-likelihood coefficients on the data's own scale. Without a
penalty the optimum does not depend on how the columns were scaled, so
the program's standardise → IRLS → de-standardise has to land on the
same numbers as a Newton iteration on the raw columns. Straightforward
numpy in float64, in row blocks; imports nothing of the program.

The Newton iteration starts from zero, as the program's IRLS does, and
every iterate is kept: Newton's iterates do not depend on the scaling of
the columns either. The stated algorithm takes, at each iteration, the
best of the full Newton step and its halvings (1, 1/2, … 1/128, none) by
the objective, and stops once the objective's relative change is under
``objective_epsilon`` (1e-6 for an unpenalised fit). Near the optimum
those candidates differ by less than a float32 objective can resolve, so
which of them a sound float32 fit takes is noise: its coefficients lie
ON the path through the Newton iterates (``path_gap``: the distance to
the nearest point of that polyline, at float32 rounding), somewhere
along its last stretch — up to 1e-3 of a coefficient short of the
optimum — and far enough along it that the deviance is within the stated
``objective_epsilon`` of the optimum's (``deviance_gap``, whose limit the
configuration states).

``precision="bf16"`` is the lower-precision control: every array of the
solve is held in bfloat16 (design matrix, linear predictor, mean,
weights, weighted design, coefficients); products are summed in float32,
as the MXU does. ``precision="bf16x"`` is the one step of it that would
tempt most, alone: the program's design matrix (the standardised
columns, float32) held in bfloat16 and everything else in float64.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 4
NAMES = ("path_gap", "deviance_gap")


def _round(a: np.ndarray, precision: str) -> np.ndarray:
    if precision != "bf16":
        return a
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def solve(data: dict, *, precision: str = "f64", block: int = 1 << 18,
          tol: float = 1e-9, max_iter: int = 50,
          beta_epsilon: float = 1e-4,
          objective_epsilon: float = 1e-6, also=()) -> dict:
    """Newton/IRLS from zero to convergence. Returns ``{"coef": [P+1]
    (intercept last), "iterates": [k, P+1], "passes": n, "deviance": d,
    "also_deviance": [...]}``; ``passes`` is
    the iteration at which the stated ``beta_epsilon`` /
    ``objective_epsilon`` end the fit; ``also_deviance`` the deviance of
    each coefficient vector in ``also`` on the same data."""
    cols, resp = data["columns"], data["response"]
    names = [n for n in cols if n != resp]
    n, P = len(cols[resp]), len(names)
    acc = np.float32 if precision == "bf16" else np.float64
    if precision == "bf16x":
        mean = [float(np.mean(cols[nm], dtype=np.float64)) for nm in names]
        sd = [float(np.std(cols[nm], dtype=np.float64, ddof=1))
              for nm in names]
    cuts = list(range(0, n, block)) + [n]
    spans = list(zip(cuts[:-1], cuts[1:]))
    scratch = threading.local()     # fresh pages are the cost on a VM:
                                    # each thread reuses two buffers

    def design(lo, hi):
        if not hasattr(scratch, "X"):
            scratch.X = np.empty((P + 1, block), acc)
            scratch.Xw = np.empty((P + 1, block), acc)
        Xb = scratch.X[:, : hi - lo]
        for j, nm in enumerate(names):
            Xb[j] = cols[nm][lo:hi]
        if precision == "bf16x":
            for j in range(P):
                Xb[j] = _round(((Xb[j] - mean[j]) / sd[j])
                               .astype(np.float32), "bf16")
        Xb[P] = 1.0
        if precision == "bf16":
            Xb[:] = _round(Xb, precision)
        return Xb, scratch.Xw[:, : hi - lo]

    def newton_part(span, beta):
        Xb, Xw = design(*span)
        yb = cols[resp][span[0]:span[1]].astype(acc)
        eta = _round(beta.astype(acc) @ Xb, precision)
        mu = _round(1.0 / (1.0 + np.exp(-eta)), precision)
        np.multiply(Xb, _round(mu * (1.0 - mu), precision), out=Xw)
        if precision == "bf16":
            Xw[:] = _round(Xw, precision)
        dev = 2.0 * float(np.sum(np.logaddexp(0.0, eta) - yb * eta,
                                 dtype=np.float64))
        return (Xw @ Xb.T).astype(np.float64), \
            (Xb @ (yb - mu)).astype(np.float64), dev

    def deviance_part(span, coefs):
        Xb, _ = design(*span)
        yb = cols[resp][span[0]:span[1]].astype(np.float64)
        return [2.0 * float(np.sum(np.logaddexp(0.0, eta) - yb * eta))
                for eta in (np.asarray(c, np.float64) @ Xb for c in coefs)]

    with ThreadPoolExecutor(THREADS) as pool:
        beta = np.zeros(P + 1, np.float64)
        iterates, devs = [], []
        passes = 0
        if precision == "bf16":
            max_iter = 12       # bfloat16 coefficients stop moving early
        for it in range(1, max_iter + 1):
            parts = list(pool.map(lambda sp: newton_part(sp, beta), spans))
            step = np.linalg.solve(sum(a for a, _, _ in parts),
                                   sum(q for _, q, _ in parts))
            devs.append(sum(d for _, _, d in parts))   # of the last iterate
            beta = _round((beta + step).astype(acc), precision) \
                .astype(np.float64)
            iterates.append(beta.copy())
            # what the stated tolerances ask for: the first iteration
            # whose step is under beta_epsilon, or the one after which the
            # objective moved by under objective_epsilon (known one
            # iteration later, from the deviance of the last two iterates)
            if not passes and len(devs) >= 3 and \
                    abs(devs[-2] - devs[-1]) <= objective_epsilon * devs[-1]:
                passes = it - 1
            if not passes and np.max(np.abs(step)) < beta_epsilon:
                passes = it
            if np.max(np.abs(step)) < tol:
                break
        if not np.isfinite(beta).all():
            raise FloatingPointError("reference GLM did not converge")
        if precision == "bf16x":    # back to the data's own scale
            def raw(b):
                out = np.append(b[:P] / sd, b[P])
                out[P] -= float(np.dot(out[:P], mean))
                return out
            beta = raw(beta)
            iterates = [raw(b) for b in iterates]
            precision = "f64"       # the deviance below: on the raw columns
        finals = [beta] + [np.asarray(c) for c in also]
        parts = list(pool.map(lambda sp: deviance_part(sp, finals), spans))
    totals = [sum(p[i] for p in parts) for i in range(len(finals))]
    return {"names": names, "coef": beta, "iterates": np.array(iterates),
            "passes": passes or len(iterates),
            "deviance": totals[0], "also_deviance": totals[1:]}


def path_gap(coef, iterates, scale) -> float:
    """Distance from ``coef`` to the polyline 0 → iterate 1 → … → the
    optimum: per stretch the nearest point by least squares in scaled
    coordinates, read as the worst coefficient's gap there."""
    pts = np.vstack([np.zeros_like(coef), iterates]) / scale
    c = coef / scale
    best = np.inf
    for a, b in zip(pts[:-1], pts[1:]):
        d = b - a
        t = np.clip(np.dot(c - a, d) / max(np.dot(d, d), 1e-300), 0.0, 1.0)
        best = min(best, float(np.max(np.abs(c - (a + t * d)))))
    return best


def check(data: dict, outputs: dict, params: dict) -> dict:
    """The numbers compared for one finished job: ``{name: value}``."""
    names = [n for n in data["columns"] if n != data["response"]]
    if list(outputs["names"]) != names:
        raise ValueError(f"model terms {outputs['names']} != {names}")
    ref = solve(data, also=[outputs["coef"]])
    scale = np.maximum(np.abs(ref["coef"]), np.median(np.abs(ref["coef"])))
    best = ref["deviance"]
    return {
        "path_gap": path_gap(outputs["coef"], ref["iterates"], scale),
        "deviance_gap": max(ref["also_deviance"][0] - best, 0.0) / best,
        "_passes": ref["passes"],
        "_coef_gap": float(np.max(np.abs(outputs["coef"] - ref["coef"])
                                  / scale)),
    }


CONTROLS = ("bf16", "bf16x")


def control(data: dict, params: dict, which: str = "bf16") -> dict:
    """A lower-precision control (one of ``CONTROLS``), in the adapter's
    format."""
    return solve(data, precision=which)
