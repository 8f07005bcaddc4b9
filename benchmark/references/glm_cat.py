"""Plain reference for the binomial GLM with factor predictors.

Unpenalised logistic regression (``lambda = 0``) with an intercept, the
factor columns expanded as H2O expands them — an indicator column a
level, the first level of each factor dropped — and the numeric columns
as they are: the maximum-likelihood coefficients on the data's own scale.
It is the same Newton iteration from zero that ``references/glm.py``
follows, and the same two numbers are compared: ``path_gap`` (the
program's coefficients to the polyline of the Newton iterates) and
``deviance_gap`` (their deviance over the optimum's). Newton's iterates
do not depend on a linear change of the columns, so it runs on the
numerics centred and scaled by their float64 mean and sd, with the
iterates mapped back.

The design is DENSE here: a block of ``BLOCK`` rows at a time becomes an
``[rows, P]`` float32 matrix of indicators, numerics and ones on the
default device, and each block's X'WX and X'(y - mu) come from float32
products at ``precision="highest"`` (the latter, where the optimum is
decided, as sums of ``SUB`` rows), added up in float64 on the host,
where the Newton step is solved in float64. Nothing of the
program is imported: not its codes walk, not its Gram. The deviance of a
final coefficient vector is float64 on the host (coefficient lookups).

``precision="bf16"`` is the lower-precision control: every array of the
solve held in bfloat16 (the standardised numerics, the linear predictor,
the mean, the weights, the weighted design, the coefficients), products
summed in float32. ``precision="bf16w"`` is the one step of it that the
program's factor Gram would take if its weights entered the products as
ONE bfloat16 piece instead of three: the Newton step formed as the
program forms it for a design held as codes, X'WX and the score
X'W(z - eta), with ``w`` and ``w·(z - eta)`` rounded to bfloat16 and
everything else float32.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("path_gap", "deviance_gap")
CONTROLS = ("bf16", "bf16w")
BLOCK = 1 << 18          # rows of one dense block on the device
SUB = 1024               # rows a float32 partial sum of the deviance
THREADS = 8


def layout(data: dict):
    """``(columns, factors, coefficient names)`` as H2O names them: a
    factor's levels after the first as ``<column>.<level>``, a numeric
    column by its name, in the columns' order (intercept not named)."""
    cols, resp, doms = data["columns"], data["response"], data["domains"]
    names = [n for n in cols if n != resp]
    factors = [n for n in names if n in doms]
    coef_names = []
    for n in names:
        coef_names += ([f"{n}.{lvl}" for lvl in doms[n][1:]] if n in doms
                       else [n])
    return names, factors, coef_names


def _round_bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("B", "widths", "precision"))
def _block_pass(codes, nums, y, valid, beta, start, *, B, widths,
                precision):
    """The block of ``B`` rows from ``start``: ``(X'WX, right-hand side,
    deviance partial sums)``. ``codes`` [F, N] int32, ``nums`` [J, N]
    standardised float32, ``y`` and ``valid`` [N]; the block's design is
    the factors' indicators in order, the numerics, then ones — dense,
    ``[B, P]``."""
    hi = jax.lax.Precision.HIGHEST
    codes = jax.lax.dynamic_slice_in_dim(codes, start, B, axis=1)
    nums = jax.lax.dynamic_slice_in_dim(nums, start, B, axis=1)
    y = jax.lax.dynamic_slice_in_dim(y, start, B)
    valid = jax.lax.dynamic_slice_in_dim(valid, start, B)
    blocks = [(codes[f][:, None] == jnp.arange(1, L)[None, :])
              .astype(jnp.float32) for f, L in enumerate(widths)]
    x_num = nums.T
    if precision == "bf16":
        x_num = _round_bf16(x_num)
    X = jnp.concatenate(blocks + [x_num, jnp.ones((B, 1), jnp.float32)],
                        axis=1)
    eta = jnp.dot(X, beta.astype(jnp.float32), precision=hi)
    if precision == "bf16":
        eta = _round_bf16(eta)
    mu = jax.nn.sigmoid(eta)
    if precision == "bf16":
        mu = _round_bf16(mu)
    w = mu * (1.0 - mu) * valid
    r = (y - mu) * valid
    if precision == "bf16w":
        # the program's step, X'WX delta = X'W (z - eta), its weights and
        # weighted residuals (w · (y - mu) / w) as one bfloat16 piece each
        H = jnp.dot(X.T * _round_bf16(w)[None, :], X, precision=hi)
        r = _round_bf16(r)
    else:
        wX = X * w[:, None]
        if precision == "bf16":
            wX = _round_bf16(wX)
        H = jnp.dot(wX.T, X, precision=hi)
    # the right-hand side, where the optimum is decided: float32 sums of
    # SUB rows, added in float64 on the host
    rhs = jnp.einsum("kn,knp->kp", r.reshape(B // SUB, SUB),
                     X.reshape(B // SUB, SUB, -1), precision=hi)
    dev = (jnp.logaddexp(0.0, eta) - y * eta) * valid
    return H, rhs, 2.0 * dev.reshape(B // SUB, SUB).sum(axis=1)


class _OnDevice:
    """The rows once on the default device, padded to whole blocks."""

    def __init__(self, data: dict):
        cols, resp, doms = data["columns"], data["response"], data["domains"]
        names, factors, _ = layout(data)
        self.numerics = [n for n in names if n not in doms]
        self.widths = tuple(len(doms[f]) for f in factors)
        n = len(cols[resp])
        self.block = min(BLOCK, max(SUB, 1 << (n - 1).bit_length()))
        npad = -(-n // self.block) * self.block
        pad = npad - n
        self.mean = np.array([np.mean(cols[c], dtype=np.float64)
                              for c in self.numerics])
        self.sd = np.array([np.std(cols[c], dtype=np.float64, ddof=1)
                            for c in self.numerics])

        def up(a, dtype, fill=0):
            return jnp.asarray(np.pad(np.asarray(a).astype(dtype), (0, pad),
                                      constant_values=fill))
        self.codes = jnp.stack([up(cols[f], np.int32, -1) for f in factors])
        self.nums = jnp.stack([up((cols[c] - m) / s, np.float32) for c, m, s
                               in zip(self.numerics, self.mean, self.sd)]) \
            if self.numerics else jnp.zeros((0, npad), jnp.float32)
        self.y = up(cols[resp], np.float32)
        self.valid = up(np.ones(n, np.float32), np.float32)
        self.starts = range(0, npad, self.block)
        # the block's columns (factors, numerics, ones) → the coefficients'
        # order (the columns' order, intercept last)
        at, where = 0, {}
        for nm in names:
            width = len(doms[nm]) - 1 if nm in doms else 1
            where[nm] = np.arange(at, at + width)
            at += width
        self.order = np.concatenate([where[nm] for nm in factors]
                                    + [where[nm] for nm in self.numerics]
                                    + [np.array([at])])

    def newton_sums(self, beta: np.ndarray, precision: str):
        """Over every block: X'WX, the right-hand side, the deviance, all
        added in float64."""
        H = rhs = 0.0
        devs = []
        beta = jnp.asarray(beta, jnp.float32)
        outs = [_block_pass(self.codes, self.nums, self.y, self.valid, beta,
                            np.int32(s), B=self.block, widths=self.widths,
                            precision=precision) for s in self.starts]
        for h, r, d in outs:
            h, r, d = jax.device_get((h, r, d))
            H = H + h.astype(np.float64)
            rhs = rhs + r.astype(np.float64).sum(axis=0)
            devs.append(d.astype(np.float64))
        return H, rhs, float(np.sum(np.concatenate(devs)))

    def to_raw(self, beta: np.ndarray) -> np.ndarray:
        """The block's coefficients, on the standardised numerics → the
        data's scale, in the coefficients' order."""
        raw = np.asarray(beta, np.float64).copy()
        J = len(self.numerics)
        k = raw.size - 1 - J
        raw[k:k + J] = beta[k:k + J] / self.sd
        raw[-1] = beta[-1] - float(np.dot(raw[k:k + J], self.mean))
        out = np.empty_like(raw)
        out[self.order] = raw
        return out


def deviance(data: dict, coefs) -> list:
    """Float64 binomial deviance of each coefficient vector (raw scale,
    intercept last), by coefficient lookup on the host."""
    cols, resp, doms = data["columns"], data["response"], data["domains"]
    names, _, _ = layout(data)
    n = len(cols[resp])
    cuts = np.linspace(0, n, 4 * THREADS + 1).astype(np.int64)

    def part(lo, hi, c):
        c = np.asarray(c, np.float64)
        eta = np.full(hi - lo, c[-1])
        k = 0
        for nm in names:
            if nm in doms:
                L = len(doms[nm])
                eta += np.concatenate([[0.0], c[k:k + L - 1]])[
                    cols[nm][lo:hi].astype(np.int64)]
                k += L - 1
            else:
                eta += c[k] * cols[nm][lo:hi].astype(np.float64)
                k += 1
        y = cols[resp][lo:hi].astype(np.float64)
        return 2.0 * float(np.sum(np.logaddexp(0.0, eta) - y * eta))

    with ThreadPoolExecutor(THREADS) as pool:
        return [sum(pool.map(lambda s: part(s[0], s[1], c),
                             zip(cuts[:-1], cuts[1:]))) for c in coefs]


def solve(data: dict, *, precision: str = "f32", max_iter: int = 20,
          beta_epsilon: float = 1e-4, objective_epsilon: float = 1e-6,
          tol: float = 1e-7) -> dict:
    """Newton/IRLS from zero. Returns ``{"coef": [P+1] (raw scale,
    intercept last), "iterates": [k, P+1], "passes": n}``; ``passes`` is
    the iteration at which the stated ``beta_epsilon`` /
    ``objective_epsilon`` end the fit. It stops where a step is under
    ``tol`` or no longer shrinks (float32 sums have a floor)."""
    dev = _OnDevice(data)
    P1 = sum(L - 1 for L in dev.widths) + len(dev.numerics) + 1
    beta = np.zeros(P1)
    iterates, devs, last = [], [], np.inf
    passes = 0
    for it in range(1, max_iter + 1):
        H, rhs, d = dev.newton_sums(beta, precision)
        devs.append(d)                                  # of the last iterate
        new = beta + np.linalg.solve(H, rhs)
        if precision == "bf16":
            import ml_dtypes
            new = new.astype(ml_dtypes.bfloat16).astype(np.float64)
        step = float(np.max(np.abs(new - beta)))
        beta = new
        iterates.append(dev.to_raw(beta))
        if not passes and len(devs) >= 3 and \
                abs(devs[-2] - devs[-1]) <= objective_epsilon * devs[-1]:
            passes = it - 1
        if not passes and step < beta_epsilon:
            passes = it
        if step < tol or (step < 1e-4 and step > 0.25 * last):
            break
        last = step
    if not np.isfinite(beta).all():
        raise FloatingPointError("reference GLM did not converge")
    return {"coef": iterates[-1], "iterates": np.array(iterates),
            "passes": passes or len(iterates)}


def path_gap(coef, iterates, scale) -> float:
    """Distance from ``coef`` to the polyline 0 → iterate 1 → … → the
    optimum, read as the worst coefficient's gap at the nearest point of
    the nearest stretch (scaled coordinates), as ``references/glm.py``."""
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
    _, _, coef_names = layout(data)
    if list(outputs["names"]) != coef_names:
        raise ValueError(f"model terms {list(outputs['names'])[:8]}... are "
                         f"not {coef_names[:8]}...")
    ref = solve(data)
    scale = np.maximum(np.abs(ref["coef"]), np.median(np.abs(ref["coef"])))
    best, got = deviance(data, [ref["coef"], outputs["coef"]])
    return {
        "path_gap": path_gap(np.asarray(outputs["coef"], np.float64),
                             ref["iterates"], scale),
        "deviance_gap": max(got - best, 0.0) / best,
        "_passes": ref["passes"],
        "_coef_gap": float(np.max(np.abs(outputs["coef"] - ref["coef"])
                                  / scale)),
    }


def control(data: dict, params: dict, which: str = "bf16") -> dict:
    """A lower-precision control (one of ``CONTROLS``), in the adapter's
    format."""
    _, _, coef_names = layout(data)
    return {"names": coef_names, "coef": solve(data, precision=which)["coef"]}
