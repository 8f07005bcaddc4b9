"""GLM — generalized linear models with elastic net.

Reference: hex/glm/GLM.java:65 — IRLSM (Gram + Cholesky + ADMM for L1,
GLM.java:1451,1995), L-BFGS (GLM.java:2056), coordinate descent; lambda
search along a regularization path; families gaussian/binomial/
quasibinomial/poisson/gamma/tweedie/multinomial/negativebinomial/ordinal.

TPU redesign (SURVEY §3.4): one IRLS iteration = one einsum Gram pass
over the row-sharded design matrix (`ops/gram.py`, psum over ICI) + a
replicated Cholesky/ADMM solve. X'WX for P coefficients costs one
[P,N]x[N,P] contraction on the MXU — the reference's careful
single-threaded Cholesky bottleneck disappears into LAX. Multinomial
runs L-BFGS on the full softmax objective (the reference's default for
multinomial is also L_BFGS).

All reference families are supported: gaussian, binomial,
quasibinomial, fractionalbinomial, poisson, gamma, tweedie,
negativebinomial (theta), multinomial, ordinal (proportional-odds
L-BFGS path) — see the Family class below and tests/test_glm_surface.py.
"""

from __future__ import annotations

import time

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np

from h2o3_tpu.frame.datainfo import (CodesDesign, DataInfo, build_datainfo,
                                     coef_stats, design_row_bytes, stats_of)
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import metrics as mm
from h2o3_tpu.models.model import (Model, ModelBuilder, ModelCategory,
                                   adapt_domain, infer_category,
                                   response_on_device)
from h2o3_tpu.ops.gram import (codes_matvec, codes_rmatvec, gram,
                               gram_kernel_name, with_gram_kernel)
from h2o3_tpu.ops.optimize import (admm_l1_quadratic,
                                   cholesky_solve_regularized, lbfgs)
from h2o3_tpu.parallel.mesh import get_mesh, row_sharding
from h2o3_tpu.telemetry import observed_jit
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.glm")


# ---- family/link layer (hex/glm/GLMModel.GLMParameters.Family) ----------
class Family:
    """linkinv/variance/deviance on mu; link derivative for IRLS."""

    def __init__(self, name: str, tweedie_power: float = 1.5,
                 link: Optional[str] = None, theta: float = 1e-5):
        self.name = name
        self.p = tweedie_power
        self.theta = theta       # negativebinomial inverse dispersion
        # (may be a traced scalar inside jit — no host float() here)
        defaults = {"gaussian": "identity", "binomial": "logit",
                    "quasibinomial": "logit", "fractionalbinomial": "logit",
                    "poisson": "log", "gamma": "log", "tweedie": "tweedie",
                    "negativebinomial": "log",
                    "multinomial": "multinomial"}
        # "family_default" is the wire spelling of "use the default link"
        # (hex/glm/GLMModel.GLMParameters.Link.family_default)
        if link in ("family_default", "auto", ""):
            link = None
        allowed = {"gaussian": {"identity", "log", "inverse"},
                   "binomial": {"logit"},
                   "quasibinomial": {"logit"},
                   "fractionalbinomial": {"logit"},
                   "poisson": {"log", "identity"},
                   "gamma": {"log", "identity", "inverse"},
                   "tweedie": {"tweedie"},
                   "negativebinomial": {"log", "identity"},
                   "multinomial": {"multinomial"}}
        if link is not None and name in allowed \
                and link not in allowed[name]:
            # family-link compatibility matrix
            # (hex/glm/GLMModel.GLMParameters validation)
            raise ValueError(
                f"Incompatible link function for selected family: "
                f"link {link} is not supported for family {name}")
        self.link = link or defaults[name]

    # mu = linkinv(eta)
    def linkinv(self, eta):
        if self.link == "identity":
            return eta
        if self.link == "logit":
            return jnp.clip(jax.nn.sigmoid(eta), 1e-7, 1 - 1e-7)
        if self.link == "log":
            return jnp.exp(jnp.clip(eta, -30.0, 30.0))
        if self.link == "inverse":
            return 1.0 / jnp.where(jnp.abs(eta) < 1e-6,
                                   jnp.sign(eta) * 1e-6 + 1e-12, eta)
        if self.link == "tweedie":
            return jnp.exp(jnp.clip(eta, -30.0, 30.0))  # log link for tweedie
        raise ValueError(self.link)

    def dmu_deta(self, eta, mu):
        if self.link == "identity":
            return jnp.ones_like(eta)
        if self.link == "logit":
            return mu * (1.0 - mu)
        if self.link in ("log", "tweedie"):
            return mu
        if self.link == "inverse":
            return -mu * mu
        raise ValueError(self.link)

    def variance(self, mu):
        if self.name == "gaussian":
            return jnp.ones_like(mu)
        if self.name in ("binomial", "quasibinomial", "fractionalbinomial"):
            return mu * (1.0 - mu)
        if self.name == "poisson":
            return jnp.maximum(mu, 1e-10)
        if self.name == "gamma":
            return jnp.maximum(mu * mu, 1e-10)
        if self.name == "tweedie":
            return jnp.maximum(mu, 1e-10) ** self.p
        if self.name == "negativebinomial":
            # var = mu + theta*mu^2 (hex/glm/GLMModel Family
            # negativebinomial; theta = inverse dispersion)
            th = jnp.maximum(self.theta, 1e-10)
            return jnp.maximum(mu * (1.0 + th * mu), 1e-10)
        raise ValueError(self.name)

    def deviance(self, y, mu):
        """Unit deviance (ModelMetricsRegressionGLM residual deviance)."""
        if self.name == "gaussian":
            return (y - mu) ** 2
        if self.name == "binomial":
            mu = jnp.clip(mu, 1e-7, 1 - 1e-7)
            return -2.0 * (y * jnp.log(mu) + (1 - y) * jnp.log1p(-mu))
        if self.name == "poisson":
            ylogy = jnp.where(y > 0, y * jnp.log(jnp.maximum(y, 1e-10) / mu), 0.0)
            return 2.0 * (ylogy - (y - mu))
        if self.name == "gamma":
            yr = jnp.maximum(y, 1e-10) / jnp.maximum(mu, 1e-10)
            return 2.0 * (-jnp.log(yr) + yr - 1.0)
        if self.name == "tweedie":
            p = self.p
            return 2.0 * (jnp.maximum(y, 0.0) ** (2 - p) / ((1 - p) * (2 - p))
                          - y * mu ** (1 - p) / (1 - p)
                          + mu ** (2 - p) / (2 - p))
        if self.name in ("quasibinomial", "fractionalbinomial"):
            # binomial log-likelihood deviance with real-valued y
            mu = jnp.clip(mu, 1e-7, 1 - 1e-7)
            return -2.0 * (y * jnp.log(mu) + (1 - y) * jnp.log1p(-mu))
        if self.name == "negativebinomial":
            th = jnp.maximum(self.theta, 1e-10)
            ylogy = jnp.where(
                y > 0, y * jnp.log(jnp.maximum(y, 1e-10) / mu), 0.0)
            return 2.0 * (ylogy - (y + 1.0 / th) * jnp.log(
                (1.0 + th * y) / (1.0 + th * mu)))
        raise ValueError(self.name)


# a fit's float32 row state beside its design: response, weights, the
# linear predictor, the IRLS weights and working response
ROW_STATE_BYTES = 24


def holds_codes(family) -> bool:
    """Whether a GLM of ``family`` holds its factor predictors as codes
    (``frame/datainfo.CodesDesign``, where a predictor is a factor):
    every family but the ordinal, whose fit slices a dense matrix."""
    return str(family).lower() != "ordinal"


def _linear(X1, B):
    """``X1 @ B``: where the design is held as codes, coefficient lookups
    per factor plus the numerics' product (``ops/gram.codes_matvec``)."""
    if isinstance(X1, CodesDesign):
        with jax.named_scope("glm.eta"):
            return codes_matvec(X1, B, mesh=get_mesh())
    return X1 @ B


def _with_intercept(X):
    """The design with the intercept column appended (``X1``)."""
    if isinstance(X, CodesDesign):
        return X.with_intercept()
    ones = jnp.ones((X.shape[0], 1), jnp.float32)
    return jnp.concatenate([X, ones], axis=1)


@partial(jax.jit, static_argnames=("family", "link", "use_l1"))
def _irls_iter(X1, coef, y, w, off, l1, l2, family: str, link: str,
               tweedie_power, theta=1e-5, *, use_l1: bool):
    """One full IRLS iteration on device: re-weight → Gram (psum over the
    mesh) → penalized solve. λ enters as traced scalars so the lambda
    path reuses one compiled program (GLM.java fitIRLSM per-lambda loop).
    """
    fam = Family(family, tweedie_power, link, theta=theta)
    # a design held as codes takes the Newton step from the score,
    # X'W(z - eta), summed straight from the rows: the IRLS form's
    # X'Wz - X'WX beta cancels to it in float32 and a factor whose
    # dropped first level is rare leaves a nearly flat direction (its
    # other levels against the intercept) that multiplies what the
    # cancellation loses — and that a rank-safety ridge would move the
    # IRLS fixed point along; a step's fixed point is the score's zero
    # whatever damps the step. A dense design keeps the IRLS form: the
    # step form moves its fits within the stopping tolerance, and they
    # are held to their float32 results (on a nearly separable frame it
    # flipped one AUC pair between CV's two fold paths)
    step_form = isinstance(X1, CodesDesign) and not use_l1
    with jax.named_scope("glm.reweight"):
        eta = _linear(X1, coef) + off
        mu = fam.linkinv(eta)
        d = fam.dmu_deta(eta, mu)
        var = fam.variance(mu)
        # working response net of the fixed offset (GLMTask with offset)
        resid = (y - mu) / jnp.where(jnp.abs(d) < 1e-10, 1e-10, d)
        z = eta - off + resid
        w_irls = w * d * d / jnp.maximum(var, 1e-10)
        dev = jnp.sum(w * fam.deviance(y, mu))

    mesh = get_mesh()
    from h2o3_tpu.parallel.mesh import MODEL_AXIS
    if mesh.shape.get(MODEL_AXIS, 1) > 1 and \
            not isinstance(X1, CodesDesign):
        # wide one-hot designs on a (data, model) mesh: column-sharded
        # Gram via the ppermute ring (SURVEY §2.4 item 6 TP-like axis)
        from h2o3_tpu.ops.gram import gram_model_sharded
        xtx, xtz, _ = gram_model_sharded(X1, w_irls, z, mesh=mesh)
    else:
        xtx, xtz, _ = gram(X1, w_irls, resid if step_form else z,
                           mesh=mesh)
    with jax.named_scope("glm.newton_solve"):
        nobs = jnp.maximum(jnp.sum(w), 1.0)
        A = xtx / nobs
        q = xtz / nobs
        Pp1 = X1.shape[1]
        penalize = jnp.concatenate(
            [jnp.ones(Pp1 - 1), jnp.zeros(1)]).astype(A.dtype)
        if use_l1:
            new_coef = admm_l1_quadratic(A + l2 * jnp.diag(penalize), q,
                                         l1, penalize)
        elif step_form:
            # the ridge a share of each column's own diagonal (a level's
            # weight): it damps a rare level's step no more than a
            # common one's
            new_coef = coef + cholesky_solve_regularized(
                A, q - l2 * penalize * coef, l2, penalize,
                ridge_boost=1e-6 * jnp.diag(A) + 1e-30)
        else:
            new_coef = cholesky_solve_regularized(A, q, l2, penalize)
        delta = jnp.max(jnp.abs(new_coef - coef))
    return new_coef, delta, dev, eta


@observed_jit("glm.irls_solve")
@partial(jax.jit, static_argnames=("family", "link", "use_l1"))
def _irls_solve(X1, coef, y, w, off, l1, l2, beta_eps, max_iter,
                family: str, link: str, tweedie_power, theta=1e-5,
                obj_eps=1e-6, *, use_l1: bool):
    """The whole IRLS loop as one compiled ``while_loop`` — per-iteration
    host syncs (one device round trip each) previously dominated GLM
    wall time on a remote-attached chip.

    Three reference behaviors (GLM.java fitIRLSM):
    - beta_epsilon stop on the coefficient delta;
    - objective_epsilon stop on relative penalized-objective change —
      load-bearing under L1, where ADMM's inexact solves jitter coef by
      more than beta_epsilon forever (every lambda burned the full
      max_iterations budget → pyunit_glm_seed's 600s timeout);
    - objective LINE SEARCH on the IRLS step (GLM.java line-search on
      quasi-separable data): undamped Newton oscillates when the MLE
      diverges, so the step is chosen as the best of {full, 1/2, ...,
      1/128, none} by penalized objective — nine cheap matvecs, all
      fused on device.

    Returns ``(coef, it)``: the coefficients and the iterations the
    loop ran (an int32 scalar, read with the coefficients)."""
    fam = Family(family, tweedie_power, link, theta=theta)
    steps = jnp.concatenate([2.0 ** -jnp.arange(8, dtype=jnp.float32),
                             jnp.zeros(1, jnp.float32)])

    def pen_of(c):
        return l1 * jnp.sum(jnp.abs(c[:-1])) \
            + 0.5 * l2 * jnp.sum(c[:-1] * c[:-1])

    def cond(state):
        coef, delta, obj, rel, it = state
        return (delta > beta_eps) & (rel > obj_eps) & (it < max_iter)

    # scope names are what a device trace shows of this program
    # (benchmark/program_trace.py): metadata only, the program is the same
    @jax.named_scope("glm.irls_iter")
    def body(state):
        coef, _, obj, _, it = state
        full, _, dev, eta = _irls_iter(X1, coef, y, w, off, l1, l2,
                                       family, link, tweedie_power,
                                       theta, use_l1=use_l1)
        with jax.named_scope("glm.line_search"):
            # candidates coef + s*(full-coef); objectives in ONE batched
            # pass
            cands = coef[None, :] + steps[:, None] * (full - coef)[None, :]
            pens = jax.vmap(pen_of)(cands)
            if isinstance(X1, CodesDesign):
                # eta is linear in the coefficients: the candidates' from
                # the reweight's and ONE lookup of the step, no [N, 9]
                # product (and no [N, 9] array where the reduction fuses)
                step_eta = _linear(X1, full - coef)
                mus = fam.linkinv(eta[:, None]
                                  + steps[None, :] * step_eta[:, None])
                # each candidate against the last, no step, row by row,
                # and the largest step within rounding of the best: along
                # a rare level's flat direction a step gains less than
                # the float32 spacing of the deviance's total, where
                # totals compared pick a step, or none, by rounding
                gain = jnp.sum(w[:, None] * (
                    fam.deviance(y[:, None], mus)
                    - fam.deviance(y, fam.linkinv(eta))[:, None]), axis=0)
                objs = gain + pens - pens[-1]
                at = dev + pens[-1]
                k = jnp.argmax(objs <= jnp.min(objs) + 1e-6 * jnp.abs(at))
                new_obj = at + objs[k]
                rel = jnp.abs(objs[k]) / jnp.maximum(jnp.abs(at), 1e-10)
            else:
                mus = fam.linkinv(X1 @ cands.T + off[:, None])   # [N, 9]
                devs = jnp.sum(w[:, None] * fam.deviance(y[:, None], mus),
                               axis=0)
                objs = devs + pens
                k = jnp.argmin(objs)
                new_obj = objs[k]
                rel = jnp.abs(obj - new_obj) / jnp.maximum(
                    jnp.abs(new_obj), 1e-10)
            new_coef = cands[k]
            delta = jnp.max(jnp.abs(new_coef - coef))
        return new_coef, delta, new_obj, rel, it + 1

    # finite sentinels: ±inf would make rel = inf/inf = NaN and the
    # NaN > eps comparison (False) would skip the loop entirely
    coef, _, _, _, it = jax.lax.while_loop(
        cond, body, (coef, jnp.float32(1e30), jnp.float32(1e30),
                     jnp.float32(2.0), jnp.int32(0)))
    return coef, it


@partial(jax.jit, static_argnames=("family", "link", "use_l1"))
def _irls_solve_path(X1, coef, y, w, off, l1s, l2s, beta_eps, max_iter,
                     family: str, link: str, tweedie_power, theta=1e-5,
                     obj_eps=1e-4, *, use_l1: bool):
    """The WHOLE lambda path as one compiled ``scan`` of IRLS solves,
    warm-starting each lambda from the previous solution (GLM.java
    lambda-search semantics). A 30-step search previously paid 30
    dispatches per fit; with 3-fold CV and multiple models that
    multiplied into pyunit_glm_seed's 600s timeout. Returns the final
    (smallest-lambda) coefficients — what the single-model path keeps —
    the path ``[L, P+1]`` and the iterations each lambda ran ``[L]``."""

    def solve_one(c, l12):
        l1, l2 = l12
        c, it = _irls_solve(X1, c, y, w, off, l1, l2, beta_eps, max_iter,
                            family, link, tweedie_power, theta, obj_eps,
                            use_l1=use_l1)
        return c, (c, it)

    coef, (path, its) = jax.lax.scan(solve_one, coef, (l1s, l2s))
    return coef, path, its


@observed_jit("glm.irls_solve_batched")
@partial(jax.jit, static_argnames=("family", "link", "use_l1"))
def _irls_solve_batched(X1, coef0, y, w, off, l1s, l2s, beta_eps,
                        max_iter, family: str, link: str, tweedie_power,
                        theta=1e-5, obj_epss=None, *, use_l1: bool):
    """Model-batched IRLS: ``vmap`` over the (alpha, lambda) product of
    a grid/AutoML shape bucket — each lane is an INDEPENDENT fit from
    the zero start (exactly what the sequential grid walk solves per
    combo; contrast _irls_solve_path, whose lambdas warm-start
    sequentially within ONE model). l1s/l2s/obj_epss ride the vmapped
    axis; X1/y/w/off broadcast. The vmapped while_loop runs until every
    lane converges, freezing finished lanes, so an M-combo sweep costs
    one dispatch instead of M. Returns ``(coefs [M, P+1], its [M])``."""

    def one(l1, l2, oe):
        return _irls_solve(X1, coef0, y, w, off, l1, l2, beta_eps,
                           max_iter, family, link, tweedie_power, theta,
                           oe, use_l1=use_l1)

    return jax.vmap(one)(l1s, l2s, obj_epss)


@partial(jax.jit, static_argnames=("family", "link", "sweeps"))
def _irls_iter_cod(X1, coef, y, w, off, l1, l2, lo, hi, family: str,
                   link: str, tweedie_power, theta=1e-5, *,
                   sweeps: int = 50):
    """One IRLS iteration solved by (optionally box-constrained) cyclic
    coordinate descent — GLM.java:1495 fitCOD and the beta_constraints /
    non_negative projected path."""
    from h2o3_tpu.ops.optimize import coordinate_descent_quadratic
    fam = Family(family, tweedie_power, link, theta=theta)
    eta = _linear(X1, coef) + off
    mu = fam.linkinv(eta)
    d = fam.dmu_deta(eta, mu)
    var = fam.variance(mu)
    z = eta - off + (y - mu) / jnp.where(jnp.abs(d) < 1e-10, 1e-10, d)
    w_irls = w * d * d / jnp.maximum(var, 1e-10)
    mesh = get_mesh()
    xtx, xtz, _ = gram(X1, w_irls, z, mesh=mesh)
    nobs = jnp.maximum(jnp.sum(w), 1.0)
    A = xtx / nobs
    q = xtz / nobs
    Pp1 = X1.shape[1]
    penalize = jnp.concatenate([jnp.ones(Pp1 - 1),
                                jnp.zeros(1)]).astype(A.dtype)
    new_coef = coordinate_descent_quadratic(A, q, l1, l2, penalize,
                                            lower=lo, upper=hi,
                                            sweeps=sweeps)
    delta = jnp.max(jnp.abs(new_coef - coef))
    return new_coef, delta


@partial(jax.jit, static_argnames=("family", "link"))
def _glm_value_grad(coef, X1, y, w, off, l2, family: str, link: str,
                    tweedie_power, theta=1e-5):
    """Penalized deviance objective + gradient (GLMGradientTask role)."""
    fam = Family(family, tweedie_power, link, theta=theta)
    Pp1 = X1.shape[1]
    penalize = jnp.concatenate([jnp.ones(Pp1 - 1), jnp.zeros(1)]).astype(jnp.float32)
    nobs = jnp.maximum(jnp.sum(w), 1.0)

    def dev_of(eta):
        return jnp.sum(w * fam.deviance(y, fam.linkinv(eta))) / (2.0 * nobs)

    if isinstance(X1, CodesDesign):
        # the chain rule by hand: d/dcoef = X' d/deta (no residual per
        # chunk of the lookup's scan is kept for a backward pass)
        dev, g = jax.value_and_grad(dev_of)(
            _linear(X1, coef.astype(jnp.float32)) + off)
        return (dev + 0.5 * l2 * jnp.sum(penalize * coef * coef),
                codes_rmatvec(X1, g, mesh=get_mesh()) + l2 * penalize * coef)

    def obj(c):
        dev = dev_of(X1 @ c.astype(jnp.float32) + off)
        return dev + 0.5 * l2 * jnp.sum(penalize * c * c)

    return jax.value_and_grad(obj)(coef)


@partial(jax.jit, static_argnames=("K",))
def _multinomial_value_grad(flat, X1, y_int, w, l2, K: int):
    Pp1 = X1.shape[1]
    penalize = jnp.concatenate([jnp.ones(Pp1 - 1), jnp.zeros(1)]).astype(jnp.float32)
    Y = (y_int[:, None] == jnp.arange(K)[None, :]).astype(jnp.float32)
    nobs = jnp.maximum(jnp.sum(w), 1.0)

    def nll_of(eta):
        return -jnp.sum(w[:, None] * Y * jax.nn.log_softmax(eta, axis=1)) \
            / nobs

    if isinstance(X1, CodesDesign):
        B = flat.reshape(Pp1, K).astype(jnp.float32)
        nll, g = jax.value_and_grad(nll_of)(_linear(X1, B))
        pen = penalize[:, None] * B
        return (nll + 0.5 * l2 * jnp.sum(pen ** 2),
                (codes_rmatvec(X1, g, mesh=get_mesh())
                 + l2 * penalize[:, None] * pen).reshape(flat.shape))

    def obj(fl):
        B = fl.reshape(Pp1, K).astype(jnp.float32)
        nll = nll_of(X1 @ B)
        return nll + 0.5 * l2 * jnp.sum((penalize[:, None] * B) ** 2)

    return jax.value_and_grad(obj)(flat)


@partial(jax.jit, static_argnames=("K", "use_l1"))
def _multinomial_irls_solve(X1, B, y_int, w, l1, l2, beta_eps, max_iter,
                            *, K: int, use_l1: bool):
    """Multinomial IRLSM: block-coordinate IRLS over classes
    (hex/glm/GLM.java:1995 fitIRLSM multinomial path — one weighted
    least-squares subproblem per class per sweep, cycled to
    convergence). Working weights p_c(1-p_c), working response from the
    class margin; L1 via the same ADMM inner solver as binomial.
    The whole sweep loop is one compiled while_loop."""
    Pp1 = X1.shape[1]
    penalize = jnp.concatenate([jnp.ones(Pp1 - 1),
                                jnp.zeros(1)]).astype(jnp.float32)
    nobs = jnp.maximum(jnp.sum(w), 1.0)
    mesh = get_mesh()

    def one_class(B, c):
        eta = _linear(X1, B)
        p = jax.nn.softmax(eta, axis=1)
        pc = p[:, c]
        yc = (y_int == c).astype(jnp.float32)
        d = jnp.maximum(pc * (1.0 - pc), 1e-10)
        z = eta[:, c] + (yc - pc) / d
        wc = w * d
        xtx, xtz, _ = gram(X1, wc, z, mesh=mesh)
        A = xtx / nobs
        q = xtz / nobs
        if use_l1:
            bc = admm_l1_quadratic(A + l2 * jnp.diag(penalize), q, l1,
                                   penalize)
        else:
            bc = cholesky_solve_regularized(A, q, l2, penalize)
        return B.at[:, c].set(bc)

    def body(state):
        B, _, it = state
        Bn = B
        for c in range(K):            # K static: unrolled class sweep
            Bn = one_class(Bn, c)
        return Bn, jnp.max(jnp.abs(Bn - B)), it + 1

    def cond(state):
        return (state[1] > beta_eps) & (state[2] < max_iter)

    B, _, _ = jax.lax.while_loop(
        cond, body, (B, jnp.float32(jnp.inf), jnp.int32(0)))
    return B


@partial(jax.jit, static_argnames=("K",))
def _ordinal_value_grad(flat, X1, y_int, w, l2, K: int):
    """Proportional-odds (cumulative logit) NLL + gradient
    (hex/glm Family.ordinal — GLM.java ordinal path).

    Params: [beta (P, no intercept term used), raw thresholds (K-1)]
    with thresholds alpha_k = a0 + cumsum(exp(d_k)) to keep them ordered.
    P(y<=k) = sigmoid(alpha_k - eta).
    """
    P = X1.shape[1] - 1            # design carries a ones column; unused
    Xb = X1[:, :P]

    def obj(fl):
        beta = fl[:P].astype(jnp.float32)
        a0 = fl[P]
        deltas = fl[P + 1:]
        alphas = jnp.concatenate(
            [a0[None], a0 + jnp.cumsum(jnp.exp(deltas))]).astype(jnp.float32)
        eta = Xb @ beta
        # cumulative probs for k = 0..K-2, bracketed by 0 and 1
        cum = jax.nn.sigmoid(alphas[None, :] - eta[:, None])
        cum = jnp.concatenate([jnp.zeros((eta.shape[0], 1)), cum,
                               jnp.ones((eta.shape[0], 1))], axis=1)
        pk = jnp.take_along_axis(cum, y_int[:, None] + 1, axis=1)[:, 0] - \
            jnp.take_along_axis(cum, y_int[:, None], axis=1)[:, 0]
        nll = -jnp.sum(w * jnp.log(jnp.clip(pk, 1e-9, 1.0))) \
            / jnp.maximum(jnp.sum(w), 1.0)
        return nll + 0.5 * l2 * jnp.sum(beta * beta)

    return jax.value_and_grad(obj)(flat)


def expand_interactions(frame: Frame, inter_cols: Sequence[str]) -> Frame:
    """Augment a frame with pairwise interaction columns among
    ``inter_cols`` (hex/DataInfo.java:16 interactions /
    InteractionWrappedVec semantics):

      num x num   → product column  a_b
      enum x enum → combined factor a_b with observed level pairs
      enum x num  → per-level masked numerics a.<level>_b

    Original Column objects are shared (no device copies)."""
    import itertools
    from h2o3_tpu.frame.column import Column, T_CAT, T_NUM
    from h2o3_tpu.parallel import mesh as mesh_mod
    cols = [frame.col(n) for n in frame.names]
    n = frame.nrows
    npad = cols[0].data.shape[0] if cols and cols[0].data is not None \
        else mesh_mod.padded_rows(n)
    shard = mesh_mod.row_sharding()
    new_cols = list(cols)
    for a, b in itertools.combinations(inter_cols, 2):
        ca, cb = frame.col(a), frame.col(b)
        if not ca.is_categorical and not cb.is_categorical:
            va, vb = ca.numeric_view(), cb.numeric_view()
            prod = va * vb
            na = jnp.isnan(prod)
            new_cols.append(Column(
                name=f"{a}_{b}", type=T_NUM,
                data=jax.device_put(jnp.where(na, 0.0, prod), shard),
                na_mask=jax.device_put(na, shard), nrows=n))
        elif ca.is_categorical and cb.is_categorical:
            ka = _fetch_np(ca.data)[:n]
            kb = _fetch_np(cb.data)[:n]
            na = (_fetch_np(ca.na_mask)[:n] | _fetch_np(cb.na_mask)[:n])
            combo = ka.astype(np.int64) * len(cb.domain or []) + kb
            combo[na] = -1
            seen = np.unique(combo[combo >= 0])
            lut = {int(c): i for i, c in enumerate(seen)}
            codes = np.array([lut.get(int(c), -1) for c in combo],
                             np.int32)
            dom = [f"{ca.domain[c // len(cb.domain)]}_"
                   f"{cb.domain[c % len(cb.domain)]}" for c in seen]
            codes_p = np.pad(np.where(codes < 0, 0, codes),
                             (0, npad - n))
            na_p = np.pad(codes < 0, (0, npad - n),
                          constant_values=True)
            new_cols.append(Column(
                name=f"{a}_{b}", type=T_CAT,
                data=jax.device_put(jnp.asarray(codes_p), shard),
                na_mask=jax.device_put(jnp.asarray(na_p), shard),
                nrows=n, domain=dom))
        else:
            cat, num = (ca, cb) if ca.is_categorical else (cb, ca)
            cname, nname = (a, b) if ca.is_categorical else (b, a)
            vnum = num.numeric_view()
            codes = jnp.asarray(np.pad(
                _fetch_np(cat.data)[:n], (0, npad - n)))
            cna = jnp.asarray(np.pad(
                _fetch_np(cat.na_mask)[:n], (0, npad - n),
                constant_values=True))
            for li, lvl in enumerate(cat.domain or []):
                v = jnp.where((codes == li) & ~cna, vnum, 0.0)
                na = jnp.isnan(v)
                new_cols.append(Column(
                    name=f"{cname}.{lvl}_{nname}", type=T_NUM,
                    data=jax.device_put(jnp.where(na, 0.0, v), shard),
                    na_mask=jax.device_put(na, shard), nrows=n))
    out = Frame(new_cols, n)
    from h2o3_tpu.core.kv import DKV
    DKV.remove(out.key)      # transient view, keep it out of the store
    return out


class GLMModel(Model):
    algo = "glm"

    def __init__(self, params, output, coef: np.ndarray, family: Family,
                 di_stats: dict, features: List[str],
                 coef_multinomial: Optional[np.ndarray] = None):
        super().__init__(params, output)
        self.coef = coef                       # [P+1] (last = intercept)
        self.coef_multinomial = coef_multinomial  # [P+1, K] or None
        self.family = family
        self.di_stats = di_stats
        self.features = features

    def _design(self, frame: Frame) -> jax.Array:
        inter = self.params.get("interactions")
        if inter:
            frame = expand_interactions(frame, inter)
        di = build_datainfo(frame, self.features,
                            standardize=self.params.get("standardize", True),
                            use_all_factor_levels=self.params.get(
                                "use_all_factor_levels", False),
                            stats_override=self.di_stats,
                            codes=holds_codes(self.output.get("family")))
        return _with_intercept(di.X)

    def _frame_offset(self, frame: Frame):
        oc = self.params.get("offset_column")
        if not oc or oc not in frame:
            return None
        ov = frame.col(oc).numeric_view()
        return jnp.where(jnp.isnan(ov), 0.0, ov).astype(jnp.float32)

    def _eta(self, frame: Frame):
        X1 = self._design(frame)
        off = self._frame_offset(frame)
        if self.coef_multinomial is not None:
            # offset is deliberately NOT applied: a per-row constant
            # added to every class margin cancels in softmax, so the
            # reference ignores it for multinomial with a warning
            # (hex/glm/GLM.java:978 "offset has no effect on
            # multinomial and will be ignored")
            return _linear(X1, jnp.asarray(self.coef_multinomial,
                                           jnp.float32))
        eta = _linear(X1, jnp.asarray(self.coef, jnp.float32))
        return eta if off is None else eta + off

    def _ordinal_probs(self, frame: Frame) -> jax.Array:
        """Device-resident ordinal class probabilities [Npad, K]
        (proportional-odds P(y<=k) differences), like the other
        families' device scoring paths."""
        X1 = self._design(frame)
        P = X1.shape[1] - 1
        eta = X1[:, :P] @ jnp.asarray(self.coef[:P], jnp.float32)
        alphas = jnp.asarray(self.output["ordinal_alphas"], jnp.float32)
        cum = jax.nn.sigmoid(alphas[None, :] - eta[:, None])
        cum = jnp.concatenate(
            [jnp.zeros((eta.shape[0], 1), jnp.float32), cum,
             jnp.ones((eta.shape[0], 1), jnp.float32)], axis=1)
        return jnp.diff(cum, axis=1)

    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        n = frame.nrows
        cat = self.output["category"]
        off = self._frame_offset(frame)
        ordinal = self.output.get("family") == "ordinal"
        if off is None or ordinal or self.coef_multinomial is not None:
            # the model's ONE compiled scoring program — the same
            # executable the serving tier dispatches, so row-payload
            # predictions match bit-for-bit (Model._serve_jit; the
            # whole pipeline stays on device, ONE fetch at the end —
            # offset is a no-op for multinomial/ordinal, GLM.java:978)
            X1 = self._design(frame)
            return self._serve_finish(_fetch_np(self._serve_jit()(X1)), n)
        eta = self._eta(frame)
        mu = _fetch_np(self.family.linkinv(eta))[:n]
        if cat == ModelCategory.BINOMIAL:
            t = self.output.get("default_threshold", 0.5)
            return {"predict": (mu >= t).astype(np.int32),
                    "p0": 1.0 - mu, "p1": mu}
        return {"predict": mu}

    def _serve_dev(self, X1):
        """Device half of the serving fast path (serving/engine.py jits
        this per row bucket): EXACTLY the device math of ``_score_raw``
        on a prepared design matrix (``_design`` output, intercept
        column included). Offset/interactions models take the engine's
        eager fallback."""
        if self.output.get("family") == "ordinal":
            P = X1.shape[1] - 1
            eta = X1[:, :P] @ jnp.asarray(self.coef[:P], jnp.float32)
            alphas = jnp.asarray(self.output["ordinal_alphas"], jnp.float32)
            cum = jax.nn.sigmoid(alphas[None, :] - eta[:, None])
            cum = jnp.concatenate(
                [jnp.zeros((eta.shape[0], 1), jnp.float32), cum,
                 jnp.ones((eta.shape[0], 1), jnp.float32)], axis=1)
            return jnp.diff(cum, axis=1)
        if self.coef_multinomial is not None:
            return jax.nn.softmax(_linear(
                X1, jnp.asarray(self.coef_multinomial, jnp.float32)), axis=1)
        return self.family.linkinv(
            _linear(X1, jnp.asarray(self.coef, jnp.float32)))

    def _serve_finish(self, fetched: np.ndarray, n: int) -> Dict[str, np.ndarray]:
        """Host half of the serving fast path: the exact host tail of
        ``_score_raw`` applied to the fetched device output."""
        cat = self.output["category"]
        if self.output.get("family") == "ordinal" or \
                cat == ModelCategory.MULTINOMIAL:
            p = fetched[:n]
            out = {"predict": p.argmax(axis=1).astype(np.int32)}
            for k in range(p.shape[1]):
                out[f"p{k}"] = p[:, k]
            return out
        mu = fetched[:n]
        if cat == ModelCategory.BINOMIAL:
            t = self.output.get("default_threshold", 0.5)
            return {"predict": (mu >= t).astype(np.int32),
                    "p0": 1.0 - mu, "p1": mu}
        return {"predict": mu}

    def model_performance(self, frame: Frame, mask_weights=None):
        """``mask_weights``: see GBMModel.model_performance (CV fast
        path holdout metrics on the parent frame)."""
        y = self.output["response"]
        cat = self.output["category"]
        eta = self._eta(frame)
        w = frame.valid_weights()
        wc_name = self.params.get("weights_column")
        if wc_name and wc_name in frame:
            wc = frame.col(wc_name).numeric_view()
            w = w * jnp.where(jnp.isnan(wc), 0.0, wc)
        if mask_weights is not None:
            w = w * jnp.asarray(mask_weights, jnp.float32)
        npad = eta.shape[0]
        if cat == ModelCategory.BINOMIAL:
            yv = adapt_domain(frame.col(y), self.output["domain"])
            yv = np.pad(yv, (0, npad - frame.nrows), constant_values=-1)
            w = w * jnp.asarray((yv >= 0).astype(np.float32))
            p = self.family.linkinv(eta)
            return mm.binomial_metrics(p, jnp.asarray(np.maximum(yv, 0).astype(np.float32)), w)
        if cat == ModelCategory.MULTINOMIAL:
            yv = adapt_domain(frame.col(y), self.output["domain"])
            yv = np.pad(yv, (0, npad - frame.nrows), constant_values=-1)
            w = w * jnp.asarray((yv >= 0).astype(np.float32))
            p = jax.nn.softmax(eta, axis=1)
            return mm.multinomial_metrics(p, jnp.asarray(np.maximum(yv, 0)), w,
                                          domain=self.output["domain"])
        yv = frame.col(y).numeric_view()
        w = w * jnp.where(jnp.isnan(yv), 0.0, 1.0)
        yv = jnp.where(jnp.isnan(yv), 0.0, yv)
        mu = self.family.linkinv(eta)
        return mm.regression_metrics(mu, yv, w,
                                     deviance_fn=lambda a, b: self.family.deviance(a, b))

    @property
    def coefficients(self) -> Dict[str, float]:
        """RAW-scale coefficients (h2o-py model.coef() semantics): when
        the model trained on a standardized design, model-space coefs
        de-standardize exactly like the wire coefficients_table does.
        Multinomial/ordinal keep model space (same exclusions as the
        wire table — ordinal's trailing coef is a placeholder, the real
        thresholds live in output['ordinal_alphas'])."""
        names = self.output["coef_names"] + ["Intercept"]
        if self.coef_multinomial is not None:
            K = self.coef_multinomial.shape[1]
            return {f"{nm}_class{k}": float(self.coef_multinomial[i, k])
                    for i, nm in enumerate(names) for k in range(K)}
        coefs = np.asarray(self.coef, np.float64)
        if self.output.get("standardized") and \
                self.output.get("family") != "ordinal":
            coefs = destandardize_coefs(
                coefs,
                self.output.get("coef_means"),
                self.output.get("coef_sds"))
        return {nm: float(c) for nm, c in zip(names, coefs)}


def destandardize_coefs(coefs: np.ndarray, mus, sds) -> np.ndarray:
    """Standardized-design coefs → raw scale: raw_j = std_j/σ_j,
    intercept shifts by Σ std_j·μ_j/σ_j. ONE implementation shared by
    the python surface and the wire coefficients_table
    (hex/glm GLMModel coefficients semantics)."""
    p = len(coefs) - 1
    mus = np.asarray(mus if mus is not None else [0.0] * p, np.float64)
    sds = np.asarray(sds if sds is not None else [1.0] * p, np.float64)
    raw = np.asarray(coefs, np.float64).copy()
    raw[:-1] = coefs[:-1] / sds
    raw[-1] = coefs[-1] - float(np.sum(coefs[:-1] * mus / sds))
    return raw


class GLMEstimator(ModelBuilder):
    """h2o-py H2OGeneralizedLinearEstimator surface
    (h2o-py/h2o/estimators/glm.py)."""

    algo = "glm"
    cv_fold_masking = True   # ml/cv.py fast path: folds = masked weights

    DEFAULTS = dict(
        family="auto", link=None, solver="auto", alpha=0.5,
        lambda_=None, lambda_search=False, nlambdas=30,
        lambda_min_ratio=1e-4, standardize=True,
        use_all_factor_levels=False, max_iterations=50,
        beta_epsilon=1e-4, objective_epsilon=-1,
        tweedie_power=1.5, theta=1e-5, seed=-1, nfolds=0,
        fold_assignment="auto",
        weights_column=None, fold_column=None, offset_column=None,
        ignored_columns=None,
        missing_values_handling="mean_imputation",
        compute_p_values=False, intercept=True,
        beta_constraints=None, non_negative=False, interactions=None,
        keep_cross_validation_models=True,
        keep_cross_validation_predictions=False,
        keep_cross_validation_fold_assignment=False,
    )

    def design_row_bytes(self, frame: Frame, x) -> int:
        """``DataInfo.X`` and ``X1`` (the same with the intercept) a row,
        as ``_fit`` builds them, and the row state."""
        p = self.params
        X = design_row_bytes(frame, x, bool(p["use_all_factor_levels"]),
                             holds_codes(p["family"]))
        return 2 * X + 4 + ROW_STATE_BYTES

    def __init__(self, **params):
        merged = dict(self.DEFAULTS)
        # h2o-py spells it "Lambda", "lambda_", or bare "lambda" (the
        # grid wire sends the raw schema name)
        for alias in ("Lambda", "lambda"):
            if alias in params:
                params["lambda_"] = params.pop(alias)
        # h2o-py's name for the tweedie power (GLMModel.GLMParameters)
        if "tweedie_variance_power" in params:
            params["tweedie_power"] = params.pop("tweedie_variance_power")
        unknown = set(params) - set(merged)
        if unknown:
            raise ValueError(f"unknown GLM params: {sorted(unknown)}")
        merged.update(params)
        super().__init__(**merged)

    # ---- solvers -----------------------------------------------------
    def _objective_eps(self) -> float:
        """GLM.java:1176 default: -1 → 1e-4 under lambda search or any
        nonzero lambda, 1e-6 for unpenalized fits."""
        oe = self.params.get("objective_epsilon")
        if oe is not None and float(oe) > 0:
            return float(oe)
        lam = self.params.get("lambda_")
        lam0 = (lam[0] if isinstance(lam, (list, tuple)) and lam
                else (lam or 0.0))
        if self.params.get("lambda_search") or float(lam0) != 0.0:
            return 1e-4
        return 1e-6

    def _fit_irlsm(self, X1, yv, w, fam: Family, l1: float, l2: float,
                   coef0, nobs: float, max_iter: int,
                   beta_eps: float, off=None):
        if off is None:
            off = jnp.zeros((X1.shape[0],), jnp.float32)
        coef = jnp.asarray(coef0, jnp.float32)
        # device arrays (coefficients, iterations run): the lambda path
        # warm-starts from the first without a host sync per lambda
        # (30-step searches × CV folds paid a blocking round trip each —
        # pyunit_glm_seed timeout)
        return _irls_solve(X1, coef, yv, w, off, jnp.float32(l1),
                           jnp.float32(l2), jnp.float32(beta_eps),
                           jnp.int32(max_iter),
                           fam.name, fam.link, jnp.float32(fam.p),
                           jnp.float32(fam.theta),
                           jnp.float32(self._objective_eps()),
                           use_l1=l1 > 0)

    def _fit_cod(self, X1, yv, w, fam: Family, l1: float, l2: float,
                 coef0: np.ndarray, max_iter: int, beta_eps: float,
                 bounds, off=None):
        """IRLS outer loop with a COD (box-constrained) inner solve;
        returns the coefficients and the iterations run."""
        Pp1 = X1.shape[1]
        if bounds is None:
            lo = jnp.full((Pp1,), -jnp.inf, jnp.float32)
            hi = jnp.full((Pp1,), jnp.inf, jnp.float32)
        else:
            lo = jnp.asarray(bounds[0], jnp.float32)
            hi = jnp.asarray(bounds[1], jnp.float32)
        if off is None:
            off = jnp.zeros((X1.shape[0],), jnp.float32)
        coef = jnp.asarray(coef0, jnp.float32)
        it = 0
        for it in range(1, max_iter + 1):
            coef, delta = _irls_iter_cod(
                X1, coef, yv, w, off, jnp.float32(l1), jnp.float32(l2),
                lo, hi, fam.name, fam.link, jnp.float32(fam.p),
                jnp.float32(fam.theta))
            if float(delta) < beta_eps:
                break
        return np.asarray(coef), it

    def _bounds_of(self, p, coef_names) -> Optional[tuple]:
        """lower/upper coefficient bounds from beta_constraints /
        non_negative (hex/glm/GLM.java BetaConstraints; the client ships
        a frame with names/lower_bounds/upper_bounds columns)."""
        Pp1 = len(coef_names) + 1
        lo = np.full(Pp1, -np.inf)
        hi = np.full(Pp1, np.inf)
        if p.get("non_negative"):
            lo[:-1] = 0.0
        bc = p.get("beta_constraints")
        if bc is not None:
            from h2o3_tpu.core.kv import DKV
            if isinstance(bc, str):
                bc = DKV.get(bc)
            rows: Dict[str, tuple] = {}
            if isinstance(bc, Frame):
                nm_col = bc.col("names")
                if nm_col.is_categorical and nm_col.domain:
                    codes = _fetch_np(nm_col.data)[: bc.nrows]
                    labels = [nm_col.domain[int(c)] if c >= 0 else None
                              for c in codes]
                else:
                    labels = [str(v) for v in nm_col.to_numpy()]
                lob = (bc.col("lower_bounds").to_numpy()
                       if "lower_bounds" in bc else [None] * bc.nrows)
                upb = (bc.col("upper_bounds").to_numpy()
                       if "upper_bounds" in bc else [None] * bc.nrows)
                for i, nm in enumerate(labels):
                    rows[str(nm)] = (lob[i], upb[i])
            elif isinstance(bc, dict):
                rows = {k: tuple(v) for k, v in bc.items()}
            for j, nm in enumerate(coef_names):
                if nm in rows:
                    l_, u_ = rows[nm]
                    if l_ is not None and not (isinstance(l_, float)
                                               and np.isnan(l_)):
                        lo[j] = float(l_)
                    if u_ is not None and not (isinstance(u_, float)
                                               and np.isnan(u_)):
                        hi[j] = float(u_)
        if not (np.isfinite(lo).any() or np.isfinite(hi).any()):
            return None
        return lo, hi

    def _fit_lbfgs(self, X1, yv, w, fam: Family, l2: float,
                   coef0: np.ndarray, nobs: float, max_iter: int,
                   off=None):
        if off is None:
            off = jnp.zeros((X1.shape[0],), jnp.float32)
        l2d = jnp.float32(l2)
        pw = jnp.float32(fam.p)
        th = jnp.float32(fam.theta)

        def vgrad(c):
            return _glm_value_grad(jnp.asarray(c, jnp.float32), X1, yv, w,
                                   off, l2d, fam.name, fam.link, pw, th)

        coef, _, n_iter = lbfgs(vgrad, coef0, max_iter=max_iter)
        return np.asarray(coef), int(n_iter)

    def _fit_multinomial(self, X1, y_int, w, K: int, l2: float,
                         nobs: float, max_iter: int,
                         solver: str = "l_bfgs", l1: float = 0.0):
        Pp1 = X1.shape[1]
        if solver in ("irlsm", "coordinate_descent",
                      "coordinate_descent_naive"):
            B0 = jnp.zeros((Pp1, K), jnp.float32)
            B = _multinomial_irls_solve(
                X1, B0, y_int, w, jnp.float32(l1), jnp.float32(l2),
                jnp.float32(1e-5), jnp.int32(max_iter), K=K,
                use_l1=l1 > 0)
            return np.asarray(B)
        l2d = jnp.float32(l2)

        def vgrad(c):
            return _multinomial_value_grad(jnp.asarray(c, jnp.float32), X1,
                                           y_int, w, l2d, K)

        sol, _, _ = lbfgs(vgrad, np.zeros(Pp1 * K), max_iter=max_iter)
        return sol.reshape(Pp1, K)

    # ---- training ----------------------------------------------------
    def _resolve_family(self, category: str) -> str:
        f = str(self.params["family"]).lower()
        if f != "auto":
            return f
        return {"Binomial": "binomial", "Multinomial": "multinomial",
                "Regression": "gaussian"}[category]

    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job, validation_frame: Optional[Frame] = None) -> Model:
        p = self.params
        mesh = get_mesh()
        category = infer_category(frame, y)
        fam_name = self._resolve_family(category)
        fam = Family(fam_name, float(p["tweedie_power"]), p["link"],
                     theta=float(p.get("theta") or 1e-5)) \
            if fam_name not in ("multinomial", "ordinal") else None

        di_frame = frame
        if p.get("interactions"):
            inter = p["interactions"]
            if isinstance(inter, str):
                inter = [c.strip().strip('"') for c in
                         inter.strip("[]").split(",")]
                p["interactions"] = inter
            di_frame = expand_interactions(frame, inter)
            x = list(x) + [c for c in di_frame.names
                           if c not in frame.names]
        from h2o3_tpu import telemetry
        with telemetry.span("glm.design"):
            di = build_datainfo(
                di_frame, x, standardize=bool(p["standardize"]),
                use_all_factor_levels=bool(p["use_all_factor_levels"]),
                missing_values_handling=p["missing_values_handling"],
                codes=holds_codes(fam_name))
            # the factor Gram's kernel mode: resolved once a fit, static
            X1 = with_gram_kernel(
                jax.device_put(_with_intercept(di.X), row_sharding(mesh)))
            codes = isinstance(X1, CodesDesign)
            telemetry.annotate(design="codes" if codes else "dense",
                               p=int(X1.shape[1]),
                               cat_levels=X1.cat_levels if codes else 0)
            # the response column joins the design row for row, as it lies
            assert X1.shape[0] == frame.nrows_padded

            w = frame.valid_weights()
            if p.get("weights_column"):
                wc = frame.col(p["weights_column"]).numeric_view()
                w = w * jnp.where(jnp.isnan(wc), 0.0, wc)
            # (CV fast path: standardization stats stay full-frame, like
            # the shared bin edges on the tree side)
            w = self._cv_masked_weights(w, frame)

            # offset_column: fixed per-row addition to eta (GLM.java offset)
            off = None
            if p.get("offset_column") and p["offset_column"] in frame:
                if fam_name == "multinomial":
                    # class-uniform offsets cancel in softmax — warn and
                    # ignore like the reference (hex/glm/GLM.java:978)
                    log.warning("offset_column has no effect on multinomial "
                                "and will be ignored")
                else:
                    ov = frame.col(p["offset_column"]).numeric_view()
                    off = jnp.where(jnp.isnan(ov), 0.0,
                                    ov).astype(jnp.float32)
            off_or0 = off if off is not None else \
                jnp.zeros((X1.shape[0],), jnp.float32)

        rc = frame.col(y)
        cmus, csds = coef_stats(di)
        output = {"category": category, "response": y, "names": list(x),
                  "coef_names": di.coef_names, "domain": rc.domain,
                  "coef_means": cmus.tolist(), "coef_sds": csds.tolist(),
                  "standardized": bool(p["standardize"]),
                  "nclasses": rc.cardinality if rc.is_categorical else 1}

        if fam_name == "ordinal":
            if not rc.is_categorical:
                raise ValueError("ordinal family requires a categorical "
                                 "response (ordered levels)")
            K = rc.cardinality
            with telemetry.span("glm.response", on_device=True,
                                host_bytes=0):
                y_dev, w = response_on_device(rc, w, categorical=True,
                                              dtype="int32")
            l2 = _l2_of(p)
            P = X1.shape[1] - 1
            l2d = jnp.float32(l2)

            def vgrad(c):
                return _ordinal_value_grad(jnp.asarray(c, jnp.float32),
                                           X1, y_dev, w, l2d, K)

            x0 = np.zeros(P + K - 1)
            x0[P + 1:] = np.log(0.5)       # small increasing gaps
            sol, _, _ = lbfgs(vgrad, x0,
                              max_iter=int(p["max_iterations"]) * 4)
            beta = np.asarray(sol[:P])
            a0 = float(sol[P])
            alphas = np.concatenate(
                [[a0], a0 + np.cumsum(np.exp(np.asarray(sol[P + 1:])))])
            output["category"] = "Ordinal"
            output["family"] = "ordinal"
            output["ordinal_alphas"] = alphas.tolist()
            coef_full = np.concatenate([beta, [0.0]])
            model = GLMModel(p, output, coef_full, Family("binomial"),
                             stats_of(di), list(x))
            probs_np = model._score_raw(frame)
            probs = jnp.asarray(np.stack(
                [np.pad(probs_np[f"p{k}"],
                        (0, X1.shape[0] - frame.nrows))
                 for k in range(K)], axis=1))
            model.training_metrics = mm.multinomial_metrics(
                probs, y_dev, w, domain=rc.domain)
            model.training_metrics.kind = "Ordinal"
            job.update(1.0)
            _finish(model, frame, validation_frame)
            return model

        if category == ModelCategory.MULTINOMIAL:
            if p.get("compute_p_values"):
                raise ValueError("compute_p_values is not supported for "
                                 "multinomial GLM (reference restriction)")
            K = rc.cardinality
            with telemetry.span("glm.response", on_device=True,
                                host_bytes=0):
                y_dev, w = response_on_device(rc, w, categorical=True,
                                              dtype="int32")
            nobs = float(jnp.sum(w))
            l2 = _l2_of(p)
            msolver = str(p["solver"]).lower()
            if msolver == "auto":
                # wide designs: K unrolled P×P grams + Cholesky per
                # sweep is O(K·P²) memory — follow the reference's
                # AUTO heuristic and fall back to L-BFGS (GLM.java
                # defaultSolver picks L_BFGS for large column counts)
                msolver = "irlsm" if X1.shape[1] <= 2000 else "l_bfgs"
            alpha_m = float(p["alpha"] if p["alpha"] is not None else 0.5)
            lam_m = p.get("lambda_") or 0.0
            if isinstance(lam_m, (list, tuple)):
                lam_m = lam_m[0] if lam_m else 0.0
            l1_m = float(alpha_m) * float(lam_m)
            B = self._fit_multinomial(X1, y_dev, w, K, l2, nobs,
                                      int(p["max_iterations"]),
                                      solver=msolver, l1=l1_m)
            model = GLMModel(p, output, B[:, 0], Family("binomial"),
                             stats_of(di), list(x), coef_multinomial=B)
            probs = jax.nn.softmax(_linear(X1, jnp.asarray(B, jnp.float32)),
                                   axis=1)
            model.training_metrics = mm.multinomial_metrics(
                probs, y_dev, w, domain=rc.domain)
            job.update(1.0)
            _finish(model, frame, validation_frame)
            return model

        # single-coefficient-vector families
        with telemetry.span("glm.response", on_device=True, host_bytes=0):
            y_dev, w = response_on_device(
                rc, w, categorical=category == ModelCategory.BINOMIAL)
            # the phase's one wait: for device work glm.design queued
            nobs = float(jnp.sum(w))

        alpha = float(p["alpha"] if p["alpha"] is not None else 0.5)
        with telemetry.span("glm.lambda_path"):
            lambdas = _lambda_path(p, X1, y_dev, w, nobs, alpha, mesh)
        if p.get("compute_p_values") and any(l != 0.0 for l in lambdas):
            # fail before the (possibly long) lambda-path fit
            raise ValueError("compute_p_values requires no regularization "
                             "(lambda = 0)")
        solver = str(p["solver"]).lower()
        bounds = self._bounds_of(p, di.coef_names)
        if solver == "auto":
            solver = "coordinate_descent" if bounds is not None else "irlsm"
        elif bounds is not None:
            # constrained solves go through the projected COD path
            solver = "coordinate_descent"

        coef = np.zeros(X1.shape[1])
        best = None
        coef_path = None
        # (glm.solve span, iterations it ran): the IRLS count stays on
        # the device until the coefficients are read
        solves = []
        fuse_path = (len(lambdas) > 1 and bounds is None
                     and solver not in ("coordinate_descent",
                                        "coordinate_descent_naive",
                                        "l_bfgs", "lbfgs"))
        from h2o3_tpu.core import recovery as _recovery
        from h2o3_tpu.core.watchdog import maybe_fail
        from h2o3_tpu.telemetry import stepprof
        if fuse_path:
            # whole regularization path in ONE compiled scan of IRLS
            # while_loops (pyunit_glm_seed: 30 lambdas x CV folds paid a
            # dispatch each — the fused path pays one per FIT)
            l1s = jnp.asarray([lam * alpha for lam in lambdas], jnp.float32)
            l2s = jnp.asarray([lam * (1.0 - alpha) for lam in lambdas],
                              jnp.float32)
            stepprof.chunk_begin()
            with telemetry.span("glm.solve", solver=solver,
                                lambdas=len(lambdas),
                                p=int(X1.shape[1]),
                                gram_kernel=gram_kernel_name(X1)) as sp:
                best, coef_path, its = _irls_solve_path(
                    X1, jnp.asarray(coef, jnp.float32), y_dev, w, off_or0,
                    l1s, l2s, jnp.float32(p["beta_epsilon"]),
                    jnp.int32(p["max_iterations"]), fam.name, fam.link,
                    jnp.float32(fam.p), jnp.float32(fam.theta),
                    jnp.float32(self._objective_eps()),
                    use_l1=alpha > 0)
                stepprof.compute_done((best, coef_path))
            solves.append((sp, its))
            stepprof.chunk_end(lambdas=len(lambdas))
            job.update(1.0, f"lambda path ({len(lambdas)})")
        else:
            # in-fit checkpointer (core/recovery.py): the IRLS outer
            # walk's host boundary is the lambda step — snapshot the
            # warm-start coefficients + path position so a killed
            # multi-lambda fit resumes at the next lambda, bit-identical
            # (the fused path is ONE dispatch and has no mid-state)
            fc = None
            li0 = 0
            if len(lambdas) > 1 and \
                    getattr(self, "_cv_fold_mask", None) is None:
                fc = _recovery.fit_checkpointer(
                    "glm", p, y, x, frame.nrows, default_every=1)
                if fc is not None:
                    _loaded = fc.load()
                    if _loaded is not None:
                        _st = _loaded[1]
                        li0 = int(_st["li"])
                        coef = np.asarray(_st["coef"])
                        best = coef
            for li, lam in enumerate(lambdas):
                if li < li0:
                    continue            # resumed past this lambda
                l1 = lam * alpha
                l2 = lam * (1.0 - alpha)
                stepprof.chunk_begin()
                with telemetry.span("glm.solve", solver=solver,
                                    lam=float(lam),
                                    p=int(X1.shape[1]),
                                    gram_kernel=gram_kernel_name(X1)) as sp:
                    if solver in ("coordinate_descent",
                                  "coordinate_descent_naive"):
                        coef, its = self._fit_cod(
                            X1, y_dev, w, fam, l1, l2, coef,
                            int(p["max_iterations"]),
                            float(p["beta_epsilon"]), bounds, off=off_or0)
                    elif solver in ("l_bfgs", "lbfgs") and l1 == 0:
                        coef, its = self._fit_lbfgs(
                            X1, y_dev, w, fam, l2, coef, nobs,
                            int(p["max_iterations"]), off=off_or0)
                    else:
                        coef, its = self._fit_irlsm(
                            X1, y_dev, w, fam, l1, l2, coef, nobs,
                            int(p["max_iterations"]),
                            float(p["beta_epsilon"]), off=off_or0)
                    stepprof.compute_done(coef)
                solves.append((sp, its))
                stepprof.chunk_end(lam=float(lam))
                job.update(1.0 / len(lambdas),
                           f"lambda {li + 1}/{len(lambdas)}")
                best = coef
                if fc is not None:
                    _li, _c = li + 1, coef
                    fc.maybe_save(li + 1, lambda: {
                        "li": _li, "coef": _recovery.snapshot_host(_c)})
                maybe_fail("fit_chunk")
                maybe_fail("device_oom")
            if fc is not None:
                fc.clear()
        with telemetry.span("glm.readback"):
            # ONE host materialization after the path: the coefficients
            # and, with them, the iterations each solve ran
            coef, ran = jax.device_get((best, [its for _, its in solves]))
            coef = np.asarray(coef)
        for (sp, _), n in zip(solves, ran):
            sp.annotate(iterations=int(np.sum(n)))
        telemetry.counter("train_iterations_total", algo="glm").inc(
            int(sum(np.sum(n) for n in ran)))

        output["lambda_best"] = float(lambdas[-1])
        # a CV sweep selects lambda by summed holdout deviance over this
        # path (GLM.java xval-deviance lambda selection) — stash it once
        # as host arrays (ml/cv.py train_with_cv picks them up)
        sel_lambda = p.get("_cv_selected_lambda")
        if sel_lambda is not None and coef_path is not None:
            li = int(np.argmin(np.abs(np.asarray(lambdas) - sel_lambda)))
            coef = np.asarray(coef_path[li])
            output["lambda_best"] = float(lambdas[li])

        if p.get("compute_p_values"):
            # std errors / z / p from the Fisher information at the MLE
            # (GLM.java compute_p_values; lambda==0 validated up front)
            output["coefficients_table"] = _p_values_table(
                X1, y_dev, w, jnp.asarray(coef, jnp.float32), fam,
                di.coef_names + ["Intercept"], nobs, off=off_or0)

        model = GLMModel(p, output, coef, fam, stats_of(di), list(x))
        if coef_path is not None:
            model._coef_path = np.asarray(coef_path)      # [L, P+1]
            model._lambda_path_vals = list(lambdas)
        with telemetry.span("glm.metrics"):
            mu = fam.linkinv(_linear(X1, jnp.asarray(coef, jnp.float32))
                             + off_or0)
            if category == ModelCategory.BINOMIAL:
                model.training_metrics = mm.binomial_metrics(mu, y_dev, w)
                model.output["default_threshold"] = \
                    model.training_metrics["max_f1_threshold"]
            else:
                model.training_metrics = mm.regression_metrics(
                    mu, y_dev, w,
                    deviance_fn=lambda a, b: fam.deviance(a, b))
            _finish(model, frame, validation_frame)
        return model


def _l2_of(p) -> float:
    lam = p["lambda_"]
    if lam is None:
        return 0.0
    lam = lam[0] if isinstance(lam, (list, tuple)) else lam
    return float(lam) * (1.0 - float(p["alpha"] or 0.0))


def _lambda_path(p, X1, y, w, nobs, alpha, mesh) -> List[float]:
    """Regularization path (GLM.java lambda search semantics)."""
    if p.get("_lambda_path_override"):
        # CV fold fits share the MAIN model's full-frame path so their
        # per-lambda holdout deviances align index-wise (the reference
        # likewise evaluates every fold on one shared path)
        return list(p["_lambda_path_override"])
    lam = p["lambda_"]
    if not p["lambda_search"]:
        if lam is None:
            return [0.0]
        return list(lam) if isinstance(lam, (list, tuple)) else [float(lam)]
    # lambda_max: smallest lambda with all (penalized) coefs zero
    ybar = float(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12))
    if isinstance(X1, CodesDesign):
        xty = jnp.abs(codes_rmatvec(X1, w * (y - ybar), mesh=mesh))[:-1]
    else:
        xty = jnp.abs((X1 * w[:, None]).T @ (y - ybar))[:-1]  # no intercept
    lam_max = float(jnp.max(xty)) / (nobs * max(alpha, 1e-3))
    lmr = float(p["lambda_min_ratio"])
    if lmr <= 0:            # wire default -1 = auto (GLMParameters)
        lmr = 1e-4
    lam_min = lam_max * lmr
    n = int(p["nlambdas"])
    if n <= 0:              # wire default -1 = auto → 100-step path
        n = 100
    return list(np.exp(np.linspace(np.log(lam_max), np.log(lam_min), n)))


def _p_values_table(X1, y, w, coef, fam: Family, names, nobs: float,
                    off=None):
    """Wald inference rows (name, coefficient, std_error, z_value,
    p_value) — hex/glm GLMModel coefficients table with p-values.

    Fisher information = X'WX with the IRLS variance weights at the
    fitted coefficients; gaussian uses the t distribution with the
    moment-estimated dispersion, other families the normal (z) with
    dispersion 1 (binomial/poisson) or the Pearson estimate (gamma/
    tweedie), matching the reference's computePValues path."""
    eta = _linear(X1, coef) if off is None else _linear(X1, coef) + off
    mu = fam.linkinv(eta)
    name = fam.name
    # general GLM Fisher weight: (dmu/deta)^2 / Var(mu) — exact for every
    # family × link combination Family supports
    dmu = fam.dmu_deta(eta, mu)
    vw = dmu * dmu / jnp.maximum(fam.variance(mu), 1e-12)
    wi = w * vw
    if isinstance(X1, CodesDesign):
        info = gram(X1, wi, jnp.zeros_like(wi), mesh=get_mesh())[0]
    else:
        info = (X1 * wi[:, None]).T @ X1
    info_h = np.asarray(info, dtype=np.float64)
    P = info_h.shape[0]
    try:
        cov = np.linalg.inv(info_h + 1e-10 * np.eye(P))
    except np.linalg.LinAlgError:
        cov = np.linalg.pinv(info_h)
    dof = max(nobs - P, 1.0)
    if name == "gaussian":
        resid = np.asarray(y - mu, dtype=np.float64)
        wh = np.asarray(w, dtype=np.float64)
        dispersion = float((wh * resid ** 2).sum() / dof)
    elif name in ("binomial", "poisson"):
        dispersion = 1.0
    else:   # gamma/tweedie: Pearson estimate over Var(mu)
        resid = np.asarray(y - mu, dtype=np.float64)
        var = np.maximum(np.asarray(fam.variance(mu), dtype=np.float64),
                         1e-12)
        wh = np.asarray(w, dtype=np.float64)
        dispersion = float((wh * resid ** 2 / var).sum() / dof)
    se = np.sqrt(np.maximum(np.diag(cov) * dispersion, 0.0))
    ch = np.asarray(coef, dtype=np.float64)
    z = np.where(se > 0, ch / np.maximum(se, 1e-300), np.inf)
    from scipy import stats as _st
    if name == "gaussian":
        pv = 2.0 * _st.t.sf(np.abs(z), df=dof)
    else:
        pv = 2.0 * _st.norm.sf(np.abs(z))
    return [{"name": nm, "coefficient": float(c), "std_error": float(s),
             "z_value": float(zz), "p_value": float(pp)}
            for nm, c, s, zz, pp in zip(names, ch, se, z, pv)]


def _finish(model: GLMModel, frame: Frame, validation_frame):
    if validation_frame is not None:
        model.validation_metrics = model.model_performance(validation_frame)


# ---- model-batched training (parallel/model_batch.py trainer) ----------


def fit_glm_batched(builder_cls, params_list: List[dict], frame: Frame,
                    y: Optional[str] = None,
                    x: Optional[Sequence[str]] = None,
                    validation_frame: Optional[Frame] = None) -> List[Model]:
    """Train a grid bucket's (alpha, lambda) product as ONE vmapped IRLS
    program (_irls_solve_batched): the design matrix, weights and
    response adapt once, per-combo l1/l2/objective-epsilon stack onto
    the vmapped axis, and the sequential walk's per-combo dispatch+
    readback round trips collapse into one per use_l1 partition (ADMM
    vs Cholesky inner solves are distinct compiled programs, exactly
    like the sequential path's use_l1 static flag).

    Raises parallel.model_batch.BatchIneligible for anything the
    vmapped solve cannot express — CV, lambda_search, constrained/
    L-BFGS solvers, multinomial/ordinal, p-values, interactions — and
    the caller falls back per-combo."""
    from h2o3_tpu.parallel.model_batch import BATCHABLE_KNOBS, BatchIneligible

    builders = [builder_cls(**p) for p in params_list]
    M = len(builders)
    b0 = builders[0]
    p0 = b0.params
    batchable = BATCHABLE_KNOBS["glm"] | {"lambda_"}
    for b in builders[1:]:
        for k, v in b.params.items():
            if k not in batchable and v != p0.get(k):
                raise BatchIneligible(f"structural param '{k}' varies")
    lams, alphas = [], []
    for b in builders:
        p = b.params
        if int(p.get("nfolds") or 0) >= 2 or p.get("fold_column"):
            raise BatchIneligible("cross-validation")
        if p.get("lambda_search"):
            raise BatchIneligible("lambda_search (warm-started path)")
        if p.get("compute_p_values"):
            raise BatchIneligible("compute_p_values")
        if p.get("beta_constraints") is not None or p.get("non_negative"):
            raise BatchIneligible("constrained solve (projected COD)")
        if p.get("interactions"):
            raise BatchIneligible("interaction expansion")
        if str(p.get("solver") or "auto").lower() not in ("auto", "irlsm"):
            raise BatchIneligible(f"solver {p.get('solver')}")
        if float(p.get("max_runtime_secs") or 0.0) > 0:
            raise BatchIneligible("per-model runtime cap")
        lam = p.get("lambda_")
        if isinstance(lam, (list, tuple)):
            if len(lam) > 1:
                raise BatchIneligible("multi-lambda combo")
            lam = lam[0] if lam else 0.0
        lams.append(float(lam or 0.0))
        alphas.append(float(p["alpha"] if p["alpha"] is not None else 0.5))

    mesh = get_mesh()
    x = b0.resolve_x(frame, x, y)
    category = infer_category(frame, y)
    if category == ModelCategory.MULTINOMIAL:
        raise BatchIneligible("multinomial")
    fam_name = b0._resolve_family(category)
    if fam_name in ("multinomial", "ordinal"):
        raise BatchIneligible(f"family {fam_name}")
    fam = Family(fam_name, float(p0["tweedie_power"]), p0["link"],
                 theta=float(p0.get("theta") or 1e-5))

    # ---- shared preamble (identical to the sequential _fit, but for the
    # factor Gram's mode: a design held as codes keeps ``off``, the XLA
    # scan — the kernel is not batched) ----------------------------------
    di = build_datainfo(frame, x, standardize=bool(p0["standardize"]),
                        use_all_factor_levels=bool(
                            p0["use_all_factor_levels"]),
                        missing_values_handling=p0["missing_values_handling"],
                        codes=holds_codes(fam_name))
    X1 = jax.device_put(_with_intercept(di.X), row_sharding(mesh))
    assert X1.shape[0] == frame.nrows_padded
    w = frame.valid_weights()
    if p0.get("weights_column"):
        wc = frame.col(p0["weights_column"]).numeric_view()
        w = w * jnp.where(jnp.isnan(wc), 0.0, wc)
    off = None
    if p0.get("offset_column") and p0["offset_column"] in frame:
        ov = frame.col(p0["offset_column"]).numeric_view()
        off = jnp.where(jnp.isnan(ov), 0.0, ov).astype(jnp.float32)
    off_or0 = off if off is not None else \
        jnp.zeros((X1.shape[0],), jnp.float32)
    rc = frame.col(y)
    cmus, csds = coef_stats(di)
    output_base = {"category": category, "response": y, "names": list(x),
                   "coef_names": di.coef_names, "domain": rc.domain,
                   "coef_means": cmus.tolist(), "coef_sds": csds.tolist(),
                   "standardized": bool(p0["standardize"]),
                   "nclasses": rc.cardinality if rc.is_categorical else 1}
    y_dev, w = response_on_device(
        rc, w, categorical=category == ModelCategory.BINOMIAL)

    # ---- one vmapped solve per use_l1 partition ----------------------
    l1_all = np.array([lams[m] * alphas[m] for m in range(M)], np.float32)
    l2_all = np.array([lams[m] * (1.0 - alphas[m]) for m in range(M)],
                      np.float32)
    oe_all = np.array([b._objective_eps() for b in builders], np.float32)
    coef0 = jnp.zeros((X1.shape[1],), jnp.float32)
    coefs = np.zeros((M, X1.shape[1]), np.float32)
    from h2o3_tpu import telemetry
    from h2o3_tpu.telemetry import stepprof
    for use_l1 in (False, True):
        # sequential parity: _fit_irlsm picks ADMM iff l1 > 0
        idx = np.where((l1_all > 0) == use_l1)[0]
        if idx.size == 0:
            continue
        stepprof.chunk_begin()
        with telemetry.span("glm.solve_batched", solver="irlsm",
                            width=int(idx.size),
                            gram_kernel=gram_kernel_name(X1)) as sp:
            out = _irls_solve_batched(
                X1, coef0, y_dev, w, off_or0,
                jnp.asarray(l1_all[idx]), jnp.asarray(l2_all[idx]),
                jnp.float32(p0["beta_epsilon"]),
                jnp.int32(p0["max_iterations"]), fam.name, fam.link,
                jnp.float32(fam.p), jnp.float32(fam.theta),
                jnp.asarray(oe_all[idx]), use_l1=use_l1)
            stepprof.compute_done(out)
        stepprof.chunk_end(width=int(idx.size))
        coefs[idx], its = jax.device_get(out)
        sp.annotate(iterations=int(its.sum()))
        telemetry.counter("train_iterations_total", algo="glm").inc(
            int(its.sum()))

    # ---- per-model unstack into ordinary Model objects ---------------
    models: List[Model] = []
    t_done = time.time()
    for m in range(M):
        output = dict(output_base)
        output["lambda_best"] = lams[m]
        model = GLMModel(builders[m].params, output, coefs[m], fam,
                         stats_of(di), list(x))
        mu = fam.linkinv(_linear(X1, jnp.asarray(coefs[m], jnp.float32))
                         + off_or0)
        if category == ModelCategory.BINOMIAL:
            model.training_metrics = mm.binomial_metrics(mu, y_dev, w)
            model.output["default_threshold"] = \
                model.training_metrics["max_f1_threshold"]
        else:
            model.training_metrics = mm.regression_metrics(
                mu, y_dev, w,
                deviance_fn=lambda a, b: fam.deviance(a, b))
        _finish(model, frame, validation_frame)
        model.output["run_time"] = time.time() - t_done
        models.append(model)
    return models
