"""A GLM with factor predictors on a design held as codes
(``frame/datainfo.CodesDesign``): X'WX and X'Wz formed from the codes
(``ops/gram.py``, scope ``gram.cat``), the linear predictor from
coefficient lookups (``glm.eta``). Held to the dense design it replaces —
Gram, coefficients, deviance, predictions, names, the coefficient table —
on one, two and four devices, to a float64 reference, and the design
the fit picks, the memory it is admitted with, the lower-precision
control failing the benchmark cell's limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_cat_reference as ref64
import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.core import memgov
from h2o3_tpu.frame.datainfo import CodesDesign, build_datainfo
from h2o3_tpu.models import glm as glm_mod
from h2o3_tpu.models.glm import GLMEstimator, _with_intercept
from h2o3_tpu.ops import gram as gram_mod
from h2o3_tpu.ops.gram import codes_matvec, codes_rmatvec, gram
from h2o3_tpu.parallel import mesh as mesh_mod

# a factor past the 128-row left operand (200 levels), three narrow ones
# gathered into one, an NA in one of them; two numerics
LEVELS = {"a": 5, "b": 40, "k": 200, "c": 3}


def mixed_columns(n=6000, seed=0, family="binomial", levels=LEVELS):
    r = np.random.default_rng(seed)
    cols = {f: r.integers(0, L, n) for f, L in levels.items()}
    cols["a"][r.random(n) < 0.02] = -1                   # NA codes
    cols["x"] = r.standard_normal(n).astype(np.float32)
    cols["z"] = (3.0 + 2.0 * r.standard_normal(n)).astype(np.float32)
    beta = {f: 0.3 * r.standard_normal(L) for f, L in levels.items()}
    eta = sum(np.where(cols[f] >= 0, beta[f][np.maximum(cols[f], 0)], 0.0)
              for f in levels) + 0.4 * cols["x"] - 0.1 * cols["z"]
    if family == "binomial":
        cols["y"] = (r.random(n) < 1 / (1 + np.exp(-eta))).astype(np.int32)
    else:
        cols["y"] = (eta + r.standard_normal(n)).astype(np.float32)
    doms = {f: [f"{f}{i:03d}" for i in range(L)] for f, L in levels.items()}
    if family == "binomial":
        doms["y"] = ["n", "y"]
    return cols, doms


@pytest.fixture(params=[1, 2, 4], ids=lambda n: f"data{n}")
def devices(request):
    """The process mesh over ``n`` devices for one test."""
    old = mesh_mod.get_mesh()
    n = request.param
    mesh_mod.set_global_mesh(mesh_mod.make_mesh(jax.devices()[:n], n, 1))
    yield n
    mesh_mod.set_global_mesh(old)


def designs(fr):
    x = [n for n in fr.names if n != "y"]
    codes = _with_intercept(build_datainfo(fr, x, codes=True).X)
    dense = _with_intercept(build_datainfo(fr, x).X)
    assert isinstance(codes, CodesDesign)
    assert codes.shape == dense.shape
    return codes, dense


@pytest.mark.parametrize("walk", [None, (512, 128)],
                         ids=["one-block", "steps-of-blocks"])
def test_the_codes_gram_is_the_dense_gram(devices, walk, monkeypatch,
                                         request):
    """``walk``: (``CAT_CHUNK``, ``CAT_SUM``) — a shard's rows in several
    steps of several blocks, as at full size, where the defaults make a
    test frame's shard one block of one step. They are read when a
    program is traced: ``gram`` traces anew at every call, the two jitted
    products' caches are cleared on both sides."""
    if walk:
        monkeypatch.setattr(gram_mod, "CAT_CHUNK", walk[0])
        monkeypatch.setattr(gram_mod, "CAT_SUM", walk[1])
        for jitted in (codes_matvec, codes_rmatvec):
            jitted.clear_cache()
            request.addfinalizer(jitted.clear_cache)
    cols, doms = mixed_columns()
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    codes, dense = designs(fr)
    r = np.random.default_rng(1)
    w = fr.valid_weights() * jnp.asarray(
        r.random(fr.nrows_padded).astype(np.float32))
    z = jnp.asarray(r.standard_normal(fr.nrows_padded).astype(np.float32))
    mesh = mesh_mod.get_mesh()
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(a) for a in gram(dense, w, z, mesh=mesh)]
        beta = jnp.asarray(r.standard_normal(dense.shape[1]), jnp.float32)
        eta = np.asarray(dense @ beta)
        xtv = np.asarray(dense.T @ z)
    got = [np.asarray(a) for a in gram(codes, w, z, mesh=mesh)]
    for g, d in zip(got, want):
        np.testing.assert_allclose(g, d, rtol=1e-5,
                                   atol=1e-5 * np.abs(d).max())
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(got[0]).T)
    np.testing.assert_allclose(np.asarray(codes_matvec(codes, beta,
                                                       mesh=mesh)),
                               eta, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(codes_rmatvec(codes, z,
                                                        mesh=mesh)),
                               xtv, rtol=1e-5, atol=1e-4)


def fit(fr, family, codes=True, monkeypatch=None, **kw):
    if not codes:
        dense = glm_mod.build_datainfo
        monkeypatch.setattr(glm_mod, "build_datainfo", lambda *a, **k:
                            dense(*a, **{**k, "codes": False}))
    try:
        return GLMEstimator(family=family, solver="irlsm", lambda_=0.0,
                            **kw).train(fr, y="y")
    finally:
        if not codes:
            monkeypatch.undo()


@pytest.mark.parametrize("family", ["binomial", "gaussian"])
def test_the_fit_on_codes_is_the_fit_on_the_dense_design(devices, family,
                                                         monkeypatch):
    """Both fits run to convergence. Narrow factors only: the dense
    solve's rank-safety ridge (1e-6 on the normalised Gram) shrinks a
    factor's weakly determined direction — its levels against its dropped
    first one — by about 1e-6 * L^2 / w, a few hundredths at 200 levels;
    the codes fit of a 200-level factor is held to float64 below."""
    cols, doms = mixed_columns(family=family,
                               levels={"a": 5, "b": 12, "c": 3})
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    tight = dict(beta_epsilon=1e-8, objective_epsilon=1e-12)
    on_codes = fit(fr, family, **tight)
    on_dense = fit(fr, family, codes=False, monkeypatch=monkeypatch, **tight)
    a, b = on_codes.coefficients, on_dense.coefficients
    assert list(a) == list(b)
    np.testing.assert_allclose(list(a.values()), list(b.values()),
                               atol=2e-4)
    for k in ("MSE", "logloss") if family == "binomial" else ("MSE",):
        assert on_codes.training_metrics[k] == pytest.approx(
            on_dense.training_metrics[k], rel=1e-5)
    col = "p1" if family == "binomial" else "predict"
    pa = on_codes.predict(fr).to_pandas()[col].values
    pb = on_dense.predict(fr).to_pandas()[col].values
    np.testing.assert_allclose(pa, pb, atol=2e-4)


def test_names_statistics_and_table_are_the_dense_views(monkeypatch):
    cols, doms = mixed_columns(n=3000, levels={"a": 5, "b": 12, "c": 3})
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    tight = dict(beta_epsilon=1e-8, objective_epsilon=1e-12)
    on_codes = fit(fr, "binomial", **tight)
    on_dense = fit(fr, "binomial", codes=False, monkeypatch=monkeypatch,
                   **tight)
    for k in ("coef_names", "coef_means", "coef_sds", "standardized"):
        assert on_codes.output[k] == on_dense.output[k], k
    raw = glm_mod.destandardize_coefs(
        np.asarray(on_codes.coef, np.float64), on_codes.output["coef_means"],
        on_codes.output["coef_sds"])
    np.testing.assert_allclose(list(on_codes.coefficients.values()), raw)
    from h2o3_tpu.api.model_schema import model_to_v3
    ta = model_to_v3(on_codes)["output"]["coefficients_table"]
    tb = model_to_v3(on_dense)["output"]["coefficients_table"]
    assert ta["columns"] == tb["columns"]
    assert ta["data"][0] == tb["data"][0]            # names, column-major
    for got, want in zip(ta["data"][1:], tb["data"][1:]):
        # the dense solve's ridge, 1e-6 * 12^2 / w along a factor's
        # levels against its first (test above)
        np.testing.assert_allclose(got, want, atol=1e-3)


@pytest.mark.parametrize("family", ["binomial", "gaussian"])
def test_the_fit_against_a_float64_reference(family):
    cols, doms = mixed_columns(n=8000, seed=3, family=family)
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    model = fit(fr, family, beta_epsilon=1e-8, objective_epsilon=1e-12)
    host = dict(cols)
    ref = ref64.fit(host, doms, "y", family)
    assert model.output["coef_names"] == ref["names"]
    got = np.array([model.coefficients[n] for n in ref["names"]]
                   + [model.coefficients["Intercept"]])
    scale = np.maximum(np.abs(ref["coef"]), np.median(np.abs(ref["coef"])))
    assert np.max(np.abs(got - ref["coef"]) / scale) < 1e-4
    col = "p1" if family == "binomial" else "predict"
    np.testing.assert_allclose(model.predict(fr).to_pandas()[col].values,
                               ref["mu"], atol=1e-4)


def last_span(name):
    return [s for s in telemetry.spans_snapshot(last=4096)
            if s["name"] == name][-1]


def test_the_fit_picks_the_codes_design_where_factors_are_predictors():
    """The cell's widths: six factors (12, 31, 7, 29, 340, 340 levels)
    and two numerics are 756 coefficients, 753 of them indicators."""
    r = np.random.default_rng(5)
    widths = {"Month": 12, "DayofMonth": 31, "DayOfWeek": 7,
              "UniqueCarrier": 29, "Origin": 340, "Dest": 340}
    cols = {f: r.integers(0, L, 4000) for f, L in widths.items()}
    cols["DepTime"] = r.integers(0, 2400, 4000)
    cols["Distance"] = r.integers(30, 4983, 4000)
    cols["y"] = r.integers(0, 2, 4000)
    doms = {f: [f"{f}{i:03d}" for i in range(L)] for f, L in widths.items()}
    doms["y"] = ["NO", "YES"]
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    GLMEstimator(family="binomial", lambda_=0.0,
                 max_iterations=1).train(fr, y="y")
    meta = last_span("glm.design")["meta"]
    assert (meta["design"], meta["p"], meta["cat_levels"]) == \
        ("codes", 756, 753)
    assert last_span("glm.solve")["meta"]["p"] == 756
    num = h2o3_tpu.Frame.from_numpy({"x": r.standard_normal(500),
                                     "y": r.integers(0, 2, 500)},
                                    domains={"y": ["NO", "YES"]})
    GLMEstimator(family="binomial", max_iterations=1).train(num, y="y")
    meta = last_span("glm.design")["meta"]
    assert (meta["design"], meta["p"], meta["cat_levels"]) == \
        ("dense", 2, 0)


def test_admission_counts_the_design_the_fit_builds(monkeypatch):
    cols, doms = mixed_columns(n=2000)
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    x = [n for n in fr.names if n != "y"]
    P = sum(L - 1 for L in LEVELS.values()) + 2
    # codes: the numerics and the intercept beside them, twice (DataInfo.X
    # and X1), and the row state
    codes = GLMEstimator(family="binomial").design_row_bytes(fr, x)
    assert codes == 2 * 2 * 4 + 4 + glm_mod.ROW_STATE_BYTES
    # an ordinal fit slices a dense matrix: every column, twice
    dense = GLMEstimator(family="ordinal").design_row_bytes(fr, x)
    assert dense == 2 * P * 4 + 4 + glm_mod.ROW_STATE_BYTES
    assert memgov.estimate_fit_bytes("glm", {}, fr, x, row_bytes=dense) \
        - memgov.estimate_fit_bytes("glm", {}, fr, x, row_bytes=codes) \
        == fr.nrows_padded * (dense - codes)
    # and admission asks the estimator: the fit's reservation is its count
    seen = []
    admit = memgov.governor.admit_fit
    monkeypatch.setattr(memgov.governor, "admit_fit",
                        lambda *a: seen.append(a[-1]) or admit(*a))
    GLMEstimator(family="binomial", lambda_=0.0,
                 max_iterations=1).train(fr, y="y")
    assert seen == [codes]


def test_the_weights_in_one_bfloat16_piece_fail_the_cells_limit():
    """The benchmark cell's reference: its control that enters the
    weights as one bfloat16 piece (what the factor Gram's three pieces
    are for) is off the Newton path by more than the limit."""
    import json
    import os
    from benchmark.generators import airlines_factors
    from benchmark.references import glm_cat
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-airlines-116m-cat.json")) as f:
        config = json.load(f)
    data = airlines_factors.generate(11, 65536,
                                     **config["generator"]["args"])
    numbers = glm_cat.check(data, glm_cat.control(data, {}, "bf16w"), {})
    assert numbers["path_gap"] > config["limits"]["path_gap"]
