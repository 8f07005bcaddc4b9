"""The factor Gram's Pallas kernel (``ops/pallas/gramkernel.py``) against
the XLA scan it replaces (``ops/gram.py`` ``_local_codes_gram``) and a
float64 Gram, in interpret mode on the CPU: the plans a design can give
— the airlines cell's six factors, a factor past the 128-row left
operand, a single factor, no numerics, NA codes, a shard's rows not a
multiple of a block —, on one and four devices; the compensation over
many blocks; fits through the kernel against fits through the scan; the
VMEM fallback and the span that says which ran."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.frame.datainfo import CodesDesign
from h2o3_tpu.models import glm as glm_mod
from h2o3_tpu.models.glm import GLMEstimator
from h2o3_tpu.ops import gram as gram_mod
from h2o3_tpu.ops import pallas as plx
from h2o3_tpu.ops.pallas import gramkernel
from h2o3_tpu.parallel import mesh as mesh_mod
from test_glm_categorical import mixed_columns

AIRLINES = (12, 31, 7, 29, 340, 340)


@pytest.fixture(params=[1, 4], ids=lambda n: f"data{n}")
def devices(request):
    """The process mesh over ``n`` devices for one test."""
    old = mesh_mod.get_mesh()
    n = request.param
    mesh_mod.set_global_mesh(mesh_mod.make_mesh(jax.devices()[:n], n, 1))
    yield n
    mesh_mod.set_global_mesh(old)


def codes_design(levels, n, nd, seed, na=0.0, rare=None):
    """A ``CodesDesign`` of ``n`` rows, its weights, working response and
    the float64 dense matrix it stands for. Each factor drops its first
    level; ``na`` the share of NA codes, ``rare`` the share of rows at a
    first level (else uniform)."""
    r = np.random.default_rng(seed)
    codes, nas, factors, cols, off = [], [], [], [], 0
    for card in levels:
        c = r.integers(1 if rare else 0, card, n)
        if rare:
            c[r.random(n) < rare] = 0
        m = r.random(n) < na
        codes.append(c.astype(np.int32))
        nas.append(m)
        factors.append((off, 1, card))
        oh = (c[:, None] == np.arange(1, card)[None, :]) & ~m[:, None]
        cols.append(oh.astype(np.float64))
        off += card - 1
    dense = r.standard_normal((n, nd)).astype(np.float32)
    X = CodesDesign(codes=tuple(jnp.asarray(c) for c in codes),
                    nas=tuple(jnp.asarray(m) for m in nas),
                    dense=jnp.asarray(dense), factors=tuple(factors),
                    dense_cols=tuple(range(off, off + nd)), p=off + nd)
    w = r.random(n).astype(np.float32)
    z = r.standard_normal(n).astype(np.float32)
    return X, w, z, np.concatenate(cols + [dense.astype(np.float64)], 1)


def float64_gram(Xd, w, z):
    w, z = w.astype(np.float64), z.astype(np.float64)
    return Xd.T @ (Xd * w[:, None]), Xd.T @ (w * z), w.sum()


def both_grams(X, w, z):
    mesh = mesh_mod.get_mesh()
    w, z = jnp.asarray(w), jnp.asarray(z)
    scan = gram_mod.gram(X, w, z, mesh=mesh)
    kern = gram_mod.gram(dataclasses.replace(X, gram_kernel="interpret"),
                         w, z, mesh=mesh)
    return ([np.asarray(a, np.float64) for a in scan],
            [np.asarray(a, np.float64) for a in kern])


def close(got, want, rel):
    for g, d in zip(got, want):
        np.testing.assert_allclose(g, d, rtol=rel,
                                   atol=rel * np.abs(d).max())


# rows a grid step of the kernel reads in the plans' tests: two 512-row
# sub-blocks. ``gram.gram`` traces anew at every call, so the patched
# value reaches the kernel without clearing a cache
BLOCK = 1024

PLANS = {
    # the cell: four narrow factors gathered, Origin x Dest
    "airlines": (AIRLINES, 3, 0.0, 2 * BLOCK),
    # a factor past CAT_GROUP_ROWS, three narrow ones gathered
    "wide": ((5, 40, 200, 3), 2, 0.0, 2 * BLOCK),
    "single": ((60,), 2, 0.0, 2 * BLOCK),
    "no-numerics": ((7, 29, 31), 0, 0.0, 2 * BLOCK),
    "na-codes": ((5, 40, 3), 2, 0.05, 2 * BLOCK),
    # a shard's second block and its last sub-block padded
    "ragged": ((12, 31, 7), 1, 0.02, BLOCK + 226),
}


@pytest.mark.parametrize("devices,plan", [
    (1, "airlines"), (4, "airlines"), (4, "wide"), (1, "single"),
    (4, "no-numerics"), (1, "na-codes"), (4, "ragged")],
    indirect=["devices"])
def test_the_kernel_is_the_scan_and_the_float64_gram(devices, plan,
                                                      monkeypatch):
    """Each plan on two grid steps a shard (``PLANS``: rows a shard)."""
    monkeypatch.setattr(gram_mod, "CAT_KERNEL_ROWS", BLOCK)
    levels, nd, na, rows = PLANS[plan]
    n = rows * devices
    assert gramkernel.block_rows(rows, BLOCK) == BLOCK < rows
    X, w, z, Xd = codes_design(levels, n, nd, seed=len(plan), na=na)
    scan, kern = both_grams(X, w, z)
    want = float64_gram(Xd, w, z)
    assert kern[0].shape == (X.p, X.p)
    np.testing.assert_allclose(kern[0], kern[0].T)
    close(kern, scan, 1e-5)
    close(kern, want, 1e-5)
    close(scan, want, 1e-5)


def test_the_compensation_survives_in_the_kernel(monkeypatch):
    """256 blocks of 1,024 rows, a factor whose dropped first level holds
    0.05% of the rows: that level's weight is the sum of all weights less
    its other levels', a difference of totals 2,000 times larger. The
    weights are multiples of 2^-12, so that a block's sums are exact in
    any order and only their addition rounds. The kernel's blocks added
    with compensation hold the level's weight to 2.5e-4 (3.3e-5 by an
    emulation of the kernel's sums); the same blocks' exact sums added in
    plain float32 do not (1.2e-3)."""
    monkeypatch.setattr(gram_mod, "CAT_KERNEL_ROWS", 1024)
    n, blocks = 262_144, 256
    X, _, z, _ = codes_design((4,), n, 1, seed=1, rare=0.0005)
    w = (np.random.default_rng(1).integers(1, 4096, n) / 4096).astype(
        np.float32)
    _, kern = both_grams(X, w, z)
    code = np.asarray(X.codes[0])
    exact = float(np.sum(w.astype(np.float64)[code == 0]))
    got = kern[2] - np.trace(kern[0][:3, :3])
    assert abs(got - exact) / exact < 2.5e-4

    def plain(v):
        """Exact block sums, added one after another in float32."""
        total = np.float32(0.0)
        for s in v.astype(np.float64).reshape(blocks, -1).sum(axis=1):
            total = np.float32(total + np.float32(s))
        return float(total)

    levels = [np.where(code == k, w, 0.0) for k in (1, 2, 3)]
    plain_first = plain(w) - sum(plain(v) for v in levels)
    assert abs(plain_first - exact) / exact > 2.5e-4


# ---------------------------------------------------- fits through the kernel


def last_span(name):
    return [s for s in telemetry.spans_snapshot(last=4096)
            if s["name"] == name][-1]


def launches():
    return telemetry.REGISTRY.value("pallas_kernel_launches_total",
                                    kernel="glm_cat_gram")


def fit(fr, monkeypatch, kernel, **kw):
    """A fit on codes with the factor Gram's kernel (interpret mode) or
    the XLA scan; the span says which ran (a multinomial fit opens no
    ``glm.solve``: the kernel's builds say it)."""
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret" if kernel else "off")
    if kw.get("family") != "multinomial":
        model = GLMEstimator(lambda_=0.0, **kw).train(fr, y="y")
        assert last_span("glm.solve")["meta"]["gram_kernel"] == \
            ("pallas" if kernel else "xla")
        return model
    # builds are counted as the program is traced: trace it anew
    glm_mod._multinomial_irls_solve.clear_cache()
    before = launches()
    model = GLMEstimator(lambda_=0.0, **kw).train(fr, y="y")
    assert (launches() > before) == kernel
    return model


def assert_same_fit(a, b, col):
    np.testing.assert_allclose(np.asarray(a.coef), np.asarray(b.coef),
                               atol=2e-4)
    for k in ("MSE", "logloss") if col == "p1" else ("MSE",):
        assert a.training_metrics[k] == pytest.approx(
            b.training_metrics[k], rel=1e-5)


@pytest.mark.parametrize("devices,family,solver", [
    (1, "binomial", "irlsm"), (4, "binomial", "irlsm"),
    (4, "gaussian", "irlsm"), (1, "binomial", "coordinate_descent")],
    indirect=["devices"])
def test_a_fit_through_the_kernel_is_the_fit_through_the_scan(
        devices, family, solver, monkeypatch):
    cols, doms = mixed_columns(n=4000, family=family)
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    kw = dict(family=family, solver=solver, beta_epsilon=1e-8,
              objective_epsilon=1e-12)
    on_kernel = fit(fr, monkeypatch, True, **kw)
    on_scan = fit(fr, monkeypatch, False, **kw)
    col = "p1" if family == "binomial" else "predict"
    assert_same_fit(on_kernel, on_scan, col)
    np.testing.assert_allclose(on_kernel.predict(fr).to_pandas()[col].values,
                               on_scan.predict(fr).to_pandas()[col].values,
                               atol=2e-4)


def test_multinomial_and_p_values_through_the_kernel(monkeypatch):
    cols, doms = mixed_columns(n=3000, levels={"a": 5, "b": 12, "c": 3})
    cols["y"] = (cols["b"] % 3).astype(np.int32)
    doms["y"] = ["u", "v", "t"]
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    a = fit(fr, monkeypatch, True, family="multinomial", max_iterations=5)
    b = fit(fr, monkeypatch, False, family="multinomial", max_iterations=5)
    np.testing.assert_allclose(np.asarray(a.output["coef_multinomial"]
                                          if "coef_multinomial" in a.output
                                          else a.coef),
                               np.asarray(b.output["coef_multinomial"]
                                          if "coef_multinomial" in b.output
                                          else b.coef), atol=2e-4)
    assert a.training_metrics["logloss"] == pytest.approx(
        b.training_metrics["logloss"], rel=1e-5)

    cols, doms = mixed_columns(n=3000, family="gaussian",
                               levels={"a": 5, "b": 12, "c": 3})
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    kw = dict(family="gaussian", compute_p_values=True)
    pa = fit(fr, monkeypatch, True, **kw).output["coefficients_table"]
    pb = fit(fr, monkeypatch, False, **kw).output["coefficients_table"]
    assert [r["name"] for r in pa] == [r["name"] for r in pb]
    for ra, rb in zip(pa, pb):
        for k in ("coefficients", "std_error", "z_value", "p_value"):
            if k in ra:
                assert ra[k] == pytest.approx(rb[k], rel=1e-4, abs=1e-6), k


def test_the_batched_grid_takes_the_scan(monkeypatch):
    """The (alpha, lambda) grid's ``vmap`` over the solve forms the Gram
    by the XLA scan, whatever mode the fit resolved; its models are the
    sequential fits through the kernel."""
    cols, doms = mixed_columns(n=2000, levels={"a": 5, "b": 12, "c": 3})
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    combos = [dict(family="binomial", alpha=0.0, lambda_=lam)
              for lam in (1e-3, 0.0)]
    batched = glm_mod.fit_glm_batched(GLMEstimator, combos, fr, y="y")
    assert last_span("glm.solve_batched")["meta"]["gram_kernel"] == "xla"
    for model, p in zip(batched, combos):
        seq = GLMEstimator(**p).train(fr, y="y")
        assert last_span("glm.solve")["meta"]["gram_kernel"] == "pallas"
        assert model.training_metrics["logloss"] == pytest.approx(
            seq.training_metrics["logloss"], rel=1e-5)


# ------------------------------------------------------- mode and fallback


def test_a_plan_past_vmem_takes_the_scan_counted(monkeypatch):
    """Two factors of 2,000 levels: their pair's sums do not fit the
    kernel's VMEM, so the fit forms the Gram by the XLA scan and counts
    the fallback once a fit; a plan that fits takes the kernel."""
    monkeypatch.setenv("H2O3TPU_PALLAS", "interpret")
    big, _, _, _ = codes_design((2000, 2000), 1024, 1, seed=2)
    small, _, _, _ = codes_design(AIRLINES, 1024, 3, seed=2)
    assert not gramkernel.fits(gram_mod._kernel_geometry(big),
                               gram_mod.CAT_KERNEL_ROWS)
    assert gramkernel.fits(gram_mod._kernel_geometry(small),
                           gram_mod.CAT_KERNEL_ROWS)
    reg = telemetry.REGISTRY
    fb0 = reg.value("pallas_fallbacks_total", reason="cat_gram_vmem")
    assert gram_mod.with_gram_kernel(big).gram_kernel == "off"
    assert reg.value("pallas_fallbacks_total",
                     reason="cat_gram_vmem") == fb0 + 1
    assert gram_mod.with_gram_kernel(small).gram_kernel == "interpret"
    assert reg.value("pallas_fallbacks_total",
                     reason="cat_gram_vmem") == fb0 + 1
    monkeypatch.setenv("H2O3TPU_PALLAS", "off")
    assert gram_mod.with_gram_kernel(small).gram_kernel == "off"


def test_the_mode_follows_the_policy_and_is_static():
    """``auto`` takes the kernel on a TPU alone; the mode is part of the
    design's tree structure, so a jitted function compiles a program a
    mode."""
    assert plx.decide("auto", "tpu", 1, True)[0] == "native"
    assert plx.decide("auto", "cpu", 1, True)[0] == "off"
    X, _, _, _ = codes_design((5, 7), 256, 1, seed=4)
    k = dataclasses.replace(X, gram_kernel="interpret")
    assert jax.tree_util.tree_structure(X) != \
        jax.tree_util.tree_structure(k)
    assert gram_mod.gram_kernel_name(X) == "xla"
    assert gram_mod.gram_kernel_name(k) == "pallas"
    assert gram_mod.gram_kernel_name(jnp.zeros((4, 2))) == "xla"
