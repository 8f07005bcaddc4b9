"""The GLM's training response is built on the device
(models/model.py ``response_on_device``): bit-equal to the host
expressions it replaced, with no device→host fetch."""

import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.models.glm import GLMEstimator
from h2o3_tpu.models.model import adapt_domain, response_on_device
from h2o3_tpu.parallel import mesh as mesh_mod

N = 1003          # pads to 1024 on the 8-device test mesh


def _labels(r, levels, n):
    lab = np.array(levels, object)[r.randint(0, len(levels), n)]
    lab[::17] = None
    return lab


def _case(name):
    """(column values, categorical?, y dtype, n)"""
    r = np.random.RandomState(28)
    if name == "binomial_na":
        return _labels(r, ["a", "b"], N), True, "float32", N
    if name == "numeric_nan":
        y = r.randn(N) * 1e3 + 1 / 3       # float64: the cast is the test
        y[::13] = np.nan
        return y, False, "float32", N
    if name == "multinomial_na":
        return _labels(r, ["lo", "mid", "hi"], N), True, "int32", N
    if name == "int32_above_2p24":
        # narrowed to int32 on the device; odd values above 2^24 have no
        # float32, so both paths have to round them the same way
        y = (2 ** 24 + 1 + 2 * r.randint(0, 2 ** 29, N)).astype(np.float64)
        y[::11] = np.nan
        return y, False, "float32", N
    if name == "unaligned_rows":
        n = 4099                           # pads to 4608
        return _labels(r, ["a", "b"], n), True, "float32", n
    raise KeyError(name)


def _host_reference(col, w, npad, categorical, dtype):
    """What models/glm.py computed on the host before (PR 27's _fit)."""
    n = col.nrows
    if categorical and dtype == "int32":       # multinomial / ordinal
        yv = mesh_mod.fetch_replicated(col.data)[:n].astype(np.int32)
        resp_na = mesh_mod.fetch_replicated(col.na_mask)[:n]
        wna = np.pad((~resp_na).astype(np.float32), (0, npad - n))
        return np.pad(yv, (0, npad - n)), w * wna
    if categorical:                            # binomial
        yraw = adapt_domain(col, col.domain)
        yv = np.pad(np.maximum(yraw, 0).astype(np.float32), (0, npad - n))
        wna = np.pad((yraw >= 0).astype(np.float32), (0, npad - n))
        return yv, w * wna
    yn = col.to_numpy()
    wna = np.pad((~np.isnan(yn)).astype(np.float32), (0, npad - n))
    yv = np.pad(np.nan_to_num(yn).astype(np.float32), (0, npad - n))
    return yv, w * wna


@pytest.mark.parametrize("name", ["binomial_na", "numeric_nan",
                                  "multinomial_na", "int32_above_2p24",
                                  "unaligned_rows"])
def test_response_on_device_equals_the_host_path(name):
    values, categorical, dtype, n = _case(name)
    r = np.random.RandomState(5)
    fr = h2o3_tpu.Frame.from_numpy(
        {"y": values, "wt": r.rand(n).astype(np.float32)},
        categorical=["y"] if categorical else [])
    col = fr.col("y")
    npad = fr.nrows_padded
    assert npad > n and col.data.shape == (npad,)
    if name == "int32_above_2p24":
        assert col.data.dtype == np.int32
    # row weights as _fit builds them: the valid mask times a column
    wc = fr.col("wt").numeric_view()
    w = fr.valid_weights() * jnp.where(jnp.isnan(wc), 0.0, wc)
    w_host = np.asarray(w)

    fetches = mesh_mod.FETCH_CALLS
    y_dev, w_dev = response_on_device(col, w, categorical=categorical,
                                      dtype=dtype)
    assert mesh_mod.FETCH_CALLS == fetches
    assert y_dev.sharding.is_equivalent_to(mesh_mod.row_sharding(), 1)
    assert w_dev.sharding.is_equivalent_to(mesh_mod.row_sharding(), 1)

    y_ref, w_ref = _host_reference(col, w_host, npad, categorical, dtype)
    y_got, w_got = np.asarray(y_dev), np.asarray(w_dev)
    assert y_got.dtype == y_ref.dtype == np.dtype(dtype)
    assert w_got.dtype == w_ref.dtype == np.float32
    assert y_got.tobytes() == y_ref.tobytes()
    assert w_got.tobytes() == w_ref.tobytes()
    assert not w_got[n:].any() and not y_got[n:].any()
    assert (w_got[:n] == 0).sum() >= n // 17      # the NAs weigh nothing


def test_string_response_is_refused():
    fr = h2o3_tpu.Frame.from_numpy(
        {"s": np.array(["p", "q", "r", "s"], object),
         "x": np.arange(4.0)}, strings=["s"])
    with pytest.raises(ValueError, match="numeric or categorical"):
        response_on_device(fr.col("s"), fr.valid_weights(),
                           categorical=False)


# coefficients of training on _train_frame, as float32 bits. The response
# built on the host gave these same bits; they were recorded again when the
# Gram became one contraction over the rows, whose float32 summation order
# moved them within the solvers' stopping tolerances
_PARENT_COEF = {
    "binomial": ["0x1.124ca40000000p+0", "-0x1.e422800000000p+0",
                 "0x1.a31d180000000p-2", "0x1.f371320000000p-3"],
    "gaussian": ["0x1.f4d8aa0000000p-1", "-0x1.0179ea0000000p+1",
                 "0x1.022e300000000p-1", "0x1.4573c00000000p-2"],
    "multinomial": ["0x1.a3a69a0000000p+0", "-0x1.67a0480000000p+0",
                    "0x1.a81dd20000000p-5", "-0x1.d3d0040000000p+1",
                    "0x1.96673a0000000p+1", "-0x1.03a9800000000p-3",
                    "0x1.a781dc0000000p-1", "-0x1.9909d60000000p-1",
                    "0x1.b486000000000p-5", "-0x1.333bb00000000p-1",
                    "-0x1.78a0e80000000p+0", "0x1.3996d80000000p-1"],
}


def _train_frame(kind):
    r = np.random.RandomState(28)
    X = r.randn(N, 3).astype(np.float32)
    eta = X @ np.array([1.0, -2.0, 0.5]) + 0.3
    cols = {f"x{i}": X[:, i] for i in range(3)}
    if kind == "binomial":
        lab = np.array(["a", "b"], object)[
            (r.rand(N) < 1 / (1 + np.exp(-eta))).astype(int)]
        lab[::17] = None
        cols["y"] = lab
    elif kind == "gaussian":
        y = (eta + 0.1 * r.randn(N)).astype(np.float64)
        y[::13] = np.nan
        cols["y"] = y
    else:
        lab = np.array(["lo", "mid", "hi"], object)[
            np.digitize(eta + r.randn(N), [-1.0, 1.0])]
        lab[::19] = None
        cols["y"] = lab
    return h2o3_tpu.Frame.from_numpy(
        cols, categorical=[] if kind == "gaussian" else ["y"])


@pytest.mark.parametrize("kind", ["binomial", "gaussian", "multinomial"])
def test_train_builds_the_response_on_device(kind):
    fr = _train_frame(kind)
    fetches = mesh_mod.FETCH_CALLS
    m = GLMEstimator(family=kind, lambda_=0.0).train(fr, y="y")
    sp = [s for s in telemetry.spans_snapshot(200)
          if s["name"] == "glm.response"][-1]
    assert sp["meta"]["on_device"] is True
    assert sp["meta"]["host_bytes"] == 0
    # the design matrix's build sent one host array: three columns'
    # (mean, sigma) as float32
    design = [s for s in telemetry.spans_snapshot(200)
              if s["name"] == "glm.design"][-1]["meta"]
    assert (design["columns"], design["host_arrays"],
            design["host_bytes"]) == (3, 1, 3 * 2 * 4)
    coef = m.coef_multinomial if kind == "multinomial" else m.coef
    got = [float(v).hex() for v in np.asarray(coef).ravel()]
    assert got == _PARENT_COEF[kind]
    # the host path fetched the categorical response's data and mask
    assert mesh_mod.FETCH_CALLS == fetches
