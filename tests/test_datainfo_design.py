"""The design matrix's build (frame/datainfo.py) hands ``_design_device``
every column's (mean, sigma) as ONE ``[cols, 2]`` float32 array: the
matrix is bit-equal to the one the program gave when each statistic
reached it as a scalar of its own, and the host pass sends the device
the same number of values however many columns there are."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.frame import datainfo
from h2o3_tpu.frame.datainfo import build_datainfo, stats_of
from h2o3_tpu.frame.rollups import rollups
from h2o3_tpu.models.model import adapt_domain

N = 1003          # pads to 1024 on the 8-device test mesh
FEATURES = ["real", "const", "count", "colour", "size", "tiny"]


@partial(jax.jit, static_argnames=("spec", "standardize"))
def _design_scalars(datas, nas, stats, *, spec, standardize):
    """``_design_device`` as it stood before: ``stats`` a tuple of
    ``(mu, sd)`` scalar pairs, one pair a column."""
    blocks = []
    for i, sp in enumerate(spec):
        na = nas[i]
        if sp[0] == "cat":
            _, first, card = sp
            code = datas[i].astype(jnp.int32)
            levels = jnp.arange(first, card, dtype=jnp.int32)
            oh = (code[:, None] == levels[None, :]).astype(jnp.float32)
            blocks.append(jnp.where(na[:, None], 0.0, oh))
        else:
            mu, sd = stats[i]
            x = datas[i].astype(jnp.float32)
            x = jnp.where(na | jnp.isnan(x), mu, x)
            if standardize:
                x = (x - mu) / sd
            blocks.append(x[:, None])
    return jnp.concatenate(blocks, axis=1)


def _sent(span):
    """What the build wrote on the enclosing span (a span that saw a
    compile carries the compile observer's attributes too)."""
    return {k: span.meta[k] for k in ("columns", "host_arrays",
                                      "host_bytes")}


def _design_numpy(datas, nas, stats, *, spec, standardize):
    """The same expansion in plain numpy float32."""
    blocks = []
    for i, sp in enumerate(spec):
        na = np.asarray(nas[i])
        if sp[0] == "cat":
            _, first, card = sp
            oh = (np.asarray(datas[i]).astype(np.int32)[:, None]
                  == np.arange(first, card, dtype=np.int32)[None, :])
            blocks.append(np.where(na[:, None], np.float32(0),
                                   oh.astype(np.float32)))
        else:
            mu, sd = (np.float32(v) for v in stats[i])
            x = np.asarray(datas[i]).astype(np.float32)
            x = np.where(na | np.isnan(x), mu, x)
            if standardize:
                x = (x - mu) / sd
            blocks.append(x[:, None])
    return np.concatenate(blocks, axis=1)


def _frame(seed, colours, n=N):
    r = np.random.RandomState(seed)
    real = r.randn(n) * 1e3 + 1 / 3            # float64: the cast counts
    real[::13] = np.nan
    colour = np.array(colours, object)[r.randint(0, len(colours), n)]
    colour[::17] = None
    size = np.array(["s", "m", "l", "xl"], object)[r.randint(0, 4, n)]
    return h2o3_tpu.Frame.from_numpy(
        {"real": real,
         "const": np.full(n, 7.25),            # sigma 0: divided by 1
         "count": r.randint(0, 256, n),        # integers, narrowed
         "colour": colour, "size": size,
         "tiny": r.rand(n).astype(np.float32) * 1e-3},
        categorical=["colour", "size"])


def _reference_inputs(frame, use_all, override):
    """The program's inputs as the host pass has to hand them over,
    worked out here from the frame: resident columns (adapted codes on
    the ``stats_override`` path), the statistics as Python floats."""
    datas, nas, stats, spec = [], [], [], []
    ni = 0
    for i, name in enumerate(FEATURES):
        c = frame.col(name)
        if c.is_categorical:
            if override is None:
                dom = c.domain
                datas.append(c.data)
                nas.append(c.na_mask)
            else:
                dom = override["domains"][i]
                codes = np.pad(adapt_domain(c, dom),
                               (0, frame.nrows_padded - frame.nrows),
                               constant_values=-1)
                datas.append(np.maximum(codes, 0).astype(np.int32))
                nas.append(codes < 0)
            spec.append(("cat", 0 if use_all else 1, max(len(dom), 1)))
            stats.append((0.0, 1.0))
        else:
            if override is None:
                roll = rollups(c)
                mu, sd = roll["mean"], roll["sigma"]
            else:
                mu = override["num_means"][ni]
                sd = override["num_sigmas"][ni]
                ni += 1
            spec.append(("num",))
            stats.append((float(mu), float(sd) if sd > 0 else 1.0))
            datas.append(c.data)
            nas.append(c.na_mask)
    return tuple(datas), tuple(nas), stats, tuple(spec)


@pytest.mark.parametrize("path", ["training", "stats_override"])
@pytest.mark.parametrize("use_all", [False, True],
                         ids=["skip_first_level", "all_levels"])
@pytest.mark.parametrize("standardize", [True, False],
                         ids=["standardized", "raw"])
def test_the_matrix_is_bit_equal_to_the_scalar_statistics_build(
        path, use_all, standardize):
    train = _frame(34, ["red", "green", "blue"])
    override = None
    frame = train
    if path == "stats_override":
        override = stats_of(build_datainfo(
            train, FEATURES, standardize=standardize,
            use_all_factor_levels=use_all))
        # a scoring frame: another level order, a level training never saw
        frame = _frame(35, ["violet", "blue", "red", "green"])
    with telemetry.span("test.design") as span:
        di = build_datainfo(frame, FEATURES, standardize=standardize,
                            use_all_factor_levels=use_all,
                            stats_override=override)
    datas, nas, stats, spec = _reference_inputs(frame, use_all, override)
    before = _design_scalars(
        datas, nas, tuple((jnp.float32(m), jnp.float32(s))
                          for m, s in stats),
        spec=spec, standardize=standardize)
    got = np.asarray(di.X)
    assert got.dtype == np.float32
    assert got.shape == (frame.nrows_padded, 4 + 5 + 2 * use_all)
    assert got.tobytes() == np.asarray(before).tobytes()
    assert got.tobytes() == _design_numpy(
        datas, nas, stats, spec=spec, standardize=standardize).tobytes()
    # the cases are in the frame: imputed NAs, the constant column, NA
    # and (when adapting) unseen levels as all-zero indicator rows
    assert not np.isnan(got).any()
    assert (got[:, 1] == (0.0 if standardize else 7.25)).all()
    assert (got[:N, 3:3 + 2 + use_all].sum(axis=1) == 0).sum() >= N // 17
    # what went up: the statistics, and on the override path each
    # categorical column's adapted codes and NA mask
    cats = 2 if override is not None else 0
    assert _sent(span) == {
        "columns": 6, "host_arrays": 1 + 2 * cats,
        "host_bytes": 6 * 2 * 4 + cats * frame.nrows_padded * (4 + 1)}


@pytest.mark.parametrize("columns", [4, 64])
def test_a_training_build_sends_one_host_array_whatever_the_columns(
        columns, monkeypatch):
    r = np.random.RandomState(columns)
    names = [f"x{j}" for j in range(columns)]
    frame = h2o3_tpu.Frame.from_numpy(
        {n: r.randn(N).astype(np.float32) for n in names})
    warm = build_datainfo(frame, names)        # rollups cached, traced

    made = {"float32": 0, "asarray": 0, "device_put": 0}

    def counting(name, real, host_only=False):
        def call(x, *a, **k):
            if not (host_only and isinstance(x, jax.Array)):
                made[name] += 1
            return real(x, *a, **k)
        return call

    # what the host pass could make a device array with, under the names
    # datainfo calls them by (the program is traced already, so nothing
    # else looks these up before ``undo``)
    monkeypatch.setattr(datainfo.jnp, "float32",
                        counting("float32", jnp.float32))
    monkeypatch.setattr(datainfo.jnp, "asarray",
                        counting("asarray", jnp.asarray))
    # placing the finished matrix on the mesh is no host value
    monkeypatch.setattr(datainfo.jax, "device_put",
                        counting("device_put", jax.device_put,
                                 host_only=True))
    with telemetry.span("test.design") as span:
        di = build_datainfo(frame, names)
    monkeypatch.undo()
    assert made == {"float32": 0, "asarray": 0, "device_put": 0}
    assert _sent(span) == {"columns": columns, "host_arrays": 1,
                           "host_bytes": columns * 2 * 4}
    assert np.asarray(di.X).tobytes() == np.asarray(warm.X).tobytes()


def test_a_build_outside_any_span_and_of_no_column():
    frame = _frame(36, ["red", "green"])
    assert telemetry.current_span() is None
    assert build_datainfo(frame, ["real", "colour"]).X.shape == (1024, 2)
    with telemetry.span("test.design") as span:
        di = build_datainfo(frame, [])
    assert di.X.shape == (1024, 0)
    assert _sent(span) == {"columns": 0, "host_arrays": 0, "host_bytes": 0}
