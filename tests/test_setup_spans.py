"""Set-up under the program's own spans and counters (PR 37): what the
compile observer keeps by program and by stage, own time per span name,
the spans opened where set-up's work is done — and nowhere on a warm
job's path."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.telemetry import compile_observer

SETUP_SPANS = {"cloud.init", "cloud.backend", "frame.encode", "frame.put",
               "frame.rollups", "bin.fetch", "bin.edges", "bin.codes"}


def _mark():
    with telemetry.span("t.mark") as sp:
        pass
    return int(sp.id[3:])


def _spans_since(mark, names=None):
    out = [s for s in telemetry.spans_snapshot(10 ** 6)
           if int(s["id"][3:]) > mark
           and (names is None or s["name"] in names)]
    return sorted(out, key=lambda s: int(s["id"][3:]))


def _row(program):
    rows = [r for r in telemetry.programs_snapshot()
            if r["program"] == program]
    return rows[0] if rows else None


def _counter(metric, /, **labels):
    return telemetry.REGISTRY.value(metric, **labels)


def _stage_total():
    return sum(_counter("xla_stage_seconds_total", stage=s)
               for s in ("trace", "lower", "compile", "cache_load"))


def _frame(n=600, seed=0):
    r = np.random.RandomState(seed)
    cols = {f"x{i}": r.randn(n).astype(np.float32) for i in range(3)}
    cols["k"] = r.randint(0, 40, n).astype(np.int32)
    cols["c"] = r.randint(0, 3, n).astype(np.int32)
    cols["y"] = (cols["x0"] + 0.3 * r.randn(n) > 0).astype(np.int32)
    return h2o3_tpu.Frame.from_numpy(
        cols, domains={"c": ["a", "b", "c"], "y": ["n", "p"]})


# ------------------------------------------------ the ledger by program


def test_a_fresh_jit_has_one_row_with_each_stage_once():
    def _ledger_probe_once(x):
        return jax.lax.sin(x) * 2.0

    f = jax.jit(_ledger_probe_once)
    assert _row("jit__ledger_probe_once") is None
    t0 = time.time()
    f(jnp.ones((7,))).block_until_ready()
    row = _row("jit__ledger_probe_once")
    assert (row["traces"], row["lowerings"],
            row["compiles"] + row["cache_loads"]) == (1, 1, 1)
    assert row["trace_s"] > 0 and row["lower_s"] > 0
    assert row["compile_s"] + row["cache_load_s"] > 0
    assert t0 <= row["first_ts"] <= row["last_ts"] <= time.time()
    f(jnp.ones((7,))).block_until_ready()       # the executable is held
    assert _row("jit__ledger_probe_once") == row


def test_a_jit_inside_a_jit_is_traced_once():
    @jax.jit
    def _ledger_probe_inner(x):
        return jnp.where(x > 0, jnp.tanh(x), 0.0).sum()

    def _ledger_probe_outer(x):
        return _ledger_probe_inner(x) + _ledger_probe_inner(2 * x)

    x = jnp.ones((9,))
    x.block_until_ready()
    before, t0 = _stage_total(), time.time()
    jax.jit(_ledger_probe_outer)(x).block_until_ready()
    wall = time.time() - t0
    # the inner traces (and jnp's own jitted helpers under them) are
    # part of the outer trace: no row, no second counted again
    assert _row("jit__ledger_probe_inner") is None
    assert _row("jit__ledger_probe_outer")["traces"] == 1
    assert 0 < _stage_total() - before <= wall


def test_the_ledger_adds_up_to_the_stage_counters():
    jax.jit(lambda x: x * 5 - 1)(jnp.ones((3,))).block_until_ready()
    rows = telemetry.programs_snapshot()
    for stage in ("trace", "lower", "compile", "cache_load"):
        assert sum(r[stage + "_s"] for r in rows) == pytest.approx(
            _counter("xla_stage_seconds_total", stage=stage), abs=1e-6)
    loaded = _counter("xla_programs_total", source="cache")
    built = _counter("xla_programs_total", source="compile")
    assert sum(r["cache_loads"] for r in rows) == loaded
    assert sum(r["compiles"] for r in rows) == built
    # what the old names count: every executable handed over
    assert _counter("xla_compile_total") == loaded + built


def test_a_load_from_the_persistent_cache_is_not_a_compile(tmp_path):
    from jax._src import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}

    def _ledger_probe_cached(x):
        return jnp.cos(x) * 3 + 7

    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        f = jax.jit(_ledger_probe_cached)
        f(jnp.ones((7,))).block_until_ready()
        row = _row("jit__ledger_probe_cached")
        assert (row["compiles"], row["cache_loads"]) == (1, 0)
        jax.clear_caches()
        x = jnp.ones((7,))      # its own programs, before the counts
        built = _counter("xla_programs_total", source="compile")
        loaded = _counter("xla_programs_total", source="cache")
        with telemetry.span("t.cache_load") as sp:
            f(x).block_until_ready()
        row = _row("jit__ledger_probe_cached")
        assert (row["traces"], row["compiles"], row["cache_loads"]) == \
            (2, 1, 1)
        assert row["cache_load_s"] > 0 and row["last_span"] == "t.cache_load"
        assert _counter("xla_programs_total", source="compile") == built
        assert _counter("xla_programs_total", source="cache") == loaded + 1
        assert sp.meta["xla_cache_loads"] == 1 and sp.meta["xla_compiles"] == 1
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_the_ledger_is_bounded(monkeypatch):
    monkeypatch.setattr(compile_observer, "_MAX_PROGRAMS",
                        len(telemetry.programs_snapshot()))
    def _ledger_probe_one_too_many(x):
        return x * 11 + 2

    jax.jit(_ledger_probe_one_too_many)(jnp.ones((3,)))
    assert _row("jit__ledger_probe_one_too_many") is None
    other = _row(compile_observer._OVERFLOW_PROGRAM)
    assert other is not None and other["traces"] >= 1
    assert len(telemetry.programs_snapshot()) <= \
        compile_observer._MAX_PROGRAMS + 1


def test_the_metrics_route_serves_the_ledger():
    from h2o3_tpu.api import server
    jax.jit(lambda x: x - 13)(jnp.ones((3,))).block_until_ready()
    body = server._metrics({}, None)
    assert body["programs"] and body["programs"] == sorted(
        body["programs"], key=lambda r: -(r["trace_s"] + r["lower_s"]
                                          + r["compile_s"]
                                          + r["cache_load_s"]))
    assert {"program", "traces", "trace_s", "lowerings", "lower_s",
            "compiles", "compile_s", "cache_loads", "cache_load_s",
            "first_ts", "last_ts", "last_span"} == set(body["programs"][0])
    text = server._metrics({"format": "prometheus"}, None)["__bytes__"]
    assert b"h2o3tpu_xla_stage_seconds_total{stage=\"trace\"}" in text
    assert b"h2o3tpu_span_own_seconds_total{name=" in text


# ---------------------------------------------------- own time per name


def test_own_seconds_of_nested_spans_add_up_to_the_root():
    names = ("t.own_root", "t.own_child", "t.own_leaf")
    before = {n: _counter("span_own_seconds_total", name=n) for n in names}
    with telemetry.span("t.own_root") as root:
        time.sleep(0.02)
        with telemetry.span("t.own_child") as child:
            time.sleep(0.03)
            with telemetry.span("t.own_leaf") as leaf:
                time.sleep(0.01)
    own = {n: _counter("span_own_seconds_total", name=n) - before[n]
           for n in names}
    assert own["t.own_leaf"] == pytest.approx(leaf.duration)
    assert own["t.own_child"] == pytest.approx(
        child.duration - leaf.duration)
    assert own["t.own_root"] == pytest.approx(
        root.duration - child.duration)
    assert sum(own.values()) == pytest.approx(root.duration)
    by = {s["name"]: s for s in telemetry.spans_snapshot(10)}
    assert by["t.own_root"]["own_ms"] == pytest.approx(
        own["t.own_root"] * 1e3, abs=1e-2)


def test_stage_seconds_under_a_span_are_not_its_own():
    x = jnp.ones((5,))
    x.block_until_ready()
    before = _stage_total()
    own0 = _counter("span_own_seconds_total", name="t.own_compiling")
    with telemetry.span("t.own_compiling") as sp:
        jax.jit(lambda v: jax.lax.exp(v) - 4)(x).block_until_ready()
    staged = _stage_total() - before
    own = _counter("span_own_seconds_total", name="t.own_compiling") - own0
    assert staged > 0 and own + staged == pytest.approx(sp.duration)
    assert sp.meta["xla_trace_s"] >= 0 and sp.meta["xla_lower_s"] >= 0
    assert sp.meta["xla_compiles"] == 1


def test_spans_total_is_gone_and_a_span_is_two_registry_operations():
    with telemetry.span("t.two_ops"):       # the metrics exist now
        pass
    ops = telemetry.REGISTRY.ops()
    with telemetry.span("t.two_ops"):
        pass
    assert telemetry.REGISTRY.ops() - ops == 2
    assert telemetry.REGISTRY.find("spans_total") == []
    assert _counter("span_seconds", name="t.two_ops") == 2      # its count


# ------------------------------------- spans where set-up's work is done


def test_from_numpy_opens_encode_and_put_a_column():
    mark = _mark()
    n = 500
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": np.arange(n, dtype=np.float64),
         "b": np.linspace(0, 1, n).astype(np.float32)})
    got = _spans_since(mark, {"frame.encode", "frame.put"})
    assert [s["name"] for s in got] == ["frame.encode", "frame.put"] * 2
    assert all(s["meta"]["columns"] == 1 for s in got)
    npad = fr.nrows_padded
    # in: the caller's bytes; up: the codec's bytes and the NA mask
    assert [s["meta"]["host_bytes"] for s in got] == \
        [n * 8, npad * 2 + npad, n * 4, npad * 4 + npad]


def test_bin_frame_opens_its_phases_on_a_miss_alone():
    from h2o3_tpu.frame.binning import bin_frame, rebin_for_scoring
    fr = _frame(seed=1)
    names = ("bin.fetch", "bin.edges", "bin.codes")
    mark = _mark()
    bm = bin_frame(fr, ["x0", "x1", "k", "c"], nbins=16)
    got = _spans_since(mark, set(names))
    assert [s["name"] for s in got] == list(names)
    fetch, edges, codes = got
    assert fetch["meta"]["columns"] == edges["meta"]["columns"] == 3
    assert fetch["meta"]["rows"] == codes["meta"]["rows"] == fr.nrows
    assert fetch["meta"]["host_bytes"] == 3 * fr.nrows * 8
    assert codes["meta"]["columns"] == 4
    assert codes["meta"]["nbins_total"] == bm.nbins_total
    mark = _mark()
    assert bin_frame(fr, ["x0", "x1", "k", "c"], nbins=16) is bm
    # a scoring rebin has no slot on the frame: no first binning either
    rebin_for_scoring(bm, _frame(seed=2))
    assert _spans_since(mark, set(names)) == []


def test_a_second_design_build_fetches_no_rollups():
    from h2o3_tpu.frame.datainfo import build_datainfo
    fr = _frame(seed=3)
    feats = ["x0", "x1", "x2", "k"]
    mark = _mark()
    build_datainfo(fr, feats)
    first = _spans_since(mark, {"frame.rollups"})
    assert first and sum(s["meta"]["columns"] for s in first) == len(feats)
    assert sum(s["meta"]["fetches"] for s in first) == len(first)
    mark = _mark()
    build_datainfo(fr, feats)
    assert _spans_since(mark, {"frame.rollups"}) == []


def test_attaching_to_the_cloud_opens_no_span_and_import_is_priced():
    mark = _mark()
    h2o3_tpu.init()                      # formed by the session fixture
    assert _spans_since(mark, {"cloud.init", "cloud.backend"}) == []
    formed = [s for s in telemetry.spans_snapshot(10 ** 6)
              if s["name"] == "cloud.init"]
    if formed:                           # still in the ring
        assert formed[0]["meta"]["platform"] == "cpu"
        assert formed[0]["meta"]["devices"] == 8
    assert _counter("span_own_seconds_total", name="cloud.init") > 0
    assert _counter("span_own_seconds_total", name="cloud.backend") > 0
    assert 0 < _counter("process_import_seconds") < 120


# ------------------------------------------------ a warm job's own spans

WARM = {
    "gbm": ("h2o3_tpu.models.gbm", "GBMEstimator",
            {"ntrees": 2, "max_depth": 3, "seed": 1},
            ["fit.admit", "gbm.bin", "gbm.init", "gbm.chunk", "gbm.rescore",
             "gbm.metrics", "fit.account", "gbm.fit", "job", "job.finish"]),
    "drf": ("h2o3_tpu.models.drf", "DRFEstimator",
            {"ntrees": 2, "max_depth": 4, "seed": 1},
            ["fit.admit", "drf.bin", "drf.init", "drf.chunk", "drf.oob",
             "drf.metrics", "fit.account", "drf.fit", "job", "job.finish"]),
    "glm": ("h2o3_tpu.models.glm", "GLMEstimator",
            {"family": "binomial", "lambda_": 0.0},
            ["fit.admit", "glm.design", "glm.response", "glm.lambda_path",
             "glm.solve", "glm.readback", "glm.metrics", "fit.account",
             "glm.fit", "job", "job.finish"]),
    "deeplearning": ("h2o3_tpu.models.deeplearning", "DeepLearningEstimator",
                     {"hidden": [8, 8], "epochs": 2, "seed": 1},
                     ["fit.admit", "deeplearning.design",
                      "deeplearning.response", "deeplearning.init",
                      "deeplearning.chunk", "deeplearning.score",
                      "deeplearning.metrics", "fit.account",
                      "deeplearning.fit", "job", "job.finish"]),
}


@pytest.mark.parametrize("algo", sorted(WARM))
def test_a_warm_job_opens_exactly_the_spans_it_opened_before(algo):
    """Pinned at the parent of PR 37 (365681f), in the order the spans
    CLOSE: set-up's spans are on the paths that do set-up's work, and a
    second ``train()`` on a frame takes none of them."""
    import importlib
    module, cls, params, pinned = WARM[algo]
    estimator = getattr(importlib.import_module(module), cls)
    fr = _frame(seed=4)
    estimator(**params).train(fr, y="y")
    mark = _mark()
    estimator(**params).train(fr, y="y")
    got = [s["name"] for s in telemetry.spans_snapshot(10 ** 6)
           if int(s["id"][3:]) > mark]
    assert got == pinned
    assert not SETUP_SPANS & set(got)
