"""Telemetry subsystem: registry, spans, compile observer, /3/Metrics —
plus regression tests for the satellite fixes that rode in with it
(DL minibatch clamp, GBM chunk-invariant PRNG, PCA mojo sigma guard,
rapids all-NA device mean).

The overhead contract (TimeLine's "cheap enough to leave on",
water/TimeLine.java:22) is asserted loosely: registry ops during a real
GBM fit x measured per-op cost must stay under 2% of fit wall time.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.telemetry import registry as reg_mod
from h2o3_tpu.telemetry.compile_observer import observed_jit


def _mk_class_frame(n=300, f=3, seed=0, key=None):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(f)}
    cols["y"] = np.array(["n", "p"], object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"], key=key)


# ------------------------------------------------------------ registry


def test_counter_gauge_histogram_basics():
    c = telemetry.counter("test_basics_total", kind="a")
    v0 = c.value
    c.inc()
    c.inc(2.5)
    assert c.value == v0 + 3.5
    # same (name, labels) -> same instance; different labels -> distinct
    assert telemetry.counter("test_basics_total", kind="a") is c
    assert telemetry.counter("test_basics_total", kind="b") is not c

    g = telemetry.gauge("test_gauge_bytes")
    g.set(10)
    g.set_max(5)
    assert g.value == 10
    g.set_max(20)
    assert g.value == 20

    h = telemetry.histogram("test_hist_seconds")
    h.observe(0.003)
    h.observe(7.0)
    assert h.count == 2
    assert abs(h.sum - 7.003) < 1e-9
    cum = dict(zip(h.bounds, h.cumulative()))
    assert cum[0.005] == 1 and cum[10.0] == 2


def test_registry_prefix_and_value():
    telemetry.counter("test_prefix_total").inc()
    snap = telemetry.snapshot()
    names = {c["name"] for c in snap["counters"]}
    assert "h2o3tpu_test_prefix_total" in names
    assert telemetry.REGISTRY.value("test_prefix_total") >= 1
    assert telemetry.REGISTRY.value("test_never_touched_total") == 0.0


def test_prometheus_exposition_format():
    telemetry.counter("test_prom_total", algo="gbm").inc(3)
    telemetry.histogram("test_prom_seconds").observe(0.2)
    text = telemetry.to_prometheus()
    assert "# TYPE h2o3tpu_test_prom_total counter" in text
    assert 'h2o3tpu_test_prom_total{algo="gbm"} 3' in text
    assert "# TYPE h2o3tpu_test_prom_seconds histogram" in text
    assert 'h2o3tpu_test_prom_seconds_bucket{le="+Inf"} ' in text
    assert "h2o3tpu_test_prom_seconds_count 1" in text


def test_counter_thread_safety():
    c = telemetry.counter("test_threads_total")
    v0 = c.value
    n_threads, per = 8, 5000

    def worker():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == v0 + n_threads * per


# --------------------------------------------------------------- spans


def test_span_nesting_and_ring():
    with telemetry.span("t.outer") as so:
        assert telemetry.current_span_id() == so.id
        with telemetry.span("t.inner", phase=1) as si:
            assert si.parent_id == so.id
        assert telemetry.current_span_id() == so.id
    assert telemetry.current_span_id() is None
    recent = telemetry.spans_snapshot(20)
    by_id = {s["id"]: s for s in recent}
    assert by_id[si.id]["parent_id"] == so.id
    assert by_id[so.id]["parent_id"] is None
    assert by_id[si.id]["meta"].get("phase") == 1
    assert telemetry.REGISTRY.value("span_seconds", name="t.outer") >= 1


def test_span_roots_are_per_thread():
    ids = {}

    def worker(tag):
        with telemetry.span(f"t.root_{tag}") as sp:
            ids[tag] = (sp.id, sp.parent_id)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(parent is None for _, parent in ids.values())


def test_timeline_events_carry_span_id():
    from h2o3_tpu.utils import timeline
    with telemetry.span("t.tl") as sp:
        timeline.record("test", "inside-span")
    evs = [e for e in timeline.snapshot()
           if e.get("what") == "inside-span"]
    assert evs and evs[-1]["span_id"] == sp.id


def test_collective_bytes_charged_to_span():
    mesh = None
    from h2o3_tpu.parallel.map_reduce import frame_reduce
    x = jnp.ones((64,), jnp.float32)
    before = telemetry.REGISTRY.value("frame_reduce_total")
    with telemetry.span("t.mr") as sp:
        out = frame_reduce(lambda a: {"s": jnp.sum(a)}, x, mesh=mesh)
    assert float(out["s"]) == 64.0
    assert telemetry.REGISTRY.value("frame_reduce_total") == before + 1
    # 8-device test mesh -> nonzero psum estimate, charged to the span
    assert sp.collective_bytes > 0
    # scope-labeled accounting (ISSUE 19): one process ⇒ every ring
    # link is intra-host; the pod series exists but stays zero
    assert telemetry.REGISTRY.value("collective_bytes_total",
                                    scope="host") > 0
    assert telemetry.REGISTRY.value("collective_bytes_total",
                                    scope="pod") == 0


# ---------------------------------------------------- compile observer


def test_observed_jit_hit_miss_per_shape_bucket():
    @observed_jit("test.obsfn")
    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.ones((3,)))          # miss (fresh compile)
    f(jnp.ones((3,)))          # hit
    f(jnp.ones((4,)))          # miss (new shape bucket)
    miss3 = telemetry.REGISTRY.value("jit_cache_miss_total",
                                     fn="test.obsfn", shapes="3")
    hit3 = telemetry.REGISTRY.value("jit_cache_hit_total",
                                    fn="test.obsfn", shapes="3")
    miss4 = telemetry.REGISTRY.value("jit_cache_miss_total",
                                     fn="test.obsfn", shapes="4")
    assert (miss3, hit3, miss4) == (1, 1, 1)


def test_global_compile_listener_counts():
    before = telemetry.REGISTRY.value("xla_compile_total")

    @jax.jit
    def g(x):
        return jnp.sin(x) + 3

    g(jnp.ones((5,)))
    assert telemetry.REGISTRY.value("xla_compile_total") > before
    assert telemetry.REGISTRY.value("xla_compile_seconds") > 0  # count


# ------------------------------------------------- end-to-end + REST


@pytest.fixture(scope="module")
def port():
    from h2o3_tpu.api.server import start_server, stop_server
    p = start_server(port=0, background=True)
    yield p
    stop_server()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def test_metrics_endpoint_after_gbm_fit(port):
    from h2o3_tpu.models.gbm import GBMEstimator
    fr = _mk_class_frame(n=300, seed=1)
    ops0 = telemetry.REGISTRY.ops()
    t0 = time.time()
    m = GBMEstimator(ntrees=5, max_depth=3, seed=7).train(fr, y="y")
    fit_wall = time.time() - t0
    ops_fit = telemetry.REGISTRY.ops() - ops0
    assert m.training_metrics["AUC"] > 0.7
    # one MRTask so frame_reduce figures too
    from h2o3_tpu.parallel.map_reduce import frame_reduce
    frame_reduce(lambda a: jnp.sum(a), fr.col("x0").data)

    st, ctype, body = _get(port, "/3/Metrics")
    assert st == 200 and "json" in ctype
    j = json.loads(body)
    counters = {(c["name"], tuple(sorted(c["labels"].items()))): c["value"]
                for c in j["metrics"]["counters"]}
    totals = {}
    for (name, _), v in counters.items():
        totals[name] = totals.get(name, 0) + v
    # the acceptance counters: compiles, MRTask invocations, jobs
    assert totals.get("h2o3tpu_xla_compile_total", 0) > 0
    assert totals.get("h2o3tpu_frame_reduce_total", 0) >= 1
    assert totals.get("h2o3tpu_jobs_completed_total", 0) >= 1
    assert totals.get("h2o3tpu_train_iterations_total", 0) >= 5
    hist_names = {h["name"] for h in j["metrics"]["histograms"]}
    assert "h2o3tpu_job_duration_seconds" in hist_names
    assert "h2o3tpu_model_fit_seconds" in hist_names
    # span tree present with hierarchy
    names = {s["name"] for s in j["spans"]}
    assert "gbm.fit" in names and "job" in names
    fit_span = next(s for s in j["spans"] if s["name"] == "gbm.fit")
    assert fit_span["parent_id"] is not None

    # prometheus exposition of the same registry
    st, ctype, body = _get(port, "/3/Metrics?format=prometheus")
    assert st == 200 and ctype.startswith("text/plain")
    text = body.decode()
    assert "# TYPE h2o3tpu_xla_compile_total counter" in text
    assert "h2o3tpu_job_duration_seconds_bucket" in text

    # loose overhead bound (acceptance: <2% of fit wall time): ops
    # recorded during the fit x measured per-op cost
    c = telemetry.counter("test_overhead_probe_total")
    t0 = time.time()
    for _ in range(20000):
        c.inc()
    per_op = (time.time() - t0) / 20000
    t0 = time.time()
    for _ in range(500):
        with telemetry.span("t.overhead"):
            pass
    per_span = (time.time() - t0) / 500
    n_spans = telemetry.REGISTRY.value("span_seconds", name="gbm.chunk") \
        + telemetry.REGISTRY.value("span_seconds", name="gbm.fit")
    est = ops_fit * per_op + n_spans * per_span
    assert est < 0.02 * fit_wall, (est, fit_wall, ops_fit)


def test_watermeter_and_profiler_report_data(port):
    st, _, body = _get(port, "/3/WaterMeterCpuTicks")
    j = json.loads(body)
    assert st == 200 and j["cpu_ticks"], "must report real tick data"
    assert all(len(row) == 4 for row in j["cpu_ticks"])
    st, _, body = _get(port, "/3/Profiler?depth=2")
    j = json.loads(body)
    assert st == 200 and j["nodes"][0]["entries"]
    # span-level profile rides along with real collected span data
    assert any(a["count"] > 0 for a in j["spans"])


# ------------------------------------------------- satellite regressions


def test_dl_fits_tiny_frame():
    """deeplearning.py minibatch floor: <~224-row frames crashed at
    trace time before the padded-row clamp."""
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    r = np.random.RandomState(11)
    n = 150
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": r.randn(n), "b": r.randn(n),
         "y": np.array(["u", "v"], object)[r.randint(0, 2, n)]},
        categorical=["y"])
    m = DeepLearningEstimator(hidden=[4], epochs=1.0, seed=3).train(
        fr, y="y")
    assert m is not None and m.net


def test_gbm_chunking_invariant_sampling():
    """gbm.py per-tree keys come from the GLOBAL tree index: running the
    boost scan as one 4-tree chunk vs 2+2 chunks (what a max_runtime cap
    does to chunk size) must give identical trees."""
    from h2o3_tpu.frame.binning import bin_frame
    from h2o3_tpu.models.distribution import get_distribution
    from h2o3_tpu.models.gbm import _boost_scan
    from h2o3_tpu.models.tree import TreeParams
    r = np.random.RandomState(5)
    n = 400
    fr = h2o3_tpu.Frame.from_numpy(
        {f"x{i}": r.randn(n) for i in range(4)})
    xcols = [f"x{i}" for i in range(4)]
    bm = bin_frame(fr, xcols, nbins=64, nbins_cats=1024)
    N = bm.bins.shape[0]
    yv = (r.randn(n) > 0).astype(np.float32)
    y = jnp.asarray(np.pad(yv, (0, N - n)))
    w = fr.valid_weights()
    margin = jnp.zeros((N,), jnp.float32)
    tp = TreeParams(max_depth=3, min_rows=5.0, nbins_total=bm.nbins_total,
                    cat_feats=tuple(bool(v) for v in bm.is_cat))
    dist = get_distribution("gaussian")
    key = jax.random.PRNGKey(42)
    kw = dict(tp=tp, dist=dist, sample_rate=0.6)

    tr_full, (m_full, _), _, _ = _boost_scan(
        bm.bins, bm.nbins, y, w, (margin, None), key, ntrees=4, tree0=0, **kw)
    tr_a, carry_a, _, _ = _boost_scan(
        bm.bins, bm.nbins, y, w, (margin, None), key, ntrees=2, tree0=0, **kw)
    tr_b, (m_b, _), _, _ = _boost_scan(
        bm.bins, bm.nbins, y, w, carry_a, key, ntrees=2, tree0=2, **kw)
    for f in tr_full._fields:
        full = np.asarray(getattr(tr_full, f))
        split = np.concatenate([np.asarray(getattr(tr_a, f)),
                                np.asarray(getattr(tr_b, f))])
        assert np.array_equal(full, split), f
    np.testing.assert_allclose(np.asarray(m_full), np.asarray(m_b),
                               rtol=1e-5, atol=1e-5)


def test_gbm_non_binding_cap_same_forest():
    """End-to-end: a non-binding max_runtime_secs must not change the
    seeded forest."""
    from h2o3_tpu.models.gbm import GBMEstimator
    fr = _mk_class_frame(n=300, f=5, seed=9)
    kw = dict(ntrees=4, max_depth=3, seed=123, sample_rate=0.6,
              col_sample_rate_per_tree=0.7)
    a = GBMEstimator(**kw).train(fr, y="y")
    b = GBMEstimator(max_runtime_secs=99999, **kw).train(fr, y="y")
    for f in a.forest._fields:
        assert np.array_equal(np.asarray(getattr(a.forest, f)),
                              np.asarray(getattr(b.forest, f))), f


def test_pca_reference_mojo_constant_column(tmp_path):
    """refmojo.py norm_mul: sigma==0 (constant standardized column) must
    emit 1.0 (DataInfo.java:620), not raise ZeroDivisionError."""
    from h2o3_tpu.genmodel.refmojo import write_reference_pca_mojo
    from h2o3_tpu.models.pca import PCAEstimator
    r = np.random.RandomState(11)
    n = 200
    fr = h2o3_tpu.Frame.from_numpy(
        {"x1": r.randn(n), "c": np.full(n, 3.0), "x2": r.randn(n)})
    m = PCAEstimator(k=2, transform="standardize", seed=3).train(fr)
    p = str(tmp_path / "pca_const.zip")
    m.download_mojo(p, format="reference")
    import zipfile
    with zipfile.ZipFile(p) as z:
        info = z.read("model.ini").decode()
    line = next(l for l in info.splitlines() if l.startswith("normMul"))
    muls = [float(v) for v in
            line.split("=", 1)[1].strip().strip("[]").split(",")]
    assert all(np.isfinite(muls)) and 1.0 in muls


def test_rapids_device_mean_all_na(monkeypatch):
    """rapids _dev_reduce: all-NA column with na.rm returns NaN like the
    host np.nanmean path, not 0.0 from a clamped denominator."""
    import h2o3_tpu.rapids as R
    from h2o3_tpu.rapids import Session, rapids
    sess = Session()
    r = np.random.RandomState(3)
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": np.full(4096, np.nan), "b": r.randn(4096)},
        key="tele_allna")
    sess.assign("tele_allna", fr)
    host = rapids('(mean (cols_py tele_allna ["a"]) 1)', sess)
    monkeypatch.setattr(R, "_DEV_MIN_ROWS", 1)
    dev = rapids('(mean (cols_py tele_allna ["a"]) 1)', sess)
    assert np.isnan(host) and np.isnan(dev)
    # sanity: the valid column still reduces on device
    dv = rapids('(mean (cols_py tele_allna ["b"]) 1)', sess)
    want = float(np.nanmean(np.asarray(fr.col("b").to_numpy())))
    assert abs(dv - want) < 2e-4 * max(1.0, abs(want))


# ------------------------------------- spans on the profiler's clock (PR 27)


def _mark():
    """The id number of a span opened and closed now."""
    with telemetry.span("t.mark") as sp:
        pass
    return int(sp.id[3:])


def _spans_since(mark, names=None):
    """Finished spans opened after ``_mark()`` gave ``mark``, in the
    order they were opened."""
    out = [s for s in telemetry.spans_snapshot(10 ** 6)
           if int(s["id"][3:]) > mark
           and (names is None or s["name"] in names)]
    return sorted(out, key=lambda s: int(s["id"][3:]))


def _host_events(trace_dir, prefix):
    """``(name, start_ns, end_ns)`` of the host-plane events of the one
    trace under ``trace_dir`` whose name starts with ``prefix``."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return sorted((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if not plane.name.startswith("/device:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith(prefix))


def test_span_lands_in_a_profiler_trace_nested_as_opened(tmp_path):
    """Every span is a TraceAnnotation named h2o3.<name> over the
    interval it times: inside a profiler session it is an event of the
    host plane, nested as the spans were."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("t.trace_outer"):
            with telemetry.span("t.trace_inner", k=1):
                jnp.ones((8, 8)).sum().block_until_ready()
            with telemetry.span("t.trace_second"):
                pass
    finally:
        jax.profiler.stop_trace()
    evs = {n: (s, e) for n, s, e in _host_events(tmp_path, "h2o3.t.trace")}
    assert set(evs) == {"h2o3.t.trace_outer", "h2o3.t.trace_inner",
                        "h2o3.t.trace_second"}
    (os_, oe), (is_, ie), (ss, se) = (evs["h2o3.t.trace_outer"],
                                      evs["h2o3.t.trace_inner"],
                                      evs["h2o3.t.trace_second"])
    assert os_ <= is_ <= ie <= ss <= se <= oe
    # outside a session the span is what it was: timed, in the ring
    with telemetry.span("t.trace_off") as sp:
        pass
    assert sp.end >= sp.start and \
        telemetry.spans_snapshot(1)[0]["name"] == "t.trace_off"


def test_the_program_writes_into_a_trace_at_one_place():
    import pathlib
    pkg = pathlib.Path(h2o3_tpu.__file__).parent
    sites = [str(p.relative_to(pkg)) for p in pkg.rglob("*.py")
             if "TraceAnnotation(" in p.read_text()]
    assert sites == ["telemetry/spans.py"]


def _glm_frame(n=4000, f=4, seed=5):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    eta = X @ np.linspace(0.9, -0.6, f)
    y = (r.rand(n) < 1.0 / (1.0 + np.exp(-eta))).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(f)}
    cols["y"] = np.array(["n", "p"], object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


@pytest.mark.parametrize("path, params, span_name", [
    ("single", {"lambda_": 0.0}, "glm.solve"),
    ("fused_path", {"lambda_search": True, "nlambdas": 5, "alpha": 0.0},
     "glm.solve"),
    ("per_lambda", {"lambda_": [0.01, 0.001], "alpha": 0.0,
                    "solver": "coordinate_descent"}, "glm.solve"),
    ("batched", None, "glm.solve_batched"),
])
def test_glm_counts_the_iterations_it_ran(path, params, span_name):
    """train_iterations_total{algo=glm} rises by the IRLS iterations
    run — not by max_iterations — and the solve spans carry them."""
    from h2o3_tpu.models.glm import GLMEstimator, fit_glm_batched
    fr = _glm_frame()
    max_it = 50
    before = telemetry.REGISTRY.value("train_iterations_total", algo="glm")
    t0 = _mark()
    if path == "batched":
        base = {"family": "binomial", "max_iterations": max_it}
        plist = [dict(GLMEstimator(**base, lambda_=lam, alpha=0.0).params)
                 for lam in (0.0, 0.01, 0.1)]
        models = fit_glm_batched(
            GLMEstimator, plist, fr, y="y",
            x=[n for n in fr.names if n != "y"])
        solves = len(models)
    else:
        GLMEstimator(family="binomial", max_iterations=max_it,
                     **params).train(fr, y="y")
        solves = {"single": 1, "fused_path": 5, "per_lambda": 2}[path]
    added = telemetry.REGISTRY.value("train_iterations_total",
                                     algo="glm") - before
    spans = _spans_since(t0, {span_name})
    assert spans and all("iterations" in s["meta"] for s in spans)
    assert added == sum(s["meta"]["iterations"] for s in spans)
    assert solves <= added < solves * max_it


def test_glm_job_opens_each_phase_span_once_in_order():
    from h2o3_tpu.models.glm import GLMEstimator
    fr = _glm_frame(seed=6)
    GLMEstimator(family="binomial", lambda_=0.0).train(fr, y="y")  # warm
    mark, t0 = _mark(), time.time()
    GLMEstimator(family="binomial", lambda_=0.0).train(fr, y="y")
    wall = time.time() - t0
    phases = ["fit.admit", "glm.design", "glm.response", "glm.lambda_path",
              "glm.solve", "glm.readback", "glm.metrics", "fit.account",
              "job.finish"]
    got = _spans_since(mark, set(phases))
    assert [s["name"] for s in got] == phases
    by = {s["name"]: s for s in _spans_since(mark)}
    # leaves of the tree job → glm.fit → phase; admission and the job's
    # tail are roots beside the job
    assert by["glm.fit"]["parent_id"] == by["job"]["id"]
    for n in phases[1:7]:
        assert by[n]["parent_id"] == by["glm.fit"]["id"], n
    assert by["fit.account"]["parent_id"] == by["glm.fit"]["id"]
    assert by["fit.admit"]["parent_id"] is None
    assert by["job.finish"]["parent_id"] is None
    # one after the other (1 ms: the snapshot's clock), inside the job
    for a, b in zip(got, got[1:]):
        assert a["start_ms"] + a["duration_ms"] <= b["start_ms"] + 1.0, \
            (a["name"], b["name"])
    # the existing bound, over every span a job now opens
    t1 = time.time()
    for _ in range(500):
        with telemetry.span("t.overhead_glm"):
            pass
    per_span = (time.time() - t1) / 500
    assert len(by) <= 16
    assert len(by) * per_span < 0.02 * wall, (len(by), per_span, wall)


def test_gbm_job_opens_its_phase_spans():
    from h2o3_tpu.models.gbm import GBMEstimator
    fr = _mk_class_frame(n=400, seed=3)
    mark = _mark()
    GBMEstimator(ntrees=3, max_depth=3, seed=1).train(fr, y="y")
    got = _spans_since(mark, {"gbm.bin", "gbm.init", "gbm.chunk",
                              "gbm.rescore", "gbm.metrics"})
    assert [s["name"] for s in got] == ["gbm.bin", "gbm.init", "gbm.chunk",
                                        "gbm.rescore", "gbm.metrics"]
    assert "train_chunk_seconds" not in telemetry.to_prometheus()


def _lowered_text(what):
    """The lowered program ``what`` of a small fit, with locations."""
    from h2o3_tpu.telemetry import compile_observer
    if what == "irls_solve":
        from h2o3_tpu.models.glm import GLMEstimator
        GLMEstimator(family="binomial", lambda_=0.0).train(
            _glm_frame(n=1000, seed=7), y="y")
        fn, args, kwargs = compile_observer.aot_source("glm.irls_solve")
        return fn.lower(*args, **kwargs).as_text(debug_info=True)
    from h2o3_tpu.models.gbm import GBMEstimator
    from h2o3_tpu.models.tree import predict_forest
    m = GBMEstimator(ntrees=2, max_depth=3, seed=2).train(
        _mk_class_frame(n=500, seed=4), y="y")
    if what == "boost_scan":
        fn, args, kwargs = compile_observer.aot_source("gbm.boost_scan")
        return fn.lower(*args, **kwargs).as_text(debug_info=True)
    return predict_forest.lower(
        m.forest, m.bm.bins, B=m.bm.nbins_total).as_text(debug_info=True)


@pytest.mark.parametrize("what, scopes", [
    ("irls_solve", ("glm.irls_iter", "glm.reweight", "glm.newton_solve",
                    "glm.line_search", "gram.accumulate", "gram.psum")),
    ("boost_scan", ("tree.hist", "tree.split_scan", "tree.partition")),
    ("predict_forest", ("forest.level",)),
])
def test_lowered_programs_name_their_device_work(what, scopes):
    """jax.named_scope names ride the lowered program's locations (the
    HLO op_name metadata a device trace shows); the programs' own names
    stay."""
    text = _lowered_text(what)
    for s in scopes:
        assert f"/{s}/" in text or f'"{s}/' in text, s
    assert {"irls_solve": "jit(_irls_solve)",
            "boost_scan": "jit(_boost_scan_jit)",
            "predict_forest": "jit(predict_forest)"}[what] in text
