"""Flight recorder, trace export, logging pipeline, RED metrics
(ISSUE 5): per-job telemetry capsules in the DKV, Chrome-trace JSON on
``GET /3/Jobs/{id}/trace`` / ``GET /3/Trace``, the rebuilt utils/log.py
pipeline behind real ``/3/Logs`` handlers, plus the satellite
regressions — the /3/Metrics scrape race, span-relative device peaks,
``get_logger`` hierarchy normalization, and the README metric-name
drift check.
"""

import json
import os
import re
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.core.job import CANCELLED, DONE, Job
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.telemetry import flight_recorder, trace_export
from h2o3_tpu.telemetry.trace_export import COMPILE_TID
from h2o3_tpu.utils import timeline
from h2o3_tpu.utils import log as logmod
from h2o3_tpu.utils.log import get_logger, log_buffer


def _mk_class_frame(n=300, f=3, seed=0, key=None):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(f)}
    cols["y"] = np.array(["n", "p"], object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"], key=key)


# ------------------------------------------------------------- capsules


def test_capsule_captures_spans_events_logs_compiles():
    """A job's capsule holds its span subtree, timeline events, log
    records, compile events, and start/end counter deltas."""
    probe = f"fr-capsule-probe-{os.getpid()}"

    def work(job):
        with telemetry.span("flt.phase", step=1):
            get_logger("flt").info("%s", probe)
            timeline.record("flt", probe)
            # a fresh tiny jit → ≥1 monitored backend compile inside
            # the job, deterministically (small compiles are never
            # persisted to the XLA disk cache)
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,))).block_until_ready()
        return "ok"

    j = Job("flt capsule").start(work)
    assert j.status == DONE
    cap = flight_recorder.get_capsule(j.key)
    assert cap is not None
    d = cap.to_dict()
    assert d["status"] == DONE and d["job_key"] == j.key
    names = {s["name"] for s in d["spans"]}
    assert {"job", "flt.phase"} <= names
    # the work span nests under the job root span
    root = next(s for s in d["spans"] if s["name"] == "job")
    phase = next(s for s in d["spans"] if s["name"] == "flt.phase")
    assert phase["parent_id"] == root["id"]
    assert any(e.get("what") == probe for e in d["events"])
    assert any(probe in l["msg"] for l in d["logs"])
    assert len(d["compiles"]) >= 1
    assert all({"ts_ms", "dur_s"} <= set(c) for c in d["compiles"])
    assert d["metric_deltas"].get("h2o3tpu_span_own_seconds_total", 0) > 0
    assert d["metric_deltas"].get("h2o3tpu_xla_compile_total", 0) >= 1


def test_cancelled_job_capsule_swept_with_scope():
    """Acceptance: a cancelled job's capsule is swept with its Scope —
    no ``<job>_telemetry`` key survives in the DKV."""
    started = threading.Event()

    def work(job):
        started.set()
        while True:
            time.sleep(0.01)
            job.update(0.0)

    j = Job("flt cancel")
    j.start(work, background=True)
    assert started.wait(20)
    # the capsule exists while the job runs
    assert flight_recorder.capsule_key(j.key) in DKV
    j.cancel()
    j.join(30)
    assert j.status == CANCELLED
    assert flight_recorder.get_capsule(j.key) is None
    assert flight_recorder.capsule_key(j.key) not in DKV
    DKV.remove(j.key)


def test_capsule_retention_ring(monkeypatch):
    """Only the newest H2O3TPU_FLIGHT_RECORDER_KEEP completed capsules
    stay in the DKV; older ones are evicted."""
    monkeypatch.setenv("H2O3TPU_FLIGHT_RECORDER_KEEP", "2")
    flight_recorder.clear()
    jobs = [Job(f"flt keep {i}").start(lambda job: "ok") for i in range(4)]
    assert all(j.status == DONE for j in jobs)
    assert flight_recorder.get_capsule(jobs[0].key) is None
    assert flight_recorder.get_capsule(jobs[1].key) is None
    assert flight_recorder.get_capsule(jobs[2].key) is not None
    assert flight_recorder.get_capsule(jobs[3].key) is not None


def test_capsule_bounded(monkeypatch):
    """A span storm truncates the capsule and counts the drops — the
    capsule is a bounded artifact, never an unbounded one."""
    monkeypatch.setattr(flight_recorder, "MAX_SPANS", 16)

    def work(job):
        for i in range(40):
            with telemetry.span("flt.storm"):
                pass
        return "ok"

    j = Job("flt bounded").start(work)
    cap = flight_recorder.get_capsule(j.key)
    assert cap is not None
    d = cap.to_dict()
    assert len(d["spans"]) == 16
    assert d["dropped"]["spans"] >= 24


def test_nested_foreground_job_captured_by_both():
    """A foreground job started inside another job's work (the grid →
    model-build shape) lands in its own capsule AND its parent's."""
    inner_key = {}

    def inner(job):
        with telemetry.span("flt.inner_work"):
            pass
        return "inner"

    def outer(job):
        ij = Job("flt inner").start(inner)
        inner_key["k"] = ij.key
        return "outer"

    oj = Job("flt outer").start(outer)
    outer_cap = flight_recorder.get_capsule(oj.key).to_dict()
    inner_cap = flight_recorder.get_capsule(inner_key["k"]).to_dict()
    assert any(s["name"] == "flt.inner_work" for s in inner_cap["spans"])
    assert any(s["name"] == "flt.inner_work" for s in outer_cap["spans"])


# ------------------------------------------------------- trace export


def test_build_trace_structure():
    spans = [
        {"id": "sp-1", "parent_id": None, "name": "job", "start_ms": 1000,
         "duration_ms": 100.0, "device_peak_bytes": 0,
         "collective_bytes": 0, "meta": {}},
        {"id": "sp-2", "parent_id": "sp-1", "name": "fit",
         "start_ms": 1010, "duration_ms": 50.0, "device_peak_bytes": 7,
         "collective_bytes": 2.0, "meta": {"algo": "gbm"}},
        {"id": "sp-9", "parent_id": None, "name": "other_root",
         "start_ms": 2000, "duration_ms": 5.0, "device_peak_bytes": 0,
         "collective_bytes": 0, "meta": {}},
    ]
    events = [{"seq": 1, "ts_ms": 1020, "kind": "flt", "what": "moment",
               "span_id": "sp-2"},
              {"seq": 2, "ts_ms": 1021, "kind": "flt", "what": "free"}]
    compiles = [{"ts_ms": 1040, "dur_s": 0.02, "event": "xla_compile"}]
    trace = trace_export.build_trace(spans, events, compiles)
    evs = trace["traceEvents"]
    assert all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    xs = {e["name"]: e for e in evs if e["ph"] == "X" and e["cat"] == "span"}
    # one tree → one tid; the second root gets its own track
    assert xs["fit"]["tid"] == xs["job"]["tid"]
    assert xs["other_root"]["tid"] != xs["job"]["tid"]
    # temporal nesting preserved (child contained in parent)
    assert xs["job"]["ts"] <= xs["fit"]["ts"]
    assert xs["fit"]["ts"] + xs["fit"]["dur"] <= \
        xs["job"]["ts"] + xs["job"]["dur"]
    assert xs["fit"]["args"]["parent_id"] == "sp-1"
    # the instant with a span_id rides its span's track; the free one
    # lands on the timeline track
    inst = {e["name"]: e for e in evs if e["ph"] == "i"}
    assert inst["moment"]["tid"] == xs["fit"]["tid"]
    assert inst["free"]["tid"] == trace_export.TIMELINE_TID
    comp = [e for e in evs if e["cat"] == "compile"]
    assert comp and all(e["tid"] == COMPILE_TID for e in comp)
    json.dumps(trace)   # strictly serializable


def test_process_trace_is_valid():
    with telemetry.span("flt.ring_probe"):
        timeline.record("flt", "ring-probe-moment")
    trace = trace_export.process_trace()
    evs = trace["traceEvents"]
    assert all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    assert any(e["name"] == "flt.ring_probe" for e in evs)
    json.dumps(trace)


def test_write_trace_artifact(tmp_path):
    path = str(tmp_path / "sub" / "trace.json")
    trace_export.write_trace(path, trace_export.process_trace())
    with open(path) as f:
        j = json.load(f)
    assert "traceEvents" in j


# ------------------------------------------------- satellite: registry


def test_metrics_scrape_race_stress():
    """Satellite: snapshot()/to_prometheus()/value()/total() racing
    first-touch metric creation on 8 threads must never raise
    (pre-fix: RuntimeError: dictionary changed size during iteration)."""
    stop = threading.Event()
    errors = []

    def creator(i):
        n = 0
        while not stop.is_set():
            telemetry.counter("flt_race_total",
                              tag=f"t{i}_{n % 200}").inc()
            n += 1

    def scraper():
        while not stop.is_set():
            try:
                telemetry.snapshot()
                telemetry.to_prometheus()
                telemetry.REGISTRY.total("flt_race_total")
                telemetry.REGISTRY.value("flt_race_total", tag="t0_0")
            except Exception as e:   # noqa: BLE001 - the assertion
                errors.append(e)
                return

    threads = [threading.Thread(target=creator, args=(i,))
               for i in range(8)]
    threads += [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errors, errors


# --------------------------------------------- satellite: span peaks


def test_span_device_peak_is_span_relative(monkeypatch):
    """Satellite: device_peak_bytes reports the high-water RISE during
    the span, not the process-wide max — a span after the global peak
    reports 0."""
    from h2o3_tpu.telemetry import spans as spans_mod
    seq = iter([100, 100, 100, 250])
    monkeypatch.setattr(spans_mod, "_device_peak", lambda: next(seq))
    with spans_mod.span("flt.peak_outer") as so:
        with spans_mod.span("flt.peak_inner") as si:
            pass
    assert si.device_peak_bytes == 0       # no rise during the inner span
    assert so.device_peak_bytes == 150     # the outer span saw the rise


# ------------------------------------------------ satellite: log names


def test_get_logger_normalizes_into_hierarchy():
    """Satellite: bare names become h2o3_tpu.<name> children so every
    logger reaches the configured sinks."""
    assert get_logger("parser").name == "h2o3_tpu.parser"
    assert get_logger("h2o3_tpu.job").name == "h2o3_tpu.job"
    assert get_logger().name == "h2o3_tpu"
    probe = f"fr-bare-name-probe-{os.getpid()}"
    get_logger("flt_bare").info("%s", probe)
    assert any(probe in ln for ln in log_buffer())


def test_log_pipeline_json_file_and_context(tmp_path):
    """JSON-lines formatter + rotating file sink + span/job context
    stamps; per-level rings select by level."""
    logmod.configure(log_dir=str(tmp_path), json_lines=True)
    try:
        with telemetry.span("flt.logspan") as sp:
            get_logger("flt_file").warning("json-file-probe")
        path = logmod.log_file_path()
        assert path and os.path.exists(path)
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if "json-file-probe" in ln]
        assert lines, "file sink missed the record"
        rec = lines[-1]
        assert rec["level"] == "WARNING"
        assert rec["logger"] == "h2o3_tpu.flt_file"
        assert rec["msg"] == "json-file-probe"
        assert rec["span_id"] == sp.id
        # per-level ring
        assert any("json-file-probe" in ln
                   for ln in log_buffer(level="WARNING"))
        assert logmod.level_counts()["WARNING"] >= 1
    finally:
        logmod.configure()          # restore env defaults


def test_log_records_carry_job_id():
    def work(job):
        get_logger("flt_jobctx").info("job-ctx-probe")
        return "ok"

    j = Job("flt logctx").start(work)
    cap = flight_recorder.get_capsule(j.key).to_dict()
    rec = next(l for l in cap["logs"] if "job-ctx-probe" in l["msg"])
    assert rec["job_id"] == j.key


# ------------------------------------------- satellite: metric names


def test_metric_names_documented_in_readme():
    """Satellite drift check: every counter/gauge/histogram literal in
    h2o3_tpu/ must appear in README §Observability — the README
    promises a stable metric surface; keep it honest."""
    rx = re.compile(r'\b(?:counter|gauge|histogram)\(\s*"([a-z0-9_]+)"')
    root = os.path.join(os.path.dirname(__file__), "..", "h2o3_tpu")
    names = set()
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    names.update(rx.findall(f.read()))
    assert names, "metric literal scan found nothing — regex rot?"
    readme = os.path.join(os.path.dirname(__file__), "..", "README.md")
    with open(readme) as f:
        text = f.read()
    lo = text.index("## Observability")
    hi = text.index("\n## ", lo + 1)
    section = text[lo:hi]
    missing = sorted(n for n in names if n not in section)
    assert not missing, (
        f"metric names not documented in README §Observability: "
        f"{missing}")
    # the ISSUE 8 surface is part of the stable contract: the cluster
    # fan-in + roofline names must stay documented even if a refactor
    # moves their instrumentation call sites out of the literal scan
    for required in ("model_fit_mfu", "model_fit_hbm_util",
                     "roofline_fits_total", "cluster_publish_total",
                     "cluster_publish_bytes", "cluster_stale_nodes",
                     "jobs_inflight"):
        assert required in section, required
    # the ISSUE 9 in-fit checkpointing surface is part of the stable
    # contract too (core/recovery.py FitCheckpointer)
    for required in ("fit_checkpoints_written_total", "fit_resumes_total",
                     "fit_checkpoint_seconds",
                     "snapshot_load_failures_total"):
        assert required in section, required
    # the ISSUE 11 memory-governance surface (core/memgov.py) is part
    # of the stable contract too
    for required in ("hbm_budget_bytes", "hbm_bytes_in_use",
                     "frames_spilled_bytes", "frame_spills_total",
                     "frame_restores_total",
                     "fit_admission_rejections_total",
                     "oom_recoveries_total"):
        assert required in section, required
    # the ISSUE 12 chunk-parallel ingest surface (io/stream.py,
    # io/formats.py, io/parser.py) is part of the stable contract too
    for required in ("ingest_bytes_total", "ingest_rows_total",
                     "parse_chunk_seconds"):
        assert required in section, required
    # the ISSUE 14 low-latency serving surface (serving/engine.py,
    # serving/batcher.py) is part of the stable contract too
    for required in ("predict_requests_total", "predict_batch_width",
                     "predict_seconds", "scorer_cache_hits_total",
                     "scorer_cache_misses_total",
                     "scorer_cache_evictions_total",
                     "scorer_cache_bytes"):
        assert required in section, required
    # the ISSUE 15 cluster work-scheduler surface
    # (parallel/scheduler.py) is part of the stable contract too
    for required in ("sched_runs_total", "sched_items_total",
                     "sched_items_completed_total",
                     "sched_items_reassigned_total",
                     "sched_leases_held", "sched_item_seconds"):
        assert required in section, required
    # the ISSUE 16 tracing + SLO surface (telemetry/trace_context.py,
    # telemetry/slo.py) is part of the stable contract too
    for required in ("slo_burn_rate", "slo_alert_active",
                     "slo_alert_transitions_total",
                     "X-H2O-Trace-Id", "traceparent",
                     "/3/Alerts", "trace_id="):
        assert required in section, required
    # the ISSUE 17 fleet serving-resilience surface (serving/fleet.py)
    # is part of the stable contract too
    for required in ("fleet_replicas_healthy", "predict_routed_total",
                     "predict_failovers_total", "replica_warm_seconds"):
        assert required in section, required
    # the ISSUE 18 durable-data-plane surface (core/durability.py)
    # is part of the stable contract too
    for required in ("frames_mirrored_bytes", "frame_rebuilds_total",
                     "frame_rebuild_seconds", "cloud_restore_seconds",
                     "frames_under_replicated"):
        assert required in section, required
    # the step-profiling surface (telemetry/stepprof.py) is part of the
    # stable contract too
    for required in ("model_fit_phase_seconds", "pod_step_skew_ratio",
                     "pod_straggler_host", "stepprof_fits_total",
                     "H2O3TPU_STEPPROF", "H2O3TPU_STEPPROF_RING",
                     "/profile"):
        assert required in section, required


# ----------------------------------------------------------- REST tier


@pytest.fixture(scope="module")
def port():
    from h2o3_tpu.api.server import start_server, stop_server
    p = start_server(port=0, background=True)
    yield p
    stop_server()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.read()


def _post(port, path, data=b""):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=data, method="POST")
    with urllib.request.urlopen(req) as r:
        return r.status, r.read()


@pytest.mark.allow_key_leak   # REST handler threads create keys
def test_rest_gbm_trace_golden(port):
    """Acceptance: a GBM fit driven through REST yields Chrome-trace
    JSON at GET /3/Jobs/{id}/trace — every event has ph/ts/pid/tid,
    span nesting is preserved, ≥3 distinct phases, ≥1 compile event."""
    # 17 features: a shape no other test in this process uses, so the
    # boost scan compiles fresh INSIDE the traced job
    _mk_class_frame(n=351, f=17, seed=3, key="flt_trace_train")
    st, body = _post(
        port,
        "/3/ModelBuilders/gbm?training_frame=flt_trace_train"
        "&response_column=y&ntrees=4&max_depth=3&seed=5"
        "&model_id=flt_trace_model")
    assert st == 200
    jk = json.loads(body)["job"]["key"]["name"]
    for _ in range(600):
        st, body = _get(port, f"/3/Jobs/{jk}")
        if json.loads(body)["jobs"][0]["status"] not in ("CREATED",
                                                         "RUNNING"):
            break
        time.sleep(0.05)
    assert json.loads(body)["jobs"][0]["status"] == "DONE"

    st, body = _get(port, f"/3/Jobs/{jk}/trace")
    assert st == 200
    trace = json.loads(body)          # must json.loads cleanly
    evs = trace["traceEvents"]
    assert all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    span_evs = [e for e in evs if e["ph"] == "X" and e["cat"] == "span"]
    names = {e["name"] for e in span_evs}
    assert len(names & {"job", "gbm.fit", "gbm.chunk"}) == 3, names
    # nesting: gbm.fit under job, gbm.chunk under gbm.fit (by parent id
    # AND by temporal containment on one track)
    by_sid = {e["args"]["span_id"]: e for e in span_evs}
    job_ev = next(e for e in span_evs if e["name"] == "job")
    fit_ev = next(e for e in span_evs if e["name"] == "gbm.fit")
    chunk_ev = next(e for e in span_evs if e["name"] == "gbm.chunk")
    assert by_sid[fit_ev["args"]["parent_id"]] is job_ev
    assert by_sid[chunk_ev["args"]["parent_id"]] is fit_ev
    assert job_ev["tid"] == fit_ev["tid"] == chunk_ev["tid"]
    assert job_ev["ts"] <= fit_ev["ts"]
    assert fit_ev["ts"] + fit_ev["dur"] <= \
        job_ev["ts"] + job_ev["dur"] + 1000   # ≤1ms rounding slack
    compiles = [e for e in evs if e["cat"] == "compile"]
    assert len(compiles) >= 1
    assert all(e["tid"] == COMPILE_TID for e in compiles)

    # the raw capsule rides the sibling endpoint
    st, body = _get(port, f"/3/Jobs/{jk}/telemetry")
    assert st == 200
    assert json.loads(body)["status"] == "DONE"

    for k in (jk, "flt_trace_model", "flt_trace_train",
              flight_recorder.capsule_key(jk)):
        DKV.remove(k)


@pytest.mark.allow_key_leak
def test_rest_trace_unknown_job_404(port):
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/3/Jobs/job_nope/trace")
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404


@pytest.mark.allow_key_leak
def test_rest_process_trace(port):
    with telemetry.span("flt.rest_ring"):
        pass
    st, body = _get(port, "/3/Trace")
    assert st == 200
    trace = json.loads(body)
    evs = trace["traceEvents"]
    assert all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    assert any(e["name"] == "flt.rest_ring" for e in evs)


@pytest.mark.allow_key_leak
def test_rest_logs_roundtrip(port):
    """Satellite acceptance: a logged line round-trips through
    GET /3/Logs and /3/Logs/download (the pre-fix stub returned
    {"log": ""} unconditionally)."""
    probe = f"fr-logs-roundtrip-{os.getpid()}"
    get_logger("flt_rest").warning("%s", probe)
    st, body = _get(port, "/3/Logs")
    assert st == 200
    j = json.loads(body)
    assert any(probe in ln for ln in j["lines"])
    assert probe in j["log"]
    st, body = _get(port, "/3/Logs?level=WARNING&last=50")
    assert st == 200
    assert any(probe in ln for ln in json.loads(body)["lines"])
    st, body = _get(port, "/3/Logs/download")
    assert st == 200
    assert probe in body.decode()


@pytest.mark.slow
@pytest.mark.allow_key_leak
def test_rest_profiler_capture_real(port):
    """POST /3/Profiler/capture: a real bounded jax.profiler window
    (slow: profiler start/stop alone costs ~10s on this jaxlib —
    tier-1 covers the endpoint via the degrade test below)."""
    st, body = _post(port, "/3/Profiler/capture?duration_ms=60")
    assert st == 200
    j = json.loads(body)
    assert "supported" in j
    if j["supported"]:
        assert j["log_dir"] and os.path.isdir(j["log_dir"])


@pytest.mark.allow_key_leak
def test_rest_profiler_capture_degrades(port, monkeypatch):
    """A backend that can't profile answers supported=false with the
    error string — never a 500 (the graceful-degrade contract)."""
    import jax.profiler as _prof
    monkeypatch.setattr(
        _prof, "start_trace",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("profiling unsupported on this backend")))
    st, body = _post(port, "/3/Profiler/capture?duration_ms=60")
    assert st == 200
    j = json.loads(body)
    assert j["supported"] is False
    assert "unsupported" in j["error"]


@pytest.mark.allow_key_leak
def test_rest_request_seconds_histogram(port):
    before = telemetry.REGISTRY.value("rest_request_seconds",
                                      route="/3/Ping", status="200")
    st, _ = _get(port, "/3/Ping")
    assert st == 200
    after = telemetry.REGISTRY.value("rest_request_seconds",
                                     route="/3/Ping", status="200")
    assert after == before + 1


@pytest.mark.allow_key_leak
def test_rest_metrics_never_500_under_creation_storm(port):
    """Acceptance: GET /3/Metrics under ≥8 threads creating fresh label
    sets never returns 500."""
    stop = threading.Event()

    def creator(i):
        n = 0
        while not stop.is_set():
            telemetry.counter("flt_storm_total",
                              tag=f"s{i}_{n % 200}").inc()
            telemetry.histogram("flt_storm_seconds",
                                tag=f"s{i}_{n % 50}").observe(0.001)
            n += 1
            # fresh label sets keep coming, but yield the GIL so the
            # scrapes stay fast — the race is about creation vs
            # iteration, not about starving the scraper
            time.sleep(0.001)

    threads = [threading.Thread(target=creator, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    try:
        for k in range(12):
            path = "/3/Metrics" if k % 2 == 0 else \
                "/3/Metrics?format=prometheus"
            st, _body = _get(port, path)
            assert st == 200
    finally:
        stop.set()
        for t in threads:
            t.join(10)


def test_queue_wait_histogram_observed():
    from h2o3_tpu.api.server import AdmissionGate
    gate = AdmissionGate(max_inflight=1, queue_depth=4, queue_wait_s=5.0)
    before = telemetry.REGISTRY.value("rest_queue_wait_seconds")
    assert gate.enter()
    got = []

    def waiter():
        got.append(gate.enter())

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    gate.leave()
    t.join(10)
    assert got == [True]
    gate.leave()
    assert telemetry.REGISTRY.value("rest_queue_wait_seconds") == before + 1
