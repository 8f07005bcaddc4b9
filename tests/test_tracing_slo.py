"""End-to-end distributed tracing + SLO burn-rate engine (ISSUE 16,
telemetry/trace_context.py + telemetry/slo.py).

Tiers:

- trace-context units: traceparent parse/format, contextvar install,
  span stamping (root spans parent under the installed context — THE
  cross-process stitch rule), detach, retroactive spans.
- histogram quantiles: bucket interpolation, merged grids, and the
  ``predict_seconds{phase}`` shared-bucket-grid regression.
- SLO engine: the multi-window burn-rate state machine on a private
  registry with a fake clock, plus the gauge-rule and capsule surfaces.
- REST: ``X-H2O-Trace-Id`` echo/generation, ``traceparent`` ingress,
  JobV3 ``trace_id``, single-process ``GET /3/Trace?trace_id=``
  stitching, ``GET /3/Trace`` bit-compat, and ``GET /3/Alerts``.
- ``multiprocess``: a REST-initiated scheduled grid on a REAL
  2-process cloud yields ONE stitched trace with causally-parented
  spans from BOTH hosts under the client's trace id.
"""

import json
import os
import re
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.telemetry import slo, spans, trace_context
from h2o3_tpu.telemetry.registry import (Histogram, MetricsRegistry,
                                         merged_quantile)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "sched_worker.py")
WORKER_TIMEOUT_S = float(os.environ.get("H2O3TPU_MP_TIMEOUT_S", "300"))


# ------------------------------------------------------- trace context


def test_traceparent_parse_roundtrip():
    tc = trace_context.TraceContext("ab" * 16, "sp-00000042",
                                    sampled=True)
    back = trace_context.parse_traceparent(tc.to_traceparent())
    assert back.trace_id == "ab" * 16
    assert back.parent_id == "sp-00000042"
    assert back.sampled


def test_traceparent_accepts_w3c_hex_parent():
    tc = trace_context.parse_traceparent(
        f"00-{'1f' * 16}-{'a' * 16}-00")
    assert tc.trace_id == "1f" * 16
    assert tc.parent_id == "a" * 16
    assert not tc.sampled


def test_traceparent_rejects_malformed():
    for bad in (None, "", "garbage", "00-short-x-01",
                f"00-{'0' * 32}-{'a' * 16}-01",      # all-zero trace id
                f"zz-{'ab' * 16}-{'a' * 16}-01"):
        assert trace_context.parse_traceparent(bad) is None


def test_traceparent_no_parent_placeholder():
    tc = trace_context.new_context()
    assert tc.parent_id is None
    back = trace_context.parse_traceparent(tc.to_traceparent())
    assert back.parent_id is None                    # 0*16 -> None


def test_child_reparents_same_trace():
    tc = trace_context.new_context()
    ch = tc.child("sp-00000007")
    assert ch.trace_id == tc.trace_id
    assert ch.parent_id == "sp-00000007"


def test_format_traceparent_none_without_context():
    assert trace_context.current() is None
    assert trace_context.format_traceparent() is None


def test_trace_scope_installs_and_restores():
    tc = trace_context.new_context()
    with trace_context.trace_scope(tc):
        assert trace_context.current() is tc
        assert trace_context.current_trace_id() == tc.trace_id
        with trace_context.trace_scope(None):         # explicit detach
            assert trace_context.current() is None
        assert trace_context.current() is tc
    assert trace_context.current() is None


# ------------------------------------------------------- span stamping


def test_spans_stamped_with_installed_trace():
    tc = trace_context.TraceContext("cd" * 16, "sp-99999999")
    with trace_context.trace_scope(tc):
        with telemetry.span("tst.root") as root:
            with telemetry.span("tst.child") as child:
                pass
    # root span: no in-process parent -> adopts the context's parent
    # (the cross-process stitch rule); child keeps its LOCAL parent
    assert root.trace_id == "cd" * 16
    assert root.parent_id == "sp-99999999"
    assert child.trace_id == "cd" * 16
    assert child.parent_id == root.id


def test_spans_unstamped_without_trace():
    with telemetry.span("tst.bare") as sp:
        pass
    assert sp.trace_id is None
    assert "trace_id" in sp.to_dict() and sp.to_dict()["trace_id"] is None


def test_detach_makes_next_span_a_root():
    tc = trace_context.TraceContext("ef" * 16, "sp-11111111")
    with telemetry.span("tst.outer") as outer:
        with trace_context.trace_scope(tc), spans.detach():
            with telemetry.span("tst.leased") as leased:
                pass
        with telemetry.span("tst.inner") as inner:
            pass
    # detached: parents under the trace context, not the local outer
    assert leased.parent_id == "sp-11111111"
    assert leased.trace_id == "ef" * 16
    # stack restored after the detach block
    assert inner.parent_id == outer.id


def test_record_finished_retroactive_span():
    t0 = time.time() - 0.5
    sp = spans.record_finished("tst.retro", t0, t0 + 0.25,
                               trace_id="12" * 16,
                               parent_id="sp-00000001", phase="queue")
    assert sp.trace_id == "12" * 16 and sp.parent_id == "sp-00000001"
    assert abs(sp.duration - 0.25) < 1e-6
    tail = telemetry.spans_snapshot(10)
    assert any(s["id"] == sp.id and s["meta"].get("phase") == "queue"
               for s in tail)


def test_job_captures_submitters_trace():
    from h2o3_tpu.core.job import Job
    tc = trace_context.TraceContext("34" * 16, None)
    seen = {}
    with trace_context.trace_scope(tc), telemetry.span("tst.ingress") \
            as ingress:
        job = Job("trace capture probe")

        def work(j):
            cur = trace_context.current()
            seen["trace_id"] = cur.trace_id if cur else None
            seen["parent_id"] = cur.parent_id if cur else None
            return 1

        job.start(work, background=True)
    job.join()
    assert job.status == "DONE"
    # the worker thread ran under the submitter's trace, re-parented
    # beneath the span that was active at Job() creation
    assert seen["trace_id"] == "34" * 16
    assert seen["parent_id"] == ingress.id
    assert job.trace_id == "34" * 16
    assert job.to_dict()["trace_id"] == "34" * 16


# -------------------------------------------------- histogram quantiles


def test_histogram_quantile_interpolates():
    reg = MetricsRegistry()
    h = reg.histogram("tst_q_seconds", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    # rank 2 of 4 falls at the top of the (1,2] bucket
    assert h.quantile(0.5) == pytest.approx(1.5, abs=0.51)
    assert h.quantile(0.0) is not None
    assert h.quantile(1.0) <= 4.0


def test_histogram_quantile_overflow_clamps_to_last_bound():
    reg = MetricsRegistry()
    h = reg.histogram("tst_q2_seconds", buckets=(0.1, 0.5))
    for _ in range(10):
        h.observe(99.0)                  # all in the +Inf overflow
    assert h.quantile(0.99) == 0.5


def test_histogram_quantile_empty_is_none():
    reg = MetricsRegistry()
    h = reg.histogram("tst_q3_seconds", buckets=(0.1, 0.5))
    assert h.quantile(0.5) is None
    assert merged_quantile([], 0.5) is None


def test_merged_quantile_requires_one_bucket_grid():
    reg = MetricsRegistry()
    a = reg.histogram("tst_m_seconds", buckets=(0.1, 0.5), leg="a")
    b = reg.histogram("tst_m_seconds", buckets=(0.1, 0.5, 1.0), leg="b")
    a.observe(0.05)
    b.observe(0.05)
    with pytest.raises(ValueError):
        merged_quantile([a, b], 0.99)
    c = reg.histogram("tst_m_seconds", buckets=(0.1, 0.5), leg="c")
    for _ in range(99):
        c.observe(0.05)
    assert merged_quantile([a, c], 0.5) <= 0.1


def test_predict_seconds_phases_share_one_bucket_grid():
    """Regression (ISSUE 16 satellite): every predict_seconds histogram
    in serving/engine.py must pass buckets=_LATENCY_BUCKETS — a phase
    on a different grid silently breaks the merged p99 the SLO rule
    reports."""
    src = open(os.path.join(
        REPO, "h2o3_tpu", "serving", "engine.py")).read()
    calls = re.findall(
        r'histogram\(\s*"predict_seconds",([^)]*)\)', src)
    assert len(calls) >= 3, "expected queue/device/scatter histograms"
    for args in calls:
        assert "buckets=_LATENCY_BUCKETS" in args.replace(" ", "") \
            .replace("\n", "") or "buckets=_LATENCY_BUCKETS" in args, \
            f"predict_seconds histogram without the shared grid: {args}"


def test_predict_seconds_live_grids_merge():
    """The live registry's predict_seconds histograms (whatever phases
    other tests have populated) must merge without a grid mismatch."""
    hists = [h for h in telemetry.REGISTRY.find("predict_seconds")
             if isinstance(h, Histogram)]
    merged_quantile(hists, 0.99)          # must not raise


# ------------------------------------------------------------ SLO engine


def _latency_engine(clock):
    reg = MetricsRegistry()
    h = reg.histogram("predict_seconds", buckets=(0.1, 0.5, 1.0),
                      phase="device")
    rule = slo.RatioRule("predict_p99_latency", objective=0.99,
                         counts_fn=slo._predict_latency_counts,
                         description="test rule")
    eng = slo.SLOEngine(registry=reg, rules=[rule],
                        now=lambda: clock[0])
    return reg, h, eng


def test_slo_burn_rate_alert_and_recovery():
    clock = [1000.0]
    reg, h, eng = _latency_engine(clock)

    def tick(dt=30.0):
        clock[0] += dt
        return eng.evaluate()

    for _ in range(50):
        h.observe(0.01)
    out = tick()
    assert out["rules"][0]["state"] == "healthy"
    assert out["alerts"] == []
    # fault-injected latency: slow predictions torch both windows
    for _ in range(200):
        h.observe(2.0)
    out = tick()
    assert out["rules"][0]["state"] == "alert"
    assert out["alerts"] and out["alerts"][0]["slo"] == \
        "predict_p99_latency"
    assert out["rules"][0]["burn_5m"] > 1.0
    assert eng.active_alerts()
    # burn-rate gauges published for the scrape
    g5 = reg.gauge("slo_burn_rate", slo="predict_p99_latency",
                   window="5m")
    assert g5.value > 1.0
    assert reg.gauge("slo_alert_active",
                     slo="predict_p99_latency").value == 1.0
    # recovery: healthy traffic displaces the burst beyond both windows
    states = []
    for _ in range(80):
        for _ in range(500):
            h.observe(0.01)
        out = tick(120.0)
        states.append(out["rules"][0]["state"])
        if out["rules"][0]["state"] == "healthy":
            break
    assert "recovery" in states, states   # long window lags the short
    assert states[-1] == "healthy"
    assert out["alerts"] == []
    assert eng.active_alerts() == []
    assert reg.gauge("slo_alert_active",
                     slo="predict_p99_latency").value == 0.0
    trans = sum(int(c.value) for c
                in reg.find("slo_alert_transitions_total"))
    assert trans >= 3                     # alert, recovery, healthy


def test_slo_short_blip_never_alerts():
    """A short burst that torches the 5m window but stays inside the
    1h error budget must visit burning and return to healthy without
    ever alerting — the long window is the confirmation gate."""
    clock = [1000.0]
    reg, h, eng = _latency_engine(clock)
    # an hour of healthy history, sampled every 60s
    for _ in range(60):
        for _ in range(20):
            h.observe(0.01)
        clock[0] += 60
        eng.evaluate()
    # blip: 5 bad — dominates the short window, < 1% of the hour
    for _ in range(5):
        h.observe(2.0)
    clock[0] += 60
    out = eng.evaluate()
    assert out["rules"][0]["state"] == "burning", out["rules"][0]
    assert out["rules"][0]["burn_5m"] > 1.0
    assert out["rules"][0]["burn_1h"] <= 1.0
    # healthy traffic resumes: the short window clears, never alerting
    states = []
    for _ in range(10):
        for _ in range(20):
            h.observe(0.01)
        clock[0] += 60
        states.append(eng.evaluate()["rules"][0]["state"])
    assert "alert" not in states, states
    assert states[-1] == "healthy"


def test_slo_gauge_rule_mfu_floor(monkeypatch):
    reg = MetricsRegistry()
    eng = slo.SLOEngine(
        registry=reg,
        rules=[slo.GaugeRule("fit_mfu_floor", check_fn=slo._mfu_check,
                             description="floor")])
    # floor disabled: vacuously healthy even with a terrible gauge
    monkeypatch.delenv("H2O3TPU_SLO_MFU_FLOOR", raising=False)
    reg.gauge("model_fit_mfu", algo="gbm").set(0.001)
    assert eng.evaluate()["rules"][0]["state"] == "healthy"
    # floor above the gauge: instant alert, instant clear
    monkeypatch.setenv("H2O3TPU_SLO_MFU_FLOOR", "0.5")
    out = eng.evaluate()
    assert out["rules"][0]["state"] == "alert"
    assert out["rules"][0]["worst_algo"] == "gbm"
    reg.gauge("model_fit_mfu", algo="gbm").set(0.9)
    assert eng.evaluate()["rules"][0]["state"] == "healthy"


def test_slo_default_rules_evaluate_on_live_registry():
    """The process-wide engine must evaluate the default rules on
    whatever the live registry holds — never raise, always report."""
    out = slo.evaluate()
    names = {r["slo"] for r in out["rules"]}
    assert names == {"predict_p99_latency", "rest_availability",
                     "heartbeat_health", "fit_mfu_floor",
                     "fleet_routing_availability", "fleet_replica_floor",
                     "data_durability_floor"}
    assert out["windows_s"] == [300.0, 3600.0]
    for r in out["rules"]:
        assert r["state"] in slo.STATES


def test_slo_default_rules_all_healthy_on_a_fresh_registry(monkeypatch):
    """With no fit, request or heartbeat recorded, every default rule
    evaluates ``healthy``: no rule reads an absent series as a breach."""
    monkeypatch.delenv("H2O3TPU_SLO_MFU_FLOOR", raising=False)
    eng = slo.SLOEngine(registry=MetricsRegistry(), now=lambda: 1000.0)
    out = eng.evaluate()
    assert ({r["slo"] for r in out["rules"]}
            == {r.name for r in slo.default_rules()})
    assert {r["slo"]: r["state"] for r in out["rules"]} == {
        r.name: "healthy" for r in slo.default_rules()}
    assert out["alerts"] == []


def test_capsule_stamps_active_slo_alerts(monkeypatch):
    """flight_recorder.finalize() snapshots slo.active_alerts() into
    the capsule (empty when nothing is firing)."""
    from h2o3_tpu.core.job import Job
    clock = [1000.0]
    reg, h, eng = _latency_engine(clock)
    eng.evaluate()                 # baseline sample before the burn
    for _ in range(100):
        h.observe(2.0)
    clock[0] += 30
    eng.evaluate()
    assert eng.active_alerts()
    monkeypatch.setattr(slo, "_ENGINE", eng)
    job = Job("slo capsule probe")
    job.start(lambda j: 1, background=True)
    job.join()
    from h2o3_tpu.telemetry.flight_recorder import get_capsule
    cap = get_capsule(job.key)
    assert cap is not None
    d = cap.to_dict()
    assert d["slo_alerts"] and d["slo_alerts"][0]["slo"] == \
        "predict_p99_latency"


# ------------------------------------------------------------- REST tier


@pytest.fixture(scope="module")
def port():
    from h2o3_tpu.api.server import start_server, stop_server
    p = start_server(port=0, background=True)
    yield p
    stop_server()


def _get(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    with urllib.request.urlopen(req) as r:
        return r.status, r.read(), dict(r.headers)


def _post(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=b"", method="POST",
                                 headers=headers or {})
    with urllib.request.urlopen(req) as r:
        return r.status, r.read(), dict(r.headers)


@pytest.mark.allow_key_leak
def test_rest_generates_and_echoes_trace_id(port):
    st, _, hdrs = _get(port, "/3/About")
    assert st == 200
    tid = hdrs.get("X-H2O-Trace-Id")
    assert tid and re.fullmatch(r"[0-9a-f]{32}", tid)
    # a second request gets a DIFFERENT generated trace
    _, _, hdrs2 = _get(port, "/3/About")
    assert hdrs2.get("X-H2O-Trace-Id") != tid


@pytest.mark.allow_key_leak
def test_rest_accepts_traceparent_header(port):
    tid = "5a" * 16
    st, _, hdrs = _get(port, "/3/About",
                       headers={"traceparent":
                                f"00-{tid}-{'0' * 16}-01"})
    assert st == 200
    assert hdrs.get("X-H2O-Trace-Id") == tid
    # malformed traceparent: never an error, a fresh id is generated
    st, _, hdrs = _get(port, "/3/About",
                       headers={"traceparent": "not-a-traceparent"})
    assert st == 200
    got = hdrs.get("X-H2O-Trace-Id")
    assert got and got != tid


@pytest.mark.allow_key_leak
def test_rest_traced_job_and_stitched_trace(port):
    """A REST model build under a traceparent: JobV3 reports the trace
    id, and GET /3/Trace?trace_id= returns ONE causally-stitched trace
    whose spans all carry that id (single-process leg of the
    cross-host acceptance test)."""
    tid = "7b" * 16
    _mk = np.random.RandomState(0)
    n = 200
    X = _mk.randn(n, 3)
    y = (X[:, 0] > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = np.array(["n", "p"], object)[y]
    h2o3_tpu.Frame.from_numpy(cols, categorical=["y"],
                              key="trc_train")
    st, body, hdrs = _post(
        port,
        "/3/ModelBuilders/gbm?training_frame=trc_train"
        "&response_column=y&ntrees=2&max_depth=2&seed=1"
        "&model_id=trc_model",
        headers={"traceparent": f"00-{tid}-{'0' * 16}-01"})
    assert st == 200
    assert hdrs.get("X-H2O-Trace-Id") == tid
    jk = json.loads(body)["job"]["key"]["name"]
    # every poll opens spans of its own: at 20 a second, a fit that
    # compiles cold (14 s on a loaded host, PR 39's whole run) can turn
    # the 1,024-span ring over and take the build's own ``rest`` span
    # with it — the one time this test failed
    for _ in range(300):
        st, body, _ = _get(port, f"/3/Jobs/{jk}")
        jd = json.loads(body)["jobs"][0]
        if jd["status"] not in ("CREATED", "RUNNING"):
            break
        time.sleep(0.25)
    assert jd["status"] == "DONE"
    # satellite: JobV3 carries the trace id
    assert jd["trace_id"] == tid

    st, body, _ = _get(port, f"/3/Trace?trace_id={tid}")
    assert st == 200
    trace = json.loads(body)
    assert trace["otherData"]["trace_id"] == tid
    assert trace["otherData"]["nodes"] == [0]
    evs = [e for e in trace["traceEvents"]
           if e.get("cat") == "span" and e["ph"] == "X"]
    names = {e["name"] for e in evs}
    # the whole causal chain wears the id: ingress, job, fit
    assert {"rest", "job", "gbm.fit"} <= names, names
    by_id = {e["args"]["span_id"]: e for e in evs}
    # single-process stitching node-qualifies ids and resolves parents
    assert all(e["args"]["span_id"].startswith("n0:") for e in evs)
    job_ev = next(e for e in evs if e["name"] == "job")
    rest_evs = [e for e in evs if e["name"] == "rest"]
    assert job_ev["args"]["parent_id"] in by_id
    assert any(by_id[job_ev["args"]["parent_id"]] is r
               for r in rest_evs)
    # every stitched span carries its node in args
    assert all(e["args"].get("node") == 0 for e in evs)


@pytest.mark.allow_key_leak
def test_rest_trace_without_id_is_bit_compatible(port):
    """GET /3/Trace without trace_id= must be byte-for-byte the
    pre-tracing export: pid-grouped, raw span ids, and NO trace_id key
    in event args."""
    from h2o3_tpu.telemetry import trace_export
    st, body, _ = _get(port, "/3/Trace")
    assert st == 200
    trace = json.loads(body)
    assert "trace_id" not in trace["otherData"]
    for e in trace["traceEvents"]:
        if e.get("cat") == "span":
            assert "trace_id" not in e["args"]
            assert not e["args"]["span_id"].startswith("n")
    # and the route output equals the library export shape
    local = trace_export.process_trace()
    assert set(trace) == set(local)


@pytest.mark.allow_key_leak
def test_rest_alerts_route(port):
    st, body, _ = _get(port, "/3/Alerts")
    assert st == 200
    out = json.loads(body)
    assert {r["slo"] for r in out["rules"]} >= {"predict_p99_latency",
                                               "rest_availability"}
    assert "alerts" in out and "burn_threshold" in out
    # cluster fan-in degrades to the local view on one process (the
    # _cluster_requested contract): same shape, same rule set
    st, body, _ = _get(port, "/3/Alerts?cluster=1")
    assert st == 200
    merged = json.loads(body)
    assert {r["slo"] for r in merged["rules"]} == \
        {r["slo"] for r in out["rules"]}
    assert "alerts" in merged and "burn_threshold" in merged
    # the library-level fan-in (what a multi-host /3/Alerts?cluster=1
    # serves) stamps each rule with its owning node
    from h2o3_tpu.telemetry import cluster
    lib = cluster.merged_alerts()
    assert lib["process_count"] == 1
    assert any(r.get("node") == 0 for r in lib["rules"])
    # the Prometheus scrape exports the slo_* gauges
    st, body, _ = _get(port, "/3/Metrics?format=prometheus")
    assert st == 200
    text = body.decode()
    assert "slo_burn_rate" in text
    assert "slo_alert_active" in text


# ------------------------------------------------- scheduler lease hops


def test_lease_payload_roundtrip_and_back_compat():
    from h2o3_tpu.parallel.scheduler import _lease_payload, _parse_lease
    items = {0: 1, 3: 2}
    tp = f"00-{'ab' * 16}-sp-00000005-01"
    raw = _lease_payload(items, tp)
    got, got_tp = _parse_lease(raw)
    assert got == items and got_tp == tp
    # no traceparent -> the legacy bare dict, parsed back trace-less
    legacy = _lease_payload(items, None)
    assert json.loads(legacy) == {"0": 1, "3": 2}
    got, got_tp = _parse_lease(legacy)
    assert got == items and got_tp is None
    assert _parse_lease(None) == ({}, None)
    assert _parse_lease("") == ({}, None)


def test_serving_members_get_phase_spans_under_own_trace():
    """The micro-batch dispatcher attributes retroactive
    queue/device/scatter spans to each member request's own trace."""
    from h2o3_tpu.models.gbm import GBMEstimator
    r = np.random.RandomState(1)
    n = 120
    X = r.randn(n, 3)
    y = (X[:, 0] > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = np.array(["n", "p"], object)[y]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    model = GBMEstimator(ntrees=2, max_depth=2, seed=1).train(fr, y="y")
    from h2o3_tpu.serving.engine import engine
    tid = "9c" * 16
    tc = trace_context.TraceContext(tid, None)
    rows = [{"x0": 0.5, "x1": -0.2, "x2": 0.1}]
    try:
        with trace_context.trace_scope(tc), \
                telemetry.span("tst.submit") as submit:
            out, domains, meta = engine.score_rows(model, rows)
        assert meta["batch_rows"] >= 1
        mine = [s for s in telemetry.spans_snapshot(2048)
                if s.get("trace_id") == tid]
        phases = {s["name"] for s in mine}
        assert {"predict.queue", "predict.device",
                "predict.scatter"} <= phases, phases
        # each phase span parents under the submitting span
        for s in mine:
            if s["name"].startswith("predict."):
                assert s["parent_id"] == submit.id
                assert s["meta"]["model"] == model.key
        # the coalesced dispatch span links the member's trace
        dsp = [s for s in telemetry.spans_snapshot(2048)
               if s["name"] == "predict.dispatch"
               and tid in (s["meta"].get("member_traces") or [])]
        assert dsp
    finally:
        engine.reset()


# ----------------------------------------------------- multiprocess leg


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
@pytest.mark.multiprocess
@pytest.mark.allow_key_leak
def test_cross_host_stitched_trace(tmp_path):
    """Acceptance (ISSUE 16): a REST request with a traceparent header
    triggering a scheduled 2-process grid produces ONE
    /3/Trace?trace_id= Chrome trace with causally-parented spans from
    BOTH hosts and the echoed X-H2O-Trace-Id."""
    out = str(tmp_path / "trace_out")
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, coord, "2", str(i), out, "trace"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    logs = []
    deadline = time.time() + WORKER_TIMEOUT_S
    for p in procs:
        try:
            stdout, _ = p.communicate(
                timeout=max(deadline - time.time(), 1.0))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            stdout, _ = p.communicate()
            stdout = (stdout or "") + \
                f"\n[TIMEOUT after {WORKER_TIMEOUT_S:.0f}s]"
        logs.append(stdout)
    assert all(rc == 0 for rc in (p.returncode for p in procs)), \
        "\n".join(logs)

    with open(f"{out}.0") as f:
        r0 = json.load(f)
    with open(f"{out}.1") as f:
        r1 = json.load(f)
    tid = "ab" * 16
    assert r0["status"] == "DONE", logs[0]
    assert r0["echoed"] == tid                # X-H2O-Trace-Id echo
    assert r0["job_trace_id"] == tid          # JobV3 satellite
    assert r1["spans_with_trace"] > 0         # lease hop stamped host 1

    trace = r0["trace"]
    assert trace["otherData"]["trace_id"] == tid
    assert sorted(trace["otherData"]["nodes"]) == [0, 1], \
        trace["otherData"]
    evs = [e for e in trace["traceEvents"]
           if e.get("cat") == "span" and e["ph"] == "X"]
    by_id = {e["args"]["span_id"]: e for e in evs}
    items0 = [e for e in evs if e["name"] == "sched.item"
              and e["args"].get("node") == 0]
    items1 = [e for e in evs if e["name"] == "sched.item"
              and e["args"].get("node") == 1]
    assert items0 and items1, {e["name"] for e in evs}
    run0 = [e for e in evs if e["name"] == "sched.run"
            and e["args"].get("node") == 0]
    assert len(run0) == 1
    # THE acceptance bit: a remote host's items parent under the
    # COORDINATOR's sched.run — a cross-process causal link, not a
    # pid-grouped track
    for e in items1:
        assert e["args"]["parent_id"] == run0[0]["args"]["span_id"], \
            (e["args"], run0[0]["args"])
    for e in items0:
        assert e["args"]["parent_id"] == run0[0]["args"]["span_id"]
    # and the whole chain hangs under the client's request: the
    # coordinator's sched.run resolves (transitively) to the rest span
    names = {e["name"] for e in evs}
    assert "rest" in names and "job" in names
    cur = run0[0]
    seen = set()
    while cur["args"]["parent_id"] in by_id and \
            cur["args"]["span_id"] not in seen:
        seen.add(cur["args"]["span_id"])
        cur = by_id[cur["args"]["parent_id"]]
    assert cur["name"] == "rest", cur["name"]
