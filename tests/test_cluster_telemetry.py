"""Cluster-scope telemetry plane (ISSUE 8): cross-process metric/trace/
log fan-in over the coordination-service KV store + roofline (MFU/HBM)
accounting.

Tier-1 legs: merge semantics on synthetic peer snapshots (counters
summed, gauges/histograms node-labeled, staleness, Prometheus grammar,
fused traces, ordered logs), the single-process contract (?cluster=1
is exactly the local view), the shutdown KV sweep, node stamping, and
the roofline path — per-fit MFU gauges/capsule annotations plus the
cost_analysis-vs-analytic 2x agreement on loop-free program units.

The ``multiprocess`` leg drives the real thing: a 2-process CPU cloud,
merged scrapes over HTTP, and a SIGKILLed peer degrading to
labeled-stale responses instead of a hang or 500.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.telemetry import cluster, flight_recorder, roofline
from h2o3_tpu.utils import log as logmod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- fake KV peer


class _FakeKV:
    def __init__(self):
        self.store = {}
        self.deleted = []

    def key_value_set(self, k, v, allow_overwrite=True):
        self.store[k] = v

    def key_value_dir_get(self, prefix):
        return [(k, v) for k, v in self.store.items()
                if k.startswith(prefix)]

    def key_value_delete(self, k):
        self.deleted.append(k)
        self.store.pop(k, None)


def _peer_snapshot(node=1, ts=None, probe_name="h2o3tpu_cluz_probe_total",
                   probe_value=200.0):
    return {
        "node": node, "ts": time.time() if ts is None else ts,
        "seq": 1, "host": "peerhost", "pid": 4242,
        "devices": [f"FAKE_CPU_{node}"],
        "metrics": {
            "counters": [{"name": probe_name, "labels": {},
                          "value": probe_value}],
            "gauges": [{"name": "h2o3tpu_cluz_gauge", "labels": {},
                        "value": 7.0}],
            "histograms": [{"name": "h2o3tpu_cluz_seconds", "labels": {},
                            "count": 3, "sum": 0.5,
                            "buckets": [[0.1, 1], [1.0, 3]]}],
        },
        "spans": [{"id": "sp-p1", "parent_id": None, "name": "peer.work",
                   "start_ms": 1000, "duration_ms": 5.0,
                   "device_peak_bytes": 0, "collective_bytes": 0,
                   "meta": {}}],
        "events": [{"seq": 1, "ts_ms": 1001, "kind": "peer",
                    "what": "peer-moment"}],
        "compiles": [{"ts_ms": 1002, "dur_s": 0.01,
                      "event": "xla_compile"}],
        "logs": [{"ts_ms": 1500, "level": "WARNING",
                  "line": "peer-log-line", "node": node}],
        "jobs_inflight": 2,
        "peak_hbm": 12345,
    }


@pytest.fixture()
def two_node(monkeypatch):
    """Pretend this process is node 0 of a 2-process cloud whose peer 1
    publishes over a fake KV client."""
    fake = _FakeKV()
    monkeypatch.setattr(cluster, "_client", lambda: fake)
    monkeypatch.setattr(cluster, "_identity", lambda: (0, 2))
    cluster.reset()
    yield fake
    cluster.reset()


# ---------------------------------------------------- merge semantics


def test_merged_counters_summed_across_nodes(two_node):
    telemetry.counter("cluz_probe_total").inc(100)
    two_node.key_value_set("h2o3tpu/telemetry/1",
                           cluster._encode(_peer_snapshot()))
    col = cluster.collect()
    assert col["stale_nodes"] == []
    m = cluster.merged_metrics(col)
    probe = [c for c in m["counters"]
             if c["name"] == "h2o3tpu_cluz_probe_total"]
    assert len(probe) == 1
    assert probe[0]["value"] == pytest.approx(
        telemetry.REGISTRY.value("cluz_probe_total") + 200.0)


def test_merged_gauges_and_histograms_carry_node_label(two_node):
    telemetry.gauge("cluz_gauge").set(3.0)
    telemetry.histogram("cluz_seconds").observe(0.2)
    two_node.key_value_set("h2o3tpu/telemetry/1",
                           cluster._encode(_peer_snapshot()))
    m = cluster.merged_metrics()
    gz = [g for g in m["gauges"] if g["name"] == "h2o3tpu_cluz_gauge"]
    assert {g["labels"]["node"] for g in gz} == {"0", "1"}
    hs = [h for h in m["histograms"]
          if h["name"] == "h2o3tpu_cluz_seconds"]
    assert {h["labels"]["node"] for h in hs} == {"0", "1"}
    # per-node histograms keep their own bucket vectors
    peer_h = next(h for h in hs if h["labels"]["node"] == "1")
    assert peer_h["count"] == 3 and peer_h["sum"] == 0.5


def test_merged_prometheus_grammar(two_node):
    telemetry.counter("cluz_probe_total").inc(0)
    two_node.key_value_set("h2o3tpu/telemetry/1",
                           cluster._encode(_peer_snapshot()))
    text = cluster.merged_prometheus()
    assert "# TYPE h2o3tpu_cluz_probe_total counter" in text
    assert '# TYPE h2o3tpu_cluz_gauge gauge' in text
    assert 'h2o3tpu_cluz_gauge{node="1"} 7' in text
    assert 'h2o3tpu_cluz_seconds_bucket{node="1",le="+Inf"} 3' in text
    assert 'h2o3tpu_cluz_seconds_count{node="1"} 3' in text


def test_stale_peer_is_labeled_but_still_served(two_node):
    two_node.key_value_set(
        "h2o3tpu/telemetry/1",
        cluster._encode(_peer_snapshot(ts=time.time() - 3600)))
    col = cluster.collect()
    assert col["stale_nodes"] == [1]
    assert 1 in col["nodes"]          # last data serves, labeled stale
    m = cluster.merged_metrics(col)
    assert any(c["name"] == "h2o3tpu_cluz_probe_total"
               for c in m["counters"])


def test_missing_peer_and_kv_failure_never_raise(two_node):
    # peer never published at all
    col = cluster.collect()
    assert col["stale_nodes"] == [1] and 1 not in col["nodes"]

    # the KV read itself blowing up degrades to all-peers-stale
    def _boom(prefix):
        raise RuntimeError("coordination service down")
    two_node.key_value_dir_get = _boom
    col = cluster.collect()
    assert col["stale_nodes"] == [1]


def test_garbled_snapshot_is_a_miss_not_a_crash(two_node):
    two_node.key_value_set("h2o3tpu/telemetry/1", "z:not-base64!!")
    col = cluster.collect()
    assert col["stale_nodes"] == [1]


def test_merged_trace_one_track_group_per_node(two_node):
    snap = _peer_snapshot(ts=time.time() - 3600)     # peer stale
    two_node.key_value_set("h2o3tpu/telemetry/1", cluster._encode(snap))
    with telemetry.span("cluz.local_span"):
        pass
    trace = cluster.merged_trace()
    evs = trace["traceEvents"]
    assert all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    span_evs = [e for e in evs if e.get("cat") == "span"]
    by_name = {e["name"]: e for e in span_evs}
    assert by_name["cluz.local_span"]["pid"] == 0
    assert by_name["peer.work"]["pid"] == 1
    # process_name metadata labels each node's track group; the stale
    # peer says so right in the label
    names = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "node 0" in names[0]
    assert "node 1" in names[1] and "[stale]" in names[1]
    assert trace["otherData"]["stale_nodes"] == [1]
    json.dumps(trace)


def test_merged_logs_timestamp_ordered_with_node_ids(two_node):
    from h2o3_tpu.utils.log import get_logger
    get_logger("cluz").warning("cluz-local-log")
    two_node.key_value_set(
        "h2o3tpu/telemetry/1",
        cluster._encode(_peer_snapshot()))    # peer line ts_ms=1500
    merged = cluster.merged_logs()
    assert any("peer-log-line" in ln for ln in merged["lines"])
    assert any("cluz-local-log" in ln for ln in merged["lines"])
    # the 1970-epoch peer line sorts first; every line carries its node
    assert merged["lines"][0] == "[node 1] peer-log-line"
    ts = [r["ts_ms"] for r in merged["records"]]
    assert ts == sorted(ts)


def test_publish_rate_limit_and_single_process_noop(two_node):
    assert cluster.publish(force=True)
    assert "h2o3tpu/telemetry/0" in two_node.store
    assert cluster._decode(two_node.store["h2o3tpu/telemetry/0"])[
        "node"] == 0
    # inside the interval the piggybacked publish is a no-op
    assert cluster.maybe_publish() is False


def test_publish_is_noop_on_single_process_cloud(monkeypatch):
    monkeypatch.setattr(cluster, "_identity", lambda: (0, 1))
    cluster.reset()
    assert cluster.publish(force=True) is False


# ------------------------------------- single-process contract (REST)


def _assert_handler_identical(fn, params_cluster, params_local):
    # two quick successive direct calls; retry once in case a stray
    # background record lands exactly between the pair
    for _ in range(2):
        a = fn(dict(params_cluster), "")
        b = fn(dict(params_local), "")
        if a == b:
            return
    assert a == b


def test_cluster_views_equal_local_on_single_process():
    """Satellite acceptance: with process_count()==1, ?cluster=1 is
    bit-identical to the local view on all three endpoints."""
    from h2o3_tpu.api.server import _logs, _metrics, _process_trace
    _assert_handler_identical(_metrics, {"cluster": "1"}, {})
    _assert_handler_identical(_process_trace, {"cluster": "1"}, {})
    _assert_handler_identical(_logs, {"cluster": "1"}, {})
    # prometheus leg too
    a = _metrics({"cluster": "1", "format": "prometheus"}, "")
    b = _metrics({"format": "prometheus"}, "")
    assert a["__bytes__"] == b["__bytes__"]


@pytest.fixture()
def declared_peaks(monkeypatch):
    """The CPU test mesh has no published peaks, so utilization is not
    measured there; a test of the MFU plumbing declares peaks through
    the override variables (and drops them from the cache afterwards)."""
    monkeypatch.setenv("H2O3TPU_PEAK_FLOPS", "1e11")
    monkeypatch.setenv("H2O3TPU_PEAK_HBM_GBPS", "20")
    yield roofline.device_peaks(refresh=True)
    monkeypatch.undo()
    roofline.device_peaks(refresh=True)


def test_cloud_nodes_carry_metrics_summary(declared_peaks):
    """Satellite: /3/Cloud per-node blocks gain the fan-in summary and
    the published process identity (no more default-0 guess)."""
    from h2o3_tpu.api.server import _cloud
    out = _cloud({}, "")
    assert out["nodes"], "no nodes in /3/Cloud"
    for nd in out["nodes"]:
        assert "metrics_summary" in nd
        assert nd["process_index"] == 0
        assert nd["gflops"] == declared_peaks["flops"] / 1e9
        ms = nd["metrics_summary"]
        assert {"jobs_inflight", "last_publish_age_s", "peak_hbm",
                "stale"} <= set(ms)
        assert ms["stale"] is False


# --------------------------------------------- shutdown KV sweep


def test_shutdown_sweeps_own_coordination_keys(monkeypatch):
    """Satellite: shutdown() deletes this process's heartbeat, roll-call
    and telemetry KV entries so a reformed cloud reads no ghosts."""
    from jax._src import distributed
    from h2o3_tpu.core import cloud as cloud_mod
    fake = _FakeKV()
    monkeypatch.setattr(distributed.global_state, "client", fake)
    cloud_mod._sweep_coordination_keys()
    # the serving fleet (ISSUE 17) and the durable data plane's frame
    # registry (ISSUE 18) sweep their per-process keys here too
    assert set(fake.deleted) == {"h2o3tpu/hb/0", "h2o3tpu/boot/0",
                                 "h2o3tpu/telemetry/0",
                                 "h2o3tpu/fleet/ep/0",
                                 "h2o3tpu/dur/reg/0/"}


# ------------------------------------------------------ node stamping


def test_log_records_and_capsules_stamped_with_node():
    """Satellite: every JSON log record and flight-recorder capsule
    carries the process's node id once cloud.init stamps it."""
    from h2o3_tpu.core.job import DONE, Job
    from h2o3_tpu.utils.log import get_logger
    logmod.set_node(3)
    try:
        get_logger("cluz_node").warning("cluz-node-stamp-probe")
        rec = next(r for r in reversed(logmod.log_records())
                   if "cluz-node-stamp-probe" in r["line"])
        assert rec["node"] == 3

        j = Job("cluz node capsule").start(lambda job: "ok")
        assert j.status == DONE
        cap = flight_recorder.get_capsule(j.key)
        assert cap.to_dict()["node"] == 3
    finally:
        logmod.set_node(0)


def test_json_formatter_includes_node():
    import logging
    logmod.set_node(5)
    try:
        fmt = logmod.JsonFormatter()
        rec = logging.LogRecord("h2o3_tpu.x", logging.INFO, "f", 1,
                                "msg", (), None)
        logmod.ContextFilter().filter(rec)
        assert json.loads(fmt.format(rec))["node"] == 5
    finally:
        logmod.set_node(0)


# --------------------------------------------------------- roofline


def _mk_class_frame(n, f, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(f)}
    cols["y"] = np.array(["a", "b"], object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


def test_device_peaks_table_and_unknown_device():
    p = roofline.device_peaks(refresh=True)
    assert p["devices"] == 8          # the conftest mesh
    # the CPU publishes no peak: nothing is invented for it
    assert p["source"] == "unknown"
    assert p["flops"] is None and p["hbm_bytes_per_s"] is None
    assert roofline.peaks_for("TPU v5 lite")["flops"] == 197e12
    assert roofline.peaks_for("TPU v5p")["flops"] == 459e12
    # a TPU the table does not list never borrows another chip's number
    unknown = roofline.peaks_for("TPU v9x", "tpu")
    assert unknown["source"] == "unknown" and unknown["flops"] is None
    assert roofline.peaks_for("", "cpu")["source"] == "unknown"
    assert roofline.peaks_for("NVIDIA H100", "gpu")["flops"] is None


def test_analytic_estimators_positive_and_scaling():
    t1 = roofline.analytic_tree_cost(1000, 10, 50, 6, 65)
    t2 = roofline.analytic_tree_cost(2000, 10, 50, 6, 65)
    assert t2["flops"] == pytest.approx(2 * t1["flops"])
    g = roofline.analytic_glm_cost(1000, 9, 8)
    assert g["flops"] == pytest.approx(2 * 9 * 9 * 1000 * 8)
    d = roofline.analytic_dl_cost(100.0, [8, 16, 2])
    assert d["flops"] > 0 and d["bytes"] > 0


def test_fit_on_device_without_peaks_records_work_but_no_mfu():
    """No published peak for the device: the fit's FLOP/byte totals are
    recorded, its utilization is not measured (no mfu anywhere)."""
    assert roofline.device_peaks(refresh=True)["source"] == "unknown"
    fr = _mk_class_frame(300, 4, seed=8)
    from h2o3_tpu.models.gbm import GBMEstimator
    est = GBMEstimator(ntrees=2, max_depth=3, seed=1)
    m = est.train(fr, y="y")
    rec = m.output["roofline"]
    assert rec["flops"] > 0 and rec["bytes"] > 0
    assert rec["mfu"] is None and rec["hbm_util"] is None
    cap = flight_recorder.get_capsule(est._job.key)
    fit = next(s for s in cap.to_dict()["spans"]
               if s["name"] == "gbm.fit")
    assert "mfu" not in fit["meta"]
    assert fit["meta"]["roofline"]["flops"] > 0


def test_gbm_fit_records_nonzero_mfu_in_gauge_and_capsule(declared_peaks):
    """Acceptance: a seeded GBM fit reports nonzero model_fit_mfu in
    the registry AND in its flight-recorder capsule's fit span."""
    fr = _mk_class_frame(600, 5, seed=3)
    from h2o3_tpu.models.gbm import GBMEstimator
    est = GBMEstimator(ntrees=5, max_depth=3, seed=1)
    est.train(fr, y="y")
    assert telemetry.REGISTRY.value("model_fit_mfu", algo="gbm") > 0
    assert telemetry.REGISTRY.value("model_fit_hbm_util",
                                    algo="gbm") > 0
    cap = flight_recorder.get_capsule(est._job.key)
    fit = next(s for s in cap.to_dict()["spans"]
               if s["name"] == "gbm.fit")
    assert fit["meta"]["mfu"] > 0
    assert fit["meta"]["roofline"]["source"] == "analytic"
    assert fit["meta"]["roofline"]["flops"] > 0


def test_dl_fit_records_nonzero_mfu_in_gauge_and_capsule(declared_peaks):
    """Acceptance: a DL fit reports nonzero model_fit_mfu too."""
    fr = _mk_class_frame(512, 8, seed=4)
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    est = DeepLearningEstimator(hidden=[8, 8], epochs=0.5, seed=1)
    est.train(fr, y="y")
    assert telemetry.REGISTRY.value("model_fit_mfu",
                                    algo="deeplearning") > 0
    cap = flight_recorder.get_capsule(est._job.key)
    fit = next(s for s in cap.to_dict()["spans"]
               if s["name"] == "deeplearning.fit")
    assert fit["meta"]["mfu"] > 0


def test_histogram_cost_analysis_agrees_with_analytic_2x():
    """Acceptance: on the GBM histogram program unit — ONE loop-free
    level build — Compiled.cost_analysis() (per-device) agrees with the
    analytic matmul count within 2x on CPU."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.ops import histogram as H
    from h2o3_tpu.parallel.mesh import get_mesh
    n, F, B, L = 2048, 6, 65, 8
    mesh = get_mesh()
    fn = jax.jit(lambda b, nid, w, g, h: H.histogram(
        b, nid, w, g, h, n_nodes=L, n_bins=B, mesh=mesh))
    ab = jax.ShapeDtypeStruct((n, F), jnp.int8)
    ai = jax.ShapeDtypeStruct((n,), jnp.int32)
    af = jax.ShapeDtypeStruct((n,), jnp.float32)
    ca = fn.lower(ab, ai, af, af, af).compile().cost_analysis()
    entries = ca if isinstance(ca, (list, tuple)) else [ca]
    cost = sum(float(e.get("flops", 0) or 0) for e in entries
               if isinstance(e, dict))
    assert cost > 0
    ndev = roofline.device_peaks()["devices"]
    # the product's left operand holds three bfloat16 pieces a statistic
    # (ops/histogram.stat_rows): piece_rows(L) rows, not 3·L
    analytic_per_device = 2.0 * H.piece_rows(L) * n * F * B / ndev
    ratio = analytic_per_device / cost
    assert 0.5 <= ratio <= 2.0, ratio


def test_dl_step_cost_analysis_agrees_with_analytic_2x():
    """Acceptance: on the DL program unit — one fused train step (the
    scan body XLA counts once) — cost_analysis agrees with the analytic
    6·params·batch count within 2x on CPU."""
    fr = _mk_class_frame(512, 9, seed=5)
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    DeepLearningEstimator(hidden=[16, 16], epochs=0.5, seed=1,
                          mini_batch_size=64).train(fr, y="y")
    kc = roofline.kernel_cost("dl.train_chunk", refresh=True)
    assert kc is not None and kc["flops"] > 0
    ndev = roofline.device_peaks()["devices"]
    per_device_batch = 64 / ndev
    est = roofline.analytic_dl_cost(per_device_batch, [9, 16, 16, 2])
    ratio = est["flops"] / kc["flops"]
    assert 0.5 <= ratio <= 2.0, ratio


def test_kernel_cost_unknown_name_is_none():
    assert roofline.kernel_cost("no.such.kernel") is None


def test_roofline_off_mode(monkeypatch):
    monkeypatch.setenv("H2O3TPU_ROOFLINE", "off")
    fr = _mk_class_frame(300, 4, seed=6)
    from h2o3_tpu.models.gbm import GBMEstimator
    est = GBMEstimator(ntrees=2, max_depth=3, seed=1)
    est.train(fr, y="y")
    cap = flight_recorder.get_capsule(est._job.key)
    fit = next(s for s in cap.to_dict()["spans"]
               if s["name"] == "gbm.fit")
    assert "mfu" not in fit["meta"]


# ----------------------------------------- 2-process fan-in (real kv)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http_json(port, path, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _http_text(port, path, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return r.status, r.read().decode()


@pytest.mark.multiprocess
def test_two_process_fanin_merge_and_sigkill_stale(tmp_path):
    """Acceptance: on a 2-process CPU cloud, /3/Metrics?cluster=1 sums
    both peers' local scrapes, /3/Trace?cluster=1 is one Perfetto trace
    with one track group per process, /3/Logs?cluster=1 merges both
    tails — and a SIGKILLed peer degrades every view to labeled-stale
    within the publish window, never a hang or 500.

    Every wait ends on observed state under ONE deadline
    (H2O3TPU_MP_TIMEOUT_S): the suite runs beside five others, and a
    fixed few-second wall measures the host's load, not the cloud."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    worker = os.path.join(REPO, "tests", "cluster_worker.py")
    timeout_s = float(os.environ.get("H2O3TPU_MP_TIMEOUT_S", "300"))
    deadline = time.time() + timeout_s
    procs = []

    def _logs_of():
        out = []
        for i, p in enumerate(procs):
            if p.poll() is None:
                p.kill()
            try:
                o, _ = p.communicate(timeout=10)
            except subprocess.TimeoutExpired:
                o = "<no output>"
            out.append(f"--- worker {i} (rc {p.returncode}) ---\n"
                       f"{(o or '')[-3000:]}")
        return "\n".join(out)

    def _get(fetch, path):
        """One REST call: 200 or the test fails — never a 500, never a
        hang past the deadline."""
        try:
            st, out = fetch(port, path,
                            timeout=max(5.0, deadline - time.time()))
        except Exception as e:   # noqa: BLE001 - any failure is the finding
            raise AssertionError(
                f"GET {path} failed: {type(e).__name__}: {e}\n{_logs_of()}")
        assert st == 200, f"GET {path} -> {st}\n{_logs_of()}"
        return out

    def _until(fetch, path, done, what):
        """Poll ``path`` until ``done(view)``; the last view and the
        workers' logs are in the failure message."""
        while True:
            view = _get(fetch, path)
            if done(view):
                return view
            assert time.time() < deadline, \
                f"{what}; last view of {path}: {str(view)[:2000]}\n{_logs_of()}"
            time.sleep(0.3)

    try:
        # the cloud: both workers' local scrapes + the REST port. A
        # worker that dies before it is ready (the coordinator port is
        # picked free, then bound seconds later by another process:
        # parallel suites can take it in between) gets a fresh port.
        for attempt in range(3):
            workdir = str(tmp_path / f"cloud{attempt}")
            os.makedirs(workdir)
            coord = f"127.0.0.1:{_free_port()}"
            procs[:] = [subprocess.Popen(
                [sys.executable, worker, coord, "2", str(i), workdir],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True) for i in range(2)]
            needed = [os.path.join(workdir, f)
                      for f in ("node0.json", "node1.json", "port.txt")]
            while not all(os.path.exists(p) for p in needed):
                if any(p.poll() is not None for p in procs):
                    break
                assert time.time() < deadline, \
                    f"cloud never formed:\n{_logs_of()}"
                time.sleep(0.1)
            else:
                break
            boot_logs = _logs_of()          # also stops the other worker
            assert attempt < 2, f"worker died during bootstrap:\n{boot_logs}"
        stop = os.path.join(workdir, "stop")
        with open(needed[0]) as f:
            local0 = json.load(f)
        with open(needed[1]) as f:
            local1 = json.load(f)
        with open(needed[2]) as f:
            port = int(f.read().strip())

        # ---- merged metrics == sum of both peers' local scrapes -----
        # poll to a clean steady state first: a transient heartbeat
        # flap during bootstrap may briefly label the peer stale
        out = _until(_http_json, "/3/Metrics?cluster=1",
                     lambda v: v["cluster"]["stale_nodes"] == [],
                     "the peer never read live")
        assert out["cluster"]["process_count"] == 2, _logs_of()
        probe = next(c for c in out["metrics"]["counters"]
                     if c["name"] == "h2o3tpu_cluster_probe_total")
        assert probe["value"] == pytest.approx(
            local0["probe"] + local1["probe"]), _logs_of()   # 100 + 200
        # per-node summaries carry the fan-in identity
        nodes = {n["node"]: n for n in out["cluster"]["nodes"]}
        assert set(nodes) == {0, 1}, _logs_of()

        text = _get(_http_text, "/3/Metrics?cluster=1&format=prometheus")
        assert f"h2o3tpu_cluster_probe_total "\
               f"{int(local0['probe'] + local1['probe'])}" in text, \
            _logs_of()
        assert 'node="1"' in text, _logs_of()

        # ---- one Perfetto trace, one track group per process --------
        def _spans(trace):
            return {e["name"]: e for e in trace["traceEvents"]
                    if e.get("cat") == "span"}
        trace = _until(_http_json, "/3/Trace?cluster=1",
                       lambda t: {"clw.node0", "clw.node1"} <= set(_spans(t)),
                       "the merged trace never held both peers' spans")
        assert _spans(trace)["clw.node0"]["pid"] == 0, _logs_of()
        assert _spans(trace)["clw.node1"]["pid"] == 1, _logs_of()

        # ---- merged logs with node ids ------------------------------
        _until(_http_json, "/3/Logs?cluster=1",
               lambda lg: all(any(f"clw-log-node{i}" in ln
                                  for ln in lg["lines"]) for i in (0, 1)),
               "the merged log never held both peers' lines")

        # ---- SIGKILL the peer: labeled-stale, never a 500 -----------
        procs[1].kill()
        out = _until(_http_json, "/3/Metrics?cluster=1",
                     lambda v: v["cluster"]["stale_nodes"] == [1],
                     "the killed peer never went stale in the metrics view")
        # the dead peer's LAST data still serves in the merged view
        probe = next(c for c in out["metrics"]["counters"]
                     if c["name"] == "h2o3tpu_cluster_probe_total")
        assert probe["value"] >= local1["probe"], _logs_of()
        _until(_http_json, "/3/Trace?cluster=1",
               lambda t: t["otherData"]["stale_nodes"] == [1],
               "the killed peer never went stale in the trace view")
        _until(_http_json, "/3/Logs?cluster=1",
               lambda lg: lg["cluster"]["stale_nodes"] == [1],
               "the killed peer never went stale in the log view")

        # clean stop for the survivor
        with open(stop, "w") as f:
            f.write("stop")
        try:
            rc = procs[0].wait(timeout=max(5.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            raise AssertionError(f"the survivor never exited:\n{_logs_of()}")
        assert rc == 0, _logs_of()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.communicate(timeout=10)
            except Exception:   # noqa: BLE001
                pass