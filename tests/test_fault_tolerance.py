"""Fault-tolerance layer tests — watchdog policy/probe, fault
injection, recovery snapshots, bench subprocess isolation, and the
SIGKILL-mid-AutoML resume contract (ISSUE 2; reference
hex/faulttolerance/Recovery.java + water/HeartBeatThread.java roles).

Everything here runs on the CPU cloud via injected faults — a real TPU
crash is never required to exercise the retry/degradation paths. The
subprocess kill/resume test is marked slow; the injection tests stay in
tier-1.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from h2o3_tpu.core import config, watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
FT_WORKER = os.path.join(REPO, "tests", "ft_worker.py")


@pytest.fixture(autouse=True)
def _clean_faults():
    watchdog.clear_faults()
    yield
    watchdog.clear_faults()


# ------------------------------------------------------------ retry policy


def test_backoff_is_exponential_and_bounded():
    p = watchdog.RetryPolicy(max_attempts=10, base_delay_s=1.0,
                             max_delay_s=8.0, jitter=0.0)
    assert [p.delay(k) for k in (1, 2, 3, 4, 5, 6)] == \
        [1.0, 2.0, 4.0, 8.0, 8.0, 8.0]


def test_backoff_jitter_spreads_but_stays_bounded():
    import random
    p = watchdog.RetryPolicy(base_delay_s=1.0, max_delay_s=30.0,
                             jitter=0.25, rng=random.Random(3))
    ds = [p.delay(1) for _ in range(50)]
    assert all(0.75 <= d <= 1.25 for d in ds)
    assert len({round(d, 6) for d in ds}) > 10    # actually jittered


def test_policy_from_config_reads_args(monkeypatch):
    monkeypatch.setattr(config.ARGS, "infra_max_attempts", 5)
    monkeypatch.setattr(config.ARGS, "infra_backoff_base_s", 0.125)
    p = watchdog.policy_from_config()
    assert p.max_attempts == 5
    assert p.base_delay_s == 0.125


def test_policy_env_overrides_win(monkeypatch):
    monkeypatch.setenv("H2O3TPU_INFRA_MAX_ATTEMPTS", "7")
    assert watchdog.policy_from_config().max_attempts == 7


def test_retry_call_recovers_from_infra_blip():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: worker restarting")
        return "ok"

    p = watchdog.RetryPolicy(max_attempts=3, base_delay_s=1.0,
                             jitter=0.0, sleep=slept.append)
    assert watchdog.retry_call(flaky, policy=p) == "ok"
    assert calls["n"] == 3
    assert slept == [1.0, 2.0]


def test_retry_call_gives_up_after_max_attempts():
    calls = {"n": 0}

    def dead():
        calls["n"] += 1
        raise RuntimeError("INTERNAL: Failed to execute XLA computation")

    p = watchdog.RetryPolicy(max_attempts=3, base_delay_s=0.0,
                             jitter=0.0, sleep=lambda s: None)
    with pytest.raises(RuntimeError):
        watchdog.retry_call(dead, policy=p)
    assert calls["n"] == 3


def test_retry_call_user_error_fails_fast():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("bad params")

    with pytest.raises(ValueError):
        watchdog.retry_call(bad, policy=watchdog.RetryPolicy(
            max_attempts=5, sleep=lambda s: None))
    assert calls["n"] == 1


# ---------------------------------------------------------------- probe


def test_probe_backend_alive():
    rt = watchdog.probe_backend(timeout_s=30.0)
    assert rt < 30.0


def test_probe_failure_injected_and_counted():
    from h2o3_tpu import telemetry
    fails0 = telemetry.REGISTRY.value("backend_probe_failures_total")
    watchdog.inject_fault("probe", times=1)
    with pytest.raises(watchdog.InjectedFault):
        watchdog.probe_backend()
    assert telemetry.REGISTRY.value(
        "backend_probe_failures_total") - fails0 == 1
    # fault consumed: the next probe finds the backend alive again
    assert watchdog.probe_backend(timeout_s=30.0) >= 0.0


def test_probe_with_retry_survives_transient_failure():
    watchdog.inject_fault("probe", times=2)
    p = watchdog.RetryPolicy(max_attempts=3, base_delay_s=0.0,
                             jitter=0.0, sleep=lambda s: None)
    assert watchdog.probe_with_retry(policy=p) >= 0.0
    assert watchdog.fired("probe") == 2


# ------------------------------------------------------- fault injection


def test_env_fault_spec_parsed(monkeypatch):
    monkeypatch.setenv("H2O3TPU_FAULTS",
                       "frame_map:2:INTERNAL:, probe:1")
    monkeypatch.setattr(watchdog, "_env_parsed", False)
    watchdog.clear_faults()
    with pytest.raises(watchdog.InjectedFault, match="INTERNAL"):
        watchdog.maybe_fail("frame_map")
    with pytest.raises(watchdog.InjectedFault):
        watchdog.maybe_fail("frame_map")
    watchdog.maybe_fail("frame_map")           # budget spent: no-op
    with pytest.raises(watchdog.InjectedFault, match="UNAVAILABLE"):
        watchdog.maybe_fail("probe")


def test_injected_fault_classifies_as_infra():
    watchdog.inject_fault("job", times=1)
    with pytest.raises(watchdog.InjectedFault) as ei:
        watchdog.maybe_fail("job")
    assert watchdog.is_infra_error(ei.value)


def test_frame_reduce_fault_retried_by_job(monkeypatch):
    """End-to-end degradation path: a psum dispatch dies with a
    classified infra error mid-job; the job-level watchdog retry reruns
    the work and succeeds — no real TPU crash required."""
    from h2o3_tpu.core.job import DONE, Job
    from h2o3_tpu.parallel.map_reduce import frame_reduce
    monkeypatch.setattr(config.ARGS, "infra_backoff_base_s", 0.001)
    watchdog.inject_fault("frame_reduce", times=1)
    x = np.arange(64.0)

    def work(job):
        return float(frame_reduce(lambda a: a.sum(), x))

    j = Job("fault-injected reduce").start(work)
    assert j.status == DONE
    assert j.result == pytest.approx(float(x.sum()))
    assert watchdog.fired("frame_reduce") == 1


# ------------------------------------------------------------- recovery


def test_recovery_state_atomic_roundtrip(tmp_path):
    from h2o3_tpu.core.recovery import Recovery
    rec = Recovery(str(tmp_path / "r"), state_name="automl_state")
    assert rec.read_state() is None
    rec.write_state({"done_steps": ["GBM_1"], "models": {}})
    assert rec.read_state()["done_steps"] == ["GBM_1"]
    # atomic: no tmp debris next to the state file
    assert os.listdir(rec.dir) == ["automl_state.json"]


def test_recovery_skips_torn_model_snapshot(tmp_path):
    from h2o3_tpu.core.recovery import Recovery
    rec = Recovery(str(tmp_path / "r"))
    with open(os.path.join(rec.dir, "model_torn.bin"), "wb") as f:
        f.write(b"\x80\x04 not a pickle")
    assert rec.load_models(["model_torn.bin"]) == []


def test_recovery_rejects_unserializable_params():
    from h2o3_tpu.core.recovery import ensure_json_safe
    with pytest.raises(ValueError, match="ndarray"):
        ensure_json_safe({"w": np.zeros(3)}, "recovery_dir fixed")


@pytest.mark.allow_key_leak      # train_capped puts keys from job threads
def test_automl_recovery_snapshot_and_resume(tmp_path, classif_frame):
    """Fast resume path (no kill): a finished single-step run leaves a
    complete state; resume restores the model instead of retraining."""
    from h2o3_tpu.automl import H2OAutoML, resume_automl
    d = str(tmp_path / "rec")
    aml = H2OAutoML(max_models=1, seed=4, nfolds=0,
                    include_algos=["glm"], max_runtime_secs=120,
                    recovery_dir=d)
    aml.train(y="y", training_frame=classif_frame)
    assert len(aml.leaderboard.models) == 1
    trained_key = aml.leaderboard.models[0].key
    state = json.load(open(os.path.join(d, "automl_state.json")))
    assert state["done_steps"] == ["GLM_1"]

    res = resume_automl(d, classif_frame)
    assert [m.key for m in res.leaderboard.models] == [trained_key]
    # nothing retrained: the restored model IS the leaderboard
    post = [e for e in res.event_log
            if e["stage"] == "model"]
    assert post == []


# --------------------------------------------- bench subprocess isolation


def _run_bench(tmp_path, extra_env, timeout=300):
    env = dict(os.environ)
    env.update({"H2O3TPU_BENCH_STUB": "1",
                "JAX_PLATFORMS": "cpu",
                "H2O3TPU_INFRA_BACKOFF_BASE_S": "0.05",
                "H2O3TPU_INFRA_BACKOFF_MAX_S": "0.1",
                "H2O3TPU_FAULT_STATE": str(tmp_path / "faultstate")})
    env.update(extra_env)
    p = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, text=True, timeout=timeout)
    # parse only up to the tail-proof summary (which re-prints every
    # line and would double-count)
    stdout = p.stdout.split("# ---- summary")[0]
    lines = [json.loads(ln) for ln in stdout.splitlines()
             if ln.strip().startswith("{")]
    return p, lines


@pytest.mark.allow_key_leak
def test_bench_wedged_config_costs_one_line(tmp_path):
    """Acceptance: an injected wedged backend (a child that never
    finishes) costs exactly one config line — the others still emit —
    and the recorded budget never goes below 0."""
    p, lines = _run_bench(tmp_path, {
        "H2O3TPU_BENCH_BUDGET_S": "240",
        # cap >> any healthy stub config (2.5-3 s alone, several times
        # that beside six compiling xdist workers) but small: the wedged
        # child burns the full cap before the kill, straight wall time
        "H2O3TPU_BENCH_CONFIG_TIMEOUT_S": "20",
        "H2O3TPU_BENCH_TRACE_DIR": str(tmp_path / "traces")})
    assert p.returncode == 0, p.stderr[-2000:]
    by_metric = {}
    for ln in lines:
        by_metric.setdefault(ln["metric"], []).append(ln)
    assert "value" in by_metric["stub config stub_a"][0]
    assert "value" in by_metric["stub config stub_b"][0]
    # every SUCCESSFUL config also banked a Chrome-trace artifact
    trace_line = by_metric["trace stub_a"][0]
    with open(trace_line["trace_path"]) as f:
        trace = json.load(f)
    assert all({"ph", "ts", "pid", "tid"} <= set(e)
               for e in trace["traceEvents"])
    wedge = by_metric["stub_wedge"][0]
    assert "wedged" in wedge["error"]
    # the roofline stub emits the hardware-relative fields (ISSUE 8):
    # every BENCH line carries mfu/hbm_util even without a backend
    rf = next(v[0] for k, v in by_metric.items()
              if k.startswith("roofline"))
    assert rf["mfu"] > 0 and rf["hbm_util"] > 0
    # the stepprof stub (ISSUE 20) proves the profiler's contracts
    # without a backend: bounded ring, straggler identity on synthetic
    # peers, and the benchdiff regression gate's pass/fail split
    sp = next(v[0] for k, v in by_metric.items()
              if k.startswith("stepprof"))
    assert sp["ring_len"] == 8 and sp["straggler"] == 1
    assert sp["skew_ratio"] > 1.5
    assert sp["benchdiff_identical_rc"] == 0
    assert sp["benchdiff_regression_rc"] == 1
    budget = by_metric["budget"][0]
    assert budget["left_s"] >= 0.0
    assert budget["budget_s"] >= 0.0
    for ln in lines:                       # no skipped line went negative
        if "skipped" in ln:
            assert "-" not in ln["skipped"]


@pytest.mark.allow_key_leak
def test_bench_preflight_probe_retries_then_recovers(tmp_path):
    """Transient probe failures (2 injected, shared across probe child
    processes via H2O3TPU_FAULT_STATE) are absorbed by the bounded
    backoff; every config line still emits."""
    # caps sized for a loaded host (six xdist workers compiling beside
    # this): a healthy stub config or probe child takes ~1-3 s alone
    p, lines = _run_bench(tmp_path, {
        "H2O3TPU_FAULTS": "probe:2",
        "H2O3TPU_BENCH_BUDGET_S": "240",
        "H2O3TPU_BENCH_CONFIG_TIMEOUT_S": "30"})
    assert p.returncode == 0, p.stderr[-2000:]
    metrics = {ln["metric"] for ln in lines if "value" in ln}
    assert {"stub config stub_a", "stub config stub_b"} <= metrics
    assert p.stderr.count("probe attempt") == 2


@pytest.mark.allow_key_leak
def test_bench_dead_backend_fails_fast_per_config(tmp_path):
    """A permanently dead backend costs error lines, not a hung bench:
    each config fails fast after the probe's bounded backoff."""
    p, lines = _run_bench(tmp_path, {
        "H2O3TPU_FAULTS": "probe:999",
        "H2O3TPU_INFRA_MAX_ATTEMPTS": "2",
        "H2O3TPU_BENCH_BUDGET_S": "240",
        "H2O3TPU_BENCH_CONFIG_TIMEOUT_S": "30"})
    assert p.returncode == 0, p.stderr[-2000:]
    errors = [ln for ln in lines if "error" in ln]
    # one per stub config (incl. grid, treekernel, cloud, roofline,
    # checkpoint, memgov, ingest, serving, sched, slo, fleet,
    # durability, globalfit, stepprof)
    assert len(errors) == 17
    assert all("backend dead" in ln["error"] for ln in errors)
    budget = [ln for ln in lines if ln["metric"] == "budget"][0]
    assert budget["left_s"] >= 0.0


# ------------------------------------------- SIGKILL-mid-AutoML resume


def _ft_frame():
    """MUST match tests/ft_worker.py build_data()."""
    import h2o3_tpu
    r = np.random.RandomState(17)
    n = 1200
    X = r.randn(n, 5)
    logits = X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2]
    y = (r.rand(n) < 1 / (1 + np.exp(-logits))).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(5)}
    cols["y"] = np.array(["no", "yes"], dtype=object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


@pytest.mark.slow
@pytest.mark.allow_key_leak
def test_sigkill_mid_automl_resume(tmp_path):
    """Acceptance: SIGKILL a worker mid-AutoML, resume_automl() in a
    fresh "cluster" (this process) — the leaderboard ends complete, and
    no step that finished pre-kill retrains."""
    from h2o3_tpu.automl import resume_automl
    d = str(tmp_path / "rec")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.Popen([sys.executable, FT_WORKER, d], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    state_path = os.path.join(d, "automl_state.json")
    deadline = time.time() + 420
    killed = False
    try:
        while time.time() < deadline:
            if proc.poll() is not None:
                break                      # finished before we could kill
            if os.path.exists(state_path):
                with open(state_path) as f:
                    st = json.load(f)
                if len(st.get("done_steps", [])) >= 1:
                    os.kill(proc.pid, signal.SIGKILL)
                    killed = True
                    break
            time.sleep(0.5)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert killed, ("worker finished (or never snapshotted) before the "
                    f"kill; rc={proc.returncode}")

    with open(state_path) as f:
        pre = json.load(f)
    pre_steps = set(pre["done_steps"])
    pre_keys = {os.path.basename(f)[:-len(".bin")]
                for fs in pre["models"].values() for f in fs}
    assert pre_steps and pre_keys

    fr = _ft_frame()
    aml = resume_automl(d, fr)
    tab = aml.leaderboard.as_table()
    lead_keys = {m.key for m in aml.leaderboard.models}
    # every pre-kill model survived into the resumed leaderboard
    assert pre_keys <= lead_keys
    # the plan continued: the resumed run reached the max_models budget
    # counting the restored models exactly once
    assert len(tab) >= len(pre_keys) + 1
    assert len(lead_keys) == len(aml.leaderboard.models)   # no dup keys
    # no step retrained twice: steps done pre-kill never ran post-resume
    post_steps = {e["message"].split(" done ")[0]
                  for e in aml.event_log if e["stage"] == "model"}
    assert not (pre_steps & post_steps), (pre_steps, post_steps)
    # and the final state is the union, each step recorded once
    with open(state_path) as f:
        final = json.load(f)
    assert len(final["done_steps"]) == len(set(final["done_steps"]))
    assert pre_steps <= set(final["done_steps"])
