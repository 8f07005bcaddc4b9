"""Fault-tolerance layer tests — watchdog policy/probe, fault
injection, recovery snapshots, and the
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
