"""Job-level infra-error retry (round-3 VERDICT item #10).

A transient XLA INTERNAL/UNAVAILABLE error must not permanently fail
a job (in round 2 one such blip killed an AutoML step for good); user
errors must still fail fast with no retry. Since the fault-tolerance
layer the retry policy is shared (core/watchdog.py): bounded attempts +
exponential backoff from core/config.py.
"""

import pytest

from h2o3_tpu.core import config
from h2o3_tpu.core.job import FAILED, DONE, Job, is_infra_error


class FakeXlaRuntimeError(Exception):
    pass


@pytest.fixture(autouse=True)
def _fast_backoff(monkeypatch):
    """Keep the watchdog backoff out of the test wallclock."""
    monkeypatch.setattr(config.ARGS, "infra_backoff_base_s", 0.001)
    monkeypatch.setattr(config.ARGS, "infra_backoff_max_s", 0.002)


def test_infra_error_retried():
    calls = {"n": 0}

    def flaky(job):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeXlaRuntimeError(
                "INTERNAL: From /job:tpu_worker/replica:0: compile "
                "failed: UNAVAILABLE: socket closed")
        return "ok"

    j = Job("flaky step").start(flaky)
    assert j.status == DONE
    assert j.result == "ok"
    assert calls["n"] == 2


def test_infra_retries_bounded_by_config(monkeypatch):
    """A permanently-dead backend gets exactly infra_max_attempts tries
    (the watchdog policy), then the job fails for good."""
    monkeypatch.setattr(config.ARGS, "infra_max_attempts", 3)
    calls = {"n": 0}

    def always_down(job):
        calls["n"] += 1
        raise FakeXlaRuntimeError("UNAVAILABLE: TPU worker process crashed")

    with pytest.raises(FakeXlaRuntimeError):
        Job("dead step").start(always_down)
    assert calls["n"] == 3


def test_user_error_fails_fast():
    calls = {"n": 0}

    def bad_params(job):
        calls["n"] += 1
        raise ValueError("unknown GBM params: ['nonsense']")

    with pytest.raises(ValueError):
        Job("user error").start(bad_params)
    assert calls["n"] == 1


def test_background_job_records_failure():
    def always_down(job):
        raise FakeXlaRuntimeError("UNAVAILABLE: TPU worker process crashed")

    j = Job("bg dead").start(always_down, background=True).join(30)
    assert j.status == FAILED
    assert "TPU worker process crashed" in j.exception


def test_is_infra_error_classification():
    assert is_infra_error(FakeXlaRuntimeError("INTERNAL: boom"))
    assert is_infra_error(RuntimeError("UNAVAILABLE: socket closed"))
    assert not is_infra_error(ValueError("INTERNAL: looks alike"))
    assert not is_infra_error(RuntimeError("plain user-visible failure"))


def test_retries_observable_in_telemetry():
    """infra_retries_total{site=job} counts every retry the policy
    grants (README §Fault tolerance metric surface)."""
    from h2o3_tpu import telemetry
    before = telemetry.REGISTRY.value("infra_retries_total", site="job")
    calls = {"n": 0}

    def flaky(job):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeXlaRuntimeError("UNAVAILABLE: worker restarting")
        return "ok"

    Job("flaky counted").start(flaky)
    after = telemetry.REGISTRY.value("infra_retries_total", site="job")
    assert after - before == 1
