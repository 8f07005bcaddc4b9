"""Training-step profiler acceptance (ISSUE 20): per-chunk phase
timing as a wall-clock partition, bounded rings, straggler/skew
verdicts on per-host snapshots, and the hand-offs at a fit's end.

The multiprocess leg spawns a REAL 2-process gloo pod
(tests/globalfit_worker.py ``profile`` mode) with ONE artificially
delayed host and asserts ``GET /3/Models/{id}/profile?cluster=1``
names that host as the straggler.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "globalfit_worker.py")

from h2o3_tpu.telemetry import stepprof  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_registry():
    stepprof.reset()
    yield
    stepprof.reset()


# ------------------------------------------------------------ unit tier


def test_ring_is_bounded_and_chunks_counted(monkeypatch):
    monkeypatch.setenv("H2O3TPU_STEPPROF_RING", "16")
    prof = stepprof.start("gbm", nrows=4000)
    assert prof is not None
    for _ in range(100):
        stepprof.chunk_begin()
        stepprof.compute_done(None)
        stepprof.chunk_end(trees=5)
    d = stepprof.finish(prof, model_key="m_ring", seconds=None)
    assert len(d["ring"]) == 16          # bounded
    assert d["chunks"] == 100            # but every chunk counted
    assert stepprof.profile_for("m_ring")["chunks"] == 100


def test_phase_partition_never_exceeds_wall_clock():
    prof = stepprof.start("gbm", nrows=100)
    for _ in range(3):
        stepprof.chunk_begin()
        time.sleep(0.01)                 # inside the compute window
        stepprof.compute_done(None)
        stepprof.chunk_end()
    time.sleep(0.02)                     # trailing host gap
    d = stepprof.finish(prof, model_key="m_part")
    assert sum(d["phases"].values()) <= d["seconds"] + 1e-3
    assert d["phases"]["compute"] >= 0.02        # 3 x 10ms windows
    assert d["phases"]["host"] >= 0.015          # the trailing gap


def test_delay_knob_charges_host_on_the_slow_chunk(monkeypatch):
    """The fault-injected slow chunk: H2O3TPU_STEPPROF_DELAY sleeps in
    chunk_end and the time lands in that chunk's host phase — the
    straggler signature the pod leg detects cross-host."""
    prof = stepprof.start("gbm", nrows=100)
    stepprof.chunk_begin()
    stepprof.compute_done(None)
    stepprof.chunk_end()
    monkeypatch.setenv("H2O3TPU_STEPPROF_DELAY", "0.08")
    stepprof.chunk_begin()
    stepprof.compute_done(None)
    stepprof.chunk_end()
    monkeypatch.delenv("H2O3TPU_STEPPROF_DELAY")
    d = stepprof.finish(prof, model_key="m_delay")
    fast, slow = d["ring"]
    assert slow["phases"]["host"] >= 0.075
    assert slow["phases"]["host"] > fast["phases"]["host"] + 0.05


def test_phase_cm_and_marks():
    prof = stepprof.start("glm", nrows=10)
    with stepprof.phase("checkpoint"):
        time.sleep(0.02)
    stepprof.mark("put_sharded_seconds", 0.5)
    d = stepprof.finish(prof, model_key="m_cm")
    assert d["phases"]["checkpoint"] >= 0.015
    assert d["marks"]["put_sharded_seconds"] == 0.5
    # marks are annotations, NOT partition members
    assert sum(d["phases"].values()) <= d["seconds"] + 1e-3


def test_profile_registry_lookup_and_miss():
    prof = stepprof.start("gbm")
    stepprof.finish(prof, model_key="m_hit")
    assert stepprof.profile_for("m_hit")["algo"] == "gbm"
    with pytest.raises(KeyError):
        stepprof.profile_for("m_nope")
    assert stepprof.last_fit_phases("gbm")["chunks"] == 0
    assert stepprof.last_fit_phases("deeplearning") == {}


def test_disabled_knob_makes_weave_free(monkeypatch):
    monkeypatch.setenv("H2O3TPU_STEPPROF", "off")
    assert stepprof.start("gbm") is None
    # the woven calls must all be no-ops without an active profile
    stepprof.chunk_begin()
    assert stepprof.compute_done("x") == "x"
    stepprof.chunk_end()
    assert stepprof.finish(None) is None


def test_snapshot_bounds_published_payload():
    for i in range(20):
        prof = stepprof.start("gbm")
        for _ in range(40):
            stepprof.chunk_begin()
            stepprof.chunk_end()
        stepprof.finish(prof, model_key=f"m_{i}")
    snap = stepprof.snapshot()
    assert len(snap["fits"]) == stepprof.SNAPSHOT_FITS
    assert all(len(f["ring"]) <= stepprof.SNAPSHOT_RING
               for f in snap["fits"])
    assert snap["fits"][0]["model_key"] == "m_19"      # newest first


# ------------------------------------------------------- skew verdicts


def _host(proc, host, compute, collective, checkpoint=0.0):
    return {"proc": proc,
            "seconds": host + compute + collective + checkpoint,
            "phases": {"host": host, "compute": compute,
                       "collective": collective,
                       "checkpoint": checkpoint}}


def test_compute_skew_names_the_straggler():
    """Synthetic 2-peer snapshots: the slow host accrues SELF time, the
    fast host accrues collective wait at the barrier probe."""
    skew = stepprof.compute_skew({
        "0": _host(0, host=0.5, compute=2.0, collective=7.5),
        "1": _host(1, host=4.0, compute=5.5, collective=0.5)})
    assert skew["straggler"] == "1"
    assert skew["straggler_proc"] == 1
    assert skew["skew_ratio"] == pytest.approx(9.5 / 2.5, rel=1e-3)
    assert skew["hosts"]["0"]["collective_share"] > 0.7
    assert skew["hosts"]["1"]["collective_share"] < 0.1


def test_compute_skew_balanced_and_empty():
    skew = stepprof.compute_skew({
        "0": _host(0, host=1.0, compute=4.0, collective=1.0),
        "1": _host(1, host=1.0, compute=4.0, collective=1.0)})
    assert skew["skew_ratio"] == pytest.approx(1.0)
    empty = stepprof.compute_skew({})
    assert empty["straggler"] is None and empty["skew_ratio"] == 0.0


def test_cluster_profile_single_process_sets_gauges():
    """On a 1-process cloud cluster_profile degrades to the local view:
    one host, skew 1.0, gauges published."""
    prof = stepprof.start("gbm", nrows=100)
    stepprof.chunk_begin()
    stepprof.compute_done(None)
    stepprof.chunk_end()
    stepprof.finish(prof, model_key="m_solo")
    from h2o3_tpu.telemetry import cluster
    cluster.publish(force=True)
    out = stepprof.cluster_profile("m_solo")
    assert out["model_key"] == "m_solo"
    assert len(out["hosts"]) == 1
    assert out["straggler_proc"] == 0
    from h2o3_tpu.telemetry.registry import REGISTRY
    assert [g.value for g in REGISTRY.find("pod_straggler_host")] == [0.0]


# ----------------------------------------------- fit-end hand-offs


def test_finish_returns_mfu_and_hands_the_capsule_a_ringless_profile():
    """``finish(mfu=)`` puts the MFU on the returned record, and the
    job's flight-recorder capsule gets that record without its ring."""
    from h2o3_tpu.telemetry import flight_recorder
    handle = flight_recorder.attach("job_stepprof_capsule")
    try:
        prof = stepprof.start("gbm", nrows=100)
        for _ in range(3):
            stepprof.chunk_begin()
            stepprof.compute_done(None)
            stepprof.chunk_end(trees=1)
        d = stepprof.finish(prof, model_key="m_capsule", mfu=0.25)
    finally:
        flight_recorder.detach(handle, "DONE")
    assert d["mfu"] == 0.25
    assert len(d["ring"]) == 3
    [block] = handle.capsule.to_dict()["step_profiles"]
    assert "ring" not in block
    assert block == {k: v for k, v in d.items() if k != "ring"}


def test_a_profiled_fit_writes_no_file_under_the_ice_root(tmp_path,
                                                          monkeypatch):
    """The fit's ``fit.account`` span closes the step profile and keeps
    nothing on disk: no baseline file appears under the ice root."""
    import h2o3_tpu
    from h2o3_tpu.core import config
    from h2o3_tpu.models.glm import GLMEstimator
    monkeypatch.setattr(config.ARGS, "ice_root", str(tmp_path))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 2)).astype(np.float32)
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": x[:, 0], "b": x[:, 1],
         "y": (x[:, 0] + 0.5 * x[:, 1]).astype(np.float32)})
    model = GLMEstimator(family="gaussian").train(fr, y="y")
    assert stepprof.profile_for(model.key)["algo"] == "glm"
    assert [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
            for f in fs] == []


# --------------------------------------------- the real 2-process leg


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.multiprocess
def test_pod_profile_names_the_delayed_straggler(tmp_path):
    """Acceptance: 2-process GBM global fit, pid 1 artificially delayed
    per chunk. /3/Models/{id}/profile?cluster=1 on pid 0 must name pid
    1 as the straggler, with pid 0's collective-wait share above the
    straggler's (the fast host waits at the barrier probe), and the
    pod_straggler_host gauge must carry the same verdict."""
    out = str(tmp_path / "profile.json")
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.update({"H2O3TPU_STEPPROF_DELAY_PID": "1",
                "H2O3TPU_STEPPROF_DELAY_S": "0.5",
                "H2O3TPU_PROFILE_PORT": str(_free_port())})
    procs = [subprocess.Popen(
        [sys.executable, WORKER, coord, "2", str(i), out, "profile"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(2)]
    logs = []
    deadline = time.time() + 240
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=max(deadline - time.time(),
                                                  1.0))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            stdout, _ = p.communicate()
            stdout = (stdout or "") + "\n[TIMEOUT]"
        logs.append(stdout)
    joined = "\n".join(f"--- worker {j} ---\n{lg[-3000:]}"
                       for j, lg in enumerate(logs))
    assert all(p.returncode == 0 for p in procs), joined
    with open(out) as f:
        res = json.load(f)
    cl = res["cluster"]
    assert cl is not None, joined
    assert len(cl["hosts"]) == 2, cl
    assert cl["straggler_proc"] == 1, cl
    # skew = max/min self-time: the injected 0.5s/chunk delay must make
    # pid 1's self-time measurably larger.  The bound is modest because
    # the timeshared 1-core container runs both hosts' real compute
    # back-to-back, diluting the ratio.
    assert cl["skew_ratio"] > 1.1, cl
    # the fast host's collective-wait share rises above the straggler's
    hosts = {h["proc"]: h for h in cl["hosts"].values()}
    assert hosts[0]["collective_share"] > hosts[1]["collective_share"], cl
    # gauge names carry the registry's export prefix (h2o3tpu_...)
    gauges = {k.rsplit("pod_", 1)[-1]: v for k, v in res["gauges"].items()}
    assert gauges["straggler_host"] == 1.0, res
    assert gauges["step_skew_ratio"] > 1.1, res
    assert res["chunks"] >= 2, res
