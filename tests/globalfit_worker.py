"""Worker for the pod-global sharded-training acceptance tests (ISSUE
19 — ONE fit data-parallel across every host).

Modes (``sys.argv[5]``):

* ``fit`` — N processes form a cloud; each supplies ONLY its
  ``mesh.owned_rows`` slice to ``Frame.from_numpy_partitioned`` and the
  pod trains one GBM + one GLM over the host-partitioned frame. pid 0
  writes bit-level artifacts (forest digest, float hexes) to `outfile`.
* ``ref`` — ONE process with ``--xla_force_host_platform_device_count=2``
  runs the SAME logical data=2 SPMD program over the legacy replicated
  ingest: the bit-exact reference the ``fit`` pod must match (same mesh
  shape ⇒ same psum tree ⇒ same float addition order).
* ``sigkill`` — both processes start a long global fit; pid 1 SIGKILLs
  itself mid-boost-loop. pid 0's job must FAIL with an infra-classified
  error within one heartbeat window of the loss being observed — no
  hang, no leaked RUNNING job.
* ``bench`` — times the global GBM fit on the partitioned frame and
  reports rows/sec (pid 0), for bench.py's ``globalfit`` config; every
  pid also drops its ``{outfile}.phases.{pid}`` step-profiler split.
* ``profile`` — ISSUE 20: 2-process fit with ONE artificially-delayed
  host (``H2O3TPU_STEPPROF_DELAY_PID``/``_S``); pid 0 queries
  ``GET /3/Models/{id}/profile?cluster=1`` and reports the
  straggler/skew verdict.

Workers that outlive a dead peer exit via ``os._exit`` — the normal
distributed teardown would barrier against the corpse.
"""

import hashlib
import json
import os
import signal
import sys
import time

coord, nproc, pid, outfile = sys.argv[1:5]
mode = sys.argv[5] if len(sys.argv) > 5 else "fit"

os.environ["JAX_PLATFORMS"] = "cpu"
# the reference run folds the pod's device count into one process so
# both runs lower the SAME data=2 SPMD program (bit-parity by program
# identity, not by luck)
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2"
                           if mode == "ref"
                           else "--xla_force_host_platform_device_count=1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                    # noqa: E402
jax.config.update("jax_default_device", None)

import h2o3_tpu                               # noqa: E402
if int(nproc) > 1:
    h2o3_tpu.init(backend="cpu", coordinator_address=coord,
                  num_processes=int(nproc), process_id=int(pid))
else:
    h2o3_tpu.init(backend="cpu")

import numpy as np                            # noqa: E402

from h2o3_tpu.core import recovery as _recovery   # noqa: E402
from h2o3_tpu.models.gbm import GBMEstimator      # noqa: E402
from h2o3_tpu.models.glm import GLMEstimator      # noqa: E402
from h2o3_tpu.parallel import mesh as mesh_mod    # noqa: E402

T0 = time.monotonic()
# deliberately NOT a multiple of hosts*devices: the padded tail must be
# invisible in every statistic (the ISSUE 19 padding-parity contract)
N_ROWS = 4001
# stopping_rounds enables the per-chunk scorer (scoring history is an
# acceptance artifact); tolerance 0 never actually stops a 10-tree fit
GBM_PARAMS = dict(ntrees=10, max_depth=4, seed=3, stopping_rounds=3,
                  stopping_tolerance=0.0, score_tree_interval=5)


def mark(stage):
    print(f"WORKER-{pid}-STAGE {time.monotonic() - T0:7.2f}s {stage}",
          flush=True)


def build_arrays(n=N_ROWS):
    r = np.random.RandomState(11)
    a = r.randn(n)
    b = r.randn(n)
    g = r.choice(["u", "v", "w"], n)
    y = 2.0 * a - b + (g == "u") * 1.5 + r.randn(n) * 0.3
    return {"a": a, "b": b, "g": g, "y": y}


def make_frame():
    """Partitioned ingest from ONLY this process's owned rows (fit /
    sigkill / bench modes) or legacy replicated ingest (ref mode)."""
    full = build_arrays()
    if mode == "ref":
        return h2o3_tpu.Frame.from_numpy(full, categorical=["g"])
    lo, hi = mesh_mod.owned_rows(N_ROWS, block=8)
    local = {k: v[lo:hi] for k, v in full.items()}
    mark(f"owned rows [{lo}, {hi})")
    return h2o3_tpu.Frame.from_numpy_partitioned(
        local, N_ROWS, categorical=["g"])


def forest_digest(forest):
    """blake2b over every stacked tree array — bit-exact forest id.
    Snapshots via recovery.snapshot_host: forest leaves are replicated
    global arrays on a multi-process mesh (not fully addressable)."""
    h = hashlib.blake2b(digest_size=16)
    for name, arr in zip(forest._fields, forest):
        v = np.asarray(_recovery.snapshot_host(arr))
        h.update(name.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


def run_fit():
    fr = make_frame()
    part_cols = sum(1 for c in fr._cols.values()
                    if getattr(c, "_part_cache", None) is not None)
    if int(pid) == 1 or int(nproc) == 1:
        # asymmetric single-process host access (the REST-handler /
        # scheduled-item contract): ONLY this process reads the host
        # view, so it must come from the ingest-seeded cache — a lazy
        # cross-process gather here would wedge the pod (peers are not
        # at this program point)
        hv = fr.col("a").host_view()
        assert hv.shape[0] == N_ROWS and \
            np.array_equal(hv, build_arrays()["a"]), "host_view parity"
        mark("asymmetric host_view ok")
    mark(f"frame up ({part_cols} partitioned cols); training")
    gbm = GBMEstimator(**GBM_PARAMS).train(fr, y="y")
    glm = GLMEstimator(family="gaussian", lambda_=0.0).train(fr, y="y")
    pred = gbm.predict(fr).col("predict").to_numpy()
    gather_keys = 0
    if int(nproc) > 1:
        # the off-mode devolution must not leave dataset-sized gather
        # blobs resident in the coordination service; queried AFTER
        # training so the peer's post-barrier deletes (issued right
        # after its allgather_rows read) have long landed
        from h2o3_tpu.frame import partition as part_mod
        gather_keys = len(list(part_mod._client().key_value_dir_get(
            part_mod.KV_PREFIX + "gather/")))
    result = {
        "mode": mode,
        "process_count": len({d.process_index for d in jax.devices("cpu")}),
        "mesh_data": mesh_mod.get_mesh().shape[mesh_mod.DATA_AXIS],
        "partitioned_cols": part_cols,
        "gather_keys_resident": gather_keys,
        "forest_digest": forest_digest(gbm.forest),
        "gbm_mse_hex": float(gbm.training_metrics["MSE"]).hex(),
        "scoring_history": [
            {"ntrees": int(e["ntrees"]),
             "deviance_hex": float(e["deviance"]).hex()}
            for e in gbm.output["scoring_history"]],
        "gbm_pred_head_hex": [float(v).hex() for v in pred[:32]],
        "glm_coefficients": {k: float(v)
                             for k, v in glm.coefficients.items()},
    }
    if int(pid) == 0:
        with open(outfile, "w") as f:
            json.dump(result, f)
    print(f"WORKER-{pid}-DONE", flush=True)
    h2o3_tpu.shutdown()


def run_bench():
    fr = make_frame()
    ntrees = int(os.environ.get("H2O3TPU_GLOBALFIT_BENCH_NTREES", "30"))
    GBMEstimator(ntrees=5, max_depth=4, seed=3).train(fr, y="y")  # warmup
    t0 = time.time()
    GBMEstimator(ntrees=ntrees, max_depth=4, seed=3).train(fr, y="y")
    dt = max(time.time() - t0, 1e-9)
    # EVERY pid reports its own phase split (telemetry/stepprof.py):
    # bench.py folds these into the per-host compute/collective/host
    # table printed next to the rows/sec line
    try:
        from h2o3_tpu.telemetry import stepprof
        ph = stepprof.last_fit_phases("gbm")
        ph["proc"] = int(pid)
        with open(f"{outfile}.phases.{pid}", "w") as f:
            json.dump(ph, f)
    except Exception as e:   # noqa: BLE001 - table is best-effort
        print(f"WORKER-{pid}-PHASES-FAILED {e}", flush=True)
    if int(pid) == 0:
        with open(outfile, "w") as f:
            json.dump({"mode": mode, "rows_per_sec": N_ROWS * ntrees / dt,
                       "seconds": dt, "ntrees": ntrees,
                       "nrows": N_ROWS}, f)
    print(f"WORKER-{pid}-DONE", flush=True)
    h2o3_tpu.shutdown()


def run_profile():
    """ISSUE 20 acceptance leg: a 2-process global GBM fit with ONE
    artificially-delayed host; ``GET /3/Models/{id}/profile?cluster=1``
    on pid 0 must name the slow host as the straggler and show the fast
    host's collective-wait share rising (it waits at the per-chunk
    barrier probe while the slow host sleeps)."""
    import urllib.request
    from h2o3_tpu.telemetry import cluster, stepprof

    delay_pid = int(os.environ.get("H2O3TPU_STEPPROF_DELAY_PID", "1"))
    delay_s = os.environ.get("H2O3TPU_STEPPROF_DELAY_S", "0.25")
    fr = make_frame()
    # warmup fit with the SAME ntrees: chunk programs compile per chunk
    # size, so an equal-shape warmup makes the profiled fit's compute
    # phase pure chunk work, not XLA compile (identical on every host —
    # it would bury the skew the delay is meant to produce)
    params = dict(GBM_PARAMS, ntrees=30)
    GBMEstimator(**params).train(fr, y="y")
    if int(pid) == delay_pid:
        # per-host injection: the pod-wide env would slow EVERY host
        os.environ["H2O3TPU_STEPPROF_DELAY"] = delay_s
        mark(f"injecting {delay_s}s/chunk delay on pid {pid}")
    mark("warm; training profiled global fit")
    gbm = GBMEstimator(**params).train(fr, y="y")
    os.environ.pop("H2O3TPU_STEPPROF_DELAY", None)
    local = stepprof.profile_for(gbm.key)
    ok = cluster.publish(force=True)
    mark(f"profile published ok={ok}; syncing")
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("stepprof-profile-published")

    if int(pid) == 0:
        # the peer's snapshot already sits in the coordination KV (the
        # publish above), so only pid 0 needs to stay up for the fetch
        from h2o3_tpu.api.server import start_server
        port = int(os.environ.get("H2O3TPU_PROFILE_PORT", "54661"))
        start_server(port=port, background=True)
        url = (f"http://127.0.0.1:{port}/3/Models/{gbm.key}"
               f"/profile?cluster=1")
        prof = json.loads(urllib.request.urlopen(url, timeout=30).read())
        from h2o3_tpu.telemetry.registry import REGISTRY
        gauges = {g.name: g.value
                  for g in REGISTRY.find("pod_step_skew_ratio")
                  + REGISTRY.find("pod_straggler_host")}
        result = {
            "mode": mode,
            "delay_pid": delay_pid,
            "model_key": gbm.key,
            "local_phases": local["phases"],
            "chunks": local["chunks"],
            "cluster": prof.get("cluster"),
            "gauges": gauges,
        }
        with open(outfile, "w") as f:
            json.dump(result, f)
    # second barrier BEFORE teardown: shutdown() sweeps this node's KV
    # snapshot first thing, so pid 1 racing into it would delete the
    # very entry pid 0's cluster fetch above still needs to read
    multihost_utils.sync_global_devices("stepprof-profile-fetched")
    print(f"WORKER-{pid}-DONE", flush=True)
    h2o3_tpu.shutdown()


def run_sigkill():
    from h2o3_tpu.core import heartbeat, watchdog
    from h2o3_tpu.core.job import RUNNING, list_jobs
    fr = make_frame()
    mark("frame up; starting long global fit")
    est = GBMEstimator(ntrees=4000, max_depth=5, seed=1)
    est.train(fr, y="y", background=True)
    job = est._job
    deadline = time.monotonic() + 120
    while job.progress <= 0.0 and job.status == RUNNING \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    mark(f"fit in boost loop (progress={job.progress:.3f})")

    if int(pid) == 1:
        # victim: die mid-collective, the unclean way a host dies
        print(f"WORKER-{pid}-KILLING-SELF", flush=True)
        os.kill(os.getpid(), signal.SIGKILL)

    # survivor (pid 0): the heartbeat monitor flags the dead peer; the
    # fit must FAIL at the next chunk boundary (or the gloo collective
    # errors out first — either way classified infra, never a hang)
    window_s = heartbeat.monitor.interval_s * heartbeat.monitor.miss_budget
    t_lost = None
    while time.monotonic() < deadline:
        if 1 in heartbeat.dead_peers() or not heartbeat.monitor.healthy():
            t_lost = time.monotonic()
            break
        if job.status != RUNNING:
            # gloo surfaced the death before the heartbeat did
            t_lost = time.monotonic()
            break
        time.sleep(0.02)
    mark("peer loss observed; waiting for the job to fail fast")
    job.join(60)
    fail_after_loss_s = (time.monotonic() - t_lost) if t_lost else None
    running_leaks = [j["description"] for j in list_jobs()
                     if j["status"] == RUNNING]
    exc = job.exception or ""
    result = {
        "mode": mode,
        "job_status": job.status,
        "job_exception": exc[-800:],
        "infra_classified": ("CloudUnhealthyError" in exc
                             or any(s in exc
                                    for s in watchdog.INFRA_SIGNS)),
        "heartbeat_window_s": window_s,
        "fail_after_loss_s": fail_after_loss_s,
        "running_leaks": running_leaks,
    }
    with open(outfile, "w") as f:
        json.dump(result, f)
    print(f"WORKER-{pid}-DONE", flush=True)
    os._exit(0)   # teardown would barrier against the dead peer


if mode in ("fit", "ref"):
    run_fit()
elif mode == "bench":
    run_bench()
elif mode == "sigkill":
    run_sigkill()
elif mode == "profile":
    run_profile()
else:
    raise SystemExit(f"unknown mode {mode!r}")
