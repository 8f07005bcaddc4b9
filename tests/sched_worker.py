"""Worker for the cluster work-scheduler multiprocess tests (ISSUE 15,
parallel/scheduler.py).

Every process runs this same script (the SPMD contract): forms a
jax.distributed CPU cloud, then trains an 8-combo GBM grid that the
scheduler fans across the hosts. Modes (argv[5]):

- ``ref``  — single process, scheduler OFF: the bit-parity reference.
- ``run``  — N processes, scheduler auto (on): the fan-out leg.
- ``kill`` — like ``run``, but process 1 SIGKILLs itself after
  completing its first scheduled item; the coordinator must detect the
  dead peer, reassign its remaining leases, and finish bit-identical.
- ``trace`` — the ISSUE 16 distributed-tracing leg: process 0 drives
  the SAME grid through REST with a ``traceparent`` header and fetches
  ``GET /3/Trace?trace_id=``; process 1 trains directly (the SPMD
  partner). The stitched trace must hold causally-parented spans from
  BOTH hosts under the client's trace id.

Each surviving process writes ``outfile.<pid>`` with the grid result
(full-precision metrics), its scheduler counters, and its job statuses.
"""

import json
import os
import signal
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
# singleton items (one per combo) so an 8-combo grid provably spreads
# across BOTH hosts; the batched path is covered by single-process tier-1
os.environ["H2O3TPU_BATCH_MODELS"] = "off"
# fast dead-peer detection for the kill leg (staleness = interval * 3)
os.environ["H2O3TPU_HEARTBEAT_INTERVAL_S"] = "0.25"
os.environ["H2O3TPU_SCHEDULER_POLL_S"] = "0.05"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

coord, nproc, pid, outfile, mode = sys.argv[1:6]
nproc, pid = int(nproc), int(pid)

os.environ["H2O3TPU_SCHEDULER"] = "off" if mode == "ref" else "auto"

import jax                                    # noqa: E402
jax.config.update("jax_default_device", None)

import h2o3_tpu                               # noqa: E402
if nproc > 1:
    h2o3_tpu.init(backend="cpu", coordinator_address=coord,
                  num_processes=nproc, process_id=pid)
else:
    h2o3_tpu.init(backend="cpu")

import numpy as np                            # noqa: E402

from h2o3_tpu.parallel import scheduler       # noqa: E402

if mode == "kill" and pid == 1:
    # publish exactly one result, then die without warning — the
    # coordinator must reassign this host's remaining leases
    _orig_execute = scheduler._execute_one

    def _execute_then_die(*args, **kwargs):
        res = _orig_execute(*args, **kwargs)
        os.kill(os.getpid(), signal.SIGKILL)
        return res

    scheduler._execute_one = _execute_then_die


def build_data():
    """MUST match tests/test_scheduler.py expectations (same rows as
    tests/mp_worker.py build_data)."""
    r = np.random.RandomState(5)
    n = 4000
    a = r.randn(n)
    b = r.randn(n)
    g = r.choice(["u", "v", "w"], n)
    y = 2.0 * a - b + (g == "u") * 1.5 + r.randn(n) * 0.3
    return h2o3_tpu.Frame.from_numpy(
        {"a": a, "b": b, "g": g, "y": y}, categorical=["g"])


fr = build_data()

from h2o3_tpu.ml.grid import GridSearch       # noqa: E402
from h2o3_tpu.models.gbm import GBMEstimator  # noqa: E402

HYPER = {"learn_rate": [0.05, 0.1],
         "sample_rate": [0.7, 1.0],
         "min_rows": [5.0, 10.0]}             # 8 combos, one shape

if mode == "trace":
    import time
    import urllib.parse
    import urllib.request

    from h2o3_tpu import telemetry
    from h2o3_tpu.telemetry import cluster

    TRACE_ID = "ab" * 16

    if pid == 0:
        # REST-initiated leg: the handler launches a background job
        # whose grid train enters scheduler.run — the same SPMD point
        # process 1 reaches directly below
        from h2o3_tpu.api.server import start_server
        port = start_server(port=0, background=True)
        tp = f"00-{TRACE_ID}-{'0' * 16}-01"
        url = (f"http://127.0.0.1:{port}/99/Grid/gbm"
               f"?training_frame={urllib.parse.quote(str(fr.key))}"
               f"&response_column=y&ntrees=3&max_depth=3&seed=3"
               f"&hyper_parameters="
               f"{urllib.parse.quote(json.dumps(HYPER))}")
        req = urllib.request.Request(url, data=b"", method="POST",
                                     headers={"traceparent": tp})
        with urllib.request.urlopen(req) as r:
            echoed = r.headers.get("X-H2O-Trace-Id")
            jk = json.loads(r.read())["job"]["key"]["name"]
        status = "?"
        # every poll is an (untraced) ``rest`` span of its own, and a
        # node publishes the last cluster.MAX_SPANS (192) spans of its
        # ring: polled ten times a second, a job of a few seconds
        # pushed the traced ``rest`` span out of what is stitched
        for _ in range(240):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/3/Jobs/{jk}") as r:
                jd = json.loads(r.read())["jobs"][0]
            status = jd["status"]
            if status not in ("CREATED", "RUNNING"):
                break
            time.sleep(0.5)
        # the stitched trace needs BOTH hosts' span rings: poll until
        # process 1's published snapshot carries its leased items
        trace = {}
        for _ in range(100):
            cluster.publish(force=True)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/3/Trace"
                    f"?trace_id={TRACE_ID}") as r:
                trace = json.loads(r.read())
            # ... its ITEMS: a heartbeat-cadence publish from the middle
            # of process 1's first item already carries traced spans
            # (fit.admit, the frame's build), so "both nodes are in the
            # trace" alone raced the items' own spans
            if any(e.get("name") == "sched.item"
                   and e.get("args", {}).get("node") == 1
                   for e in trace.get("traceEvents", [])):
                break
            time.sleep(0.2)
        result = {"pid": pid, "status": status, "echoed": echoed,
                  "job_trace_id": jd.get("trace_id"),
                  "trace": trace}
    else:
        # offset this process's span-id counter so its span ids cannot
        # collide with the COORDINATOR's sched.run id — cross-node
        # parent resolution in trace_export prefers a same-node owner
        for _ in range(512):
            with telemetry.span("trace_test.pad"):
                pass
        GridSearch(GBMEstimator, HYPER, ntrees=3, max_depth=3,
                   seed=3).train(fr, y="y")
        # keep publishing until process 0 banked its stitched trace
        for _ in range(300):
            cluster.publish(force=True)
            if os.path.exists(f"{outfile}.0"):
                break
            time.sleep(0.2)
        result = {"pid": pid,
                  "sched": scheduler.snapshot(),
                  "spans_with_trace": sum(
                      1 for s in telemetry.spans_snapshot(2048)
                      if s.get("trace_id") == TRACE_ID)}
    with open(f"{outfile}.{pid}", "w") as f:
        json.dump(result, f)
    print(f"SCHED-WORKER-{pid}-DONE", flush=True)
    if pid == 0:
        # the coordination service lives in THIS process: exiting while
        # peer 1 still polls it turns the socket close into a fatal
        # UNAVAILABLE in that process (pjrt distributed client CHECK) —
        # hold on until the peer has banked its result
        for _ in range(600):
            if os.path.exists(f"{outfile}.1"):
                break
            time.sleep(0.1)
    # skip the distributed-shutdown barrier: results are on disk, and
    # the processes finish at different times by design
    os._exit(0)

grid = GridSearch(GBMEstimator, HYPER, ntrees=3, max_depth=3,
                  seed=3).train(fr, y="y")

# full-precision walk-order leaderboard: the bit-parity payload (repr
# round-trips exactly through json)
rows = [[json.dumps(m.output.get("grid_params"), sort_keys=True),
         float(m.training_metrics["MSE"])] for m in grid.models]

from h2o3_tpu import telemetry                # noqa: E402
from h2o3_tpu.core.job import list_jobs      # noqa: E402

result = {
    "pid": pid,
    "grid": rows,
    "sched": scheduler.snapshot(),
    "items_completed_here": telemetry.REGISTRY.value(
        "sched_items_completed_total", host=str(pid)),
    "job_statuses": sorted(j["status"] for j in list_jobs()),
}
with open(f"{outfile}.{pid}", "w") as f:
    json.dump(result, f)
print(f"SCHED-WORKER-{pid}-DONE", flush=True)

if mode == "kill":
    # peer 1 is dead: a collective or the distributed-shutdown barrier
    # would wait on it forever — results are on disk, leave hard
    os._exit(0)
h2o3_tpu.shutdown()
