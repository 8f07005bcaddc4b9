"""Worker for the durable-data-plane SIGKILL acceptance test (ISSUE 18
— the reference's node-loss recovery tier).

Two processes form a cloud with ``H2O3TPU_DATA_DURABILITY=mirror``:

* pid 1 ingests a deterministic frame (write-through mirrored into the
  shared ``H2O3TPU_DUR_DIR``), then starts a checkpointed GBM fit whose
  traveling snapshots land in the shared fit-checkpoint dir. The parent
  SIGKILLs it after the first snapshot appears.
* pid 0 waits for the heartbeat monitor to declare pid 1 dead, runs the
  recovery supervisor, and asserts: the frame is rebuilt bit-identically
  from its mirror, re-homed locally, visible in
  ``frame_rebuilds_total{source=mirror}``; the interrupted fit resumes
  from the dead peer's snapshot and finishes bit-identical to an
  undisturbed reference fit; no RUNNING job leaks.

Exits via ``os._exit`` — the normal distributed teardown would barrier
against the dead peer.
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

coord, nproc, pid, outfile = sys.argv[1:5]

import jax                                    # noqa: E402
jax.config.update("jax_default_device", None)

import h2o3_tpu                               # noqa: E402
h2o3_tpu.init(backend="cpu", coordinator_address=coord,
              num_processes=int(nproc), process_id=int(pid))

import numpy as np                            # noqa: E402

from h2o3_tpu.core import durability, heartbeat  # noqa: E402
from h2o3_tpu.models.gbm import GBMEstimator     # noqa: E402
from h2o3_tpu.parallel import mesh as mesh_mod   # noqa: E402

GBM_PARAMS = dict(ntrees=80, max_depth=3, learn_rate=0.1, seed=7)
DEADLINE_S = float(os.environ.get("H2O3TPU_MP_TIMEOUT_S", "300")) - 30.0
T0 = time.monotonic()


def build_data():
    r = np.random.RandomState(23)
    n = 1500
    a = r.randn(n)
    b = r.randn(n)
    c = r.randn(n)
    y = 1.5 * a - 0.5 * b + np.sin(c) + r.randn(n) * 0.2
    return h2o3_tpu.Frame.from_numpy({"a": a, "b": b, "c": c, "y": y})


def mark(stage):
    print(f"WORKER-{pid}-STAGE {time.monotonic() - T0:7.2f}s {stage}",
          flush=True)


def wait_for(pred, what, timeout_s=60.0):
    mark(f"waiting: {what}")
    end = min(time.monotonic() + timeout_s, T0 + DEADLINE_S)
    while time.monotonic() < end:
        if pred():
            mark(f"done: {what}")
            return
        time.sleep(0.1)
    raise TimeoutError(f"pid {pid}: timed out waiting for {what}")


if int(pid) == 1:
    # -- victim: ingest (mirrored) + checkpointed fit, then be killed
    with mesh_mod.local_mesh_scope():
        fr = build_data()
        assert fr.key in durability.stats()["mirrored"], \
            "write-through mirror did not register the frame"
        mark("frame mirrored; starting checkpointed fit")
        # the parent SIGKILLs this process once the fit's first
        # traveling snapshot lands in the shared checkpoint dir
        GBMEstimator(**GBM_PARAMS).train(fr, y="y")
    # only reached if the parent's kill never landed — that is a test
    # failure upstream; report and exit cleanly
    print(f"WORKER-{pid}-UNEXPECTED-SURVIVAL", flush=True)
    os._exit(1)

# -- survivor (pid 0): recover, resume, and reference-check

# the victim registers exactly one frame in the coordination KV
wait_for(lambda: len(durability.registry(1)) == 1,
         "peer 1's registry entry")
(frame_key, entry), = durability.registry(1).items()
want_digest = entry["digest"]
assert entry.get("gen"), f"peer frame was not mirrored: {entry}"

# heartbeat declares the SIGKILLed peer dead once its beat goes stale
wait_for(lambda: 1 in heartbeat.dead_peers(), "heartbeat death of pid 1",
         timeout_s=120.0)

# run the recovery supervisor until the frame is re-homed here — the
# heartbeat piggyback races this same call; both paths are idempotent
# and the parent sets H2O3TPU_DUR_REBUILD_S low enough to retry fast
from h2o3_tpu.core.kv import DKV              # noqa: E402
wait_for(lambda: durability.maybe_rebuild() >= 0 and frame_key in DKV,
         "rebuild of the lost frame")

from h2o3_tpu import telemetry                # noqa: E402
fr = DKV.get(frame_key)
with mesh_mod.local_mesh_scope():
    got_digest = durability.frame_digest(fr)
mark("frame rebuilt + digest checked")
assert got_digest == want_digest, \
    f"rebuilt frame is not bit-identical: {got_digest} != {want_digest}"
mirror_rebuilds = telemetry.counter(
    "frame_rebuilds_total", source="mirror").value
assert mirror_rebuilds >= 1, "rebuild not visible in frame_rebuilds_total"

# resume the dead peer's fit: same (algo, params, y, x, nrows) →
# same fingerprint → the traveling snapshot it wrote is picked up
os.environ.pop("H2O3TPU_FIT_CHECKPOINT_HOLD_S", None)
# local_work_scope: these fits run purely on local devices (the
# scheduler work-item pattern) — the dead peer must not fail them
with heartbeat.local_work_scope(), mesh_mod.local_mesh_scope():
    resumed = GBMEstimator(**GBM_PARAMS).train(fr, y="y")
    resumed_pred = resumed.predict(fr).col("predict").to_numpy()
mark("resumed fit done")

# undisturbed reference: same data + params, checkpointing off
os.environ.pop("H2O3TPU_FIT_CHECKPOINT_DIR", None)
with heartbeat.local_work_scope(), mesh_mod.local_mesh_scope():
    fresh = GBMEstimator(**GBM_PARAMS).train(fr, y="y")
    fresh_pred = fresh.predict(fr).col("predict").to_numpy()
mark("reference fit done")
assert np.array_equal(resumed_pred, fresh_pred), \
    "resumed fit is not bit-identical to the undisturbed reference"

running = [k for k in DKV.keys()
           if getattr(DKV.get_raw(k), "status", None) == "RUNNING"]
assert not running, f"RUNNING job leak after recovery: {running}"

result = {
    "frame_key": frame_key,
    "digest_match": True,
    "rebuild_source": "mirror",
    "mirror_rebuilds_total": float(mirror_rebuilds),
    "resumed_mse": float(resumed.training_metrics["MSE"]),
    "fresh_mse": float(fresh.training_metrics["MSE"]),
    "bit_identical_fit": True,
    "under_replicated": telemetry.gauge("frames_under_replicated").value,
}
with open(outfile, "w") as f:
    json.dump(result, f)
print(f"WORKER-{pid}-DONE", flush=True)
os._exit(0)
