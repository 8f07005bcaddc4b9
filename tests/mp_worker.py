"""Worker for the multi-process cloud test (the reference's
multi-JVM-on-localhost tier, multiNodeUtils.sh:22-27 / @CloudSize(n)).

Each process runs this script with the SAME deterministic data; the
jax.distributed coordinator forms the cloud; training runs SPMD over the
cross-process mesh. Process 0 writes metrics to `outfile` for the parent
test to compare with the single-process run.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

coord, nproc, pid, outfile = sys.argv[1:5]

import jax                                    # noqa: E402
jax.config.update("jax_default_device", None)

import h2o3_tpu                               # noqa: E402
# the multi-process cloud forms over the per-process CPU devices
h2o3_tpu.init(backend="cpu", coordinator_address=coord,
              num_processes=int(nproc), process_id=int(pid))

import numpy as np                            # noqa: E402


def build_data():
    r = np.random.RandomState(5)
    n = 4000
    a = r.randn(n)
    b = r.randn(n)
    g = r.choice(["u", "v", "w"], n)
    y = 2.0 * a - b + (g == "u") * 1.5 + r.randn(n) * 0.3
    return h2o3_tpu.Frame.from_numpy(
        {"a": a, "b": b, "g": g, "y": y}, categorical=["g"])


fr = build_data()

from h2o3_tpu.models.gbm import GBMEstimator     # noqa: E402
from h2o3_tpu.models.glm import GLMEstimator     # noqa: E402

gbm = GBMEstimator(ntrees=10, max_depth=4, seed=3).train(fr, y="y")
glm = GLMEstimator(family="gaussian", lambda_=0.0).train(fr, y="y")

gbm_pred = gbm.predict(fr).col("predict").to_numpy()

# peer health: the heartbeat monitor auto-starts for multi-process
# clouds; give it one interval to publish + read beats, then record
# what this process sees of its peers
import time                                   # noqa: E402
from h2o3_tpu.core import heartbeat           # noqa: E402
heartbeat.monitor.round()
time.sleep(0.1)
info = h2o3_tpu.cluster_info()
result = {
    "process_count": len({d.process_index
                          for d in jax.devices("cpu")}),
    "gbm_mse": float(gbm.training_metrics["MSE"]),
    "gbm_pred_head": [float(v) for v in gbm_pred[:16]],
    "glm_coefficients": {k: float(v) for k, v in glm.coefficients.items()},
    "cloud_healthy": info["cloud_healthy"],
    "heartbeat_running": info["heartbeat"]["running"],
    "peers_seen": sorted(int(p) for p in info["heartbeat"]["peers"]),
    "uptime_ms": info["cloud_uptime_ms"],
}

if int(pid) == 0:
    with open(outfile, "w") as f:
        json.dump(result, f)
print(f"WORKER-{pid}-DONE", flush=True)
# exercise the full teardown path on a REAL multi-process cloud:
# heartbeat stops, mesh resets, the distributed client disconnects
h2o3_tpu.shutdown()
