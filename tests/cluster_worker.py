"""Worker for the 2-process cluster-telemetry test (ISSUE 8 fan-in).

Each process forms the jax.distributed cloud, bumps a probe counter by
a node-distinct amount, closes a node-distinct span, logs a
node-distinct line, publishes its snapshot, and records its local
scrape for the parent to compare against the merged ``?cluster=1``
views. Process 0 additionally serves REST; the parent drives the
scrape-merge-kill-stale scenario over HTTP, then drops a stop file.
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
# fast cadence so the kill→stale transition happens inside the test
# (0.5s beats keep the peer-staleness window at 1.5s — wide enough that
# GIL/scheduler pauses on a busy CI host don't flap peers unhealthy)
os.environ.setdefault("H2O3TPU_HEARTBEAT_INTERVAL_S", "0.5")
os.environ.setdefault("H2O3TPU_CLUSTER_METRICS_INTERVAL_S", "0.2")
os.environ.setdefault("H2O3TPU_CLUSTER_METRICS_STALE_S", "2.0")

sys.path.insert(0,
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

coord, nproc, pid, workdir = sys.argv[1:5]
pid = int(pid)

import jax                                    # noqa: E402
jax.config.update("jax_default_device", None)

import h2o3_tpu                               # noqa: E402
h2o3_tpu.init(backend="cpu", coordinator_address=coord,
              num_processes=int(nproc), process_id=pid)

from h2o3_tpu import telemetry                # noqa: E402
from h2o3_tpu.telemetry import cluster        # noqa: E402
from h2o3_tpu.utils.log import get_logger     # noqa: E402

# node-distinct telemetry the parent asserts on in the merged views
telemetry.counter("cluster_probe_total").inc(100 * (pid + 1))
with telemetry.span(f"clw.node{pid}"):
    pass
get_logger("clw").warning("clw-log-node%d", pid)
assert cluster.publish(force=True), "snapshot publish failed"

with open(os.path.join(workdir, f"node{pid}.json"), "w") as f:
    json.dump({"node": pid,
               "probe": telemetry.REGISTRY.value("cluster_probe_total")},
              f)

STOP = os.path.join(workdir, "stop")
# the parent's one deadline bounds this process too (it must outlive
# every wait of the test, and not outlive a parent that died)
DEADLINE = time.time() + float(os.environ.get("H2O3TPU_MP_TIMEOUT_S", "300"))

if pid == 0:
    from h2o3_tpu.api.server import start_server
    port = start_server(port=0, background=True)
    with open(os.path.join(workdir, "port.txt"), "w") as f:
        f.write(str(port))
print(f"CLUSTER-WORKER-{pid}-READY", flush=True)

while time.time() < DEADLINE and not os.path.exists(STOP):
    time.sleep(0.05)

# the peer may already be SIGKILLed: a cooperative shutdown would wait
# on the dead coordination channel, so exit hard — KV-sweep-on-shutdown
# has its own single-process unit test (test_cluster_telemetry.py)
print(f"CLUSTER-WORKER-{pid}-DONE", flush=True)
os._exit(0)
