"""Worker for the fleet serving-resilience multiprocess tests
(ISSUE 17, serving/fleet.py).

Every process runs this same script (the SPMD contract): forms a
2-process jax.distributed CPU cloud, trains one GBM, then exercises the
replica registry + health-routed predictions. Modes (argv[5]):

- ``serve`` — process 0 publishes the model's device-independent binary
  and serves a warm replica; process 1 (which holds NO local copy)
  drives concurrent row-payload predicts through its OWN REST edge —
  node symmetry: the fleet router proxies every request to the replica
  and the answers must be bit-identical to ``Model.predict``.
- ``kill`` — process 1 is the only replica; process 0 proxies a load
  through it, then SIGKILLs it mid-stream (via the ``.killflag`` file).
  The survivor must hedge the burst to a local install of the published
  binary (bounded errors, answers still bit-identical), see the dead
  peer excluded within one heartbeat staleness window, and drain clean.

Each surviving process writes ``outfile.<pid>`` with its predictions,
routing counters, and fleet stats (full-precision floats via json).
"""

import json
import os
import signal
import sys
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
# fast dead-peer detection for the kill leg (staleness = interval * 3)
os.environ["H2O3TPU_HEARTBEAT_INTERVAL_S"] = "0.25"
# fresh load reads + quick adoption during the short test window
os.environ["H2O3TPU_FLEET_LOAD_TTL_S"] = "0.2"
os.environ["H2O3TPU_FLEET_ADOPT_S"] = "0.5"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

coord, nproc, pid, outfile, mode = sys.argv[1:6]
nproc, pid = int(nproc), int(pid)

import jax                                    # noqa: E402
jax.config.update("jax_default_device", None)

import h2o3_tpu                               # noqa: E402
h2o3_tpu.init(backend="cpu", coordinator_address=coord,
              num_processes=nproc, process_id=pid)

import numpy as np                            # noqa: E402

from h2o3_tpu import telemetry                # noqa: E402
from h2o3_tpu.core.kv import DKV              # noqa: E402
from h2o3_tpu.serving import fleet            # noqa: E402
from h2o3_tpu.serving.rows import serving_schema   # noqa: E402

N_ROWS = 2000
N_PAYLOAD = 16


def build_data():
    r = np.random.RandomState(17)
    a = r.randn(N_ROWS)
    b = r.randn(N_ROWS)
    g = r.choice(["u", "v", "w"], N_ROWS)
    y = 2.0 * a - b + (g == "u") * 1.5 + r.randn(N_ROWS) * 0.3
    return h2o3_tpu.Frame.from_numpy(
        {"a": a, "b": b, "g": g, "y": y}, categorical=["g"])


def rows_of(model, fr, hi):
    """JSON-shaped payloads reproducing fr[:hi] exactly (the
    tests/test_serving.py _rows_of idiom, numerics + categoricals)."""
    schema = serving_schema(model)
    cache = {nm: fr.col(nm).to_numpy() for nm, _ in schema
             if nm in fr.names}
    rows = []
    for i in range(hi):
        r = {}
        for nm, dom in schema:
            if nm not in cache:
                continue
            v = float(cache[nm][i])
            if np.isnan(v):
                r[nm] = None
            elif dom is not None:
                r[nm] = dom[int(v)]
            else:
                r[nm] = v
        rows.append(r)
    return rows


fr = build_data()

from h2o3_tpu.models.gbm import GBMEstimator  # noqa: E402

model = GBMEstimator(ntrees=3, max_depth=3, seed=7).train(fr, y="y")
MKEY = str(model.key)

# the bit-parity reference: Model.predict on the SAME rows, computed
# SPMD (both processes participate) BEFORE any replica moves
base = model.predict(fr).col("predict").to_numpy()
REF = [float(v) for v in base[:N_PAYLOAD]]
ROWS = rows_of(model, fr, N_PAYLOAD)

from h2o3_tpu.api.server import start_server  # noqa: E402

port = start_server(port=0, background=True)


def post_rows(to_port, timeout=15.0):
    """One row-payload predict; returns (status, predictions|msg)."""
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{to_port}/3/Predictions/models/{MKEY}",
        data=json.dumps({"rows": ROWS}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"retry_after": e.headers.get("Retry-After"),
                        "body": e.read().decode("utf-8", "replace")[:300]}
    except Exception as e:   # noqa: BLE001 - connection refused etc.
        return -1, {"error": f"{type(e).__name__}: {e}"}


def drive(n, threads):
    """n predicts against OUR edge across `threads` workers; returns
    (ok_preds, errors) — every 200's predict column, every non-200."""
    ok, errors, lock = [], [], threading.Lock()

    def _one():
        code, out = post_rows(port)
        with lock:
            if code == 200:
                ok.append([float(v) for v in out["predictions"]["predict"]])
            else:
                errors.append({"code": code, "out": out})

    for lo in range(0, n, threads):
        ts = [threading.Thread(target=_one)
              for _ in range(min(threads, n - lo))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    return ok, errors


def wait_for(cond, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    raise RuntimeError(f"timed out waiting for {what}")


def routed_counters():
    return {d: telemetry.REGISTRY.value("predict_routed_total", decision=d)
            for d in ("local", "proxy", "redirect", "install", "none")}


def failover_counters():
    return {r: telemetry.REGISTRY.value("predict_failovers_total", reason=r)
            for r in ("connection", "timeout", "http_5xx", "error")}


result = {"pid": pid, "ref": REF, "port": port}

# Publish is an SPMD point on a live cloud (the device-lowering pickle
# allgathers any cross-process sharded array), so BOTH processes call
# it here — only then does ownership diverge per mode.
fleet.publish(model)

if mode == "serve":
    if pid == 0:
        # the replica host: serve from an INSTALLED copy of the
        # published binary (the exact path an adopting peer runs —
        # numpy constants, engine pre-warmed)
        DKV.remove(MKEY)
        fleet.install_published(MKEY)
        # hold until the client banked its result (the coordination
        # service lives here); then drain through normal shutdown
        wait_for(lambda: os.path.exists(f"{outfile}.1"), 120,
                 "client outfile")
        result["replicas"] = sorted(fleet.replicas(MKEY))
        result["stats"] = fleet.stats()
    else:
        # the routing-only node: NO local copy — node symmetry says its
        # REST edge must still answer, via the fleet
        DKV.remove(MKEY)
        wait_for(lambda: 0 in fleet.replicas(MKEY)
                 and 0 in fleet.endpoints(), 60, "replica 0 in registry")
        ok, errors = drive(32, threads=4)
        result.update({
            "n_ok": len(ok), "errors": errors,
            "preds": ok[-1] if ok else None,
            "all_identical": all(p == REF for p in ok),
            "routed": routed_counters(),
        })
    with open(f"{outfile}.{pid}", "w") as f:
        json.dump(result, f)
    print(f"FLEET-WORKER-{pid}-DONE", flush=True)
    h2o3_tpu.shutdown()
    sys.exit(0)

# ---- kill mode ----

killflag = f"{outfile}.killflag"

if pid == 1:
    # the ONLY replica: serve until process 0 raises the kill flag,
    # then die without warning
    DKV.remove(MKEY)
    fleet.install_published(MKEY)
    while not os.path.exists(killflag):
        time.sleep(0.05)
    os.kill(os.getpid(), signal.SIGKILL)

# pid 0: routes everything through the doomed replica
DKV.remove(MKEY)
wait_for(lambda: 1 in fleet.replicas(MKEY) and 1 in fleet.endpoints(),
         60, "replica 1 in registry")

# phase A — steady state: every predict proxies to the replica
ok_a, err_a = drive(12, threads=3)

# phase B — SIGKILL the replica mid-stream; hedged failover must bound
# the burst by falling back to a local install of the published binary
with open(killflag, "w") as f:
    f.write("die")
t_kill = time.monotonic()
ok_b, err_b = drive(40, threads=4)

# the heartbeat must exclude the dead peer within one staleness window
wait_for(lambda: 1 in fleet._dead_set(), 15, "dead-peer exclusion")
t_detect = time.monotonic() - t_kill

# phase C — post-exclusion: routing never offers the dead peer again
ok_c, err_c = drive(6, threads=2)

result.update({
    "phase_a": {"n_ok": len(ok_a), "errors": err_a,
                "identical": all(p == REF for p in ok_a)},
    "phase_b": {"n_ok": len(ok_b), "errors": err_b,
                "identical": all(p == REF for p in ok_b)},
    "phase_c": {"n_ok": len(ok_c), "errors": err_c,
                "identical": all(p == REF for p in ok_c)},
    "detect_s": t_detect,
    "hb_window_s": (float(os.environ["H2O3TPU_HEARTBEAT_INTERVAL_S"])
                    * 3),
    "routed": routed_counters(),
    "failovers": failover_counters(),
    "local_replica_after": MKEY in fleet.stats()["local_replicas"],
})

# the survivor drains clean: replicas deregistered, engine emptied,
# registry marked draining — queued work would 503, nothing hangs
fleet.drain()
result["stats_after_drain"] = fleet.stats()
from h2o3_tpu.serving.engine import engine    # noqa: E402
result["engine_warm_after_drain"] = engine.warm_models()

with open(f"{outfile}.{pid}", "w") as f:
    json.dump(result, f)
print(f"FLEET-WORKER-{pid}-DONE", flush=True)
# peer 1 is dead: the distributed-shutdown barrier would wait forever —
# results are on disk, leave hard (the sched_worker kill-leg contract)
os._exit(0)
