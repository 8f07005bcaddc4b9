"""Worker for the SIGKILL-mid-GBM fit-checkpoint test
(tests/test_fit_checkpoint.py; pattern of tests/ft_worker.py).

Modes (argv[1]):
  fit     — GBM fit with in-fit checkpointing into argv[2]; the parent
            SIGKILLs this process while it holds inside the chunk
            boundary right after its first snapshot
            (H2O3TPU_FIT_CHECKPOINT_HOLD_S widens the kill window)
  resume  — the same fit again with the same checkpoint dir: it must
            resume from the snapshot the killed run left, THEN train
            the uninterrupted reference fit in the same (1-device)
            session; both results dump to argv[3] with ref_/res_
            prefixes plus the resume counters

Deterministic data: build_data() must stay identical across modes (the
resumed "cluster" trains on the same frame a restarted driver would
re-import).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

mode = sys.argv[1]
ckpt_dir = sys.argv[2]
out_path = sys.argv[3]

os.environ["H2O3TPU_FIT_CHECKPOINT_DIR"] = ckpt_dir
os.environ["H2O3TPU_FIT_CHECKPOINT_EVERY"] = "25"
if mode == "fit":
    os.environ["H2O3TPU_FIT_CHECKPOINT_HOLD_S"] = "600"

import numpy as np                            # noqa: E402

import h2o3_tpu                               # noqa: E402

h2o3_tpu.init(backend="cpu")


def build_data():
    r = np.random.RandomState(23)
    n = 4000
    X = r.randn(n, 6)
    logits = X[:, 0] * 1.2 - X[:, 1] + 0.4 * X[:, 2]
    y = (r.rand(n) < 1 / (1 + np.exp(-logits))).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(6)}
    cols["y"] = np.array(["no", "yes"], dtype=object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


from h2o3_tpu import telemetry                # noqa: E402
from h2o3_tpu.models.gbm import GBMEstimator  # noqa: E402
from h2o3_tpu.models.tree import Tree         # noqa: E402

fr = build_data()


def train_once():
    # scored path (early stopping on, never binding at tol=0):
    # exercises scoring history + stopper state through the snapshot
    return GBMEstimator(ntrees=50, max_depth=3, seed=5,
                        stopping_rounds=2, stopping_tolerance=0.0,
                        score_tree_interval=5).train(fr, y="y")


def dump(prefix, model, out):
    for f in Tree._fields:
        out[prefix + f] = np.asarray(getattr(model.forest, f))
    out[prefix + "f0"] = np.asarray(model.f0)
    hist = model.output["scoring_history"]
    out[prefix + "hist_ntrees"] = np.asarray([h["ntrees"] for h in hist])
    out[prefix + "hist_deviance"] = np.asarray(
        [h["deviance"] for h in hist])
    out[prefix + "logloss"] = np.float64(
        model.training_metrics["logloss"])
    out[prefix + "auc"] = np.float64(model.training_metrics["AUC"])


if mode == "fit":
    train_once()                               # parent kills mid-fit
    print("FITCKPT-WORKER-DONE fit", flush=True)
    sys.exit(0)

# mode == "resume": the resumed fit FIRST (the killed run's snapshot is
# live), then — its completion cleared the snapshot — the uninterrupted
# reference on the same 1-device mesh
out = {}
resumed = train_once()
out["fit_resumes_total"] = np.float64(
    telemetry.REGISTRY.total("fit_resumes_total"))
out["fit_checkpoints_written_total"] = np.float64(
    telemetry.REGISTRY.total("fit_checkpoints_written_total"))
out["snapshot_left"] = np.float64(sum(
    f.endswith(".fitsnap") for f in os.listdir(ckpt_dir)))
dump("res_", resumed, out)
reference = train_once()
out["fit_resumes_total_after_ref"] = np.float64(
    telemetry.REGISTRY.total("fit_resumes_total"))
dump("ref_", reference, out)
np.savez(out_path, **out)
print("FITCKPT-WORKER-DONE resume", flush=True)
