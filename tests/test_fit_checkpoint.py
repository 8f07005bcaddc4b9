"""ISSUE 9 — in-fit checkpointing, H2O-parity ``checkpoint=``
continuation, and the self-healing job supervisor.

Three legs, one contract (core/recovery.py FitCheckpointer +
core/job.py supervisor + models/{gbm,drf,deeplearning,glm}.py):

- in-fit snapshots at training-loop host boundaries; resume is
  **bit-identical** to an uninterrupted fit (asserted for GBM, DL, GLM
  via the ``fit_chunk`` fault-injection site, and for GBM again via a
  real SIGKILL in a subprocess);
- ``checkpoint=`` extends a donor model (GBM/DRF/XGBoost forests, DL
  epochs) with H2O-shaped validation errors for non-modifiable knobs;
- the job supervisor re-enters a fit from its snapshot on infra-class
  failures instead of restarting at round 0.

Satellites: corrupt-snapshot quarantine, orphan-tmp sweep, metric
wiring into flight-recorder capsules, the resume_automl snapshot-dir
read-count regression, and README knob/name documentation.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.core import config, recovery, watchdog
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.tree import Tree

WORKER = os.path.join(os.path.dirname(__file__), "fitckpt_worker.py")


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.setattr(config.ARGS, "infra_backoff_base_s", 0.001)
    monkeypatch.setattr(config.ARGS, "infra_backoff_max_s", 0.01)
    monkeypatch.delenv("H2O3TPU_FIT_CHECKPOINT_DIR", raising=False)
    monkeypatch.delenv("H2O3TPU_FIT_CHECKPOINT_EVERY", raising=False)
    monkeypatch.delenv("H2O3TPU_FIT_CHECKPOINT_HOLD_S", raising=False)
    yield
    watchdog.clear_faults()


def _classif_frame(n=2000, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, 5)
    yv = (X[:, 0] + 0.3 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(5)}
    cols["y"] = np.array(["a", "b"], object)[yv]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


def _forests_equal(a: Tree, b: Tree):
    for f in Tree._fields:
        av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert av.shape == bv.shape, (f, av.shape, bv.shape)
        assert np.array_equal(av, bv), f


# ------------------------------------------------- FitCheckpointer unit


def test_fit_checkpointer_roundtrip_and_cadence(tmp_path):
    fc = recovery.FitCheckpointer(str(tmp_path / "gbm_x.fitsnap"),
                                  "gbm", every=10)
    assert fc.load() is None                       # nothing yet
    assert not fc.maybe_save(5, lambda: {})        # below cadence
    assert fc.maybe_save(10, lambda: {"done": 10, "arr": np.arange(3)})
    assert not fc.maybe_save(15, lambda: {})       # 5 past last save
    assert fc.maybe_save(20, lambda: {"done": 20, "arr": np.arange(4)})
    unit, st = fc.load()
    assert unit == 20 and st["done"] == 20
    assert np.array_equal(st["arr"], np.arange(4))
    # atomic: no tmp debris after a completed save
    assert not os.path.exists(fc.path + ".tmp")
    fc.clear()
    assert fc.load() is None
    assert not os.path.exists(fc.path)


def test_corrupt_snapshot_quarantined(tmp_path):
    """Satellite: a bit-flipped snapshot is renamed *.corrupt, counted,
    and load returns None — never a crash, never a silent wrong model."""
    fc = recovery.FitCheckpointer(str(tmp_path / "gbm_y.fitsnap"),
                                  "gbm", every=1)
    fc.save(7, {"done": 7})
    with open(fc.path, "r+b") as f:
        f.seek(3)
        f.write(b"\xff\xff\xff")                   # bit flips
    c0 = telemetry.REGISTRY.total("snapshot_load_failures_total")
    assert fc.load() is None
    assert telemetry.REGISTRY.total("snapshot_load_failures_total") == c0 + 1
    names = os.listdir(tmp_path)
    assert any(n.endswith(".corrupt") for n in names), names
    assert not os.path.exists(fc.path)             # moved aside


# -------------------------------------- supervisor resume (fault inject)


def _multiclass_frame(n=2000, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, 5)
    yv = np.digitize(X[:, 0] + 0.3 * r.randn(n), [-0.5, 0.5])
    cols = {f"x{i}": X[:, i] for i in range(5)}
    cols["y"] = np.array(["a", "b", "c"], object)[yv]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


_STOP = dict(stopping_rounds=2, stopping_tolerance=0.0, score_tree_interval=5)
# one chunk loop and one snapshot format serve every kind of fit
# (models/gbm.py _run_chunks): what is scored, and so what rides in the
# snapshot, is all that differs
_FIT_KINDS = {
    "plain": (_classif_frame, None, {}),
    "stop_on_training": (_classif_frame, None, _STOP),
    "stop_on_validation": (_classif_frame, lambda: _classif_frame(700, 1),
                           _STOP),
    "multinomial": (_multiclass_frame, None, {}),
    "multinomial_stop_on_validation": (
        _multiclass_frame, lambda: _multiclass_frame(700, 1), _STOP),
}


@pytest.mark.parametrize("kind", list(_FIT_KINDS))
def test_gbm_infra_fault_resumes_bit_identical(tmp_path, kind):
    """Leg 2+3 acceptance (in-process): an infra-classed failure at the
    chunk boundary after the first snapshot makes the job supervisor
    re-enter the fit from the snapshot; forest, metrics and scoring
    history are bit-identical to an uninterrupted fit, with exactly one
    resume counted — and the counters land in the job's flight-recorder
    capsule."""
    mk_frame, mk_valid, extra = _FIT_KINDS[kind]
    fr = mk_frame()
    vf = mk_valid() if mk_valid else None
    kw = dict(ntrees=50, max_depth=3, seed=5, **extra)
    clean = GBMEstimator(**kw).train(fr, y="y", validation_frame=vf)
    watchdog.inject_fault("fit_chunk", times=1)
    r0 = telemetry.REGISTRY.total("fit_resumes_total")
    w0 = telemetry.REGISTRY.total("fit_checkpoints_written_total")
    b = GBMEstimator(**kw)
    with recovery.fit_checkpoint_scope(str(tmp_path)):
        m = b.train(fr, y="y", validation_frame=vf)
    assert telemetry.REGISTRY.total("fit_resumes_total") == r0 + 1
    assert telemetry.REGISTRY.total("fit_checkpoints_written_total") > w0
    _forests_equal(clean.forest, m.forest)
    assert np.array_equal(np.asarray(clean.f0), np.asarray(m.f0))
    assert clean.output["scoring_history"] == m.output["scoring_history"]
    assert clean.output["varimp"] == m.output["varimp"]
    assert float(clean.training_metrics["logloss"]) == \
        float(m.training_metrics["logloss"])
    # the snapshot was cleared on completion (dir may be gone entirely)
    assert not [f for f in (os.listdir(tmp_path)
                            if os.path.isdir(tmp_path) else [])
                if f.endswith(recovery.FIT_SUFFIX)]
    # capsule wiring: the job's counter deltas include the new metrics
    from h2o3_tpu.telemetry import flight_recorder
    cap = flight_recorder.get_capsule(b._job.key).to_dict()
    deltas = cap["metric_deltas"]
    assert any("fit_checkpoints_written_total" in k for k in deltas), deltas
    assert any("fit_resumes_total" in k for k in deltas)


def _write_garbage(path):
    with open(path, "wb") as f:
        f.write(b"\x80\x04 definitely not a fit snapshot")


def _write_without_margin(path):
    """A well-formed snapshot of the right algo and version whose state
    lacks a field every resuming GBM fit reads (as one written by an
    older program would)."""
    recovery.FitCheckpointer(path, "gbm", every=1).save(
        25, {"done": 25, "trees": None,
             "gains_total": np.zeros(5, np.float32)})


@pytest.mark.parametrize("write", [_write_garbage, _write_without_margin],
                         ids=["garbage", "lacks_a_field"])
def test_gbm_unusable_snapshot_quarantined(tmp_path, write):
    """Fit-level quarantine: garbage at the fit's own snapshot path, or
    a readable snapshot that lacks what the fit needs → moved aside,
    counted, restart from round 0 with the same model as a clean run —
    no resume counted, never a half-resumed fit."""
    fr = _classif_frame()
    kw = dict(ntrees=30, max_depth=3, seed=5, **_STOP)
    clean = GBMEstimator(**kw).train(fr, y="y")
    b = GBMEstimator(**kw)
    probe = recovery._fit_fingerprint("gbm", b.params, "y",
                                      clean.output["names"], fr.nrows)
    write(os.path.join(str(tmp_path), f"gbm_{probe}{recovery.FIT_SUFFIX}"))
    r0 = telemetry.REGISTRY.total("fit_resumes_total")
    c0 = telemetry.REGISTRY.total("snapshot_load_failures_total")
    with recovery.fit_checkpoint_scope(str(tmp_path)):
        m = b.train(fr, y="y")
    assert telemetry.REGISTRY.total("fit_resumes_total") == r0
    assert telemetry.REGISTRY.total("snapshot_load_failures_total") == c0 + 1
    assert any(n.endswith(".corrupt") for n in os.listdir(tmp_path))
    _forests_equal(clean.forest, m.forest)
    assert clean.output["scoring_history"] == m.output["scoring_history"]


def test_deeplearning_infra_fault_resumes_bit_identical(tmp_path,
                                                        monkeypatch):
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    fr = _classif_frame()
    kw = dict(hidden=[8], epochs=30, seed=3, stopping_rounds=2)
    clean = DeepLearningEstimator(**kw).train(fr, y="y")
    monkeypatch.setenv("H2O3TPU_FIT_CHECKPOINT_EVERY", "200")
    watchdog.inject_fault("fit_chunk", times=1)
    r0 = telemetry.REGISTRY.total("fit_resumes_total")
    with recovery.fit_checkpoint_scope(str(tmp_path)):
        m = DeepLearningEstimator(**kw).train(fr, y="y")
    assert telemetry.REGISTRY.total("fit_resumes_total") == r0 + 1
    for a, b in zip(clean.net, m.net):
        assert np.array_equal(np.asarray(a["W"]), np.asarray(b["W"]))
        assert np.array_equal(np.asarray(a["b"]), np.asarray(b["b"]))
    assert clean.output["scoring_history"] == m.output["scoring_history"]


def test_glm_infra_fault_resumes_bit_identical(tmp_path):
    from h2o3_tpu.models.glm import GLMEstimator
    fr = _classif_frame()
    kw = dict(family="binomial", lambda_=[0.05, 0.01, 0.001],
              solver="l_bfgs", max_iterations=20)
    clean = GLMEstimator(**kw).train(fr, y="y")
    watchdog.inject_fault("fit_chunk", times=1)
    r0 = telemetry.REGISTRY.total("fit_resumes_total")
    with recovery.fit_checkpoint_scope(str(tmp_path)):
        m = GLMEstimator(**kw).train(fr, y="y")
    assert telemetry.REGISTRY.total("fit_resumes_total") == r0 + 1
    assert np.array_equal(np.asarray(clean.coef), np.asarray(m.coef))


# ------------------------------------------- H2O-parity checkpoint=


def test_gbm_checkpoint_extends_prefix_bit_equal():
    """Acceptance: checkpoint= extends ntrees with the first N trees
    bit-equal to the donor; incompatible knobs raise H2O-shaped errors."""
    fr = _classif_frame()
    part = GBMEstimator(ntrees=25, max_depth=3, seed=5,
                        sample_rate=1.0).train(fr, y="y")
    res = GBMEstimator(ntrees=50, max_depth=3, seed=5, sample_rate=1.0,
                       checkpoint=part.key).train(fr, y="y")
    assert res.forest.feat.shape[0] == 50
    for f in Tree._fields:
        assert np.array_equal(np.asarray(getattr(part.forest, f)),
                              np.asarray(getattr(res.forest, f))[:25]), f
    # non-modifiable knobs → reference error shape
    for knob, val in (("max_depth", 5), ("nbins", 32),
                      ("sample_rate", 0.7), ("min_rows", 5.0)):
        kw = dict(ntrees=50, seed=5, sample_rate=1.0, max_depth=3,
                  checkpoint=part.key)
        kw[knob] = val
        with pytest.raises(ValueError) as ei:
            GBMEstimator(**kw).train(fr, y="y")
        msg = str(ei.value)
        assert f"ERRR on field: _{knob}" in msg, msg
        assert "cannot be modified if checkpoint is provided" in msg
    # ntrees must exceed the donor's
    with pytest.raises(ValueError, match="must exceed"):
        GBMEstimator(ntrees=25, max_depth=3, seed=5, sample_rate=1.0,
                     checkpoint=part.key).train(fr, y="y")


def test_drf_checkpoint_extends_bit_equal_to_longer_run():
    """DRF continues the bagging PRNG chain AND the OOB accumulators:
    4 + checkpoint-to-10 is bit-equal to a single 10-tree run, metrics
    included."""
    from h2o3_tpu.models.drf import DRFEstimator
    fr = _classif_frame()
    full = DRFEstimator(ntrees=8, max_depth=4, seed=5).train(fr, y="y")
    part = DRFEstimator(ntrees=4, max_depth=4, seed=5).train(fr, y="y")
    res = DRFEstimator(ntrees=8, max_depth=4, seed=5,
                       checkpoint=part.key).train(fr, y="y")
    _forests_equal(full.forest, res.forest)
    assert float(full.training_metrics["AUC"]) == \
        pytest.approx(float(res.training_metrics["AUC"]), abs=1e-9)
    with pytest.raises(ValueError, match="ERRR on field: _mtries"):
        DRFEstimator(ntrees=8, max_depth=4, seed=5, mtries=2,
                     checkpoint=part.key).train(fr, y="y")


def test_xgboost_facade_checkpoint_forwards():
    from h2o3_tpu.models.xgboost import XGBoostEstimator
    fr = _classif_frame()
    part = XGBoostEstimator(ntrees=25, max_depth=3, seed=5).train(fr, y="y")
    res = XGBoostEstimator(ntrees=50, max_depth=3, seed=5,
                           checkpoint=part.key).train(fr, y="y")
    assert res.forest.feat.shape[0] == 50
    for f in Tree._fields:
        assert np.array_equal(np.asarray(getattr(part.forest, f)),
                              np.asarray(getattr(res.forest, f))[:25]), f


def test_dl_checkpoint_continues_epochs_and_optimizer():
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    fr = _classif_frame()
    part = DeepLearningEstimator(hidden=[8], epochs=1, seed=3).train(
        fr, y="y")
    assert part._steps_trained > 0
    # ADADELTA accumulators are live on the donor (restorable state)
    assert float(np.abs(part._opt_state[0]["W"]["eg2"]).sum()) > 0
    cont = DeepLearningEstimator(hidden=[8], epochs=2, seed=3,
                                 checkpoint=part.key).train(fr, y="y")
    assert cont._steps_trained > part._steps_trained
    # continuation differs from a cold 2-epoch run ONLY via restored
    # state; it must differ from the donor (it actually trained more)
    assert not np.array_equal(np.asarray(part.net[0]["W"]),
                              np.asarray(cont.net[0]["W"]))


def test_checkpoint_combo_is_batch_ineligible():
    """Grid leg: a checkpointed combo must never enter the vmapped
    batch path — per-combo fallback preserves donor semantics."""
    from h2o3_tpu.parallel import model_batch
    with pytest.raises(model_batch.BatchIneligible, match="checkpoint"):
        model_batch.train_bucket(
            GBMEstimator, {"checkpoint": "model_gbm_donor"},
            [{"learn_rate": 0.1}, {"learn_rate": 0.2}], None, y="y")


# ---------------------------------------- recovery_dir composition


def test_grid_recovery_resumes_inside_combo(tmp_path, monkeypatch):
    """A combo whose fit died mid-way (snapshot left under
    <recovery_dir>/fit_state) resumes INSIDE the combo when the grid
    walk re-reaches it — not at tree 0."""
    from h2o3_tpu.ml.grid import GridSearch
    d = str(tmp_path / "rec")
    fr = _classif_frame()
    fixed = dict(ntrees=50, max_depth=3, seed=7)
    combos = {"learn_rate": [0.1, 0.2]}
    # reference: the clean 0.2-combo model
    clean = GBMEstimator(**{**fixed, "learn_rate": 0.2}).train(fr, y="y")
    # simulate the kill: run the 0.2 combo under the grid's fit_state
    # scope with retries disabled — the fit dies after its first
    # snapshot, which SURVIVES (the walk never completed)
    monkeypatch.setattr(config.ARGS, "infra_max_attempts", 1)
    watchdog.inject_fault("fit_chunk", times=1)
    with recovery.fit_checkpoint_scope(os.path.join(d, "fit_state")):
        with pytest.raises(Exception):
            GBMEstimator(**{**fixed, "learn_rate": 0.2}).train(fr, y="y")
    snaps = os.listdir(os.path.join(d, "fit_state"))
    assert any(f.endswith(recovery.FIT_SUFFIX) for f in snaps), snaps
    monkeypatch.setattr(config.ARGS, "infra_max_attempts", 3)
    # the resumed walk: sequential (batching off isolates the combo
    # path), recovery_dir composes the fit_state scope automatically
    monkeypatch.setenv("H2O3TPU_BATCH_MODELS", "off")
    r0 = telemetry.REGISTRY.total("fit_resumes_total")
    g = GridSearch(GBMEstimator, combos, recovery_dir=d,
                   **fixed).train(fr, y="y")
    assert telemetry.REGISTRY.total("fit_resumes_total") == r0 + 1
    assert len(g.models) == 2
    resumed = next(m for m in g.models
                   if m.output["grid_params"] == {"learn_rate": 0.2})
    _forests_equal(clean.forest, resumed.forest)
    # the completed walk swept its fit_state snapshots
    assert not os.path.exists(os.path.join(d, "fit_state")) or \
        not os.listdir(os.path.join(d, "fit_state"))


# -------------------------------------------------- satellite sweeps


def test_sweep_orphaned_fit_tmp_and_partial_dirs(tmp_path):
    """Satellite: shutdown()/conftest sweep removes *.tmp debris a kill
    left behind and prunes empty partial snapshot dirs; completed
    snapshots stay (they are resumable state)."""
    d = str(tmp_path / "ck")
    fc = recovery.FitCheckpointer(os.path.join(d, "gbm_z.fitsnap"),
                                  "gbm", 1)
    fc.save(1, {"done": 1})
    with open(fc.path + ".tmp", "wb") as f:     # orphaned tmp (torn kill)
        f.write(b"torn write")
    removed = recovery.sweep_fit_checkpoints()
    assert removed >= 1
    assert not os.path.exists(fc.path + ".tmp")
    assert os.path.exists(fc.path)              # real snapshot untouched
    fc.clear()
    # dir now empty → pruned by the next sweep
    recovery.sweep_fit_checkpoints()
    assert not os.path.exists(d)


def test_resume_automl_snapshot_dir_read_counts(tmp_path, monkeypatch):
    """Satellite regression: step-completion snapshots read each nested
    snapshot dir ONCE (one os.listdir) instead of one os.path.exists
    per model — the pre-fix behavior re-stat'ed the leaderboard dir on
    every step snapshot."""
    from h2o3_tpu.automl import H2OAutoML
    d = str(tmp_path / "rec")
    aml = H2OAutoML(max_models=4, recovery_dir=d, nfolds=0)
    step = "GBM_grid_1"
    os.makedirs(os.path.join(d, step))
    keys = [f"model_gbm_fake{i}" for i in range(6)]
    for k in keys:
        with open(os.path.join(d, step, f"{k}.bin"), "wb") as f:
            f.write(b"x")

    class _FakeModel:
        def __init__(self, key):
            self.key = key

    listdir_calls = []
    exists_calls = []
    real_listdir = os.listdir
    import h2o3_tpu.automl as automl_mod

    def counting_listdir(p):
        listdir_calls.append(p)
        return real_listdir(p)

    real_exists = os.path.exists

    def counting_exists(p):
        exists_calls.append(p)
        return real_exists(p)

    monkeypatch.setattr(automl_mod.os, "listdir", counting_listdir)
    monkeypatch.setattr(automl_mod.os.path, "exists", counting_exists)
    models = [_FakeModel(k) for k in keys]
    aml._on_step_done(step, models, "y", None)
    aml._on_step_done(step, models, "y", None)   # second snapshot: cached
    sub = os.path.join(d, step)
    assert listdir_calls.count(sub) == 1, listdir_calls
    assert not [p for p in exists_calls if p.startswith(sub)], exists_calls
    # and the state recorded the nested snapshot paths, not fresh saves
    state = json.load(open(os.path.join(d, "automl_state.json")))
    assert sorted(state["models"][step]) == \
        sorted(f"{step}/{k}.bin" for k in keys)


def test_readme_documents_checkpoint_contract():
    """Satellite: README §Fault tolerance names the knobs, the
    bit-identity guarantee, and the supervisor decision table."""
    readme = os.path.join(os.path.dirname(__file__), "..", "README.md")
    with open(readme) as f:
        text = f.read()
    lo = text.index("## Fault tolerance")
    section = text[lo:text.index("\n## ", lo + 1)]
    for needle in ("H2O3TPU_FIT_CHECKPOINT_DIR",
                   "H2O3TPU_FIT_CHECKPOINT_EVERY",
                   "bit-identical", "checkpoint=", "fail fast",
                   "re-enter fit from snapshot", "*.corrupt"):
        assert needle in section, needle


# --------------------------------------- SIGKILL-mid-GBM (acceptance)


@pytest.mark.multiprocess
@pytest.mark.allow_key_leak
def test_sigkill_mid_gbm_fit_resumes_bit_identical(tmp_path):
    """Acceptance: SIGKILL a worker mid-GBM-fit (inside the chunk
    boundary right after its first in-fit snapshot); re-running the fit
    in a fresh process resumes from the snapshot and produces a
    bit-identical forest, metrics, and scoring history vs. an
    uninterrupted reference fit, with fit_resumes_total == 1."""
    ck = str(tmp_path / "ck")
    out_npz = str(tmp_path / "out.npz")
    env = dict(os.environ)
    for k in ("JAX_PLATFORMS", "H2O3TPU_FIT_CHECKPOINT_DIR",
              "H2O3TPU_FIT_CHECKPOINT_EVERY",
              "H2O3TPU_FIT_CHECKPOINT_HOLD_S"):
        env.pop(k, None)

    # the fit run holds inside the chunk boundary after its first
    # snapshot (H2O3TPU_FIT_CHECKPOINT_HOLD_S in the worker) — the kill
    # deterministically lands MID-FIT
    proc = subprocess.Popen([sys.executable, WORKER, "fit", ck,
                             str(tmp_path / "never.npz")], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 180
    killed = False
    try:
        while time.time() < deadline:
            if proc.poll() is not None:
                break
            if os.path.isdir(ck) and any(
                    f.endswith(recovery.FIT_SUFFIX)
                    for f in os.listdir(ck)):
                os.kill(proc.pid, signal.SIGKILL)
                killed = True
                break
            time.sleep(0.05)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert killed, (f"worker finished (or never snapshotted) before the "
                    f"kill; rc={proc.returncode}")
    assert any(f.endswith(recovery.FIT_SUFFIX) for f in os.listdir(ck))

    # fresh process: the resumed fit first, then the uninterrupted
    # reference on the same 1-device mesh (one session, shared compiles)
    p = subprocess.run([sys.executable, WORKER, "resume", ck, out_npz],
                       env=env, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode == 0, p.stdout + p.stderr
    out = np.load(out_npz)
    assert float(out["fit_resumes_total"]) == 1.0
    # the reference fit never resumed (the completed resume cleared it)
    assert float(out["fit_resumes_total_after_ref"]) == 1.0
    assert float(out["snapshot_left"]) == 0.0
    for f in Tree._fields + ("f0", "hist_ntrees", "hist_deviance"):
        assert np.array_equal(out["ref_" + f], out["res_" + f]), f
    assert float(out["ref_logloss"]) == float(out["res_logloss"])
    assert float(out["ref_auc"]) == float(out["res_auc"])
