"""AutoML — step-provider modeling plan + budgeted execution.

Reference: ai/h2o/automl/AutoML.java:49 — planWork (AutoML.java:420)
allocates a budget across modeling steps from ModelingStepsProviders
(modeling/{XGBoost,GLM,GBM,DRF,DeepLearning,StackedEnsemble}
StepsProvider), learn (AutoML.java:760) executes defaults → grids →
exploitation under max_models / max_runtime_secs with per-model caps,
every model cross-validated, results ranked in
hex.leaderboard.Leaderboard, StackedEnsembles last; optional
TargetEncoding preprocessing (ai/h2o/automl/preprocessing/
TargetEncoding.java) for tree algos on high-cardinality categoricals.

The step plan lives in automl/steps.py; budget/per-model-cap
enforcement in automl/executor.py.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional, Sequence

from h2o3_tpu.automl.executor import Budget, run_step, train_capped
from h2o3_tpu.automl.steps import modeling_plan
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.ml.ensemble import StackedEnsembleEstimator
from h2o3_tpu.ml.leaderboard import Leaderboard
from h2o3_tpu.models import get_builder
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.automl")


class H2OAutoML:
    """h2o-py H2OAutoML-compatible surface (h2o-py/h2o/automl/).

    ``keep_cross_validation_predictions`` is effectively always True here
    (holdouts are kept in-memory for stacking); ``balance_classes`` is not
    implemented and warns if set; ``verbosity`` only affects logging.
    """

    def __init__(self, max_models: int = 0, max_runtime_secs: float = 3600.0,
                 seed: int = -1, nfolds: int = 5,
                 project_name: Optional[str] = None,
                 sort_metric: Optional[str] = None,
                 include_algos: Optional[Sequence[str]] = None,
                 exclude_algos: Optional[Sequence[str]] = None,
                 stopping_rounds: int = 3, stopping_tolerance: float = 1e-3,
                 keep_cross_validation_predictions: bool = True,
                 verbosity: str = "warn", balance_classes: bool = False,
                 max_runtime_secs_per_model: float = 0.0,
                 preprocessing: Optional[Sequence[str]] = None,
                 recovery_dir: Optional[str] = None):
        self.max_models = int(max_models)
        self.max_runtime_secs = float(max_runtime_secs)
        self.seed = int(seed) if int(seed) >= 0 else 5723
        # h2o-py sends nfolds=-1 for "auto" (H2OAutoML default since
        # 3.46); the reference resolves it to 5-fold CV (AutoML.java
        # nfolds default) — builders reject a literal -1
        self.nfolds = 5 if int(nfolds) == -1 else int(nfolds)
        self.project_name = project_name or f"automl_{int(time.time())}"
        self.sort_metric = sort_metric
        self.include = ({a.lower() for a in include_algos}
                        if include_algos else None)
        self.exclude = {a.lower() for a in (exclude_algos or ())}
        self.leaderboard_obj = Leaderboard(self.project_name, sort_metric)
        self.stopping_rounds = int(stopping_rounds)
        self.stopping_tolerance = float(stopping_tolerance)
        self.max_runtime_secs_per_model = float(max_runtime_secs_per_model)
        self.preprocessing = list(preprocessing or [])
        self.event_log: List[dict] = []
        # hex/faulttolerance/Recovery.java role for AutoML: when set,
        # every trained model + per-step walk state snapshot to this dir
        # so resume_automl() can continue after a crash (core/recovery.py)
        self.recovery_dir = recovery_dir
        self._recovery = None
        if recovery_dir:
            from h2o3_tpu.core.recovery import Recovery
            self._recovery = Recovery(recovery_dir,
                                      state_name="automl_state")
        self._skip_steps: set = set()       # step ids done pre-crash
        self._prior_models: List = []       # models restored on resume
        self._step_models: dict = {}        # step id -> snapshot files
        # snapshot-dir listing cache: each nested grid-step dir is read
        # ONCE per run (one os.listdir), never one os.path.exists per
        # model per step snapshot — resume_automl on a wide leaderboard
        # paid a filesystem stat per restored model per step
        self._snapshot_listing: dict = {}   # step id -> {relative paths}
        if balance_classes:
            log.warning("balance_classes is not implemented; ignoring")

    # -- helpers -------------------------------------------------------
    def _allowed(self, algo: str) -> bool:
        a = algo.lower()
        if self.include is not None and a not in self.include:
            return False
        return a not in self.exclude

    @property
    def leader(self):
        return self.leaderboard_obj.leader

    @property
    def leaderboard(self):
        return self.leaderboard_obj

    def predict(self, frame: Frame) -> Frame:
        if getattr(self, "_te_model", None) is not None:
            # models trained on target-encoded columns; encode the
            # scoring frame the same way (TargetEncoding preprocessing)
            frame = self._te_model.transform(frame)
        return self.leader.predict(frame)

    # -- train ---------------------------------------------------------
    def _maybe_target_encode(self, frame: Frame, y: str, x):
        """Optional TargetEncoding preprocessing for tree algos
        (ai/h2o/automl/preprocessing/TargetEncoding.java): encode
        categorical predictors with cardinality >= 25 using kfold-safe
        encodings; returns (encoded_frame, te_model) or (frame, None)."""
        if "target_encoding" not in self.preprocessing:
            return frame, None
        high_card = [n for n in (x or frame.names)
                     if n != y and frame.col(n).is_categorical
                     and frame.col(n).cardinality >= 25]
        if not high_card:
            return frame, None
        from h2o3_tpu.models.targetencoder import TargetEncoderEstimator
        te = TargetEncoderEstimator(
            data_leakage_handling="loo", noise=0.01,
            blending=True, seed=self.seed).train(frame, y=y, x=high_card)
        enc = te.transform(frame, as_training=True)
        self._log_event("preprocessing", f"target-encoded {high_card}")
        return enc, te

    def _log_event(self, stage: str, message: str):
        self.event_log.append({"timestamp": time.time(), "stage": stage,
                               "message": message})
        log.info("automl[%s]: %s", stage, message)

    def _lr_annealing_step(self, budget, training_frame, y, x):
        """Exploitation (GBMStepsProvider lr_annealing): retrain the best
        GBM so far with more trees and an annealed learn rate."""
        best_gbm = next((m for m in self.leaderboard_obj.sorted_models()
                         if m.algo == "gbm"), None)
        if best_gbm is None:
            return None
        params = {k: v for k, v in best_gbm.params.items()
                  if k in get_builder("gbm").accepted_params()}
        params.update(ntrees=max(int(params.get("ntrees", 50) * 2), 100),
                      learn_rate=float(params.get("learn_rate", 0.1)) * 0.5,
                      stopping_rounds=3, nfolds=self.nfolds)
        return train_capped(get_builder("gbm")(**params),
                            training_frame, y, x, budget)

    # -- fault tolerance (core/recovery.py; resume_automl below) -------
    def _recovery_params(self) -> dict:
        """Ctor kwargs, JSON-shaped, sufficient to rebuild this run."""
        return {
            "max_models": self.max_models,
            "max_runtime_secs": self.max_runtime_secs,
            "seed": self.seed,
            "nfolds": self.nfolds,
            "project_name": self.project_name,
            "sort_metric": self.sort_metric,
            "include_algos": sorted(self.include) if self.include else None,
            "exclude_algos": sorted(self.exclude) or None,
            "stopping_rounds": self.stopping_rounds,
            "stopping_tolerance": self.stopping_tolerance,
            "max_runtime_secs_per_model": self.max_runtime_secs_per_model,
            "preprocessing": self.preprocessing or None,
        }

    def _snapshot_state(self, y: str, x) -> None:
        self._recovery.write_state({
            "params": self._recovery_params(),
            "y": y, "x": list(x) if x else None,
            "done_steps": sorted(self._skip_steps),
            "models": self._step_models,
        })

    def _step_snapshot_files(self, step_id: str) -> set:
        """Relative snapshot paths under the step's nested recovery dir,
        read with ONE os.listdir per step per run (cached — was one
        os.path.exists per model per step snapshot)."""
        cached = self._snapshot_listing.get(step_id)
        if cached is not None:
            return cached
        sub = os.path.join(self._recovery.dir, step_id)
        files: set = set()
        if os.path.isdir(sub):
            files = {f"{step_id}/{f}" for f in os.listdir(sub)
                     if f.endswith(".bin")}
        self._snapshot_listing[step_id] = files
        return files

    def _on_step_done(self, step_id: str, models: List, y: str, x) -> None:
        """Persist leaderboard membership + step completion after every
        trained model reaches the leaderboard (Recovery.onModel role).
        Grid steps already snapshotted per-model into their nested dir;
        everything else snapshots here."""
        if self._recovery is None:
            return
        grid_files = self._step_snapshot_files(step_id)
        files = []
        for m in models:
            rel = f"{step_id}/{m.key}.bin"
            if rel in grid_files:
                files.append(rel)                    # grid snapshot
            else:
                files.append(self._recovery.save_model(m))
        self._step_models[step_id] = files
        self._skip_steps.add(step_id)
        self._snapshot_state(y, x)

    def train(self, y: str, training_frame: Frame,
              x: Optional[Sequence[str]] = None,
              validation_frame: Optional[Frame] = None,
              leaderboard_frame: Optional[Frame] = None):
        t0 = time.time()
        budget = Budget(self.max_models, self.max_runtime_secs,
                       self.max_runtime_secs_per_model)
        if self._prior_models:
            # resumed run: restored models count toward max_models —
            # the budget must not re-spend what the dead process trained
            budget.add_trained(len(self._prior_models))
        plan = modeling_plan(self.seed, include=self.include,
                             exclude=self.exclude)
        self._log_event("init", f"plan: {[st.id for st in plan]}")
        if self._skip_steps:
            self._log_event(
                "resume", f"skipping {sorted(self._skip_steps)} "
                f"({len(self._prior_models)} models restored)")
        if self._recovery is not None:
            # state exists from minute zero: a kill before the first
            # model still leaves a resumable run
            self._snapshot_state(y, x)
        training_frame, te_model = self._maybe_target_encode(
            training_frame, y, x)
        self._te_model = te_model
        if te_model is not None and x is not None:
            # explicit predictor list: the encoded columns must join it
            x = list(x) + [c for c in training_frame.names
                           if c.endswith("_te")]
        trained: List = []

        # candidates run as PARALLEL jobs within each priority group
        # (hex/ParallelModelBuilder.java; AutoML.java:760 learn walks
        # groups in order). Groups are barriers: exploitation steps
        # read the leaderboard that earlier groups produced. On one
        # chip parallelism overlaps host-side prep + compiles with
        # device execution; on a pod each job gets its own dispatch.
        import os as _os
        par = int(_os.environ.get("H2O3TPU_AUTOML_PARALLEL", "0") or 0)
        if par <= 0:
            # ONE chip: sequential by default. Parallel workers each pay
            # their own first-shape compile and contend for the chip
            # (unverified on the current chip set-up; the earlier finding
            # was 3 parallel candidates ALL hitting a per-model cap that
            # each clears warm and sequential). The async
            # dispatch queue already overlaps host prep with device
            # execution inside one thread; on a pod, raise via env.
            par = 1
        from h2o3_tpu.parallel import scheduler as _sched
        if par > 1 and _sched.active():
            # the cluster work scheduler already fans steps across
            # hosts, and its SPMD run() entry needs every process to
            # reach scheduled runs in the same order — thread-parallel
            # step submission would interleave differently per process
            self._log_event(
                "scheduler", "H2O3TPU_AUTOML_PARALLEL ignored on a "
                "scheduled cloud (steps fan out across hosts instead)")
            par = 1
        from concurrent.futures import ThreadPoolExecutor, as_completed
        groups = sorted({s.group for s in plan if s.kind != "ensemble"})
        for g in groups:
            if budget.exhausted():
                self._log_event("budget", "budget exhausted; stopping plan")
                break
            steps_g = [s for s in plan
                       if s.group == g and s.kind != "ensemble"
                       and s.id not in self._skip_steps]
            with ThreadPoolExecutor(max_workers=par) as ex:
                futs = {ex.submit(run_step, self, s, budget,
                                  training_frame, y, x): s
                        for s in steps_g}
                for fut in as_completed(futs):
                    step = futs[fut]
                    try:
                        models = fut.result()
                    except TimeoutError as e:
                        self._log_event("timeout", f"{step.id}: {e}")
                        continue
                    except Exception as e:
                        self._log_event("error", f"{step.id} failed: {e}")
                        continue
                    if not models:
                        continue
                    trained.extend(models)
                    self.leaderboard_obj.add(*models)
                    self._on_step_done(step.id, models, y, x)
                    self._log_event(
                        "model",
                        f"{step.id} done ({budget.trained} models, "
                        f"{time.time() - t0:.0f}s)")

        # stacked ensembles last (StackedEnsembleStepsProvider):
        # best-of-family + all-models. Resumed models participate — CV
        # holdouts ride the binary snapshots (persist pickles them).
        with_cv = [m for m in self._prior_models + trained
                   if getattr(m, "_cv_holdout", None) is not None]
        best_of_family = {}
        if self._allowed("stackedensemble") and len(with_cv) >= 2:
            for m in self.leaderboard_obj.sorted_models():
                if m in with_cv and m.algo not in best_of_family:
                    best_of_family[m.algo] = m
            if (len(best_of_family) >= 2 and
                    "StackedEnsemble_BestOfFamily" not in self._skip_steps):
                try:
                    se = StackedEnsembleEstimator(
                        base_models=list(best_of_family.values())).train(
                        training_frame, y=y, x=x)
                    se.output["automl_step"] = "StackedEnsemble_BestOfFamily"
                    self.leaderboard_obj.add(se)
                    self._on_step_done("StackedEnsemble_BestOfFamily",
                                       [se], y, x)
                except Exception as e:
                    self._log_event("error",
                                    f"best-of-family ensemble failed: {e}")
            if (len(with_cv) > max(2, len(best_of_family)) and
                    "StackedEnsemble_AllModels" not in self._skip_steps):
                try:
                    se2 = StackedEnsembleEstimator(
                        base_models=with_cv[:10]).train(
                        training_frame, y=y, x=x)
                    se2.output["automl_step"] = "StackedEnsemble_AllModels"
                    self.leaderboard_obj.add(se2)
                    self._on_step_done("StackedEnsemble_AllModels",
                                       [se2], y, x)
                except Exception as e:
                    self._log_event("error",
                                    f"all-models ensemble failed: {e}")

        if self._recovery is not None:
            # the plan completed: unconsumed in-fit snapshots under the
            # recovery dir (combo/model killed then resumed elsewhere)
            # must not leak into the next resume
            from h2o3_tpu.core import recovery as recovery_mod
            recovery_mod.clear_fit_snapshots(
                os.path.join(self._recovery.dir, "fit_state"))
        self._log_event("done",
                        f"{len(self.leaderboard_obj.models)} models in "
                        f"{time.time() - t0:.0f}s; leader="
                        f"{self.leader.key if self.leader else None}")
        return self.leader


def resume_automl(recovery_dir: str, training_frame: Frame,
                  validation_frame: Optional[Frame] = None,
                  leaderboard_frame: Optional[Frame] = None) -> H2OAutoML:
    """Resume an AutoML run killed mid-plan from its recovery snapshots
    (hex/faulttolerance/Recovery.onDone re-run path, AutoML flavor).

    Rebuilds the leaderboard from the persisted model binaries, marks the
    completed steps done so no step retrains twice, and continues the
    modeling plan from the next step. The wallclock budget restarts (the
    dead process's elapsed time is unknowable and usually irrelevant
    after a restart); ``max_models`` counts restored models. Returns the
    resumed :class:`H2OAutoML` with a complete leaderboard."""
    from h2o3_tpu.core.recovery import Recovery
    state = Recovery(recovery_dir, state_name="automl_state").read_state()
    if state is None:
        raise FileNotFoundError(
            f"no automl_state.json under {recovery_dir}")
    aml = H2OAutoML(recovery_dir=recovery_dir, **state["params"])
    rec = aml._recovery
    files = [f for fs in state["models"].values() for f in fs]
    prior = rec.load_models(files)
    aml._prior_models = prior
    aml._skip_steps = set(state["done_steps"])
    aml._step_models = dict(state["models"])
    aml.leaderboard_obj.add(*prior)
    aml._log_event("resume", f"restored {len(prior)} models, "
                   f"{len(aml._skip_steps)} steps done")
    aml.train(y=state["y"], training_frame=training_frame,
              x=state["x"], validation_frame=validation_frame,
              leaderboard_frame=leaderboard_frame)
    return aml
