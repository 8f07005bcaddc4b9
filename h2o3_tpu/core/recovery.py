"""Recovery — crash-survivable snapshot/resume state for long walks
AND for the fit in flight.

Reference: hex/faulttolerance/Recovery.java:21-45 — when a Grid or
AutoML run is started with a recovery directory, every trained model
and the walk state are persisted there so a fresh cluster can pick the
work up after a node dies. Here the same contract backs both
ml/grid.py (per-combo snapshots, resume_grid) and automl
(per-step snapshots, resume_automl in automl/__init__.py).

On-disk layout under ``recovery_dir``::

    <state name>.json      walk state (atomic: tmp + rename)
    <model key>.bin        one binary snapshot per trained model
    <step id>/             nested Recovery of a grid step (AutoML)
    fit_state/             in-fit snapshots of the combo in flight

State writes are atomic (write-to-tmp + ``os.rename``) so a SIGKILL
mid-write leaves the previous consistent snapshot, never a torn file.
Model snapshots go through io/persist.py (device-independent pickle),
so a run killed on an 8-device mesh resumes fine on one device.

**In-fit checkpointing** (:class:`FitCheckpointer`): the walk layer
above snapshots *between* models; a SIGKILL mid-fit still threw away
every boosting round already paid for. GBM (every K trees at the
`_boost_scan` host boundary), GLM (lambda-path outer iterations) and
DeepLearning (epoch boundaries) call the checkpointer to atomically
persist device-independent partial state — including the PRNG key
chain, early-stop history and scoring history — so a resumed fit is
**bit-identical** to an uninterrupted one (the DrJAX-style replayable
state-capture discipline, arxiv 2403.07128; Orbax-style async
snapshotting per SNIPPETS.md costs <1% of step time — ours is bounded
by the `fit_checkpoint_seconds` histogram).

A corrupt/truncated snapshot is *quarantined* (renamed ``*.corrupt``,
``snapshot_load_failures_total`` incremented) and the fit restarts
cleanly — never a crash, never a silent wrong model.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.recovery")


def quarantine_snapshot(path: str, err: BaseException) -> Optional[str]:
    """Move an unreadable snapshot aside as ``<path>.corrupt`` (never
    crash, never silently reuse it) and count the failure. Returns the
    quarantine path, or None when even the rename failed."""
    from h2o3_tpu import telemetry
    telemetry.counter("snapshot_load_failures_total").inc()
    dest = path + ".corrupt"
    n = 0
    while os.path.exists(dest):            # keep every corpse for forensics
        n += 1
        dest = f"{path}.corrupt.{n}"
    try:
        os.rename(path, dest)
    except OSError as re:
        log.warning("recovery: could not quarantine %s: %s", path, re)
        return None
    log.warning("recovery: quarantined corrupt snapshot %s -> %s (%s)",
                path, os.path.basename(dest), err)
    return dest


class Recovery:
    """One recovery directory: model snapshots + an atomic state file."""

    def __init__(self, recovery_dir: str, state_name: str = "state"):
        self.dir = recovery_dir
        self.state_name = state_name
        os.makedirs(recovery_dir, exist_ok=True)

    # ------------------------------------------------------------ state
    @property
    def state_path(self) -> str:
        return os.path.join(self.dir, f"{self.state_name}.json")

    def write_state(self, state: dict) -> None:
        """Atomic state snapshot: a kill mid-write must leave the prior
        consistent state, not a torn JSON (Recovery.java writes the
        recovery state via the persist layer for the same reason)."""
        tmp = self.state_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, self.state_path)

    def read_state(self) -> Optional[dict]:
        if not os.path.exists(self.state_path):
            return None
        with open(self.state_path) as f:
            return json.load(f)

    def has_state(self) -> bool:
        return os.path.exists(self.state_path)

    # ------------------------------------------------------------ models
    def save_model(self, model) -> str:
        """Snapshot one trained model; returns its file name."""
        from h2o3_tpu.io.persist import save_model
        fname = f"{model.key}.bin"
        save_model(model, os.path.join(self.dir, fname))
        return fname

    def load_models(self, files: List[str]) -> List:
        from h2o3_tpu.io.persist import load_model
        out = []
        for f in files:
            path = os.path.join(self.dir, f)
            try:
                out.append(load_model(path))
            except FileNotFoundError as e:
                log.warning("recovery: missing snapshot %s: %s", path, e)
            except Exception as e:  # noqa: BLE001 - a torn tail snapshot
                # (killed mid-save_model) costs one model, not the
                # resume; the corpse is quarantined so a later resume
                # cannot trip over it again
                quarantine_snapshot(path, e)
        return out

    def sub(self, name: str) -> "Recovery":
        """Nested recovery dir (one per AutoML grid step)."""
        return Recovery(os.path.join(self.dir, name),
                        state_name=self.state_name)


def ensure_json_safe(params: Dict, what: str) -> None:
    """Fail fast (before any model trains) when walk params cannot be
    serialized into the recovery state."""
    for k, v in params.items():
        try:
            json.dumps(v)
        except TypeError:
            raise ValueError(
                f"{what} requires JSON-serializable params; "
                f"'{k}'={type(v).__name__} is not") from None


# ===================================================================
# In-fit checkpointing (FitCheckpointer)
# ===================================================================

FIT_SNAPSHOT_VERSION = 1
FIT_SUFFIX = ".fitsnap"

# directory override for the current fit — ml/grid.py and
# automl/executor.py point it INSIDE their recovery_dir so a
# SIGKILL-mid-combo resumes inside the combo; models/model.py captures
# it on the caller thread and re-installs it on the job worker thread
_fit_dir_var: contextvars.ContextVar = contextvars.ContextVar(
    "h2o3tpu_fit_ckpt_dir", default=None)

_fit_lock = threading.Lock()
# every directory a checkpointer ever touched in this process — the
# shutdown()/conftest sweep walks these for orphaned tmp files
_fit_dirs_used: set = set()
# last snapshot THIS thread wrote/loaded: the job supervisor
# (core/job.py) consults it on an infra retry to log/decide
# resume-vs-restart without reaching into builder internals
_thread_state = threading.local()

# post-save observer for the current context — the cluster work
# scheduler (parallel/scheduler.py) installs a hook that republishes
# every written snapshot to the coordination-service KV so a reassigned
# work item's new owner can resume the fit mid-flight
_post_save_var: contextvars.ContextVar = contextvars.ContextVar(
    "h2o3tpu_fit_post_save", default=None)


@contextlib.contextmanager
def post_save_scope(hook: Callable[[str, bytes], None]):
    """Call ``hook(path, blob)`` after every ``FitCheckpointer.save``
    in this context (exceptions in the hook never fail the fit)."""
    tok = _post_save_var.set(hook)
    try:
        yield
    finally:
        _post_save_var.reset(tok)


def fit_checkpoint_dir() -> Optional[str]:
    """Resolved in-fit snapshot directory: the contextvar scope wins
    (grid/AutoML recovery composition), then ``H2O3TPU_FIT_CHECKPOINT_DIR``,
    then ``Config.fit_checkpoint_dir``. None/empty = checkpointing off."""
    d = _fit_dir_var.get()
    if d:
        return d
    d = os.environ.get("H2O3TPU_FIT_CHECKPOINT_DIR")
    if d:
        return d
    from h2o3_tpu.core.config import ARGS
    return getattr(ARGS, "fit_checkpoint_dir", "") or None


@contextlib.contextmanager
def fit_checkpoint_scope(directory: Optional[str]):
    """Scope the fit-checkpoint directory for the current context
    (passing None is a transparent no-op that keeps env/config
    resolution intact)."""
    tok = _fit_dir_var.set(directory)
    try:
        yield
    finally:
        _fit_dir_var.reset(tok)


def fit_checkpoint_every(default: int) -> int:
    """Snapshot cadence in algo-native units (GBM: trees, DL: minibatch
    steps, GLM: lambda-path iterations). ``H2O3TPU_FIT_CHECKPOINT_EVERY``
    / ``Config.fit_checkpoint_every`` override the caller's default."""
    env = os.environ.get("H2O3TPU_FIT_CHECKPOINT_EVERY")
    if env:
        return max(1, int(env))
    from h2o3_tpu.core.config import ARGS
    v = int(getattr(ARGS, "fit_checkpoint_every", 0) or 0)
    return v if v > 0 else max(1, int(default))


def _fit_fingerprint(algo: str, params: Dict, y, x, nrows: int) -> str:
    """Stable cross-process identity of one fit: the resumed process
    must find the snapshot the dead one wrote, so the file name derives
    from (algo, params, response, predictors, row count) — never from a
    per-process model/job key."""
    import hashlib
    canon = {}
    for k, v in params.items():
        if k == "checkpoint" and v is not None:
            v = getattr(v, "key", v)       # Model object → its key
        canon[k] = repr(v)
    payload = json.dumps(
        {"algo": algo, "y": y, "x": list(x) if x else None,
         "nrows": int(nrows), "params": canon}, sort_keys=True)
    return hashlib.blake2b(payload.encode(), digest_size=10).hexdigest()


def snapshot_host(x):
    """Device-independent host snapshot of (possibly cross-process
    sharded) fit state — what every ``FitCheckpointer.maybe_save``
    state_fn must use for device arrays. ``np.asarray`` raises on a
    row-sharded array of a multi-process cloud (it spans non-addressable
    devices); this lowers through the same ladder as model persistence
    (io/persist.py): fully-addressable → device_get, cross-process
    replicated → read the local replica, cross-process sharded →
    allgather to the GLOBAL array, so a reformed cloud of any size can
    re-shard the snapshot and resume. On multi-process clouds the
    allgather is an SPMD collective: every process must call at the
    same program point (the shared snapshot cadence guarantees it)."""
    import jax
    import numpy as np

    def _snap(v):
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            if v.sharding.is_fully_replicated:
                return np.asarray(v.addressable_shards[0].data)
            from h2o3_tpu.parallel.mesh import fetch_replicated
            return np.asarray(fetch_replicated(v))
        return np.asarray(v)
    return jax.tree_util.tree_map(_snap, x)


def fit_checkpointer(algo: str, params: Dict, y, x, nrows: int,
                     default_every: int) -> Optional["FitCheckpointer"]:
    """The builder-facing entry point: returns a checkpointer when
    in-fit snapshotting is enabled for this context, else None."""
    d = fit_checkpoint_dir()
    if not d:
        return None
    fp = _fit_fingerprint(algo, params, y, x, nrows)
    return FitCheckpointer(
        os.path.join(d, f"{algo}_{fp}{FIT_SUFFIX}"), algo,
        fit_checkpoint_every(default_every))


class FitCheckpointer:
    """Periodic, atomic, device-independent snapshots of one fit's
    partial state, written at host boundaries the training loops
    already cross (GBM tree chunks, DL step chunks, GLM lambdas).

    The on-disk artifact is one pickle (version + algo + unit + state)
    published via write-to-tmp + ``os.replace`` so a SIGKILL mid-write
    leaves the previous consistent snapshot. ``load()`` quarantines
    anything unreadable and returns None — a corrupt snapshot costs the
    resume, never correctness."""

    def __init__(self, path: str, algo: str, every: int):
        self.path = path
        self.algo = algo
        self.every = max(1, int(every))
        self._last_unit = 0
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with _fit_lock:
            _fit_dirs_used.add(os.path.dirname(path) or ".")

    # -- write ---------------------------------------------------------
    def due(self, unit: int) -> bool:
        return unit - self._last_unit >= self.every

    def save(self, unit: int, state: Dict[str, Any]) -> None:
        from h2o3_tpu import telemetry
        from h2o3_tpu.telemetry import stepprof
        t0 = time.time()
        # an active fit profile charges the snapshot write to its
        # "checkpoint" phase (IO time is neither compute nor host prep)
        with stepprof.phase("checkpoint"):
            blob = pickle.dumps({"version": FIT_SNAPSHOT_VERSION,
                                 "algo": self.algo, "unit": int(unit),
                                 "state": state}, protocol=4)
            tmp = self.path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        self._last_unit = int(unit)
        _thread_state.last = (self.path, int(unit), self.algo)
        hook = _post_save_var.get()
        if hook is not None:
            try:
                hook(self.path, blob)
            except Exception as e:   # noqa: BLE001 - observer only
                log.warning("fit checkpoint post-save hook failed: %s", e)
        telemetry.counter("fit_checkpoints_written_total",
                          algo=self.algo).inc()
        telemetry.histogram("fit_checkpoint_seconds").observe(
            time.time() - t0)
        # test hook (SIGKILL-mid-fit tests): widen the crash window so
        # the killer deterministically lands between a snapshot and the
        # next chunk — analogous to the watchdog fault-injection knobs
        hold = float(os.environ.get("H2O3TPU_FIT_CHECKPOINT_HOLD_S",
                                    "0") or 0)
        if hold > 0:
            time.sleep(hold)

    def maybe_save(self, unit: int,
                   state_fn: Callable[[], Dict[str, Any]]) -> bool:
        """Snapshot when the cadence is due; ``state_fn`` defers the
        (host-sync) state capture so off-cadence boundaries cost one
        integer compare."""
        if not self.due(unit):
            return False
        self.save(unit, state_fn())
        return True

    # -- read ----------------------------------------------------------
    def load(self, needs=()) -> Optional[Tuple[int, Dict[str, Any]]]:
        """(unit, state) of the last snapshot, or None. Counts
        ``fit_resumes_total{algo}`` on success; quarantines on any
        failure (bit-flip, truncation, version drift, or a state that
        lacks one of the fields in ``needs`` — what the resuming fit
        would read: a fit is resumed whole or not at all)."""
        if not os.path.exists(self.path):
            return None
        try:
            with open(self.path, "rb") as f:
                payload = pickle.load(f)
            if payload.get("version") != FIT_SNAPSHOT_VERSION:
                raise ValueError(
                    f"fit snapshot version {payload.get('version')} != "
                    f"{FIT_SNAPSHOT_VERSION}")
            if payload.get("algo") != self.algo:
                raise ValueError(
                    f"fit snapshot algo {payload.get('algo')!r} != "
                    f"{self.algo!r}")
            unit = int(payload["unit"])
            state = payload["state"]
            missing = sorted(set(needs) - set(state))
            if missing:
                raise ValueError(f"fit snapshot lacks {missing}")
        except Exception as e:  # noqa: BLE001 - quarantine boundary
            quarantine_snapshot(self.path, e)
            return None
        self._last_unit = unit
        _thread_state.last = (self.path, unit, self.algo)
        from h2o3_tpu import telemetry
        telemetry.counter("fit_resumes_total", algo=self.algo).inc()
        log.info("fit resume: %s from snapshot unit %d (%s)",
                 self.algo, unit, self.path)
        return unit, state

    def clear(self) -> None:
        """Remove the snapshot once the fit completed — a finished model
        must never resume."""
        for pp in (self.path, self.path + ".tmp"):
            try:
                os.remove(pp)
            except OSError:
                pass
        _thread_state.last = None


def thread_fit_snapshot() -> Optional[Tuple[str, int, str]]:
    """(path, unit, algo) of the last in-fit snapshot this thread wrote
    or loaded, if it still exists on disk — the job supervisor's
    resume-vs-restart probe (core/job.py retry loop)."""
    t = getattr(_thread_state, "last", None)
    if t and os.path.exists(t[0]):
        return t
    return None


def clear_fit_snapshots(directory: str) -> int:
    """Remove every fit snapshot (and tmp debris) under ``directory``;
    rmdir it when empty. Grid/AutoML call this when their walk
    completes — unconsumed snapshots (e.g. a combo that got batch-
    trained on resume) must not leak."""
    removed = 0
    if not os.path.isdir(directory):
        return 0
    for f in list(os.listdir(directory)):
        if FIT_SUFFIX in f:
            try:
                os.remove(os.path.join(directory, f))
                removed += 1
            except OSError:
                pass
    try:
        if not os.listdir(directory):
            os.rmdir(directory)
    except OSError:
        pass
    with _fit_lock:
        _fit_dirs_used.discard(directory)
    return removed


def sweep_fit_checkpoints(extra_dir: Optional[str] = None) -> int:
    """Sweep ORPHANED in-fit checkpoint debris: ``*.tmp`` files a kill
    left behind and partial (now-empty) snapshot directories. Completed
    ``*.fitsnap`` snapshots are intentional resumable state and stay.
    Called by ``shutdown()`` and the conftest leak check (extends the
    PR 2 sweep). Returns how many entries were removed."""
    with _fit_lock:
        dirs = set(_fit_dirs_used)
    if extra_dir:
        dirs.add(extra_dir)
    env_d = os.environ.get("H2O3TPU_FIT_CHECKPOINT_DIR")
    if env_d:
        dirs.add(env_d)
    removed = 0
    for d in dirs:
        if not os.path.isdir(d):
            with _fit_lock:
                _fit_dirs_used.discard(d)
            continue
        for f in list(os.listdir(d)):
            if f.endswith(FIT_SUFFIX + ".tmp"):
                try:
                    os.remove(os.path.join(d, f))
                    removed += 1
                except OSError:
                    pass
        try:
            if not os.listdir(d):
                os.rmdir(d)
                removed += 1
                with _fit_lock:
                    _fit_dirs_used.discard(d)
        except OSError:
            pass
    try:
        # mirror-blob debris rides the same sweep cadence: orphaned
        # *.framesnap.tmp from a kill mid-write plus unregistered
        # *.framesnap blobs (core/durability.py, ISSUE 18)
        from h2o3_tpu.core import durability as _durability
        removed += _durability.sweep_debris()
    except Exception:       # noqa: BLE001 - durability is optional
        pass
    return removed
