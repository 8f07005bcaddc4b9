"""Job system — async job tracking with progress and cancellation.

Reference: water/Job.java:24 (start/update/progress, lines 206-225) and the
REST polling loop (client polls GET /3/Jobs/{id}). Jobs here run either
inline (fast path: device compute is async anyway, the Python 'job' merely
brackets it) or on a worker thread for long trainings so the REST server
stays responsive — the analogue of launching the ModelBuilder Driver on the
F/J pool (hex/ModelBuilder.java:234).
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable, Optional

from h2o3_tpu.core import heartbeat as heartbeat_mod
from h2o3_tpu.core import request_ctx, watchdog
from h2o3_tpu.core.kv import DKV, make_key
from h2o3_tpu.core.scope import Scope
from h2o3_tpu.core.watchdog import is_infra_error  # noqa: F401 - re-export
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.job")

CREATED, RUNNING, DONE, FAILED, CANCELLED = (
    "CREATED", "RUNNING", "DONE", "FAILED", "CANCELLED")

# classification + retry policy live in core/watchdog.py (shared with
# bench.py and the probe); kept as an alias for existing importers
_INFRA_SIGNS = watchdog.INFRA_SIGNS


def free_device_memory(reason: str = "") -> None:
    """Best-effort HBM pressure release: drop jit executable caches and
    collect dropped buffers (the water/Cleaner.java role for a device
    whose backend reports no memory stats)."""
    import gc
    try:
        import jax
        jax.clear_caches()
    except Exception:
        pass
    gc.collect()
    log.info("freed device caches%s", f" ({reason})" if reason else "")


class JobCancelledException(Exception):
    pass


# cancellation is a user decision, never a retryable infra blip
watchdog.NON_RETRYABLE.append(JobCancelledException)


class Job:
    """One unit of trackable async work (reference water/Job.java:24)."""

    def __init__(self, description: str, work: float = 1.0, dest: Optional[str] = None):
        self.key = make_key("job")
        self.description = description
        self.dest = dest                      # key of the result object
        self.status = CREATED
        self.exception: Optional[str] = None
        self._work = max(work, 1e-9)
        self._worked = 0.0
        self._msg = ""
        self.start_time = 0.0
        self.end_time = 0.0
        self._cancel_requested = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.result: Any = None
        # run exactly once when the job ends, whatever the status —
        # the memory governor parks its reservation release here
        # (core/memgov.py; retries re-enter fn, so the work itself
        # cannot host end-of-job cleanup)
        self._finalizers: list = []
        # request deadline (absolute monotonic) captured at SUBMISSION
        # time from the request context (api/server.py installs it for
        # ?_timeout_ms= / X-H2O-Deadline-Ms requests); background jobs
        # run on a fresh thread whose context would not inherit it, so
        # Job.start re-installs it via request_ctx.job_scope
        self.deadline: Optional[float] = request_ctx.current_deadline()
        # distributed trace context captured at SUBMISSION time, same
        # discipline as the deadline above: re-parented under the
        # submitting thread's active span (the REST request span) so
        # the job's root span stitches causally under the request that
        # created it, then re-installed on the worker thread by
        # job_scope (telemetry/trace_context.py)
        from h2o3_tpu.telemetry import spans as _spans
        from h2o3_tpu.telemetry import trace_context as _trace
        tc = _trace.current()
        self.trace = tc.child(_spans.current_span_id()
                              or tc.parent_id) if tc is not None else None
        self.trace_id: Optional[str] = tc.trace_id if tc else None
        DKV.put(self.key, self)

    # -- lifecycle (Job.start / Job.update, water/Job.java:206-225) ------
    def start(self, fn: Callable[["Job"], Any], background: bool = False) -> "Job":
        self.status = RUNNING
        self.start_time = time.time()
        from h2o3_tpu import telemetry
        from h2o3_tpu.telemetry import flight_recorder
        from h2o3_tpu.utils.timeline import record as _tl
        _tl("job", f"start {self.description}", key=self.key)
        telemetry.counter("jobs_started_total").inc()
        # live in-flight count: the per-node load summary GET /3/Cloud
        # and the cluster fan-in snapshots report (telemetry/cluster.py)
        telemetry.gauge("jobs_inflight").add(1)

        # the flight-recorder handle crosses the _run → _body closure
        # boundary via this cell (attach must run on the WORKER thread —
        # a background thread's context is fresh, so the contextvar set
        # in start()'s thread would never reach the work)
        rec_cell = []

        def _body():
            # every key the work creates is tracked in a job-local Scope:
            # a cancelled/expired job must release its partial keys
            # (water/Scope.java exit-on-abort role) instead of leaking
            # half-built models/frames into the DKV; DONE and FAILED
            # jobs keep theirs (pollers read FAILED results' state)
            sc = Scope()
            sc.__enter__()
            cloud_lost = False
            try:
                # the telemetry capsule key is DKV.put INSIDE this
                # Scope: a cancelled job's capsule is swept with its
                # partial keys (telemetry/flight_recorder.py)
                if rec_cell:
                    flight_recorder.publish(rec_cell[0])
                # bounded retries for infra-class errors only, under the
                # shared watchdog policy (backoff + jitter, attempts from
                # core/config.py). Supervisor contract: when the failed
                # work left an in-fit snapshot (core/recovery.py
                # FitCheckpointer), re-entering the fit resumes from it
                # instead of round 0 — the builder consults the same
                # checkpointer on entry; otherwise the work restarts
                # from scratch (model builds are idempotent).
                policy = watchdog.policy_from_config()
                attempt = 0
                while True:
                    attempt += 1
                    try:
                        watchdog.maybe_fail("job")
                        self.result = fn(self)
                        break
                    except Exception as e:  # noqa: BLE001
                        if (attempt >= policy.max_attempts
                                or not is_infra_error(e)
                                or self._cancel_requested.is_set()):
                            raise
                        if (isinstance(e, heartbeat_mod.CloudUnhealthyError)
                                and not heartbeat_mod.monitor.healthy()):
                            # fail-fast contract: retrying against a
                            # cloud that is STILL unhealthy just burns
                            # the backoff budget — recovery_dir
                            # snapshot/resume is the comeback path
                            raise
                        delay = policy.delay(attempt)
                        # consult the in-fit checkpointer: a surviving
                        # snapshot means the retry RE-ENTERS the fit at
                        # its last persisted boundary (bit-identical
                        # continuation) instead of restarting at round 0
                        from h2o3_tpu.core import recovery as _recovery
                        snap = _recovery.thread_fit_snapshot()
                        if snap is not None:
                            log.warning(
                                "job %s: infra error; supervisor will "
                                "resume the %s fit from its snapshot "
                                "(unit %d) in %.1fs (attempt %d/%d): %s",
                                self.key, snap[2], snap[1], delay,
                                attempt, policy.max_attempts, e)
                            _tl("job",
                                f"infra-resume {self.description}",
                                key=self.key, unit=snap[1],
                                error=str(e)[:200])
                        else:
                            log.warning(
                                "job %s: retrying after infra error "
                                "in %.1fs (attempt %d/%d): %s",
                                self.key, delay, attempt,
                                policy.max_attempts, e)
                            _tl("job", f"infra-retry {self.description}",
                                key=self.key, error=str(e)[:200])
                            self._worked = 0.0
                        telemetry.counter("infra_retries_total",
                                          site="job").inc()
                        if "RESOURCE_EXHAUSTED" in f"{e}":
                            # OOM escalation ladder (README §Memory
                            # governance): rung 1 purges the jit
                            # executable caches; rung 2 (repeat OOM)
                            # governor-evicts cold frames plus the
                            # per-frame device_matrix/bin caches; the
                            # snapshot consult above is rung 3 — the
                            # retry RESUMES from the checkpoint rather
                            # than restarting at round 0
                            free_device_memory("RESOURCE_EXHAUSTED retry")
                            telemetry.counter("oom_recoveries_total",
                                              stage="purge_jit").inc()
                            if attempt >= 2:
                                from h2o3_tpu.core.memgov import governor
                                freed = governor.evict_for_oom()
                                telemetry.counter("oom_recoveries_total",
                                                  stage="evict").inc()
                                log.warning(
                                    "job %s: repeat OOM — evicted cold "
                                    "frames + %.1f MB of device caches",
                                    self.key, freed / 1e6)
                            if snap is not None:
                                telemetry.counter("oom_recoveries_total",
                                                  stage="resume").inc()
                        policy.sleep(delay)
                if self.dest and self.result is not None:
                    DKV.put(self.dest, self.result)
                self.status = DONE
                _tl("job", f"done {self.description}", key=self.key)
            except JobCancelledException:
                self.status = CANCELLED
                _tl("job", f"cancelled {self.description}", key=self.key)
            except request_ctx.DeadlineExceeded as e:
                # an expired request deadline is a cancellation, not a
                # failure: the REST tier answers 408 and the job must
                # end CANCELLED, never linger RUNNING (ISSUE 3 contract)
                self.status = CANCELLED
                self._msg = "deadline exceeded"
                _tl("job", f"deadline-cancelled {self.description}",
                    key=self.key, error=str(e)[:200])
            except Exception as e:  # noqa: BLE001 - job boundary
                # exception BEFORE status: pollers react to FAILED by
                # reading .exception, which must already be set
                self.exception = "".join(
                    traceback.format_exception(type(e), e, e.__traceback__))
                self.status = FAILED
                # a cloud-unhealthy failure sweeps its partial keys like
                # a cancellation: the half-built model came off a
                # degraded mesh and must not linger in the DKV (resume
                # comes from recovery_dir snapshots, not these keys)
                cloud_lost = isinstance(e, heartbeat_mod.CloudUnhealthyError)
                _tl("job", f"failed {self.description}", key=self.key,
                    error=str(e)[:200])
                log.error("job %s failed: %s", self.key, e)
                if not background:
                    raise
            finally:
                self.end_time = time.time()
                if self.status != CANCELLED and not cloud_lost:
                    sc.keep(*sc._tracked)
                sc.__exit__(None, None, None)

        def _run():
            # the job is the ROOT telemetry span: everything the work
            # does (fit spans, boost chunks, compiles) nests under it —
            # background jobs run on their own thread, whose fresh
            # contextvar context makes this a root span automatically.
            # The flight recorder attaches FIRST so the root job span
            # itself lands in the capsule when it closes.
            handle = flight_recorder.attach(self.key, self.description)
            if handle is not None:
                rec_cell.append(handle)
            try:
                # job_scope makes this job + its captured deadline
                # visible to cancel_point() checks at chunk boundaries
                # (parallel/map_reduce.py) no matter how deep the work
                # nests — background threads start with a fresh
                # contextvar context, so this re-install is what carries
                # the request deadline across the thread hop
                with request_ctx.job_scope(self, deadline=self.deadline,
                                           trace=self.trace), \
                        telemetry.span("job", key=self.key,
                                       desc=self.description):
                    _body()
            finally:
                # what a job still does once its root span has closed
                # (the capsule wants that span whole): a root span of
                # its own, so the tail is charged to the program too
                with telemetry.span("job.finish", key=self.key):
                    for fin in self._finalizers:
                        try:
                            fin()
                        except Exception:   # noqa: BLE001 - best-effort
                            pass
                    flight_recorder.detach(handle, status=self.status)
                    telemetry.gauge("jobs_inflight").add(-1)
                    telemetry.counter("jobs_completed_total",
                                      status=self.status).inc()
                    telemetry.histogram("job_duration_seconds").observe(
                        (self.end_time or time.time()) - self.start_time)

        if background:
            self._thread = threading.Thread(target=_run, daemon=True, name=self.key)
            self._thread.start()
        else:
            _run()
        return self

    def update(self, units: float, msg: str = "") -> None:
        self._worked = min(self._work, self._worked + units)
        if msg:
            self._msg = msg
        if self._cancel_requested.is_set():
            raise JobCancelledException(self.key)
        if self.deadline is not None and time.monotonic() >= self.deadline:
            from h2o3_tpu import telemetry
            telemetry.counter("request_deadline_exceeded_total").inc()
            raise request_ctx.DeadlineExceeded(
                f"job {self.key}: request deadline exceeded "
                f"(observed at progress update)")
        heartbeat_mod.check_healthy("job.update")

    @property
    def progress(self) -> float:
        if self.status == DONE:
            return 1.0
        return self._worked / self._work

    @property
    def progress_msg(self) -> str:
        return self._msg

    def add_finalizer(self, fn: Callable[[], None]) -> None:
        """Register end-of-job cleanup (runs once in the worker's
        finally, after DONE/FAILED/CANCELLED is settled)."""
        self._finalizers.append(fn)

    def cancel(self) -> None:
        self._cancel_requested.set()

    def cancel_requested(self) -> bool:
        """Polled at chunk boundaries (request_ctx.cancel_point — the
        water/Job.java stop_requested() analogue)."""
        return self._cancel_requested.is_set()

    def join(self, timeout: Optional[float] = None) -> "Job":
        if self._thread is not None:
            self._thread.join(timeout)
        return self

    @property
    def run_time(self) -> float:
        end = self.end_time or time.time()
        return end - self.start_time if self.start_time else 0.0

    def to_dict(self) -> dict:
        """JobV3 wire shape (water/api/schemas3/JobV3.java) — the real
        h2o-py H2OJob reads key.name, dest.name, status, progress,
        auto_recoverable, warnings (h2o-py/h2o/job.py:36-56)."""
        dest_type = "Key<Keyed>"
        if self.dest:
            from h2o3_tpu.models.model import Model
            if isinstance(DKV.get_raw(self.dest), Model):
                dest_type = "Key<Model>"
        return {
            "__meta": {"schema_version": 3, "schema_name": "JobV3",
                       "schema_type": "Job"},
            "key": {"name": self.key, "type": "Key<Job>",
                    "URL": f"/3/Jobs/{self.key}"},
            "description": self.description,
            "status": self.status,
            "progress": self.progress,
            "progress_msg": self._msg,
            "start_time": int(self.start_time * 1000),
            "msec": int(self.run_time * 1000),
            "dest": {"name": self.dest or "", "type": dest_type},
            "exception": self.exception,
            "stacktrace": self.exception,
            # the whole job's cross-host trace is one
            # GET /3/Trace?trace_id= fetch away (ISSUE 16)
            "trace_id": self.trace_id,
            "warnings": [],
            "auto_recoverable": False,
            "ready_for_view": True,
            "run_time_ms": int(self.run_time * 1000),
        }


def list_jobs() -> list:
    out = []
    for k in DKV.keys("job_"):
        # the key can be removed between keys() and get() (remove_all
        # from another handler thread) — skip dead keys instead of
        # AttributeError'ing on None
        j = DKV.get(k)
        if isinstance(j, Job):
            out.append(j.to_dict())
    return out
