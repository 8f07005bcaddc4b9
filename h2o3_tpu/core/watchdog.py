"""Backend watchdog — liveness probe, shared infra-retry policy, fault
injection.

Reference: the reference platform treats node death as a first-class
event (water/HeartBeatThread.java:1 pings every node each second and
ejects corpses from the cloud; hex/faulttolerance/Recovery.java resumes
the work they dropped). The TPU analogue of a dead node is a wedged or
restarting TPU worker process: ``jax.devices()`` hangs or every
dispatch dies with INTERNAL/UNAVAILABLE. Round 5 lost the whole bench
scoreboard to exactly that — the first ``device_put`` hit a corpse and
every ad-hoc retry hit it again.

This module centralizes what used to be scattered one-shot retries
(core/job.py):

- ``probe_backend()``    — cheap liveness check: ``jax.devices()`` plus a
  tiny ``device_put`` round-trip, optionally bounded by a thread-timeout
  (a hung transfer must not hang the prober).
- ``RetryPolicy``        — bounded exponential backoff with jitter;
  defaults come from ``core/config.py`` (``H2O3TPU_INFRA_*`` env knobs).
- ``retry_call()``       — run a callable under the policy, retrying only
  classified infra errors.
- ``is_infra_error()``   — the single classifier for retryable
  infra-class failures (moved here from core/job.py, which re-exports).
- ``bounded_call()``     — run a callable on a daemon thread with a hard
  deadline (the thread-timeout prober; a hung device transfer or
  collective must never hang the caller). Used by ``probe_backend`` and
  the cloud heartbeat (core/heartbeat.py).
- fault injection        — ``inject_fault()`` / ``H2O3TPU_FAULTS`` plant
  classified failures at named sites (``probe``, ``job``,
  ``frame_reduce``, ``frame_map``, ``heartbeat``, ``cloud_init``,
  ``fit_chunk`` — the GBM/GLM/DL training-loop host boundaries where
  the FitCheckpointer snapshots — and ``device_oom``, the same
  boundaries raising RESOURCE_EXHAUSTED so the OOM escalation ladder
  of core/job.py runs deterministically) so every retry/degradation
  path runs in tier-1 CPU tests instead of waiting for a real TPU
  crash.

Telemetry: ``backend_probes_total``, ``backend_probe_failures_total``,
``infra_retries_total{site=}`` (README §Fault tolerance).
"""

from __future__ import annotations

import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from h2o3_tpu.core import config as _config
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.watchdog")

# transient infra failures of the chip's runtime — distinct from user
# errors and worth bounded retries. RESOURCE_EXHAUSTED is retryable
# because callers purge the jit executable cache first (see core/job.py
# free_device_memory): the cache pins HBM, so pressure can show up as
# this error before the governor's own accounting sees it. "Gloo" is
# the CPU cross-process collective transport: a peer dying mid-collective
# surfaces as FAILED_PRECONDITION "Gloo collective ... Connection closed
# by peer", which is cloud infrastructure, never user code.
INFRA_SIGNS = ("INTERNAL:", "UNAVAILABLE:", "DEADLINE_EXCEEDED",
               "RESOURCE_EXHAUSTED", "Gloo")

# exception types never worth a retry, regardless of message. Modules
# that define their own (e.g. core/job.py JobCancelledException) append
# to this at import so the classifier needs no circular import.
NON_RETRYABLE: List[type] = [ValueError, TypeError, KeyError]


def is_infra_error(e: BaseException) -> bool:
    """True for retryable infra-class errors (XlaRuntimeError INTERNAL /
    UNAVAILABLE), False for user/programming errors."""
    if isinstance(e, tuple(NON_RETRYABLE)):
        return False
    msg = f"{type(e).__name__}: {e}"
    return any(s in msg for s in INFRA_SIGNS)


# ------------------------------------------------------------ fault injection


class InjectedFault(Exception):
    """Planted by the fault-injection hooks; message carries an
    INFRA_SIGNS token so it classifies as retryable."""


_faults_lock = threading.Lock()
# site -> {"left": remaining failures, "sign": message token}
_faults: Dict[str, Dict[str, Any]] = {}
_fired: Dict[str, int] = {}       # site -> injected-failure count (tests)


def _state_path() -> Optional[str]:
    """Optional cross-process fault budget: when H2O3TPU_FAULT_STATE
    names a directory, consumed counts persist there so N injected
    failures span N fresh subprocesses (a per-process counter would
    reset with every child and the site could never recover)."""
    return os.environ.get("H2O3TPU_FAULT_STATE") or None


def _parse_env_faults() -> None:
    """H2O3TPU_FAULTS="site:count[:SIGN],site2:count" — planted once at
    first use; programmatic inject_fault() overrides."""
    spec = os.environ.get("H2O3TPU_FAULTS", "")
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        site = bits[0]
        with _faults_lock:
            if site in _faults:
                continue
        count = int(bits[1]) if len(bits) > 1 and bits[1] else 1
        sign = bits[2] if len(bits) > 2 and bits[2] else None
        inject_fault(site, times=count, sign=sign)


_env_parsed = False


def inject_fault(site: str, times: int = 1,
                 sign: Optional[str] = None) -> None:
    """Plant `times` classified failures at a named site. ``sign``
    defaults per site: ``device_oom`` faults as RESOURCE_EXHAUSTED (so
    the job supervisor's OOM escalation ladder runs), everything else
    as UNAVAILABLE."""
    if sign is None:
        sign = "RESOURCE_EXHAUSTED" if site == "device_oom" \
            else "UNAVAILABLE"
    with _faults_lock:
        _faults[site] = {"left": int(times), "sign": sign}


def clear_faults() -> None:
    with _faults_lock:
        _faults.clear()
        _fired.clear()


def fired(site: str) -> int:
    """How many injected failures a site has raised (test assertion)."""
    with _faults_lock:
        return _fired.get(site, 0)


def _consume_shared(site: str, budget: int) -> bool:
    """Cross-process consumption: bump <state>/<site>.count under an
    exclusive lockfile; True while consumed < budget (i.e. still fail)."""
    d = _state_path()
    path = os.path.join(d, f"fault_{site}.count")
    os.makedirs(d, exist_ok=True)
    lock = path + ".lock"
    for _ in range(200):                      # ~2s worst case
        try:
            fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            os.close(fd)
            break
        except FileExistsError:
            time.sleep(0.01)
    try:
        consumed = 0
        if os.path.exists(path):
            with open(path) as f:
                consumed = int(f.read().strip() or 0)
        if consumed >= budget:
            return False
        with open(path, "w") as f:
            f.write(str(consumed + 1))
        return True
    finally:
        try:
            os.unlink(lock)
        except OSError:
            pass


def maybe_fail(site: str) -> None:
    """Injection hook — called at the top of every guarded site
    (probe / job / frame_reduce / frame_map). No-op unless a fault is
    planted there."""
    global _env_parsed
    if not _env_parsed:
        _env_parsed = True
        _parse_env_faults()
    with _faults_lock:
        f = _faults.get(site)
        if f is None or f["left"] <= 0:
            return
        shared = _state_path() is not None
        if not shared:
            f["left"] -= 1
        budget = int(f["left"])
        sign = f["sign"]
    if shared and not _consume_shared(site, budget):
        return
    with _faults_lock:
        _fired[site] = _fired.get(site, 0) + 1
    raise InjectedFault(f"{sign}: injected fault at site '{site}'")


# ------------------------------------------------------------- retry policy


@dataclass
class RetryPolicy:
    """Bounded exponential backoff with jitter.

    ``max_attempts`` counts total tries (1 = no retry). Delay before
    retry k (k starting at 1) is ``base * 2**(k-1)`` clamped to ``max``,
    then multiplied by a uniform jitter in ``[1-jitter, 1+jitter]`` so a
    fleet of retriers cannot thundering-herd a recovering worker."""
    max_attempts: int = 3
    base_delay_s: float = 0.5
    max_delay_s: float = 30.0
    jitter: float = 0.25
    sleep: Callable[[float], None] = time.sleep
    rng: random.Random = field(default_factory=random.Random)

    def delay(self, retry_index: int) -> float:
        d = min(self.base_delay_s * (2.0 ** max(retry_index - 1, 0)),
                self.max_delay_s)
        if self.jitter > 0:
            d *= self.rng.uniform(1.0 - self.jitter, 1.0 + self.jitter)
        return max(d, 0.0)


def policy_from_config(**overrides) -> RetryPolicy:
    """The shared policy, from core/config.py. Reads config.ARGS at call
    time (init() rebinds the singleton), with H2O3TPU_INFRA_* env
    overrides applied on top so processes that never call init() — the
    bench parent, probe children — still honor the knobs."""
    args = _config.ARGS
    env = os.environ.get
    kw = dict(
        max_attempts=int(env("H2O3TPU_INFRA_MAX_ATTEMPTS",
                             args.infra_max_attempts)),
        base_delay_s=float(env("H2O3TPU_INFRA_BACKOFF_BASE_S",
                               args.infra_backoff_base_s)),
        max_delay_s=float(env("H2O3TPU_INFRA_BACKOFF_MAX_S",
                              args.infra_backoff_max_s)))
    kw.update(overrides)
    return RetryPolicy(**kw)


def retry_call(fn: Callable[[], Any], policy: Optional[RetryPolicy] = None,
               site: str = "call",
               on_retry: Optional[Callable[[BaseException, int], None]] = None):
    """Run ``fn`` under the retry policy; only infra-class errors are
    retried, anything else propagates immediately."""
    from h2o3_tpu import telemetry
    policy = policy or policy_from_config()
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - classified below
            if attempt >= policy.max_attempts or not is_infra_error(e):
                raise
            telemetry.counter("infra_retries_total", site=site).inc()
            d = policy.delay(attempt)
            log.warning("%s: infra error (attempt %d/%d), retrying in "
                        "%.1fs: %s", site, attempt, policy.max_attempts,
                        d, e)
            if on_retry is not None:
                on_retry(e, attempt)
            policy.sleep(d)


# ------------------------------------------------------------ liveness probe


def bounded_call(fn: Callable[[], Any], timeout_s: float,
                 name: str = "bounded-call") -> Any:
    """Run ``fn`` on a daemon thread with a hard deadline.

    A wedged worker accepts a transfer/collective and never completes
    it; the sync is the part that hangs. On deadline the worker thread
    is abandoned (it dies with the process — for a dead backend that is
    imminent anyway) and a classified DEADLINE_EXCEEDED error is raised
    so retry/degradation layers treat it as infra-class."""
    done = threading.Event()
    box: Dict[str, Any] = {}

    def _runner():
        try:
            box["val"] = fn()
        except BaseException as e:  # noqa: BLE001 - reraised below
            box["err"] = e
        finally:
            done.set()

    t = threading.Thread(target=_runner, daemon=True, name=name)
    t.start()
    if not done.wait(timeout_s):
        raise TimeoutError(
            f"DEADLINE_EXCEEDED: {name} hung > {timeout_s}s")
    if "err" in box:
        raise box["err"]
    return box.get("val")


def _probe_once() -> None:
    maybe_fail("probe")
    import jax
    import numpy as np
    devs = jax.devices()
    if not devs:
        raise RuntimeError("UNAVAILABLE: backend reports no devices")
    # tiny round-trip: host -> HBM -> compute -> host. A wedged worker
    # accepts the transfer but never completes it; the float() sync is
    # the part that hangs, which is why probe_backend bounds it.
    x = jax.device_put(np.arange(8.0, dtype=np.float32), devs[0])
    total = float(x.sum())
    if total != 28.0:
        raise RuntimeError(f"INTERNAL: probe round-trip corrupt ({total})")


def probe_backend(timeout_s: Optional[float] = None) -> float:
    """Liveness probe; returns round-trip seconds. Raises a classified
    infra error when the backend is dead, corrupt, or slower than
    ``timeout_s`` (default ARGS.probe_timeout_s; 0/None = unbounded)."""
    from h2o3_tpu import telemetry
    if timeout_s is None:
        timeout_s = float(getattr(_config.ARGS, "probe_timeout_s",
                                  0.0)) or None
    t0 = time.time()
    try:
        if timeout_s:
            bounded_call(_probe_once, timeout_s, name="backend-probe")
        else:
            _probe_once()
    except BaseException:
        telemetry.counter("backend_probe_failures_total").inc()
        raise
    telemetry.counter("backend_probes_total").inc()
    return time.time() - t0


def probe_with_retry(policy: Optional[RetryPolicy] = None,
                     timeout_s: Optional[float] = None) -> float:
    """Probe under the shared retry policy (bench pre-flight)."""
    return retry_call(lambda: probe_backend(timeout_s),
                      policy=policy, site="probe")
