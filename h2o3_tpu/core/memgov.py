"""MemoryGovernor — HBM as a governed resource.

Reference: the reference platform budgets its heap centrally
(water/MemoryManager.java: MEM_MAX and the CAN_ALLOC gate) and lets the
Cleaner thread swap cold Values to ice against that budget
(water/Cleaner.java:85-162). The TPU port had only the raw mechanics:
an LRU spiller with no budget source of truth, an OOM "recovery" that
purged the jit cache and restarted from round 0, and a ``/3/Cloud``
reporting ``free_mem: 0``.

This module is the single budget truth plus the policies around it:

- **Budget resolution** (``device_limit_bytes`` / ``budget_bytes``):
  device ``bytes_limit`` when the backend reports it, else the
  ``H2O3TPU_HBM_BUDGET_MB`` knob (deterministic and testable on CPU,
  where ``memory_stats()`` is empty), else the tracked sum of resident
  frame/cache bytes. ``ops/merge.py``'s out-size cap and
  ``core/cleaner.py``'s ``pressure()`` both route through here.
- **Predictive admission** (``admit_fit`` / ``reserve``): before a fit
  dispatches, its device footprint is estimated from the input frame
  bytes plus the roofline byte estimators (telemetry/roofline.py); a
  fit that would overshoot first spills cold frames via the Cleaner and
  only then is rejected pre-dispatch with an actionable error naming
  projected vs available bytes. Concurrent fits hold reservations in a
  ledger so two individually-admissible fits cannot jointly overshoot —
  bounded wait for a release, then reject (the AdmissionGate contract
  of api/server.py, applied to bytes instead of request slots).
- **OOM eviction** (``evict_for_oom``): the job supervisor's
  RESOURCE_EXHAUSTED escalation ladder (core/job.py) calls in here to
  drop the per-frame ``device_matrix``/``bin_frame`` caches — device
  residents that were previously pinned for the process lifetime — and
  spill every cold frame, before resuming the fit from its checkpoint.
- **Memory truth** (``snapshot`` / ``refresh_gauges``): the
  ``hbm_bytes_in_use`` / ``hbm_budget_bytes`` / ``frames_spilled_bytes``
  gauges, and the governor-backed ``free_mem``/``max_mem``/``swap_mem``
  of GET /3/Cloud.

Telemetry: the gauges above plus ``frame_spills_total``,
``frame_restores_total``, ``fit_admission_rejections_total{reason}``,
``oom_recoveries_total{stage}`` (README §Memory governance).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

from h2o3_tpu.core import config as _config
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.memgov")


class MemoryBudgetExceeded(ValueError):
    """Pre-dispatch admission rejection — deliberately a ValueError so
    the watchdog never retries it and the REST tier maps it to 412 with
    the H2OErrorV3 shape (api/server.py error mapping). The message
    names projected vs available bytes so the client can act (free
    frames, raise H2O3TPU_HBM_BUDGET_MB, or shrink the fit)."""

    def __init__(self, msg: str, projected: int = 0, available: int = 0,
                 budget: int = 0):
        super().__init__(msg)
        self.projected = int(projected)
        self.available = int(available)
        self.budget = int(budget)


class Reservation:
    """One fit's entry in the admission ledger."""

    __slots__ = ("owner", "nbytes", "ts", "released")

    def __init__(self, owner: str, nbytes: int):
        self.owner = owner
        self.nbytes = int(nbytes)
        self.ts = time.monotonic()
        self.released = False

    def __repr__(self):
        return f"<Reservation {self.owner} {self.nbytes / 1e6:.1f}MB>"


# auxiliary device-cache registry: subsystems holding device bytes
# OUTSIDE the DKV frame caches (e.g. the serving tier's compiled-scorer
# cache) register here so the eviction ladders can reclaim them the
# same way they drop frame device caches
_AUX_LOCK = threading.Lock()
_AUX_CACHES: Dict[str, tuple] = {}   # name -> (nbytes_fn, evict_fn)


def register_aux_cache(name: str, nbytes_fn, evict_fn) -> None:
    """Register an auxiliary device cache with the governor.

    ``nbytes_fn() -> int`` reports the cache's current device bytes;
    ``evict_fn(exclude=None) -> int`` drops it and returns bytes freed.
    Idempotent by name (re-registration replaces the hooks)."""
    with _AUX_LOCK:
        _AUX_CACHES[name] = (nbytes_fn, evict_fn)


def aux_cache_bytes() -> int:
    """Device bytes held by registered auxiliary caches."""
    total = 0
    with _AUX_LOCK:
        hooks = list(_AUX_CACHES.values())
    for nbytes_fn, _ in hooks:
        try:
            total += int(nbytes_fn() or 0)
        except Exception:   # noqa: BLE001 - accounting is best-effort
            pass
    return total


def _evict_aux_caches(exclude: Optional[set] = None) -> int:
    freed = 0
    with _AUX_LOCK:
        hooks = list(_AUX_CACHES.items())
    for name, (_, evict_fn) in hooks:
        try:
            freed += int(evict_fn(exclude=exclude) or 0)
        except Exception as e:   # noqa: BLE001 - one bad hook must not
            log.warning("aux cache '%s' eviction failed: %s", name, e)
    return freed


def _frame_cache_nbytes(fr) -> int:
    """Device bytes pinned by a frame's derived caches: the stacked
    ``device_matrix`` arrays and the ``bin_frame`` BinnedMatrix results
    (frame/frame.py, frame/binning.py)."""
    total = 0
    for m in list(getattr(fr, "_matrix_cache", {}).values()):
        total += int(getattr(m, "nbytes", 0) or 0)
    for bm in list(getattr(fr, "_bin_cache", {}).values()):
        for attr in ("bins", "edges"):
            a = getattr(bm, attr, None)
            total += int(getattr(a, "nbytes", 0) or 0)
        for t in list(getattr(bm, "_tile_cache", {}).values()):
            total += int(getattr(t, "nbytes", 0) or 0)
    return total


# level passes whose streamed bytes stand for a tree fit's working set
TREE_WORKING_LEVELS = 6.0


def estimate_fit_bytes(algo: str, params: Optional[Dict], frame, x,
                       validation_frame=None,
                       row_bytes: Optional[int] = None) -> int:
    """Projected device footprint of one fit: the resident input frames,
    the stacked f32 design matrix the builders materialize, and one
    algo-native unit's worth of the roofline streamed-bytes estimate
    (one tree / one IRLS iteration / one epoch — the transient working
    set alive between chunk boundaries).

    Where the device reports its own memory, the budget it is held to is
    ONE device's (``bytes_limit`` and ``bytes_in_use`` of device 0), so
    the projection is a device's share: every part above is row-sized
    and the rows are sharded over the mesh's ``data`` axis (a 116M-row
    fit on four chips was refused as 27 GB against one chip's 16,
    PERF.md §6, PR 39). Without device stats the budget is the knob's,
    held against the tracked total, and so is the projection.
    ``row_bytes``: a row's bytes of the fit's design and row state, as
    ``ModelBuilder.design_row_bytes`` counts them; else 4 B a feature."""
    from h2o3_tpu.core.cleaner import _frame_nbytes, device_memory_stats
    from h2o3_tpu.parallel.mesh import data_size
    est = _frame_nbytes(frame)
    if validation_frame is not None and validation_frame is not frame:
        est += _frame_nbytes(validation_frame)
    feats = max(len(x or []), 1)
    npad = int(getattr(frame, "nrows_padded", None)
               or getattr(frame, "nrows", 0) or 0)
    est += npad * (feats * 4 if row_bytes is None else int(row_bytes))
    try:
        from h2o3_tpu.telemetry import roofline
        cost = roofline.analytic_fit_cost(algo, params or {}, None,
                                          frame, x)
    except Exception:   # noqa: BLE001 - estimate must never block a fit
        cost = None
    if cost:
        d = cost.get("detail", {})
        units = float(d.get("trees") or d.get("iterations") or 0.0)
        if not units:
            # DL details carry samples; one epoch = nrows samples
            samples = float(d.get("samples", 0.0) or 0.0)
            rows = float(getattr(frame, "nrows", 0) or 1)
            units = samples / rows if samples else 1.0
        depth = float(d.get("depth") or 0.0)
        if depth > TREE_WORKING_LEVELS:
            # a tree streams its rows once a level, but what is alive
            # between chunk boundaries is the row state, whatever the
            # depth: count the passes the flagship depth stands for (the
            # depth-20 default forest read 25 GB a tree here and was
            # refused a chip it fits, PERF.md §6, PR 35)
            units *= depth / TREE_WORKING_LEVELS
        est += int(float(cost.get("bytes", 0.0)) / max(units, 1.0))
    if device_memory_stats():
        est = -(-int(est) // data_size())
    return int(est)


class MemoryGovernor:
    """Process-wide HBM budget arbiter (singleton ``governor``)."""

    def __init__(self):
        self._cond = threading.Condition(threading.Lock())
        self._reservations: List[Reservation] = []
        self._spilled_bytes = 0      # live bytes on ice (npz spills)
        self._mirror_bytes = 0       # live mirror blobs (durability)

    # -- budget truth --------------------------------------------------
    def device_limit_bytes(self) -> int:
        """The hard budget: device ``bytes_limit`` when the backend
        reports one, else the ``H2O3TPU_HBM_BUDGET_MB`` knob in bytes;
        0 = no limit known (ungoverned)."""
        from h2o3_tpu.core.cleaner import device_memory_stats
        stats = device_memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
        # env read at call time (policy_from_config pattern): tests and
        # bench children set the knob without rebuilding config.ARGS
        mb = os.environ.get("H2O3TPU_HBM_BUDGET_MB")
        if mb is None:
            mb = getattr(_config.ARGS, "hbm_budget_mb", 0)
        try:
            return int(float(mb)) << 20
        except (TypeError, ValueError):
            return 0

    def governed(self) -> bool:
        return self.device_limit_bytes() > 0 and self._mode() != "off"

    def budget_bytes(self) -> int:
        """The effective budget every surface reports: the hard limit,
        or (nothing known) the tracked resident bytes themselves."""
        return self.device_limit_bytes() or self.resident_bytes()

    def _mode(self) -> str:
        return str(os.environ.get("H2O3TPU_MEMGOV",
                                  getattr(_config.ARGS, "memgov", "auto"))
                   ).lower()

    def _wait_s(self) -> float:
        env = os.environ.get("H2O3TPU_MEMGOV_WAIT_S")
        if env is not None:
            return float(env)
        return float(getattr(_config.ARGS, "memgov_wait_s", 5.0))

    # -- accounting ----------------------------------------------------
    def resident_bytes(self) -> int:
        """Tracked device bytes: every in-memory DKV frame's columns
        plus its derived device caches (stubs on ice count zero)."""
        from h2o3_tpu.core.cleaner import _frame_nbytes
        from h2o3_tpu.core.kv import DKV
        from h2o3_tpu.frame.frame import Frame
        total = 0
        for key in list(DKV.keys()):
            v = DKV.get_raw(key)
            if isinstance(v, Frame):
                total += _frame_nbytes(v) + _frame_cache_nbytes(v)
            del v
        return total

    def bytes_in_use(self) -> int:
        """Device bytes_in_use when the backend reports it, else the
        tracked resident bytes."""
        from h2o3_tpu.core.cleaner import device_memory_stats
        stats = device_memory_stats()
        if stats:
            return int(stats.get("bytes_in_use", 0))
        return self.resident_bytes()

    def pressure(self) -> float:
        """Fraction of the budget in use; 0 when ungoverned (no limit
        to be under pressure against)."""
        limit = self.device_limit_bytes()
        if not limit:
            return 0.0
        return self.bytes_in_use() / limit

    def spilled_bytes(self) -> int:
        with self._cond:
            return self._spilled_bytes

    def note_spill(self, nbytes: int) -> None:
        """A frame went to ice (Cleaner npz spill)."""
        with self._cond:
            self._spilled_bytes += max(int(nbytes), 0)
        self.refresh_gauges()

    def note_unspill(self, nbytes: int) -> None:
        """An ice copy was reclaimed (restore won / key removed /
        stub clobbered by a newer put)."""
        with self._cond:
            self._spilled_bytes = max(
                self._spilled_bytes - max(int(nbytes), 0), 0)
            self._cond.notify_all()
        self.refresh_gauges()

    def mirror_bytes(self) -> int:
        with self._cond:
            return self._mirror_bytes

    def account_mirror(self, delta: int) -> None:
        """Durability mirror blobs flow through the governor's ledger
        like spills do (core/durability.py write-through), so
        ``frames_mirrored_bytes`` publishes from the same memory-truth
        surface as the other byte gauges."""
        with self._cond:
            self._mirror_bytes = max(self._mirror_bytes + int(delta), 0)
        self.refresh_gauges()

    def reserved_bytes(self) -> int:
        with self._cond:
            return sum(r.nbytes for r in self._reservations)

    # -- eviction ------------------------------------------------------
    def evict_frame_caches(self, exclude: Optional[set] = None) -> int:
        """Drop every frame's device_matrix/bin_frame caches (previously
        pinned for the process lifetime) plus any registered auxiliary
        device caches (compiled scorers etc.); returns bytes released."""
        from h2o3_tpu.core.kv import DKV
        from h2o3_tpu.frame.frame import Frame
        freed = 0
        for key in list(DKV.keys()):
            if exclude and key in exclude:
                continue
            v = DKV.get_raw(key)
            if isinstance(v, Frame):
                freed += v.drop_device_caches()
            del v
        freed += _evict_aux_caches(exclude=exclude)
        if freed:
            log.info("evicted %.1f MB of frame device caches", freed / 1e6)
        return freed

    def evict_for_admission(self, needed: int,
                            exclude: Optional[set] = None) -> int:
        """Spill cold frames until ``needed`` bytes fit under the budget
        (or nothing cold remains); returns frames spilled."""
        from h2o3_tpu.core.cleaner import cleaner
        limit = self.device_limit_bytes()
        freed = 0
        while self.bytes_in_use() + self.reserved_bytes() + needed > limit:
            spilled = cleaner.spill_coldest(1, exclude=exclude)
            if not spilled:
                break
            freed += 1
        return freed

    def evict_for_oom(self, exclude: Optional[set] = None) -> int:
        """The heavy rung of the OOM ladder: drop every derived device
        cache AND spill every cold frame. Returns cache bytes freed."""
        from h2o3_tpu.core.cleaner import cleaner
        freed = self.evict_frame_caches(exclude=exclude)
        cleaner.spill_coldest(n=1 << 30, exclude=exclude)
        self.refresh_gauges()
        return freed

    # -- admission -----------------------------------------------------
    def reserve(self, owner: str, nbytes: int,
                exclude: Optional[set] = None,
                timeout_s: Optional[float] = None) -> Reservation:
        """Admit ``nbytes`` of projected footprint or raise
        ``MemoryBudgetExceeded``. Spills cold frames first; when other
        jobs' reservations are what blocks admission, waits (bounded)
        for a release before rejecting."""
        from h2o3_tpu import telemetry
        rsv = Reservation(owner, nbytes)
        if not self.governed():
            with self._cond:
                self._reservations.append(rsv)
            return rsv
        limit = self.device_limit_bytes()
        deadline = time.monotonic() + (self._wait_s()
                                       if timeout_s is None else timeout_s)
        while True:
            in_use = self.bytes_in_use()
            reserved = self.reserved_bytes()
            if in_use + reserved + nbytes <= limit:
                with self._cond:
                    self._reservations.append(rsv)
                self.refresh_gauges()
                return rsv
            # rung 1: make room by spilling cold frames
            self.evict_for_admission(nbytes, exclude=exclude)
            in_use = self.bytes_in_use()
            if in_use + self.reserved_bytes() + nbytes <= limit:
                continue
            # rung 2: the blocker is other fits' reservations — wait
            # (bounded) for one to release, AdmissionGate-style
            if self.reserved_bytes() > 0 and time.monotonic() < deadline:
                with self._cond:
                    self._cond.wait(timeout=min(
                        0.25, max(deadline - time.monotonic(), 0.01)))
                continue
            reason = "contention" if self.reserved_bytes() > 0 else "budget"
            available = max(limit - in_use - self.reserved_bytes(), 0)
            telemetry.counter("fit_admission_rejections_total",
                              reason=reason).inc()
            log.warning("admission rejected for %s: projected %d B > "
                        "available %d B (budget %d B, reason=%s)",
                        owner, nbytes, available, limit, reason)
            raise MemoryBudgetExceeded(
                f"fit '{owner}' rejected before dispatch: projected "
                f"device footprint {nbytes} bytes exceeds available "
                f"HBM {available} bytes (budget {limit} bytes, "
                f"{in_use} in use, {self.reserved_bytes()} reserved by "
                f"concurrent fits; reason={reason}). Free or delete "
                f"frames, raise H2O3TPU_HBM_BUDGET_MB, or shrink the "
                f"fit.", projected=nbytes, available=available,
                budget=limit)

    def admit_replica(self, model_key: str, nbytes: int) -> Reservation:
        """Serving-replica admission (ISSUE 17): reserve the replica's
        projected device bytes NOW or raise ``MemoryBudgetExceeded`` —
        no bounded wait, because a fleet peer that cannot take the
        replica must DECLINE registration immediately so the registry
        offers it to the next healthy peer instead of queueing behind
        fits. The reservation lives as long as the replica; the fleet
        releases it on deregistration/eviction."""
        return self.reserve(f"replica:{model_key}", nbytes,
                            timeout_s=0.0)

    def release(self, rsv: Optional[Reservation]) -> None:
        if rsv is None or rsv.released:
            return
        with self._cond:
            rsv.released = True
            try:
                self._reservations.remove(rsv)
            except ValueError:
                pass
            self._cond.notify_all()
        self.refresh_gauges()

    def admit_fit(self, algo: str, params: Optional[Dict], frame, x,
                  validation_frame=None,
                  row_bytes: Optional[int] = None) -> Reservation:
        """ModelBuilder.train's pre-dispatch hook: estimate → reserve
        (spill / bounded wait / reject)."""
        projected = estimate_fit_bytes(algo, params, frame, x,
                                       validation_frame, row_bytes)
        exclude = {getattr(frame, "key", None),
                   getattr(validation_frame, "key", None)} - {None}
        return self.reserve(f"{algo}:{getattr(frame, 'key', '?')}",
                            projected, exclude=exclude)

    # -- surfacing -----------------------------------------------------
    def snapshot(self) -> Dict:
        limit = self.device_limit_bytes()
        in_use = self.bytes_in_use()
        budget = limit or in_use
        return {"budget_bytes": budget,
                "limit_bytes": limit,
                "bytes_in_use": in_use,
                "free_bytes": max(budget - in_use, 0),
                "spilled_bytes": self.spilled_bytes(),
                "aux_cache_bytes": aux_cache_bytes(),
                "reserved_bytes": self.reserved_bytes(),
                "reservations": len(self._reservations),
                "governed": self.governed()}

    def refresh_gauges(self) -> None:
        """Publish the memory truth into the metrics registry (and
        therefore flight-recorder capsules + /3/Cloud fan-in)."""
        try:
            from h2o3_tpu import telemetry
            telemetry.gauge("hbm_budget_bytes").set(self.budget_bytes())
            telemetry.gauge("hbm_bytes_in_use").set(self.bytes_in_use())
            telemetry.gauge("frames_spilled_bytes").set(
                self.spilled_bytes())
            telemetry.gauge("frames_mirrored_bytes").set(
                self.mirror_bytes())
        except Exception:   # noqa: BLE001 - gauges are best-effort
            pass

    def reset(self) -> None:
        """Shutdown/test hook: drop all ledger state."""
        with self._cond:
            self._reservations.clear()
            self._spilled_bytes = 0
            self._mirror_bytes = 0
            self._cond.notify_all()


governor = MemoryGovernor()
