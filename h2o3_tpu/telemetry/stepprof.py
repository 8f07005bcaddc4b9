"""Training-step profiler — per-chunk phase timing, pod skew, stragglers.

PR 19 shipped pod-global sharded training with an honest 0.54x 2-host
scaling number and nothing that says *why*: ``collective_bytes_total``
counts bytes but no instrument decomposes a training step into where
the wall clock went. This module is that instrument. Every fit carries
a bounded ring of per-chunk phase timings:

    host        python between dispatches — binning, stop checks,
                job.update, transfers, fault-injected delays
    compute     device dispatch → block_until_ready of the chunk's
                compiled scan/solve
    collective  timed psum / frame_reduce waits, plus the per-chunk
                barrier probe on a multi-process mesh (the wait a fast
                host spends on a straggler)
    checkpoint  in-fit snapshot writes (core/recovery.py)

The accounting is a PARTITION of the fit's wall clock: each charger
advances a single ``last_mark`` watermark, so phase sums never exceed
wall time and anything unattributed lands in ``host``.

Chunk loops weave three calls (models/gbm.py, glm.py, deeplearning.py):
``chunk_begin()`` (charges the inter-chunk host gap), ``compute_done()``
(blocks on the chunk outputs and charges compute), ``chunk_end()``
(barrier probe + ring record + ``model_fit_phase_seconds{algo,phase}``
observations on the shared SECONDS_BUCKETS grid, so cluster-merged
quantiles stay exact — telemetry/registry.merged_quantile).

Cross-host: ``snapshot()`` rides the PR 8 cluster fan-in
(telemetry/cluster.py local_snapshot "stepprof" block); the coordinator
calls ``cluster_profile(model_key)`` to merge per-host profiles of ONE
pod-global fit into skew/straggler verdicts — ``pod_step_skew_ratio``
and ``pod_straggler_host`` gauges plus per-host collective-wait shares.
Straggler identity needs no clock sync: a slow host shows up as large
SELF time (total − collective) on itself and as collective wait on
every fast host, because the barrier probe makes the wait observable.

Knobs: ``H2O3TPU_STEPPROF`` (auto|on|off; env over Config.stepprof),
``H2O3TPU_STEPPROF_RING`` (per-fit chunk-ring bound),
``H2O3TPU_STEPPROF_DELAY`` (test-only per-chunk sleep, charged to host
— the fault-injected "slow chunk"/straggler used by tier-1 and bench).
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from h2o3_tpu.telemetry.registry import counter, gauge, histogram

PHASES = ("host", "compute", "collective", "checkpoint")

# completed profiles retained for GET /3/Models/{id}/profile
MAX_COMPLETED = 32
# completed fits published per cluster snapshot (newest first)
SNAPSHOT_FITS = 8
# ring entries shipped per published fit (full ring stays local)
SNAPSHOT_RING = 16


def _knob() -> str:
    env = os.environ.get("H2O3TPU_STEPPROF")
    if env:
        return str(env).lower()
    try:
        from h2o3_tpu.core.config import ARGS
        return str(getattr(ARGS, "stepprof", "auto") or "auto").lower()
    except Exception:   # noqa: BLE001 - config must never gate profiling
        return "auto"


def enabled() -> bool:
    """auto/on profile every fit; off disables the weave entirely."""
    return _knob() != "off"


def ring_size() -> int:
    env = os.environ.get("H2O3TPU_STEPPROF_RING")
    if env is not None:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    try:
        from h2o3_tpu.core.config import ARGS
        return max(1, int(getattr(ARGS, "stepprof_ring", 128)))
    except Exception:   # noqa: BLE001
        return 128


def _delay_s() -> float:
    try:
        return float(os.environ.get("H2O3TPU_STEPPROF_DELAY", "0") or 0)
    except ValueError:
        return 0.0


def _proc_index() -> int:
    try:
        from h2o3_tpu.telemetry.cluster import _identity
        return int(_identity()[0])
    except Exception:   # noqa: BLE001 - identity is best-effort
        return 0


class FitProfile:
    """One fit's phase ledger: bounded per-chunk ring + running totals.

    Single-writer by construction (the fit's worker thread); readers
    (cluster publish, REST) take shallow copies under the lock."""

    __slots__ = ("algo", "nrows", "proc", "t0_wall", "last_mark",
                 "totals", "marks", "ring", "chunks_total", "_cur",
                 "model_key", "seconds", "_token", "_lock")

    def __init__(self, algo: str, nrows: int = 0,
                 ring: Optional[int] = None):
        self.algo = algo
        self.nrows = int(nrows)
        self.proc = _proc_index()
        self.t0_wall = time.time()
        self.last_mark = time.perf_counter()
        self.totals = {p: 0.0 for p in PHASES}
        # wall-clock marks (NOT part of the phase partition): transfer
        # and fetch seconds/calls from the parallel/mesh.py weave
        self.marks: Dict[str, float] = {}
        self.ring: deque = deque(maxlen=ring or ring_size())
        self.chunks_total = 0
        self._cur: Optional[Dict] = None
        self.model_key: Optional[str] = None
        self.seconds = 0.0
        self._token = None
        self._lock = threading.Lock()

    def _charge(self, phase_name: str, dur: float) -> None:
        if dur <= 0.0:
            return
        with self._lock:
            self.totals[phase_name] = \
                self.totals.get(phase_name, 0.0) + dur
            if self._cur is not None:
                ph = self._cur["phases"]
                ph[phase_name] = ph.get(phase_name, 0.0) + dur

    def mark(self, name: str, dur: float) -> None:
        with self._lock:
            self.marks[name] = self.marks.get(name, 0.0) + dur

    def to_dict(self, ring_tail: Optional[int] = None) -> Dict:
        with self._lock:
            ring = list(self.ring)
        if ring_tail is not None:
            ring = ring[-ring_tail:]
        total = sum(self.totals.values())
        coll = self.totals.get("collective", 0.0)
        return {
            "algo": self.algo,
            "model_key": self.model_key,
            "proc": self.proc,
            "nrows": self.nrows,
            "ts": self.t0_wall,
            "seconds": round(self.seconds or total, 6),
            "chunks": self.chunks_total,
            "phases": {p: round(v, 6) for p, v in self.totals.items()},
            "marks": {k: round(v, 6) for k, v in self.marks.items()},
            "collective_share": round(coll / total, 6) if total > 0
            else 0.0,
            "ring": ring,
        }


# active profile on the fit's worker thread (models/model.py _run)
_PROFILE: contextvars.ContextVar[Optional[FitProfile]] = \
    contextvars.ContextVar("h2o3tpu_stepprof", default=None)

_reg_lock = threading.Lock()
# model_key -> completed profile dict, oldest first (REST lookups)
_completed: "OrderedDict[str, Dict]" = OrderedDict()
# live profiles visible to cross-thread readers (cluster publish)
_live: List[FitProfile] = []
# compiled barrier probes keyed by id(mesh)
_barriers: Dict[int, Any] = {}


def active() -> Optional[FitProfile]:
    return _PROFILE.get()


def reset() -> None:
    """Tests only — drop every registry, live profile, and this
    module's metric families (fits trained by OTHER test files in the
    same process would otherwise bleed into SLO-rule assertions)."""
    with _reg_lock:
        _completed.clear()
        del _live[:]
        _barriers.clear()
    try:
        from h2o3_tpu.telemetry.registry import REGISTRY
        for name in ("pod_step_skew_ratio", "pod_straggler_host",
                     "stepprof_fits_total", "model_fit_phase_seconds"):
            REGISTRY.drop(name)
    except Exception:   # noqa: BLE001 - reset is best-effort
        pass


# ------------------------------------------------------------ lifecycle


def start(algo: str, nrows: int = 0) -> Optional[FitProfile]:
    """Attach a profile to the current context; None when disabled."""
    if not enabled():
        return None
    prof = FitProfile(algo, nrows=nrows)
    prof._token = _PROFILE.set(prof)
    with _reg_lock:
        _live.append(prof)
        while len(_live) > MAX_COMPLETED:
            _live.pop(0)
    return prof


def finish(prof: Optional[FitProfile], model_key: Optional[str] = None,
           seconds: Optional[float] = None,
           mfu: Optional[float] = None) -> Optional[Dict]:
    """Close the profile: flush the trailing host gap, register the
    completed record for REST/cluster readers, and attach it to any
    active flight-recorder capsule. Never raises — profiling must never
    fail a fit."""
    if prof is None:
        return None
    try:
        if prof._cur is not None:
            chunk_end()
        now = time.perf_counter()
        prof._charge("host", now - prof.last_mark)
        prof.last_mark = now
        prof.model_key = model_key
        prof.seconds = float(seconds) if seconds else \
            (time.time() - prof.t0_wall)
        # the caller's own wall measurement can bracket more tightly
        # than the charge watermark by sub-ms slack; published seconds
        # must cover the charged span or sum(phases) <= seconds breaks
        prof.seconds = max(prof.seconds, sum(prof.totals.values()))
        if prof._token is not None:
            try:
                _PROFILE.reset(prof._token)
            except ValueError:      # finished on a different context
                _PROFILE.set(None)
        d = prof.to_dict()
        if mfu is not None:
            d["mfu"] = float(mfu)
        with _reg_lock:
            if prof in _live:
                _live.remove(prof)
            if model_key:
                _completed[str(model_key)] = d
                while len(_completed) > MAX_COMPLETED:
                    _completed.popitem(last=False)
        counter("stepprof_fits_total", algo=prof.algo).inc()
        try:
            from h2o3_tpu.telemetry import flight_recorder
            flight_recorder.record_step_profile(
                {k: v for k, v in d.items() if k != "ring"})
        except Exception:   # noqa: BLE001 - capsule capture best-effort
            pass
        return d
    except Exception:   # noqa: BLE001 - profiling must never fail a fit
        return None


# ---------------------------------------------------------- chunk weave


def chunk_begin() -> None:
    """Open a chunk record; the host gap since the last charge (stop
    checks, job.update, binning between chunks) lands in THIS chunk."""
    prof = _PROFILE.get()
    if prof is None:
        return
    if prof._cur is not None:        # dangling (early-stop break)
        chunk_end()
    now = time.perf_counter()
    with prof._lock:
        prof._cur = {"phases": {p: 0.0 for p in PHASES}, "t0": now}
    prof._charge("host", now - prof.last_mark)
    prof.last_mark = now


def compute_done(out: Any = None) -> Any:
    """Block on the chunk's device outputs and charge the window since
    the last mark to ``compute``. With no active profile this is a
    no-op passthrough — dispatch overlap is untouched."""
    prof = _PROFILE.get()
    if prof is None:
        return out
    if out is not None:
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:   # noqa: BLE001 - host-only outputs are fine
            pass
    now = time.perf_counter()
    prof._charge("compute", now - prof.last_mark)
    prof.last_mark = now
    return out


def _mp_mesh():
    """The installed global mesh iff it spans >1 process (the only case
    the barrier probe can observe a straggler). jax-lazy via
    sys.modules so a backend-free process never triggers init."""
    m = sys.modules.get("h2o3_tpu.parallel.mesh")
    if m is None or getattr(m, "_GLOBAL_MESH", None) is None:
        return None
    try:
        mesh = m.get_mesh()     # honors local_mesh_scope overrides
        procs = {getattr(d, "process_index", 0)
                 for d in mesh.devices.flat}
        return mesh if len(procs) > 1 else None
    except Exception:   # noqa: BLE001 - probe is best-effort
        return None


def _barrier_probe(mesh) -> None:
    """Timed 1-element psum over the data axis: a fast host measures
    here the time it spends waiting for the slowest peer to reach the
    same chunk boundary. Compiled once per mesh."""
    import functools
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from h2o3_tpu.parallel import mesh as mesh_mod
    ent = _barriers.get(id(mesh))
    if ent is None:
        n = mesh.shape[mesh_mod.DATA_AXIS]

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(mesh_mod.DATA_AXIS), out_specs=P(),
                           check_vma=False)
        def _ps(x):
            return jax.lax.psum(x, mesh_mod.DATA_AXIS)

        arr = mesh_mod.put_sharded(np.ones((n,), np.float32),
                                   mesh_mod.row_sharding(mesh))
        ent = (jax.jit(_ps), arr)
        if len(_barriers) >= 4:      # stale-mesh backstop
            _barriers.clear()
        _barriers[id(mesh)] = ent
    fn, arr = ent
    jax.block_until_ready(fn(arr))


def chunk_end(**meta) -> None:
    """Close the chunk: test delay (host), barrier probe (collective),
    then record the ring entry and observe every phase into
    ``model_fit_phase_seconds{algo,phase}``."""
    prof = _PROFILE.get()
    if prof is None or prof._cur is None:
        return
    try:
        delay = _delay_s()
        if delay > 0:               # the fault-injected slow chunk
            time.sleep(delay)
        now = time.perf_counter()
        prof._charge("host", now - prof.last_mark)
        prof.last_mark = now
        mesh = _mp_mesh()
        if mesh is not None:
            try:
                _barrier_probe(mesh)
            except Exception:   # noqa: BLE001 - never fail the fit
                pass
            now = time.perf_counter()
            prof._charge("collective", now - prof.last_mark)
            prof.last_mark = now
    finally:
        with prof._lock:
            cur, prof._cur = prof._cur, None
        t_end = time.perf_counter()
        rec = {"dur": round(t_end - cur["t0"], 6),
               "phases": {p: round(v, 6)
                          for p, v in cur["phases"].items()}}
        rec.update(meta)
        with prof._lock:
            prof.ring.append(rec)
            prof.chunks_total += 1
        for p, v in cur["phases"].items():
            # one shared bucket grid (default SECONDS_BUCKETS) so
            # cluster-merged quantiles stay exact (merged_quantile)
            histogram("model_fit_phase_seconds", algo=prof.algo,
                      phase=p).observe(v)


@contextlib.contextmanager
def phase(name: str):
    """Charge a window to a named phase (e.g. ``checkpoint`` around
    core/recovery.py snapshot writes). The gap since the last mark
    stays host time, so the partition remains exact."""
    prof = _PROFILE.get()
    if prof is None:
        yield
        return
    t0 = time.perf_counter()
    prof._charge("host", t0 - prof.last_mark)
    prof.last_mark = t0
    try:
        yield
    finally:
        now = time.perf_counter()
        prof._charge(name, now - t0)
        prof.last_mark = now


def t_mark() -> Optional[float]:
    """Window-open timestamp for ``collective_done`` — None (free) when
    no profile is active."""
    return time.perf_counter() if _PROFILE.get() is not None else None


def collective_done(out: Any, t0: Optional[float]) -> None:
    """Charge a timed psum/frame_reduce window (parallel/map_reduce.py):
    blocks on the reduce output so the wait is observed, charges
    ``collective`` from ``t0``, host before it."""
    prof = _PROFILE.get()
    if prof is None or t0 is None:
        return
    try:
        import jax
        jax.block_until_ready(out)
    except Exception:   # noqa: BLE001
        pass
    now = time.perf_counter()
    prof._charge("host", t0 - prof.last_mark)
    prof._charge("collective", now - t0)
    prof.last_mark = now


def mark(name: str, dur: float) -> None:
    """Accumulate a wall-clock mark (transfer/fetch seconds from the
    parallel/mesh.py weave). NOT part of the phase partition — marks
    annotate where host time went, they don't re-charge it."""
    prof = _PROFILE.get()
    if prof is not None and dur > 0:
        prof.mark(name, dur)


# ----------------------------------------------------------- reads


def profile_for(model_key: str) -> Dict:
    """Completed profile for a model key; KeyError → REST 404."""
    with _reg_lock:
        d = _completed.get(str(model_key))
        if d is None:
            raise KeyError(f"no step profile for model {model_key!r}")
        return dict(d)


def last_fit_phases(algo: str) -> Dict:
    """Most recent completed fit's phase totals for an algo."""
    with _reg_lock:
        for d in reversed(_completed.values()):
            if d.get("algo") == algo:
                return {"phases": dict(d.get("phases") or {}),
                        "collective_share": d.get("collective_share",
                                                  0.0),
                        "chunks": d.get("chunks", 0)}
    return {}


def snapshot() -> Dict:
    """This process's publishable block (cluster fan-in): bounded
    recent completed fits + inflight marks."""
    with _reg_lock:
        fits = [dict(d) for d in list(_completed.values())
                [-SNAPSHOT_FITS:]][::-1]
        live = list(_live)
    for f in fits:
        f["ring"] = (f.get("ring") or [])[-SNAPSHOT_RING:]
    inflight = []
    for prof in live:
        try:
            d = prof.to_dict(ring_tail=SNAPSHOT_RING)
            d["inflight"] = True
            inflight.append(d)
        except Exception:   # noqa: BLE001 - racing a finishing fit
            pass
    return {"proc": _proc_index(), "fits": fits, "inflight": inflight}


# ------------------------------------------------------- skew / cluster


def compute_skew(per_host: Dict[Any, Dict]) -> Dict:
    """Pure (jax-free) skew verdict over per-host profiles of ONE fit.

    SELF time = total − collective: a straggler does NOT wait, so its
    collective share stays low while every fast host's rises — the
    host with max self time IS the straggler, no clock sync needed."""
    hosts: Dict[str, Dict] = {}
    for node, f in (per_host or {}).items():
        ph = dict(f.get("phases") or {})
        total = sum(ph.values()) or float(f.get("seconds") or 0.0)
        coll = float(ph.get("collective", 0.0))
        self_t = max(total - coll, 0.0)
        key = str(node)
        hosts[key] = {
            "proc": int(f.get("proc", key if key.isdigit() else 0)),
            "total": round(total, 6),
            "collective": round(coll, 6),
            "self": round(self_t, 6),
            "collective_share": round(coll / total, 6)
            if total > 0 else 0.0,
            "phases": ph,
        }
    if not hosts:
        return {"hosts": {}, "skew_ratio": 0.0,
                "straggler": None, "straggler_proc": None}
    straggler = max(hosts, key=lambda n: hosts[n]["self"])
    selfs = [h["self"] for h in hosts.values()]
    ratio = min(max(selfs) / max(min(selfs), 1e-9), 1e6) \
        if max(selfs) > 0 else 1.0
    return {"hosts": hosts,
            "skew_ratio": round(ratio, 4),
            "straggler": straggler,
            "straggler_proc": hosts[straggler]["proc"]}


def cluster_profile(model_key: str) -> Dict:
    """Merge every host's profile of one pod-global fit (PR 8 fan-in)
    into the skew/straggler verdict, and publish it as the
    ``pod_step_skew_ratio`` / ``pod_straggler_host`` gauges."""
    from h2o3_tpu.telemetry import cluster
    with _reg_lock:
        local = _completed.get(str(model_key))
    algo = (local or {}).get("algo")
    snap = cluster.collect()
    per_host: Dict[str, Dict] = {}
    for node, s in (snap.get("nodes") or {}).items():
        blk = (s or {}).get("stepprof") or {}
        fits = blk.get("fits") or []
        match = next((f for f in fits
                      if f.get("model_key") == model_key), None)
        if match is None and algo:
            # pod-global fits generate per-process model keys; fall
            # back to the peer's most recent fit of the same algo
            match = next((f for f in fits if f.get("algo") == algo),
                         None)
        if match is not None:
            per_host[str(node)] = match
    skew = compute_skew(per_host)
    if skew["straggler"] is not None:
        gauge("pod_step_skew_ratio").set(float(skew["skew_ratio"]))
        gauge("pod_straggler_host").set(float(skew["straggler_proc"]))
    skew.update({"model_key": model_key,
                 "process_count": snap.get("process_count", 1),
                 "stale_nodes": snap.get("stale_nodes", [])})
    return skew
