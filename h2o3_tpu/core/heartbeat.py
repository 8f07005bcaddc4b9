"""Cloud heartbeat — peer-health monitoring and fail-fast degradation.

Reference: water/HeartBeatThread.java:16 pings every node each second;
water/Paxos.java ejects nodes that miss their beat from the committed
cloud, and every MRTask blocked on a dead node fails instead of hanging
forever. The TPU-native hazard is worse: a collective (psum) issued
against a mesh with a dead peer never returns — there is no RPC timeout
inside XLA — so every frame_reduce would hang the worker thread.

This module runs the HeartBeatThread analogue:

- **Single-process cloud** (one controller, local devices): each round
  is a tiny psum over the mesh — the same dispatch path every
  frame_reduce takes — bounded by the watchdog's thread-timeout prober
  (``bounded_call``). A wedged backend turns the round into a miss
  instead of a hang.
- **Multi-process cloud** (jax.distributed): rounds ride the
  coordination-service key-value store (the control plane that formed
  the cloud), NOT device collectives — two Python threads issuing
  collectives in different orders across processes can deadlock the
  mesh, which is exactly the failure this thread must detect, so the
  monitor stays out-of-band like the reference's heartbeat UDP channel
  vs. compute TCP split. Each process publishes ``hb/<pid> = now`` every
  round and reads every peer's last beat back: genuine per-peer
  last-seen tracking.

Misses accumulate per round; ``miss_budget`` consecutive misses (or a
peer's beat going stale past ``interval * miss_budget``) flips the cloud
unhealthy. The flag is checked at every chunk boundary
(parallel/map_reduce.py, Job.update via request_ctx.cancel_point) so
in-flight jobs fail within one heartbeat interval with a classified
:class:`CloudUnhealthyError` — infra-class, so job-level retries and
grid/AutoML ``recovery_dir`` snapshots compose with it — rather than
blocking on a collective that will never complete.

Telemetry: ``heartbeat_rounds_total``, ``heartbeat_misses_total{peer=}``,
``cloud_peers_healthy`` gauge (README §Cloud formation).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Any, Dict, List, Optional

from h2o3_tpu.core import config as _config
from h2o3_tpu.core import watchdog
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.heartbeat")

KV_PREFIX = "h2o3tpu/hb/"


class CloudUnhealthyError(Exception):
    """The cloud missed its heartbeat budget; collectives can no longer
    be trusted to complete. The message carries an INFRA_SIGNS token so
    ``watchdog.is_infra_error`` classifies it retryable — job-level
    retries and recovery_dir snapshot/resume compose with it."""

    def __init__(self, reason: str, site: str = ""):
        at = f" at {site}" if site else ""
        super().__init__(f"UNAVAILABLE: cloud unhealthy{at} — {reason}")
        self.reason = reason
        self.site = site


class HeartbeatMonitor:
    """Background peer-health thread (one per process, like the
    reference's one HeartBeatThread per node)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.interval_s = 1.0
        self.miss_budget = 3
        self.timeout_s = 5.0
        self.rounds = 0
        self.consecutive_misses = 0
        # pid -> {"last_seen": wall-clock ts of last agreement/beat,
        #         "healthy": bool}
        self.peers: Dict[int, Dict[str, Any]] = {}
        # fast-path flag read lock-free at every chunk boundary
        self._unhealthy_reason: Optional[str] = None
        self._psum_fn = None            # cached per-mesh agreement fn
        self._psum_mesh = None
        # captured ONCE at start(): jax.process_count()/process_index()
        # can re-enter (and block on) backend initialization, which must
        # never happen from the monitor thread mid-round
        self._nproc = 1
        self._pid = 0

    # -------------------------------------------------------- lifecycle
    def start(self, interval_s: Optional[float] = None,
              miss_budget: Optional[int] = None,
              timeout_s: Optional[float] = None,
              thread: bool = True) -> None:
        """Launch the monitor (idempotent). Defaults from core/config.py
        (H2O3TPU_HEARTBEAT_{INTERVAL_S,MISS_BUDGET,TIMEOUT_S}).
        ``thread=False`` configures peers/knobs but leaves rounds to the
        caller — deterministic tests and the bench cloud leg drive
        ``round()`` synchronously."""
        args = _config.ARGS
        with self._lock:
            self.interval_s = float(interval_s
                                    if interval_s is not None
                                    else args.heartbeat_interval_s)
            self.miss_budget = int(miss_budget
                                   if miss_budget is not None
                                   else args.heartbeat_miss_budget)
            self.timeout_s = float(timeout_s
                                   if timeout_s is not None
                                   else args.heartbeat_timeout_s
                                   ) or self.interval_s
            if self._thread is not None:
                return
            self._stop.clear()
            self._unhealthy_reason = None
            self.consecutive_misses = 0
            now = time.time()
            import jax
            self._nproc = jax.process_count()
            self._pid = jax.process_index()
            self.peers = {p: {"last_seen": now, "healthy": True}
                          for p in range(self._nproc)}
            if thread:
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True,
                                                name="cloud-heartbeat")
                self._thread.start()
        log.info("heartbeat up: interval=%.2fs miss_budget=%d timeout=%.2fs",
                 self.interval_s, self.miss_budget, self.timeout_s)

    def stop(self) -> None:
        """Stop and reset so a re-formed cloud starts clean."""
        with self._lock:
            t, self._thread = self._thread, None
        self._stop.set()
        if t is not None:
            t.join(timeout=max(self.timeout_s, 2.0) + 1.0)
        with self._lock:
            self._unhealthy_reason = None
            self.consecutive_misses = 0
            self.peers = {}
            self._psum_fn = None
            self._psum_mesh = None

    @property
    def running(self) -> bool:
        return self._thread is not None

    # ---------------------------------------------------------- status
    def healthy(self) -> bool:
        return self._unhealthy_reason is None

    def reason(self) -> Optional[str]:
        return self._unhealthy_reason

    def mark_unhealthy(self, reason: str) -> None:
        """Flip the cloud unhealthy (round-miss budget exhausted, or a
        test/operator decision). Chunk boundaries observe it on their
        next dispatch."""
        from h2o3_tpu import telemetry
        first = self._unhealthy_reason is None
        self._unhealthy_reason = reason
        with self._lock:
            for st in self.peers.values():
                st["healthy"] = False
            telemetry.gauge("cloud_peers_healthy").set(0)
        if first:
            log.error("cloud UNHEALTHY: %s", reason)

    def mark_healthy(self) -> None:
        """Clear the unhealthy flag and per-peer health. ``last_seen``
        is deliberately NOT touched: it tracks actual observed beats
        (kv rounds) or completed agreements (psum rounds) — refreshing
        it here would mask a dead peer's staleness behind every
        successful round."""
        from h2o3_tpu import telemetry
        was = self._unhealthy_reason
        self._unhealthy_reason = None
        with self._lock:
            self.consecutive_misses = 0
            for st in self.peers.values():
                st["healthy"] = True
            telemetry.gauge("cloud_peers_healthy").set(len(self.peers))
        if was is not None:
            log.warning("cloud healthy again (was: %s)", was)

    def status(self) -> dict:
        """Peer-health block for cluster_info() / GET /3/Cloud."""
        with self._lock:
            peers = {str(p): dict(st) for p, st in self.peers.items()}
        return {
            "running": self.running,
            "healthy": self.healthy(),
            "reason": self._unhealthy_reason,
            "interval_s": self.interval_s,
            "miss_budget": self.miss_budget,
            "rounds": self.rounds,
            "consecutive_misses": self.consecutive_misses,
            "peers": peers,
        }

    # ---------------------------------------------------------- rounds
    def _loop(self) -> None:
        # first round fires immediately so a freshly formed cloud gets
        # a last_seen baseline before any job dispatches
        while True:
            try:
                self.round()
            except Exception as e:      # noqa: BLE001 - never kill the loop
                log.warning("heartbeat round error (uncounted): %s", e)
            if self._stop.wait(self.interval_s):
                return

    def round(self) -> bool:
        """One heartbeat round; returns True on agreement. Public so
        tests and the bench cloud leg can drive rounds synchronously."""
        from h2o3_tpu import telemetry
        telemetry.counter("heartbeat_rounds_total").inc()
        with self._lock:
            self.rounds += 1
        try:
            watchdog.maybe_fail("heartbeat")
            if self._nproc > 1:
                stale = watchdog.bounded_call(
                    self._kv_round, self.timeout_s, name="heartbeat-kv")
            else:
                watchdog.bounded_call(
                    self._psum_round, self.timeout_s, name="heartbeat-psum")
                stale = []
        except Exception as e:          # noqa: BLE001 - classified as a miss
            self._miss(list(self.peers), f"{type(e).__name__}: {e}")
            return False
        if stale:
            self._miss(stale, f"peer beat stale: {stale}")
            return False
        self.mark_healthy()
        return True

    def _miss(self, peer_ids, why: str) -> None:
        from h2o3_tpu import telemetry
        with self._lock:
            self.consecutive_misses += 1
            misses = self.consecutive_misses
            for p in peer_ids:
                telemetry.counter("heartbeat_misses_total",
                                  peer=str(p)).inc()
                if p in self.peers:
                    self.peers[p]["healthy"] = False
            telemetry.gauge("cloud_peers_healthy").set(
                sum(1 for st in self.peers.values() if st["healthy"]))
        log.warning("heartbeat miss %d/%d: %s", misses, self.miss_budget,
                    why)
        if misses >= self.miss_budget:
            self.mark_unhealthy(
                f"{misses} consecutive heartbeat misses ({why})")

    # agreement checks ------------------------------------------------
    def _psum_round(self) -> None:
        """Single-controller agreement: a tiny psum over the mesh — the
        exact dispatch path frame_reduce takes, so a backend that would
        hang the next chunk hangs (and times out) here first."""
        import jax
        import numpy as np
        from h2o3_tpu.parallel import mesh as mesh_mod
        mesh = mesh_mod.get_mesh()
        if self._psum_fn is None or self._psum_mesh is not mesh:
            import functools
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            from h2o3_tpu.parallel.mesh import DATA_AXIS

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=P(DATA_AXIS), out_specs=P(),
                               check_vma=False)
            def _agree(x):
                return jax.lax.psum(x.sum(), DATA_AXIS)

            self._psum_fn = jax.jit(_agree)
            self._psum_mesh = mesh
        d = mesh.shape[mesh_mod.DATA_AXIS]
        x = jax.device_put(np.ones((d,), dtype=np.float32),
                           mesh_mod.row_sharding(mesh))
        total = float(self._psum_fn(x))
        if total != float(d):
            raise RuntimeError(
                f"INTERNAL: heartbeat psum corrupt ({total} != {d})")
        # a completed psum IS an all-peer agreement: everyone's beat
        now = time.time()
        with self._lock:
            for st in self.peers.values():
                st["last_seen"] = now

    def _kv_round(self):
        """Multi-process agreement over the coordination-service KV
        store: publish our beat, read every peer's. Returns the list of
        process ids whose beat is stale past interval*miss_budget."""
        from jax._src import distributed
        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "UNAVAILABLE: no coordination-service client")
        now = time.time()
        client.key_value_set(f"{KV_PREFIX}{self._pid}", repr(now),
                             allow_overwrite=True)
        # cluster-telemetry snapshot piggybacks on the beat cadence —
        # same out-of-band rule (KV write, never a device collective),
        # same bounded_call window; its own interval rate-limits it and
        # a publish failure never counts as a heartbeat miss
        try:
            from h2o3_tpu.telemetry import cluster
            cluster.maybe_publish()
        except Exception as e:      # noqa: BLE001 - publish is best-effort
            log.debug("cluster telemetry publish skipped: %s", e)
        # fleet re-warm piggybacks here too: when a replica's last host
        # dies, the least-loaded healthy peer adopts the published model
        # (rate-limited inside maybe_adopt; install runs off-thread)
        try:
            from h2o3_tpu.serving import fleet
            fleet.maybe_adopt()
        except Exception as e:      # noqa: BLE001 - adopt is best-effort
            log.debug("fleet adopt check skipped: %s", e)
        # frame recovery supervisor piggybacks last: once a peer's beat
        # is declared stale, the least-loaded survivor rebuilds the
        # dead peer's registered frames from mirror-or-lineage and
        # re-homes them (rate-limited inside maybe_rebuild; KV-only —
        # never a device collective)
        try:
            from h2o3_tpu.core import durability
            durability.maybe_rebuild_async()
        except Exception as e:      # noqa: BLE001 - rebuild best-effort
            log.debug("durability rebuild check skipped: %s", e)
        beats = {}
        for key, val in client.key_value_dir_get(KV_PREFIX):
            try:
                beats[int(key.rsplit("/", 1)[-1])] = float(val)
            except ValueError:
                continue
        stale_after = self.interval_s * self.miss_budget
        stale = []
        with self._lock:
            for p in self.peers:
                ts = beats.get(p)
                if ts is not None:
                    self.peers[p]["last_seen"] = max(
                        self.peers[p]["last_seen"], ts)
                # a peer that has not beaten recently is suspect; our
                # own beat was just written so never stales here
                if now - self.peers[p]["last_seen"] > stale_after:
                    stale.append(p)
        return stale


monitor = HeartbeatMonitor()

# chunk boundaries inside this scope skip the cloud-unhealthy fail-fast:
# scheduled work items (parallel/scheduler.py) train purely on LOCAL
# devices, so a dead peer cannot wedge them — failing them fast would
# abandon exactly the work that can still finish and serve the
# reassignment of the dead peer's items
_LOCAL_WORK: contextvars.ContextVar[bool] = \
    contextvars.ContextVar("h2o3tpu_local_work", default=False)


@contextlib.contextmanager
def local_work_scope():
    """Mark this thread's work as local-device-only: ``check_healthy``
    becomes a no-op so an unhealthy cloud (a dead peer) does not kill
    fits that issue no cross-process collectives. Cancel/deadline checks
    in Job.update still apply."""
    token = _LOCAL_WORK.set(True)
    try:
        yield
    finally:
        _LOCAL_WORK.reset(token)


def dead_peers() -> List[int]:
    """Process ids whose beat is stale past ``interval * miss_budget``.

    Deliberately based on ``last_seen`` staleness, not the per-peer
    ``healthy`` flag — ``mark_unhealthy`` flips every peer's flag, so
    staleness is the only signal that distinguishes the actually-dead
    peer from the bystanders (the scheduler's reassignment trigger)."""
    now = time.time()
    stale_after = monitor.interval_s * monitor.miss_budget
    with monitor._lock:
        return [p for p, st in monitor.peers.items()
                if p != monitor._pid
                and now - st["last_seen"] > stale_after]


def healthy_peers() -> List[int]:
    """Process ids (self included) whose beat is fresh — the complement
    of :func:`dead_peers` over the known peer set. The fleet router uses
    this to build its candidate pool before consulting load."""
    now = time.time()
    stale_after = monitor.interval_s * monitor.miss_budget
    with monitor._lock:
        fresh = [p for p, st in monitor.peers.items()
                 if p == monitor._pid
                 or now - st["last_seen"] <= stale_after]
        if monitor._pid not in fresh:
            fresh.append(monitor._pid)   # single-process / monitor off
        return sorted(fresh)


def check_healthy(site: str = "") -> None:
    """Fail-fast checkpoint — called at chunk boundaries alongside
    cancel_point. Raises CloudUnhealthyError once the monitor has
    declared the cloud unhealthy, so a job dies within one heartbeat
    interval instead of hanging on the next collective."""
    reason = monitor._unhealthy_reason
    if reason is not None:
        if _LOCAL_WORK.get():
            return                     # local-only work: peers irrelevant
        from h2o3_tpu import telemetry
        telemetry.counter("cloud_unhealthy_failfast_total").inc()
        raise CloudUnhealthyError(reason, site=site)
