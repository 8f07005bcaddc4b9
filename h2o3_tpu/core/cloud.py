"""Cloud bootstrap — process/mesh startup, the ``h2o.init()`` analogue.

Reference call stack (SURVEY §3.1): h2o.init (h2o-py/h2o/h2o.py:138) →
water.H2O.main (water/H2O.java:2328) → NetworkInit → Paxos heartbeat
consensus (water/Paxos.java:40) → CLOUD committed. TPU-native: membership
is either a single process over local devices or ``jax.distributed``
across hosts (its coordinator barrier replaces the heartbeat quorum); the
"cloud" object is a ``jax.sharding.Mesh``. Cloud shape locks at first use
just like Paxos._cloudLocked (water/Paxos.java:32) because the mesh is
baked into compiled programs.

Hardening (ISSUE 7): ``jax.distributed.initialize`` runs under the
shared watchdog RetryPolicy with a bounded coordinator-connect timeout
(``H2O3TPU_CLOUD_TIMEOUT_S``); a post-init roll call over the
coordination-service KV store names the process ids that went missing
when formation is partial; ``core/heartbeat.py`` watches peer health for
the life of the cloud; ``shutdown()`` tears all of it down — heartbeat,
cleaner, mesh, distributed client — so a later ``init()`` reforms
cleanly instead of attaching to stale state.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax

from h2o3_tpu import telemetry
from h2o3_tpu.core import config as _config
from h2o3_tpu.core import heartbeat as heartbeat_mod
from h2o3_tpu.core import watchdog
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.parallel import mesh as mesh_mod
from h2o3_tpu.utils.log import get_logger
from h2o3_tpu.version import __version__

log = get_logger("h2o3_tpu.cloud")

_STARTED = False
_CLOUD_START_MS = 0        # wall-clock ms at init() (cloud_uptime_ms base)
_DISTRIBUTED = False       # this process ran jax.distributed.initialize

BOOT_KV_PREFIX = "h2o3tpu/boot/"


def _cloud_timeout_s(cfg) -> float:
    return float(os.environ.get("H2O3TPU_CLOUD_TIMEOUT_S",
                                cfg.cloud_timeout_s))


def _distributed_init(coordinator_address: str, num_processes: int,
                      process_id: int, timeout_s: float) -> None:
    """One jax.distributed.initialize attempt, retryable: a failed
    attempt tears the half-open client down so the next one starts
    clean (initialize raises on double-init)."""
    global _DISTRIBUTED
    watchdog.maybe_fail("cloud_init")
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=max(int(timeout_s), 1))
        _DISTRIBUTED = True
    except Exception as e:
        log.warning("cloud formation attempt failed (coordinator=%s "
                    "process %s/%s): %s", coordinator_address, process_id,
                    num_processes, e)
        try:
            jax.distributed.shutdown()
        except Exception:   # noqa: BLE001 - nothing half-open to close
            pass
        raise


def _roll_call(num_processes: int, process_id: int,
               timeout_s: float) -> None:
    """Post-init agreement: every process publishes its id and waits at
    a barrier. When a peer dies between connect and first use, THIS is
    where the hole gets a name — the diagnostic lists exactly which
    process ids never reported, instead of the first collective
    hanging."""
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:      # single-process init path
        return
    client.key_value_set(f"{BOOT_KV_PREFIX}{process_id}",
                         f"{os.uname().nodename}:{os.getpid()}",
                         allow_overwrite=True)
    try:
        client.wait_at_barrier("h2o3tpu_boot_rollcall",
                               max(int(timeout_s * 1000), 1000))
    except Exception as e:
        seen = set()
        try:
            for key, _val in client.key_value_dir_get(BOOT_KV_PREFIX):
                seen.add(int(key.rsplit("/", 1)[-1]))
        except Exception:   # noqa: BLE001 - diagnostics are best-effort
            pass
        missing = sorted(set(range(num_processes)) - seen)
        raise RuntimeError(
            f"UNAVAILABLE: partial cloud formation — expected "
            f"{num_processes} processes, missing ids {missing or '?'} "
            f"after {timeout_s:.0f}s roll call ({e})") from e


# the persistent XLA compile cache when JAX_COMPILATION_CACHE_DIR is not
# set: ONE fixed directory inside the checkout (the path is part of the
# cache key, so a directory that moves — /tmp, a pid, a tempfile name —
# never hits). Tests, worker processes, bench and chip_smoke.py all
# share it through init().
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set jax already reads it and no
    directory is set in code; otherwise ``COMPILE_CACHE_DIR``. A cache
    directory that cannot be created raises — repeated sessions (tests,
    bench, the chip smoke) recompiling everything is a fault to see,
    not a warning to scroll past."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def init(backend: Optional[str] = None,
         data_axis: Optional[int] = None,
         model_axis: Optional[int] = None,
         coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         restore_dir: Optional[str] = None,
         **kwargs) -> dict:
    """Start (or attach to) the cloud. Analogue of h2o.init (h2o.py:49,138).

    Single-host: builds the mesh over local devices. Multi-host: pass
    ``coordinator_address``/``num_processes``/``process_id`` and every host
    calls this with the same arguments — ``jax.distributed.initialize`` is
    the clouding protocol (replaces multicast/flatfile discovery,
    water/init/NetworkInit.java:62-174), retried under the shared
    watchdog policy and bounded by ``H2O3TPU_CLOUD_TIMEOUT_S``.

    ``restore_dir`` reforms the cloud's DKV from a ``cloud_checkpoint``
    directory (POST /3/CloudCheckpoint) — frames land bit-identically
    (digest-verified) and models re-register (core/durability.py, the
    rolling-restart / disaster-recovery path).
    """
    if (_STARTED and backend is None and coordinator_address is None
            and data_axis is None and model_axis is None
            and num_processes is None and process_id is None
            and restore_dir is None and not kwargs):
        # cloud already formed and no explicit backend/mesh re-shape
        # requested: attach, don't reform (h2o.init attaches to a
        # running cluster; silently re-detecting devices here could
        # swap the session's mesh to a different backend mid-flight)
        return cluster_info()
    with telemetry.span("cloud.init") as sp:
        info = _form_cloud(backend, data_axis, model_axis,
                           coordinator_address, num_processes, process_id,
                           restore_dir, **kwargs)
        sp.annotate(platform=info["platform"], devices=info["cloud_size"])
    return info


def _form_cloud(backend, data_axis, model_axis, coordinator_address,
                num_processes, process_id, restore_dir, **kwargs) -> dict:
    """``init``'s forming path: configuration, compile cache, the
    distributed client where asked for, the backend and the mesh."""
    global _STARTED, _CLOUD_START_MS
    cfg = _config.Config.from_env(backend=backend, data_axis=data_axis,
                                  model_axis=model_axis, **kwargs)
    _config.ARGS = cfg

    # rebuild the logging pipeline: level/dir/format knobs set between
    # import and init() (H2O3TPU_LOG_*, init(log_level=...)) must take
    # effect — utils/log.py configure() is idempotent
    from h2o3_tpu.utils import log as _log
    _log.configure(level=cfg.log_level,
                   log_dir=cfg.log_dir or None)

    setup_compile_cache()

    if coordinator_address is not None and not _STARTED:
        timeout_s = _cloud_timeout_s(cfg)
        # the CPU backend only speaks cross-process collectives through
        # gloo; the flag must be set BEFORE the first backend client is
        # created or the psum tree dies with "Multiprocess computations
        # aren't implemented on the CPU backend" — the standing
        # multiprocess-CPU failure this PR retires
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception as e:       # noqa: BLE001 — TPU-only jaxes
            log.warning("cpu collectives unavailable (multi-process CPU "
                        "meshes will not form): %s", e)
        watchdog.retry_call(
            lambda: _distributed_init(coordinator_address,
                                      int(num_processes),
                                      int(process_id), timeout_s),
            site="cloud_init")
        _roll_call(int(num_processes), int(process_id), timeout_s)
        # reformed-cloud hygiene for the work scheduler: swept at INIT,
        # where the roll-call barrier proves no process is mid-run —
        # never at shutdown, where processes arrive at different times
        # and a sweep wedges a peer still reading its last run
        if int(process_id) == 0:
            from h2o3_tpu.parallel import scheduler as _scheduler_mod
            _scheduler_mod.sweep_keys()
            # same reasoning for the serving-fleet registry: stale
            # replica/endpoint entries and published binaries from the
            # previous incarnation are swept once the roll-call barrier
            # proves nobody is still routing against them
            from h2o3_tpu.serving import fleet as _fleet_mod
            _fleet_mod.sweep_keys()
            # and the durability registry/blob subtree: a reformed
            # cloud must never rebuild the previous incarnation's
            # frames from its ghost registry entries
            from h2o3_tpu.core import durability as _durability_mod
            _durability_mod.sweep_keys()
        # stamp this process's cloud identity on every log record and
        # flight-recorder capsule (utils/log.py ContextFilter) so merged
        # cluster views stay attributable — set here, NOT read from
        # jax.process_index() inside the logging hot path
        _log.set_node(int(process_id))

    # the first jax.devices() of a process brings the backend up
    with telemetry.span("cloud.backend"):
        devices = jax.devices(cfg.backend) if cfg.backend \
            else jax.devices()
        m = mesh_mod.make_mesh(devices, cfg.data_axis, cfg.model_axis)
    mesh_mod.set_global_mesh(m)
    _STARTED = True
    _CLOUD_START_MS = int(time.time() * 1000)
    # peer health: always for multi-process clouds (a dead peer hangs
    # every collective — someone must notice), opt-in for single-process
    hb = (cfg.heartbeat or "auto").lower()
    if hb == "on" or (hb == "auto" and jax.process_count() > 1):
        heartbeat_mod.monitor.start()
    info = cluster_info()
    log.info("cloud up: %s", info)
    # Cleaner thread (water/Cleaner.java): opt-in — spilling mid-test
    # would make timings nondeterministic, so default off like the
    # reference's -cleaner flag family
    if os.environ.get("H2O3_TPU_SPILL") == "1":
        from h2o3_tpu.core.cleaner import cleaner
        cleaner.start()
        log.info("cleaner started (threshold %.0f%%)",
                 cleaner.threshold * 100)
    if restore_dir:
        from h2o3_tpu.core import durability as _durability_mod
        restored = _durability_mod.cloud_restore(restore_dir)
        info["restored"] = restored
    return info


def cluster_info() -> dict:
    """GET /3/Cloud shape (water/api/CloudHandler.java)."""
    m = mesh_mod.get_mesh()
    devs = list(m.devices.flat)
    hb = heartbeat_mod.monitor.status()
    now_ms = int(time.time() * 1000)
    return {
        "version": __version__,
        "cloud_name": _config.ARGS.name,
        "cloud_size": len(devs),
        # hardcoded True until ISSUE 7: now the heartbeat monitor's
        # verdict (trivially healthy when the monitor is off)
        "cloud_healthy": hb["healthy"],
        "mesh_shape": dict(m.shape),
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "devices": [str(d) for d in devs],
        "platform": devs[0].platform if devs else "none",
        "build_age_sec": 0,
        "cloud_uptime_ms": (now_ms - _CLOUD_START_MS
                            if _STARTED and _CLOUD_START_MS else 0),
        "heartbeat": hb,
        # cluster work scheduler (parallel/scheduler.py): this host's
        # lease/throughput view; GET /3/Cloud?cluster=1 merges peers'
        "scheduler": _scheduler_snapshot(),
    }


def _scheduler_snapshot() -> dict:
    from h2o3_tpu.parallel import scheduler
    return scheduler.snapshot()


def _sweep_coordination_keys() -> None:
    """Delete THIS process's heartbeat/bootstrap/telemetry entries from
    the coordination-service KV store. Runs during shutdown, before the
    distributed client disconnects: a reformed cloud (shutdown → init)
    must never read the previous incarnation's ghost beats or stale
    metric snapshots."""
    try:
        from jax._src import distributed
        client = distributed.global_state.client
    except Exception:       # noqa: BLE001 - no distributed runtime
        return
    if client is None:
        return
    pidx = heartbeat_mod.monitor._pid
    try:
        pidx = jax.process_index()
    except Exception:       # noqa: BLE001 - keep the monitor's capture
        pass
    from h2o3_tpu.telemetry import cluster
    for prefix in (heartbeat_mod.KV_PREFIX, BOOT_KV_PREFIX,
                   cluster.KV_PREFIX):
        try:
            client.key_value_delete(f"{prefix}{pidx}")
        except Exception:   # noqa: BLE001 - absent key / service down
            pass
    try:
        # fleet endpoint + replica rows are per-process too; published
        # model binaries stay (a lagging peer may still be installing
        # one) and are garbage-collected by the init-time prefix sweep
        from h2o3_tpu.serving import fleet as _fleet_mod
        _fleet_mod.sweep_local_keys(client, pidx)
    except Exception:       # noqa: BLE001 - fleet tier is optional
        pass
    try:
        # partitioned-ingest metadata this process published (codec
        # facts, off-mode gather blobs) — per-exchange keys are dead
        # the moment the frame exists, but a reformed cloud reuses
        # exchange counters from zero and must never read ghosts
        from h2o3_tpu.frame import partition as _partition_mod
        _partition_mod.sweep_local_keys(client)
    except Exception:       # noqa: BLE001 - ingest tier is optional
        pass
    try:
        # durability registry rows + mirror blobs this process homes:
        # a clean shutdown is not a peer death — survivors must not
        # "rebuild" frames the operator deliberately took down
        from h2o3_tpu.core import durability as _durability_mod
        _durability_mod.sweep_local_keys(client, pidx)
    except Exception:       # noqa: BLE001 - durability tier is optional
        pass
    # scheduler run subtrees are NOT swept here: processes reach
    # shutdown at different times, and deleting h2o3tpu/sched/ while a
    # lagging peer still polls its last run's done manifest wedges that
    # peer forever. Old runs are garbage-collected run-over-run instead
    # (scheduler.run deletes the run-before-last, which every process
    # has provably finished installing), and the subtree dies with the
    # coordination service itself.


def shutdown() -> None:
    """Drop all state (reference: POST /3/Shutdown).

    Tears down everything ``init()`` built — heartbeat and cleaner
    threads, this process's coordination-KV entries (beats, roll-call
    marker, telemetry snapshot), the DKV, the global mesh, and the
    jax.distributed client — so a subsequent ``init()`` reforms the
    cloud instead of attaching to stale state."""
    global _STARTED, _CLOUD_START_MS, _DISTRIBUTED
    try:
        # fleet drain FIRST, while the heartbeat still marks us healthy:
        # deregister local replicas and pull the REST endpoint so peers
        # stop routing predictions here before anything else tears down
        from h2o3_tpu.serving import fleet as _fleet_mod
        _fleet_mod.drain()
    except Exception:       # noqa: BLE001 - fleet tier is optional
        pass
    heartbeat_mod.monitor.stop()
    try:
        from h2o3_tpu.core.cleaner import cleaner
        cleaner.stop()
    except Exception:       # noqa: BLE001 - cleaner is optional
        pass
    _sweep_coordination_keys()
    try:
        # orphaned FitCheckpointer tmp files / partial snapshot dirs
        # (a kill mid-write leaves *.tmp debris; completed .fitsnap
        # snapshots are resumable state and stay)
        from h2o3_tpu.core import recovery as _recovery
        _recovery.sweep_fit_checkpoints()
    except Exception:       # noqa: BLE001 - sweep is best-effort
        pass
    try:
        # clear this process's durability state (registry keys, mirror
        # blobs, framesnap.tmp debris) — the ISSUE 18 shutdown contract
        from h2o3_tpu.core import durability as _durability_mod
        _durability_mod.reset()
        _durability_mod.sweep_debris()
    except Exception:       # noqa: BLE001 - durability is optional
        pass
    try:
        # the admission ledger and bytes-on-ice accounting die with the
        # cloud: a reformed cloud must not inherit ghost reservations
        from h2o3_tpu.core.memgov import governor
        governor.reset()
    except Exception:       # noqa: BLE001 - governor is optional
        pass
    DKV.clear()
    mesh_mod.set_global_mesh(None)
    if _DISTRIBUTED:
        try:
            jax.distributed.shutdown()
        except Exception as e:   # noqa: BLE001 - already down is fine
            log.warning("jax.distributed shutdown: %s", e)
        _DISTRIBUTED = False
    _STARTED = False
    _CLOUD_START_MS = 0
