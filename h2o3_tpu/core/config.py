"""Runtime configuration (analogue of water.H2O.OptArgs, reference
h2o-core/src/main/java/water/H2O.java:209,296-355).

The reference parses a flat CLI flag struct plus ``sys.ai.h2o.*`` system
properties (H2O.java:1321-1334). Here: a flat dataclass overridable from
``init()`` kwargs and ``H2O3TPU_*`` environment variables.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass
class Config:
    name: str = "h2o3-tpu"           # cloud name (-name)
    port: int = 54321                 # REST port (-port)
    log_level: str = "INFO"           # -log_level
    nthreads: int = 0                 # 0 = all (host-side thread pools)
    # mesh shape: data axis size; 0 = all visible devices
    data_axis: int = 0
    # optional second axis for model-parallel Gram/GLM (SURVEY §2.4 item 6)
    model_axis: int = 1
    backend: Optional[str] = None     # None = jax default; 'cpu' forces host
    # chunked-compute block size (rows per scan step in map/reduce kernels);
    # analogue of the reference's chunk target (water/fvec/Vec.java chunk
    # sizing), chosen for MXU tiling: multiple of 8*128.
    block_rows: int = 32768
    # default number of histogram bins (reference nbins, hex/tree/DHistogram.java)
    nbins: int = 64
    ice_root: str = "/tmp/h2o3_tpu"   # spill/checkpoint dir (-ice_root)
    # -- fault tolerance (core/watchdog.py shared retry policy) --------
    # total attempts for infra-class errors (1 = no retry); the analogue
    # of the reference's sys.ai.h2o.* retry properties
    infra_max_attempts: int = 3
    infra_backoff_base_s: float = 0.5   # first retry delay (doubles)
    infra_backoff_max_s: float = 30.0   # backoff ceiling
    # backend liveness probe deadline; 0 = unbounded (probe_backend)
    probe_timeout_s: float = 60.0
    # -- in-fit checkpointing (core/recovery.py FitCheckpointer) -------
    # directory for periodic mid-fit snapshots (GBM tree chunks, GLM
    # lambda iterations, DL epoch boundaries); "" = off. Grid/AutoML
    # recovery_dir= overrides this per combo via fit_checkpoint_scope
    fit_checkpoint_dir: str = ""
    # snapshot cadence in algo-native units (GBM trees / DL steps /
    # GLM lambdas); 0 = per-algo default (GBM 25 trees, DL one epoch,
    # GLM every lambda)
    fit_checkpoint_every: int = 0
    # -- cloud formation + peer health (core/cloud.py, core/heartbeat.py)
    # coordinator-connect bound for jax.distributed.initialize AND the
    # post-init roll-call barrier; the analogue of the reference's
    # stall_till_cloudsize timeout (water/H2O.java waitForCloudSize)
    cloud_timeout_s: float = 120.0
    # seconds between heartbeat rounds (HeartBeatThread pings every
    # second in the reference, water/HeartBeatThread.java:16)
    heartbeat_interval_s: float = 1.0
    # consecutive missed rounds before the cloud is declared unhealthy
    # (Paxos ejects after HeartBeatThread.TIMEOUT misses)
    heartbeat_miss_budget: int = 3
    # per-round deadline for the agreement check; 0 = use the interval
    heartbeat_timeout_s: float = 5.0
    # peer-health monitor: "auto" (default) runs it for multi-process
    # clouds where a dead peer would hang every collective; "on" forces
    # it for single-process clouds too (rounds become tiny bounded
    # psums); "off" disables it entirely
    heartbeat: str = "auto"
    # -- request hardening (api/server.py admission gate + bounds) -----
    # max requests executing handlers concurrently; the analogue of the
    # reference's bounded Jetty thread pool (water/api/RequestServer)
    rest_max_inflight: int = 64
    # requests allowed to WAIT for a slot once saturated; anything past
    # inflight+queue fails fast with 503 + Retry-After
    rest_queue_depth: int = 16
    # longest a queued request waits for a slot before 503
    rest_queue_wait_s: float = 10.0
    # Content-Length cap for buffered bodies (MB); /3/PostFile streams
    # to disk in chunks and is exempt
    rest_max_body_mb: int = 256
    # -- observability (telemetry/flight_recorder.py + utils/log.py) ---
    # rotating per-process log file directory; "" = stream+ring only
    log_dir: str = ""
    # completed-job telemetry capsules retained in the DKV (newest
    # first); cancelled jobs' capsules are swept with their Scope
    flight_recorder_keep: int = 32
    # -- cluster telemetry fan-in (telemetry/cluster.py) ---------------
    # per-peer metric/trace/log snapshots over the coordination-service
    # KV store: "auto" (default) publishes on multi-process clouds only,
    # "on" forces, "off" disables — the ?cluster=1 views then degrade to
    # the local process
    cluster_metrics: str = "auto"
    # seconds between snapshot publishes (piggybacked on the heartbeat
    # beat cadence — a publish never outpaces the beat)
    cluster_metrics_interval_s: float = 5.0
    # a peer whose newest snapshot is older than this is reported in
    # stale_nodes (its last data still serves, labeled stale)
    cluster_metrics_stale_s: float = 15.0
    # -- roofline accounting (telemetry/roofline.py) -------------------
    # per-fit FLOP/byte accounting against device peaks: "auto" =
    # analytic estimates everywhere + Compiled.cost_analysis() totals on
    # TPU backends; "analytic" / "cost" force one path; "off" disables
    roofline: str = "auto"
    # -- model batching (parallel/model_batch.py) ----------------------
    # grid/AutoML combos sharing one compiled program train as a single
    # vmapped batch: "auto" (default) batches eligible buckets of >= 2
    # combos; "off"/"0" forces the sequential per-combo walk
    batch_models: str = "auto"
    # -- HBM memory governor (core/memgov.py) --------------------------
    # deterministic HBM budget in MB when the backend reports no
    # bytes_limit (the CPU backend, i.e. tests);
    # 0 = no explicit budget (the governor only observes)
    hbm_budget_mb: int = 0
    # bounded wait for concurrent fits' reservations to release before
    # a pre-dispatch admission rejection (the AdmissionGate contract
    # applied to bytes instead of request slots)
    memgov_wait_s: float = 5.0
    # "auto" (default) = enforce admission whenever a budget source
    # exists; "off" = observe only, never reject
    memgov: str = "auto"
    # -- chunk-parallel ingest (io/chunking.py + io/stream.py) ---------
    # tokenizer workers for the chunk-parallel parse pipeline: 0 = one
    # per host core (the reference's MultiFileParseTask fans chunks to
    # the local FJ pool), 1 = the exact sequential fallback path
    parse_workers: int = 0
    # byte-window size fed to each tokenizer worker, in MB (the FileVec
    # chunk-size analogue for the parse plane)
    parse_chunk_mb: int = 64
    # -- low-latency scoring tier (serving/, README §Serving) ----------
    # row cap for one coalesced predict dispatch; also the ceiling of
    # the power-of-two row buckets the compiled scorer cache keys on
    score_batch_max_rows: int = 4096
    # how long the per-model dispatcher waits to coalesce concurrent
    # predict requests into one padded device dispatch
    score_batch_wait_ms: float = 2.0
    # bounded per-model predict queue; a full queue answers 503 +
    # Retry-After (the AdmissionGate overload contract on the scoring
    # queue)
    score_batch_queue_depth: int = 256
    # -- cluster work scheduler (parallel/scheduler.py) ----------------
    # fan independent fits (grid combos, AutoML steps, CV folds) across
    # cloud processes over the coordination-service KV: "auto" (default)
    # schedules on multi-process clouds only, "on" forces the code path
    # (single process = everything leases to process 0), "off" keeps
    # every fit on the coordinator
    scheduler: str = "auto"
    # seconds between KV polls in the worker lease loop and the
    # coordinator's completion wait (cheap control-plane reads)
    scheduler_poll_s: float = 0.2
    # a leased item whose owner's heartbeat goes stale past
    # interval * miss_budget is reassigned after this extra grace
    scheduler_reassign_grace_s: float = 0.0
    # hard wall on one scheduled run's completion wait; 0 = no deadline
    # (budget enforcement lives in grid/AutoML, not the scheduler)
    scheduler_timeout_s: float = 0.0
    # -- pod-global sharded training (parallel/mesh.py, frame/frame.py)
    # host-partitioned frame placement for data-parallel fits across the
    # whole pod: "auto"/"on" let partitioned ingest home each process's
    # row shards locally (ONE fit spans every host); "off" devolves
    # partitioned ingest to the legacy fully-replicated layout. The
    # single-process path is bit-identical in every mode.
    global_fit: str = "auto"
    # -- training-step profiler (telemetry/stepprof.py) ----------------
    # per-chunk phase timing (host/compute/collective/checkpoint) woven
    # through every fit: "auto"/"on" profile every fit (registry op +
    # one device sync per chunk — <2% on bench chunks), "off" disables
    # the weave entirely
    stepprof: str = "auto"
    # bounded per-fit ring of chunk records kept for /profile + capsule
    stepprof_ring: int = 128
    # -- performance kernels (ops/pallas/) -----------------------------
    # fused Pallas tree kernels (histogram+split+partition per level):
    # "auto" = Pallas on TPU backends, XLA elsewhere; "off" = always the
    # XLA path; "interpret" = force the kernels through the Pallas
    # interpreter (CPU parity testing). The XLA path remains the
    # always-available fallback (ops/pallas.decide)
    pallas: str = "auto"

    # fields that parse as int from the environment (annotations are
    # strings under `from __future__ import annotations`, so resolve
    # by hand)
    _INT_FIELDS = frozenset({"port", "nthreads", "data_axis", "model_axis",
                             "block_rows", "nbins", "infra_max_attempts",
                             "rest_max_inflight", "rest_queue_depth",
                             "rest_max_body_mb", "flight_recorder_keep",
                             "heartbeat_miss_budget",
                             "fit_checkpoint_every", "hbm_budget_mb",
                             "parse_workers", "parse_chunk_mb",
                             "score_batch_max_rows",
                             "score_batch_queue_depth",
                             "stepprof_ring"})
    _FLOAT_FIELDS = frozenset({"infra_backoff_base_s", "infra_backoff_max_s",
                               "probe_timeout_s", "rest_queue_wait_s",
                               "cloud_timeout_s", "heartbeat_interval_s",
                               "heartbeat_timeout_s",
                               "cluster_metrics_interval_s",
                               "cluster_metrics_stale_s",
                               "memgov_wait_s", "score_batch_wait_ms",
                               "scheduler_poll_s",
                               "scheduler_reassign_grace_s",
                               "scheduler_timeout_s"})

    @staticmethod
    def from_env(**overrides) -> "Config":
        cfg = Config()
        for f in dataclasses.fields(Config):
            env = os.environ.get("H2O3TPU_" + f.name.upper())
            if env is not None:
                if f.name in Config._INT_FIELDS:
                    val = int(env)
                elif f.name in Config._FLOAT_FIELDS:
                    val = float(env)
                else:
                    val = env
                setattr(cfg, f.name, val)
        for k, v in overrides.items():
            if v is not None and hasattr(cfg, k):
                setattr(cfg, k, v)
        return cfg


# process-wide config singleton (reference: static H2O.ARGS)
ARGS = Config()
