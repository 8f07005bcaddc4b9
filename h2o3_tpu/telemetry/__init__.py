"""Runtime telemetry — metrics registry + hierarchical spans + compile
observer, exposed via ``GET /3/Metrics`` (api/server.py).

The reference ships observability as a design constraint (TimeLine,
WaterMeter, Profiler — PAPER.md §Timeline/Logs); this package is the
TPU runtime's equivalent for its OWN failure modes: XLA compile storms,
shape-bucket misses, and device-memory pressure. Always on, cheap
(registry op ≈ 1µs; see test_telemetry.py overhead bound).

Where a first fit's time goes is answered by two series that add up:
``span_own_seconds_total{name=}`` (a span's duration less its children,
telemetry/spans.py) and ``xla_stage_seconds_total{stage=}`` (tracing,
lowering, compiling, loading from the persistent cache; by program in
``programs_snapshot()``, telemetry/compile_observer.py). The set-up
path opens its own spans where the work is done and never on a cached
path: ``cloud.init`` / ``cloud.backend``, ``frame.encode`` /
``frame.put``, ``frame.rollups``, ``bin.fetch`` / ``bin.edges`` /
``bin.codes``; the gauge ``process_import_seconds`` is the package's
own import.

Request hardening (api/server.py + core/request_ctx.py) reports
through the same registry: ``rest_inflight_requests`` (gauge),
``rest_rejected_total{reason=}``, ``request_deadline_exceeded_total``,
``rest_client_disconnects_total``; the RED duration legs are
``rest_request_seconds{route,status}`` and ``rest_queue_wait_seconds``.

Post-hoc, per-job debuggability rides the same instrumentation:
``flight_recorder`` captures each Job's span subtree, timeline events,
compiles, and log records into a bounded DKV capsule
(``<job_key>_telemetry``), and ``trace_export`` renders capsules or
the whole process ring as Perfetto-loadable Chrome trace JSON
(``GET /3/Jobs/{id}/trace``, ``GET /3/Trace``).

Surface (stable metric names — README §Observability):

    from h2o3_tpu import telemetry
    telemetry.counter("frame_reduce_total").inc()
    with telemetry.span("gbm.fit", trees=100):
        ...
    telemetry.snapshot() / telemetry.to_prometheus()
"""

from h2o3_tpu.telemetry.registry import (BYTES_BUCKETS, REGISTRY,
                                         SECONDS_BUCKETS, counter, gauge,
                                         histogram)
from h2o3_tpu.telemetry import flight_recorder
from h2o3_tpu.telemetry.spans import (add_collective_bytes, annotate,
                                      current_span, current_span_id, span)
from h2o3_tpu.telemetry.spans import snapshot as spans_snapshot
from h2o3_tpu.telemetry.spans import aggregate as spans_aggregate
from h2o3_tpu.telemetry.compile_observer import (compiles_snapshot, install,
                                                 observed_jit,
                                                 programs_snapshot)
from h2o3_tpu.telemetry import trace_export
from h2o3_tpu.telemetry import trace_context
from h2o3_tpu.telemetry import slo
from h2o3_tpu.telemetry import cluster
from h2o3_tpu.telemetry import roofline
from h2o3_tpu.telemetry import stepprof
from h2o3_tpu.telemetry import perfbase

snapshot = REGISTRY.snapshot
to_prometheus = REGISTRY.to_prometheus

# the compile listener is process-wide and costs nothing when idle;
# importing telemetry anywhere arms it (core/job.py imports this, so
# every entry path — REST, python API, bench — is covered)
install()

__all__ = [
    "BYTES_BUCKETS", "SECONDS_BUCKETS", "REGISTRY",
    "counter", "gauge", "histogram",
    "span", "annotate", "current_span", "current_span_id",
    "add_collective_bytes", "spans_snapshot", "spans_aggregate",
    "install", "observed_jit", "snapshot", "to_prometheus",
    "compiles_snapshot", "programs_snapshot", "flight_recorder", "trace_export",
    "trace_context", "slo", "cluster", "roofline", "stepprof",
    "perfbase",
]
