"""ScoringEngine — the compiled-scorer cache behind the serving tier.

Training compiles once and streams millions of rows; serving inverts
the ratio: many small requests, each of which would pay a fresh XLA
trace on any new shape. The fix is the same full-program compilation
stance the rest of the runtime takes (arXiv 1810.09868): per model,
ONE jitted predict program per padded ROW BUCKET (powers of two up to
``H2O3TPU_SCORE_BATCH_MAX_ROWS`` — the serving face of the PR 4 shape
bucket planner, ``parallel/model_batch.row_bucket``), warmed at model
registration so the first request never pays a trace, with donated
input buffers on accelerator backends.

Bit-identity contract (asserted in tier-1, tests/test_serving.py): the
device half of each program is EXACTLY the device math of the model's
``_score_raw`` (``Model._serve_dev``), the host tail is EXACTLY its
host math (``Model._serve_finish``), and the shared post-processing
(threshold/argmax/calibrator/domains) is the same
``Model._finish_predict`` that ``Model.predict`` calls. Padding rows
never leak: every per-row op here is row-count-stable, and outputs are
sliced to logical rows before post-processing.

Eviction: the scorer cache registers with the PR 11 memory governor as
an auxiliary device cache (``core/memgov.register_aux_cache``) — the
OOM/admission ladders drop compiled scorers alongside
``Frame.drop_device_caches``, counted in
``scorer_cache_evictions_total``.

Metrics (README §Observability): ``predict_requests_total{algo}``,
``predict_batch_width``, ``predict_seconds{phase=queue|device|scatter}``,
``scorer_cache_{hits,misses,evictions}_total``, ``scorer_cache_bytes``.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu.core import request_ctx
from h2o3_tpu.serving import rows as rows_mod
from h2o3_tpu.serving.batcher import MicroBatcher, PendingScore, \
    QueueSaturated, batch_knobs
from h2o3_tpu.serving.rows import ServingUnsupported
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.serving")

_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
_WIDTH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _serve_mesh_scope():
    """Serving is strictly process-local work: on a multi-process cloud
    it must run on THIS host's devices (the scheduler's local-mesh
    idiom), never the global mesh — a single-sided dispatch onto a
    cross-process sharding either fails or produces a result no one
    process can read — and under the heartbeat's local-work exemption,
    so a DEAD peer degrades fleet routing without killing this host's
    own scoring. Single-process: no-op."""
    import contextlib
    import jax
    stack = contextlib.ExitStack()
    if jax.process_count() > 1:
        from h2o3_tpu.core import heartbeat
        from h2o3_tpu.parallel import mesh as mesh_mod
        stack.enter_context(mesh_mod.local_mesh_scope())
        stack.enter_context(heartbeat.local_work_scope())
    return stack


def _const_nbytes(model) -> int:
    """Device bytes pinned by the model's own parameters (closure
    constants of its compiled scorers)."""
    import jax
    total = 0
    for attr in ("forest", "coef", "coef_multinomial", "net", "f0"):
        obj = getattr(model, attr, None)
        if obj is None:
            continue
        for leaf in jax.tree_util.tree_leaves(obj):
            total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def donating_jit(model):
    """The accelerator's scoring program: a separate jit of the SAME
    traced fn as ``Model._serve_jit`` (identical HLO → identical
    numerics) with the input buffer donated — serving inputs are
    transient, and donation frees a bucket of HBM per dispatch."""
    import jax
    return jax.jit(model._serve_dev, donate_argnums=(0,))


class CompiledScorer:
    """One model's seat in the scorer cache: its serving schema, the
    jitted device program (shared across row buckets — XLA keys the
    executable on the padded input shape), and the bucket bookkeeping
    the hit/miss metrics and byte accounting ride on."""

    def __init__(self, model):
        import jax
        self.model = model
        self.algo = model.algo
        self.schema = rows_mod.serving_schema(model)
        oc = model.params.get("offset_column")
        if oc and all(nm != oc for nm, _ in self.schema):
            # offset rides as a plain numeric input column; offset
            # models score through the eager fallback (see below), but
            # the payload schema must still accept the column
            self.schema.append((oc, None))
        self.domains = rows_mod.domains_of(self.schema)
        self.fallback_reason = self._fallback_reason()
        self.buckets: Dict[int, int] = {}    # padded rows -> input bytes
        self.serve = None
        self.prep: Optional[Callable] = None
        if self.fallback_reason is None:
            from h2o3_tpu.telemetry.compile_observer import observed_jit
            self.prep = self._prep_fn()
            if jax.default_backend() == "cpu":
                # SHARE the model's own compiled program
                # (Model._serve_jit — also what _score_raw runs):
                # bit-identity by construction, and predicts warm the
                # serving cache and vice versa
                base = model._serve_jit()
            else:
                base = donating_jit(model)
            self.serve = observed_jit(f"serving.{self.algo}")(base)
        self.const_nbytes = _const_nbytes(model)

    def _fallback_reason(self) -> Optional[str]:
        m = self.model
        if not hasattr(m, "_serve_dev") or not hasattr(m, "_serve_finish"):
            return "no device scoring program"
        if m.params.get("offset_column"):
            return "offset_column"
        if m.algo == "deeplearning" and m.params.get("autoencoder"):
            return "autoencoder"
        return None

    def _prep_fn(self) -> Callable:
        """Frame → the device input of the jitted program (eager
        adaptTestForTrain half: training-edge binning / design
        expansion — itself shape-bucketed and jit-cached downstream)."""
        m = self.model
        if self.algo in ("gbm", "drf"):
            from h2o3_tpu.frame.binning import rebin_for_scoring
            return lambda fr: rebin_for_scoring(m.bm, fr).bins
        if self.algo == "glm":
            return m._design
        if self.algo == "deeplearning":
            return lambda fr: m._design(fr).X
        raise ServingUnsupported(f"no prep for algo '{self.algo}'")

    def nbytes(self) -> int:
        """Estimated device bytes this scorer pins: model constants +
        per-bucket input workspace (the executables themselves are
        untracked by jax; this is the accountable floor)."""
        return self.const_nbytes + sum(self.buckets.values())


class ScoringEngine:
    """Per-model compiled-scorer cache + continuous micro-batching
    (singleton ``engine``; README §Serving)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._scorers: Dict[str, CompiledScorer] = {}
        self._batchers: Dict[str, MicroBatcher] = {}
        self._memgov_registered = False

    # -- registration --------------------------------------------------
    def register(self, model) -> CompiledScorer:
        """Idempotent model registration: build the scorer, warm-compile
        the smallest row bucket (the first request must never pay a
        trace), and start the model's micro-batch dispatcher."""
        with self._lock:
            sc = self._scorers.get(model.key)
            if sc is not None and sc.model is model:
                return sc
        sc = CompiledScorer(model)       # may raise ServingUnsupported
        self._warm_up(model, sc)
        with self._lock:
            self._scorers[model.key] = sc
            if model.key not in self._batchers:
                self._batchers[model.key] = MicroBatcher(
                    model.key,
                    lambda batch, _mk=model.key: self._dispatch(_mk, batch))
            self._register_memgov()
        self._refresh_gauge()
        log.info("registered serving scorer for %s (%s%s)", model.key,
                 model.algo,
                 f", eager fallback: {sc.fallback_reason}"
                 if sc.fallback_reason else ", compiled")
        return sc

    def _warm_up(self, model, sc: CompiledScorer) -> None:
        """Score one all-NA row through the full prep+device+finish
        pipeline: compiles the smallest bucket's program AND the eager
        adaptation path (binning / design jits) at registration time."""
        from h2o3_tpu import telemetry
        t0 = time.monotonic()
        with telemetry.span("serving.warmup", algo=model.algo,
                            model=model.key):
            cols = rows_mod.parse_rows(sc.schema, [{}])
            self._score_cols(model, sc, cols, 1, warm=True)
        log.info("serving warm-up for %s took %.3fs", model.key,
                 time.monotonic() - t0)

    def _register_memgov(self) -> None:
        if self._memgov_registered:
            return
        from h2o3_tpu.core import memgov
        memgov.register_aux_cache("serving_scorers",
                                  self.cache_nbytes, self.evict)
        self._memgov_registered = True

    # -- public scoring ------------------------------------------------
    def score_rows(self, model, rows: List[dict],
                   deadline: Optional[float] = None,
                   wait_timeout_s: float = 300.0
                   ) -> Tuple[Dict[str, np.ndarray], Dict, Dict]:
        """The REST row-payload entry: parse → enqueue → coalesced
        device dispatch → this request's slice. Returns
        ``(columns, domains, meta)``. Raises :class:`QueueSaturated`
        (→ 503) on a full queue and ``DeadlineExceeded`` (→ 408) when
        the request deadline expires in the queue or in flight."""
        from h2o3_tpu import telemetry
        sc = self.register(model)
        telemetry.counter("predict_requests_total", algo=model.algo).inc()
        cols = rows_mod.parse_rows(sc.schema, rows)
        if deadline is None:
            deadline = request_ctx.current_deadline()
        # the submitter's trace rides its queue seat: the dispatcher
        # thread attributes retroactive queue/device/scatter sub-spans
        # back to each member request's OWN trace (parent = the span
        # submitting here, typically the rest ingress span)
        from h2o3_tpu.telemetry import spans as _spans
        from h2o3_tpu.telemetry import trace_context as _trace
        tc = _trace.current()
        trace = tc.child(_spans.current_span_id() or tc.parent_id) \
            if tc is not None else None
        pending = PendingScore(cols, len(rows), deadline=deadline,
                               trace=trace)
        self._batchers[model.key].submit(pending)
        timeout = wait_timeout_s
        if deadline is not None:
            timeout = max(deadline - time.monotonic(), 0.0) + 0.25
        if not pending.wait(timeout):
            raise request_ctx.DeadlineExceeded(
                f"predict for {model.key} did not complete within "
                f"{timeout:.1f}s")
        if pending.error is not None:
            raise pending.error
        out, domains = pending.result
        return out, domains, dict(pending.meta)

    def score_columns(self, model, cols: Dict[str, np.ndarray], n: int
                      ) -> Tuple[Dict[str, np.ndarray], Dict]:
        """Direct (batcher-bypassing) scoring of pre-parsed columns —
        the parity-test and warm-path surface."""
        sc = self.register(model)
        return self._score_cols(model, sc, cols, n)

    # -- dispatch ------------------------------------------------------
    def _dispatch(self, model_key: str, batch: List[PendingScore]) -> None:
        from h2o3_tpu import telemetry
        with self._lock:
            sc = self._scorers.get(model_key)
        if sc is None:
            for p in batch:
                p.finish(error=KeyError(
                    f"serving scorer for {model_key} was evicted"))
            return
        from h2o3_tpu.telemetry import spans as spans_mod
        traced = [p for p in batch if p.trace is not None]
        with telemetry.span("predict.dispatch", model=model_key,
                            requests=len(batch)) as dsp:
            if traced:
                # the coalesced dispatch is ONE device program serving
                # many traces — link them all on the dispatch span
                dsp.annotate(member_traces=sorted(
                    {p.trace.trace_id for p in traced}))
            now = time.monotonic()
            wall = time.time()
            q_hist = telemetry.histogram("predict_seconds",
                                         buckets=_LATENCY_BUCKETS,
                                         phase="queue")
            for p in batch:
                q_wait = now - p.enqueue_t
                q_hist.observe(q_wait)
            telemetry.histogram("predict_batch_width",
                                buckets=_WIDTH_BUCKETS).observe(
                float(len(batch)))
            cols = rows_mod.concat_columns([p.cols for p in batch])
            n = sum(p.n for p in batch)
            t_dev = time.monotonic()
            w_dev = time.time()
            out, domains = self._score_cols(sc.model, sc, cols, n)
            telemetry.histogram("predict_seconds",
                                buckets=_LATENCY_BUCKETS,
                                phase="device").observe(
                time.monotonic() - t_dev)
            t_sc = time.monotonic()
            w_sc = time.time()
            off = 0
            for p in batch:
                sl = {nm: arr[off:off + p.n] for nm, arr in out.items()}
                p.finish(result=(sl, domains), batch_requests=len(batch),
                         batch_rows=n)
                off += p.n
            telemetry.histogram("predict_seconds",
                                buckets=_LATENCY_BUCKETS,
                                phase="scatter").observe(
                time.monotonic() - t_sc)
            w_end = time.time()
            # retroactive per-member phase spans, each under its OWN
            # request's trace (parent = the submitting span): the
            # stitched trace shows every member's queue wait + its
            # share of the coalesced device/scatter work
            for p in traced:
                q_wait = max(now - p.enqueue_t, 0.0)
                spans_mod.record_finished(
                    "predict.queue", wall - q_wait, wall,
                    trace_id=p.trace.trace_id,
                    parent_id=p.trace.parent_id,
                    model=model_key, dispatch_span=dsp.id)
                spans_mod.record_finished(
                    "predict.device", w_dev, w_sc,
                    trace_id=p.trace.trace_id,
                    parent_id=p.trace.parent_id,
                    model=model_key, dispatch_span=dsp.id,
                    batch_requests=len(batch), batch_rows=n)
                spans_mod.record_finished(
                    "predict.scatter", w_sc, w_end,
                    trace_id=p.trace.trace_id,
                    parent_id=p.trace.parent_id,
                    model=model_key, dispatch_span=dsp.id)

    # -- the compiled pipeline -----------------------------------------
    def _score_cols(self, model, sc: CompiledScorer,
                    cols: Dict[str, np.ndarray], n: int,
                    warm: bool = False) -> Tuple[Dict, Dict]:
        """Score a batch of training-adapted host columns: window to the
        bucket cap, pad each window to its power-of-two row bucket, run
        the compiled program, reassemble, and apply the shared
        ``Model._finish_predict`` tail."""
        max_rows = int(batch_knobs()["max_rows"])
        parts = []
        with _serve_mesh_scope():
            for lo in range(0, n, max_rows):
                hi = min(lo + max_rows, n)
                win = cols if (lo == 0 and hi == n) else \
                    {nm: a[lo:hi] for nm, a in cols.items()}
                parts.append(
                    self._score_window(model, sc, win, hi - lo, warm))
            merged = parts[0] if len(parts) == 1 else {
                nm: np.concatenate([p[nm] for p in parts])
                for nm in parts[0]}
            return model._finish_predict(merged)

    def _score_window(self, model, sc: CompiledScorer,
                      cols: Dict[str, np.ndarray], n: int,
                      warm: bool) -> Dict[str, np.ndarray]:
        from h2o3_tpu import telemetry
        from h2o3_tpu.core.kv import DKV
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.parallel.model_batch import row_bucket
        bucket = row_bucket(n, int(batch_knobs()["max_rows"]))
        fr = Frame.from_numpy(cols, domains=sc.domains, pad_to=bucket)
        # transient scoring view — keep it out of the store (the
        # expand_interactions idiom, models/glm.py)
        DKV.remove(fr.key)
        try:
            if sc.fallback_reason is not None:
                if not warm:
                    telemetry.counter("scorer_cache_misses_total",
                                      algo=sc.algo, path="eager").inc()
                return model._score_raw(fr)
            x = sc.prep(fr)
            padded = int(fr.nrows_padded)
            hit = padded in sc.buckets
            if not warm:
                telemetry.counter(
                    "scorer_cache_hits_total" if hit
                    else "scorer_cache_misses_total",
                    algo=sc.algo, path="compiled").inc()
            if not hit:
                sc.buckets[padded] = int(getattr(x, "nbytes", 0) or 0)
                self._refresh_gauge()
            fetched = np.asarray(sc.serve(x))
            return model._serve_finish(fetched, n)
        finally:
            fr.drop_device_caches()

    # -- memory governance ---------------------------------------------
    def cache_nbytes(self) -> int:
        with self._lock:
            return sum(sc.nbytes() for sc in self._scorers.values())

    def evict(self, exclude: Optional[set] = None) -> int:
        """Drop compiled scorers (memgov eviction ladder hook); returns
        estimated bytes released. Batchers stay up — the next request
        re-registers and re-warms its model."""
        from h2o3_tpu import telemetry
        freed = 0
        evicted = []
        with self._lock:
            for key in list(self._scorers):
                if exclude and key in exclude:
                    continue
                sc = self._scorers.pop(key)
                freed += sc.nbytes()
                evicted.append(key)
                telemetry.counter("scorer_cache_evictions_total",
                                  algo=sc.algo).inc()
        if freed:
            log.info("evicted %d compiled scorers (%.1f MB est.)",
                     len(evicted), freed / 1e6)
        if evicted:
            # a replica whose scorer was evicted is no longer warm:
            # deregister it from the fleet registry so routing stops
            # sending here and the least-loaded healthy peer re-warms it
            # (serving/fleet.py maybe_adopt)
            try:
                from h2o3_tpu.serving import fleet
                fleet.on_scorers_evicted(evicted)
            except Exception:   # noqa: BLE001 - registry is best-effort
                pass
        self._refresh_gauge()
        return freed

    def _refresh_gauge(self) -> None:
        try:
            from h2o3_tpu import telemetry
            telemetry.gauge("scorer_cache_bytes").set(self.cache_nbytes())
        except Exception:   # noqa: BLE001 - gauges are best-effort
            pass

    # -- lifecycle -----------------------------------------------------
    def queue_depth(self, model_key: Optional[str] = None) -> int:
        """Pending predict requests (one model, or every batcher) — the
        per-peer load signal the fleet router and the telemetry fan-in
        serving block report."""
        with self._lock:
            if model_key is not None:
                b = self._batchers.get(model_key)
                return b.depth() if b is not None else 0
            return sum(b.depth() for b in self._batchers.values())

    def warm_models(self) -> List[str]:
        """Model keys with a warm compiled scorer in this process."""
        with self._lock:
            return sorted(self._scorers)

    def drain(self) -> None:
        """Graceful shutdown (ISSUE 17): deregister this process's
        replicas from the fleet registry FIRST (routing stops sending
        here), then close every batcher — the dispatcher thread joins,
        its in-flight batch finishes, and queued requests fail fast with
        :class:`BatcherDraining` (→ 503 + Retry-After) instead of
        hanging on abandoned futures."""
        try:
            from h2o3_tpu.serving import fleet
            fleet.deregister_local(reason="draining")
        except Exception:   # noqa: BLE001 - registry is best-effort
            pass
        self.reset()

    def stats(self) -> Dict:
        with self._lock:
            return {
                "models": {
                    k: {"algo": sc.algo,
                        "compiled": sc.fallback_reason is None,
                        "fallback_reason": sc.fallback_reason,
                        "buckets": sorted(sc.buckets),
                        "nbytes": sc.nbytes()}
                    for k, sc in self._scorers.items()},
                "cache_nbytes": self.cache_nbytes(),
            }

    def reset(self) -> None:
        """Test/shutdown hook: drop scorers and stop dispatchers."""
        with self._lock:
            batchers = list(self._batchers.values())
            self._batchers.clear()
            self._scorers.clear()
        for b in batchers:
            b.close()
        self._refresh_gauge()


# process-wide engine (the scorer cache is per-process, like the DKV)
engine = ScoringEngine()
