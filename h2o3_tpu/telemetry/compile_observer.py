"""Compile observer — XLA compile storms made visible.

The recurring production failure mode of this runtime is not compute,
it is COMPILATION: every distinct padded shape is a fresh 20-40s XLA
trace+compile (ops/segments.py, frame/binning.py shape-bucket notes),
and a workload that misses the shape buckets silently spends its wall
time in the compiler. Two complementary probes:

1. ``install()`` hooks ``jax.monitoring`` duration events, so EVERY
   backend compile in the process increments
   ``xla_compile_total`` / ``xla_compile_seconds`` — no call-site
   changes needed, and compile time is charged to the active span.

2. ``observed_jit("name")`` decorates a jitted entry point and counts
   executable-cache hits vs fresh compiles per SHAPE-BUCKET (the
   argument signature XLA keys on), via the function's jit cache size
   before/after each call:
   ``jit_cache_{hit,miss}_total{fn=,shapes=}``. This is what tells an
   operator that e.g. k-fold CV is compiling per fold instead of
   hitting the padded_rows bucket.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List

from h2o3_tpu.telemetry import spans
from h2o3_tpu.telemetry.registry import counter, histogram

_installed = False
_install_lock = threading.Lock()

# recent compile events (end timestamp + duration) — the dedicated
# compile track in Chrome-trace exports (telemetry/trace_export.py)
_COMPILE_RING_CAPACITY = 512
_compile_ring: deque = deque(maxlen=_COMPILE_RING_CAPACITY)
_compile_ring_lock = threading.Lock()

# per observed fn: shape-signature interning with a cap, so label
# cardinality stays bounded even under pathological shape churn
_MAX_SHAPE_LABELS = 32
_shape_labels: Dict[str, set] = {}

# AOT replay sources for roofline accounting (telemetry/roofline.py):
# on each fresh compile the observed jit entry point's call signature is
# stashed as ABSTRACT shapes (jax.ShapeDtypeStruct — no device buffers
# retained), so Compiled.cost_analysis() can later be taken off a
# re-lowering of the exact executable the fit ran, without holding HBM.
_aot_sources: Dict[str, tuple] = {}
_aot_lock = threading.Lock()


def _abstractify(x):
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if isinstance(shape, tuple) and dtype is not None:
        import jax
        return jax.ShapeDtypeStruct(shape, dtype)
    return x


def _record_aot_source(name: str, jit_fn, args, kwargs) -> None:
    try:
        import jax
        aargs = jax.tree_util.tree_map(_abstractify, args)
        akwargs = {k: jax.tree_util.tree_map(_abstractify, v)
                   for k, v in kwargs.items()}
        with _aot_lock:
            _aot_sources[name] = (jit_fn, aargs, akwargs)
    except Exception:   # noqa: BLE001 - accounting must never break a fit
        pass


def aot_source(name: str):
    """(jit_fn, abstract_args, abstract_kwargs) of the most recent fresh
    compile of an observed entry point, or None."""
    with _aot_lock:
        return _aot_sources.get(name)


def aot_source_names():
    with _aot_lock:
        return sorted(_aot_sources)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_duration(name: str, secs: float, **kw) -> None:
    if name != _COMPILE_EVENT:
        return
    counter("xla_compile_total").inc()
    histogram("xla_compile_seconds").observe(secs)
    sp = spans.current_span()
    ev = {"ts_ms": int(time.time() * 1000), "dur_s": round(secs, 6),
          "event": "xla_compile",
          "span_id": sp.id if sp is not None else None}
    with _compile_ring_lock:
        _compile_ring.append(ev)
    try:
        from h2o3_tpu.telemetry import flight_recorder
        flight_recorder.record_compile(ev)
    except Exception:   # noqa: BLE001 - capture is best-effort
        pass
    if sp is not None:
        sp.meta["xla_compiles"] = sp.meta.get("xla_compiles", 0) + 1
        sp.meta["xla_compile_s"] = round(
            sp.meta.get("xla_compile_s", 0.0) + secs, 3)


def compiles_snapshot(last: int = _COMPILE_RING_CAPACITY) -> List[Dict]:
    """Most recent compile events, oldest first."""
    with _compile_ring_lock:
        evs = list(_compile_ring)
    return evs[-max(int(last), 0):]


def install() -> None:
    """Register the jax.monitoring listener (idempotent, process-wide)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            _installed = True
        except Exception:   # noqa: BLE001 - telemetry must never break init
            pass


def _sig_of(a) -> str:
    shape = getattr(a, "shape", None)
    if isinstance(shape, tuple):    # arrays only (Mesh.shape is a dict)
        return "x".join(map(str, shape)) or "0d"
    if isinstance(a, (list, tuple)) and a:      # pytree-of-arrays args
        inner = [_sig_of(v) for v in a[:8]]
        inner = [s for s in inner if s]
        return "[" + "|".join(inner) + "]" if inner else ""
    return ""


def _shape_sig(args, kwargs) -> str:
    """Compact shape-bucket signature of the array arguments — the part
    of the jit cache key an operator can act on (pick better buckets)."""
    parts = [s for s in (_sig_of(a) for a in args) if s]
    for k in sorted(kwargs):
        s = _sig_of(kwargs[k])
        if s:
            parts.append(f"{k}:{s}")
    return ",".join(parts) or "scalar"


def _bucket_label(fn_name: str, sig: str) -> str:
    seen = _shape_labels.setdefault(fn_name, set())
    if sig in seen:
        return sig
    if len(seen) >= _MAX_SHAPE_LABELS:
        return "overflow"
    seen.add(sig)
    return sig


def observed_jit(name: str) -> Callable:
    """Decorator for a ``jax.jit``-ed function: per-shape-bucket cache
    hit/miss accounting. Stack ABOVE the jit decorator:

        @observed_jit("gbm.boost_scan")
        @partial(jax.jit, static_argnames=(...))
        def _boost_scan_jit(...): ...
    """
    def deco(jit_fn):
        import functools

        @functools.wraps(jit_fn)
        def wrapper(*args, **kwargs):
            size_of = getattr(jit_fn, "_cache_size", None)
            if size_of is None:            # not a jit object: pass through
                return jit_fn(*args, **kwargs)
            before = size_of()
            out = jit_fn(*args, **kwargs)
            fresh = size_of() > before
            sig = _bucket_label(name, _shape_sig(args, kwargs))
            counter("jit_cache_miss_total" if fresh
                    else "jit_cache_hit_total", fn=name, shapes=sig).inc()
            if fresh:
                spans.annotate(fresh_compile=name)
                # miss-only: interning abstract shapes per call would tax
                # hot entry points (ops.segment_sum) for nothing new
                _record_aot_source(name, jit_fn, args, kwargs)
            return out
        return wrapper
    return deco
