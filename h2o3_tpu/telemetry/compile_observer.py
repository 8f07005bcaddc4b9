"""Compile observer — where a first fit's minute goes, by program.

The recurring production failure mode of this runtime is not compute,
it is getting a program ready: every distinct padded shape is traced,
lowered and then compiled or loaded from the persistent cache
(ops/segments.py, frame/binning.py shape-bucket notes), and a workload
that misses the shape buckets silently spends its wall time there.
Two complementary probes:

1. ``install()`` hooks ``jax.monitoring``. JAX times three stages of
   every program it makes ready — ``jaxpr_trace_duration`` (host
   Python: nothing caches it across processes),
   ``jaxpr_to_mlir_module_duration`` (lowering, likewise) and
   ``backend_compile_duration``, which wraps ``compile_or_get_cached``
   and so covers a load from the persistent cache as much as a
   compilation; a ``cache_retrieval_time_sec`` reported inside it says
   which of the two it was. The observer keeps

   - ``xla_stage_seconds_total{stage=trace|lower|compile|cache_load}``
     and ``xla_programs_total{source=compile|cache}``, each second
     counted ONCE: an event reported inside another on the same thread
     (a ``jit`` traced inside a ``jit``, an eager op compiled while an
     outer function is traced) is taken out of the outer one, and a
     nested trace is simply part of its outer trace;
   - a bounded ledger by program (``programs_snapshot()``, served by
     ``GET /3/Metrics``): traces, lowerings, compiles and cache loads
     with their seconds, first and last time stamp, and the span that
     was active at the last event — which program recompiled, and
     under which phase;
   - ``xla_compile_total`` / ``xla_compile_seconds``: every executable
     the backend handed over, LOADED OR COMPILED (their old meaning,
     now said truthfully), and the same on the active span
     (``xla_compiles``, ``xla_compile_s``, beside ``xla_trace_s``,
     ``xla_lower_s``, ``xla_cache_loads``). The stage seconds count as
     the span's children: its own time is what it did itself
     (telemetry/spans.py).

   The events fire only when something is traced or compiled: a warm
   job pays nothing for any of it.

2. ``observed_jit("name")`` decorates a jitted entry point and counts
   executable-cache hits vs fresh compiles per SHAPE-BUCKET (the
   argument signature XLA keys on), via the function's jit cache size
   before/after each call:
   ``jit_cache_{hit,miss}_total{fn=,shapes=}``. This is what tells an
   operator that e.g. k-fold CV is compiling per fold instead of
   hitting the padded_rows bucket.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable, Dict, List

from h2o3_tpu.telemetry import spans
from h2o3_tpu.telemetry.registry import counter, histogram

_installed = False
_install_lock = threading.Lock()

# recent stage events (end timestamp, duration, own seconds, program;
# ``event``: xla_trace, xla_lower, xla_compile, xla_cache_load) — the
# dedicated compile track in Chrome-trace exports
# (telemetry/trace_export.py), and what says WHEN a stage second was
# spent where the counters say how many
_COMPILE_RING_CAPACITY = 4096
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

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_STAGE_OF = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
             _COMPILE_EVENT: "compile"}
# a ledger row's count of each stage (its seconds are ``<stage>_s``)
_COUNT_OF = {"trace": "traces", "lower": "lowerings",
             "compile": "compiles", "cache_load": "cache_loads"}

# the ledger by program: bounded, one row for whatever comes after
_MAX_PROGRAMS = 256
_OVERFLOW_PROGRAM = "(other programs)"
_programs: Dict[str, Dict] = {}
_programs_lock = threading.Lock()

# ``_local.stack``: the stage events open on this thread, outermost
# first. JAX reports a stage's start (a scalar event) and its end (a
# duration event), so the events between the two are its children
_local = threading.local()


def _program_name(fun_name: str) -> str:
    """One name for a program through its stages — XLA's own, as a
    device trace shows it: tracing reports ``f``, lowering and
    compilation ``jit(f)``; the module is ``jit_f``."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        how, _, inner = name[:-1].partition("(")
        return f"{how}_{inner}"
    return "jit_" + name


@dataclasses.dataclass
class _OpenStage:
    event: str
    fun_name: object
    child_s: float = 0.0    # seconds of the events reported inside
    loaded: bool = False    # a cache retrieval was reported inside


def _open_stages() -> List[_OpenStage]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _on_stage_start(name: str, _start, **kw) -> None:
    if name in _STAGE_OF:
        _open_stages().append(_OpenStage(name, kw.get("fun_name")))


def _close_stage(name: str, fun_name) -> _OpenStage:
    """Pop this event's frame, and whatever was left open above it. An
    end without its start (a listener installed in mid-stage) is an
    event of its own."""
    stack = _open_stages()
    for i in range(len(stack) - 1, -1, -1):
        if (stack[i].event, stack[i].fun_name) == (name, fun_name):
            closed = stack[i]
            del stack[i:]
            return closed
    return _OpenStage(name, fun_name)


def _ledger_add(program: str, stage: str, own_s: float, now: float,
                span_name) -> None:
    with _programs_lock:
        row = _programs.get(program)
        if row is None:
            if len(_programs) >= _MAX_PROGRAMS:
                program = _OVERFLOW_PROGRAM
                row = _programs.get(program)
            if row is None:
                row = _programs[program] = {
                    "program": program, "traces": 0, "trace_s": 0.0,
                    "lowerings": 0, "lower_s": 0.0,
                    "compiles": 0, "compile_s": 0.0,
                    "cache_loads": 0, "cache_load_s": 0.0,
                    "first_ts": now, "last_ts": now, "last_span": None}
        row[_COUNT_OF[stage]] += 1
        row[stage + "_s"] += own_s
        row["last_ts"] = now
        row["last_span"] = span_name


def _on_duration(name: str, secs: float, **kw) -> None:
    stack = _open_stages()
    if name == _CACHE_LOAD_EVENT:
        # reported inside backend_compile_duration, on its thread: that
        # acquisition is a load from the persistent cache, not a compile
        if stack and stack[-1].event == _COMPILE_EVENT:
            stack[-1].loaded = True
        return
    stage = _STAGE_OF.get(name)
    if stage is None:
        return
    fun_name = kw.get("fun_name")
    closed = _close_stage(name, fun_name)
    loaded = closed.loaded
    if stack:
        # inside another event: its seconds hold these already. A jit
        # traced there is simply part of it (what was counted inside
        # the inner trace goes up with it); a lowering or a compilation
        # keeps its own stage and is taken out of the outer one
        if stage == "trace":
            stack[-1].child_s += closed.child_s
            return
        stack[-1].child_s += secs
    if stage == "compile" and loaded:
        stage = "cache_load"
    own_s = max(secs - closed.child_s, 0.0)
    now = time.time()
    sp = spans.current_span()
    counter("xla_stage_seconds_total", stage=stage).inc(own_s)
    program = _program_name(fun_name)
    _ledger_add(program, stage, own_s, now,
                sp.name if sp is not None else None)
    ev = {"ts_ms": int(now * 1000), "dur_s": round(secs, 6),
          "event": "xla_" + stage, "program": program,
          "own_s": round(own_s, 6),
          "span_id": sp.id if sp is not None else None}
    with _compile_ring_lock:
        _compile_ring.append(ev)
    if sp is not None:
        sp.child_s += own_s
        if name != _COMPILE_EVENT:
            key = f"xla_{stage}_s"
            sp.meta[key] = round(sp.meta.get(key, 0.0) + own_s, 3)
    if name != _COMPILE_EVENT:
        return
    # an executable handed over by the backend, loaded or compiled
    counter("xla_programs_total",
            source="cache" if loaded else "compile").inc()
    counter("xla_compile_total").inc()
    histogram("xla_compile_seconds").observe(secs)
    try:
        from h2o3_tpu.telemetry import flight_recorder
        flight_recorder.record_compile(ev)
    except Exception:   # noqa: BLE001 - capture is best-effort
        pass
    if sp is not None:
        sp.meta["xla_compiles"] = sp.meta.get("xla_compiles", 0) + 1
        sp.meta["xla_compile_s"] = round(
            sp.meta.get("xla_compile_s", 0.0) + secs, 3)
        if loaded:
            sp.meta["xla_cache_loads"] = \
                sp.meta.get("xla_cache_loads", 0) + 1


def programs_snapshot() -> List[Dict]:
    """The ledger by program, the most seconds first. Over its rows the
    stage seconds add up to ``xla_stage_seconds_total``."""
    with _programs_lock:
        rows = [dict(r) for r in _programs.values()]
    rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"] + r["compile_s"]
                              + r["cache_load_s"]))
    return rows


def compiles_snapshot(last: int = _COMPILE_RING_CAPACITY) -> List[Dict]:
    """Most recent stage events, oldest first."""
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
            monitoring.register_scalar_listener(_on_stage_start)
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
