"""Hierarchical span tracer — where a distributed fit spends its time.

Reference: the reference answers "where did the time go" with the
TimeLine packet ring + /3/Profiler stack samples; the TPU runtime's
time sinks are instead structured phases (job → algo.fit → boost chunk
→ xla compile), so the primitive here is a nested span:

    with span("gbm.fit"):
        with span("gbm.chunk", trees=25):
            ...

Each span records wall time, the device-memory high-water mark at exit
(``device.memory_stats()['peak_bytes_in_use']``, best-effort — the CPU
backend reports none), and any collective-byte estimates charged
to it by the dispatch layer (parallel/map_reduce.py). Nesting is
contextvar-based, so worker threads (background jobs) get their own
root spans for free. Finished spans land in a fixed ring (the TimeLine
capacity discipline) and feed two durable series by name in the
registry: the histogram ``span_seconds{name=}`` — wall time and the
count of spans, every child counted again in its parent — and the
counter ``span_own_seconds_total{name=}`` — the span's duration less
its child spans and less the XLA stage seconds reported while it was
the active span (telemetry/compile_observer.py). Own seconds add up:
over all names they are the wall time a thread spent under spans, ring
or no ring. ``GET /3/Metrics`` serves all of it.

Timeline events recorded while a span is active carry its id
(utils/timeline.py), tying the flat event ring to the span tree.

Every span is also a ``jax.profiler.TraceAnnotation("h2o3.<name>")``
over the same interval: while a profiler session is open the span lands
in the trace's host plane, on the device trace's clock, so device idle
time can be charged to program phases (benchmark/program_trace.py).
With no session open the annotation is a flag test.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from jax._src import xla_bridge
from jax.profiler import TraceAnnotation

from h2o3_tpu.telemetry.registry import counter, histogram

_CAPACITY = 1024
_finished: deque = deque(maxlen=_CAPACITY)
_finished_lock = threading.Lock()
_ids = itertools.count(1)

_current: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("h2o3tpu_span", default=None)

# the profiler-trace name of a span: fixed, so that a trace reader can
# tell the program's spans from a harness's own annotations
TRACE_PREFIX = "h2o3."


class Span:
    __slots__ = ("id", "name", "parent_id", "trace_id", "start", "end",
                 "meta", "device_peak_bytes", "collective_bytes",
                 "child_s", "_token", "_peak_base")

    def __init__(self, name: str, parent_id: Optional[str],
                 trace_id: Optional[str] = None, **meta):
        self.id = f"sp-{next(_ids):08d}"
        self.name = name
        self.parent_id = parent_id
        self.trace_id = trace_id
        self.start = time.time()
        self.end = 0.0
        self.meta = meta
        self.device_peak_bytes = 0
        self.collective_bytes = 0.0
        # seconds of this span that a child span or an XLA stage event
        # has to its own name
        self.child_s = 0.0
        self._token = None
        self._peak_base = 0

    @property
    def duration(self) -> float:
        return (self.end or time.time()) - self.start

    def annotate(self, **meta) -> None:
        self.meta.update(meta)

    def to_dict(self) -> Dict:
        return {"id": self.id, "parent_id": self.parent_id,
                "trace_id": self.trace_id,
                "name": self.name,
                "start_ms": int(self.start * 1000),
                "duration_ms": round(self.duration * 1000, 3),
                "own_ms": round(max(self.duration - self.child_s, 0.0)
                                * 1000, 3),
                "device_peak_bytes": self.device_peak_bytes,
                "collective_bytes": self.collective_bytes,
                "meta": {k: v for k, v in self.meta.items()}}


def _device_peak() -> int:
    """Device HBM high-water, 0 when the backend reports no stats (the
    CPU backend — job.py documents that pressure then shows up as
    RESOURCE_EXHAUSTED, not as this gauge) and 0 while no backend is up:
    a span never brings one up by asking (seconds on a TPU host; that
    is ``init()``'s to do, under its ``cloud.backend`` span)."""
    try:
        import jax
        if not xla_bridge.backends_are_initialized():
            return 0
        s = jax.devices()[0].memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0) or 0)
    except Exception:   # noqa: BLE001 - stats are strictly best-effort
        return 0


@contextmanager
def span(name: str, **meta):
    """Open a child of the current span (root if none) for the duration
    of the with-block. Exceptions propagate; the span still closes.

    ``device_peak_bytes`` is SPAN-RELATIVE: the process high-water mark
    is read at entry as a baseline, and the span reports how far the
    high-water ROSE while it was open. Best-effort semantics: the mark
    is process-wide and monotonic, so concurrent spans each get charged
    the shared rise, and a span that allocated under an earlier
    high-water reports 0 (pre-fix every span after the global peak
    reported the same global max). Backends without ``memory_stats``
    report 0 throughout."""
    from h2o3_tpu.telemetry import trace_context
    parent = _current.get()
    tc = trace_context.current()
    # cross-process/cross-thread stitch: a ROOT span (no in-process
    # parent) parents under the installed trace context's parent id —
    # the submitting request's span on the other side of the hop
    parent_id = parent.id if parent is not None \
        else (tc.parent_id if tc is not None else None)
    sp = Span(name, parent_id,
              trace_id=tc.trace_id if tc is not None else None, **meta)
    sp._peak_base = _device_peak()
    sp._token = _current.set(sp)
    try:
        # the one place the program writes into a profiler trace:
        # exactly the interval the span times
        with TraceAnnotation(TRACE_PREFIX + name):
            yield sp
    finally:
        _current.reset(sp._token)
        sp.end = time.time()
        sp.device_peak_bytes = max(0, _device_peak() - sp._peak_base)
        seconds = sp.end - sp.start
        if parent is not None:
            # charge child collective traffic up the tree so a root job
            # span totals its whole subtree; the seconds go up so that
            # the parent's own time leaves them out
            parent.collective_bytes += sp.collective_bytes
            parent.child_s += seconds
        with _finished_lock:
            _finished.append(sp)
        counter("span_own_seconds_total", name=name).inc(
            max(seconds - sp.child_s, 0.0))
        histogram("span_seconds", name=name).observe(seconds)
        # per-job flight recorder capture (one contextvar read when no
        # recorder is attached — telemetry/flight_recorder.py)
        try:
            from h2o3_tpu.telemetry import flight_recorder
            flight_recorder.record_span(sp)
        except Exception:   # noqa: BLE001 - capture is best-effort
            pass
        from h2o3_tpu.utils.timeline import record as _tl
        _tl("span", f"{name} {sp.duration * 1000:.1f}ms",
            span_id=sp.id, parent_id=sp.parent_id)


@contextmanager
def detach():
    """Detach from the in-process span stack for the with-block: the
    next span opened becomes a ROOT, parenting under the installed
    trace context (if any) instead of the local ancestor. A leased
    scheduler item executes under the LEASE's causality — the
    coordinator's sched.run — not the local polling loop's."""
    token = _current.set(None)
    try:
        yield
    finally:
        _current.reset(token)


def record_finished(name: str, start: float, end: float, *,
                    trace_id: Optional[str] = None,
                    parent_id: Optional[str] = None, **meta) -> Span:
    """Record a span whose interval was measured AFTER the fact — the
    serving batcher's queue/device/scatter phases are timed inside the
    coalesced dispatch, then attributed back to each member request's
    own trace. Skips the device-peak baseline (the interval is already
    closed) but otherwise lands in the same ring/metrics/flight
    recorder as a live span. It has no children, so all of it is its
    own time; it is charged to no parent (the phases of a coalesced
    dispatch overlap their requests' spans many times over)."""
    sp = Span(name, parent_id, trace_id=trace_id, **meta)
    sp.start = float(start)
    sp.end = float(end)
    with _finished_lock:
        _finished.append(sp)
    seconds = max(sp.end - sp.start, 0.0)
    counter("span_own_seconds_total", name=name).inc(seconds)
    histogram("span_seconds", name=name).observe(seconds)
    try:
        from h2o3_tpu.telemetry import flight_recorder
        flight_recorder.record_span(sp)
    except Exception:   # noqa: BLE001 - capture is best-effort
        pass
    return sp


def current_span() -> Optional[Span]:
    return _current.get()


def current_span_id() -> Optional[str]:
    sp = _current.get()
    return sp.id if sp is not None else None


def add_collective_bytes(n: float) -> None:
    """Charge an estimated collective payload to the active span."""
    sp = _current.get()
    if sp is not None:
        sp.collective_bytes += n


def annotate(**meta) -> None:
    """Attach metadata to the active span (no-op without one)."""
    sp = _current.get()
    if sp is not None:
        sp.meta.update(meta)


def snapshot(last: int = 100) -> List[Dict]:
    """Most recent finished spans, oldest first."""
    with _finished_lock:
        evs = list(_finished)
    return [s.to_dict() for s in evs[-max(int(last), 0):]]


def aggregate() -> List[Dict]:
    """Per-name rollup of the finished ring (the /3/Profiler span view):
    count, total/mean wall ms, max device peak."""
    with _finished_lock:
        evs = list(_finished)
    agg: Dict[str, Dict] = {}
    for s in evs:
        a = agg.setdefault(s.name, {"name": s.name, "count": 0,
                                    "total_ms": 0.0,
                                    "device_peak_bytes": 0,
                                    "collective_bytes": 0.0})
        a["count"] += 1
        a["total_ms"] += s.duration * 1000
        a["device_peak_bytes"] = max(a["device_peak_bytes"],
                                     s.device_peak_bytes)
        a["collective_bytes"] += s.collective_bytes
    out = sorted(agg.values(), key=lambda a: -a["total_ms"])
    for a in out:
        a["total_ms"] = round(a["total_ms"], 3)
        a["mean_ms"] = round(a["total_ms"] / max(a["count"], 1), 3)
    return out


def clear() -> None:
    """Tests only."""
    with _finished_lock:
        _finished.clear()
