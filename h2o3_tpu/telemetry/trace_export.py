"""Chrome trace-event export — spans/timeline/compiles → Perfetto.

Converts telemetry into the Trace Event Format JSON that Chrome's
``about:tracing`` and https://ui.perfetto.dev load directly (the same
consumer-side workflow the reference gets from its Flow timeline, and
the dispatch/compile visibility DrJAX leans on):

- every finished span becomes a complete (``ph: "X"``) event; spans of
  one root tree share a ``tid`` so parent/child nesting renders as the
  usual flame stack (a child is temporally contained in its parent on
  the same track, and ``args.span_id``/``args.parent_id`` keep the
  exact tree recoverable);
- timeline moments (utils/timeline.py) become instant (``ph: "i"``)
  events, placed on their span's track when they carry a ``span_id``;
- XLA compiles get a dedicated track (``tid`` :data:`COMPILE_TID`) so
  a compile storm is visible as a solid bar even when it happens under
  many different spans.

Two entry points: :func:`capsule_trace` renders one job's flight
recorder capsule (``GET /3/Jobs/{key}/trace``), :func:`process_trace`
renders the whole process ring (``GET /3/Trace``, bench artifacts).

Timestamps are microseconds since the unix epoch (Perfetto normalizes
to the earliest event); durations are microseconds.
"""

from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional

# reserved synthetic tracks (real span tracks count up from 1)
COMPILE_TID = 9001
TIMELINE_TID = 9000
STEPPROF_TID = 9002


def _span_tids(spans: List[Dict]):
    """Track id per span: every span of one root tree shares a tid, so
    Perfetto renders the tree as one flame stack. Spans whose parent
    fell off the ring start a tree of their own. Returns
    ``(span_id→tid, tid→root-name-label)``."""
    by_id = {s["id"]: s for s in spans}

    def root_of(s: Dict) -> Dict:
        seen = set()
        while s["parent_id"] in by_id and s["id"] not in seen:
            seen.add(s["id"])
            s = by_id[s["parent_id"]]
        return s

    tids: Dict[str, int] = {}
    root_tid: Dict[str, int] = {}
    labels: Dict[int, str] = {}
    # roots numbered by first-seen start time → stable track order
    for s in sorted(spans, key=lambda s: s["start_ms"]):
        r = root_of(s)
        if r["id"] not in root_tid:
            root_tid[r["id"]] = len(root_tid) + 1
            labels[root_tid[r["id"]]] = f"spans:{r['name']}"
        tids[s["id"]] = root_tid[r["id"]]
    return tids, labels


def _span_event(s: Dict, pid: int, tid: int) -> Dict:
    args = {"span_id": s["id"], "parent_id": s["parent_id"],
            "device_peak_bytes": s.get("device_peak_bytes", 0),
            "collective_bytes": s.get("collective_bytes", 0)}
    args.update(s.get("meta") or {})
    return {"name": s["name"], "cat": "span", "ph": "X",
            "ts": int(s["start_ms"] * 1000),
            "dur": max(int(round(s["duration_ms"] * 1000)), 1),
            "pid": pid, "tid": tid, "args": args}


def _instant_event(e: Dict, pid: int, tid: int) -> Dict:
    args = {k: v for k, v in e.items()
            if k not in ("kind", "what", "ts_ms", "seq")}
    return {"name": e.get("what", "?"), "cat": e.get("kind", "timeline"),
            "ph": "i", "ts": int(e.get("ts_ms", 0) * 1000), "s": "t",
            "pid": pid, "tid": tid, "args": args}


def _compile_event(c: Dict, pid: int) -> Dict:
    dur_us = max(int(round(c.get("dur_s", 0.0) * 1e6)), 1)
    return {"name": c.get("event", "xla_compile"), "cat": "compile",
            "ph": "X", "ts": int(c.get("ts_ms", 0) * 1000) - dur_us,
            "dur": dur_us, "pid": pid, "tid": COMPILE_TID,
            "args": {"seconds": c.get("dur_s", 0.0)}}


def _stepprof_events(profiles: Iterable[Dict], pid: int) -> List[Dict]:
    """Per-fit phase breakdown (telemetry/stepprof.py finish records in
    the capsule) → one stacked bar per fit on a dedicated track: the
    phases are a PARTITION of the fit's wall clock, so laying them
    end-to-end from the fit's start renders "where the time went" as
    contiguous phase-annotated segments."""
    out: List[Dict] = []
    for f in profiles:
        t = float(f.get("ts", 0.0)) * 1e6          # epoch seconds → us
        label = f.get("model_key") or f.get("algo", "fit")
        for ph, secs in (f.get("phases") or {}).items():
            dur_us = int(round(float(secs) * 1e6))
            if dur_us <= 0:
                continue
            out.append({"name": f"{label}:{ph}", "cat": "stepprof",
                        "ph": "X", "ts": int(t), "dur": max(dur_us, 1),
                        "pid": pid, "tid": STEPPROF_TID,
                        "args": {"phase": ph, "algo": f.get("algo"),
                                 "seconds": round(float(secs), 6),
                                 "chunks": f.get("chunks", 0),
                                 "collective_share":
                                     f.get("collective_share", 0.0),
                                 "mfu": f.get("mfu")}})
            t += dur_us
    return out


def _meta_event(pid: int, tid: Optional[int], name: str, label: str) -> Dict:
    return {"name": name, "cat": "__metadata", "ph": "M", "ts": 0,
            "pid": pid, "tid": tid if tid is not None else 0,
            "args": {"name": label}}


def _events_for(spans: Iterable[Dict], events: Iterable[Dict],
                compiles: Iterable[Dict], pid: int,
                process_name: str,
                step_profiles: Iterable[Dict] = ()) -> List[Dict]:
    """All trace events of ONE process/track-group (shared by the
    single-process build_trace and the multi-node cluster_trace)."""
    spans = list(spans)
    tids, tid_labels = _span_tids(spans)
    out: List[Dict] = [_meta_event(pid, None, "process_name", process_name)]
    for t in sorted(tid_labels):
        out.append(_meta_event(pid, t, "thread_name", tid_labels[t]))
    out.append(_meta_event(pid, TIMELINE_TID, "thread_name", "timeline"))
    out.append(_meta_event(pid, COMPILE_TID, "thread_name", "xla-compile"))
    for s in spans:
        out.append(_span_event(s, pid, tids[s["id"]]))
    for e in events:
        tid = tids.get(e.get("span_id"), TIMELINE_TID)
        out.append(_instant_event(e, pid, tid))
    for c in compiles:
        out.append(_compile_event(c, pid))
    sp = _stepprof_events(step_profiles, pid)
    if sp:
        out.append(_meta_event(pid, STEPPROF_TID, "thread_name",
                               "step-profile"))
        out.extend(sp)
    return out


def build_trace(spans: Iterable[Dict], events: Iterable[Dict] = (),
                compiles: Iterable[Dict] = (),
                process_name: str = "h2o3-tpu",
                extra: Optional[Dict] = None,
                step_profiles: Iterable[Dict] = ()) -> Dict:
    """Assemble Chrome trace JSON from already-snapshotted telemetry."""
    out = _events_for(spans, events, compiles, os.getpid(), process_name,
                      step_profiles=step_profiles)
    trace = {"traceEvents": out, "displayTimeUnit": "ms",
             "otherData": {"source": "h2o3_tpu.telemetry.trace_export"}}
    if extra:
        trace["otherData"].update(extra)
    return trace


def cluster_trace(nodes: Dict[int, Dict],
                  extra: Optional[Dict] = None) -> Dict:
    """Fuse per-peer ring tails into ONE Chrome trace: each node's
    events carry ``pid`` = its process_index, so Perfetto renders one
    track group per host (the telemetry/cluster.py ``?cluster=1``
    payload). ``nodes[n]`` = {"spans", "events", "compiles", "label"}."""
    out: List[Dict] = []
    for n in sorted(nodes):
        d = nodes[n]
        out.extend(_events_for(d.get("spans", ()), d.get("events", ()),
                               d.get("compiles", ()), int(n),
                               d.get("label", f"h2o3-tpu node {n}")))
    trace = {"traceEvents": out, "displayTimeUnit": "ms",
             "otherData": {"source": "h2o3_tpu.telemetry.trace_export"}}
    if extra:
        trace["otherData"].update(extra)
    return trace


def stitched_trace(trace_id: str, nodes: Dict[int, Dict],
                   extra: Optional[Dict] = None) -> Dict:
    """ONE request's causal trace across hosts (``GET
    /3/Trace?trace_id=``): each node's span ring is filtered to
    ``trace_id`` and the survivors merge into a SINGLE track group
    (``pid`` 1 — the trace is the unit, not the host), with tids
    assigned per causal tree ACROSS processes: a remote root whose
    ``parent_id`` names a span on another host (the traceparent
    propagated through a scheduler lease or job hop) joins that span's
    flame stack instead of starting a pid-grouped track of its own.

    Span ids are per-process counters, so merged ids are node-qualified
    (``n<node>:sp-…``); a ``parent_id`` is resolved to the node that
    owns it — same node first, else the unique other owner (the
    cross-process link), else left dangling as its own root. Each
    span's originating ``node`` rides its args."""
    node_spans: Dict[int, List[Dict]] = {}
    node_events: Dict[int, List[Dict]] = {}
    for n in nodes:
        d = nodes[n]
        node_spans[int(n)] = [s for s in d.get("spans", ())
                              if s.get("trace_id") == trace_id]
        node_events[int(n)] = list(d.get("events", ()))
    node_ids = {n: {s["id"] for s in ss} for n, ss in node_spans.items()}

    def qual(n: int, sid: Optional[str]) -> Optional[str]:
        if sid is None:
            return None
        if sid in node_ids[n]:
            return f"n{n}:{sid}"
        owners = [m for m, ids in node_ids.items() if sid in ids]
        if len(owners) == 1:
            return f"n{owners[0]}:{sid}"
        return sid      # unknown (off-ring) or ambiguous → dangles
    spans: List[Dict] = []
    for n, ss in sorted(node_spans.items()):
        for s in ss:
            s2 = dict(s)
            s2["id"] = f"n{n}:{s['id']}"
            s2["parent_id"] = qual(n, s.get("parent_id"))
            s2["meta"] = {**(s.get("meta") or {}), "node": n}
            spans.append(s2)
    tids, tid_labels = _span_tids(spans)
    pid = 1
    out: List[Dict] = [_meta_event(pid, None, "process_name",
                                   f"h2o3-tpu trace {trace_id}")]
    for t in sorted(tid_labels):
        out.append(_meta_event(pid, t, "thread_name", tid_labels[t]))
    for s in spans:
        out.append(_span_event(s, pid, tids[s["id"]]))
    # timeline instants only when they attribute to a span OF THIS
    # trace (events carry no trace id of their own)
    for n, evs in sorted(node_events.items()):
        for e in evs:
            tid = tids.get(qual(n, e.get("span_id")))
            if tid is not None:
                out.append(_instant_event({**e, "node": n}, pid, tid))
    trace = {"traceEvents": out, "displayTimeUnit": "ms",
             "otherData": {"source": "h2o3_tpu.telemetry.trace_export",
                           "trace_id": trace_id,
                           "span_count": len(spans),
                           "nodes": sorted(n for n, ss
                                           in node_spans.items() if ss)}}
    if extra:
        trace["otherData"].update(extra)
    return trace


def capsule_trace(capsule) -> Dict:
    """One job's flight-recorder capsule → Chrome trace JSON."""
    d = capsule.to_dict()
    return build_trace(
        d["spans"], d["events"], d["compiles"],
        process_name=f"h2o3-tpu job {d['job_key']}",
        extra={"job_key": d["job_key"], "description": d["description"],
               "status": d["status"], "metric_deltas": d["metric_deltas"],
               "dropped": d["dropped"]},
        step_profiles=d.get("step_profiles") or ())


def process_trace(last_spans: int = 2048, last_events: int = 2048,
                  last_compiles: int = 512) -> Dict:
    """The whole process ring (spans + timeline + compiles) → Chrome
    trace JSON; the ``GET /3/Trace`` and bench-artifact payload."""
    from h2o3_tpu.telemetry import compile_observer, spans as spans_mod
    from h2o3_tpu.utils import timeline
    return build_trace(
        spans_mod.snapshot(last_spans),
        timeline.snapshot(last_events),
        compile_observer.compiles_snapshot(last_compiles))


def write_trace(path: str, trace: Dict) -> str:
    """Write a trace JSON artifact."""
    import json
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
