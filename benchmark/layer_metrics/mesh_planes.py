"""What a fit on a mesh wrote into a profiler trace, plane by plane: the
scoped device ops of EVERY device plane (``program_trace`` reads the
first alone, which is the whole story on one chip), and the ``gbm.chunk``
spans' account of how a job was split over the mesh. For the ``mesh_*``
readers, which average over the planes as ``trace_reduce`` does.

* Scopes: each device plane carries its own event metadata, so the
  ``tf_op`` stat is read plane by plane with ``program_trace``'s protobuf
  walk, and a plane's ops are hung under their containers by
  ``program_trace.resolve_scopes``.
* Chunk meta: the program's span ring (``telemetry.spans_snapshot()``,
  what ``GET /3/Metrics`` serves) — ``data_shards``, ``rows_per_shard``,
  ``psum_bytes`` of the ``gbm.chunk`` spans that started in the window.

A ``Reading`` may carry both (``mesh_planes``: ``{plane: [Op]}``;
``chunk_spans``: a list of span meta): ``benchmark/tests`` builds them
by hand. Where neither is there, nothing is read.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Dict, List, Optional, Tuple

from benchmark import program_trace as pt
from benchmark import trace_reduce as tr

PSUM_SCOPES = ("tree.psum", "tree.leaf_psum")
ALL_REDUCE = re.compile(r"^all-reduce")
SCAN = r"jit__boost_scan"


def plane_scopes(raw: bytes) -> Dict[str, Dict[Tuple[int, str], tuple]]:
    """``{plane: {(program id, event name): scope path}}`` of every device
    plane of an ``XSpace`` (the fields ``program_trace.metadata_scopes``
    names)."""
    out = {}
    for f, plane in pt._fields(raw):
        if f != 1:
            continue
        name = next((v for k, v in pt._fields(plane) if k == 2),
                    b"").decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        stat_names = {k: dict(pt._fields(m)).get(2, b"").decode()
                      for k, m in pt._map_entries(plane, 5)}
        scopes = {}
        for _, meta in pt._map_entries(plane, 4):
            ev_name, tf_op, program = "", "", 0
            for k, v in pt._fields(meta):
                if k == 2:
                    ev_name = v.decode(errors="replace")
                elif k == 5:
                    stat = dict(pt._fields(v))
                    what = stat_names.get(stat.get(1))
                    if what == "tf_op":
                        tf_op = stat.get(5, b"").decode(errors="replace")
                    elif what == "program_id":
                        program = stat.get(3, 0)
            path = pt.scope_path(tf_op)
            if path:
                scopes[(program, ev_name)] = path
        out[name] = scopes
    return out


def load(path: str) -> Dict[str, List[pt.Op]]:
    """Every device plane's ``XLA Ops`` events of ``path`` with their
    module and scope path, on ``trace_reduce``'s clock."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        scopes = plane_scopes(f.read())
    planes: Dict[str, List[pt.Op]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        mods = sorted((float(ev.start_ns),
                       float(ev.start_ns + ev.duration_ns), ev.name)
                      for line in plane.lines if line.name == tr.MODULES_LINE
                      for ev in line.events)
        starts = [m[0] for m in mods]
        mine = scopes.get(plane.name, {})
        ops = planes.setdefault(plane.name, [])
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                module, program = "", 0
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and start < mods[i][1]:
                    module = tr.module_name(mods[i][2])
                    m = re.search(r"\((\d+)\)$", mods[i][2])
                    program = int(m.group(1)) if m else 0
                ops.append(pt.Op(plane.name, line.name, tr.op_name(ev.name),
                                 start, float(ev.duration_ns), module,
                                 mine.get((program, ev.name), ())))
    return planes


_LOADED: Dict[str, Dict[str, List[pt.Op]]] = {}


def of(reading) -> Optional[Dict[str, List[pt.Op]]]:
    """The scoped ops of every device plane of a run: those the
    ``Reading`` carries, else those of the raw trace ``run.py`` has just
    read (still on disk while the readers run), else nothing."""
    carried = getattr(reading, "mesh_planes", None)
    if carried is not None:
        return carried
    trace_dir = os.path.join(pt.ROOT, "bench_out", reading.cell["name"],
                             "trace")
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                path = os.path.join(base, f)
                key = f"{path}@{os.path.getmtime(path)}"
                if key not in _LOADED:
                    _LOADED.clear()
                    _LOADED[key] = load(path)
                return _LOADED[key]
    return None


def own_by_plane(planes, module: str, lo: float, hi: float, want
                 ) -> Dict[str, float]:
    """Per plane, the own nanoseconds in ``[lo, hi]`` of the ops of the
    programs matching ``module`` that ``want(op, scope path)`` picks."""
    rx = re.compile(module)
    out = {}
    for plane, ops in planes.items():
        mine = [o for o in ops
                if rx.match(o.module) and o.end_ns > lo and o.start_ns < hi]
        path = pt.resolve_scopes(mine)
        out[plane] = sum(own for o, own in tr.self_times(mine)
                         if want(o, path[o]))
    return out


def under_psum(op, path) -> bool:
    return any(s in PSUM_SCOPES for s in path)


def has_psum_scope(planes) -> bool:
    """Whether the program named its collectives (a psum op carries its
    scope in its own metadata)."""
    return any(under_psum(o, o.scope) for ops in planes.values()
               for o in ops)


def chunk_spans(reading) -> List[dict]:
    """The meta of the ``gbm.chunk`` spans that started in the window and
    say how the job was split over the mesh."""
    carried = getattr(reading, "chunk_spans", None)
    if carried is not None:
        return [m for m in carried if "data_shards" in m]
    try:
        from h2o3_tpu import telemetry
    except ImportError:
        return []
    if not reading.jobs or getattr(reading, "t_window", None) is None:
        return []
    lo, hi = reading.t_window, max(j["end"] for j in reading.jobs)
    return [s["meta"] for s in telemetry.spans_snapshot(last=1 << 20)
            if s["name"] == "gbm.chunk" and "data_shards" in s["meta"]
            and lo <= s["start_ms"] / 1e3 <= hi]


def data_shards(reading) -> Optional[int]:
    """The ``data`` shards the window's jobs ran over, or nothing where
    the spans do not say (a program before this reader)."""
    shards = {int(m["data_shards"]) for m in chunk_spans(reading)}
    return shards.pop() if len(shards) == 1 else None
