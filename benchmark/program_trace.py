"""What the program itself wrote into a profiler trace, which the
reduction (``trace_reduce.load_xplane``) drops: its host spans and the
scope names on its device work.

* Host spans: ``h2o3_tpu.telemetry.span(name)`` is a
  ``TraceAnnotation("h2o3." + name)``, so every program span is an event
  of the host plane on the device trace's clock.
* Scopes: ``jax.named_scope`` names ride the HLO ``op_name`` metadata.
  A TPU trace carries that as the stat ``tf_op`` of the *event metadata*
  of the ``XLA Ops`` line (``jit(_irls_solve)/.../glm.irls_iter/
  gram.blocks/reshape:``), which ``jax.profiler.ProfileData`` does not
  expose; ``metadata_scopes`` reads just that map from the file's bytes.
  A scope is a path component with a dot in it (``glm.irls_iter``): the
  program's naming rule, which no primitive or transform name follows.

Two questions are answered here, with ``trace_reduce``'s arithmetic:
``charge_idle`` — which program span was the host in while the device
sat idle — and ``device_by_scope`` — whose device time an XLA program's
operations are. A trace of a program that has neither spans nor scopes
gives empty answers, never an error; ``of(reading)`` is ``None`` where
there is no raw trace to read (the recorded fixtures, a CPU rehearsal).
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                  # run as a script (``record``)
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
PROGRAM_PREFIX = "h2o3."
SCOPE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
UNATTRIBUTED = "unattributed"
UNSCOPED = "unscoped"
RELATIVE = "~"


@dataclasses.dataclass(frozen=True, eq=False)
class Op(tr.Event):
    """A device op event with the scope path its HLO metadata names
    (outermost first; empty where the compiler made the op). An event
    is itself, not its fields: ``eq=False`` keeps it a cheap dict key."""
    scope: Tuple[str, ...] = ()


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Tuple[str, float, float]]   # (name without prefix, start, end)
    ops: List[Op]                           # first device plane, XLA Ops

    def to_table(self) -> dict:
        """Plain lists (JSON): the ops share one plane and one line."""
        return {"spans": [list(s) for s in self.spans],
                "plane": self.ops[0].plane if self.ops else "",
                "line": self.ops[0].line if self.ops else "",
                "ops": [[o.name, o.start_ns, o.dur_ns, o.module,
                         "/".join(o.scope)] for o in self.ops]}

    @classmethod
    def from_table(cls, table: dict) -> "ProgramTrace":
        return cls([(str(n), float(s), float(e))
                    for n, s, e in table["spans"]],
                   [Op(table["plane"], table["line"], str(r[0]),
                       float(r[1]), float(r[2]), str(r[3]),
                       tuple(p for p in str(r[4]).split("/") if p))
                    for r in table["ops"]])


# ---- reading the file ----------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if not c & 0x80:
            return r, i


def _fields(b: bytes) -> Iterable[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _map_entries(plane: bytes, field: int):
    """``(key, value bytes)`` of a ``map<int64, message>`` field."""
    for f, entry in _fields(plane):
        if f == field:
            kv = dict(_fields(entry))
            yield kv.get(1, 0), kv.get(2, b"")


def metadata_scopes(raw: bytes) -> Dict[Tuple[int, str], Tuple[str, ...]]:
    """``{(program id, event name): scope path}`` from the event
    metadata of the first device plane of an ``XSpace`` (``raw``: the
    bytes of an ``.xplane.pb``). XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64_value = 3,
    .str_value = 5; XStatMetadata.name = 2."""
    for f, plane in _fields(raw):
        if f != 1:
            continue
        name = next((v for k, v in _fields(plane) if k == 2), b"").decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        stat_names = {k: dict(_fields(m)).get(2, b"").decode()
                      for k, m in _map_entries(plane, 5)}
        out = {}
        for _, meta in _map_entries(plane, 4):
            ev_name, tf_op, program = "", "", 0
            for k, v in _fields(meta):
                if k == 2:
                    ev_name = v.decode(errors="replace")
                elif k == 5:
                    stat = dict(_fields(v))
                    what = stat_names.get(stat.get(1))
                    if what == "tf_op":
                        tf_op = stat.get(5, b"").decode(errors="replace")
                    elif what == "program_id":
                        program = stat.get(3, 0)
            path = scope_path(tf_op)
            if path:
                out[(program, ev_name)] = path
        return out
    return {}


def scope_path(op_name: str) -> Tuple[str, ...]:
    """``jit(f)/while/body/glm.irls_iter/gram.blocks/reshape:`` →
    ``("glm.irls_iter", "gram.blocks")``. The body of a ``shard_map``
    is named from its own root (``gram.blocks/while/body/...``, no
    ``jit(f)`` in front): such a path starts with ``RELATIVE`` until
    ``resolve_scopes`` has hung it under the op that contains it."""
    path = tuple(p for p in op_name.split("/") if SCOPE.match(p))
    if path and not op_name.startswith("jit("):
        return (RELATIVE,) + path
    return path


def load_xplane(path: str) -> ProgramTrace:
    """The program's part of ``path`` (an ``.xplane.pb``), on the same
    clock as ``trace_reduce.load_xplane`` reads."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        scopes = metadata_scopes(f.read())
    data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    ops: List[Op] = []
    device_done = False
    for plane in data.planes:
        is_dev = bool(tr.DEVICE_PLANE.match(plane.name))
        if is_dev and device_done:
            continue
        if not is_dev:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        spans.append((ev.name[len(PROGRAM_PREFIX):],
                                      float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
            continue
        device_done = True
        mods = sorted((float(ev.start_ns),
                       float(ev.start_ns + ev.duration_ns), ev.name)
                      for line in plane.lines if line.name == tr.MODULES_LINE
                      for ev in line.events)
        starts = [m[0] for m in mods]
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
                ops.append(Op(plane.name, line.name, tr.op_name(ev.name),
                              start, float(ev.duration_ns), module,
                              scopes.get((program, ev.name), ())))
    return ProgramTrace(sorted(spans, key=lambda s: (s[1], -s[2])), ops)


_LOADED: Dict[str, ProgramTrace] = {}     # the last file read, by path


def of(reading) -> Optional[ProgramTrace]:
    """The program's trace of a run: the one the ``Reading`` carries
    (``benchmark/tests``), else the raw trace ``run.py`` has just read
    (``bench_out/<cell>/trace``, still on disk when the readers run),
    else nothing."""
    carried = getattr(reading, "program_trace", None)
    if carried is not None:
        return carried
    trace_dir = os.path.join(ROOT, "bench_out", reading.cell["name"],
                             "trace")
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                path = os.path.join(base, f)
                key = f"{path}@{os.path.getmtime(path)}"
                if key not in _LOADED:
                    _LOADED.clear()
                    _LOADED[key] = load_xplane(path)
                return _LOADED[key]
    return None


# ---- idle time, by program span ------------------------------------------

def charge_idle(pt: ProgramTrace, busy, lo: float, hi: float,
                names: Optional[Sequence[str]] = None) -> Dict[str, float]:
    """Idle nanoseconds of ``[lo, hi]`` — the window minus ``busy``
    (sorted disjoint device-busy intervals) — each charged to the
    innermost program span that covers it, else to ``unattributed``.
    With ``names``, only spans so named are charged: what a deeper span
    of another name covers goes to the one of ``names`` around it. The
    values add up to the window's idle time."""
    left = tr.subtract([(lo, hi)], busy)
    acc: Dict[str, float] = {}
    # properly nested spans: the shorter of two that overlap is the inner
    for name, s, e in sorted(pt.spans, key=lambda sp: sp[2] - sp[1]):
        if e <= lo or s >= hi or (names is not None and name not in names):
            continue
        part = tr.total(tr.clip(left, s, e))
        if part > 0:
            acc[name] = acc.get(name, 0.0) + part
            left = tr.subtract(left, [(s, e)])
    acc[UNATTRIBUTED] = tr.total(left)
    return acc


def idle_by_span(reading, names: Optional[Sequence[str]] = None,
                 within: Optional[str] = None
                 ) -> Optional[Dict[str, float]]:
    """``charge_idle`` of a ``Reading``'s window on its first device
    plane; with ``within``, of the parts of the window under the
    harness's spans of that name (``"job"``). Nothing where the trace
    holds no program span or no device plane."""
    pt = of(reading)
    planes = reading.trace.device_planes()
    if pt is None or not pt.spans or not planes:
        return None
    lo, hi = reading.window_ns
    busy = tr.busy_intervals(reading.trace, planes[0], lo, hi)
    parts = [(lo, hi)] if within is None else tr.clip(
        [(e.start_ns, e.end_ns) for e in reading.trace.host_spans(within)],
        lo, hi)
    acc: Dict[str, float] = {}
    for s, e in parts:
        for name, ns in charge_idle(pt, tr.clip(busy, s, e), s, e,
                                    names).items():
            acc[name] = acc.get(name, 0.0) + ns
    return acc


# ---- device time, by scope -----------------------------------------------

def resolve_scopes(ops: Sequence[Op]) -> Dict[Op, Tuple[str, ...]]:
    """Each op's whole scope path. An op the compiler made (a layout
    copy, a ``while``) carries no metadata: a container then takes the
    longest path its scoped children share — those named from the
    program's root if it has any — and what is still without one takes
    its container's. A ``RELATIVE`` path is hung under the nearest op
    around it whose path is named from the root."""
    order = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    parent: Dict[Op, Optional[Op]] = {}
    children: Dict[Op, List[Op]] = {}
    stack: List[Op] = []
    for o in order:
        while stack and o.start_ns >= stack[-1].end_ns:
            stack.pop()
        parent[o] = stack[-1] if stack else None
        if stack:
            children.setdefault(stack[-1], []).append(o)
        stack.append(o)
    path = {o: o.scope for o in order}
    for o in reversed(order):                 # children before containers
        if not path[o] and o in children:
            kids = [path[c] for c in children[o] if path[c]]
            rooted = [k for k in kids if k[0] != RELATIVE]
            if rooted or kids:
                path[o] = tuple(os.path.commonprefix(rooted or kids))
    root: Dict[Optional[Op], Tuple[str, ...]] = {None: ()}
    for o in order:                           # containers before children
        up = parent[o]
        if path[o][:1] == (RELATIVE,):
            root[o] = root[up]
            path[o] = root[up] + path[o][1:]
        elif path[o]:
            root[o] = path[o]
        else:
            root[o] = root[up]
            path[o] = path[up] if up is not None else ()
    return path


def own_times(pt: ProgramTrace, module: str, lo: float, hi: float
              ) -> List[Tuple[Op, Tuple[str, ...], float]]:
    """``(op, scope path, own nanoseconds)`` of the ops of the XLA
    programs whose name matches ``module`` (a regular expression, from
    the start) that lie in ``[lo, hi]``."""
    rx = re.compile(module)
    ops = [o for o in pt.ops
           if rx.match(o.module) and o.end_ns > lo and o.start_ns < hi]
    path = resolve_scopes(ops)
    return [(o, path[o], own) for o, own in tr.self_times(ops)]


def device_by_scope(pt: ProgramTrace, module: str, lo: float, hi: float
                    ) -> Dict[str, float]:
    """Own device nanoseconds of those programs' ops by innermost
    scope, else ``unscoped``. The values add up to the programs' device
    time."""
    acc: Dict[str, float] = {}
    for _, path, own in own_times(pt, module, lo, hi):
        key = path[-1] if path else UNSCOPED
        acc[key] = acc.get(key, 0.0) + own
    return acc


def ops_by_scope(pt: ProgramTrace, module: str, lo: float, hi: float,
                 k: int = 4) -> Dict[str, list]:
    """For a person (``PERF.md`` section 5): under each XLA program and
    scope path, the ``k`` ops with most own time, ``[[op, seconds],
    ...]``."""
    acc: Dict[str, Dict[str, float]] = {}
    for o, path, own in own_times(pt, module, lo, hi):
        key = f"{o.module}: " + ("/".join(path) or UNSCOPED)
        if path and not o.scope:
            key += " (inherited)"
        by = acc.setdefault(key, {})
        by[o.name] = by.get(o.name, 0.0) + own
    return {s: [[n, ns / 1e9] for n, ns in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]
            for s, by in sorted(acc.items(),
                                key=lambda kv: -sum(kv[1].values()))}


# ---- recording a fixture -------------------------------------------------

def record(argv: Sequence[str]) -> int:
    """``python benchmark/program_trace.py --workload <cell> --seed <n>
    --out DIR``: one traced run of the cell (``run.py --trace 1
    --dump-trace``), then, from the raw trace it read, ``DIR/<cell>.
    program.json`` — the program's spans and scoped ops of the window's
    first jobs beside the harness's spans there and what the readers
    made of them (gzip it into ``benchmark/fixtures``) — and
    ``DIR/<cell>.program.summary.json``, the whole window for a person:
    idle seconds by program span, device seconds by scope and op."""
    import argparse
    import json
    from benchmark import run as bench_run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--out", required=True)
    ap.add_argument("--module", default=".*",
                    help="XLA programs of the summary's scope table")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    # the raw trace is deleted once read: read it here too, while
    # ``run.py`` dumps its own reduction (a cell may have no reader that
    # does)
    dump = bench_run.dump_reading

    def read_then_dump(reading, *args):
        of(reading)
        dump(reading, *args)

    bench_run.dump_reading = read_then_dump
    rc = bench_run.main(["--workload", a.workload, "--seed", a.seed,
                         "--seconds", a.seconds, "--trace", "1",
                         "--dump-trace", a.out]
                        + ["--rehearse"] * a.rehearse)
    if rc or not _LOADED:
        return rc or 3
    (pt,) = _LOADED.values()
    with open(os.path.join(a.out, f"{a.workload}.reading.json")) as f:
        rec = json.load(f)
    host = [r for r in rec["events"] if not tr.DEVICE_PLANE.match(r[0])]
    (win,) = [r for r in host if r[2] == tr.HOST_PREFIX + "window"]
    lo, hi = win[3], win[3] + win[4]
    small = ProgramTrace(
        [s for s in pt.spans if lo <= s[1] and s[2] <= hi],
        [o for o in pt.ops if lo <= o.start_ns and o.end_ns <= hi])
    with open(os.path.join(a.out, f"{a.workload}.program.json"), "w") as f:
        json.dump({"program": small.to_table(), "harness": host,
                   "jobs": rec["jobs"], "expect": rec["expect"]}, f)
    wlo = min(s[1] for s in pt.spans) if pt.spans else 0.0
    whi = max(s[2] for s in pt.spans) if pt.spans else 0.0
    busy = tr.merge((o.start_ns, o.end_ns) for o in pt.ops)
    with open(os.path.join(a.out, f"{a.workload}.program.summary.json"),
              "w") as f:
        json.dump({
            "span_seconds": {n: sum(e - s for m, s, e in pt.spans
                                    if m == n) / 1e9
                             for n in sorted({s[0] for s in pt.spans})},
            "idle_seconds_by_span": {
                k: v / 1e9 for k, v in charge_idle(
                    pt, tr.clip(busy, wlo, whi), wlo, whi).items()},
            "device_seconds_by_scope": {
                k: v / 1e9 for k, v in device_by_scope(
                    pt, a.module, wlo, whi).items()},
            "ops_by_scope": ops_by_scope(pt, a.module, wlo, whi)},
            f, indent=1)
    return 0


if __name__ == "__main__":
    from benchmark import program_trace     # one module, not two
    sys.exit(program_trace.record(sys.argv[1:]))
