"""The one reduction from a profiler trace to named device intervals.

``load_xplane`` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``,
nothing but JAX) into a ``Trace``: a flat table of events
``(plane, line, name, start_ns, dur_ns, module)``, kept for the device
planes and for the harness's own host annotations (names that start
with ``bench.``). Everything else is arithmetic on that table, so it
can be checked on a small recorded table (``benchmark/fixtures``) with
no profiler and no chip.

Device time is read from the ``XLA Ops`` line of each device plane:
one event per executed HLO op (named by the op's own name, ``fusion.2``,
not the whole HLO line the profiler prints), nested where an op (a ``while``, a
``call``) contains others. Busy time is the union of those intervals;
an op's own time is its duration minus its children's. ``module`` is
the XLA program the op ran in (the ``XLA Modules`` line's event that
contains it), e.g. ``jit_predict_forest``.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace:
    def __init__(self, events: Iterable[Event]):
        self.events: List[Event] = sorted(
            events, key=lambda e: (e.plane, e.line, e.start_ns, -e.dur_ns))

    # ---- construction --------------------------------------------------

    @classmethod
    def from_table(cls, rows: Sequence[Sequence]) -> "Trace":
        return cls(Event(str(r[0]), str(r[1]), str(r[2]), float(r[3]),
                         float(r[4]), str(r[5]) if len(r) > 5 else "")
                   for r in rows)

    def to_table(self) -> list:
        return [[e.plane, e.line, e.name, e.start_ns, e.dur_ns, e.module]
                for e in self.events]

    # ---- selections ----------------------------------------------------

    def device_planes(self) -> List[str]:
        return sorted({e.plane for e in self.events
                       if DEVICE_PLANE.match(e.plane)})

    def ops(self, plane: Optional[str] = None) -> List[Event]:
        """Device op events, of one device plane or of all."""
        return [e for e in self.events if e.line == OPS_LINE
                and DEVICE_PLANE.match(e.plane)
                and (plane is None or e.plane == plane)]

    def host_spans(self, name: str) -> List[Event]:
        """The harness's own annotations called ``name``."""
        return [e for e in self.events
                if not DEVICE_PLANE.match(e.plane)
                and e.name == HOST_PREFIX + name]


def load_xplane(path: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb``) into a ``Trace``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events: List[Event] = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        mods: List[Event] = []
        ops: List[Event] = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not is_dev and not ev.name.startswith(HOST_PREFIX):
                    continue
                e = Event(plane.name, line.name, op_name(ev.name),
                          float(ev.start_ns), float(ev.duration_ns))
                (mods if line.name == MODULES_LINE and is_dev else ops
                 ).append(e)
        mods.sort(key=lambda e: e.start_ns)
        starts = [m.start_ns for m in mods]
        for e in ops:
            module = ""
            if is_dev and mods:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns < mods[i].end_ns:
                    module = module_name(mods[i].name)
            events.append(dataclasses.replace(e, module=module))
        events.extend(mods)
    return Trace(events)


def op_name(raw: str) -> str:
    """The profiler names a device op by its whole HLO line
    (``%fusion.2 = f32[...] fusion(...)``): keep the op's own name,
    ``fusion.2``."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()


def module_name(raw: str) -> str:
    """``jit_predict_forest(1234567)`` → ``jit_predict_forest``."""
    return re.sub(r"\(\d+\)$", "", raw).strip()


# ---- interval arithmetic ------------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_intervals(trace: Trace, plane: str, lo: float, hi: float):
    return clip(merge((e.start_ns, e.end_ns) for e in trace.ops(plane)),
                lo, hi)


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi]`` (trace clock, ns) in which an operation
    ran on the device, averaged over the device planes."""
    planes = trace.device_planes()
    if not planes:
        return 0.0
    return sum(total(busy_intervals(trace, p, lo, hi))
               for p in planes) / len(planes) / 1e9


def idle_share(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """1 − busy/window, or nothing where no device op ran at all."""
    busy = busy_seconds(trace, lo, hi)
    if busy <= 0 or hi <= lo:
        return None
    return 1.0 - busy / ((hi - lo) / 1e9)


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each op's own nanoseconds: its duration minus that of the ops
    nested directly inside it (same plane and line)."""
    out: List[Tuple[Event, float]] = []
    by_plane = {}
    for e in ops:
        by_plane.setdefault((e.plane, e.line), []).append(e)
    for evs in by_plane.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: List[list] = []           # [event, child_ns]
        for e in evs:
            while stack and e.start_ns >= stack[-1][0].end_ns:
                done, child = stack.pop()
                out.append((done, max(done.dur_ns - child, 0.0)))
            if stack:
                stack[-1][1] += e.dur_ns
            stack.append([e, 0.0])
        while stack:
            done, child = stack.pop()
            out.append((done, max(done.dur_ns - child, 0.0)))
    return out


def device_seconds(trace: Trace, want: Callable[[Event], bool],
                   lo: float, hi: float) -> float:
    """Union of the device intervals of the ops ``want`` selects, within
    ``[lo, hi]``, averaged over the device planes, in seconds."""
    planes = trace.device_planes()
    if not planes:
        return 0.0
    acc = 0.0
    for p in planes:
        acc += total(clip(merge((e.start_ns, e.end_ns)
                                for e in trace.ops(p) if want(e)), lo, hi))
    return acc / len(planes) / 1e9


def in_module(pattern: str) -> Callable[[Event], bool]:
    """Selects the device ops that ran inside an XLA program whose name
    matches ``pattern`` (a regular expression, from the start)."""
    rx = re.compile(pattern)
    return lambda e: bool(rx.match(e.module))


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` device operations with most own time in the window:
    ``[[module/op, seconds], ...]`` (summed over events and planes,
    divided by the planes)."""
    planes = max(len(trace.device_planes()), 1)
    acc = {}
    for e, own in self_times([o for o in trace.ops()
                              if o.end_ns > lo and o.start_ns < hi]):
        key = f"{e.module}/{e.name}" if e.module else e.name
        acc[key] = acc.get(key, 0.0) + own
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / planes / 1e9] for name, ns in ranked]


def subtract(intervals, holes):
    """``intervals`` minus ``holes`` (both sorted and disjoint)."""
    out = []
    for s, e in intervals:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append((s, hs))
            s = max(s, he)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def idle_gaps(trace: Trace, lo: float, hi: float,
              labels: Sequence[str], k: int = 10) -> list:
    """Idle seconds of the first device plane in the window, charged to
    what the host was doing: every idle nanosecond goes to the innermost
    of the harness's spans ``labels`` (given outermost first) that
    covers it, else to ``unattributed``. ``[[label, seconds], ...]``,
    largest first."""
    planes = trace.device_planes()
    if not planes:
        return []
    left = subtract([(lo, hi)], busy_intervals(trace, planes[0], lo, hi))
    acc = {}
    for lab in reversed(labels):
        cover = merge((e.start_ns, e.end_ns) for e in trace.host_spans(lab))
        rest = subtract(left, cover)
        acc[lab] = total(left) - total(rest)
        left = rest
    acc["unattributed"] = total(left)
    ranked = sorted(((n, ns) for n, ns in acc.items() if ns > 0),
                    key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def summary(trace: Trace, k: int = 40) -> dict:
    """What a person looks at first: planes, lines, and the names that
    take most time in each line."""
    out = {}
    for e in trace.events:
        line = out.setdefault(e.plane, {}).setdefault(e.line, {})
        n, ns = line.get(e.name, (0, 0.0))
        line[e.name] = (n + 1, ns + e.dur_ns)
    return {pl: {ln: sorted(([nm, c, ns / 1e9] for nm, (c, ns) in d.items()),
                            key=lambda r: -r[2])[:k]
                 for ln, d in lines.items()}
            for pl, lines in out.items()}
