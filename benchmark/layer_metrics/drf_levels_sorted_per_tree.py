"""Frontier levels of a tree that ordered the rows by node, as the
program itself reports them: the mean ``levels_sorted`` of the
``drf.chunk`` spans in the program's span ring
(``telemetry.spans_snapshot()``, what ``GET /3/Metrics`` serves) that
started inside the window on the host clock. Nothing where the spans
carry no count (a program that sorts at every level says nothing; a
recorded table has no live span ring)."""


def read(r):
    try:
        from h2o3_tpu import telemetry
    except ImportError:
        return None
    if not r.jobs or getattr(r, "t_window", None) is None:
        return None
    lo, hi = r.t_window, max(j["end"] for j in r.jobs)
    ran = [s["meta"]["levels_sorted"]
           for s in telemetry.spans_snapshot(last=1 << 20)
           if s["name"] == "drf.chunk" and "levels_sorted" in s["meta"]
           and lo <= s["start_ms"] / 1e3 <= hi]
    return sum(ran) / len(ran) if ran else None
