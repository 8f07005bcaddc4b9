"""IRLS iterations a job ran, as the program itself reports them: the
mean ``iterations`` of the ``glm.solve`` spans in the program's span
ring (``telemetry.spans_snapshot()``, what ``GET /3/Metrics`` serves)
that started inside the window on the host clock. Has to equal the
plain reference's ``passes``. Nothing where the spans carry no count."""


def read(r):
    try:
        from h2o3_tpu import telemetry
    except ImportError:
        return None
    if not r.jobs:
        return None
    lo, hi = r.t_window, max(j["end"] for j in r.jobs)
    ran = [s["meta"]["iterations"]
           for s in telemetry.spans_snapshot(last=1 << 20)
           if s["name"] == "glm.solve" and "iterations" in s["meta"]
           and lo <= s["start_ms"] / 1e3 <= hi]
    return sum(ran) / len(ran) if ran else None
