"""Mini-batch steps the fit computed and threw away, over the steps it
computed: a chunk's program always runs its static number of steps
(``steps_run``) and masks those past the ones still wanted (``steps``),
as the ``deeplearning.chunk`` spans in the program's span ring
(``telemetry.spans_snapshot()``, what ``GET /3/Metrics`` serves) that
started inside the window report them. Nothing where the spans carry no
``steps_run``."""


def read(r):
    try:
        from h2o3_tpu import telemetry
    except ImportError:
        return None
    if not r.jobs or getattr(r, "t_window", None) is None:
        return None             # a recorded table: no live span ring
    lo, hi = r.t_window, max(j["end"] for j in r.jobs)
    ran = [(s["meta"]["steps_run"], s["meta"]["steps"])
           for s in telemetry.spans_snapshot(last=1 << 20)
           if s["name"] == "deeplearning.chunk" and "steps_run" in s["meta"]
           and lo <= s["start_ms"] / 1e3 <= hi]
    computed = sum(run for run, _ in ran)
    if computed <= 0:
        return None
    return 100.0 * sum(run - kept for run, kept in ran) / computed
