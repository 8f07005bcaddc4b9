"""Traffic kind ``fit-loop``: whole ``train()`` jobs on a resident frame,
one after another from one caller (a closed loop, as a user's script or
AutoML is). A new job starts while the window's clock reads under
``seconds``; the window closes when the last job started has ended, so
every job in it is whole. Each job's model and job records are released
once its outputs are read, so peak memory does not grow with the number
of jobs a window happens to hold.

Parameter (the traffic file): ``job`` — what a job overrides in the
configuration's estimator parameters.

End-to-end metric of this kind: ``fit_s`` — wall seconds of the window,
its start to the end of its last job, over the whole jobs completed in
it: all the time over all the jobs, never a median of jobs.
"""

from __future__ import annotations

SPANS = ("job", "read_outputs", "between_jobs")


def check(traffic: dict) -> None:
    if not isinstance(traffic.get("job", {}), dict):
        raise ValueError("fit-loop: 'job' must be an object of estimator "
                         "parameters")


def one_job(sut, traffic: dict, span) -> dict:
    """One whole job: train, read its outputs to the host, release."""
    with span("job"):
        model, made = sut.train()
        with span("read_outputs"):
            outputs = sut.read_outputs(model)
    with span("between_jobs"):
        sut.release(model, made)
    return outputs


def run(sut, traffic: dict, seconds: float, clock, span) -> list:
    """Drive the window. Returns one record per completed job:
    ``{"start": s, "end": s, "outputs": ...}`` on ``clock``."""
    jobs = []
    t0 = clock()
    while clock() - t0 < seconds:
        start = clock()
        outputs = one_job(sut, traffic, span)
        jobs.append({"start": start, "end": clock(), "outputs": outputs})
    return jobs


def end_to_end(jobs: list, t_window: float) -> dict:
    """This kind's end-to-end numbers over the completed ``jobs`` of a
    window that opened at ``t_window`` (``clock``'s seconds)."""
    return {"fit_s": (jobs[-1]["end"] - t_window) / len(jobs)}
