"""Device-idle milliseconds a job spent on the job path: the idle time
under the harness's ``bench.job`` spans that falls to the program's
``fit.admit``, ``job``, ``glm.fit`` (each its own time, outside the fit's
phases), ``fit.account`` and ``job.finish`` spans, or to no program span,
over the jobs of the traced window.

The four ``*_idle_ms`` readers share this file's table: every idle
nanosecond under ``bench.job`` goes to the innermost span of ``PHASES``
that covers it, so the four add up to a job's idle time, and with the
device-busy time a job to ``fit_s`` of the traced window."""

from benchmark import program_trace

PHASES = {
    "job_path_idle_ms": ("fit.admit", "job", "glm.fit", "fit.account",
                         "job.finish", program_trace.UNATTRIBUTED),
    "glm_prepare_idle_ms": ("glm.design", "glm.response",
                            "glm.lambda_path"),
    "glm_solve_idle_ms": ("glm.solve", "glm.readback"),
    "glm_metrics_idle_ms": ("glm.metrics",),
}
NAMES = tuple(n for names in PHASES.values() for n in names)


def read_part(r, metric: str):
    acc = program_trace.idle_by_span(r, names=NAMES, within="job")
    if acc is None or not r.jobs:
        return None
    return sum(acc.get(n, 0.0) for n in PHASES[metric]) / 1e6 / len(r.jobs)


def read(r):
    return read_part(r, "job_path_idle_ms")
