"""Device-idle milliseconds a job spent while the GBM fit prepared its
inputs on the host: the idle time under the harness's ``bench.job``
spans charged to the program's ``gbm.bin`` (binning, or finding the
frame's cached bins), ``gbm.init`` (the response's trip to the host and
back, the initial margin) and ``gbm.fit``'s own time (what lies between
its phases), over the jobs of the traced window. Every idle nanosecond
under ``gbm.fit`` goes to the innermost span of ``NAMES`` that covers
it, so the boosting chunks, the re-scoring and the metrics are not
charged here. Nothing where the trace holds no program span."""

from benchmark import program_trace

PREPARE = ("gbm.bin", "gbm.init", "gbm.fit")
NAMES = PREPARE + ("gbm.chunk", "gbm.rescore", "gbm.metrics")


def read(r):
    acc = program_trace.idle_by_span(r, names=NAMES, within="job")
    if acc is None or not r.jobs:
        return None
    return sum(acc.get(n, 0.0) for n in PREPARE) / 1e6 / len(r.jobs)
