"""Device-idle milliseconds a job spent while the DeepLearning fit
prepared its inputs on the host: the idle time under the harness's
``bench.job`` spans charged to the program's ``deeplearning.design``
(the design matrix and the row mask), ``deeplearning.response`` (the
response's trip to the host and back), ``deeplearning.init`` (weights
and optimizer state) and ``deeplearning.fit``'s own time (what lies
between its phases), over the jobs of the traced window. Every idle
nanosecond under ``deeplearning.fit`` goes to the innermost span of
``NAMES`` that covers it, so the training chunks, the scoring passes and
the metrics are not charged here (``dl_score_idle_ms`` shares the
table). Nothing where the trace holds no ``deeplearning.design`` span."""

from benchmark import program_trace

PREPARE = ("deeplearning.design", "deeplearning.response",
           "deeplearning.init", "deeplearning.fit")
SCORE = ("deeplearning.score", "deeplearning.metrics")
NAMES = PREPARE + ("deeplearning.chunk",) + SCORE


def read_part(r, part):
    pt = program_trace.of(r)
    if pt is None or not r.jobs or \
            not any(name == part[0] for name, _, _ in pt.spans):
        return None
    acc = program_trace.idle_by_span(r, names=NAMES, within="job")
    if acc is None:
        return None
    return sum(acc.get(n, 0.0) for n in part) / 1e6 / len(r.jobs)


def read(r):
    return read_part(r, PREPARE)
