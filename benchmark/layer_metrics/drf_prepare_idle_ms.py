"""Device-idle milliseconds a job spent while the forest fit prepared its
inputs on the host: the idle time under the harness's ``bench.job``
spans charged to the program's ``drf.bin`` (binning, or finding the
frame's cached bins), ``drf.init`` (the target matrix, the key chain)
and ``drf.fit``'s own time (what lies between its phases: the row-state
program and its one fetch), over the jobs of the traced window. Every
idle nanosecond under ``drf.fit`` goes to the innermost span of
``NAMES`` that covers it — the partition of ``job_path_idle_ms.py`` —
so the tree chunks, the out-of-bag pass and the metrics are not charged
here. Nothing where the trace holds no program span."""

from benchmark import program_trace

PREPARE = ("drf.bin", "drf.init", "drf.fit")
NAMES = PREPARE + ("drf.chunk", "drf.oob", "drf.metrics")


def read(r):
    acc = program_trace.idle_by_span(r, names=NAMES, within="job")
    if acc is None or not r.jobs or not any(n in acc for n in NAMES):
        return None
    return sum(acc.get(n, 0.0) for n in PREPARE) / 1e6 / len(r.jobs)
