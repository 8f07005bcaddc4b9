"""Device-idle milliseconds a job spent scoring: the idle time under the
harness's ``bench.job`` spans charged to the program's
``deeplearning.score`` (each full-data loss between chunks and the
scalar's way back) and ``deeplearning.metrics`` (the sample mask, the
training metrics), by ``dl_prepare_idle_ms``'s table. Nothing where the
trace holds no ``deeplearning.score`` span."""

from benchmark.layer_metrics import dl_prepare_idle_ms as table


def read(r):
    return table.read_part(r, table.SCORE)
