"""Device-idle milliseconds a job spent in the training metrics: idle
time charged to the program's ``glm.metrics`` span (``X·β``, the metrics
pass's dispatch and read-back, the host arithmetic on its histogram)
(``job_path_idle_ms.PHASES``)."""

from benchmark.layer_metrics.job_path_idle_ms import read_part


def read(r):
    return read_part(r, "glm_metrics_idle_ms")
