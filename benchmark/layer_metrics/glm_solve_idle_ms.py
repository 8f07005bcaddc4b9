"""Device-idle milliseconds a job spent around the solve: idle time
charged to the program's ``glm.solve`` span (dispatch latency before the
program starts, the block after it ends) and ``glm.readback`` (the
coefficients' and the iteration count's way to the host)
(``job_path_idle_ms.PHASES``)."""

from benchmark.layer_metrics.job_path_idle_ms import read_part


def read(r):
    return read_part(r, "glm_solve_idle_ms")
