"""Device-idle milliseconds a job spent while the GLM fit prepared its
inputs on the host: idle time charged to the program's ``glm.design``
(design matrix, weights, offset), ``glm.response`` (the response's trip
to the host and back) and ``glm.lambda_path`` spans
(``job_path_idle_ms.PHASES``)."""

from benchmark.layer_metrics.job_path_idle_ms import read_part


def read(r):
    return read_part(r, "glm_prepare_idle_ms")
