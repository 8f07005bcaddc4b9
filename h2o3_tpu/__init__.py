"""h2o3_tpu — a TPU-native, JAX/XLA-based reimplementation of the H2O-3
distributed ML platform (reference: sashashura/h2o-3).

The reference is a JVM cluster: Frame/Vec/Chunk columnar store + MRTask
map/reduce (h2o-core/src/main/java/water/MRTask.java) + hex.* algorithms.
Here the same capabilities are rebuilt TPU-first:

- Frame        = dict of dtype-narrowed device arrays sharded over a
                 ``jax.sharding.Mesh`` 'data' axis (replaces water.fvec).
- map/reduce   = ``shard_map`` + ``psum`` over ICI (replaces the MRTask
                 node tree + Fork/Join, water/MRTask.java:716-756).
- algorithms   = jitted JAX programs (histogram GBM/DRF on the MXU, GLM via
                 einsum Gram + Cholesky, DeepLearning as an MLP, ...).
- REST surface = the /3 and /99 JSON API kept compatible in spirit with
                 water.api.RequestServer so h2o-py-style clients can drive it.

Public API mirrors the h2o-py module surface (h2o-py/h2o/h2o.py):
``init``, ``import_file``, ``H2OFrame``-like ``Frame``, estimator classes.
"""

import time as _time

_T_IMPORT = _time.time()

try:
    # pandas >= 3.0 backs str columns with pyarrow; libarrow segfaults
    # under this image's threading profile (observed: handler threads in
    # the REST server dying inside libarrow.so during frame ops). Python
    # string storage sidesteps the native library entirely — string work
    # is host-side control plane here, never the hot path.
    import pandas as _pd
    _pd.set_option("mode.string_storage", "python")
except Exception:
    pass

from h2o3_tpu.version import __version__
from h2o3_tpu.core.cloud import init, cluster_info, shutdown
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.io.parser import (export_file, import_file, parse_raw,
                                upload_numpy)
from h2o3_tpu.io.sql import import_sql_select, import_sql_table
from h2o3_tpu.io.persist import (load_frame, load_model, persist_manager,
                                 save_frame, save_model)
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.core.memgov import MemoryBudgetExceeded
from h2o3_tpu.core.scope import Scope
from h2o3_tpu.core.udf import (upload_custom_distribution,
                               upload_custom_metric)

# what importing the package cost this process (jax and pandas among
# it where nobody imported them before): set-up no span can hold
from h2o3_tpu.telemetry import gauge as _gauge
_gauge("process_import_seconds").set(_time.time() - _T_IMPORT)

__all__ = [
    "__version__",
    "upload_custom_distribution",
    "upload_custom_metric",
    "init",
    "cluster_info",
    "shutdown",
    "Frame",
    "import_file",
    "export_file",
    "import_sql_select",
    "import_sql_table",
    "parse_raw",
    "upload_numpy",
    "DKV",
    "MemoryBudgetExceeded",
    "save_frame",
    "load_frame",
    "save_model",
    "load_model",
    "persist_manager",
]
