"""Conformance backend: boot the cloud + REST server, print the port."""

import faulthandler
import os
import signal
import sys
import time

faulthandler.register(signal.SIGUSR1)   # kill -USR1 <pid> dumps stacks

# Default: whatever backend JAX finds (the TPU on a chip machine).
# H2O3TPU_CONF_CPU=1 opts into host CPU for parallel/offline runs.
_cpu = os.environ.get("H2O3TPU_CONF_CPU") == "1"
if _cpu:
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import h2o3_tpu                               # noqa: E402
h2o3_tpu.init(backend="cpu" if _cpu else None)
from h2o3_tpu.api.server import start_server  # noqa: E402

port = start_server(port=int(sys.argv[1]) if len(sys.argv) > 1 else 0)
print(f"PORT={port}", flush=True)
while True:
    time.sleep(3600)
