"""Conformance harness: run the REAL h2o-py client against our server.

Usage:
    python conformance/harness.py smoke          # connect+train smoke test
    python conformance/harness.py pyunit <file>  # run one reference pyunit

The reference client is imported unmodified from /root/reference/h2o-py
(plus the tiny `future` shim in conformance/shims). Datasets referenced as
smalldata/... are resolved through a symlink farm built at runtime in a
temp dir — no reference files are copied into the repo.
"""

from __future__ import annotations

import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_PY = "/root/reference/h2o-py"

sys.path.insert(0, os.path.join(REPO, "conformance", "shims"))
sys.path.insert(0, REF_PY)
sys.path.insert(0, REPO)

# Map smalldata-relative paths → real files available in this environment.
# Only genuinely-present reference data files are linked; everything else
# is synthesized by gen_data.py with the right schema.
SMALLDATA_LINKS = {
    "prostate/prostate.csv": f"{REF_PY}/h2o/h2o_data/prostate.csv",
    "prostate/prostate.csv.zip": None,     # synthesized (zip of the csv)
    # the real smalldata/iris/iris.csv is HEADERLESS (pyunits genfromtxt
    # it); synthesized from the headered extdata copy in build_smalldata
    "iris/iris.csv": None,
    "iris/iris_wheader.csv": "/root/reference/h2o-r/h2o-package/inst/extdata/iris_wheader.csv",
    "extdata/australia.csv": "/root/reference/h2o-core/src/main/resources/extdata/australia.csv",
    "extdata/housevotes.csv": "/root/reference/h2o-core/src/main/resources/extdata/housevotes.csv",
    "extdata/walking.csv": "/root/reference/h2o-r/h2o-package/inst/extdata/walking.csv",
}


def build_smalldata(root: str) -> str:
    """Create the smalldata/ symlink+synthetic farm under `root`."""
    sd = os.path.join(root, "smalldata")
    for rel, src in SMALLDATA_LINKS.items():
        dst = os.path.join(sd, rel)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        if src and os.path.exists(src) and not os.path.exists(dst):
            os.symlink(src, dst)
    iris_hl = os.path.join(sd, "iris/iris.csv")
    if not os.path.exists(iris_hl):
        src = "/root/reference/h2o-core/src/main/resources/extdata/iris.csv"
        with open(src) as f, open(iris_hl, "w") as out:
            out.writelines(f.readlines()[1:])      # drop the header line
    import zipfile
    pz = os.path.join(sd, "prostate/prostate.csv.zip")
    if not os.path.exists(pz):
        with zipfile.ZipFile(pz, "w") as z:
            z.write(os.path.join(sd, "prostate/prostate.csv"),
                    "prostate.csv")
    from conformance import gen_data
    gen_data.generate_all(sd)
    return sd


def start_backend(port: int = 0) -> int:
    """Same backend contract as server_main.py: JAX's default backend,
    H2O3TPU_CONF_CPU=1 opts into host CPU."""
    cpu = os.environ.get("H2O3TPU_CONF_CPU") == "1"
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import h2o3_tpu
    h2o3_tpu.init(backend="cpu" if cpu else None)
    from h2o3_tpu.api.server import start_server
    return start_server(port=port)


def connect(port: int):
    import h2o
    h2o.connect(url=f"http://127.0.0.1:{port}", verbose=False,
                strict_version_check=False)
    return h2o


def smoke():
    port = start_backend()
    h2o = connect(port)
    print("connected:", h2o.cluster().cloud_name, h2o.cluster().version)

    tmp = tempfile.mkdtemp(prefix="h2o3tpu_conf_")
    sd = build_smalldata(tmp)
    os.chdir(tmp)

    fr = h2o.import_file(os.path.join(sd, "prostate/prostate.csv"))
    print("imported:", fr.nrow, "x", fr.ncol, fr.names)
    fr["CAPSULE"] = fr["CAPSULE"].asfactor()

    from h2o.estimators.gbm import H2OGradientBoostingEstimator
    m = H2OGradientBoostingEstimator(ntrees=10, max_depth=4, seed=42)
    m.train(x=["AGE", "RACE", "PSA", "GLEASON"], y="CAPSULE",
            training_frame=fr)
    print("trained:", m.model_id)
    print("auc:", m.auc())
    pred = m.predict(fr)
    print("pred:", pred.nrow, pred.names)
    print("SMOKE OK")


def run_pyunit(path: str):
    port = start_backend()
    connect(port)
    tmp = tempfile.mkdtemp(prefix="h2o3tpu_conf_")
    build_smalldata(tmp)
    os.chdir(tmp)
    sys.path.insert(0, os.path.join(REF_PY, "tests"))
    import runpy
    runpy.run_path(path, run_name="__main__")


if __name__ == "__main__":
    cmd = sys.argv[1] if len(sys.argv) > 1 else "smoke"
    if cmd == "smoke":
        smoke()
    elif cmd == "pyunit":
        run_pyunit(sys.argv[2])
