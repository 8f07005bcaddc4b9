#!/usr/bin/env python3
"""chip_smoke.py — does the system, as it stands, run on the chip?

One process drives the main path once through the entry points a user
calls — ``h2o3_tpu.init()``, CSV ingest, estimator ``.train``,
``Model.predict``, the REST server — at the bench's flagship widths, on
data made from ``--seed``, and checks every result by the repo's own
means. One JSON line per phase; any phase that raises ends the run with
``{"ok": false, ...}`` and exit 1. The last line of a good run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py              one chip, all phases
    python chip_smoke.py --chips 4    ONLY the row-sharded GBM/GLM fits on
                                      a data=4 mesh against the same fits
                                      on a data=1 mesh (same process)
    python chip_smoke.py --rehearse   tiny sizes, accepts the CPU, Pallas
                                      in interpret mode: the control-flow
                                      rehearsal (and a tier-1 test)

Without ``--rehearse`` any platform but ``tpu`` fails in the first
phase: nothing here continues on the CPU. The numbers printed are
set-up facts of one run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# (real, rehearse) — widths are never cut, only rows/trees
SIZES = {
    "airlines_rows": (5_000_000, 4_000),
    "gbm_trees": (50, 4),
    "gbm_trees_4chip": (25, 4),
    "glm_rows": (2_000_000, 4_000),
    "dl_rows": (200_000, 2_000),
    "score_rows": (100_000, 1_000),
}
GLM_COLS = 28           # HIGGS shape (bench_glm)
DL_INPUTS = 784         # MNIST shape (bench_dl)
DL_HIDDEN = [200, 200]
SERVE_REQUESTS = 5
SERVE_ROWS = 8


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _json_cell(v):
    """A raw host cell (level string, float, None/NaN for NA) as JSON."""
    if v is None or v != v:
        return None
    return v if isinstance(v, str) else float(v)


def level_check(mesh, n_bins, is_cat, interpret):
    """A jitted ``(bins, nb, w, g, h) -> {level: (hist_xla, hist_kernel,
    nid_xla, nid_kernel, nid_partition_kernel)}`` for tree levels 0 and
    3: the kernel level pass beside the XLA composition on the same
    rows, and the partition kernel alone on the XLA path's own split
    decisions (where routing has to agree exactly)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from h2o3_tpu.models.tree import TreeScalars
    from h2o3_tpu.ops import pallas as plx
    from h2o3_tpu.ops.pallas import treekernel as tk
    from h2o3_tpu.parallel.mesh import DATA_AXIS
    is_cat = jnp.asarray(is_cat)
    sc = TreeScalars(jnp.float32(10.0), jnp.float32(1.0),
                     jnp.float32(1e-5), jnp.int32(6))
    inf = jnp.full((1,), jnp.inf, jnp.float32)

    @jax.jit
    def check(bins, nb, w, g, h):
        n, F = bins.shape
        cm = jnp.ones((F,), bool)
        stats = jnp.stack([w, w * g, w * h])
        nid, prev, out = jnp.zeros((n,), jnp.int32), None, {}
        for d in range(4):
            kw = dict(d=d, n_nodes=2 ** d, n_bins=n_bins, block_rows=4096,
                      mesh=mesh)
            x = tk.xla_level(bins, nid, w, g, h, prev, cm, nb, is_cat,
                             None, -inf, inf, sc, **kw)
            if d in (0, 3):
                k = tk.fused_level(bins, nid, stats, prev, cm, nb, is_cat,
                                   None, -inf, inf, sc, interpret=interpret,
                                   **kw)
                _, _, bf, bt, bnal, _, _, lmask, split, _ = x
                tile = plx.tile_rows(F, n_bins, 2 ** d)
                routed = jax.shard_map(       # a kernel needs its shard
                    lambda b, i, *decisions: tk._partition_call(
                        b.T, i[None, :], *decisions, n_bins=n_bins,
                        block_rows=tile, interpret=interpret)[0],
                    mesh=mesh,
                    in_specs=(P(DATA_AXIS), P(DATA_AXIS)) + (P(),) * 6,
                    out_specs=P(DATA_AXIS), check_vma=False)(
                    bins, nid, bf, bt, bnal, split, is_cat[bf] & split,
                    lmask)
                out[d] = (x[0], k[0], x[-1], k[-1], routed)
            prev, nid = x[0], x[-1]
        return out

    return check


class Smoke:
    def __init__(self, args):
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.out = args.out
        self.device = None

    def size(self, name: str) -> int:
        return SIZES[name][1 if self.rehearse else 0]

    # ------------------------------------------------------------ harness

    def phase(self, name, fn):
        """Run one phase: time it, charge it the XLA compile seconds the
        compile observer saw meanwhile, print its line. A raise ends the
        run — nothing is caught and carried past."""
        t0 = time.time()
        c0 = self.compile_seconds()
        try:
            facts = fn() or {}
        except BaseException as e:   # noqa: BLE001 - reported, then exit 1
            emit({"ok": False, "phase": name,
                  "error": f"{type(e).__name__}: {e}"[:2000]})
            raise SystemExit(1)
        line = {"phase": name, "seconds": round(time.time() - t0, 3)}
        c1 = self.compile_seconds()
        if c0 is not None and c1 is not None:
            line["compile_seconds"] = round(c1 - c0, 3)
        line.update(facts)
        emit(line)

    @staticmethod
    def compile_seconds():
        try:
            from h2o3_tpu import telemetry
        except ImportError:         # the init phase reports it
            return None
        return float(telemetry.histogram("xla_compile_seconds").sum)

    def require_tpu(self, what, arr) -> list:
        plats = sorted({d.platform for d in arr.devices()})
        if not self.rehearse and plats != ["tpu"]:
            raise RuntimeError(f"{what} lives on {plats}, not on a TPU")
        return plats

    # ------------------------------------------------------------- phases

    def init(self, want_devices: int):
        import jax
        import jaxlib
        import h2o3_tpu
        from importlib import metadata
        from h2o3_tpu.telemetry import roofline
        if self.rehearse and jax.default_backend() != "tpu":
            # the rehearsal walks the kernel code path through the
            # Pallas interpreter; a real run never does
            os.environ["H2O3TPU_PALLAS"] = "interpret"
        info = h2o3_tpu.init()
        d = jax.devices()[0]
        self.device = {"platform": d.platform, "kind": d.device_kind,
                       "count": len(jax.devices())}
        if not self.rehearse:
            if d.platform != "tpu":
                raise RuntimeError(
                    f"JAX found platform {d.platform!r}, not a TPU")
            if len(jax.devices()) != want_devices:
                raise RuntimeError(
                    f"need {want_devices} chip(s), JAX reports "
                    f"{len(jax.devices())}")
            if info["mesh_shape"]["data"] != want_devices:
                raise RuntimeError(
                    f"init() built mesh {info['mesh_shape']}, "
                    f"expected data={want_devices}")
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = None
        return {"device": self.device, "mesh_shape": info["mesh_shape"],
                "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu,
                "compile_cache_dir": jax.config.jax_compilation_cache_dir,
                "peaks": roofline.device_peaks(refresh=True)}

    def ingest(self):
        from h2o3_tpu import native
        from h2o3_tpu.io.stream import stream_import_csv
        from h2o3_tpu.utils.synth import write_airlines_csv
        n = self.size("airlines_rows")
        path = os.path.join(self.out, f"airlines_{n}.csv")
        t0 = time.time()
        write_airlines_csv(path, n, self.seed)
        t_write = time.time() - t0
        so = native.library_path()
        prebuilt = os.path.exists(so)
        if native.load_csv_parser() is None:
            raise RuntimeError("native CSV tokenizer did not build/load "
                               "(g++ is part of the installation)")
        t0 = time.time()
        self.air = stream_import_csv(path)
        t_parse = time.time() - t0
        nbytes = os.path.getsize(path)
        os.unlink(path)
        if (self.air.nrows, self.air.ncols) != (n, 11):
            raise RuntimeError(f"parsed {self.air.nrows} x "
                               f"{self.air.ncols}, wrote {n} x 11")
        return {"rows": n, "csv_bytes": nbytes,
                "write_seconds": round(t_write, 3),
                "parse_seconds": round(t_parse, 3),
                "tokenizer": "native",
                "native_library": os.path.basename(so),
                "native_built_now": not prebuilt}

    def _pallas_facts(self):
        import jax
        from h2o3_tpu import telemetry
        from h2o3_tpu.ops import pallas as plx
        from h2o3_tpu.parallel.mesh import data_size
        mode, reason = plx.decide(plx.knob_value(), jax.default_backend(),
                                  data_size(), plx.available())
        if mode == "interpret" and not self.rehearse:
            raise RuntimeError("Pallas resolved to interpret mode")
        return {
            "pallas_mode": mode, "pallas_off_reason": reason,
            "pallas_kernel_launches_total":
                telemetry.REGISTRY.total("pallas_kernel_launches_total"),
            "pallas_fallbacks_total": {
                m.labels.get("reason", ""): m.value
                for m in telemetry.REGISTRY.find("pallas_fallbacks_total")}}

    def kernels_vs_xla(self, rows=65_536):
        """``level_check`` on the fit's own binned rows: the histogram
        kernel within 1% of XLA's (the two round f32 products
        differently), the partition kernel EXACTLY equal to the XLA
        routing when both are given the same split decisions."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from h2o3_tpu.parallel.mesh import get_mesh, padded_rows
        bm = self.gbm_model.bm
        n = min(padded_rows(rows), bm.bins.shape[0])
        r = np.random.RandomState(self.seed + 11)
        out = level_check(
            get_mesh(), bm.nbins_total, np.asarray(bm.is_cat, bool),
            interpret=jax.default_backend() != "tpu")(
            bm.bins[:n], bm.nbins, jnp.ones((n,), jnp.float32),
            jnp.asarray(r.randn(n).astype(np.float32)),
            jnp.full((n,), 0.25, jnp.float32))
        facts = {}
        for d, (hx, hk, nx, nk, routed) in out.items():
            hx, hk = np.asarray(hx, np.float64), np.asarray(hk, np.float64)
            rel = float(np.abs(hx - hk).max() / np.abs(hx).max())
            same = float((np.asarray(nx) == np.asarray(nk)).mean())
            exact = bool((np.asarray(nx) == np.asarray(routed)).all())
            facts[f"level{d}"] = {
                "hist_max_diff_over_max": rel,
                "rows_routed_alike": same,
                "partition_kernel_equals_xla_routing": exact}
            if not (rel <= 1e-2 and exact and same >= 0.99):
                raise RuntimeError(f"kernels vs XLA at level {d}: "
                                   f"{facts[f'level{d}']}")
        return facts

    def gbm(self, trees_key="gbm_trees"):
        from h2o3_tpu.models.gbm import GBMEstimator
        from h2o3_tpu.utils.synth import AIRLINES_RESPONSE
        ntrees = self.size(trees_key)
        self.gbm_model = GBMEstimator(ntrees=ntrees, max_depth=6,
                                      seed=1).train(
            self.air, y=AIRLINES_RESPONSE)
        auc = float(self.gbm_model.training_metrics["AUC"])
        bm = self.gbm_model.bm
        if not auc >= 0.75:
            raise RuntimeError(f"training AUC {auc} < 0.75")
        facts = {"ntrees": ntrees, "max_depth": 6, "auc": auc,
                 "nbins_total": bm.nbins_total,
                 "bins_shape": list(bm.bins.shape),
                 "bins_dtype": str(bm.bins.dtype)}
        facts.update(self._pallas_facts())
        if facts["pallas_mode"] != "off":
            facts["kernels_vs_xla"] = self.kernels_vs_xla()
        return facts

    def _glm_frame(self):
        import numpy as np
        import h2o3_tpu
        n = self.size("glm_rows")
        r = np.random.RandomState(self.seed + 3)
        X = r.randn(n, GLM_COLS).astype(np.float32)
        beta = r.randn(GLM_COLS) * 0.3
        yv = (r.rand(n) < 1 / (1 + np.exp(-(X @ beta)))).astype(int)
        cols = {f"x{i}": X[:, i] for i in range(GLM_COLS)}
        cols["y"] = np.array(["b", "s"], object)[yv]
        return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"]), beta

    def glm(self):
        import numpy as np
        from h2o3_tpu.models.glm import GLMEstimator
        fr, beta = self._glm_frame()
        m = GLMEstimator(family="binomial", solver="irlsm", lambda_=0.0,
                         max_iterations=8, standardize=True).train(
            fr, y="y")
        self.glm_coef = {k: float(v) for k, v in m.coefficients.items()}
        coef = np.array([self.glm_coef[f"x{i}"] for i in range(GLM_COLS)])
        auc = float(m.training_metrics["AUC"])
        if not np.isfinite(list(self.glm_coef.values())).all():
            raise RuntimeError("GLM coefficients are not finite")
        err = float(np.abs(coef - beta).max())
        # the generator's own beta is the reference: 8 IRLS iterations
        # on >= thousands of rows land near it; 0.5 is far outside noise
        if not (auc > 0.6 and err < 0.5):
            raise RuntimeError(f"GLM off: AUC {auc}, max |coef - beta| "
                               f"{err}")
        return {"rows": fr.nrows, "cols": GLM_COLS, "auc": auc,
                "max_abs_coef_error_vs_generator": err}

    def dl(self):
        import numpy as np
        import h2o3_tpu
        from h2o3_tpu.models.deeplearning import DeepLearningEstimator
        n = self.size("dl_rows")
        r = np.random.RandomState(self.seed + 5)
        X = (r.rand(n, DL_INPUTS) > 0.8).astype(np.float32)
        # learnable labels: the class whose random projection is largest
        yv = np.argmax(X @ r.randn(DL_INPUTS, 10).astype(np.float32),
                       axis=1)
        cols = {f"p{i}": X[:, i] for i in range(DL_INPUTS)}
        cols["label"] = yv.astype(str)
        fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["label"])
        del X, cols
        # the loss must FALL: a tenth of an epoch against a whole one
        # (the fused chunk program is shared by any epoch count)
        losses = []
        for epochs in (0.1, 1.0):
            m = DeepLearningEstimator(
                hidden=DL_HIDDEN, activation="rectifier", epochs=epochs,
                seed=1).train(fr, y="label")
            losses.append(float(m.training_metrics["logloss"]))
        if not (np.isfinite(losses).all() and losses[1] < losses[0]):
            raise RuntimeError(f"DL logloss did not fall: {losses}")
        return {"rows": n, "inputs": DL_INPUTS, "hidden": DL_HIDDEN,
                "logloss_after_0.1_epoch": losses[0],
                "logloss_after_1_epoch": losses[1]}

    def score(self):
        import numpy as np
        from h2o3_tpu.genmodel import load_mojo
        from h2o3_tpu.models.generic import _frame_raw_columns
        n = self.size("score_rows")
        self.slice = self.air.row_slice(0, n)
        self.slice_pred = self.gbm_model.predict(self.slice)
        path = os.path.join(self.out, "gbm_mojo.zip")
        self.gbm_model.download_mojo(path)
        mojo = load_mojo(path)
        self.slice_raw = _frame_raw_columns(self.slice, mojo.names)
        offline = mojo.predict(self.slice_raw)
        worst = 0.0
        for k in ("p0", "p1"):
            a = self.slice_pred.col(k).to_numpy().astype(np.float64)
            b = np.asarray(offline[k], np.float64)
            if a.shape != (n,) or not np.isfinite(a).all():
                raise RuntimeError(f"predict column {k}: shape {a.shape} "
                                   f"or non-finite values")
            worst = max(worst, float(np.abs(a - b).max()))
        if not worst <= 1e-5:
            raise RuntimeError(f"predict vs MOJO reference: max diff "
                               f"{worst} > 1e-5")
        return {"rows": n, "max_abs_diff_vs_mojo": worst}

    def serve(self):
        import numpy as np
        from h2o3_tpu import telemetry
        from h2o3_tpu.api.server import start_server, stop_server
        m = self.gbm_model
        port = start_server(port=0, background=True)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/3/Cloud", timeout=60) as r:
                cloud = json.loads(r.read())
            if not self.rehearse and cloud.get("platform") != "tpu":
                raise RuntimeError(f"/3/Cloud platform: "
                                   f"{cloud.get('platform')!r}")
            want = {k: self.slice_pred.col(k).to_numpy()
                    for k in ("p0", "p1")}
            for i in range(SERVE_REQUESTS):
                lo = i * SERVE_ROWS
                rows = [{k: _json_cell(v[j])
                         for k, v in self.slice_raw.items()}
                        for j in range(lo, lo + SERVE_ROWS)]
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/3/Predictions/models/{m.key}",
                    data=urllib.parse.urlencode(
                        {"rows": json.dumps(rows)}).encode(),
                    method="POST")
                with urllib.request.urlopen(req, timeout=120) as r:
                    j = json.loads(r.read())
                for k, v in want.items():
                    got = np.asarray(j["predictions"][k], np.float64)
                    ref = np.asarray(v[lo:lo + SERVE_ROWS], np.float64)
                    if not np.array_equal(got, ref):
                        raise RuntimeError(
                            f"request {i} column {k}: REST differs from "
                            f"Model.predict by "
                            f"{float(np.abs(got - ref).max())}")
        finally:
            stop_server()
        misses = sum(
            c.value for c in telemetry.REGISTRY.find("jit_cache_miss_total")
            if c.labels.get("fn") == "serving.gbm")
        return {"cloud_platform": cloud.get("platform"),
                "requests": SERVE_REQUESTS, "rows_per_request": SERVE_ROWS,
                "bit_identical_to_predict": True,
                "jit_cache_miss_total{fn=serving.gbm}": misses}

    def device_check(self):
        import jax
        from h2o3_tpu.serving.engine import engine
        m = self.gbm_model
        on = {"binned_matrix": self.require_tpu("binned matrix",
                                                m.bm.bins),
              "forest": self.require_tpu("forest", m.forest.feat)}
        scorer = engine.register(m)          # the cached compiled scorer
        x = scorer.prep(self.slice.row_slice(0, SERVE_ROWS))
        on["serving_input"] = self.require_tpu("serving input", x)
        on["serving_output"] = self.require_tpu("serving output",
                                                scorer.serve(x))
        stats = jax.devices()[0].memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            if not self.rehearse:
                raise RuntimeError(
                    "the backend reports no memory statistics")
            stats = {}
        return {"lives_on": on,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit")}

    # ------------------------------------------------ four chips (option)

    def shards(self):
        """The binned matrix must be spread over the mesh, not parked
        on the first chip."""
        from h2o3_tpu.parallel.mesh import data_size
        bins = self.gbm_model.bm.bins
        sh = bins.addressable_shards
        devs = {s.device.id for s in sh}
        nbytes = [int(s.data.nbytes) for s in sh]
        n = data_size()
        if len(sh) != n or len(devs) != n or \
                max(nbytes) > 1.05 * min(nbytes):
            raise RuntimeError(f"binned matrix shards: {len(sh)} on "
                               f"devices {sorted(devs)}, bytes {nbytes}")
        return {"shards": len(sh), "devices": sorted(devs),
                "shard_bytes": nbytes}

    def level_pass_collectives(self):
        """The program the fit ran (re-lowered from the compile
        observer's record of it) must reduce across the mesh."""
        from h2o3_tpu.telemetry import compile_observer
        jit_fn, aargs, akwargs = compile_observer.aot_source(
            "gbm.boost_scan")
        txt = jit_fn.lower(*aargs, **akwargs).compile().as_text()
        if "all-reduce" not in txt:
            raise RuntimeError("no all-reduce in the compiled boost scan")
        return {"all_reduce_ops": txt.count(" all-reduce("),
                "tpu_custom_calls": txt.count("tpu_custom_call")}

    def run_four_chips(self):
        import numpy as np
        import h2o3_tpu
        from h2o3_tpu.core.kv import DKV
        res = {}

        def gbm_on(tag):
            facts = self.gbm("gbm_trees_4chip")
            m = self.gbm_model
            res["pred" + tag] = m.predict(self.air).col("p1").to_numpy()
            res["auc" + tag] = facts["auc"]
            res["forest" + tag] = [np.asarray(getattr(m.forest, f))
                                   for f in ("is_split", "feat", "thresh")]
            if tag == "4":
                facts.update(self.shards())
                facts.update(self.level_pass_collectives())
            return facts

        def glm_on(tag):
            facts = self.glm()
            res["coef" + tag] = dict(self.glm_coef)
            return facts

        def remesh():
            for k in list(DKV.keys()):
                DKV.remove(k)
            del self.air, self.gbm_model
            info = h2o3_tpu.init(data_axis=1)
            if info["mesh_shape"]["data"] != 1:
                raise RuntimeError(f"mesh {info['mesh_shape']}")
            return {"mesh_shape": info["mesh_shape"]}

        def compare():
            dp = np.abs(res["pred4"] - res["pred1"])
            dc = max(abs(res["coef4"][k] - res["coef1"][k])
                     for k in res["coef1"])
            # where the two forests first part ways ([tree, level,
            # node]): a split that flipped on a near-tie changes every
            # later tree, so this is what a missed bar has to show
            differ = np.argwhere(np.logical_or.reduce(
                [a != b for a, b in zip(res["forest4"], res["forest1"])]))
            facts = {"gbm_max_abs_pred_diff": float(dp.max()),
                     "gbm_share_of_rows_beyond_1e-4":
                         float((dp > 1e-4).mean()),
                     "gbm_auc_data4": res["auc4"],
                     "gbm_auc_data1": res["auc1"],
                     "gbm_first_forest_difference":
                         differ[0].tolist() if len(differ) else None,
                     "glm_max_abs_coef_diff": dc}
            facts["bars_met"] = bool(dp.max() <= 1e-4 and dc <= 1e-3)
            # a few thousand rows over two 125-level categoricals leave
            # exact ties between splits, which summation order breaks:
            # the rehearsal reports the bars and does not enforce them
            if not facts["bars_met"] and not self.rehearse:
                raise RuntimeError(f"data=4 vs data=1: {json.dumps(facts)}")
            return facts

        self.phase("init", lambda: self.init(want_devices=4))
        self.phase("ingest_data4", self.ingest)
        self.phase("gbm_data4", lambda: gbm_on("4"))
        self.phase("glm_data4", lambda: glm_on("4"))
        self.phase("remesh_data1", remesh)
        self.phase("ingest_data1", self.ingest)
        self.phase("gbm_data1", lambda: gbm_on("1"))
        self.phase("glm_data1", lambda: glm_on("1"))
        self.phase("compare", compare)

    def run_one_chip(self):
        self.phase("init", lambda: self.init(want_devices=1))
        self.phase("ingest", self.ingest)
        self.phase("gbm", self.gbm)
        self.phase("glm", self.glm)
        self.phase("dl", self.dl)
        self.phase("score", self.score)
        self.phase("serve", self.serve)
        self.phase("device_check", self.device_check)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="scratch directory (CSV, MOJO); removed at exit")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    smoke = Smoke(args)
    try:
        if args.chips == 4:
            smoke.run_four_chips()
        else:
            smoke.run_one_chip()
    finally:
        shutil.rmtree(args.out, ignore_errors=True)
    emit({"ok": True, "device": smoke.device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
