"""Roofline accounting — per-fit FLOP/byte totals against device peaks.

The north star ("as fast as the hardware allows") and ROADMAP item 5
(DL at 0.14% MFU) need a measuring stick: raw rows/sec says nothing
about how far a fit sits from the chip. This module sizes every model
fit against the accelerator roofline the way DrJAX (arxiv 2403.07128)
sizes its MapReduce primitives against peak and the Julia-to-TPU
pipeline (arxiv 1810.09868) reports utilization per compiled program:

- :func:`device_peaks` looks the device's peak FLOP/s and HBM
  bandwidth up in a device_kind table of published TPU numbers
  (``H2O3TPU_PEAK_FLOPS`` / ``H2O3TPU_PEAK_HBM_GBPS`` override). A
  device that is not in the table has NO peaks (``source: "unknown"``)
  and its fits' MFU / HBM utilization are not measured — never another
  chip's number, never an invented CPU or GPU "peak";
- per-fit work has two legs: **analytic** — closed-form per-algo
  estimates (GBM histogram matmuls, GLM IRLS Gram builds, DL dense
  fwd+bwd) — always drive the fit-level totals, and **cost_analysis**
  — ``Compiled.cost_analysis()`` taken off a re-lowering of the
  observed jit entry point's cached abstract call signature
  (telemetry/compile_observer.py ``aot_source``) — grounds them:
  XLA's numbers are per-device and count scan/while bodies ONCE, so
  they validate the analytic model per program unit (one histogram
  build, one DL step — tier-1 asserts 2x agreement) and ride fit
  records as diagnostics rather than being multiplied by guessed trip
  counts;
- :func:`record_model_fit` (hooked into the ``<algo>.fit`` span,
  models/model.py) emits ``model_fit_mfu{algo}`` and
  ``model_fit_hbm_util{algo}`` gauges, annotates the fit span (so the
  numbers land in flight-recorder capsules), and returns the record
  bench.py re-emits per config.

Mode knob ``H2O3TPU_ROOFLINE`` / ``Config.roofline``: ``auto``
(default) attaches cost_analysis diagnostics on TPU backends — where
re-lowering hits the persistent XLA cache and fits are large — and
skips them elsewhere; ``cost`` / ``analytic`` force; ``off`` disables
recording. MFU and HBM-utilization values are FRACTIONS (0..1) of the
AGGREGATE mesh peak (per-device peak x device count), not percent.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Tuple

from h2o3_tpu.telemetry.registry import REGISTRY, counter, gauge
from h2o3_tpu.telemetry import spans as spans_mod

# ------------------------------------------------------------- peaks

# device_kind substring (lowercase) → (peak FLOP/s dense bf16/fp32 mix,
# HBM bytes/s). Public TPU spec numbers; matched longest-first.
_TPU_PEAKS: List[Tuple[str, float, float]] = [
    ("v6e", 918e12, 1640e9),       # Trillium
    ("v6", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),    # "TPU v5 lite" device_kind spelling
    ("v5litepod", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
]
_peaks_lock = threading.Lock()
_peaks_cache: Optional[Dict] = None


def peaks_for(device_kind: str, platform: str = "") -> Dict:
    """Pure table lookup (no jax import) — also the bench stub path.
    A device_kind the table does not list yields ``flops`` and
    ``hbm_bytes_per_s`` of None with ``source: "unknown"``."""
    kind = (device_kind or "").lower()
    for sub, flops, bw in _TPU_PEAKS:
        if sub in kind:
            return {"flops": flops, "hbm_bytes_per_s": bw,
                    "device_kind": device_kind,
                    "source": f"tpu-spec:{sub}"}
    return {"flops": None, "hbm_bytes_per_s": None,
            "device_kind": device_kind or platform, "source": "unknown"}


def device_peaks(refresh: bool = False) -> Dict:
    """Detected PER-DEVICE peaks for the active backend plus the device
    count (fit totals are whole-mesh, so utilization divides by the
    aggregate), with ``H2O3TPU_PEAK_FLOPS`` / ``H2O3TPU_PEAK_HBM_GBPS``
    env overrides on top. Cached (the backend does not change
    mid-process)."""
    global _peaks_cache
    with _peaks_lock:
        if _peaks_cache is not None and not refresh:
            return dict(_peaks_cache)
    kind, plat, ndev = "", "", 1
    try:
        import jax
        d = jax.devices()[0]
        kind = getattr(d, "device_kind", "") or ""
        plat = getattr(d, "platform", "") or ""
        ndev = max(jax.device_count(), 1)
    except Exception:   # noqa: BLE001 - peaks must never break a fit
        pass
    p = peaks_for(kind, plat)
    p["devices"] = ndev
    env_f = os.environ.get("H2O3TPU_PEAK_FLOPS")
    env_b = os.environ.get("H2O3TPU_PEAK_HBM_GBPS")
    try:
        if env_f:
            p["flops"] = float(env_f)
            p["source"] = "env-override"
        if env_b:
            p["hbm_bytes_per_s"] = float(env_b) * 1e9
            p["source"] = "env-override"
    except ValueError:
        pass
    with _peaks_lock:
        _peaks_cache = dict(p)
    return p


# -------------------------------------------------------------- mode


def mode() -> str:
    """off | analytic | cost | auto — env wins over config (the
    watchdog/gate knob pattern)."""
    m = os.environ.get("H2O3TPU_ROOFLINE")
    if not m:
        try:
            from h2o3_tpu.core import config as _cfg
            m = _cfg.ARGS.roofline
        except Exception:   # noqa: BLE001 - config not importable yet
            m = "auto"
    m = (m or "auto").lower()
    return m if m in ("off", "analytic", "cost", "auto") else "auto"


def _use_cost() -> bool:
    m = mode()
    if m == "cost":
        return True
    if m == "auto":
        try:
            import jax
            return jax.default_backend() == "tpu"
        except Exception:   # noqa: BLE001
            return False
    return False


# -------------------------------------------- analytic fit estimates

# algo → family of analytic estimator + the observed jit entry points
# whose calls carry the fit's device work (compile_observer names)
_TREE_KERNELS = ("gbm.boost_scan", "gbm.boost_scan_multi",
                 "gbm.boost_scan_batched")
ALGO_KERNELS: Dict[str, Tuple[str, ...]] = {
    "gbm": _TREE_KERNELS, "drf": _TREE_KERNELS, "xgboost": _TREE_KERNELS,
    "glm": ("glm.irls_solve", "glm.irls_solve_batched"),
    "deeplearning": ("dl.train_chunk",),
}


def analytic_tree_cost(rows: int, features: int, trees: int, depth: int,
                       bins: int) -> Dict:
    """Histogram-build matmuls — the tree FLOPs that touch the MXU: per
    row per tree, levels 0..depth-1 contract [3·2^l, C] x [C, F·B]
    (ops/histogram.py _block_hist; same count bench.py's historical
    mfu_pct used). A float32 product counted once: that each statistic
    enters as three bfloat16 pieces, so the MXU does three times this,
    is how the sums stay float32, not more work done.
    Bytes: each level re-streams the int8 binned matrix,
    the 3-stat payload, and the node-id vector."""
    flops = 2.0 * 3.0 * (2 ** depth - 1) * features * bins * rows * trees
    bytes_ = float(rows) * trees * depth * (features + 3 * 4 + 4)
    return {"flops": flops, "bytes": bytes_,
            "detail": {"rows": rows, "features": features, "trees": trees,
                       "depth": depth, "bins": bins}}


def analytic_glm_cost(rows: int, coefs: int, iterations: int,
                      solver: str = "irlsm") -> Dict:
    """IRLS is Gram-dominated (2·n·p² per iteration, ops/gram.py);
    L-BFGS/COD are matvec passes (~4·n·p). Bytes: the design matrix
    streams once per iteration (f32)."""
    s = (solver or "irlsm").lower()
    per_row = 2.0 * coefs * coefs if s in ("irlsm", "auto") else 4.0 * coefs
    return {"flops": per_row * rows * max(iterations, 1),
            "bytes": 4.0 * rows * coefs * max(iterations, 1),
            "detail": {"rows": rows, "coefs": coefs,
                       "iterations": iterations, "solver": s}}


def analytic_dl_cost(samples: float, layer_sizes) -> Dict:
    """Dense MLP fwd+bwd: 6 FLOPs per weight per sample (2 fwd + 4 bwd).
    Bytes: activations in/out per layer plus one weight read+write per
    sample-equivalent (optimizer state churn folded into the x3)."""
    sizes = [int(s) for s in layer_sizes]
    params = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    act = sum(sizes)
    return {"flops": 6.0 * params * max(samples, 1.0),
            "bytes": 4.0 * max(samples, 1.0) * (act + 3.0 * params /
                                                max(samples, 1.0)),
            "detail": {"samples": samples, "params": params,
                       "layers": sizes}}


def _nbins() -> int:
    try:
        from h2o3_tpu.core import config as _cfg
        return int(_cfg.ARGS.nbins) + 1      # +1: the NA bin
    except Exception:   # noqa: BLE001
        return 65


def analytic_fit_cost(algo: str, params: Dict, model, frame,
                      x) -> Optional[Dict]:
    """Closed-form fit-work estimate from the builder's own knobs — the
    always-available fallback when no cost_analysis source exists."""
    rows = int(getattr(frame, "nrows", 0) or 0)
    feats = max(len(x or []), 1)
    if rows <= 0:
        return None
    if algo in ("gbm", "drf", "xgboost"):
        out = getattr(model, "output", {}) or {}
        hist = out.get("scoring_history") or []
        trees = int(params.get("ntrees") or 50)
        if hist:
            try:
                trees = max(int(h.get("ntrees", 0)) for h in hist) or trees
            except Exception:   # noqa: BLE001
                pass
        depth = int(params.get("max_depth") or 6)
        return analytic_tree_cost(rows, feats, trees, depth, _nbins())
    if algo == "glm":
        out = getattr(model, "output", {}) or {}
        coefs = len(out.get("coef_names") or []) + 1 or feats + 1
        iters = int(params.get("max_iterations") or 50)
        return analytic_glm_cost(rows, coefs, iters,
                                 str(params.get("solver") or "irlsm"))
    if algo == "deeplearning":
        out = getattr(model, "output", {}) or {}
        hidden = [int(h) for h in (params.get("hidden") or [200, 200])]
        nclasses = len(out.get("domain") or []) or 1
        sizes = [feats] + hidden + [max(nclasses, 1)]
        samples = float(params.get("epochs") or 10.0) * rows
        return analytic_dl_cost(samples, sizes)
    return None


# --------------------------------------- cost_analysis (AOT replay)

_cost_cache: Dict[str, Optional[Dict]] = {}
_cost_lock = threading.Lock()


def kernel_cost(name: str, refresh: bool = False) -> Optional[Dict]:
    """``Compiled.cost_analysis()`` totals (flops, bytes accessed) for
    the observed jit entry point ``name``, replayed from the compile
    observer's cached abstract signature. The re-lowering compiles once
    per (name, newest shape bucket) and is cached here; on backends
    with the persistent XLA cache armed (core/cloud.py init) the XLA
    leg is a disk hit. Returns None when the entry point never compiled
    in this process or the backend reports no costs.

    Semantics — these are XLA's numbers, read them as such: costs are
    PER-DEVICE (a shard_map'd program reports one shard's work) and
    ``scan``/``while`` BODIES COUNT ONCE regardless of trip count. A
    loop-free program unit (one histogram build, one DL train step)
    therefore compares directly against its analytic estimate divided
    by the device count — tier-1 asserts 2x agreement on exactly those
    units — while scan-heavy fit programs (the 25-tree boost scan) are
    structurally undercounted, which is why fit-level MFU totals come
    from the analytic path (record_model_fit)."""
    from h2o3_tpu.telemetry import compile_observer
    src = compile_observer.aot_source(name)
    if src is None:
        return None
    key = name
    with _cost_lock:
        if not refresh and key in _cost_cache:
            c = _cost_cache[key]
            return dict(c) if c else None
    result: Optional[Dict] = None
    try:
        jit_fn, aargs, akwargs = src
        compiled = jit_fn.lower(*aargs, **akwargs).compile()
        ca = compiled.cost_analysis()
        entries = ca if isinstance(ca, (list, tuple)) else [ca]
        flops = sum(float(e.get("flops", 0.0) or 0.0)
                    for e in entries if isinstance(e, dict))
        bytes_ = sum(float(e.get("bytes accessed", 0.0) or 0.0)
                     for e in entries if isinstance(e, dict))
        if flops > 0 or bytes_ > 0:
            result = {"flops": flops, "bytes": bytes_, "kernel": name}
    except Exception:   # noqa: BLE001 - accounting must never break a fit
        result = None
    with _cost_lock:
        _cost_cache[key] = result
    return dict(result) if result else None


def _kernel_calls(algo: str) -> float:
    """Total calls of the algo's observed entry points so far (cache
    hits + misses). Deltas of this across a fit give the call count the
    cost_analysis totals scale by."""
    names = ALGO_KERNELS.get(algo, ())
    total = 0.0
    snap = REGISTRY.snapshot()["counters"]
    for c in snap:
        if c["name"] in ("h2o3tpu_jit_cache_hit_total",
                         "h2o3tpu_jit_cache_miss_total") and \
                c["labels"].get("fn") in names:
            total += c["value"]
    return total


def fit_probe(algo: str) -> Dict:
    """Snapshot taken at fit START (models/model.py) so record_model_fit
    can attribute kernel calls to this fit alone."""
    return {"algo": algo, "kernel_calls": _kernel_calls(algo)}


# ------------------------------------------------------------ record


def record_model_fit(builder, model, frame, x, seconds: float,
                     probe: Optional[Dict] = None,
                     span=None) -> Optional[Dict]:
    """Compute this fit's FLOP/byte totals, emit the
    ``model_fit_mfu{algo}`` / ``model_fit_hbm_util{algo}`` gauges,
    annotate the fit span (``span``, else the active one) so the numbers
    ride the flight recorder capsule, and return the record. Never
    raises."""
    try:
        if mode() == "off" or seconds <= 0:
            return None
        algo = getattr(builder, "algo", "?")
        est = analytic_fit_cost(algo, getattr(builder, "params", {}) or {},
                                model, frame, x)
        if est is None:
            return None
        flops, bytes_, source = est["flops"], est["bytes"], "analytic"
        # cost_analysis diagnostics ride along where the mode wants them
        # (per-device, loop-bodies-once — see kernel_cost); the fit
        # TOTAL stays analytic so scan trip counts are never faked
        kc = None
        calls = 0.0
        if probe is not None:
            calls = _kernel_calls(algo) - probe.get("kernel_calls", 0.0)
        if _use_cost():
            for name in ALGO_KERNELS.get(algo, ()):
                kc = kernel_cost(name)
                if kc is not None:
                    break
        peaks = device_peaks()
        # a device with no published peaks: work totals are recorded,
        # utilization is not measured (None; the gauges stay unset)
        ndev = peaks.get("devices", 1)
        mfu = (flops / (seconds * peaks["flops"] * ndev)
               if peaks["flops"] else None)
        hbm = (bytes_ / (seconds * peaks["hbm_bytes_per_s"] * ndev)
               if peaks["hbm_bytes_per_s"] else None)
        rec = {"algo": algo, "seconds": round(seconds, 4),
               "flops": flops, "bytes": bytes_,
               "mfu": mfu, "hbm_util": hbm, "source": source,
               "kernel_calls": calls, "kernel_cost": kc,
               "peak_flops": peaks["flops"],
               "peak_hbm_bytes_per_s": peaks["hbm_bytes_per_s"],
               "devices": peaks.get("devices", 1),
               "device_kind": peaks["device_kind"]}
        if mfu is not None:
            gauge("model_fit_mfu", algo=algo).set(mfu)
        if hbm is not None:
            gauge("model_fit_hbm_util", algo=algo).set(hbm)
        counter("roofline_fits_total", algo=algo, source=source).inc()
        roofline_meta = {"flops": flops, "bytes": bytes_,
                         "source": source, "seconds": round(seconds, 4)}
        if kc is not None:
            roofline_meta["kernel_cost"] = kc
        # unrounded: a toy fit's MFU on a big mesh is legitimately tiny
        # and must survive into the capsule as nonzero
        annotate = span.annotate if span is not None \
            else spans_mod.annotate
        if mfu is not None and hbm is not None:
            annotate(mfu=mfu, hbm_util=hbm)
        annotate(roofline=roofline_meta)
        # per-fit record on the MODEL: model_fit_mfu{algo} is a
        # latest-wins gauge, so concurrent fits of the same algo
        # (scheduler-spread grids) overwrite each other there — the
        # per-fit truth lives here and in the capsule, the gauge stays
        # "most recent fit" by contract (README §Observability)
        try:
            model.output["roofline"] = dict(rec)
        except Exception:   # noqa: BLE001 - accounting must never fail
            pass
        return rec
    except Exception:   # noqa: BLE001 - accounting must never fail a fit
        return None


def last_fit(algo: str) -> Dict:
    """Most recent fit's utilization gauges (bench.py per-config
    fields): {"mfu": fraction, "hbm_util": fraction}."""
    return {"mfu": float(REGISTRY.value("model_fit_mfu", algo=algo)),
            "hbm_util": float(REGISTRY.value("model_fit_hbm_util",
                                             algo=algo))}
