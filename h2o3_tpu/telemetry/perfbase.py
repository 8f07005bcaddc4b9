"""Perf-regression baselines — persisted step-time/MFU floors per fit
shape, and the gauge the ``fit_step_regression`` SLO rule watches.

BENCH_r03–r05 exist but nothing ever compared them; this module is the
in-process half of that guard (scripts/benchdiff.py is the offline
half). Every profiled fit (telemetry/stepprof.py finish) records its
mean step time under a baseline key

    (algo, shape-bucket, device_kind, pallas-mode)

— the same axes that change a compiled program's identity, so a
baseline never compares a 4K-row CPU fit against a 50M-row TPU one.
Baselines persist as one JSON file per key under
``<ice_root>/perf_baselines/`` (atomic tmp+rename, the recovery.py
snapshot idiom): ``best`` is the lowest mean step seconds ever seen,
``history`` a bounded tail of recent runs with their phase splits.

Each record sets ``fit_step_baseline_ratio{algo}`` = current/best;
the default SLO rule ``fit_step_regression`` (telemetry/slo.py) alerts
when any ratio reaches ``H2O3TPU_SLO_STEP_REGRESSION`` (default 1.25 —
a fit's step-time distribution degraded ≥25% vs its stored baseline).
"""

from __future__ import annotations

import json
import math
import os
import re
import time
from typing import Dict, List, Optional

from h2o3_tpu.telemetry.registry import gauge

HISTORY_KEEP = 16


def baseline_dir() -> str:
    env = os.environ.get("H2O3TPU_PERF_BASELINE_DIR")
    if env:
        return env
    try:
        from h2o3_tpu.core.config import ARGS
        root = ARGS.ice_root
    except Exception:   # noqa: BLE001 - config not importable yet
        root = "/tmp/h2o3_tpu"
    return os.path.join(root, "perf_baselines")


def shape_bucket(nrows: int) -> str:
    """Power-of-two row bucket — the same coarse shape identity
    parallel/mesh.py padded_rows buckets compilation on."""
    n = max(int(nrows), 1)
    return f"r{1 << (n - 1).bit_length()}"


def _device_kind() -> str:
    try:
        from h2o3_tpu.telemetry import roofline
        return str(roofline.device_peaks().get("device_kind", "unknown"))
    except Exception:   # noqa: BLE001 - backend-free processes
        return "unknown"


def _pallas_mode() -> str:
    try:
        from h2o3_tpu.ops import pallas as pallas_policy
        return str(pallas_policy.knob_value())
    except Exception:   # noqa: BLE001
        return "auto"


def baseline_key(algo: str, nrows: int,
                 device_kind: Optional[str] = None,
                 pallas_mode: Optional[str] = None) -> str:
    raw = "_".join([str(algo), shape_bucket(nrows),
                    device_kind or _device_kind(),
                    pallas_mode or _pallas_mode()])
    return re.sub(r"[^A-Za-z0-9_.-]", "-", raw)


def _path(key: str) -> str:
    return os.path.join(baseline_dir(), key + ".json")


def load(key: str) -> Optional[Dict]:
    try:
        with open(_path(key)) as f:
            return json.load(f)
    except Exception:   # noqa: BLE001 - missing/corrupt = no baseline
        return None


def _store(key: str, doc: Dict) -> None:
    os.makedirs(baseline_dir(), exist_ok=True)
    tmp = _path(key) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, _path(key))


def record_fit(algo: str, nrows: int, profile: Dict,
               mfu: Optional[float] = None) -> Optional[float]:
    """Fold one completed fit profile (stepprof.finish) into its
    baseline; returns the step-time ratio vs the stored best (None when
    the fit has no chunks to average). Never raises."""
    try:
        chunks = int(profile.get("chunks") or 0)
        seconds = float(profile.get("seconds") or 0.0)
        if chunks <= 0 or seconds <= 0:
            return None
        step_s = seconds / chunks
        if not math.isfinite(step_s) or step_s <= 0:
            return None
        key = baseline_key(algo, nrows)
        doc = load(key) or {"key": key, "algo": algo,
                            "shape_bucket": shape_bucket(nrows),
                            "device_kind": _device_kind(),
                            "pallas": _pallas_mode(),
                            "unit": "seconds",
                            "best_step_seconds": step_s,
                            "history": []}
        best = float(doc.get("best_step_seconds") or step_s)
        ratio = step_s / max(best, 1e-12)
        entry = {"ts": time.time(), "step_seconds": round(step_s, 6),
                 "chunks": chunks,
                 "phases": dict(profile.get("phases") or {})}
        if mfu is not None:
            entry["mfu"] = float(mfu)
        doc["history"] = (doc.get("history") or [])[-(HISTORY_KEEP - 1):] \
            + [entry]
        doc["best_step_seconds"] = min(best, step_s)
        doc["last_step_seconds"] = round(step_s, 6)
        if mfu is not None:
            doc["best_mfu"] = max(float(doc.get("best_mfu") or 0.0),
                                  float(mfu))
        _store(key, doc)
        gauge("fit_step_baseline_ratio", algo=algo).set(ratio)
        return ratio
    except Exception:   # noqa: BLE001 - the guard must never fail a fit
        return None


def snapshot_metrics() -> List[Dict]:
    """Every stored baseline as a benchdiff-comparable metric line
    (``{"metric", "value", "unit", "phases"}``) — so
    ``scripts/benchdiff.py`` diffs a baseline dir against a BENCH_*.json
    or another baseline snapshot with one code path."""
    out: List[Dict] = []
    d = baseline_dir()
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        doc = load(name[:-len(".json")])
        if not doc:
            continue
        hist = doc.get("history") or []
        out.append({"metric": doc.get("key", name[:-len(".json")]),
                    "value": float(doc.get("last_step_seconds")
                                   or doc.get("best_step_seconds") or 0),
                    "unit": "seconds",
                    "best": float(doc.get("best_step_seconds") or 0),
                    "phases": dict((hist[-1].get("phases") or {})
                                   if hist else {})})
    return out
