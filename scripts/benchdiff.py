#!/usr/bin/env python3
"""benchdiff — diff two BENCH_*.json artifacts / perf-baseline snapshots
into a pass/fail table with per-phase deltas.

BENCH_r03–r05 exist but nothing ever compared them; this is the offline
half of the perf-regression guard (telemetry/perfbase.py is the
in-process half). Pure stdlib — runs anywhere, jax-free, in well under
a second (the scripts/tier1.sh ``perfguard`` target runs it against the
committed BENCH_r05.json on every capped CI run).

Accepted inputs (auto-detected per file):

* a driver BENCH artifact: ``{"n", "cmd", "rc", "tail", ...}`` — metric
  lines are the JSON objects embedded one-per-line in ``tail``, parsed
  only up to the ``# ---- summary`` re-print (which would double-count)
  and deduped by metric name (first wins);
* a bare list of metric objects, or ``{"metrics": [...]}``;
* a ``telemetry/perfbase.py`` baseline file, or a directory of them.

Comparison: metrics present in BOTH sides with a numeric ``value``.
Direction comes from ``unit`` — ``*/sec*`` means higher is better,
``seconds`` means lower is better. A change worse than ``--threshold``
(default 0.25, the ≥25% SLO bound) is a REGRESSION and the exit code is
nonzero; an identical pair (or a pair with no comparable metrics — e.g.
two all-error r05 runs) passes with exit 0. When both sides carry a
``phases`` dict the per-phase deltas print alongside, so a regression
says WHERE the step got slower (host vs compute vs collective wait).

Usage:
    python scripts/benchdiff.py OLD NEW [--threshold 0.25] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

SUMMARY_MARK = "# ---- summary"


# ------------------------------------------------------------- loading


def _metrics_from_tail(tail: str) -> List[Dict]:
    """JSON metric lines out of a BENCH artifact's stdout tail, stopping
    at the tail-proof summary and deduping by metric (first wins)."""
    out: List[Dict] = []
    seen = set()
    for ln in tail.splitlines():
        ln = ln.strip()
        if ln.startswith(SUMMARY_MARK):
            break
        if not ln.startswith("{"):
            continue
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        name = obj.get("metric")
        if not isinstance(obj, dict) or not name or name in seen:
            continue
        seen.add(name)
        out.append(obj)
    return out


def _normalize(doc) -> Optional[List[Dict]]:
    """One loaded JSON document → a metric list, or None if unknown."""
    if isinstance(doc, list):
        return [m for m in doc if isinstance(m, dict) and "metric" in m]
    if not isinstance(doc, dict):
        return None
    if "tail" in doc:                       # driver BENCH artifact
        return _metrics_from_tail(str(doc.get("tail") or ""))
    if isinstance(doc.get("metrics"), list):
        return _normalize(doc["metrics"])
    if "metric" in doc:
        return [doc]
    if "best_step_seconds" in doc:          # perfbase baseline file
        hist = doc.get("history") or []
        return [{"metric": doc.get("key", "baseline"),
                 "value": float(doc.get("last_step_seconds")
                                or doc.get("best_step_seconds") or 0),
                 "unit": "seconds",
                 "phases": dict((hist[-1].get("phases") or {})
                                if hist else {})}]
    return None


def load_metrics(path: str) -> List[Dict]:
    """Metric list from a file or a perf-baseline directory."""
    if os.path.isdir(path):
        out: List[Dict] = []
        for name in sorted(os.listdir(path)):
            if name.endswith(".json"):
                out.extend(load_metrics(os.path.join(path, name)))
        return out
    with open(path) as f:
        doc = json.load(f)
    metrics = _normalize(doc)
    if metrics is None:
        raise ValueError(f"{path}: unrecognized benchdiff input format")
    return metrics


# ------------------------------------------------------------ comparing


def _higher_is_better(unit: str) -> bool:
    u = (unit or "").lower()
    if "/sec" in u or u.endswith("/s"):
        return True
    if "second" in u or u == "s":
        return False
    return True


def compare(old: List[Dict], new: List[Dict],
            threshold: float = 0.25) -> Dict:
    """Pass/fail verdict over the metrics present in both sides.

    Returns {"rows": [...], "regressions": [names], "compared": n,
    "ok": bool}; ok is True when nothing regressed past the threshold —
    including the degenerate no-comparable-metrics case (two identical
    all-error runs must pass, not crash)."""
    old_by = {m["metric"]: m for m in old
              if isinstance(m.get("value"), (int, float))}
    rows: List[Dict] = []
    regressions: List[str] = []
    for m in new:
        name = m.get("metric")
        v_new = m.get("value")
        base = old_by.get(name)
        if base is None or not isinstance(v_new, (int, float)):
            continue
        v_old = float(base["value"])
        unit = str(m.get("unit") or base.get("unit") or "")
        hib = _higher_is_better(unit)
        delta = (float(v_new) - v_old) / abs(v_old) if v_old else 0.0
        worse = -delta if hib else delta
        regressed = worse > threshold
        row = {"metric": name, "old": v_old, "new": float(v_new),
               "unit": unit, "delta_pct": round(delta * 100.0, 2),
               "regressed": regressed}
        op, np_ = base.get("phases"), m.get("phases")
        if isinstance(op, dict) and isinstance(np_, dict):
            row["phase_deltas"] = {
                p: round(float(np_.get(p, 0.0)) - float(op.get(p, 0.0)),
                         6)
                for p in sorted(set(op) | set(np_))}
        rows.append(row)
        if regressed:
            regressions.append(name)
    return {"rows": rows, "regressions": regressions,
            "compared": len(rows), "ok": not regressions,
            "threshold": threshold}


# ------------------------------------------------------------- printing


def _fmt_row(r: Dict) -> str:
    flag = "FAIL" if r["regressed"] else "ok"
    line = (f"  [{flag:4s}] {r['metric'][:58]:58s} "
            f"{r['old']:>12.4g} -> {r['new']:>12.4g} "
            f"{r['unit']:<14s} {r['delta_pct']:+7.1f}%")
    if r.get("phase_deltas"):
        deltas = "  ".join(f"{p}{d:+.3f}s"
                           for p, d in r["phase_deltas"].items() if d)
        if deltas:
            line += f"\n         phases: {deltas}"
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline BENCH_*.json / baseline dir")
    ap.add_argument("new", help="candidate BENCH_*.json / baseline dir")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="regression bound as a fraction (default 0.25)")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict as one JSON object")
    args = ap.parse_args(argv)
    try:
        old = load_metrics(args.old)
        new = load_metrics(args.new)
    except (OSError, ValueError) as e:
        print(f"benchdiff: {e}", file=sys.stderr)
        return 2
    verdict = compare(old, new, threshold=args.threshold)
    if args.json:
        print(json.dumps(verdict, indent=1))
    else:
        print(f"benchdiff: {args.old} -> {args.new} "
              f"({verdict['compared']} comparable metrics, "
              f"threshold {args.threshold:.0%})")
        for r in verdict["rows"]:
            print(_fmt_row(r))
        if not verdict["rows"]:
            print("  (no comparable metrics — pass by vacuity)")
        print(f"benchdiff: {'PASS' if verdict['ok'] else 'FAIL'} "
              f"({len(verdict['regressions'])} regression(s))")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
