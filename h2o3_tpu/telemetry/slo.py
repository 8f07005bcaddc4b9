"""Declarative SLOs + multi-window burn-rate alerting.

The registry (registry.py) accumulates since boot but nothing CONSUMES
it to judge health against objectives — an operator watching
``/3/Metrics`` has data, not answers. This module is the answer layer:
a small set of declarative SLO rules evaluated on demand from the
live registry with the standard multi-window burn-rate construction
(alert when the error budget burns faster than allowed over BOTH a
short 5m and a long 1h window; the long window confirms the burn is
real, the short window clears fast on recovery).

Burn rate = (observed error rate over a window) / (budgeted error
rate), where budget = ``1 - objective``. Rate > 1 means the budget is
burning faster than the objective allows. Registry counters are
cumulative-since-boot, so the engine keeps a bounded ring of
(timestamp, per-rule cumulative counts) samples — one per ``evaluate``
at >= 1s spacing — and window deltas come from the newest sample at or
before the window start (falling back to the oldest sample when the
process is younger than the window).

Per-rule state machine, transitions counted in
``slo_alert_transitions_total{slo,to}`` and recorded as ``slo``
timeline events (which flow into any recording flight-recorder
capsule):

    healthy -> burning   short-window burn exceeded, long not yet
    burning -> alert     long window confirms (both windows over)
    alert   -> recovery  short window back under budget
    recovery-> healthy   long window drained too

Surfaces: ``GET /3/Alerts`` (+ ``?cluster=1`` via telemetry/cluster.py
fan-in), ``slo_*`` gauges in the Prometheus scrape (refreshed on every
evaluate, which ``GET /3/Metrics`` triggers), and a final
``slo_alerts`` snapshot stamped into every flight-recorder capsule at
job end.

Default rules: predict p99 latency (``predict_seconds`` — all phases,
merged across ONE shared bucket grid), REST availability
(``rest_request_seconds{status}`` + ``rest_rejected_total``),
heartbeat health (``heartbeat_misses_total`` vs
``heartbeat_rounds_total``), and a fit-MFU floor
(``model_fit_mfu{algo}``, off by default). Everything here is
deliberately jax-free: tests/test_tracing_slo.py drives the full
state machine with a private registry and a fake clock.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from h2o3_tpu.telemetry.registry import (REGISTRY, MetricsRegistry,
                                         Counter, Histogram,
                                         merged_quantile)

SHORT_WINDOW_S = 300.0
LONG_WINDOW_S = 3600.0
_MIN_SAMPLE_SPACING_S = 1.0
_MAX_SAMPLES = 4096

# states a rule can be in; "alert" is the only one surfaced as firing
STATES = ("healthy", "burning", "alert", "recovery")


class RatioRule:
    """Burn-rate SLO over a cumulative (bad, total) pair."""

    kind = "ratio"

    def __init__(self, name: str, objective: float,
                 counts_fn: Callable[[MetricsRegistry],
                                     Tuple[float, float]],
                 detail_fn: Optional[
                     Callable[[MetricsRegistry], Dict]] = None,
                 description: str = ""):
        self.name = name
        self.objective = float(objective)
        self.counts_fn = counts_fn
        self.detail_fn = detail_fn
        self.description = description

    def counts(self, reg: MetricsRegistry) -> Tuple[float, float]:
        return self.counts_fn(reg)

    def detail(self, reg: MetricsRegistry) -> Dict:
        if self.detail_fn is None:
            return {}
        try:
            return self.detail_fn(reg)
        except Exception as e:   # noqa: BLE001 - detail is best-effort
            return {"detail_error": str(e)}


class GaugeRule:
    """Instant-predicate SLO (no windows): healthy <-> alert."""

    kind = "gauge"
    objective = None

    def __init__(self, name: str,
                 check_fn: Callable[[MetricsRegistry],
                                    Tuple[bool, Dict]],
                 description: str = ""):
        self.name = name
        self.check_fn = check_fn
        self.description = description

    def check(self, reg: MetricsRegistry) -> Tuple[bool, Dict]:
        return self.check_fn(reg)


# ------------------------------------------------------- default rules


def _predict_latency_threshold() -> float:
    try:
        return float(os.environ.get("H2O3TPU_SLO_PREDICT_P99_S", "0.5"))
    except ValueError:
        return 0.5


def _under_threshold(h: Histogram, thr: float) -> Tuple[int, int]:
    """(observations <= thr, total observations) for one histogram —
    the histogram-bucket latency SLI (observations past the last bound
    only appear in the total, i.e. count as bad)."""
    counts, total = h.counts_snapshot()
    cut = bisect.bisect_right(h.bounds, thr)
    return sum(counts[:cut]), total


def _predict_latency_counts(reg: MetricsRegistry) -> Tuple[float, float]:
    thr = _predict_latency_threshold()
    good = total = 0
    for h in reg.find("predict_seconds"):
        if isinstance(h, Histogram):
            g, t = _under_threshold(h, thr)
            good += g
            total += t
    return float(total - good), float(total)


def _predict_latency_detail(reg: MetricsRegistry) -> Dict:
    hists = [h for h in reg.find("predict_seconds")
             if isinstance(h, Histogram)]
    try:
        p99 = merged_quantile(hists, 0.99)
    except ValueError as e:      # mismatched grids: report, don't 500
        return {"threshold_seconds": _predict_latency_threshold(),
                "p99_seconds": None, "detail_error": str(e)}
    return {"threshold_seconds": _predict_latency_threshold(),
            "p99_seconds": p99}


def _rest_availability_counts(reg: MetricsRegistry) -> Tuple[float, float]:
    bad = total = 0.0
    for h in reg.find("rest_request_seconds"):
        if isinstance(h, Histogram):
            total += h.count
            if str(h.labels.get("status", "")).startswith("5"):
                bad += h.count
    # a rejected request never reached a handler: it is its own trial
    for c in reg.find("rest_rejected_total"):
        if isinstance(c, Counter):
            total += c.value
            bad += c.value
    return bad, total


def _heartbeat_counts(reg: MetricsRegistry) -> Tuple[float, float]:
    """Each agreement round is one trial; a round any peer missed is a
    bad trial (an approximation — misses are per peer — but the burn
    math only needs a rate that rises with degradation)."""
    bad = sum(c.value for c in reg.find("heartbeat_misses_total")
              if isinstance(c, Counter))
    total = sum(c.value for c in reg.find("heartbeat_rounds_total")
                if isinstance(c, Counter))
    return float(bad), float(max(total, bad))


def _fleet_routing_counts(reg: MetricsRegistry) -> Tuple[float, float]:
    """Each routed prediction is one trial; each failover hop is a bad
    trial (serving/fleet.py). Failovers CAN outnumber routes when every
    hop in a hedge chain fails, so clamp total like heartbeat does."""
    bad = sum(c.value for c in reg.find("predict_failovers_total")
              if isinstance(c, Counter))
    total = sum(c.value for c in reg.find("predict_routed_total")
                if isinstance(c, Counter))
    return float(bad), float(max(total, bad))


def _fleet_replicas_check(reg: MetricsRegistry) -> Tuple[bool, Dict]:
    """Every model the fleet registry tracks keeps at least one healthy
    replica; a model at zero is one heartbeat window from 503s."""
    vals = {str(g.labels.get("model", "?")): g.value
            for g in reg.find("fleet_replicas_healthy")}
    if not vals:
        return True, {"models": 0, "min_replicas": None}
    worst = min(vals, key=vals.get)
    return vals[worst] >= 1.0, {"models": len(vals),
                                "min_replicas": vals[worst],
                                "worst_model": worst}


def _data_durability_check(reg: MetricsRegistry) -> Tuple[bool, Dict]:
    """Every durability-registered frame keeps at least one live
    replica: ``frames_under_replicated`` counts frames whose home peer
    is heartbeat-dead and which no survivor has rebuilt yet
    (core/durability.py). Non-zero means the rebuild supervisor is
    behind — or the data is one more failure from gone."""
    under = max((g.value for g in reg.find("frames_under_replicated")),
                default=0.0)
    return under == 0.0, {"under_replicated": int(under)}


def _mfu_floor() -> float:
    try:
        return float(os.environ.get("H2O3TPU_SLO_MFU_FLOOR", "0"))
    except ValueError:
        return 0.0


def _mfu_check(reg: MetricsRegistry) -> Tuple[bool, Dict]:
    floor = _mfu_floor()
    vals = {str(g.labels.get("algo", "?")): g.value
            for g in reg.find("model_fit_mfu")}
    if floor <= 0.0 or not vals:
        return True, {"floor": floor,
                      "min_mfu": min(vals.values()) if vals else None}
    worst = min(vals, key=vals.get)
    return vals[worst] >= floor, {"floor": floor,
                                  "min_mfu": vals[worst],
                                  "worst_algo": worst}


def default_rules() -> List[object]:
    return [
        RatioRule(
            "predict_p99_latency", objective=0.99,
            counts_fn=_predict_latency_counts,
            detail_fn=_predict_latency_detail,
            description="99% of predict phases complete within "
                        "H2O3TPU_SLO_PREDICT_P99_S (default 0.5s), "
                        "measured from predict_seconds"),
        RatioRule(
            "rest_availability", objective=0.999,
            counts_fn=_rest_availability_counts,
            description="99.9% of REST requests neither 5xx nor "
                        "rejected (rest_request_seconds{status} + "
                        "rest_rejected_total)"),
        RatioRule(
            "heartbeat_health", objective=0.9,
            counts_fn=_heartbeat_counts,
            description="90% of heartbeat agreement rounds miss-free "
                        "(heartbeat_misses_total / "
                        "heartbeat_rounds_total)"),
        GaugeRule(
            "fit_mfu_floor", check_fn=_mfu_check,
            description="every model_fit_mfu{algo} gauge stays above "
                        "H2O3TPU_SLO_MFU_FLOOR (0 disables)"),
        RatioRule(
            "fleet_routing_availability", objective=0.99,
            counts_fn=_fleet_routing_counts,
            description="99% of fleet-routed predictions land without "
                        "a failover hop (predict_failovers_total / "
                        "predict_routed_total)"),
        GaugeRule(
            "fleet_replica_floor", check_fn=_fleet_replicas_check,
            description="every fleet-registered model keeps at least "
                        "one healthy replica (fleet_replicas_healthy)"),
        GaugeRule(
            "data_durability_floor", check_fn=_data_durability_check,
            description="every durability-registered frame keeps at "
                        "least one live replica "
                        "(frames_under_replicated stays 0)"),
    ]


# ------------------------------------------------------------- engine


class SLOEngine:
    def __init__(self, registry: MetricsRegistry = REGISTRY,
                 rules: Optional[List[object]] = None,
                 now: Callable[[], float] = time.monotonic,
                 burn_threshold: float = 1.0):
        self.registry = registry
        self.rules = list(rules) if rules is not None else default_rules()
        self._now = now
        self.burn_threshold = float(burn_threshold)
        self._samples: deque = deque(maxlen=_MAX_SAMPLES)
        self._state: Dict[str, str] = {r.name: "healthy"
                                       for r in self.rules}
        self._since: Dict[str, float] = {}
        self._lock = threading.Lock()

    # -- burn math -----------------------------------------------------
    def _baseline(self, now: float, window: float):
        """Newest sample at or before the window start (oldest sample
        when the history is younger than the window)."""
        base = None
        for ts, counts in self._samples:
            if ts <= now - window:
                base = (ts, counts)
            else:
                break
        if base is None and self._samples:
            base = self._samples[0]
        return base

    def _burn(self, rule, cur: Tuple[float, float], now: float,
              window: float) -> float:
        base = self._baseline(now, window)
        if base is None:
            return 0.0
        b0, t0 = base[1].get(rule.name, (0.0, 0.0))
        dbad, dtotal = cur[0] - b0, cur[1] - t0
        if dtotal <= 0:
            return 0.0
        err = min(max(dbad / dtotal, 0.0), 1.0)
        return err / max(1.0 - rule.objective, 1e-9)

    # -- state machine -------------------------------------------------
    def _step(self, name: str, short_over: bool, long_over: bool) -> str:
        s = self._state[name]
        if s == "healthy":
            if short_over and long_over:
                return "alert"
            if short_over:
                return "burning"
        elif s == "burning":
            if short_over and long_over:
                return "alert"
            if not short_over:
                return "healthy"
        elif s == "alert":
            if not short_over:
                return "healthy" if not long_over else "recovery"
        elif s == "recovery":
            if short_over:
                return "alert"
            if not long_over:
                return "healthy"
        return s

    def _transition(self, name: str, new: str, now: float) -> None:
        old = self._state[name]
        if new == old:
            return
        self._state[name] = new
        if new == "alert":
            self._since[name] = now
        elif new in ("healthy", "burning"):
            self._since.pop(name, None)
        self.registry.counter("slo_alert_transitions_total",
                              slo=name, to=new).inc()
        try:
            from h2o3_tpu.utils.timeline import record as _tl
            _tl("slo", f"{name}: {old} -> {new}", slo=name, state=new)
        except Exception:   # noqa: BLE001 - recording is best-effort
            pass

    # -- evaluation ----------------------------------------------------
    def evaluate(self) -> Dict:
        with self._lock:
            return self._evaluate_locked()

    def _evaluate_locked(self) -> Dict:
        now = self._now()
        reg = self.registry
        rules_out: List[Dict] = []
        for r in self.rules:
            if r.kind == "ratio":
                cur = r.counts(reg)
                bs = self._burn(r, cur, now, SHORT_WINDOW_S)
                bl = self._burn(r, cur, now, LONG_WINDOW_S)
                self._transition(
                    r.name,
                    self._step(r.name, bs > self.burn_threshold,
                               bl > self.burn_threshold), now)
                reg.gauge("slo_burn_rate", slo=r.name,
                          window="5m").set(bs)
                reg.gauge("slo_burn_rate", slo=r.name,
                          window="1h").set(bl)
                entry = {"slo": r.name, "kind": r.kind,
                         "state": self._state[r.name],
                         "objective": r.objective,
                         "burn_5m": round(bs, 4), "burn_1h": round(bl, 4),
                         "bad": cur[0], "total": cur[1],
                         "description": r.description}
                entry.update(r.detail(reg))
            else:
                try:
                    ok, detail = r.check(reg)
                except Exception as e:   # noqa: BLE001 - never 500
                    ok, detail = True, {"check_error": str(e)}
                self._transition(r.name,
                                 "healthy" if ok else "alert", now)
                entry = {"slo": r.name, "kind": r.kind,
                         "state": self._state[r.name],
                         "description": r.description}
                entry.update(detail)
            reg.gauge("slo_alert_active", slo=r.name).set(
                1.0 if self._state[r.name] == "alert" else 0.0)
            if r.name in self._since:
                entry["since"] = round(self._since[r.name], 3)
            rules_out.append(entry)
        # sample AFTER computing burns: the current instant must not be
        # its own baseline
        if (not self._samples
                or now - self._samples[-1][0] >= _MIN_SAMPLE_SPACING_S):
            self._samples.append(
                (now, {r.name: r.counts(reg) for r in self.rules
                       if r.kind == "ratio"}))
        alerts = [e for e in rules_out
                  if e["state"] in ("alert", "recovery")]
        return {"now": round(now, 3),
                "burn_threshold": self.burn_threshold,
                "windows_s": [SHORT_WINDOW_S, LONG_WINDOW_S],
                "alerts": alerts, "rules": rules_out}

    def states(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._state)

    def active_alerts(self) -> List[Dict]:
        """Alerting/recovering rules WITHOUT re-evaluating — the
        side-effect-free snapshot flight-recorder capsules stamp at
        job end."""
        with self._lock:
            return [{"slo": n, "state": s,
                     "since": round(self._since[n], 3)
                     if n in self._since else None}
                    for n, s in self._state.items()
                    if s in ("alert", "recovery")]


# ------------------------------------------------- process-wide engine

_ENGINE: Optional[SLOEngine] = None
_ENGINE_LOCK = threading.Lock()


def engine() -> SLOEngine:
    global _ENGINE
    if _ENGINE is None:
        with _ENGINE_LOCK:
            if _ENGINE is None:
                _ENGINE = SLOEngine()
    return _ENGINE


def evaluate() -> Dict:
    """Evaluate the process-wide engine (the /3/Alerts + /3/Metrics
    refresh path)."""
    return engine().evaluate()


def active_alerts() -> List[Dict]:
    """No-side-effect alert snapshot; [] before the first evaluate."""
    if _ENGINE is None:
        return []
    return _ENGINE.active_alerts()
