"""What set-up was made of, as the program itself counted it — shared by
the ``setup_*`` readers beside this file (no metric of its own).

Read after the window from ``telemetry.snapshot()``: the durable
counters ``span_own_seconds_total{name=}`` (a span's duration less its
child spans and less the XLA stage seconds reported under it) and
``xla_stage_seconds_total{stage=}`` / ``xla_programs_total{source=}``
(tracing, lowering, compiling, loading from the persistent cache, each
second once), and the gauge ``process_import_seconds``. The spans of
``SPANS`` are opened only where set-up's work is done — a warm job opens
none of them — so their totals are set-up's. The stage counters go on
counting after the window, when the plain reference compiles programs
of its own in this process: the stage events newer than the window's
start (``telemetry.compiles_snapshot()``, each with its own seconds)
are taken off again.

Nothing where the program has no such counter (a commit before them)
and nothing without a live process (a recorded fixture has no
``t_window``).

``python benchmark/layer_metrics/setup_parts.py --workload <cell> --seed
<n> --out DIR`` is one ``run.py --trace 1`` run that also writes
``DIR/<cell>.setup.json`` for a person: every part above beside the
harness's own timers, set-up's stage seconds by program, and the
warm-up job's spans with their own time (``PERF.md`` §5, "Where set-up
goes")."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:                  # run as a script (``record``)
    sys.path.insert(0, ROOT)

STAGES = ("trace", "lower", "compile", "cache_load")
SPANS = ("cloud.init", "cloud.backend", "frame.encode", "frame.put",
         "frame.rollups", "bin.fetch", "bin.edges", "bin.codes")
PREFIX = "h2o3tpu_"


def _telemetry(r):
    if getattr(r, "t_window", None) is None:
        return None
    try:
        from h2o3_tpu import telemetry
    except ImportError:
        return None
    return telemetry


def _values(telemetry, kind: str, name: str, label: str) -> dict:
    """``{label value: number}`` of one metric family, empty where the
    program does not have it."""
    return {m["labels"].get(label): m["value"]
            for m in telemetry.snapshot()[kind]
            if m["name"] == PREFIX + name}


def _less_late(r, family: str, label: str, values, events, weight):
    """The counter ``family`` summed over ``values`` of ``label``, less
    ``weight(e)`` of every stage event named in ``events`` that ended
    after the window opened. ``None`` where the program has no such
    counter, or its event ring no longer reaches back to the window."""
    telemetry = _telemetry(r)
    if telemetry is None:
        return None
    total = _values(telemetry, "counters", family, label)
    ring = telemetry.compiles_snapshot(1 << 20)
    if not total or (ring and ring[0]["ts_ms"] / 1e3 > r.t_window):
        return None
    return sum(total.get(v, 0.0) for v in values) - sum(
        weight(e) for e in ring
        if e["ts_ms"] / 1e3 > r.t_window and e["event"] in events)


def stage_seconds(r, *stages):
    """Own seconds of set-up in the given stages of ``STAGES``."""
    return _less_late(r, "xla_stage_seconds_total", "stage", stages,
                      {"xla_" + s for s in stages},
                      lambda e: e.get("own_s", 0.0))


def programs_compiled(r):
    """Programs set-up compiled in this process: the backend compiled
    them although a persistent cache is configured."""
    return _less_late(r, "xla_programs_total", "source", ("compile",),
                      {"xla_compile"}, lambda e: 1)


def own_seconds(r, *names):
    """Own seconds of the spans ``names`` (of ``SPANS``): 0 where the
    program has the counter and the cell opens none of them."""
    telemetry = _telemetry(r)
    if telemetry is None:
        return None
    own = _values(telemetry, "counters", "span_own_seconds_total", "name")
    if not own:
        return None
    return sum(own.get(n, 0.0) for n in names)


def import_seconds(r):
    telemetry = _telemetry(r)
    if telemetry is None:
        return None
    return _values(telemetry, "gauges", "process_import_seconds",
                   None).get(None)


def breakdown(r) -> dict:
    """Set-up of the run behind ``r`` as one table, or ``{}`` where the
    program counts none of it."""
    telemetry = _telemetry(r)
    if telemetry is None or stage_seconds(r, *STAGES) is None:
        return {}
    own = _values(telemetry, "counters", "span_own_seconds_total", "name")
    opened = {h["labels"]["name"]: h["count"]
              for h in telemetry.snapshot()["histograms"]
              if h["name"] == PREFIX + "span_seconds"}
    by_program: dict = {}
    for e in telemetry.compiles_snapshot(1 << 20):
        if e["ts_ms"] / 1e3 <= r.t_window:
            row = by_program.setdefault(e["program"], dict.fromkeys(
                ("xla_" + s for s in STAGES), 0.0))
            row[e["event"]] += e["own_s"]
    job = [{"name": s["name"], "duration_ms": s["duration_ms"],
            "own_ms": s["own_ms"], "meta": s["meta"]}
           for s in telemetry.spans_snapshot(1 << 20)
           if s["start_ms"] / 1e3 < r.t_window and s["name"] not in SPANS]
    setup_s = r.end_to_end["setup_s"]
    parts = {"import": import_seconds(r),
             **{n: own.get(n, 0.0) for n in SPANS},
             **{s: stage_seconds(r, s) for s in STAGES}}
    known = r.setup_seconds["setup_data"] + sum(parts.values()) + \
        r.end_to_end.get("fit_s", 0.0)
    return {
        "setup_s": setup_s, "harness_s": dict(r.setup_seconds),
        "fit_s": r.end_to_end.get("fit_s"), "parts_s": parts,
        "spans_opened": {n: opened.get(n, 0) for n in SPANS},
        "programs_compiled": programs_compiled(r),
        "unattributed_s": setup_s - known,
        "programs": sorted(
            ({"program": k, **v} for k, v in by_program.items()),
            key=lambda row: -sum(v for v in row.values()
                                 if isinstance(v, float)))[:24],
        "programs_seen": len(by_program),
        "warmup_job_spans": job}


def record(argv) -> int:
    import argparse
    import json
    from benchmark import run as bench_run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    read_layers, seen = bench_run.read_layers, {}

    def read_then_keep(per_layer, readers, reading):
        seen.setdefault("table", breakdown(reading))
        return read_layers(per_layer, readers, reading)

    bench_run.read_layers = read_then_keep
    rc = bench_run.main(["--workload", a.workload, "--seed", a.seed,
                         "--seconds", a.seconds, "--trace", "1"]
                        + ["--rehearse"] * a.rehearse)
    if rc == 0:
        os.makedirs(a.out, exist_ok=True)
        with open(os.path.join(a.out, f"{a.workload}.setup.json"),
                  "w") as f:
            json.dump(seen.get("table", {}), f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(record(sys.argv[1:]))
