#!/usr/bin/env python3
"""benchmark/run.py — run one cell of ``BENCHMARK.json`` once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: set-up (``init``, data from the seed, frame, one warm-up
job), a measured window driven by the cell's traffic loop, then — with
the program's state released — the plain reference's comparison that
decides ``correct``. The last line of standard output is the contract's
JSON object; the numbers compared, each beside its limit, are its last
key and the last lines of standard error.

Nothing here names a cell, a configuration, a traffic mix or a metric
but ``setup_s``: the cell names its configuration and traffic, each of
those names the files it needs, and the traffic kind measures its own
end-to-end metrics (see ``benchmark/README.md``).

``--rehearse`` (tiny rows, any platform, platform printed truthfully) is
for the CPU rehearsal and the benchmark's own tests only.
"""

from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "bench_out")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(Exception):
    """A named fault of the benchmark's own files or of the machine."""


# ---- files found by name --------------------------------------------------

def load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(folder: str, name: str, what: str):
    path = os.path.join(HERE, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"{what} {name!r}: no file "
                         f"{os.path.relpath(path, ROOT)}")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(
        ".".join(p for p in ("benchmark", folder, name) if p))


def load_cell(bench: dict, workload: str) -> dict:
    """The cell with everything its files say: ``{"cell", "config",
    "traffic", "end_to_end", "per_layer"}``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"workload {workload!r} is not in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}.get(cell["config"])
    if entry is None:
        raise BenchError(f"configuration {cell['config']!r} is not in "
                         "BENCHMARK.json")
    config = load_json(os.path.join(ROOT, entry["file"]),
                       f"configuration {cell['config']!r}")
    traffic = load_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json"),
        f"traffic {cell['traffic']!r}")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"), "peaks table")
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in "
                         f"benchmark/peaks.json (has: "
                         f"{sorted(table['devices'])})")
    return table["devices"][kind]


# ---- spans and counters ---------------------------------------------------

class Spans:
    """The harness's own host spans: kept in memory on the host clock,
    and written into the profiler's trace (``bench.<name>``) so that
    idle gaps can be charged to them on the trace's clock."""

    def __init__(self):
        self.done = []          # (name, start_s, end_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.time()
        with jax.profiler.TraceAnnotation("bench." + name):
            try:
                yield
            finally:
                self.done.append((name, t0, time.time()))

    def seconds(self, name: str) -> float:
        return sum(e - s for n, s, e in self.done if n == name)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache retrievals: a
    program the process has not loaded yet) as jax.monitoring reports
    them."""

    def __init__(self):
        self.n = 0

    def install(self):
        import jax.monitoring

        def on_event(event, duration, **_):
            if event in COMPILE_EVENTS:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


# ---- the system under test ------------------------------------------------

class SystemUnderTest:
    """The only code here that calls the program, and only through what a
    user calls: ``init``, ``Frame.from_numpy``, the estimator's
    ``train``, ``DKV.remove``."""

    def __init__(self, config: dict, job: dict, seed: int):
        self.config = config
        est = config["estimator"]
        self.params = dict(est["params"])
        self.params.update(job)
        if est.get("seed_param"):
            # estimators take a 32-bit seed; --seed may be larger
            self.params[est["seed_param"]] = int(seed) % (2 ** 31 - 1)
        self.adapter = load_module("adapters", config["adapter"], "adapter")
        self.frame = None

    def init(self) -> dict:
        import jax
        import h2o3_tpu
        h2o3_tpu.init()
        est = self.config["estimator"]
        self.estimator = getattr(importlib.import_module(est["module"]),
                                 est["class"])
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    def build_frame(self, data: dict):
        import jax
        import h2o3_tpu
        self.response = data["response"]
        self.frame = h2o3_tpu.Frame.from_numpy(data["columns"],
                                               domains=data["domains"])
        for name in self.frame.names:
            col = self.frame.col(name)
            for part in (col.data, col.na_mask):
                if part is not None:
                    jax.block_until_ready(part)

    def train(self):
        import h2o3_tpu
        before = set(h2o3_tpu.DKV.keys())
        model = self.estimator(**self.params).train(self.frame,
                                                    y=self.response)
        return model, set(h2o3_tpu.DKV.keys()) - before

    def read_outputs(self, model) -> dict:
        return self.adapter.read_outputs(model)

    def release(self, model, made) -> None:
        import h2o3_tpu
        for key in made:
            h2o3_tpu.DKV.remove(key)
        del model

    def drop_frame(self) -> None:
        import h2o3_tpu
        if self.frame is not None:
            h2o3_tpu.DKV.remove(self.frame.key)
            self.frame = None
        gc.collect()


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ---- what a per-layer reader may read --------------------------------------

class Reading:
    """Handed to every ``layer_metrics/<metric>.py`` ``read``. A reader
    that finds nothing to read returns ``None`` and the metric is left
    out of the line."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def least_seconds(self, roofline: str, shapes: dict):
        """``(seconds, bound)``: the least time this device could take
        for the work ``rooflines/<roofline>.py`` counts from ``shapes``
        — the larger of ops over peak ops/s and bytes over peak bytes/s
        — and which of the two bounds it. Nothing without peaks."""
        if self.peaks is None:
            return None
        work = load_module("rooflines", roofline, "roofline").work(shapes)
        by_ops = work["flops"] / self.peaks["flops_per_s"]
        by_bytes = work["bytes"] / self.peaks["bytes_per_s"]
        return max(by_ops, by_bytes), \
            ("ops" if by_ops >= by_bytes else "bytes")


def share_pct(part: float, whole: float, what: str) -> float:
    """``100 * part / whole``; over 105% is a fault of the count or of
    the clock, never clipped."""
    pct = 100.0 * part / whole
    if pct > 105.0:
        raise BenchError(f"{what} reads {pct:.1f}% (> 105%): the work is "
                         "counted too high or the time leaves part out")
    return pct


def read_layers(per_layer, readers, reading) -> dict:
    out = {}
    for m in per_layer:
        value = readers[m["name"]].read(reading)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def dump_reading(reading, per_layer, readers, out_dir: str, jobs: int = 2):
    """For a person and for ``benchmark/tests``: a summary of the whole
    trace, and a small ``Reading`` — the event table cut to the window's
    first ``jobs`` jobs, with what every reader makes of it."""
    tr, trace, cell = reading.tr, reading.trace, reading.cell["name"]
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell}.summary.json"), "w") as f:
        json.dump(tr.summary(trace), f, indent=1)
    lo = reading.window_ns[0]
    ends = sorted(e.end_ns for e in trace.host_spans("job") if
                  e.start_ns >= lo)
    hi = ends[min(jobs, len(ends)) - 1]
    rows = [r for r in trace.to_table()
            if lo <= r[3] and r[3] + r[4] <= hi and
            r[2] != tr.HOST_PREFIX + "window"]
    rows.append(["/host:CPU", "harness", tr.HOST_PREFIX + "window", lo,
                 hi - lo, ""])
    kept = reading.jobs[:jobs]
    small = Reading(**{**reading.__dict__, "trace": tr.Trace.from_table(rows),
                       "window_ns": (lo, hi), "jobs": kept,
                       "end_to_end": reading.loop.end_to_end(
                           kept, reading.t_window)})
    expect = {k: v["value"] for k, v in
              read_layers(per_layer, readers, small).items()}
    with open(os.path.join(out_dir, f"{cell}.reading.json"), "w") as f:
        json.dump({"events": rows, "shapes": small.shapes,
                   "jobs": [{"start": j["start"], "end": j["end"]}
                            for j in kept],
                   "setup_seconds": small.setup_seconds,
                   "compiles_in_window": small.compiles_in_window,
                   "memory_peak_bytes": small.memory_peak_bytes,
                   "device_kind": reading.device_kind,
                   "end_to_end": small.end_to_end, "expect": expect}, f)


# ---- one run ---------------------------------------------------------------

def process_start() -> float:
    """Wall-clock time this process started (``/proc``), else the time
    this file was first executed."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        t = time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
        return t if 0 <= T_IMPORT - t < 60 else T_IMPORT
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def same_outputs(a, b) -> bool:
    import numpy as np
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(
            same_outputs(a[k], b[k]) for k in a)
    if isinstance(a, np.ndarray):
        return a.shape == b.shape and bool(np.array_equal(a, b))
    return a == b


def find_trace(trace_dir: str) -> str:
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    raise BenchError(f"the profiler wrote no .xplane.pb under {trace_dir}")


def run(args) -> int:
    t_start = process_start()
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"),
                      "BENCHMARK.json")
    loaded = load_cell(bench, args.workload)
    cell, config, traffic = (loaded[k] for k in
                             ("cell", "config", "traffic"))
    loop = load_module("loops", traffic["kind"], "traffic kind")
    loop.check(traffic)
    generator = load_module("generators", config["generator"]["name"],
                            "generator")
    reference = load_module("references", config["reference"], "reference")
    readers = {m["name"]: load_module("layer_metrics", m["name"],
                                      "per-layer metric")
               for m in loaded["per_layer"]} if args.trace else {}

    counter = CompileCounter()
    counter.install()
    spans = Spans()
    sut = SystemUnderTest(config, traffic.get("job", {}), args.seed)
    with spans("setup_init"):
        device = sut.init()
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell["chips"]):
        raise BenchError(
            f"cell {cell['name']!r} needs {cell['chips']} TPU chip(s); "
            f"JAX reports {device['count']} x {device['platform']}")
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" \
        else None
    rows = int(config["rehearse_rows"] if args.rehearse
               else config["rows"])

    with spans("setup_frame"):
        with spans("setup_data"):
            data = generator.generate(args.seed, rows,
                                      **config["generator"].get("args", {}))
        sut.build_frame(data)
    with spans("setup_warmup"):
        loop.one_job(sut, traffic, spans)

    seconds = float(args.seconds)
    trace_dir = os.path.join(OUT_DIR, cell["name"], "trace")
    if args.trace:
        import jax
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles_before = counter.n
    t_window = time.time()
    with spans("window"):
        jobs = loop.run(sut, traffic, seconds, time.time, spans)
    t_close = time.time()
    compiles_in_window = counter.n - compiles_before
    if args.trace:
        import jax
        jax.profiler.stop_trace()
    peak_bytes = memory_peak_bytes()
    setup_s = t_window - t_start
    done = [j for j in jobs if "error" not in j]
    failed = len(jobs) - len(done)
    print(json.dumps({
        "setup_parts_s": {n[6:]: round(spans.seconds(n), 3)
                          for n in ("setup_init", "setup_data",
                                    "setup_frame", "setup_warmup")},
        "jobs": len(done), "window_s": round(t_close - t_window, 3),
        "compiles_in_window": compiles_in_window,
        "job_s": [round(j["end"] - j["start"], 4) for j in done][:64]}),
        flush=True)

    # ---- correctness: after the window, the program's state released ----
    sut.drop_frame()
    checks = {}
    facts = {}
    limits = config["limits"]
    if done:
        import numpy as np
        pick = int(np.random.default_rng(int(args.seed)).integers(len(done)))
        params = dict(config["reference_params"])
        params.update(traffic.get("job", {}))
        t_ref = time.time()
        numbers = reference.check(data, done[pick]["outputs"], params)
        # "_name": a fact the reference counted (not compared), for the
        # rooflines' shapes
        facts = {k[1:]: numbers.pop(k) for k in list(numbers)
                 if k.startswith("_")}
        numbers["jobs_differ"] = float(sum(
            not same_outputs(done[0]["outputs"], j["outputs"])
            for j in done[1:]))
        for name, value in numbers.items():
            if name not in limits:
                raise BenchError(f"configuration has no limit for the "
                                 f"compared number {name!r}")
            checks[name] = [float(value), float(limits[name])]
        ref_seconds = time.time() - t_ref
    else:
        ref_seconds = 0.0
    correct = bool(done) and failed == 0 and all(
        v == v and v <= lim for v, lim in checks.values())
    del data

    # ---- metrics ---------------------------------------------------------
    # the traffic kind owns its end-to-end numbers; set-up is the harness's
    measured = dict(loop.end_to_end(done, t_window)) if done else {}
    measured["setup_s"] = setup_s
    metrics = {}
    breakdown = None
    if not args.trace:
        for m in loaded["end_to_end"]:
            if m["name"] not in measured:
                raise BenchError(
                    f"end-to-end metric {m['name']!r} is not one the "
                    f"traffic kind {traffic['kind']!r} measures "
                    f"(it has: {sorted(measured)})")
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = load_module("", "trace_reduce", "trace reduction")
        trace = tr.load_xplane(find_trace(trace_dir))
        win = trace.host_spans("window")
        if len(win) != 1:
            raise BenchError(f"trace holds {len(win)} bench.window spans")
        lo, hi = win[0].start_ns, win[0].end_ns
        shapes = dict(config.get("shapes", {}))
        shapes.update(traffic.get("job", {}))
        shapes.update(facts)
        shapes["rows"] = rows
        reading = Reading(
            cell=cell, config=config, traffic=traffic, shapes=shapes,
            jobs=done, trace=trace, window_ns=(lo, hi), tr=tr,
            setup_seconds={n: spans.seconds(n) for n in
                           ("setup_init", "setup_data", "setup_frame",
                            "setup_warmup")},
            compiles_in_window=compiles_in_window,
            memory_peak_bytes=peak_bytes, peaks=peaks, share_pct=share_pct,
            device_kind=device["kind"], end_to_end=measured, loop=loop,
            t_window=t_window)
        metrics = read_layers(loaded["per_layer"], readers, reading)
        if args.dump_trace:
            dump_reading(reading, loaded["per_layer"], readers,
                         args.dump_trace)
        device["busy_s"] = tr.busy_seconds(trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": tr.top_ops(trace, lo, hi),
                     "idle_gaps": tr.idle_gaps(trace, lo, hi,
                                               ("window",) + loop.SPANS)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = peak_bytes

    result = {"correct": correct, "attempted": len(jobs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["reference_s"] = ref_seconds
    result["checks"] = checks
    sys.stdout.flush()
    for name, (value, limit) in checks.items():
        print(f"check {name} = {value:.6g}  limit {limit:.6g}  "
              f"{'ok' if value <= limit else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny rows, any platform: CPU rehearsal and "
                         "the benchmark's own tests only")
    ap.add_argument("--dump-trace", default=None, metavar="DIR",
                    help="with --trace 1: also write there a summary of "
                         "the trace to look at by hand, and the first "
                         "jobs' reduced event table with what the "
                         "readers made of it (a test fixture)")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except BenchError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
