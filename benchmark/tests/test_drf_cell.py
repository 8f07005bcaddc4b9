"""The cell ``drf-airlines-d20.fit-48m``: ``run.py --rehearse`` end to
end on the CPU, the lower-precision control and two planted faults of
the timed path (a forest stopped short of its depth, as every default
forest was before PR 35; the per-node column draw ignored) coming out not
correct, its job roofline's count, and its four readers: the two that
read the trace on the program's part of a chip run
(``fixtures/drf-airlines-d20.fit-48m.program.json.gz``, written by
``program_trace.py``'s ``record``), the two that read the span ring on
spans made here.

Run as a script on the chip, it is the full-size witness for the same
three: ``python benchmark/tests/test_drf_cell.py --seed <n>`` makes one
short run of the cell with each fault planted (the cut at level 14, where
the program stopped before), then puts the reference's control in the
program's place, and prints ``correct`` and every check of each
(``--sound`` adds a run of the cell as it stands; ``--control`` runs the
control alone).
"""

import contextlib
import gzip
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:                       # run as a script
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import program_trace as ptm  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

CELL = "drf-airlines-d20.fit-48m"
FIXTURES = os.path.join(os.path.dirname(HERE), "fixtures")
TRACE_READERS = ("drf_prepare_idle_ms", "drf_frontier_share_pct")
NEW_READERS = TRACE_READERS + ("drf_frontier_roofline",
                               "drf_levels_frontier_per_tree")


def loaded_cell():
    return bench_run.load_cell(bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json"), "BENCHMARK.json"), CELL)


def reader(name):
    return bench_run.load_module("layer_metrics", name, "per-layer metric")


# ---- faults of the timed path ---------------------------------------------

def stopped_at(level):
    @contextlib.contextmanager
    def a_forest_stopped_short():
        """Every job grows its trees to ``level`` levels and no further,
        whatever the configuration states."""
        init = bench_run.SystemUnderTest.__init__

        def capped(self, config, job, seed):
            init(self, config, job, seed)
            self.params["max_depth"] = level

        bench_run.SystemUnderTest.__init__ = capped
        try:
            yield
        finally:
            bench_run.SystemUnderTest.__init__ = init
    return a_forest_stopped_short


@contextlib.contextmanager
def the_column_draw_ignored():
    """Every node may split on every column."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.models import tree
    draw = tree._mtries_mask
    tree._mtries_mask = lambda key, heap, F, mtries: jnp.ones(
        (heap.shape[0], F), bool)
    jax.clear_caches()                  # the forest program is traced anew
    try:
        yield
    finally:
        tree._mtries_mask = draw
        jax.clear_caches()


def run_in_process(seed, *, rehearse=True, seconds=0.5):
    """One run of the cell in this process; the result object."""
    import io
    out, sys.stdout = sys.stdout, io.StringIO()
    try:
        rc = bench_run.run(bench_run.argparse.Namespace(
            workload=CELL, seed=seed, seconds=seconds, trace=0,
            rehearse=rehearse, dump_trace=None))
        text = sys.stdout.getvalue()
    finally:
        sys.stdout = out
    assert rc == 0
    return json.loads(text.strip().splitlines()[-1])


def control_numbers(seed, rows=None):
    """The reference's control in the program's place: ``(numbers,
    limits)``."""
    loaded = loaded_cell()
    config, traffic = loaded["config"], loaded["traffic"]
    gen = bench_run.load_module("generators", config["generator"]["name"],
                                "generator")
    ref = bench_run.load_module("references", config["reference"],
                                "reference")
    data = gen.generate(seed, rows or config["rehearse_rows"],
                        **config["generator"].get("args", {}))
    params = {**config["reference_params"], **traffic.get("job", {}),
              "seed": seed % (2 ** 31 - 1)}
    numbers = ref.check(data, ref.control(data, params), params)
    return numbers, config["limits"]


def failed_limits(numbers, limits):
    return [k for k, v in numbers.items()
            if not k.startswith("_") and not v <= limits[k]]


# ---- rehearsal -------------------------------------------------------------

def rehearse(trace):
    import subprocess
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse_last_line_is_the_contracts_object(trace):
    p = rehearse(trace)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"      # said truthfully
    assert set(line["checks"]) == set(loaded_cell()["config"]["limits"])
    if trace:
        # the one new reader a CPU run can feed: the span ring's count
        assert line["metrics"]["drf_levels_frontier_per_tree"]["value"] \
            == 11.0
        assert not (set(NEW_READERS) - {"drf_levels_frontier_per_tree"}) \
            & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"fit_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_sound_run_in_process_is_correct():
    result = run_in_process(78)
    assert result["correct"] is True, result["checks"]
    for name in ("depth_gap", "mtries_gap", "leaf_rows_gap", "jobs_differ"):
        assert result["checks"][name][0] == 0


FAULTS = {"stopped_short": (stopped_at(10), "depth_gap"),
          "column_draw_ignored": (the_column_draw_ignored, "mtries_gap")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_of_the_timed_path_is_not_correct(fault):
    plant, want = FAULTS[fault]
    with plant():
        result = run_in_process(78)
    assert result["correct"] is False
    bad = [k for k, (v, lim) in result["checks"].items() if not v <= lim]
    assert want in bad, result["checks"]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_lower_precision_control_is_not_correct(seed):
    numbers, limits = control_numbers(seed)
    assert failed_limits(numbers, limits), numbers


def test_every_limit_has_its_reason():
    config = loaded_cell()["config"]
    assert set(config["limits_why"]) == set(config["limits"])
    ref = bench_run.load_module("references", config["reference"], "r")
    assert set(ref.NAMES) | {"jobs_differ"} == set(config["limits"])


# ---- rooflines -------------------------------------------------------------

def test_the_job_roofline_counts_from_shapes_alone():
    shapes = dict(loaded_cell()["config"]["shapes"], rows=48_000_000,
                  ntrees=2)
    fit = importlib.import_module("benchmark.rooflines.drf-fit")
    one = importlib.import_module("benchmark.rooflines.tree-hist")
    whole = fit.work(shapes)
    assert whole["flops"] == 2 * 20 * 48_000_000 * 10 * 3
    assert whole["bytes"] == 2 * 20 * 48_000_000 * (10 + 12)
    # a pass of the job is a pass of the level roofline less the node id
    assert whole["flops"] == 40 * one.work(shapes)["flops"]
    assert whole["bytes"] == 40 * (one.work(shapes)["bytes"]
                                   - 4 * 48_000_000)


# ---- the readers -----------------------------------------------------------

def fixture_reading(**over):
    with gzip.open(os.path.join(FIXTURES, f"{CELL}.program.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    pt = ptm.ProgramTrace.from_table(rec["program"])
    trace = tr.Trace.from_table(
        rec["harness"] + [[o.plane, o.line, o.name, o.start_ns, o.dur_ns,
                           o.module] for o in pt.ops])
    (win,) = trace.host_spans("window")
    loaded = loaded_cell()
    kw = dict(cell=loaded["cell"], config=loaded["config"],
              traffic=loaded["traffic"], shapes=rec["shapes"], trace=trace,
              tr=tr, jobs=rec["jobs"], window_ns=(win.start_ns, win.end_ns),
              program_trace=pt, share_pct=bench_run.share_pct,
              peaks=bench_run.peaks_for(rec["device_kind"]),
              t_window=None)
    kw.update(over)
    return bench_run.Reading(**kw), rec["expect"]


def test_the_trace_readers_on_the_recorded_program_trace(monkeypatch):
    """The fixture is the first seconds of a chip run's first job
    (``cut_fixture``): the readers read there what they read when it
    was cut; the roofline's pass count comes from the live span ring, so
    it is handed the cell's 11 levels a tree."""
    reading, expect = fixture_reading()
    monkeypatch.setattr(reader("drf_levels_frontier_per_tree"), "read",
                        lambda r: 11.0)
    for name in TRACE_READERS + ("drf_frontier_roofline",):
        got = reader(name).read(reading)
        assert got == pytest.approx(expect[name], rel=1e-9), name
        assert got > 0
    assert 0 < expect["drf_frontier_share_pct"] <= 100
    assert 0 < expect["drf_frontier_roofline"] <= 105


def test_the_frontiers_device_time_falls_to_its_three_scopes():
    reading, _ = fixture_reading()
    share = reader("drf_frontier_share_pct")
    by = share.by_scope(reading)
    assert set(share.SCOPES) <= set(by)
    assert all(by[s] > 0 for s in share.SCOPES)


def test_a_program_without_the_spans_gives_the_readers_nothing():
    """The parent of the PR that brought the cell cannot run it at all;
    any program without ``drf.*`` spans or ``tree.frontier.*`` scopes
    gives the four readers nothing, and none raises."""
    reading, _ = fixture_reading()
    pt = reading.program_trace
    bare = ptm.ProgramTrace(
        [s for s in pt.spans if not s[0].startswith("drf.")],
        [o for o in pt.ops if "frontier" not in " ".join(o.scope)])
    reading.program_trace = bare
    for name in NEW_READERS:
        assert reader(name).read(reading) is None, name


def test_the_levels_are_counted_from_the_chunk_spans():
    from h2o3_tpu import telemetry
    t0 = time.time()
    time.sleep(2e-3)            # a span's start is kept in whole ms
    for _ in range(3):
        with telemetry.span("drf.chunk", trees=2, levels_kernel=9,
                            levels_xla=0, levels_frontier=11):
            pass
    reading = bench_run.Reading(t_window=t0, jobs=[{"end": time.time()}])
    assert reader("drf_levels_frontier_per_tree").read(reading) == 11.0
    # spans of a program that does not say how its levels ran: nothing
    t1 = time.time() + 1e-3
    time.sleep(2e-3)
    with telemetry.span("drf.chunk", trees=2):
        pass
    reading = bench_run.Reading(t_window=t1, jobs=[{"end": time.time()}])
    assert reader("drf_levels_frontier_per_tree").read(reading) is None


# ---- cutting a chip run's program trace down to a fixture ------------------

def cut_fixture(src_dir, seconds):
    """``program_trace.py``'s record of a chip run holds a whole job: over
    a million device ops, too many to keep. Cut it to the first
    ``seconds`` of the first job — spans and harness spans clipped there,
    the ops that end inside — and write what the trace readers make of
    THAT as its ``expect`` (``fixtures/<cell>.program.json.gz``)."""
    with open(os.path.join(src_dir, f"{CELL}.program.json")) as f:
        rec = json.load(f)
    with open(os.path.join(src_dir, f"{CELL}.reading.json")) as f:
        whole = json.load(f)
    (job,) = [r for r in rec["harness"]
              if r[2] == tr.HOST_PREFIX + "job"][:1]
    lo, hi = job[3], job[3] + seconds * 1e9

    def clip(rows):
        return [r[:3] + [max(r[3], lo), min(r[3] + r[4], hi)
                         - max(r[3], lo)] + r[5:]
                for r in rows if r[3] < hi and r[3] + r[4] > lo]
    harness = clip([r for r in rec["harness"]
                    if r[2] != tr.HOST_PREFIX + "window"])
    harness.append(["/host:CPU", "harness", tr.HOST_PREFIX + "window", lo,
                    hi - lo, ""])
    table = rec["program"]
    table["spans"] = [[n, max(s, lo), min(e, hi)] for n, s, e in
                      table["spans"] if s < hi and e > lo]
    table["ops"] = [o for o in table["ops"] if lo <= o[1]
                    and o[1] + o[2] <= hi]
    out = {"program": table, "harness": harness,
           "jobs": [{"start": 0.0, "end": float(seconds)}],
           "shapes": whole["shapes"], "device_kind": whole["device_kind"],
           "expect": {}}
    path = os.path.join(FIXTURES, f"{CELL}.program.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump(out, f)
    reading, _ = fixture_reading()
    levels = reader("drf_levels_frontier_per_tree")
    levels_read, levels.read = levels.read, lambda r: 11.0
    try:
        out["expect"] = {n: reader(n).read(reading) for n in
                         TRACE_READERS + ("drf_frontier_roofline",)}
    finally:
        levels.read = levels_read
    with gzip.open(path, "wt") as f:
        json.dump(out, f)
    return path, len(table["ops"]), out["expect"]


# ---- the full-size witness, on the chip ------------------------------------

def main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--cut-fixture", metavar="DIR", default=None,
                    help="cut DIR's recorded program trace to the first "
                         "--seconds of its first job, as the fixture")
    ap.add_argument("--sound", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="the control alone")
    args = ap.parse_args(argv)
    if args.cut_fixture:
        print(json.dumps(cut_fixture(args.cut_fixture, args.seconds)))
        return 0
    config = loaded_cell()["config"]
    runs = [] if args.control else [
        ("stopped_at_14", stopped_at(14)),
        ("column_draw_ignored", the_column_draw_ignored)]
    if args.sound:
        runs.insert(0, ("as_it_stands", contextlib.nullcontext))
    for name, plant in runs:
        with plant():
            result = run_in_process(args.seed, rehearse=False,
                                    seconds=args.seconds)
        print(json.dumps({"run": name, "correct": result["correct"],
                          "checks": result["checks"],
                          "reference_s": result["reference_s"],
                          "metrics": result["metrics"]}), flush=True)
    t0 = time.time()
    numbers, limits = control_numbers(args.seed, rows=config["rows"])
    print(json.dumps({"run": "control", "seconds": time.time() - t0,
                      "correct": not failed_limits(numbers, limits),
                      "failed": failed_limits(numbers, limits),
                      "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
