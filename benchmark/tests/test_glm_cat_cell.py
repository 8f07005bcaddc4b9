"""The cell ``glm-airlines-116m-cat.fit-116m``: ``run.py --rehearse`` end
to end on the CPU, the two lower-precision controls and two planted
faults of the timed path (the ``Origin`` x ``Dest`` block of the factor
Gram dropped; the weights entering the factor Gram as one bfloat16 piece
instead of three) coming out not correct, the rooflines' counts, and the
two new readers on a chip run's recorded program trace
(``fixtures/glm-airlines-116m-cat.fit-116m.program.json.gz``).

Run as a script on the chip, it is the full-size witness:
``python benchmark/tests/test_glm_cat_cell.py --seed <n>`` makes one
short run of the cell with each fault planted, then one with each control
of the reference in the job's place before ``run.py``'s own comparison,
and prints ``correct`` and every check of each (``--control N``: the
controls alone, on N seeds; ``--which``: which). ``--record DIR`` is
one traced run of the cell that also writes ``DIR/<cell>.program.json.gz``
(the first ``--cut`` seconds of its first job, the fixture) and
``DIR/<cell>.summary.json`` (the whole window: seconds by program span,
idle by span, device seconds by scope and the ops under each).
"""

import contextlib
import functools
import gzip
import importlib
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:                       # run as a script
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import program_trace as ptm  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

CELL = "glm-airlines-116m-cat.fit-116m"
FIXTURES = os.path.join(os.path.dirname(HERE), "fixtures")
NEW_READERS = ("glm_cat_gram_share_pct", "glm_cat_gram_roofline")


def loaded_cell():
    return bench_run.load_cell(bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json"), "BENCHMARK.json"), CELL)


def reader(name):
    return bench_run.load_module("layer_metrics", name, "per-layer metric")


# ---- faults of the timed path ---------------------------------------------

@contextlib.contextmanager
def _local_gram_as(make):
    """Every shard's factor Gram replaced by ``make(the sound one)``."""
    import jax
    from h2o3_tpu.ops import gram
    sound = gram._local_codes_gram
    gram._local_codes_gram = make(sound)
    jax.clear_caches()                  # the solve program is traced anew
    try:
        yield
    finally:
        gram._local_codes_gram = sound
        jax.clear_caches()


def a_factor_block_dropped():
    """The ``Origin`` x ``Dest`` block of X'WX left at zero."""
    def make(sound):
        def dropped(X, wz):
            xtx, xtz, ws = sound(X, wz)
            (o, _, lo), (d, _, ld) = X.factors[4], X.factors[5]
            xtx = xtx.at[o:o + lo - 1, d:d + ld - 1].set(0.0)
            return xtx.at[d:d + ld - 1, o:o + lo - 1].set(0.0), xtz, ws
        return dropped
    return _local_gram_as(make)


def weights_in_one_piece():
    """The weights and weighted residuals enter the factor Gram as ONE
    bfloat16 piece (what three pieces of each are there for)."""
    import jax

    def make(sound):
        return lambda X, wz: sound(X, jax.lax.reduce_precision(
            wz, exponent_bits=8, mantissa_bits=7))
    return _local_gram_as(make)


def run_in_process(seed, *, rehearse=True, seconds=0.5, trace=0):
    """One run of the cell in this process; the result object."""
    import io
    out, sys.stdout = sys.stdout, io.StringIO()
    try:
        rc = bench_run.run(bench_run.argparse.Namespace(
            workload=CELL, seed=seed, seconds=seconds, trace=trace,
            rehearse=rehearse, dump_trace=None))
        text = sys.stdout.getvalue()
    finally:
        sys.stdout = out
    assert rc == 0
    return json.loads(text.strip().splitlines()[-1])


@contextlib.contextmanager
def control_in_place(which):
    """``run.py``'s comparison handed the reference's control ``which``,
    made from the run's own data, in place of the job it picked: the
    harness's own verdict (``correct``, ``checks``) on the control."""
    load = bench_run.load_module

    def loading(kind, name, what):
        module = load(kind, name, what)
        if kind != "references":
            return module
        swapped = types.ModuleType(module.__name__)
        swapped.__dict__.update(vars(module))
        swapped.check = lambda data, outputs, params: module.check(
            data, module.control(data, params, which), params)
        return swapped

    bench_run.load_module = loading
    try:
        yield
    finally:
        bench_run.load_module = load


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
        # the span ring's count is read on any platform; the two new
        # readers read a device trace, which a CPU run has not
        assert line["metrics"]["glm_iterations_per_job"]["value"] >= 3
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert not set(NEW_READERS) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"fit_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_sound_run_in_process_is_correct():
    result = run_in_process(41)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["path_gap"][0] < 0.1 * \
        result["checks"]["path_gap"][1]


FAULTS = {"factor_block_dropped": a_factor_block_dropped,
          "weights_in_one_piece": weights_in_one_piece}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_of_the_timed_path_is_not_correct(fault):
    with FAULTS[fault]():
        result = run_in_process(41)
    assert result["correct"] is False
    bad = [k for k, (v, lim) in result["checks"].items() if not v <= lim]
    assert "path_gap" in bad, result["checks"]


@pytest.mark.parametrize("which", ["bf16", "bf16w"])
def test_the_lower_precision_controls_are_not_correct(which):
    with control_in_place(which):
        result = run_in_process(7)
    assert result["correct"] is False
    bad = [k for k, (v, lim) in result["checks"].items() if not v <= lim]
    assert "path_gap" in bad, result["checks"]


# ---- rooflines -------------------------------------------------------------

def test_the_rooflines_count_from_shapes_alone():
    shapes = dict(loaded_cell()["config"]["shapes"], rows=116_000_000,
                  passes=5)
    one = importlib.import_module("benchmark.rooflines.cat-gram-pass")
    fit = importlib.import_module("benchmark.rooflines.glm-cat-fit")
    # nine non-zeros a row: six indicators, two numerics, the intercept
    assert one.work(shapes)["flops"] == 116_000_000 * 2 * (45 + 9)
    # six int32 codes, two float32 numerics, w and w·z
    assert one.work(shapes)["bytes"] == 116_000_000 * (24 + 8 + 8)
    whole = fit.work(shapes)
    assert whole == {k: 5 * v for k, v in one.work(shapes).items()}


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
              peaks=bench_run.peaks_for(rec["device_kind"]), t_window=None)
    kw.update(over)
    return bench_run.Reading(**kw), rec["expect"]


def test_the_readers_on_the_recorded_program_trace():
    """The fixture is the first part of a chip run's first job
    (``--record``): the readers read there what they read when it was
    cut, a share of busy time and a roofline share under 105%."""
    reading, expect = fixture_reading()
    for name in NEW_READERS:
        got = reader(name).read(reading)
        assert got == pytest.approx(expect[name], rel=1e-9), name
        assert got > 0
    assert 0 < expect["glm_cat_gram_share_pct"] <= 100
    assert 0 < expect["glm_cat_gram_roofline"] < 105


def test_a_program_without_the_scope_gives_the_readers_nothing():
    """The parent cannot run the cell; a program whose factor Gram names
    no ``gram.cat`` (a design held dense) gives both readers nothing, and
    neither raises."""
    reading, _ = fixture_reading()
    pt = reading.program_trace
    reading.program_trace = ptm.ProgramTrace(
        pt.spans, [o for o in pt.ops if "gram.cat" not in o.scope])
    for name in NEW_READERS:
        assert reader(name).read(reading) is None, name


# ---- a traced chip run, recorded -------------------------------------------

def record(seed, out_dir, cut_seconds, rehearse=False):
    """One traced run; the fixture and the window's summary beside it."""
    os.makedirs(out_dir, exist_ok=True)
    read_layers = bench_run.read_layers

    def read_and_keep(per_layer, readers, reading):
        pt = ptm.of(reading)
        lo, hi = reading.window_ns
        busy = tr.merge((o.start_ns, o.end_ns) for o in pt.ops)
        with open(os.path.join(out_dir, f"{CELL}.summary.json"), "w") as f:
            json.dump({
                "jobs": len(reading.jobs),
                "span_seconds": {n: sum(e - s for m, s, e in pt.spans
                                        if m == n and lo <= s <= hi) / 1e9
                                 for n in sorted({s[0] for s in pt.spans})},
                "idle_seconds_by_span": {
                    k: v / 1e9 for k, v in ptm.charge_idle(
                        pt, tr.clip(busy, lo, hi), lo, hi).items()},
                "device_seconds_by_scope": {
                    k: v / 1e9 for k, v in ptm.device_by_scope(
                        pt, ".*", lo, hi).items()},
                "ops_by_scope": ptm.ops_by_scope(pt, ".*", lo, hi, k=6)},
                f, indent=1)
        host = [r for r in reading.trace.to_table()
                if not tr.DEVICE_PLANE.match(r[0])]
        (job,) = [r for r in host if r[2] == tr.HOST_PREFIX + "job"][:1]
        clo, chi = job[3], job[3] + cut_seconds * 1e9
        harness = [r[:3] + [max(r[3], clo), min(r[3] + r[4], chi)
                            - max(r[3], clo)] + r[5:] for r in host
                   if r[3] < chi and r[3] + r[4] > clo
                   and r[2] != tr.HOST_PREFIX + "window"]
        harness.append(["/host:CPU", "harness", tr.HOST_PREFIX + "window",
                        clo, chi - clo, ""])
        small = ptm.ProgramTrace(
            [[n, max(s, clo), min(e, chi)] for n, s, e in pt.spans
             if s < chi and e > clo],
            [o for o in pt.ops if clo <= o.start_ns and o.end_ns <= chi])
        rec = {"program": small.to_table(), "harness": harness,
               "jobs": [{"start": 0.0, "end": float(cut_seconds)}],
               "shapes": reading.shapes,
               "device_kind": reading.device_kind, "expect": {}}
        path = os.path.join(out_dir, f"{CELL}.program.json.gz")
        with gzip.open(path, "wt") as f:
            json.dump(rec, f)
        cut = bench_run.Reading(**{
            **reading.__dict__, "program_trace": ptm.ProgramTrace.from_table(
                rec["program"]),
            "trace": tr.Trace.from_table(harness + [
                [o.plane, o.line, o.name, o.start_ns, o.dur_ns, o.module]
                for o in small.ops]),
            "window_ns": (clo, chi), "jobs": rec["jobs"]})
        rec["expect"] = {n: reader(n).read(cut) for n in NEW_READERS}
        with gzip.open(path, "wt") as f:
            json.dump(rec, f)
        return read_layers(per_layer, readers, reading)

    bench_run.read_layers = read_and_keep
    try:
        return run_in_process(seed, rehearse=rehearse, seconds=30, trace=1)
    finally:
        bench_run.read_layers = read_layers


# ---- the full-size witness, on the chip ------------------------------------

def main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", type=int, default=0, metavar="N",
                    help="the controls alone, on N seeds from --seed on")
    ap.add_argument("--which", default="bf16,bf16w",
                    help="with --control: the controls, comma-separated")
    ap.add_argument("--record", metavar="DIR", default=None,
                    help="one traced run, its fixture and summary in DIR")
    ap.add_argument("--cut", type=float, default=0.3,
                    help="seconds of the first job the fixture keeps")
    ap.add_argument("--rehearse", action="store_true",
                    help="with --record: tiny rows, any platform")
    args = ap.parse_args(argv)
    if args.record:
        result = record(args.seed, args.record, args.cut, args.rehearse)
        print(json.dumps(result), flush=True)
        return 0
    runs = [] if args.control else [
        (args.seed, name, plant) for name, plant in sorted(FAULTS.items())]
    runs += [(seed, f"control {which}",
              functools.partial(control_in_place, which))
             for seed in range(args.seed, args.seed + (args.control or 1))
             for which in args.which.split(",")]
    for seed, name, plant in runs:
        with plant():
            result = run_in_process(seed, rehearse=False,
                                    seconds=args.seconds)
        print(json.dumps({"run": name, "seed": seed,
                          "correct": result["correct"],
                          "checks": result["checks"],
                          "reference_s": result["reference_s"],
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
