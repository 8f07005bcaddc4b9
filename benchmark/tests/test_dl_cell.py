"""The cell ``dl-mnist8m-200x200.fit-1m``: ``run.py --rehearse`` end to
end on the CPU, the lower-precision control and two planted faults of
the timed path coming out not correct, its rooflines' counts, and its
five readers on the program's part of a chip run
(``fixtures/dl-mnist8m-200x200.fit-1m.program.json.gz``: the first jobs'
``h2o3.*`` spans and scoped ops, written by ``program_trace.py``'s
``record``, with the ``shapes`` and ``device_kind`` of the
``reading.json`` it writes beside it; that file is
``fixtures/dl-mnist8m-200x200.fit-1m.reading.json.gz``).

Run as a script on the chip, it is the full-size witness for the same
three: ``python benchmark/tests/test_dl_cell.py --seed <n>`` makes one
short run of the cell as it stands and one with each fault planted, then
puts the reference's control in the program's place, and prints
``correct`` and every check of each.
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

CELL = "dl-mnist8m-200x200.fit-1m"
FIXTURES = os.path.join(os.path.dirname(HERE), "fixtures")
NEW_READERS = ("dl_train_share_pct", "dl_train_roofline",
               "dl_masked_steps_pct", "dl_prepare_idle_ms",
               "dl_score_idle_ms")


def loaded_cell():
    return bench_run.load_cell(bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json"), "BENCHMARK.json"), CELL)


def reader(name):
    return bench_run.load_module("layer_metrics", name, "per-layer metric")


# ---- faults of the timed path ---------------------------------------------

@contextlib.contextmanager
def as_it_stands():
    yield


@contextlib.contextmanager
def an_epoch_left_out():
    """Every job trains one epoch fewer than the configuration states."""
    init = bench_run.SystemUnderTest.__init__

    def fewer(self, config, job, seed):
        init(self, config, job, seed)
        self.params["epochs"] = self.params["epochs"] - 1

    bench_run.SystemUnderTest.__init__ = fewer
    try:
        yield
    finally:
        bench_run.SystemUnderTest.__init__ = init


@contextlib.contextmanager
def the_second_layers_update_dropped():
    """Every step hands the second layer's weights, biases and ADADELTA
    state back unchanged."""
    import jax
    from h2o3_tpu.models import deeplearning as dl
    step = dl._train_step_impl

    def dropped(params, opt_state, *args, **kwargs):
        new_p, new_s = step(params, opt_state, *args, **kwargs)
        new_p[1], new_s[1] = params[1], opt_state[1]
        return new_p, new_s

    dl._train_step_impl = dropped
    jax.clear_caches()                  # the chunk's program is traced anew
    try:
        yield
    finally:
        dl._train_step_impl = step
        jax.clear_caches()


FAULTS = (an_epoch_left_out, the_second_layers_update_dropped)


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
        # the program keeps the last job's design matrix (and through it
        # the frame) for a predict that may follow: at full size the next
        # run's frame would not fit beside it
        from h2o3_tpu.models import deeplearning
        deeplearning._DESIGN_MEMO = None
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
         "--workload", CELL, "--seed", "2147483999", "--seconds", "4",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
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
        # the one new reader a CPU run can feed: 320 of 400 steps
        assert line["metrics"]["dl_masked_steps_pct"]["value"] == 20.0
        assert not (set(NEW_READERS) - {"dl_masked_steps_pct"}) \
            & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"fit_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_sound_run_in_process_is_correct():
    result = run_in_process(78)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["steps_gap"][0] == 0
    assert result["checks"]["jobs_differ"][0] == 0


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_a_planted_fault_of_the_timed_path_is_not_correct(fault):
    with fault():
        result = run_in_process(78)
    assert result["correct"] is False
    bad = [k for k, (v, lim) in result["checks"].items() if not v <= lim]
    want = "steps_gap" if fault is an_epoch_left_out else "weight_gap"
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

def test_the_rooflines_count_from_shapes_alone():
    shapes = dict(loaded_cell()["config"]["shapes"], rows=1048576)
    step = importlib.import_module("benchmark.rooflines.mlp-step")
    fit = importlib.import_module("benchmark.rooflines.dl-fit")
    pairs = [784 * 200, 200 * 200, 200 * 10]
    one = step.work(shapes)
    # forward, weight gradients, and input gradients of layers 2 and 3
    assert one["flops"] == 2 * 16384 * (2 * sum(pairs) + sum(pairs[1:]))
    assert one["bytes"] == 16384 * 784 * 4 + (sum(pairs) + 410) * 24
    whole = fit.work(shapes)
    scored = 1048576 + 10000
    assert whole["flops"] == 640 * one["flops"] + scored * 2 * sum(pairs)
    assert whole["bytes"] == 640 * one["bytes"] \
        + 1048576 * 784 * (2 + 4) + scored * 784 * 4
    # the reference's count of steps overrides the shapes' own
    assert fit.work(dict(shapes, steps=64))["flops"] < whole["flops"] / 5


# ---- the readers on a chip run's reduced trace and program trace ----------

def test_the_cells_readers_on_the_recorded_reading():
    """``run.py --dump-trace``'s event table: every reader of the cell
    that needs no program span reads what it read on the chip."""
    from benchmark.tests.test_trace_reduce import fixture_reading as reduced
    loaded, reading, expect = reduced(CELL)
    assert {m["name"] for m in loaded["per_layer"]} == set(expect)
    for m in loaded["per_layer"]:
        got = reader(m["name"]).read(reading)
        if got is None:     # needs the program's spans: the next tests
            assert m["name"] in ("dl_masked_steps_pct", "dl_prepare_idle_ms",
                                 "dl_score_idle_ms",
                                 "idle_unattributed_pct"), m["name"]
            continue
        assert got == pytest.approx(expect[m["name"]], rel=1e-9), m["name"]
        if m["unit"] == "%":
            assert 0 < got <= 105, m["name"]


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
              t_window=rec["jobs"][0]["start"])
    kw.update(over)
    return bench_run.Reading(**kw), rec["expect"]


def test_the_new_readers_on_the_recorded_program_trace():
    reading, expect = fixture_reading()
    for name in NEW_READERS:
        if name == "dl_masked_steps_pct":     # reads the live span ring
            continue
        got = reader(name).read(reading)
        assert got == pytest.approx(expect[name], rel=1e-9), name
        assert got > 0
    assert 0 < expect["dl_train_share_pct"] <= 100
    assert 0 < expect["dl_train_roofline"] <= 105
    assert expect["dl_masked_steps_pct"] == 20.0


def test_busy_and_idle_by_span_are_the_jobs_wall_time():
    """The six phase spans and the job path take every idle nanosecond
    under ``bench.job``: with the busy time they are the jobs' wall
    time, and under 2% of the idle time falls to no span at all."""
    reading, _ = fixture_reading()
    table = reader("dl_prepare_idle_ms")
    job_path = ("fit.admit", "job", "fit.account", "job.finish")
    acc = ptm.idle_by_span(reading, names=table.NAMES + job_path,
                           within="job")
    jobs = reading.trace.host_spans("job")
    wall = sum(e.end_ns - e.start_ns for e in jobs)
    busy = sum(tr.total(tr.clip(tr.busy_intervals(
        reading.trace, reading.trace.device_planes()[0], *reading.window_ns),
        e.start_ns, e.end_ns)) for e in jobs)
    assert busy + sum(acc.values()) == pytest.approx(wall, rel=1e-9)
    assert acc[ptm.UNATTRIBUTED] < 0.02 * sum(acc.values())
    # the two readers charge by the fit's own spans alone, as
    # ``gbm_prepare_idle_ms`` does: ``fit.account`` lies inside
    # ``deeplearning.fit`` and falls to its own time there
    mine = ptm.idle_by_span(reading, names=table.NAMES, within="job")
    by_metric = sum(reader(n).read(reading) for n in
                    ("dl_prepare_idle_ms", "dl_score_idle_ms"))
    named = sum(mine.get(n, 0.0) for n in table.PREPARE + table.SCORE)
    assert by_metric == pytest.approx(named / 1e6 / len(reading.jobs))
    assert named == pytest.approx(
        sum(acc.get(n, 0.0) for n in table.PREPARE + table.SCORE
            + ("fit.account",)))


def test_the_chunks_device_time_falls_to_the_scopes():
    """But for ONE op the compiler makes: it hoists the rounding of the
    first product's operand out of the scan — a bfloat16 copy of the
    whole matrix once a chunk (``convert.N``, a fifth of the program's
    device time), which carries no name. ``dl.update`` and ``dl.slice``
    have no op of their own on the chip either: each ADADELTA update is
    fused into the fusion that makes its gradient, the slices into the
    first product's."""
    reading, _ = fixture_reading()
    module = reader("dl_train_roofline").MODULE
    by = ptm.device_by_scope(reading.program_trace, module,
                             *reading.window_ns)
    assert {"dl.forward", "dl.backward", "dl.mask"} <= set(by)
    hoisted = sum(own for op, path, own in ptm.own_times(
        reading.program_trace, module, *reading.window_ns)
        if not path and op.name.startswith("convert"))
    assert 0.15 < hoisted / sum(by.values()) < 0.30
    assert by[ptm.UNSCOPED] - hoisted < 0.05 * sum(by.values())


def test_a_program_without_the_spans_gives_the_readers_nothing():
    """The parent of the PR that brought the cell runs it with one span
    (``deeplearning.chunk``, no ``steps_run``) and no scope: the idle
    readers and the masked-steps reader return nothing, never raise."""
    reading, _ = fixture_reading()
    pt = reading.program_trace
    old = ptm.ProgramTrace(
        [s for s in pt.spans if not s[0].startswith("deeplearning.")
         or s[0] in ("deeplearning.fit", "deeplearning.chunk")], pt.ops)
    reading.program_trace = old
    assert reader("dl_prepare_idle_ms").read(reading) is None
    assert reader("dl_score_idle_ms").read(reading) is None
    assert reader("dl_masked_steps_pct").read(reading) is None
    assert reader("dl_train_share_pct").read(reading) > 0


def test_masked_steps_are_counted_from_the_chunk_spans():
    from h2o3_tpu import telemetry
    t0 = time.time()
    time.sleep(2e-3)            # a span's start is kept in whole ms
    for kept in (200, 200, 200, 40):
        with telemetry.span("deeplearning.chunk", steps=kept, steps_run=200,
                            batch=16384, bf16=True):
            pass
    reading = bench_run.Reading(t_window=t0, jobs=[{"end": time.time()}])
    assert reader("dl_masked_steps_pct").read(reading) == \
        pytest.approx(100.0 * 160 / 800)
    # spans of a program that does not say what it computed: nothing
    t1 = time.time() + 1e-3
    time.sleep(2e-3)
    with telemetry.span("deeplearning.chunk", steps=40):
        pass
    reading = bench_run.Reading(t_window=t1, jobs=[{"end": time.time()}])
    assert reader("dl_masked_steps_pct").read(reading) is None


# ---- the same three at full size, on the chip ------------------------------

def main(argv):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", type=int, default=0, metavar="N",
                    help="only the control, on N seeds from --seed on")
    a = ap.parse_args(argv)
    rows = loaded_cell()["config"]["rows"]
    for seed in range(a.seed, a.seed + a.control):
        numbers, limits = control_numbers(seed, rows=rows)
        print(json.dumps({"run": "control", "seed": seed,
                          "correct": not failed_limits(numbers, limits),
                          "checks": {k: [v, limits.get(k)] for k, v in
                                     numbers.items()}}), flush=True)
    if a.control:
        return 0
    for i, fault in enumerate((as_it_stands,) + FAULTS):
        with fault():
            result = run_in_process(a.seed + i, rehearse=False,
                                    seconds=a.seconds)
        print(json.dumps({"run": fault.__name__, "seed": a.seed + i,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checks": result["checks"]}), flush=True)
    numbers, limits = control_numbers(a.seed, rows=rows)
    print(json.dumps({"run": "control", "seed": a.seed,
                      "correct": not failed_limits(numbers, limits),
                      "checks": {k: [v, limits.get(k)] for k, v in
                                 numbers.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
