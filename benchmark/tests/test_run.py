"""``run.py --rehearse`` end to end on the CPU for both cells, the named
errors for missing files, and the two tests "How correct is decided"
asks for: the lower-precision control comes out not correct, and a run
with the timed path broken underneath comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.tests import waiting_cells

ROOT = bench_run.ROOT
CELLS = ["glm-higgs.fit-11m", waiting_cells.GBM, waiting_cells.BTAG]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def merged_bench():
    """``BENCHMARK.json`` with the waiting cells' entries appended."""
    return waiting_cells.merged(
        bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"), "b"))


def rehearse(cell, *extra, seed=2147483999, cwd=ROOT, trace=0):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), "--rehearse", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})


@pytest.fixture(scope="module")
def merged_checkout(tmp_path_factory):
    return waiting_cells.merged_checkout(tmp_path_factory.mktemp("merged"))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_last_line_is_the_contracts_object(cell, trace,
                                                    merged_checkout):
    p = rehearse(cell, trace=trace,
                 cwd=merged_checkout if cell in waiting_cells.WAITING
                 else ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    keys = list(line)
    assert RESULT_KEYS <= set(keys) and keys[-1] == "checks"
    assert set(keys) - RESULT_KEYS <= {"breakdown", "reference_s", "checks"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"      # said truthfully
    bench = merged_bench()
    want = bench["per_layer"] if trace else bench["end_to_end"]
    mine = {m["name"] for m in want
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= mine
    if not trace:
        assert set(line["metrics"]) == mine
        assert all(m["value"] > 0 for m in line["metrics"].values())
    # every number compared is printed beside its limit, last on stderr
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json and benchmark/ alone, to break."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_alone_it_fails_without_a_result(copy):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=str(copy), capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0 and "h2o3_tpu" in p.stderr
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("gone, says", [
    ("configs/glm-higgs.json", "configuration 'glm-higgs'"),
    ("traffic/fit-loop-default.json", "traffic 'fit-loop-default'"),
    ("loops/fit-loop.py", "traffic kind 'fit-loop'"),
    ("generators/higgs.py", "generator 'higgs'"),
    ("references/glm.py", "reference 'glm'"),
    ("layer_metrics/device_idle_pct.py",
     "per-layer metric 'device_idle_pct'"),
])
def test_a_missing_file_is_a_named_error(copy, gone, says):
    os.unlink(copy / "benchmark" / gone)
    p = rehearse(CELLS[0], cwd=str(copy), trace=1)
    assert p.returncode == 3, p.stderr[-2000:]
    assert says in p.stderr and "no file" in p.stderr
    assert '"correct"' not in p.stdout


def test_unknown_workload_is_a_named_error():
    p = rehearse("no-such.cell")
    assert p.returncode == 3 and "is not in BENCHMARK.json" in p.stderr


def test_a_waiting_cell_is_not_in_the_benchmark():
    p = rehearse(waiting_cells.GBM)
    assert p.returncode == 3 and "is not in BENCHMARK.json" in p.stderr


# ---- a traffic kind brings its end-to-end metrics as files ---------------

JOBS_PER_S = {"name": "jobs_per_s", "unit": "jobs/s", "better": "higher",
              "bound": 0.05, "source": "host_clock",
              "workloads": [CELLS[0]]}


def add_end_to_end(copy, kind):
    """In the copy: one more end-to-end metric on the first cell, and its
    traffic mix moved to the traffic kind ``kind``."""
    with open(copy / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["end_to_end"].append(JOBS_PER_S)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    mix = copy / "benchmark" / "traffic" / "fit-loop-default.json"
    with open(mix) as f:
        traffic = json.load(f)
    with open(mix, "w") as f:
        json.dump({**traffic, "kind": kind}, f)


def test_a_new_traffic_kind_brings_its_own_end_to_end_metric(copy):
    (copy / "benchmark" / "loops" / "fit-loop-rate.py").write_text(
        "import importlib\n"
        "base = importlib.import_module('benchmark.loops.fit-loop')\n"
        "SPANS, check, one_job, run = (base.SPANS, base.check,\n"
        "                              base.one_job, base.run)\n"
        "def end_to_end(jobs, t_window):\n"
        "    out = base.end_to_end(jobs, t_window)\n"
        "    return {**out, 'jobs_per_s': 1.0 / out['fit_s']}\n")
    add_end_to_end(copy, "fit-loop-rate")
    p = rehearse(CELLS[0], cwd=str(copy))
    assert p.returncode == 0, p.stderr[-2000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    assert set(metrics) == {"fit_s", "setup_s", "jobs_per_s"}
    assert metrics["jobs_per_s"]["value"] == \
        pytest.approx(1.0 / metrics["fit_s"]["value"])
    assert metrics["jobs_per_s"]["unit"] == "jobs/s"


def test_a_metric_the_traffic_kind_does_not_measure_is_a_named_error(copy):
    add_end_to_end(copy, "fit-loop")
    p = rehearse(CELLS[0], cwd=str(copy))
    assert p.returncode == 3 and "'jobs_per_s' is not one the traffic " \
        "kind 'fit-loop' measures" in p.stderr
    assert '"correct"' not in p.stdout


# ---- correct has to be able to come out false ----------------------------

@pytest.fixture(autouse=True)
def in_process_runs_see_the_waiting_cells(monkeypatch):
    load = bench_run.load_json
    monkeypatch.setattr(
        bench_run, "load_json", lambda path, what:
        waiting_cells.merged(load(path, what)) if what == "BENCHMARK.json"
        else load(path, what))


def run_in_process(cell, capsys, seed=77):
    args = bench_run.argparse.Namespace(
        workload=cell, seed=seed, seconds=0.5, trace=0, rehearse=True,
        dump_trace=None)
    assert bench_run.run(args) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def altered_leaf(outputs):
    outputs["leaf"] = outputs["leaf"].copy()
    outputs["leaf"][0, np.argmax(outputs["leaf_rows"][0])] *= 1.05
    return outputs


def altered_coefficient(outputs):
    outputs["coef"] = outputs["coef"].copy()
    outputs["coef"][3] *= 1.01
    return outputs


def state_unchanged(outputs):
    """A boosting step that hands its margin back unchanged: every leaf
    of the last tree is 0."""
    outputs["leaf"] = outputs["leaf"].copy()
    outputs["leaf"][-1] = 0.0
    return outputs


FAULTS = [("gbm-airlines-d6.fit-48m", altered_leaf),
          ("gbm-airlines-d6.fit-48m", state_unchanged),
          ("glm-higgs.fit-11m", altered_coefficient)]


@pytest.mark.parametrize("cell, fault", FAULTS,
                         ids=[f"{c.split('.')[0]}-{f.__name__}"
                              for c, f in FAULTS])
def test_an_answer_altered_where_it_is_produced(cell, fault, capsys,
                                                monkeypatch):
    read = bench_run.SystemUnderTest.read_outputs
    monkeypatch.setattr(bench_run.SystemUnderTest, "read_outputs",
                        lambda self, model: fault(read(self, model)))
    assert run_in_process(cell, capsys)["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out(cell, capsys, monkeypatch):
    build = bench_run.SystemUnderTest.build_frame

    def half(self, data):
        n = len(data["columns"][data["response"]]) // 2
        build(self, {**data, "columns": {k: v[:n] for k, v in
                                         data["columns"].items()}})

    monkeypatch.setattr(bench_run.SystemUnderTest, "build_frame", half)
    assert run_in_process(cell, capsys)["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_the_sound_run_in_process_is_correct(cell, capsys):
    """On the CPU, whose products are float32: the second witness for the
    cells that wait because the chip's are not."""
    assert run_in_process(cell, capsys)["correct"] is True


CONTROLS = [("glm-higgs.fit-11m", "bf16"), (waiting_cells.BTAG, "bf16"),
            (waiting_cells.BTAG, "bf16x"), (waiting_cells.GBM, None)]


@pytest.mark.parametrize("cell, which", CONTROLS)
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_lower_precision_control_is_not_correct(cell, which, seed):
    """The reference put in the program's place, one precision down
    (bfloat16 for the float32 the configurations state; where the data
    has level-valued columns also the design matrix alone in bfloat16),
    at a size a test can hold: it has to fail at least one limit."""
    loaded = bench_run.load_cell(merged_bench(), cell)
    config, traffic = loaded["config"], loaded["traffic"]
    gen = bench_run.load_module("generators", config["generator"]["name"],
                                "generator")
    ref = bench_run.load_module("references", config["reference"],
                                "reference")
    data = gen.generate(seed, config["rehearse_rows"],
                        **config["generator"].get("args", {}))
    params = {**config["reference_params"], **traffic.get("job", {})}
    numbers = ref.check(data, ref.control(data, params, which) if which
                        else ref.control(data, params), params)
    failed = [k for k, v in numbers.items() if not k.startswith("_")
              and not v <= config["limits"][k]]
    assert failed, numbers
