"""The trace reduction and every per-layer reader, on small recorded
tables: a hand-made one for the interval arithmetic, and one cut from a
chip run of each cell (``benchmark/fixtures``) for the readers."""

import gzip
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark import trace_reduce as tr
from benchmark.tests import waiting_cells

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
DEV, OPS, MODS = "/device:TPU:0", tr.OPS_LINE, tr.MODULES_LINE


def table():
    # window 0..1000 ns; ops busy on [100,300], [300,400], a while
    # [600,900] holding two body ops [620,700], [710,890]
    return tr.Trace.from_table([
        ["/host:CPU", "main", "bench.window", 0, 1000, ""],
        ["/host:CPU", "main", "bench.job", 0, 500, ""],
        ["/host:CPU", "main", "bench.between_jobs", 500, 100, ""],
        ["/host:CPU", "main", "bench.job", 600, 400, ""],
        [DEV, OPS, "fusion.1", 100, 200, "jit_a"],
        [DEV, OPS, "kernel_x", 300, 100, "jit_a"],
        [DEV, OPS, "while.2", 600, 300, "jit_b"],
        [DEV, OPS, "kernel_x", 620, 80, "jit_b"],
        [DEV, OPS, "fusion.3", 710, 180, "jit_b"],
    ])


def test_interval_union():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert tr.total(tr.clip([(0, 3), (5, 7)], 2, 6)) == 2
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_busy_and_idle_share():
    t = table()
    assert tr.busy_seconds(t, 0, 1000) == pytest.approx(600e-9)
    assert tr.idle_share(t, 0, 1000) == pytest.approx(0.4)
    # no device op at all: nothing to read, never 0 or 1
    host_only = tr.Trace.from_table(
        [["/host:CPU", "main", "bench.window", 0, 1000, ""]])
    assert tr.idle_share(host_only, 0, 1000) is None


def test_kernel_summed_time_and_self_time():
    t = table()
    assert tr.device_seconds(t, lambda e: e.name == "kernel_x",
                             0, 1000) == pytest.approx(180e-9)
    assert tr.device_seconds(t, lambda e: e.module == "jit_b",
                             0, 1000) == pytest.approx(300e-9)
    own = {(e.name, e.start_ns): ns for e, ns in tr.self_times(t.ops())}
    assert own[("while.2", 600)] == 300 - 80 - 180
    assert own[("kernel_x", 620)] == 80
    top = tr.top_ops(t, 0, 1000, k=2)
    assert top[0][0] == "jit_a/fusion.1" and \
        top[0][1] == pytest.approx(200e-9)


def test_idle_gaps_go_to_the_innermost_span():
    gaps = dict(tr.idle_gaps(table(), 0, 1000,
                             ("window", "job", "between_jobs")))
    # [0,100] and [400,500] under job 1, [500,600] between jobs,
    # [900,1000] under job 2
    assert gaps == {"job": pytest.approx(300e-9),
                    "between_jobs": pytest.approx(100e-9)}


def test_module_name():
    assert tr.module_name("jit_predict_forest(123456)") == \
        "jit_predict_forest"


def test_share_over_105_is_an_error():
    assert bench_run.share_pct(1.0, 1.0, "x") == 100.0
    with pytest.raises(bench_run.BenchError, match="105"):
        bench_run.share_pct(1.06, 1.0, "x")


def test_unknown_device_kind_is_an_error():
    assert bench_run.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(bench_run.BenchError, match="peaks.json"):
        bench_run.peaks_for("TPU v9 imaginary")


def fixture_reading(name):
    """A ``Reading`` rebuilt from a recorded chip run of one cell: the
    reduced event table and what the harness knew beside it."""
    with gzip.open(os.path.join(FIXTURES, f"{name}.reading.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    trace = tr.Trace.from_table(rec["events"])
    win = trace.host_spans("window")[0]
    bench = waiting_cells.merged(bench_run.load_json(
        os.path.join(bench_run.ROOT, "BENCHMARK.json"), "BENCHMARK.json"))
    loaded = bench_run.load_cell(bench, name)
    reading = bench_run.Reading(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], shapes=rec["shapes"],
        jobs=rec["jobs"], trace=trace, tr=tr,
        window_ns=(win.start_ns, win.end_ns),
        setup_seconds=rec["setup_seconds"],
        compiles_in_window=rec["compiles_in_window"],
        memory_peak_bytes=rec["memory_peak_bytes"],
        peaks=bench_run.peaks_for(rec["device_kind"]),
        device_kind=rec["device_kind"],
        share_pct=bench_run.share_pct, end_to_end=rec["end_to_end"])
    return loaded, reading, rec["expect"]


@pytest.mark.parametrize("cell", ["glm-higgs.fit-11m",
                                  "gbm-airlines-d6.fit-48m"])
def test_every_reader_on_a_recorded_trace(cell):
    loaded, reading, expect = fixture_reading(cell)
    assert {m["name"] for m in loaded["per_layer"]} == set(expect)
    for m in loaded["per_layer"]:
        reader = bench_run.load_module("layer_metrics", m["name"],
                                       "per-layer metric")
        got = reader.read(reading)
        assert got is not None, m["name"]
        assert got == pytest.approx(expect[m["name"]], rel=1e-9), m["name"]
        if m["unit"] == "%":
            assert 0 < got <= 105, m["name"]


def test_load_xplane_keeps_the_harness_spans(tmp_path):
    """Stage one on a trace recorded here (CPU: no device plane): the
    harness's annotations come through with their clock."""
    import jax
    import jax.numpy as jnp
    spans = bench_run.Spans()
    jax.profiler.start_trace(str(tmp_path))
    with spans("window"):
        with spans("job"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load_xplane(bench_run.find_trace(str(tmp_path)))
    (win,), (job,) = trace.host_spans("window"), trace.host_spans("job")
    assert win.start_ns <= job.start_ns and job.end_ns <= win.end_ns
    assert trace.device_planes() == [] and tr.busy_seconds(trace, 0, 1) == 0
