"""``benchmark/program_trace.py`` and the readers that stand on it: the
arithmetic on hand-made tables, the file reader on a hand-encoded
``XSpace`` and on a trace recorded here (CPU), and everything together
on the program's part of a chip run of ``glm-higgs.fit-11m``
(``fixtures/glm-higgs.fit-11m.program.json.gz``: the first two jobs'
``h2o3.*`` spans and scoped ops, written by ``program_trace.py``'s
``record``)."""

import gzip
import json
import os

import pytest

from benchmark import program_trace as ptm
from benchmark import run as bench_run
from benchmark import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
CELL = "glm-higgs.fit-11m"
DEV, OPS = "/device:TPU:0", tr.OPS_LINE
SOLVE = "jit__irls_solve"
IDLE_METRICS = ("job_path_idle_ms", "glm_prepare_idle_ms",
                "glm_solve_idle_ms", "glm_metrics_idle_ms")
TRACE_METRICS = IDLE_METRICS + ("idle_unattributed_pct",
                                "gram_slice_share_pct")


def reader(name):
    return bench_run.load_module("layer_metrics", name, "per-layer metric")


def op(name, start, dur, op_name, module=SOLVE):
    return ptm.Op(DEV, OPS, name, start, dur, module,
                  ptm.scope_path(op_name))


# ---- arithmetic on hand-made tables ---------------------------------------

def spans_table():
    # window 0..1000; device busy on [100,300] and [600,900]
    return ptm.ProgramTrace(
        [("fit.admit", 0, 50), ("job", 50, 950), ("glm.fit", 60, 900),
         ("glm.design", 60, 100), ("mr.frame_reduce", 70, 90),
         ("glm.solve", 100, 420), ("glm.metrics", 420, 880),
         ("job.finish", 950, 980)], [])


def test_idle_goes_to_the_innermost_span():
    busy = [(100, 300), (600, 900)]
    acc = ptm.charge_idle(spans_table(), busy, 0, 1000)
    assert acc == {"fit.admit": 50, "job": 10 + 50, "glm.design": 20,
                   "mr.frame_reduce": 20, "glm.solve": 120,
                   "glm.metrics": 180, "job.finish": 30,
                   "unattributed": 20}      # glm.fit's own time was busy
    assert sum(acc.values()) == 1000 - 500
    # only the named spans are charged: a deeper one of another name
    # falls to the one around it
    named = ptm.charge_idle(spans_table(), busy, 0, 1000,
                            names=("job", "glm.design", "glm.solve"))
    assert named == {"glm.design": 40, "glm.solve": 120,
                     "job": 500 - 40 - 120 - 50 - 50, "unattributed": 100}


def test_a_trace_without_program_spans_charges_nothing():
    assert ptm.charge_idle(ptm.ProgramTrace([], []), [(0, 10)], 0, 100) \
        == {"unattributed": 90}


def scoped_ops():
    # one IRLS iteration as the chip shows it: the loop and the scan are
    # compiler-made `while`s, the layout copy carries no metadata, the
    # shard_map body is named from its own root
    root = "jit(_irls_solve)/while/body/glm.irls_iter/"
    return [op("while.1", 0, 1000, ""),
            op("fusion.1", 10, 90, root + "glm.reweight/mul"),
            op("copy.42", 100, 100, ""),
            op("slice_bitcast_fusion.2", 200, 100, "gram.blocks/reshape"),
            op("while.2", 300, 400, ""),
            op("dynamic-slice_fusion.2", 310, 100,
               "gram.blocks/while/body/dynamic_slice"),
            op("select_add_fusion.2", 410, 100,
               "gram.blocks/while/body/gram.accumulate/dot_general"),
            op("cholesky.1", 700, 100, root + "glm.newton_solve/cholesky"),
            op("copy.9", 1100, 50, ""),
            op("fusion.7", 2000, 100, "jit(other)/x.y/add", "jit_other")]


def test_scope_path_keeps_the_dotted_names():
    assert ptm.scope_path("jit(f)/while/body/glm.irls_iter/gram.blocks/"
                          "reshape:") == ("glm.irls_iter", "gram.blocks")
    assert ptm.scope_path("jit(f)/jit(main)/dot_general:") == ()
    assert ptm.scope_path("gram.blocks/while/body/dynamic_slice") == \
        (ptm.RELATIVE, "gram.blocks")


def test_device_time_by_innermost_scope():
    pt = ptm.ProgramTrace([], scoped_ops())
    by = ptm.device_by_scope(pt, SOLVE, 0, 3000)
    assert by == {
        "glm.reweight": 90,
        # the loop's own time and the copy inside it: inherited
        "glm.irls_iter": (1000 - 90 - 100 - 100 - 400 - 100) + 100,
        "gram.blocks": 100 + (400 - 200) + 100,
        "gram.accumulate": 100, "glm.newton_solve": 100,
        "unscoped": 50}
    assert sum(by.values()) == 1050          # the program's device time
    paths = {o.name: p for o, p in ptm.resolve_scopes(pt.ops).items()}
    assert paths["select_add_fusion.2"] == \
        ("glm.irls_iter", "gram.blocks", "gram.accumulate")
    assert paths["copy.42"] == ("glm.irls_iter",) and paths["copy.9"] == ()


# ---- the file reader -------------------------------------------------------

def varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_metadata_scopes_reads_tf_op_from_the_event_metadata():
    def stat(meta_id, **kw):
        body = field(1, meta_id)
        if "s" in kw:
            body += field(5, kw["s"].encode())
        if "u" in kw:
            body += field(3, kw["u"])
        return field(5, body)

    def entry(number, key, message):
        return field(number, field(1, key) + field(2, message))

    plane = (field(2, b"/device:TPU:0")
             + entry(5, 7, field(1, 7) + field(2, b"tf_op"))
             + entry(5, 8, field(1, 8) + field(2, b"program_id"))
             + entry(4, 1, field(1, 1) + field(2, b"%fusion.2 = f32[8]")
                     + stat(8, u=99)
                     + stat(7, s="jit(f)/glm.irls_iter/gram.blocks/mul:"))
             + entry(4, 2, field(1, 2) + field(2, b"%copy.1 = f32[8]")
                     + stat(8, u=99)))
    raw = field(1, field(2, b"/host:CPU")) + field(1, plane)
    assert ptm.metadata_scopes(raw) == {
        (99, "%fusion.2 = f32[8]"): ("glm.irls_iter", "gram.blocks")}


def test_load_xplane_keeps_the_program_spans_on_the_harness_clock(tmp_path):
    """A trace recorded here (CPU: no device plane): the program's spans
    come through as ``h2o3.*`` events on the clock ``trace_reduce`` reads
    the harness's on, nested as opened."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu import telemetry
    spans = bench_run.Spans()
    jax.profiler.start_trace(str(tmp_path))
    with spans("job"):
        with telemetry.span("job"):
            with telemetry.span("glm.solve", lam=0.0):
                jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = bench_run.find_trace(str(tmp_path))
    (outer,) = tr.load_xplane(path).host_spans("job")
    pt = ptm.load_xplane(path)
    assert [s[0] for s in pt.spans] == ["job", "glm.solve"] and not pt.ops
    (_, js, je), (_, ss, se) = pt.spans
    assert outer.start_ns <= js <= ss <= se <= je <= outer.end_ns


# ---- the readers on a chip run's program trace ----------------------------

def fixture_reading(**over):
    with gzip.open(os.path.join(FIXTURES, f"{CELL}.program.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    pt = ptm.ProgramTrace.from_table(rec["program"])
    trace = tr.Trace.from_table(
        rec["harness"] + [[o.plane, o.line, o.name, o.start_ns, o.dur_ns,
                           o.module] for o in pt.ops])
    (win,) = trace.host_spans("window")
    kw = dict(cell={"name": CELL}, trace=trace, tr=tr, jobs=rec["jobs"],
              window_ns=(win.start_ns, win.end_ns), program_trace=pt,
              share_pct=bench_run.share_pct)
    kw.update(over)
    return bench_run.Reading(**kw), rec["expect"]


def test_readers_on_the_recorded_program_trace():
    reading, expect = fixture_reading()
    for name in TRACE_METRICS:
        assert reader(name).read(reading) == \
            pytest.approx(expect[name], rel=1e-9), name
    assert expect["glm_iterations_per_job"] == 4.0
    assert 0 <= expect["idle_unattributed_pct"] < 10
    assert 0 < expect["gram_slice_share_pct"] <= 100


def test_busy_and_the_idle_metrics_are_the_window():
    reading, _ = fixture_reading()
    lo, hi = reading.window_ns
    jobs = len(reading.jobs)
    busy_ms = tr.busy_seconds(reading.trace, lo, hi) * 1e3
    idle_ms = sum(reader(n).read(reading) for n in IDLE_METRICS) * jobs
    outside = tr.subtract([(lo, hi)], tr.merge(
        (e.start_ns, e.end_ns) for e in reading.trace.host_spans("job")))
    assert tr.total(outside) / 1e6 < 1.0      # jobs fill the window
    assert busy_ms + idle_ms == pytest.approx(
        (hi - lo - tr.total(outside)) / 1e6, rel=1e-6)
    # and the whole window, span by span
    acc = ptm.idle_by_span(reading)
    assert busy_ms + sum(acc.values()) / 1e6 == \
        pytest.approx((hi - lo) / 1e6, rel=1e-9)
    assert {"fit.admit", "glm.design", "glm.response", "glm.lambda_path",
            "glm.solve", "glm.readback", "glm.metrics", "fit.account",
            "job.finish"} <= set(acc)


def test_the_solve_programs_time_lies_in_named_scopes():
    reading, _ = fixture_reading()
    by = ptm.device_by_scope(reading.program_trace, SOLVE,
                             *reading.window_ns)
    whole = sum(by.values())
    assert {"gram.blocks", "gram.accumulate", "glm.reweight",
            "glm.line_search", "glm.newton_solve"} <= set(by)
    assert by.get(ptm.UNSCOPED, 0.0) <= 0.10 * whole


def test_without_a_raw_trace_the_readers_read_nothing():
    reading, _ = fixture_reading(program_trace=None,
                                 cell={"name": "no-such-cell"})
    for name in TRACE_METRICS:
        assert reader(name).read(reading) is None, name


def test_without_program_spans_or_scopes_the_readers_read_nothing():
    """The parent program: a trace with device work and no ``h2o3.*``
    event, no scope."""
    reading, _ = fixture_reading()
    reading.program_trace = ptm.ProgramTrace(
        [], [ptm.Op(o.plane, o.line, o.name, o.start_ns, o.dur_ns, o.module)
             for o in reading.program_trace.ops])
    for name in TRACE_METRICS:
        assert reader(name).read(reading) is None, name


def test_a_share_goes_through_share_pct():
    def refuse(part, whole, what):
        raise bench_run.BenchError(f"{what} reads over 105%")

    reading, _ = fixture_reading(share_pct=refuse)
    for name in ("idle_unattributed_pct", "gram_slice_share_pct"):
        with pytest.raises(bench_run.BenchError, match="105"):
            reader(name).read(reading)
    with pytest.raises(bench_run.BenchError, match="105"):
        bench_run.share_pct(106.0, 100.0, "x")
