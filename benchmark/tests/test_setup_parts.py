"""The ``setup_*`` readers (``layer_metrics/setup_parts.py``): in a
rehearsal every one that lists the cell reads a number the others are
consistent with; on a recorded fixture, where no process lives, none
reads anything."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.layer_metrics import setup_parts
from benchmark.tests.test_run import rehearse
from benchmark.tests.test_trace_reduce import fixture_reading

ROOT = bench_run.ROOT
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"), "b")
NEW = [m for m in BENCH["per_layer"]
       if m["moves"] == "setup_s" and m["source"] == "program_counter"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_the_entries_are_as_the_issue_lists_them():
    assert [m["name"] for m in NEW] == [
        "setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
        "setup_programs_compiled", "setup_frame_encode_s",
        "setup_bin_host_s", "setup_rollups_s", "setup_unattributed_pct"]
    assert BENCH["per_layer"][-len(NEW):] == NEW        # appended
    lists = {m["name"]: m["workloads"] for m in NEW}
    trees = ["gbm-airlines-d6.fit-48m", "drf-airlines-d20.fit-48m"]
    assert lists.pop("setup_bin_host_s") == trees
    assert lists.pop("setup_rollups_s") == [c for c in CELLS
                                            if c not in trees]
    assert all(w == CELLS for w in lists.values())


@pytest.mark.parametrize("cell", CELLS)
def test_a_rehearsal_reads_every_part_and_they_fit_set_up(cell):
    p = rehearse(cell, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()
             if x.startswith("{")]
    got = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    mine = [m for m in NEW if cell in m["workloads"]]
    assert {m["name"] for m in mine} <= set(got)
    # set-up is at least its parts as the harness timed them
    setup_s = sum(lines[0]["setup_parts_s"][k]
                  for k in ("init", "frame", "warmup"))
    parts = [m["name"] for m in mine if m["unit"] == "s"]
    assert all(0 <= got[n] for n in parts), got
    assert sum(got[n] for n in parts) <= setup_s, (got, setup_s)
    assert got["setup_trace_lower_s"] > 0
    assert got["setup_compile_s"] + got["setup_cache_load_s"] > 0
    assert got["setup_programs_compiled"] >= 0
    assert got["setup_frame_encode_s"] > 0
    assert -5 <= got["setup_unattributed_pct"] <= 100, got


@pytest.mark.parametrize("cell", ["glm-higgs.fit-11m",
                                  "gbm-airlines-d6.fit-48m"])
def test_a_recorded_fixture_gives_the_readers_nothing(cell):
    _, reading, _ = fixture_reading(cell)
    assert not hasattr(reading, "t_window")
    for m in NEW:
        reader = bench_run.load_module("layer_metrics", m["name"], "m")
        assert reader.read(reading) is None, m["name"]


def test_a_program_without_the_counters_gives_the_readers_nothing(
        monkeypatch):
    """The parent commit: a live process, no such counter."""
    from h2o3_tpu import telemetry
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "counters": [], "gauges": [], "histograms": []})
    reading = bench_run.Reading(
        t_window=0.0, end_to_end={"setup_s": 9.0, "fit_s": 1.0},
        setup_seconds={"setup_data": 1.0})
    for m in NEW:
        reader = bench_run.load_module("layer_metrics", m["name"], "m")
        assert reader.read(reading) is None, m["name"]


def test_the_references_programs_are_taken_off_again(monkeypatch):
    """Stage seconds counted after the window opened (the plain
    reference compiles in this process) are not set-up's."""
    from h2o3_tpu import telemetry
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"counters": [
        {"name": "h2o3tpu_xla_stage_seconds_total",
         "labels": {"stage": "compile"}, "value": 5.0},
        {"name": "h2o3tpu_xla_stage_seconds_total",
         "labels": {"stage": "trace"}, "value": 2.0},
        {"name": "h2o3tpu_xla_programs_total",
         "labels": {"source": "compile"}, "value": 7.0}],
        "gauges": [], "histograms": []})
    events = [
        {"ts_ms": 99_000, "event": "xla_compile", "own_s": 3.5},
        {"ts_ms": 101_000, "event": "xla_trace", "own_s": 0.5},
        {"ts_ms": 102_000, "event": "xla_compile", "own_s": 1.5}]
    monkeypatch.setattr(telemetry, "compiles_snapshot", lambda n: events)
    reading = bench_run.Reading(t_window=100.0)
    assert setup_parts.stage_seconds(reading, "compile") == 3.5
    assert setup_parts.stage_seconds(reading, "trace", "lower") == 1.5
    assert setup_parts.programs_compiled(reading) == 6.0
    # a ring that no longer reaches back to the window's start
    monkeypatch.setattr(telemetry, "compiles_snapshot",
                        lambda n: events[1:])
    assert setup_parts.stage_seconds(reading, "compile") is None
