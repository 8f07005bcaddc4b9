"""``BENCHMARK.json`` against the limits of the builder's contract that
can be checked without a run."""

import json
import os
import re

from benchmark import run as bench_run
from benchmark.tests import waiting_cells

ROOT = bench_run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_to_the_contract():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    b = json.load(open(path))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p) and
                                              ".." not in p
                                              for p in b["paths"])
    assert len(b["command"]) <= 32 and all(line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # the full check has to fit: 2 + 14 x 24 runs of run_seconds + 60
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names) <= 24
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert c["file"] not in files and PATH.match(c["file"])
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        held = json.load(open(os.path.join(ROOT, c["file"])))
        assert held["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])

    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == \
        len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert line(w["why"])
    assert {w["config"] for w in b["workloads"]} == set(names)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        max(1, len(cells) // 4)

    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert 1 <= len(b["per_layer"]) <= 128
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert line(m["layer"])
        assert set(m.get("workloads", cells)) <= set(cells)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # every cell reports setup_s, another end-to-end and a per-layer metric
    for c in cells:
        mine = [m for m in b["per_layer"]
                if c in m.get("workloads", cells)]
        assert mine and len(e2e) >= 2


def test_files_under_paths_are_named_from_name_characters():
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert PATH.match(rel), rel


def test_every_file_a_cell_names_exists():
    b = waiting_cells.merged(
        json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))
    assert len({w["name"] for w in b["workloads"]}) == len(b["workloads"])
    for w in b["workloads"]:
        loaded = bench_run.load_cell(b, w["name"])
        cfg, traffic = loaded["config"], loaded["traffic"]
        for folder, name in (("loops", traffic["kind"]),
                             ("generators", cfg["generator"]["name"]),
                             ("adapters", cfg["adapter"]),
                             ("references", cfg["reference"]),
                             ("rooflines", cfg["job_roofline"])):
            assert bench_run.load_module(folder, name, folder)
        for m in loaded["per_layer"]:
            assert bench_run.load_module("layer_metrics", m["name"], "m")
        ref = bench_run.load_module("references", cfg["reference"], "r")
        assert set(ref.NAMES) | {"jobs_differ"} == set(cfg["limits"])
