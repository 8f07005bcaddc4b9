"""The cell ``gbm-airlines-116m-d6.fit-4chip``: its four readers on a
synthetic four-plane trace, its plain reference against ``gbm.py``'s,
and ``run.py --rehearse`` on four CPU devices as it stands and with a
fault planted where a chip run could not see it otherwise: a level's
histogram NOT all-reduced, so each shard splits on its own rows.

Run as a script it makes one such run (``--fault shard_local`` or none)
and prints ``run.py``'s result line: the tests run it in a child process
that has four CPU devices.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:                       # run as a script
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import program_trace as pt  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

CELL = "gbm-airlines-116m-d6.fit-4chip"
READERS = ("mesh_psum_share_pct", "mesh_step_mfu",
           "mesh_hist_kernel_roofline", "mesh_local_skew_pct")
MS = 1_000_000                                  # ns


def loaded_cell():
    return bench_run.load_cell(bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json"), "BENCHMARK.json"), CELL)


def reader(name):
    return bench_run.load_module("layer_metrics", name, "per-layer metric")


# ---- a synthetic trace ------------------------------------------------------

def plane_ops(plane, local_ms, psum_ms, scoped=True):
    """One job on one chip: the boosting scan's ``while`` holding the
    histogram kernel, a fusion and the level's all-reduce, then a
    re-scoring op; ``local_ms`` of the scan's work outside the psum."""
    scan, t = "jit__boost_scan_jit", 0.0
    hist, fusion = local_ms * 0.75, local_ms * 0.25
    rel = (pt.RELATIVE,) if scoped else ()
    body = [("tree_hist.3", hist, ("tree.hist",)),
            ("fusion.7", fusion, ("tree.split_scan",)),
            ("all-reduce.2", psum_ms, rel + ("tree.psum",) if scoped else ())]
    total = sum(d for _, d, _ in body)
    ops = [pt.Op(plane, tr.OPS_LINE, "while.1", 0.0, total * MS, scan, ())]
    for name, dur, scope in body:
        ops.append(pt.Op(plane, tr.OPS_LINE, name, t * MS, dur * MS, scan,
                         scope))
        t += dur
    ops.append(pt.Op(plane, tr.OPS_LINE, "fusion.9", t * MS, 10 * MS,
                     "jit_predict_forest", ("forest.level",)))
    return ops


def synthetic(local=(80, 80, 80, 90), psum=10, shards=4, scoped=True,
              fit_s=0.2):
    planes = {f"/device:TPU:{i}": plane_ops(f"/device:TPU:{i}", ms, psum,
                                            scoped)
              for i, ms in enumerate(local)}
    events = [tr.Event(o.plane, o.line, o.name, o.start_ns, o.dur_ns,
                       o.module) for ops in planes.values() for o in ops]
    events.append(tr.Event("/host:CPU", "harness", "bench.window", 0.0,
                           fit_s * 1e9))
    loaded = loaded_cell()
    shapes = dict(loaded["config"]["shapes"], ntrees=2,
                  rows=loaded["config"]["rows"])
    spans = [{"trees": 2, "data_shards": shards,
              "rows_per_shard": 117_440_512 // shards,
              "psum_bytes": 969_216 if shards > 1 else 0}] if scoped else []
    return bench_run.Reading(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], shapes=shapes,
        jobs=[{"start": 0.0, "end": fit_s}],
        trace=tr.Trace(events), window_ns=(0.0, fit_s * 1e9), tr=tr,
        peaks=bench_run.peaks_for("TPU v5 lite"),
        share_pct=bench_run.share_pct, end_to_end={"fit_s": fit_s},
        t_window=0.0, mesh_planes=planes, chunk_spans=spans)


def test_the_psum_share_is_its_time_over_busy_time():
    r = synthetic()
    busy = (0.1 + 0.1 + 0.1 + 0.11) / 4         # a plane: scan + rescore
    share = 100 * 0.010 / busy
    assert reader("mesh_psum_share_pct").read(r) == pytest.approx(share)


def test_the_skew_is_the_spread_of_the_chips_local_work():
    r = synthetic(local=(80, 80, 80, 90))
    assert reader("mesh_local_skew_pct").read(r) == pytest.approx(
        100 * 10 / 82.5)
    assert reader("mesh_local_skew_pct").read(synthetic(
        local=(80, 80, 80, 80))) == pytest.approx(0.0)


def test_on_one_shard_the_mesh_shares_are_the_one_chip_shares():
    r = synthetic(local=(80,), shards=1)
    assert reader("mesh_step_mfu").read(r) == pytest.approx(
        reader("step_mfu").read(r))
    assert reader("mesh_hist_kernel_roofline").read(r) == pytest.approx(
        reader("hist_kernel_roofline").read(r))


def test_on_four_shards_the_mesh_shares_count_a_chips_rows():
    four, one = synthetic(shards=4), synthetic(shards=1)
    assert reader("mesh_step_mfu").read(four) == pytest.approx(
        reader("step_mfu").read(four) / 4)
    # a plane's kernel time is the same in both: a chip's quarter of the
    # rows in the same time is a quarter of the share
    assert reader("mesh_hist_kernel_roofline").read(four) == pytest.approx(
        reader("mesh_hist_kernel_roofline").read(one) / 4)


def test_a_program_without_the_scopes_and_spans_gives_nothing():
    r = synthetic(scoped=False)
    for name in READERS:
        assert reader(name).read(r) is None, name


def test_the_readers_list_the_new_cell_alone():
    b = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"), "b")
    for m in b["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
        if m["name"] in ("step_mfu", "hist_kernel_roofline"):
            assert CELL not in m["workloads"]


# ---- the plain reference ------------------------------------------------------

def test_the_rows_reference_is_gbm_pys_to_the_digit():
    """``gbm_rows`` holds the rows narrow; every number it compares is
    the one ``gbm.check`` computes from the same job."""
    import h2o3_tpu
    from benchmark.adapters import gbm as adapter
    from benchmark.generators import airlines
    from benchmark.references import gbm, gbm_rows
    from h2o3_tpu.models.gbm import GBMEstimator
    config = loaded_cell()["config"]
    params = dict(config["reference_params"], ntrees=2)
    h2o3_tpu.init()
    data = airlines.generate(2 ** 31 + 39, config["rehearse_rows"])
    frame = h2o3_tpu.Frame.from_numpy(data["columns"],
                                      domains=data["domains"])
    model = GBMEstimator(ntrees=2, max_depth=6, seed=39).train(
        frame, y=data["response"])
    outputs = adapter.read_outputs(model)
    h2o3_tpu.DKV.remove(model.key)
    h2o3_tpu.DKV.remove(frame.key)
    assert gbm_rows.check(data, outputs, params) == \
        gbm.check(data, outputs, params)
    assert gbm_rows.NAMES == gbm.NAMES and gbm_rows.control is gbm.control


# ---- run.py on four CPU devices -------------------------------------------

def rehearse_on_four(fault=None, seed=3900000201):
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--seed", str(seed)]
        + (["--fault", fault] if fault else []),
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    return line


def test_the_sound_rehearsal_on_four_devices_is_correct():
    line = rehearse_on_four()
    assert line["correct"] is True, line["checks"]


def test_a_shard_local_histogram_is_not_correct():
    line = rehearse_on_four("shard_local")
    assert line["correct"] is False, line["checks"]


def plant_shard_local_histograms():
    """Every level histogram (``[L, F, B, 3]``: the CPU takes the XLA
    level path) stays with its shard: the psum over ``data`` hands the
    local sums back. The leaf sums and the row-state scalars are still
    reduced."""
    import jax
    psum = jax.lax.psum

    def local_histograms(x, axis_name, **kw):
        if getattr(x, "ndim", 0) == 4:
            return x
        return psum(x, axis_name, **kw)

    jax.lax.psum = local_histograms


def main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", required=True)
    ap.add_argument("--fault", choices=("shard_local",), default=None)
    a = ap.parse_args(argv)
    if a.fault == "shard_local":
        plant_shard_local_histograms()
    return bench_run.main(["--workload", CELL, "--seed", a.seed,
                           "--seconds", "1", "--trace", "0", "--rehearse"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
