"""The airlines GBM data-parallel, as the cell ``gbm-airlines-116m-d6.
fit-4chip`` runs it on four chips, at a test's size on the CPU mesh.

The same frame is fit on a ``data`` = 1 and a ``data`` = 4 mesh of the
forced CPU devices (the session's mesh is put back after each):

(a) both fits pass the plain reference (``benchmark/references/gbm.py``)
    within the configuration's limits;
(b) the four-shard fit grows the same split feature and bin at every
    node as the one-shard fit, and leaves within rounding of it;
(c) every program the four-shard job compiled (its HLO, as XLA compiled
    it) moves nothing row-sized between devices: all-reduces only, none
    larger than the largest level histogram, and no value with the
    global row count as a dimension (every row-sized value is a shard);
(d) ``gbm.chunk`` says how the job was split: ``data_shards``,
    ``rows_per_shard`` and ``psum_bytes`` — the count from the shapes,
    which is also what the boosting program's all-reduces move;
(e) a compiled chunk is sized by a chip's rows: at the cell's 116M rows,
    one chunk of the two trees on four chips, not two chunks of one.
"""

import contextlib
import json
import os
import re

import jax
import jax._src.compiler as jax_compiler
import numpy as np
import pytest

import h2o3_tpu
from benchmark.adapters import gbm as gbm_adapter
from benchmark.generators import airlines
from benchmark.references import gbm as gbm_reference
from h2o3_tpu import telemetry
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.tree import TreeParams, trees_per_chunk
from h2o3_tpu.parallel import mesh as mesh_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "gbm-airlines-116m-d6.json")) as f:
    CONFIG = json.load(f)
ROWS, NTREES, SEED = 20_000, 2, 3900000039       # the cell's 2-tree job
PARAMS = dict(CONFIG["reference_params"], ntrees=NTREES)
SHARDS = [1, 4]
COLLECTIVE = re.compile(
    r"=\s+(\([^=]*?\)|\S+)\s+(all-reduce|all-gather|all-to-all|"
    r"collective-permute|reduce-scatter)(?:-start)?\(")
ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
JOB_PROGRAMS = {"jit__row_state_program", "jit__bin_device",
                "jit__boost_scan_jit", "jit_predict_forest",
                "jit__binomial_pass"}


def _bytes(shape: str) -> int:
    """Bytes of an HLO result type: ``f32[16,10,126,3]{...}`` or a tuple."""
    size = {"f32": 4, "s32": 4, "u32": 4, "f16": 2, "bf16": 2, "s8": 1,
            "u8": 1, "pred": 1}
    return sum(size[dt] * int(np.prod([int(d) for d in dims.split(",")
                                       if d] or [1]))
               for dt, dims in ARRAY.findall(shape))


@contextlib.contextmanager
def data_mesh(d: int):
    """A ``data`` = d mesh over the first d forced CPU devices for the
    length of the block; the session's mesh is put back."""
    old = mesh_mod.get_mesh()
    mesh_mod.set_global_mesh(mesh_mod.make_mesh(jax.devices()[:d], d, 1))
    try:
        yield
    finally:
        mesh_mod.set_global_mesh(old)


@contextlib.contextmanager
def captured_hlo():
    """The optimized HLO of every program XLA compiles (or loads from the
    persistent cache) in the block: ``[(name, text)]``."""
    seen = []
    compile_or_get_cached = jax_compiler.compile_or_get_cached

    def recording(*args, **kwargs):
        executable = compile_or_get_cached(*args, **kwargs)
        seen.extend((m.name, m.to_string())
                    for m in executable.hlo_modules())
        return executable

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax_compiler, "compile_or_get_cached", recording)
        yield seen


FITS = {}


def fitted(d: int) -> dict:
    """The generated frame fit on a ``data`` = d mesh (once a process):
    the adapter's outputs, the ``gbm.chunk`` spans, the HLO the job
    compiled, the padded rows, the bins and features."""
    if d not in FITS:
        data = airlines.generate(SEED, ROWS)
        with data_mesh(d), captured_hlo() as hlo:
            frame = h2o3_tpu.Frame.from_numpy(data["columns"],
                                              domains=data["domains"])
            before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
            model = GBMEstimator(**dict(
                CONFIG["estimator"]["params"], ntrees=NTREES,
                seed=SEED % (2 ** 31 - 1))).train(frame, y=data["response"])
            FITS[d] = {
                "shards": d, "data": data,
                "outputs": gbm_adapter.read_outputs(model),
                "chunks": [s["meta"]
                           for s in telemetry.spans_snapshot(1 << 20)
                           if s["name"] == "gbm.chunk"
                           and s["id"] not in before],
                "hlo": list(hlo), "npad": frame.nrows_padded,
                "B": model.bm.nbins_total, "F": model.bm.bins.shape[1]}
            h2o3_tpu.DKV.remove(model.key)
            h2o3_tpu.DKV.remove(frame.key)
    return FITS[d]


@pytest.fixture(params=SHARDS, ids=[f"data{d}" for d in SHARDS])
def fit(request):
    yield fitted(request.param)
    assert mesh_mod.get_mesh().shape[mesh_mod.DATA_AXIS] == 8


def _level_histogram_bytes(f, depth=6) -> list:
    """Bytes of each level's all-reduced histogram: the root, then the
    left children, 3 float32 statistics a (feature, bin)."""
    return [4 * 3 * f["F"] * f["B"] * max(2 ** (d - 1), 1)
            for d in range(depth)]


# ---- (a) the plain reference --------------------------------------------

def test_the_fit_is_correct_by_the_configurations_limits(fit):
    numbers = gbm_reference.check(fit["data"], fit["outputs"], PARAMS)
    over = {k: (v, CONFIG["limits"][k]) for k, v in numbers.items()
            if not v <= CONFIG["limits"][k]}
    assert not over, f"data={fit['shards']} over its limits: {over}"


# ---- (b) node by node ------------------------------------------------------

def test_four_shards_grow_the_one_shard_trees():
    a, b = fitted(1)["outputs"], fitted(4)["outputs"]
    for k in ("feat", "is_split", "cat_split", "value", "left_set"):
        np.testing.assert_array_equal(b[k], a[k], err_msg=k)
    np.testing.assert_allclose(b["leaf"], a["leaf"], rtol=1e-6, atol=1e-9)
    np.testing.assert_array_equal(b["leaf_rows"], a["leaf_rows"])
    assert abs(b["f0"] - a["f0"]) <= 1e-6 * abs(a["f0"])


# ---- (c) nothing row-sized between devices -------------------------------

def test_the_jobs_programs_move_nothing_row_sized(fit):
    """On one shard a psum is an all-reduce over one device (the CPU
    compiler keeps it, ``replica_groups={{0}}``; it moves nothing)."""
    d = fit["shards"]
    if d > 1:
        assert JOB_PROGRAMS <= {name for name, _ in fit["hlo"]}
    largest = max(_level_histogram_bytes(fit))
    global_rows = re.compile(r"\[(?:[\d,]*,)?%d(?:,[\d,]*)?\]"
                             % fit["npad"])
    for name, text in fit["hlo"]:
        for m in COLLECTIVE.finditer(text):
            shape, op = m.groups()
            assert op == "all-reduce", (name, op, shape)
            assert _bytes(shape) <= largest, (name, shape)
            one = "replica_groups={{0}}" in text[m.end():m.end() + 300]
            assert one == (d == 1), (name, shape)
        if d > 1:
            assert not global_rows.search(text), (
                f"{name} holds a value of all {fit['npad']} rows on a chip")


# ---- (d) the chunk's account of the split ---------------------------------

def test_the_chunk_span_says_how_the_job_was_split(fit):
    (chunk,) = fit["chunks"]
    d = fit["shards"]
    assert chunk["trees"] == NTREES
    assert chunk["data_shards"] == d
    assert chunk["rows_per_shard"] == fit["npad"] // d
    leaf_sums = 4 * 3 * 2 ** 6
    count = NTREES * (sum(_level_histogram_bytes(fit)) + leaf_sums)
    assert chunk["psum_bytes"] == (count if d > 1 else 0)
    if d > 1:
        (scan,) = [t for n, t in fit["hlo"] if n == "jit__boost_scan_jit"]
        moved = sum(_bytes(s) for s, _ in COLLECTIVE.findall(scan))
        assert chunk["psum_bytes"] == NTREES * moved


# ---- (e) a chunk sized by a chip's rows -----------------------------------

@pytest.mark.parametrize("d, trees", [(1, 1), (4, 4)])
def test_a_chunk_is_sized_by_the_rows_of_a_shard(d, trees):
    tp = TreeParams(max_depth=6, nbins_total=126)
    with data_mesh(d):
        rows = mesh_mod.padded_rows(CONFIG["rows"])
        assert rows == 117_440_512
        assert trees_per_chunk(tp, rows, capped=False) == trees
