"""The default random forest against the benchmark's plain float64
reference (``benchmark/references/drf.py``), at a size a CPU test holds.

The configuration ``benchmark/configs/drf-airlines-d20.json`` states
H2O's defaults — depth 20, ``mtries`` 3 of 10, bag 0.632, ``min_rows`` 1
— and "trees grow to the stated depth". Here, on 4,000 generated rows:
the forest equals the reference split for split and leaf for leaf (bag
and column draws replayed from the seed), a forest stopped short of its
depth — what ``models/drf.py`` did before PR 35 — and one whose column draw is
ignored come out not ``correct`` by the configuration's own limits, and
so does the reference's bfloat16 control; the fit's spans say what ran.
"""

import json
import os

import jax
import numpy as np
import pytest

import h2o3_tpu
from benchmark.adapters import drf as drf_adapter
from benchmark.generators import airlines
from benchmark.references import drf as drf_reference
from h2o3_tpu import telemetry
from h2o3_tpu.models import tree as tree_mod
from h2o3_tpu.models.drf import DRFEstimator

pytestmark = pytest.mark.allow_key_leak     # module-scoped frame below

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "drf-airlines-d20.json")) as f:
    CONFIG = json.load(f)
ROWS, NTREES, SEED = 4_000, 2, 3500000017
PARAMS = dict(CONFIG["reference_params"], ntrees=NTREES)
LIMITS = CONFIG["limits"]


def failed(numbers):
    return sorted(k for k, v in numbers.items()
                  if not k.startswith("_") and not v <= LIMITS[k])


@pytest.fixture(scope="module")
def data():
    return airlines.generate(SEED, ROWS)


@pytest.fixture(scope="module")
def frame(data):
    return h2o3_tpu.Frame.from_numpy(data["columns"],
                                     domains=data["domains"])


def fit(frame, data, **over):
    params = dict(CONFIG["estimator"]["params"], ntrees=NTREES,
                  seed=SEED % (2 ** 31 - 1))
    params.update(over)
    before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
    model = DRFEstimator(**params).train(frame, y=data["response"])
    spans = {}
    for s in telemetry.spans_snapshot(1 << 20):
        if s["name"].startswith("drf.") and s["id"] not in before:
            spans.setdefault(s["name"], []).append(s["meta"])
    return model, spans


@pytest.fixture(scope="module")
def sound(frame, data):
    model, spans = fit(frame, data)
    outputs = drf_adapter.read_outputs(model)
    return model, spans, outputs, drf_reference.check(data, outputs, PARAMS)


@pytest.mark.parametrize("number", drf_reference.NAMES)
def test_the_default_forest_is_the_references(sound, number):
    """Split for split and leaf for leaf: sums of 0/1 responses under
    0/1 weights are exact in float32, so every gap is rounding of the
    gain's and the leaf's own arithmetic."""
    numbers = sound[3]
    assert numbers[number] <= LIMITS[number]
    assert numbers[number] <= {"gain_gap": 1e-9, "leaf_gap": 1e-6,
                               "oob_logloss_gap": 1e-6,
                               "oob_auc_gap": 1e-3}.get(number, 0.0)


def test_the_counted_facts_are_the_programs(sound):
    model, spans, outputs, numbers = sound
    chunk = spans["drf.chunk"][-1]
    assert numbers["_depth_reached"] == chunk["depth_reached"] \
        == model.output["depth_reached"] > tree_mod.FRONTIER_FROM
    assert numbers["_leaves"] == chunk["leaves"] == sum(
        int((~t["is_split"]).sum()) for t in outputs["trees"].values())
    assert chunk["levels_frontier"] == 20 - tree_mod.FRONTIER_FROM
    assert chunk["levels_xla"] == tree_mod.FRONTIER_FROM
    assert chunk["levels_kernel"] == 0              # the CPU runs XLA


def test_the_fits_spans_say_what_ran(sound, frame, data):
    _, first, _, _ = sound
    assert first["drf.init"][-1]["on_device"] is True
    assert first["drf.init"][-1]["host_bytes"] == 0
    _, warm = fit(frame, data)
    assert warm["drf.bin"][-1]["cache"] == "hit"
    assert {"drf.bin", "drf.init", "drf.chunk", "drf.oob",
            "drf.metrics"} <= set(warm)


def test_a_forest_stopped_short_is_not_correct(frame, data):
    """What every default forest was before PR 35, at level 14; 4,000
    rows are pure by then, so the cut is planted at level 10 here and at
    14 on the chip (benchmark/tests/test_drf_cell.py)."""
    model, _ = fit(frame, data, max_depth=10)
    numbers = drf_reference.check(data, drf_adapter.read_outputs(model),
                                  PARAMS)
    assert numbers["depth_gap"] >= 1 and numbers["gain_gap"] > 0.5
    assert {"depth_gap", "gain_gap"} <= set(failed(numbers))


def test_an_ignored_column_draw_is_not_correct(frame, data, monkeypatch):
    monkeypatch.setattr(
        tree_mod, "_mtries_mask",
        lambda key, heap, F, mtries: jax.numpy.ones((heap.shape[0], F),
                                                    bool))
    jax.clear_caches()                  # the forest program is traced anew
    try:
        model, _ = fit(frame, data)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    numbers = drf_reference.check(data, drf_adapter.read_outputs(model),
                                  PARAMS)
    assert numbers["mtries_gap"] > 0 and "mtries_gap" in failed(numbers)


@pytest.mark.parametrize("seed", [5, 6])
def test_the_bfloat16_control_is_not_correct(seed):
    """The reference's own forest with every sum held in bfloat16, put in
    the program's place (20,000 rows: sums past 256 round)."""
    data = airlines.generate(seed, 20_000)
    params = dict(PARAMS, seed=seed)
    numbers = drf_reference.check(data, drf_reference.control(data, params),
                                  params)
    assert failed(numbers)


def test_the_replayed_bag_is_the_programs(sound, data):
    """A leaf's rows are the in-bag rows the replay puts there: the bag
    and the routing both have to be the program's."""
    model, _, outputs, numbers = sound
    assert numbers["leaf_rows_gap"] == 0.0
    (kb, _), _ = drf_reference.replay_keys(outputs["seed"], NTREES)
    bag = drf_reference.bag_of(kb, 0.632, outputs["rows_padded"], ROWS)
    t0 = outputs["trees"]["t0"]
    assert t0["leaf_rows"][~t0["is_split"]].sum() == bag.sum()
    assert 0.61 < bag.mean() < 0.65
