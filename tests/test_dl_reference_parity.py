"""The DeepLearning fit against the benchmark's plain reference
(``benchmark/references/dl.py``: a float32 replay of every mini-batch
ADADELTA step), as far as a CPU can hold it.

The cell ``dl-mnist8m-200x200.fit-1m`` decides on the chip whether the
program's bfloat16-operand products stay inside the configuration's
limits. Here, where every product is float32, at 4,096 rows x 64 of the
generator's pixel columns, ``[32, 32]``, ten classes, three epochs:

(a) the final weights, the scored losses and the steps that took effect
    are the replay's within float32 rounding, for three seeds;
(b) so are they at a row count the batch does not divide, where the
    epoch's last slice is clamped to the padded frame's tail on one
    device and runs past it on a mesh of several;
(c) a constant column (the generator's outer ring) standardises to
    zeros, not NaNs, on both sides;
(d) the replay one precision down (weights and ADADELTA state kept in
    bfloat16) does NOT pass the same tolerances;
(e) ``model.predict`` on the fixed block gives the reference forward
    pass's probabilities for the reference's weights — the cell itself
    applies the job's weights through the reference's forward;
(f) a fit leaves the six ``deeplearning.*`` phase spans with the
    attributes the benchmark's readers take.
"""

import numpy as np
import pytest

import h2o3_tpu
from benchmark.adapters import dl as dl_adapter
from benchmark.generators import mnist_like
from benchmark.references import dl as dl_reference
from h2o3_tpu import telemetry
from h2o3_tpu.models.deeplearning import DeepLearningEstimator
from h2o3_tpu.parallel.mesh import get_mesh

ROWS, INPUTS, HIDDEN, EPOCHS = 4096, 64, [32, 32], 3
SEEDS = (3300000011, 3300000012, 3300000013)
# 64 neighbouring pixel columns from the image's left edge inwards:
# C365 (row 13, column 0) lies on the always-zero ring
COLUMNS = mnist_like.NAMES[364:364 + INPUTS]
# float32 rounding through 48 steps, rows sharded over the test mesh's
# eight devices (the gradient's sum is taken in another order)
TOLERANCE = {"loss_gap": 1e-5, "weight_gap": 1e-5, "logloss_gap": 1e-5,
             "error_gap": 5e-4, "steps_gap": 0}
PHASES = ("design", "response", "init", "chunk", "score", "metrics")


def generated(seed, rows):
    d = mnist_like.generate(seed, rows)
    cols = {n: d["columns"][n] for n in COLUMNS}
    cols[d["response"]] = d["columns"][d["response"]]
    return {"columns": cols, "domains": d["domains"],
            "response": d["response"]}


def fit(seed, rows, **params):
    """One fit through ``train()``: the data, the reference's parameters
    for it, what the benchmark's adapter reads, the fit's spans and the
    model's own predictions on the frame."""
    data = generated(seed, rows)
    frame = h2o3_tpu.Frame.from_numpy(data["columns"],
                                      domains=data["domains"])
    t0 = telemetry.spans_snapshot(last=1)
    t0 = t0[-1]["start_ms"] if t0 else 0
    job_seed = seed % (2 ** 31 - 1)
    model = DeepLearningEstimator(hidden=HIDDEN, epochs=EPOCHS,
                                  seed=job_seed, **params).train(
        frame, y=data["response"])
    spans = [s for s in telemetry.spans_snapshot(last=1 << 12)
             if s["name"].startswith("deeplearning.")
             and s["start_ms"] >= t0]
    pred = model.predict(frame).to_pandas()
    out = {"data": data, "outputs": dl_adapter.read_outputs(model),
           "spans": spans, "pred": pred, "seed": job_seed,
           "params": {"hidden": HIDDEN, "epochs": EPOCHS, "rho": 0.99,
                      "epsilon": 1e-8, "mini_batch_size": 1,
                      "padded_rows": frame.nrows_padded}}
    h2o3_tpu.DKV.remove(model.key)
    h2o3_tpu.DKV.remove(frame.key)
    return out


@pytest.fixture(scope="module")
def fits():
    return {seed: fit(seed, ROWS) for seed in SEEDS}


def within(numbers, tolerance=TOLERANCE):
    return {k: v for k, v in numbers.items()
            if not k.startswith("_") and not v <= tolerance[k]}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fit_is_the_replay(fits, seed):
    f = fits[seed]
    numbers = dl_reference.check(f["data"], f["outputs"], f["params"])
    assert not within(numbers), numbers
    # 4,096 rows give a batch of 256: 16 steps an epoch, 48 a fit, all
    # of them effective, scored once at the end
    assert numbers["_steps"] == 48 and numbers["_batch"] == 256
    assert f["outputs"]["steps"] == 48
    assert f["outputs"]["score_steps"] == [48]
    assert numbers["_weights"] == 64 * 32 + 32 + 32 * 32 + 32 + 32 * 10 + 10


def test_the_epochs_last_slice_at_a_row_count_the_batch_does_not_divide():
    """4,040 rows in a 4,096-row padded frame, batch 128: ``(t · 128)
    mod 4040`` reads 4,024 at step 63, past ``4096 - 128``. On ONE
    device ``dynamic_slice`` clamps the start, and both sides then read
    the frame's last 128 rows, padding (weight 0) included. On a mesh of
    several devices the row-sharded slice is not clamped: it reads on
    past the frame's end as rows of weight 0 (the replay with the clamp
    pushed out of reach) — the same seed gives another model on another
    mesh wherever the batch does not divide the rows (PERF.md section
    7). The cell's 1,048,576 rows are 64 whole batches."""
    f = fit(SEEDS[0], 4040)
    assert f["params"]["padded_rows"] == 4096
    starts = [(t * 128) % 4040 for t in range(94)]
    assert sum(s > 4096 - 128 for s in starts) == 1
    clamped = dl_reference.check(f["data"], f["outputs"], f["params"])
    assert clamped["_batch"] == 128 and clamped["_steps"] == 94
    unclamped = dl_reference.check(f["data"], f["outputs"],
                                   dict(f["params"], padded_rows=1 << 20))
    one_device = get_mesh().shape["data"] == 1
    mine, other = (clamped, unclamped) if one_device \
        else (unclamped, clamped)
    assert not within(mine), mine
    assert "weight_gap" in within(other), other


def test_a_constant_column_standardises_to_zeros(fits):
    f = fits[SEEDS[0]]
    X, _ = dl_reference.matrix(f["data"])
    ring = COLUMNS.index("C365")
    assert X[:, ring].max() == 0 and X[:, ring + 1].max() > 0
    mean, sd = dl_reference.standardise(X)
    assert mean[ring] == 0 and sd[ring] == 1
    assert all(np.isfinite(w).all()
               for w in f["outputs"]["weights"].values())
    # no gradient ever reaches the column's weights but through ADADELTA's
    # epsilon: they stay where the initialiser put them
    init = dl_reference.initial_weights(f["seed"], [INPUTS] + HIDDEN + [10])
    np.testing.assert_array_equal(f["outputs"]["weights"]["0"][ring],
                                  np.asarray(init[0]["W"])[ring])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_lower_precision_control_is_not_the_replay(fits, seed):
    f = fits[seed]
    params = dict(f["params"], seed=f["seed"])
    numbers = dl_reference.check(
        f["data"], dl_reference.control(f["data"], params), params)
    assert "weight_gap" in within(numbers), numbers
    assert numbers["weight_gap"] > 100 * TOLERANCE["weight_gap"]


def test_predict_is_the_reference_forward_pass(fits):
    f = fits[SEEDS[1]]
    import jax
    with jax.default_matmul_precision("highest"):
        rp = dl_reference.Replay(f["data"], f["params"])
        theta = dl_reference.weights_of(f["outputs"])
        xb = (np.asarray(rp.d["X"][:ROWS], np.float32)
              - np.asarray(rp.d["mean"])) / np.asarray(rp.d["sd"])
        p = np.asarray(jax.nn.softmax(dl_reference.forward(theta, xb),
                                      axis=1))
    got = f["pred"][[f"p{k}" for k in range(10)]].to_numpy()
    np.testing.assert_allclose(got, p, atol=2e-6)
    assert (f["pred"]["predict"].to_numpy().astype(int)
            == p.argmax(axis=1)).mean() > 0.999


def test_a_fit_leaves_its_phase_spans(fits):
    spans = fits[SEEDS[2]]["spans"]
    by = {}
    for s in spans:
        by.setdefault(s["name"].split(".", 1)[1], []).append(s["meta"])
    assert set(PHASES) | {"fit"} <= set(by)
    for once in ("design", "response", "init", "metrics", "fit"):
        assert len(by[once]) == 1, once
    # every column's statistics went up as ONE [INPUTS, 2] float32
    # array, and nothing else did
    design = by["design"][0]
    assert (design["columns"], design["host_arrays"],
            design["host_bytes"]) == (INPUTS, 1, INPUTS * 2 * 4)
    resp = by["response"][0]
    # the label's codes and NA mask fetched, the NA weight and the
    # padded int32 codes sent: int32 + bool + float32 + int32 a row
    assert resp["on_device"] is False and resp["host_bytes"] == ROWS * 13
    (chunk,) = by["chunk"]
    assert chunk["steps"] == 48 and chunk["steps_run"] == 200
    assert chunk["batch"] == 256 and chunk["bf16"] is False
    assert [s["step"] for s in by["score"]] == [48]
