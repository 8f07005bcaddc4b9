"""Benchmark suite: the five BASELINE.json configs, one JSON line each.

    python bench.py            # all five configs under the time budget
    python bench.py gbm        # one config by substring
    python bench.py --one gbm  # run one config in-process (child mode)
    python bench.py --probe    # backend liveness probe (child mode)
    H2O3TPU_BENCH_FAST=1       # scaled-down shapes (CI smoke)
    H2O3TPU_BENCH_BUDGET_S=N   # wallclock budget (default 1500s)
    H2O3TPU_BENCH_FULL=1       # force the 50M-row GBM escalation
    H2O3TPU_BENCH_CONFIG_TIMEOUT_S=N  # per-config hard cap override
    H2O3TPU_BENCH_TRACE_DIR=DIR       # Chrome-trace artifacts per config
                                      # (default /tmp/h2o3tpu_bench_traces)

Structure (round-3 contract): the flagship GBM line is emitted FIRST at
a scale that finishes in minutes; every other config is bounded; the
50M-row GBM escalation runs LAST and only if the remaining budget
allows.

Fault tolerance (round-5 lesson — BENCH_r05 banked ZERO lines when the
first device_put hit a wedged TPU worker and the in-place retry hit the
corpse again until the budget went to -22s): the parent process never
touches the backend. Each config runs in a FRESH CHILD process with a
hard per-config timeout, preceded by a backend liveness probe
(core/watchdog.py probe, itself a subprocess) under the shared
bounded-backoff retry policy. A wedged worker therefore costs one
config line, not the scoreboard, and the budget is clamped at zero.

Configs (BASELINE.json):
  1. gbm      GBM binomial 100 trees depth 6, airlines schema 5M rows
              (+50M escalation when budget allows), ingest included.
  2. glm      GLM binomial IRLS + L-BFGS, HIGGS-shape 11M x 28.
  3. dl       DeepLearning MLP [200,200] rectifier, MNIST shape — the one
              config with a PUBLISHED reference number (80K samples/sec
              single node, hex/deeplearning/README.md:26-34).
  4. xgb      XGBoost-facade hist trees, airlines schema 5M rows.
  5. automl   H2OAutoML max_models=20 wallclock, airlines 500K rows,
              bounded by max_runtime_secs.

vs_baseline: config 3 compares against the published 80K samples/sec.
The others carry ESTIMATED single-node JVM numbers (the reference
publishes none in-tree — BASELINE.md): GBM 1.0e6 rows/sec·tree, GLM
1.0e7 row-iters/sec, XGBoost 2.0e6 rows/sec·tree, AutoML est. 300s
wallclock for the same config. Estimates are marked in the output.
"""

import json
import os
import sys
import time

import numpy as np

FAST = os.environ.get("H2O3TPU_BENCH_FAST") == "1"
# stub mode (tests): tiny stdlib-only configs exercise the parent
# harness — subprocess isolation, timeouts, probes, budget clamping —
# without booting a backend (tests/test_bench_harness.py)
STUB = os.environ.get("H2O3TPU_BENCH_STUB") == "1"
BUDGET_S = float(os.environ.get("H2O3TPU_BENCH_BUDGET_S", "1500"))
_T0 = time.time()

# infra-class error signatures: transient failures of the chip's
# runtime, NOT user errors (mirrors watchdog.INFRA_SIGNS — kept inline
# so the parent can classify a child's stderr without importing
# anything heavy)
_INFRA_SIGNS = ("INTERNAL", "UNAVAILABLE", "DEADLINE_EXCEEDED",
                "RESOURCE_EXHAUSTED: Attempting")


def _remaining() -> float:
    """Wallclock budget left, clamped at zero: a config that overruns
    its estimate must not drive the recorded budget negative (the
    round-5 scoreboard showed -22s)."""
    return max(0.0, BUDGET_S - (time.time() - _T0))


# ---------------------------------------------------------------- helpers


_EMITTED = []    # every metric line, re-printed at exit (tail-proof)


def _emit_raw(line):
    _EMITTED.append(line)
    print(json.dumps(line), flush=True)


def _emit(metric, value, unit, vs_baseline, baseline_kind, **extra):
    line = {"metric": metric, "value": round(value, 1), "unit": unit,
            "vs_baseline": round(vs_baseline, 3),
            "baseline": baseline_kind}
    line.update(extra)
    _emit_raw(line)


def _print_summary():
    # tail-proof summary: the driver captures only the END of stdout, and
    # round 3 lost its flagship GBM/GLM/DL lines to scroll-off — re-print
    # every metric line as the very last output so the tail always has
    # all of them (VERDICT r3 weak #9). Registered via atexit so a
    # driver SIGTERM/exception mid-config still flushes what exists.
    if _EMITTED:
        print("# ---- summary: all metric lines (re-printed, tail-proof) "
              "----", flush=True)
        for line in _EMITTED:
            print(json.dumps(line), flush=True)
        _EMITTED.clear()


def _airlines_csv(n_rows: int) -> str:
    """Write (once) an airlines-schema CSV of n_rows to /tmp; returns path.

    Real on-disk data so the bench includes the ingest path (streaming
    CSV → HBM)."""
    path = f"/tmp/h2o3tpu_airlines_{n_rows}.csv"
    if os.path.exists(path):
        return path
    from h2o3_tpu.utils.synth import write_airlines_csv
    t0 = time.time()
    write_airlines_csv(path, n_rows, seed=7)
    print(f"# wrote {path} ({os.path.getsize(path)/1e9:.2f} GB) "
          f"in {time.time()-t0:.0f}s", file=sys.stderr)
    return path


def _tree_mfu_pct(rows_per_sec_tree: float, depth: int, n_features: int,
                  n_bins: int = 65) -> float:
    """MFU of the histogram matmuls (the tree FLOPs that touch the MXU):
    per row per tree, levels 0..depth-1 contract [3L,C]x[C,F*B] with
    L=2^level nodes -> 2 * 3*(2^depth - 1) * F*B flops (ops/histogram.py
    _block_hist), against the v5e bf16 peak 197 TFLOP/s."""
    flops_per_row_tree = 2 * 3 * (2 ** depth - 1) * n_features * n_bins
    return 100 * rows_per_sec_tree * flops_per_row_tree / 197e12


def _hbm_peak():
    import jax
    try:
        s = jax.devices()[0].memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0) or 0)
    except Exception:
        return 0


def _compile_count() -> int:
    """Process-wide XLA backend compiles so far (telemetry registry).

    Emitted per config as a DELTA over the timed run: a warmed run
    should report compiles_timed=0 — anything else means the timed
    number includes compiler wall time, the exact failure mode the
    telemetry subsystem exists to expose."""
    from h2o3_tpu import telemetry
    return int(telemetry.REGISTRY.value("xla_compile_total"))


def _roofline_fields(algo):
    """Hardware-relative axis per config (telemetry/roofline.py): the
    last fit's MFU and HBM-bandwidth utilization as FRACTIONS of the
    detected device peaks — BENCH rounds become comparable across
    backends, not just across rows/sec. Rides the step-profiler phase
    breakdown (telemetry/stepprof.py) along: every BENCH line says not
    just how fast but WHERE the step wall-clock went."""
    out = {}
    try:
        from h2o3_tpu.telemetry import roofline
        f = roofline.last_fit(algo)
        out.update({"mfu": round(f["mfu"], 6),
                    "hbm_util": round(f["hbm_util"], 6)})
    except Exception:   # noqa: BLE001 - accounting must never fail a config
        pass
    try:
        from h2o3_tpu.telemetry import stepprof
        ph = stepprof.last_fit_phases(algo)
        if ph.get("phases"):
            out["phases"] = ph["phases"]
            out["collective_share"] = ph.get("collective_share", 0.0)
    except Exception:   # noqa: BLE001
        pass
    return out


# ---------------------------------------------------------------- configs


def _gbm_at(n_rows: int, ntrees: int, depth: int, tag: str):
    from h2o3_tpu.core.kv import DKV
    from h2o3_tpu.io.stream import stream_import_csv
    from h2o3_tpu.models.gbm import GBMEstimator
    path = _airlines_csv(n_rows)
    # warm the transfer/dispatch machinery on a 2K-row slice so the
    # ingest number measures STREAMING rate, not one-time process setup
    # (first device_put etc. cost ~9s of pure init in a fresh process)
    wpath = "/tmp/h2o3tpu_ingest_warmup.csv"
    with open(path) as fsrc, open(wpath, "w") as fdst:
        for _ in range(2001):
            ln = fsrc.readline()
            if not ln:
                break
            fdst.write(ln)
    wfr = stream_import_csv(wpath)
    DKV.remove(wfr.key)
    t0 = time.time()
    fr = stream_import_csv(path)
    t_ingest = time.time() - t0
    # warmup: boosting runs as compiled scans over 25-tree chunks, so a
    # 25-tree train on the SAME frame compiles the exact program the
    # timed run reuses — no second full-scale train needed (the round-2
    # double-train blew the driver window)
    wm = GBMEstimator(ntrees=min(25, ntrees), max_depth=depth,
                      seed=1).train(fr, y="IsDepDelayed")
    DKV.remove(wm.key)
    del wm
    c0 = _compile_count()
    t1 = time.time()
    model = GBMEstimator(ntrees=ntrees, max_depth=depth, seed=1).train(
        fr, y="IsDepDelayed")
    t_train = time.time() - t1
    rows_per_sec = n_rows * ntrees / t_train
    _emit(
        f"GBM-{ntrees}trees-d{depth} airlines {n_rows/1e6:.0f}M rows "
        f"({tag}; streamed CSV ingest + train)",
        rows_per_sec, "rows/sec/chip",
        rows_per_sec / 1.0e6, "estimated JVM 1.0e6 rows/sec-tree",
        ingest_seconds=round(t_ingest, 1),
        ingest_mb_per_sec=round(os.path.getsize(path) / 1e6 / t_ingest, 1),
        train_seconds=round(t_train, 1),
        total_seconds=round(t_ingest + t_train, 1),
        auc=round(float(model.training_metrics["AUC"]), 4),
        mfu_pct=round(_tree_mfu_pct(rows_per_sec, depth, 10), 2),
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2),
        compiles_timed=_compile_count() - c0,
        compiles_total=_compile_count(),
        **_roofline_fields("gbm"))


def bench_gbm():
    """Flagship line, emitted FIRST and sized to finish in minutes."""
    n_rows = 1_000_000 if FAST else 5_000_000
    _gbm_at(n_rows, ntrees=100, depth=6, tag="flagship")


def bench_gbm_full():
    """North-star-scale escalation; runs LAST, only under budget."""
    n_rows = 5_000_000 if FAST else 50_000_000
    _gbm_at(n_rows, ntrees=100, depth=6, tag="north-star scale")


def bench_glm():
    import h2o3_tpu
    from h2o3_tpu.models.glm import GLMEstimator
    n = 1_000_000 if FAST else 11_000_000
    p = 28
    r = np.random.RandomState(3)
    X = r.randn(n, p).astype(np.float32)
    beta = r.randn(p) * 0.3
    yv = (r.rand(n) < 1 / (1 + np.exp(-(X @ beta)))).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(p)}
    cols["y"] = np.array(["b", "s"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    del X

    for solver, max_it in (("irlsm", 8), ("l_bfgs", 40)):
        est = GLMEstimator(family="binomial", solver=solver, lambda_=0.0,
                           max_iterations=max_it, standardize=True)
        est.train(fr, y="y")          # warmup/compile
        c0 = _compile_count()
        t0 = time.time()
        m = GLMEstimator(family="binomial", solver=solver, lambda_=0.0,
                         max_iterations=max_it,
                         standardize=True).train(fr, y="y")
        dt = time.time() - t0
        row_iters = n * max_it / dt
        # MFU: IRLSM is Gram-dominated (2*n*p^2 per iter, ops/gram.py);
        # L-BFGS is two matvec passes (4*n*p per iter). Both shapes are
        # HBM-bandwidth-bound at p=28, so these run low by design.
        flops_per_row_iter = 2 * p * p if solver == "irlsm" else 4 * p
        _emit(
            f"GLM binomial {solver.upper()} HIGGS-shape {n/1e6:.0f}Mx{p}",
            row_iters, "row-iters/sec/chip",
            row_iters / 1.0e7, "estimated JVM 1.0e7 row-iters/sec",
            train_seconds=round(dt, 2),
            mfu_pct=round(100 * row_iters * flops_per_row_iter / 197e12, 3),
            auc=round(float(m.training_metrics["AUC"]), 4),
            compiles_timed=_compile_count() - c0,
            peak_hbm_gb=round(_hbm_peak() / 1e9, 2),
            **_roofline_fields("glm"))


def bench_dl():
    import h2o3_tpu
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    n = 100_000 if FAST else 1_000_000
    d = 784                      # MNIST shape → published 80K/s baseline
    epochs = 2.0 if FAST else 8.0   # enough steps to amortize the
    #                                 per-chunk host sync
    r = np.random.RandomState(5)
    X = (r.rand(n, d) > 0.8).astype(np.float32)
    yv = r.randint(0, 10, n)
    cols = {f"p{i}": X[:, i] for i in range(d)}
    cols["label"] = yv.astype(str)
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["label"])
    del X, cols

    # warmup compiles the SAME programs the timed run uses (the fused
    # chunk is a fixed-size program with a traced step limit, so any
    # epoch count shares it)
    DeepLearningEstimator(hidden=[200, 200], activation="rectifier",
                          epochs=0.1, seed=1).train(fr, y="label")
    c0 = _compile_count()
    t0 = time.time()
    m = DeepLearningEstimator(hidden=[200, 200], activation="rectifier",
                              epochs=epochs, seed=1).train(fr, y="label")
    dt = time.time() - t0
    sps = n * epochs / dt
    # MFU: 6 flops per weight per sample (fwd 2 + bwd 4) over the three
    # dense layers, against the v5e bf16 peak (197 TFLOP/s)
    params = d * 200 + 200 * 200 + 200 * 10
    mfu = sps * 6 * params / 197e12
    # convergence proof rides the line: training classification error
    # must beat the 10-class prior (0.9) by a wide margin
    err = None
    for k in ("error_rate", "err", "mean_per_class_error"):
        try:
            err = round(float(m.training_metrics[k]), 4)
            break
        except Exception:
            continue
    _emit(
        f"DeepLearning [200,200] rectifier MNIST-shape {n/1e6:.1f}M",
        sps, "samples/sec/chip",
        sps / 80_000.0, "PUBLISHED 80K samples/sec 1-node "
        "(hex/deeplearning/README.md:26)",
        train_seconds=round(dt, 2), mfu_pct=round(100 * mfu, 2),
        train_err=err,
        compiles_timed=_compile_count() - c0,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2),
        **_roofline_fields("deeplearning"))


def bench_xgb():
    from h2o3_tpu.io.stream import stream_import_csv
    from h2o3_tpu.models.xgboost import XGBoostEstimator
    n_rows = 1_000_000 if FAST else 5_000_000
    ntrees = 50
    fr = stream_import_csv(_airlines_csv(n_rows))
    XGBoostEstimator(ntrees=5, max_depth=6, seed=1).train(
        fr, y="IsDepDelayed")
    c0 = _compile_count()
    t0 = time.time()
    m = XGBoostEstimator(ntrees=ntrees, max_depth=6, seed=1).train(
        fr, y="IsDepDelayed")
    dt = time.time() - t0
    rps = n_rows * ntrees / dt
    _emit(
        f"XGBoost-facade hist {ntrees}trees airlines {n_rows/1e6:.0f}M",
        rps, "rows/sec/chip",
        rps / 2.0e6, "estimated JVM xgboost-hist 2.0e6 rows/sec-tree",
        train_seconds=round(dt, 2),
        mfu_pct=round(_tree_mfu_pct(rps, 6, 10), 2),
        auc=round(float(m.training_metrics["AUC"]), 4),
        compiles_timed=_compile_count() - c0,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2),
        **_roofline_fields("xgboost"))


def bench_sort():
    """Device radix-order path: 10M-row two-key sort + single-key merge
    (water/rapids/RadixOrder + BinaryMerge roles)."""
    import h2o3_tpu
    from h2o3_tpu.ops.sort import device_sort
    from h2o3_tpu.rapids import _device_merge
    n = 1_000_000 if FAST else 10_000_000
    r = np.random.RandomState(11)
    fr = h2o3_tpu.Frame.from_numpy({
        "k": r.randint(0, n // 2, n).astype(float),
        "b": r.randn(n), "v": np.arange(n, dtype=float)})
    import jax.numpy as jnp
    w = device_sort(fr, ["k", "b"], [True, True])  # warmup/compile
    float(jnp.sum(w.col("k").data))   # force completion (scalar host fetch)
    for c in w.names:                 # drain every async column gather
        float(jnp.sum(w.col(c).data))
    t0 = time.time()
    out = device_sort(fr, ["k", "b"], [True, True])
    for c in out.names:
        float(jnp.sum(out.col(c).data))
    dt = time.time() - t0
    _emit(f"Sort 2-key {n/1e6:.0f}M rows (device radix-order)",
          n / dt, "rows/sec/chip",
          (n / dt) / 5.0e6, "estimated JVM RadixOrder 5.0e6 rows/sec",
          sort_seconds=round(dt, 2))
    rf = h2o3_tpu.Frame.from_numpy({
        "k": r.randint(0, n // 2, n // 4).astype(float),
        "rv": np.arange(n // 4, dtype=float)})
    _device_merge(fr, rf, "inner")                 # warmup/compile
    t0 = time.time()
    m = _device_merge(fr, rf, "inner")
    dt = time.time() - t0
    _emit(f"Merge inner {n/1e6:.0f}M x {n/4e6:.1f}M rows (device join)",
          n / dt, "rows/sec/chip",
          (n / dt) / 3.0e6, "estimated JVM BinaryMerge 3.0e6 rows/sec",
          merge_seconds=round(dt, 2), out_rows=m.nrows)


def bench_cloud():
    """Cloud control plane (ISSUE 7): shutdown → init reformation cost
    plus heartbeat agreement round-trip over the live mesh — the two
    latencies a multi-host pod pays at bootstrap and once per interval
    for the life of the cloud."""
    import h2o3_tpu
    from h2o3_tpu.core import heartbeat
    t0 = time.time()
    h2o3_tpu.shutdown()
    h2o3_tpu.init()
    boot_s = time.time() - t0
    heartbeat.monitor.start(interval_s=3600, thread=False)  # manual rounds
    assert heartbeat.monitor.round()          # warmup/compile
    reps = 50
    t0 = time.time()
    for _ in range(reps):
        assert heartbeat.monitor.round()
    rtt = (time.time() - t0) / reps
    heartbeat.monitor.stop()
    _emit("cloud bootstrap + heartbeat agreement round-trip",
          1.0 / rtt, "rounds/sec", 1.0,
          "H2O HeartBeatThread 1 round/sec/node",
          bootstrap_s=round(boot_s, 3),
          heartbeat_rtt_ms=round(rtt * 1e3, 3))


def bench_automl():
    from h2o3_tpu.automl import H2OAutoML
    from h2o3_tpu.io.stream import stream_import_csv
    n_rows = 200_000 if FAST else 500_000
    fr = stream_import_csv(_airlines_csv(n_rows))
    # hard wallclock bound: AutoML must never outlive the bench budget
    # (round 2's unbounded 20-model 3-fold run ate the driver window)
    cap = max(120.0, min(420.0, _remaining() - 120.0))
    t0 = time.time()
    aml = H2OAutoML(max_models=20, seed=1, nfolds=3, max_runtime_secs=cap)
    aml.train(y="IsDepDelayed", training_frame=fr)
    dt = time.time() - t0
    tab = aml.leaderboard.as_table()
    best_auc = None
    try:
        best_auc = round(float(tab[0].get("auc")), 4)
    except Exception:
        pass
    est_ref = 300.0   # estimated JVM wallclock, same 500K-row config
    planned = 20
    extra = {}
    if len(tab) < planned // 2:
        # LOUD shortfall flag (VERDICT r4 weak #10): a 3-of-20 run must
        # not hide inside a green rc=0
        extra["SHORTFALL"] = f"trained {len(tab)}/{planned} planned"
    _emit(
        f"AutoML max_models=20 airlines {n_rows/1e3:.0f}K wallclock",
        dt, "seconds",
        est_ref / dt, "estimated JVM 300s same config",
        n_models=len(tab), planned_models=planned, best_auc=best_auc,
        max_runtime_secs=round(cap, 0), **extra)


def bench_grid():
    """Model-batched grid search (parallel/model_batch.py): one
    numeric-only GBM shape bucket trained as a single vmapped program
    vs the sequential per-combo walk — models/sec, both paths."""
    import h2o3_tpu
    from h2o3_tpu.ml.grid import GridSearch
    from h2o3_tpu.models.gbm import GBMEstimator
    n = 100_000 if FAST else 500_000
    r = np.random.RandomState(9)
    X = r.randn(n, 6).astype(np.float32)
    yv = (X[:, 0] + 0.5 * X[:, 1] + 0.5 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(6)}
    cols["y"] = np.array(["N", "Y"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    hyper = {"learn_rate": [0.05, 0.08, 0.1, 0.15],
             "sample_rate": [0.7, 1.0],
             "min_rows": [5.0, 20.0]}            # 16 combos, ONE bucket
    n_combos = 4 * 2 * 2
    fixed = dict(ntrees=20, max_depth=6, seed=1)

    def _run(batch_mode):
        os.environ["H2O3TPU_BATCH_MODELS"] = batch_mode
        try:
            t0 = time.time()
            g = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
            return time.time() - t0, g
        finally:
            os.environ.pop("H2O3TPU_BATCH_MODELS", None)

    # warmup compiles both programs on a 2-combo slice
    wf = dict(fixed)
    whyper = {"learn_rate": [0.05, 0.1]}
    for mode in ("auto", "off"):
        os.environ["H2O3TPU_BATCH_MODELS"] = mode
        GridSearch(GBMEstimator, whyper, **wf).train(fr, y="y")
    os.environ.pop("H2O3TPU_BATCH_MODELS", None)
    c0 = _compile_count()
    t_bat, g_bat = _run("auto")
    compiles_bat = _compile_count() - c0
    t_seq, _ = _run("off")
    mps_bat = n_combos / t_bat
    mps_seq = n_combos / t_seq
    _emit(
        f"grid GBM {n_combos} combos {n/1e3:.0f}K rows "
        f"(model-batched vmap vs sequential walk)",
        mps_bat, "models/sec",
        mps_bat / mps_seq, "sequential per-combo walk, same config",
        batched_seconds=round(t_bat, 1),
        sequential_seconds=round(t_seq, 1),
        n_models=len(g_bat.models),
        compiles_timed=compiles_bat,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2))


def bench_sched():
    """Cluster work scheduler (ISSUE 15, parallel/scheduler.py): the
    same 16-combo GBM grid through the scheduled path — items planned,
    leased, models detached, lowered to device-independent bytes and
    reinstalled (the exact cross-host contract) — vs the
    coordinator-only walk. On the single-process bench cloud the
    scheduled run degrades to the inline executor, so this line prices
    the scheduling + serialization tax every distributed run pays; the
    model counts must match exactly (the bit-parity contract's cheap
    proxy here, asserted in full by the multiprocess tier-1 test)."""
    import h2o3_tpu
    from h2o3_tpu.core import config as _cfg
    from h2o3_tpu.ml.grid import GridSearch
    from h2o3_tpu.models.gbm import GBMEstimator
    from h2o3_tpu.parallel import scheduler
    n = 50_000 if FAST else 200_000
    r = np.random.RandomState(23)
    X = r.randn(n, 6).astype(np.float32)
    yv = (X[:, 0] - 0.5 * X[:, 2] + 0.5 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(6)}
    cols["y"] = np.array(["N", "Y"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    hyper = {"learn_rate": [0.05, 0.08, 0.1, 0.15],
             "sample_rate": [0.7, 1.0],
             "min_rows": [5.0, 20.0]}            # 16 combos
    n_combos = 4 * 2 * 2
    fixed = dict(ntrees=10, max_depth=5, seed=7)

    def _run(sched_mode):
        prev = _cfg.ARGS.scheduler
        _cfg.ARGS.scheduler = sched_mode
        try:
            t0 = time.time()
            g = GridSearch(GBMEstimator, hyper, **fixed).train(fr, y="y")
            return time.time() - t0, g
        finally:
            _cfg.ARGS.scheduler = prev

    # warmup compiles both paths on a 2-combo slice
    whyper = {"learn_rate": [0.05, 0.1]}
    prev = _cfg.ARGS.scheduler
    for m in ("on", "off"):
        _cfg.ARGS.scheduler = m
        try:
            GridSearch(GBMEstimator, whyper, **fixed).train(fr, y="y")
        finally:
            _cfg.ARGS.scheduler = prev
    s0 = scheduler.snapshot()
    t_on, g_on = _run("on")
    s1 = scheduler.snapshot()
    t_off, g_off = _run("off")
    assert len(g_on.models) == len(g_off.models) == n_combos
    assert s1["runs"] == s0["runs"] + 1, (s0, s1)
    mps_on = n_combos / t_on
    mps_off = n_combos / t_off
    _emit(
        f"sched GBM {n_combos} combos {n/1e3:.0f}K rows "
        f"(scheduled lease/detach/install path vs coordinator-only walk)",
        mps_on, "models/sec",
        mps_on / mps_off, "coordinator-only walk, same config",
        scheduled_seconds=round(t_on, 1),
        coordinator_seconds=round(t_off, 1),
        sched_items=s1["items_done"] - s0["items_done"],
        n_models=len(g_on.models),
        leases_held_now=scheduler.leases_held())


def bench_treekernel():
    """Kernel-level histogram+split+partition throughput
    (rows·features/sec), fused Pallas level pass vs the XLA composition
    on identical shapes — the ISSUE 6 microbench behind the flagship
    GBM number. Native Pallas on TPU; on other backends the kernels run
    through the interpreter at a token size (the line then measures the
    interpreter, and says so)."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.frame.binning import BinnedMatrix
    from h2o3_tpu.models.tree import TreeScalars
    from h2o3_tpu.ops.pallas import treekernel as tk
    from h2o3_tpu.parallel.mesh import (get_mesh, padded_rows,
                                        put_sharded, row_sharding)

    native = jax.default_backend() == "tpu"
    n = (1 << 23 if not FAST else 1 << 21) if native else 1 << 14
    F, B, L, d, block_rows = 10, 65, 8, 3, 4096
    n = padded_rows(n)
    r = np.random.RandomState(13)
    mesh = get_mesh()
    bm = BinnedMatrix(
        bins=put_sharded(jnp.asarray(r.randint(0, B, (n, F)).astype(np.int8)),
                         row_sharding()),
        nbins=jnp.full((F,), B - 1, jnp.int32),
        edges=jnp.zeros((F, B - 2), jnp.float32),
        is_cat=np.zeros((F,), bool), names=[f"x{i}" for i in range(F)],
        nbins_total=B, nrows=n, domains=[None] * F)
    tiles = bm.tile_view(block_rows)           # bin-major tile layout
    bins = tiles.bins
    nid = put_sharded(jnp.asarray(r.randint(0, L, n).astype(np.int32)),
                      row_sharding())
    w = jnp.asarray((r.rand(n) > 0.05).astype(np.float32))
    g = jnp.asarray(r.randn(n).astype(np.float32))
    h = jnp.asarray(r.rand(n).astype(np.float32))
    stats = jnp.stack([w, w * g, w * h]).astype(jnp.float32)
    # any nonneg prev histogram exercises the sibling-subtract path;
    # throughput does not care that it is synthetic
    prev = jnp.asarray(
        np.abs(r.randn(L // 2, F, B, 3)).astype(np.float32)) * 8.0
    cm = jnp.ones((F,), bool)
    nb = bm.nbins
    lo = jnp.full((1,), -jnp.inf, jnp.float32)
    hi = jnp.full((1,), jnp.inf, jnp.float32)
    sc = TreeScalars(jnp.float32(10.0), jnp.float32(1.0),
                     jnp.float32(1e-5), jnp.int32(30))
    kw = dict(d=d, n_nodes=L, n_bins=B, block_rows=block_rows, mesh=mesh)

    def run_pallas(bins, nid, stats, prev):
        out = tk.fused_level(bins, nid, stats, prev, cm, nb, None, None,
                             lo, hi, sc, interpret=not native, **kw)
        return out[1], out[-1]          # gains + routed ids force all

    def run_xla(bins, nid, prev):
        out = tk.xla_level(bins, nid, w, g, h, prev, cm, nb, None, None,
                           lo, hi, sc, **kw)
        return out[1], out[-1]

    jp = jax.jit(run_pallas)
    jx = jax.jit(run_xla)
    for f in jax.block_until_ready(jp(bins, nid, stats, prev)):
        pass                            # warmup/compile
    jax.block_until_ready(jx(bins, nid, prev))
    reps = 10 if native else 3
    c0 = _compile_count()
    t0 = time.time()
    for _ in range(reps):
        out = jp(bins, nid, stats, prev)
    jax.block_until_ready(out)
    t_pallas = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        out = jx(bins, nid, prev)
    jax.block_until_ready(out)
    t_xla = (time.time() - t0) / reps
    rate_p = n * F / t_pallas
    rate_x = n * F / t_xla
    _emit(
        f"treekernel fused hist+split+partition level d={d} "
        f"{n/1e6:.1f}M rows x {F}F x {B}B "
        f"({'native Pallas' if native else 'Pallas interpreter'})",
        rate_p, "rows-feat/sec/chip",
        rate_p / rate_x, "XLA histogram+scan+route, same shapes/mesh",
        xla_rows_feat_per_sec=round(rate_x, 1),
        pallas_level_ms=round(t_pallas * 1e3, 2),
        xla_level_ms=round(t_xla * 1e3, 2),
        tile_rows=tiles.rows, tiles=tiles.ntiles,
        mode="native" if native else "interpret",
        compiles_timed=_compile_count() - c0,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2))


def bench_checkpoint():
    """In-fit checkpoint overhead (ISSUE 9): the SAME GBM fit with and
    without FitCheckpointer snapshotting at the default 25-tree cadence
    — the overhead %% is the acceptance number (<= 2%% of fit wall time
    on the flagship config; core/recovery.py)."""
    import tempfile

    import h2o3_tpu
    from h2o3_tpu import telemetry
    from h2o3_tpu.core import recovery
    from h2o3_tpu.models.gbm import GBMEstimator
    n = 200_000 if FAST else 1_000_000
    r = np.random.RandomState(11)
    X = r.randn(n, 8).astype(np.float32)
    yv = (X[:, 0] + 0.5 * X[:, 1] + 0.5 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.array(["N", "Y"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    del X
    kw = dict(ntrees=100, max_depth=6, seed=1)
    wm = GBMEstimator(**{**kw, "ntrees": 25}).train(fr, y="y")  # warmup
    from h2o3_tpu.core.kv import DKV
    DKV.remove(wm.key)
    t0 = time.time()
    GBMEstimator(**kw).train(fr, y="y")
    t_plain = time.time() - t0
    d = tempfile.mkdtemp(prefix="h2o3tpu_bench_ckpt_")
    w0 = telemetry.REGISTRY.total("fit_checkpoints_written_total")
    with recovery.fit_checkpoint_scope(d):
        t0 = time.time()
        GBMEstimator(**kw).train(fr, y="y")
        t_ckpt = time.time() - t0
    writes = int(telemetry.REGISTRY.total("fit_checkpoints_written_total")
                 - w0)
    overhead_pct = 100.0 * (t_ckpt - t_plain) / max(t_plain, 1e-9)
    _emit(
        f"checkpoint GBM-100trees-d6 {n/1e3:.0f}K rows (in-fit "
        f"snapshotting every 25 trees vs none)",
        overhead_pct, "overhead_pct",
        t_plain / max(t_ckpt, 1e-9), "same fit without checkpointing",
        plain_seconds=round(t_plain, 2),
        checkpointed_seconds=round(t_ckpt, 2),
        snapshots_written=writes,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2))


def bench_memgov():
    """HBM governor overhead (ISSUE 11): the SAME GBM fit with an
    unlimited budget vs a tight ``H2O3TPU_HBM_BUDGET_MB`` that forces
    the admission path to spill cold frames before dispatch — the
    overhead %% plus the spill/restore counts are the scoreboard
    numbers (core/memgov.py)."""
    import h2o3_tpu
    from h2o3_tpu import telemetry
    from h2o3_tpu.core import memgov
    from h2o3_tpu.core.cleaner import _frame_nbytes
    from h2o3_tpu.core.kv import DKV
    from h2o3_tpu.models.gbm import GBMEstimator
    n = 100_000 if FAST else 500_000
    r = np.random.RandomState(13)
    X = r.randn(n, 8).astype(np.float32)
    yv = (X[:, 0] + 0.5 * X[:, 1] + 0.5 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.array(["N", "Y"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    # cold residents for the governor to spill ahead of the fit
    decoys = [h2o3_tpu.Frame.from_numpy(
        {f"d{i}": r.randn(n).astype(np.float32) for i in range(8)})
        for _ in range(3)]
    del X
    kw = dict(ntrees=50, max_depth=6, seed=1)
    feats = [f"x{i}" for i in range(8)]
    wm = GBMEstimator(**{**kw, "ntrees": 10}).train(fr, y="y")  # warmup
    DKV.remove(wm.key)
    t0 = time.time()
    GBMEstimator(**kw).train(fr, y="y")
    t_plain = time.time() - t0
    s0 = telemetry.REGISTRY.total("frame_spills_total")
    r0 = telemetry.REGISTRY.total("frame_restores_total")
    # budget sized so the fit admits only after ~half the decoy bytes
    # spill: resident + projected > budget > (resident - decoys) +
    # projected
    proj = memgov.estimate_fit_bytes("gbm", kw, fr, feats)
    decoy_bytes = sum(_frame_nbytes(d) for d in decoys)
    budget = memgov.governor.resident_bytes() - decoy_bytes // 2 + proj
    os.environ["H2O3TPU_HBM_BUDGET_MB"] = str(max(budget >> 20, 1))
    try:
        t0 = time.time()
        GBMEstimator(**kw).train(fr, y="y")
        t_tight = time.time() - t0
        DKV.get(decoys[0].key)      # touch a spilled decoy: restore
    finally:
        os.environ.pop("H2O3TPU_HBM_BUDGET_MB", None)
    spills = int(telemetry.REGISTRY.total("frame_spills_total") - s0)
    restores = int(telemetry.REGISTRY.total("frame_restores_total") - r0)
    overhead_pct = 100.0 * (t_tight - t_plain) / max(t_plain, 1e-9)
    _emit(
        f"memgov GBM-50trees-d6 {n/1e3:.0f}K rows (tight HBM budget "
        f"with admission spills vs unlimited)",
        overhead_pct, "overhead_pct",
        t_plain / max(t_tight, 1e-9), "same fit, unlimited budget",
        plain_seconds=round(t_plain, 2),
        tight_seconds=round(t_tight, 2),
        budget_mb=max(budget >> 20, 1),
        spills=spills, restores=restores,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2))


def bench_ingest():
    """Chunk-parallel ingest pipeline (ISSUE 12): airlines-CSV MB/s with
    the tokenizer fan-out vs the SAME pipeline pinned to one worker
    (bit-identical output by construction — tests/test_ingest_parallel
    asserts the bits, this config measures the ratio), plus the
    row-group-parallel Parquet fast path over the same rows."""
    from h2o3_tpu.core.kv import DKV
    from h2o3_tpu.io.chunking import resolve_workers
    from h2o3_tpu.io.formats import parse_parquet
    from h2o3_tpu.io.stream import stream_import_csv
    n = 1_000_000 if FAST else 10_000_000
    path = _airlines_csv(n)
    nbytes = os.path.getsize(path)

    def _run(workers):
        fr = stream_import_csv(path, workers=workers)
        rows = fr.nrows
        DKV.remove(fr.key)
        return rows

    _run(1)                                 # warmup/compile both legs
    t0 = time.time()
    rows = _run(1)
    t_seq = max(time.time() - t0, 1e-9)
    t0 = time.time()
    _run(None)
    t_par = max(time.time() - t0, 1e-9)
    w = resolve_workers()
    _emit(f"Ingest airlines CSV {n/1e6:.0f}M rows x{w} workers "
          f"(chunk-parallel tokenize + overlapped transfer)",
          nbytes / t_par / 1e6, "MB/sec",
          t_seq / t_par, "same pipeline, workers=1",
          seq_mb_per_s=round(nbytes / t_seq / 1e6, 1),
          workers=w, rows=rows, file_mb=round(nbytes / 1e6, 1),
          seq_seconds=round(t_seq, 2), par_seconds=round(t_par, 2))
    # Parquet leg: same rows through the arrow-columnar fast path (no
    # CSV tokenizer at all) — baseline is the sequential CSV wall time
    import pyarrow.csv as pacsv
    import pyarrow.parquet as pq
    ppath = path.rsplit(".", 1)[0] + ".parquet"
    if not os.path.exists(ppath):
        pq.write_table(pacsv.read_csv(path), ppath + ".tmp",
                       row_group_size=1 << 20)
        os.rename(ppath + ".tmp", ppath)
    pbytes = os.path.getsize(ppath)
    DKV.remove(parse_parquet(ppath).key)    # warmup
    t0 = time.time()
    fr = parse_parquet(ppath)
    t_pq = max(time.time() - t0, 1e-9)
    DKV.remove(fr.key)
    _emit(f"Ingest airlines Parquet {n/1e6:.0f}M rows "
          f"(row-group-parallel arrow fast path)",
          pbytes / t_pq / 1e6, "MB/sec",
          t_seq / t_pq, "same rows, sequential CSV",
          parquet_seconds=round(t_pq, 2),
          file_mb=round(pbytes / 1e6, 1), workers=w)


def bench_serving():
    """Low-latency scoring tier (ISSUE 14): row-payload predict QPS and
    tail latency through the continuous micro-batcher vs the SAME
    requests scored one at a time through ``Model.predict``. Outputs
    are bit-identical by construction — the serving engine dispatches
    the model's own compiled program (models/model.py _serve_jit) — so
    this config measures throughput/latency only, plus the compile
    observer's per-bucket miss counts (a compile storm here means the
    row buckets are broken)."""
    import threading

    import h2o3_tpu
    from h2o3_tpu import telemetry
    from h2o3_tpu.core.kv import DKV
    from h2o3_tpu.models.gbm import GBMEstimator
    from h2o3_tpu.serving.engine import engine
    from h2o3_tpu.serving.rows import parse_rows, serving_schema

    n = 20_000 if FAST else 100_000
    r = np.random.RandomState(14)
    X = r.randn(n, 8).astype(np.float32)
    yv = (X[:, 0] + 0.5 * X[:, 1] + 0.5 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.array(["N", "Y"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    model = GBMEstimator(ntrees=20, max_depth=5, seed=1).train(fr, y="y")

    n_clients = 16
    reqs_per_client = 25 if FAST else 50
    rows_per_req = 8
    feats = [f"x{i}" for i in range(8)]
    rr = np.random.RandomState(15)
    payloads = []
    for _ in range(n_clients * reqs_per_client):
        vals = rr.randn(rows_per_req, len(feats))
        payloads.append([
            {f: float(vals[i, j]) for j, f in enumerate(feats)}
            for i in range(rows_per_req)])

    # sequential baseline: what a naive per-request server does —
    # parse rows, build a frame, Model.predict, fetch (warmed first so
    # neither leg pays XLA compiles inside the timed window)
    schema = serving_schema(model)

    def _predict_once(rows):
        parsed = parse_rows(schema, rows)
        pf = h2o3_tpu.Frame.from_numpy(
            parsed, domains={nm: d for nm, d in schema if d is not None})
        DKV.remove(pf.key)
        try:
            out = model.predict(pf)
            DKV.remove(out.key)
        finally:
            pf.drop_device_caches()

    _predict_once(payloads[0])                   # warm the per-request shape
    engine.register(model)                       # warm the serving tier
    n_seq = min(len(payloads), 40 if FAST else 80)
    t0 = time.time()
    for rows in payloads[:n_seq]:
        _predict_once(rows)
    t_seq = max(time.time() - t0, 1e-9)
    qps_seq = n_seq / t_seq

    # concurrent leg: n_clients threads hammer engine.score_rows; the
    # micro-batcher coalesces whatever overlaps into one padded dispatch
    lat = []
    lat_lock = threading.Lock()
    errors = []

    def _client(cid):
        mine = payloads[cid * reqs_per_client:(cid + 1) * reqs_per_client]
        for rows in mine:
            t = time.time()
            try:
                engine.score_rows(model, rows)
            except BaseException as e:   # noqa: BLE001 - scoreboard, not crash
                errors.append(e)
                return
            with lat_lock:
                lat.append(time.time() - t)

    # untimed warm burst: compiles the coalesced row buckets so the
    # timed window measures steady-state serving, not first-compile
    warm_threads = [threading.Thread(
        target=lambda: engine.score_rows(model, payloads[0]))
        for _ in range(n_clients)]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join()
    lat.clear()

    d0 = engine._batchers[model.key].dispatches
    t0 = time.time()
    threads = [threading.Thread(target=_client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_conc = max(time.time() - t0, 1e-9)
    assert not errors, errors[0]
    qps = len(lat) / t_conc
    lat_ms = sorted(v * 1e3 for v in lat)
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]
    dispatches = engine._batchers[model.key].dispatches - d0

    # compile accounting: every serving compile must map to a distinct
    # row bucket — more misses than buckets means the cache is broken
    with telemetry.REGISTRY._lock:
        miss_sigs = [labels for (nm, _), m in
                     telemetry.REGISTRY._metrics.items()
                     for labels in [getattr(m, "labels", {})]
                     if nm.endswith("jit_cache_miss_total")
                     and labels.get("fn") == "serving.gbm" and m.value > 0]
    buckets = len(engine._scorers[model.key].buckets)
    assert len(miss_sigs) <= max(buckets, 1), (miss_sigs, buckets)

    _emit(f"serving GBM row-payload predict {n_clients} clients x "
          f"{reqs_per_client} reqs x {rows_per_req} rows "
          f"(continuous micro-batch vs sequential Model.predict)",
          qps, "requests/sec", qps / qps_seq,
          "same requests, sequential Model.predict",
          sequential_qps=round(qps_seq, 1),
          p50_ms=round(p50, 2), p99_ms=round(p99, 2),
          requests=len(lat), dispatches=dispatches,
          mean_batch_width=round(len(lat) / max(dispatches, 1), 2),
          row_buckets=buckets,
          serving_compiles=len(miss_sigs),
          scorer_cache_hits=int(telemetry.REGISTRY.total(
              "scorer_cache_hits_total")),
          scorer_cache_misses=int(telemetry.REGISTRY.total(
              "scorer_cache_misses_total")))


def bench_tracing():
    """Distributed-tracing overhead (ISSUE 16): the SAME GBM fit with
    and without a trace context installed (the REST ingress condition —
    every span additionally stamps/propagates the request's trace id;
    telemetry/trace_context.py). The overhead %% is the acceptance
    number (< 2%% of fit wall time)."""
    import h2o3_tpu
    from h2o3_tpu import telemetry
    from h2o3_tpu.core.kv import DKV
    from h2o3_tpu.models.gbm import GBMEstimator
    from h2o3_tpu.telemetry import trace_context
    n = 200_000 if FAST else 1_000_000
    r = np.random.RandomState(16)
    X = r.randn(n, 8).astype(np.float32)
    yv = (X[:, 0] + 0.5 * X[:, 1] + 0.5 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(8)}
    cols["y"] = np.array(["N", "Y"], object)[yv]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    del X
    kw = dict(ntrees=100, max_depth=6, seed=1)
    wm = GBMEstimator(**{**kw, "ntrees": 25}).train(fr, y="y")  # warmup
    DKV.remove(wm.key)
    t0 = time.time()
    GBMEstimator(**kw).train(fr, y="y")
    t_plain = time.time() - t0
    with trace_context.trace_scope(trace_context.new_context()), \
            telemetry.span("rest", route="/99/bench"):
        t0 = time.time()
        m = GBMEstimator(**kw).train(fr, y="y")
        t_traced = time.time() - t0
    # every span of the traced fit carries the request's trace id
    stamped = sum(1 for s in telemetry.spans_snapshot(2048)
                  if s.get("trace_id"))
    assert stamped > 0, "traced fit produced no trace-stamped spans"
    DKV.remove(m.key)
    overhead_pct = 100.0 * (t_traced - t_plain) / max(t_plain, 1e-9)
    assert overhead_pct < 2.0, \
        f"tracing overhead {overhead_pct:.2f}% >= 2% acceptance bound"
    _emit(
        f"tracing GBM-100trees-d6 {n/1e3:.0f}K rows (trace context "
        f"installed + ingress span vs bare fit)",
        overhead_pct, "overhead_pct",
        t_plain / max(t_traced, 1e-9), "same fit without tracing",
        plain_seconds=round(t_plain, 2),
        traced_seconds=round(t_traced, 2),
        trace_stamped_spans=stamped,
        peak_hbm_gb=round(_hbm_peak() / 1e9, 2))


_FLEET_WORKER_SRC = '''
"""bench fleet worker: one pod process (generated by bench.py)."""
import json, os, signal, sys, threading, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["H2O3TPU_HEARTBEAT_INTERVAL_S"] = "0.25"
os.environ["H2O3TPU_FLEET_LOAD_TTL_S"] = "0.2"
sys.path.insert(0, os.environ["H2O3TPU_BENCH_REPO"])
coord, nproc, pid, outfile = sys.argv[1:5]
nproc, pid = int(nproc), int(pid)
import jax
jax.config.update("jax_default_device", None)
import h2o3_tpu
h2o3_tpu.init(backend="cpu", coordinator_address=coord,
              num_processes=nproc, process_id=pid)
import numpy as np
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.serving import fleet

r = np.random.RandomState(31)
n = 1500
fr = h2o3_tpu.Frame.from_numpy(
    {"a": r.randn(n), "b": r.randn(n),
     "y": r.randn(n) + 0.5})
from h2o3_tpu.models.gbm import GBMEstimator
model = GBMEstimator(ntrees=3, max_depth=3, seed=9).train(fr, y="y")
MKEY = str(model.key)
ROWS = [{"a": float(i) * 0.1, "b": 1.0 - float(i) * 0.05}
        for i in range(8)]
from h2o3_tpu.api.server import start_server
port = start_server(port=0, background=True)
killflag = outfile + ".killflag"

# publish is an SPMD point on a live cloud (the lowering pickle
# allgathers cross-process sharded arrays): both processes call it here
fleet.publish(model)

if pid == 1:
    DKV.remove(MKEY)
    fleet.install_published(MKEY)
    while not os.path.exists(killflag):
        time.sleep(0.05)
    os.kill(os.getpid(), signal.SIGKILL)

DKV.remove(MKEY)
deadline = time.monotonic() + 60
while not (1 in fleet.replicas(MKEY) and 1 in fleet.endpoints()):
    if time.monotonic() > deadline:
        raise RuntimeError("replica never registered")
    time.sleep(0.05)

import urllib.request


def predict_once(timeout=20.0):
    req = urllib.request.Request(
        "http://127.0.0.1:%d/3/Predictions/models/%s" % (port, MKEY),
        data=json.dumps({"rows": ROWS}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    t = time.monotonic()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        out = json.loads(resp.read())
    return time.monotonic() - t, out["predictions"]["predict"]


def drive(n_req, clients):
    lats, preds, lock = [], [], threading.Lock()

    def one():
        lat, p = predict_once()
        with lock:
            lats.append(lat)
            preds.append(p)

    t0 = time.monotonic()
    for lo in range(0, n_req, clients):
        ts = [threading.Thread(target=one)
              for _ in range(min(clients, n_req - lo))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    wall = max(time.monotonic() - t0, 1e-9)
    lats.sort()
    return {"qps": len(lats) / wall,
            "p99_ms": lats[min(len(lats) - 1,
                               int(len(lats) * 0.99))] * 1e3,
            "pred": preds[0]}


n_req = int(os.environ.get("H2O3TPU_BENCH_FLEET_REQS", "30"))
predict_once()                                   # warm the route
routed = {c: drive(n_req, c) for c in (1, 4)}

with open(killflag, "w") as f:
    f.write("die")
t0 = time.monotonic()
recovery_s, pred_after = None, None
while time.monotonic() - t0 < 90:
    try:
        _lat, pred_after = predict_once()
        recovery_s = time.monotonic() - t0
        break
    except Exception:
        time.sleep(0.05)

local = {c: drive(n_req, c) for c in (1, 4)}

with open(outfile + ".0", "w") as f:
    json.dump({"routed": routed, "local": local,
               "recovery_s": recovery_s, "pred_after": pred_after,
               "installed": MKEY in fleet.stats()["local_replicas"]},
              f)
print("FLEET-BENCH-0-DONE", flush=True)
os._exit(0)
'''


def bench_fleet():
    """Fleet serving resilience (ISSUE 17, serving/fleet.py): a REAL
    2-process CPU cloud — one replica node, one routing-only node. The
    router node's REST edge answers row-payload predicts by proxying to
    the replica (routed leg), then the replica is SIGKILLed and the line
    prices the RECOVERY: hedged failover installs the published binary
    locally and the first successful answer stamps recovery_seconds.
    The local leg (post-recovery) is the single-node baseline — routed
    p99 carries one 127.0.0.1 HTTP hop over it, and the answers must
    match exactly (the bit-parity contract's cheap proxy here; asserted
    in full by tests/test_fleet.py)."""
    import socket
    import subprocess
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        worker = os.path.join(tmp, "fleet_bench_worker.py")
        with open(worker, "w") as f:
            f.write(_FLEET_WORKER_SRC)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        out = os.path.join(tmp, "fleet.json")
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["H2O3TPU_BENCH_REPO"] = os.path.dirname(
            os.path.abspath(__file__))
        env["H2O3TPU_BENCH_FLEET_REQS"] = "20" if FAST else "40"
        procs = [subprocess.Popen(
            [sys.executable, worker, coord, "2", str(i), out],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT) for i in range(2)]
        deadline = time.time() + 420
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.time(), 1.0))
            except subprocess.TimeoutExpired:
                for q in procs:
                    if q.poll() is None:
                        q.kill()
        assert procs[0].returncode == 0, "fleet driver process failed"
        with open(out + ".0") as f:
            res = json.load(f)

    assert res["recovery_s"] is not None, "never recovered from kill"
    assert res["installed"], "failover never installed the binary"
    # bit-parity proxy: routed, post-kill, and local answers identical
    assert (res["routed"][  "4"]["pred"] == res["local"]["4"]["pred"]
            == res["pred_after"])
    qps_r1, qps_r4 = res["routed"]["1"]["qps"], res["routed"]["4"]["qps"]
    qps_l4 = res["local"]["4"]["qps"]
    _emit(
        "fleet routed row-payload predict, 2-process cloud "
        "(proxy to replica; SIGKILL replica -> hedged local install)",
        qps_r4, "requests/sec",
        qps_r4 / max(qps_l4, 1e-9), "same predicts served locally "
        "(single node, post-recovery)",
        routed_qps_1client=round(qps_r1, 1),
        routed_qps_4clients=round(qps_r4, 1),
        client_scaling=round(qps_r4 / max(qps_r1, 1e-9), 2),
        routed_p99_ms=round(res["routed"]["4"]["p99_ms"], 2),
        local_p99_ms=round(res["local"]["4"]["p99_ms"], 2),
        local_qps_4clients=round(qps_l4, 1),
        kill_recovery_seconds=round(res["recovery_s"], 3))


_DUR_WORKER_SRC = '''
"""bench durability worker: one pod process (generated by bench.py)."""
import json, os, signal, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["H2O3TPU_HEARTBEAT_INTERVAL_S"] = "0.25"
os.environ["H2O3TPU_DATA_DURABILITY"] = "mirror"
os.environ["H2O3TPU_DUR_REBUILD_S"] = "0.05"
sys.path.insert(0, os.environ["H2O3TPU_BENCH_REPO"])
coord, nproc, pid, outfile = sys.argv[1:5]
nproc, pid = int(nproc), int(pid)
os.environ["H2O3TPU_DUR_DIR"] = outfile + ".mirror"
import jax
jax.config.update("jax_default_device", None)
import h2o3_tpu
h2o3_tpu.init(backend="cpu", coordinator_address=coord,
              num_processes=nproc, process_id=pid)
import numpy as np
from h2o3_tpu.core import durability
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.parallel import mesh as mesh_mod

killflag = outfile + ".killflag"
if pid == 1:
    # victim: mirror one frame, announce it, wait for the kill order
    with mesh_mod.local_mesh_scope():
        r = np.random.RandomState(7)
        n = 100_000
        fr = h2o3_tpu.Frame.from_numpy(
            {"a": r.randn(n), "b": r.randn(n), "y": r.randn(n)})
    assert fr.key in durability.stats()["mirrored"]
    with open(killflag + ".ready", "w") as f:
        f.write(fr.key)
    while not os.path.exists(killflag):
        time.sleep(0.02)
    os.kill(os.getpid(), signal.SIGKILL)

# pid 0: wait for the victim's mirrored frame, order the kill, and
# time kill -> frame re-homed locally (staleness detection included)
deadline = time.monotonic() + 60
while not os.path.exists(killflag + ".ready"):
    if time.monotonic() > deadline:
        raise RuntimeError("victim never mirrored its frame")
    time.sleep(0.02)
with open(killflag + ".ready") as f:
    fkey = f.read().strip()
nbytes = durability.registry(1)[fkey]["nbytes"]
with open(killflag, "w") as f:
    f.write("die")
t0 = time.monotonic()
rebuilt_s = None
while time.monotonic() - t0 < 90:
    durability.maybe_rebuild()
    if fkey in DKV:
        rebuilt_s = time.monotonic() - t0
        break
    time.sleep(0.02)
from h2o3_tpu import telemetry
with open(outfile + ".0", "w") as f:
    json.dump({"kill_to_rebuild_s": rebuilt_s,
               "rebuilds": telemetry.counter(
                   "frame_rebuilds_total", source="mirror").value,
               "mirror_nbytes": nbytes}, f)
print("DUR-BENCH-0-DONE", flush=True)
os._exit(0)
'''


def bench_durability():
    """Durable data plane (ISSUE 18, core/durability.py): write-through
    mirror overhead on ingest — ``durability=off`` is the zero-overhead
    default (hook sites gate on the raw env knob before importing
    anything) — plus kill-to-rebuild wall time on a REAL 2-process
    cloud: a peer mirrors a frame, is SIGKILLed, and the survivor's
    recovery supervisor re-homes the frame from its mirror."""
    import shutil
    import socket
    import subprocess
    import tempfile

    import h2o3_tpu
    from h2o3_tpu.core import durability
    from h2o3_tpu.core.kv import DKV
    n = 200_000 if FAST else 2_000_000
    r = np.random.RandomState(11)
    cols = {"a": r.randn(n), "b": r.randn(n), "y": r.randn(n)}
    nbytes = sum(v.nbytes for v in cols.values())

    def _ingest():
        fr = h2o3_tpu.Frame.from_numpy(cols)
        DKV.remove(fr.key)

    _ingest()                                # warmup/compile
    t0 = time.time()
    _ingest()
    t_off = max(time.time() - t0, 1e-9)
    dur_dir = tempfile.mkdtemp(prefix="h2o3tpu-bench-mirror-")
    os.environ["H2O3TPU_DATA_DURABILITY"] = "mirror"
    os.environ["H2O3TPU_DUR_DIR"] = dur_dir
    try:
        _ingest()                            # warmup the mirror path
        t0 = time.time()
        _ingest()
        t_mir = max(time.time() - t0, 1e-9)
    finally:
        os.environ.pop("H2O3TPU_DATA_DURABILITY", None)
        os.environ.pop("H2O3TPU_DUR_DIR", None)
        durability.reset()
        shutil.rmtree(dur_dir, ignore_errors=True)
    _emit(f"durability mirror write-through, {n/1e6:.1f}M-row ingest "
          "(blocks persisted + digested + registered per frame)",
          (t_mir / t_off - 1.0) * 100.0, "percent overhead",
          t_mir / t_off, "durability=off (zero-overhead default)",
          off_seconds=round(t_off, 3), mirror_seconds=round(t_mir, 3),
          frame_mb=round(nbytes / 1e6, 1))

    with tempfile.TemporaryDirectory() as tmp:
        worker = os.path.join(tmp, "dur_bench_worker.py")
        with open(worker, "w") as f:
            f.write(_DUR_WORKER_SRC)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        out = os.path.join(tmp, "dur.json")
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["H2O3TPU_BENCH_REPO"] = os.path.dirname(
            os.path.abspath(__file__))
        procs = [subprocess.Popen(
            [sys.executable, worker, coord, "2", str(i), out],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT) for i in range(2)]
        deadline = time.time() + 420
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.time(), 1.0))
            except subprocess.TimeoutExpired:
                for q in procs:
                    if q.poll() is None:
                        q.kill()
        assert procs[0].returncode == 0, "durability driver failed"
        with open(out + ".0") as f:
            res = json.load(f)

    assert res["kill_to_rebuild_s"] is not None, "never rebuilt"
    assert res["rebuilds"] >= 1, "rebuild not visible in telemetry"
    _emit("durability kill-to-rebuild, 2-process cloud (SIGKILL the "
          "frame's home; survivor re-homes it from the mirror)",
          res["kill_to_rebuild_s"], "seconds", 1.0,
          "includes heartbeat staleness detection",
          mirror_nbytes=res["mirror_nbytes"],
          rebuilds=res["rebuilds"])


def bench_globalfit():
    """Pod-global sharded training (ISSUE 19, H2O3TPU_GLOBAL_FIT): ONE
    GBM fit data-parallel across a REAL 2-process gloo cloud over a
    host-partitioned frame, vs the same fit on 1 host. On this 1-core
    container both processes timeshare one core, so a ratio below 1.0
    measures collective + timeshare overhead, not pod speedup — the
    scoreboard says so. Plus the SIGKILL-mid-fit leg: a peer dies
    inside the global boost loop and the survivor's job must FAIL
    fast, infra-classified, with no RUNNING job leak."""
    import socket
    import subprocess
    import tempfile

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "globalfit_worker.py")

    def _pod(mode, nproc, tmp, extra_env=None):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        out = os.path.join(tmp, f"{mode}_{nproc}.json")
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.update(extra_env or {})
        procs = [subprocess.Popen(
            [sys.executable, worker, coord, str(nproc), str(i), out, mode],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT) for i in range(nproc)]
        deadline = time.time() + 240
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.time(), 1.0))
            except subprocess.TimeoutExpired:
                for q in procs:
                    if q.poll() is None:
                        q.kill()
        if mode != "sigkill":
            assert all(p.returncode == 0 for p in procs), \
                f"globalfit {mode} pod failed"
        else:
            # pid 1 SIGKILLs itself by design, but the surviving pid 0
            # must exit cleanly — a crashed/killed survivor would make
            # any report on disk stale, not a valid result
            assert procs[0].returncode == 0, \
                "globalfit sigkill survivor (pid 0) did not exit cleanly"
        assert os.path.exists(out), \
            f"globalfit {mode} pod wrote no report (hung and killed?)"
        with open(out) as f:
            return json.load(f)

    def _host_phases(tmp, mode, nproc):
        """Per-pid step-profiler splits the workers dropped next to the
        report file — the WHY behind the rows/sec ratio (compute vs
        collective wait vs host, per host)."""
        out = {}
        base = os.path.join(tmp, f"{mode}_{nproc}.json")
        for i in range(nproc):
            try:
                with open(f"{base}.phases.{i}") as f:
                    out[str(i)] = json.load(f)
            except Exception:   # noqa: BLE001 - table is best-effort
                pass
        return out

    with tempfile.TemporaryDirectory() as tmp:
        one = _pod("bench", 1, tmp)
        two = _pod("bench", 2, tmp)
        host_phases = _host_phases(tmp, "bench", 2)
        ratio = two["rows_per_sec"] / max(one["rows_per_sec"], 1e-9)
        _emit("globalfit GBM rows/sec, 2-host gloo pod on a host-"
              "partitioned frame (1-core container: both hosts "
              "timeshare one core, so the ratio is overhead, not "
              "speedup)",
              two["rows_per_sec"], "rows/sec", ratio,
              "same fit on 1 host",
              one_host_rows_per_sec=round(one["rows_per_sec"], 1),
              ntrees=two["ntrees"], nrows=two["nrows"],
              host_phases=host_phases)
        # human-readable per-host phase table next to the rows/sec line
        if host_phases:
            print("# globalfit per-host phase breakdown "
                  "(seconds; telemetry/stepprof.py)", flush=True)
            print(f"# {'host':>4} {'compute':>9} {'collective':>11} "
                  f"{'hostprep':>9} {'checkpoint':>10} {'coll%':>6}",
                  flush=True)
            for h in sorted(host_phases):
                ph = host_phases[h].get("phases") or {}
                tot = sum(ph.values()) or 1.0
                print(f"# {h:>4} {ph.get('compute', 0.0):>9.3f} "
                      f"{ph.get('collective', 0.0):>11.3f} "
                      f"{ph.get('host', 0.0):>9.3f} "
                      f"{ph.get('checkpoint', 0.0):>10.3f} "
                      f"{100.0 * ph.get('collective', 0.0) / tot:>5.1f}%",
                      flush=True)

        kill = _pod("sigkill", 2, tmp,
                    {"H2O3TPU_HEARTBEAT_INTERVAL_S": "0.25",
                     "H2O3TPU_HEARTBEAT_MISS_BUDGET": "2"})
        assert kill["job_status"] == "FAILED", kill
        assert kill["infra_classified"], kill
        assert kill["running_leaks"] == [], kill
        _emit("globalfit SIGKILL-mid-fit, 2-host pod (peer dies inside "
              "the global boost loop; survivor's job fails fast, "
              "classified infra, no RUNNING job leak)",
              kill["fail_after_loss_s"], "seconds", 1.0,
              f"heartbeat window {kill['heartbeat_window_s']:.2f}s",
              job_status=kill["job_status"])


CONFIGS = [("gbm", bench_gbm), ("glm", bench_glm), ("dl", bench_dl),
           ("xgb", bench_xgb), ("sort", bench_sort),
           ("grid", bench_grid), ("treekernel", bench_treekernel),
           ("cloud", bench_cloud), ("checkpoint", bench_checkpoint),
           ("memgov", bench_memgov), ("ingest", bench_ingest),
           ("serving", bench_serving), ("sched", bench_sched),
           ("tracing", bench_tracing), ("fleet", bench_fleet),
           ("durability", bench_durability),
           ("globalfit", bench_globalfit),
           ("automl", bench_automl), ("gbm-full", bench_gbm_full)]

# minimum seconds a config plausibly needs; skipped (with a JSON note)
# rather than started when the remaining budget is below it
_MIN_NEED = {"gbm": 60, "glm": 90, "dl": 60, "xgb": 60, "sort": 60,
             "grid": 120, "treekernel": 60, "cloud": 30, "automl": 180,
             "checkpoint": 90, "memgov": 90, "ingest": 90,
             "serving": 60, "sched": 120, "tracing": 90, "fleet": 120,
             "durability": 120, "globalfit": 120, "gbm-full": 600}

# hard per-config wallclock cap (child process killed past it): a
# wedged worker costs one line, never the scoreboard
_HARD_CAP = {"gbm": 900, "glm": 600, "dl": 600, "xgb": 600, "sort": 400,
             "grid": 600, "treekernel": 400, "cloud": 300, "automl": 900,
             "checkpoint": 600, "memgov": 600, "ingest": 600,
             "serving": 600, "sched": 600, "tracing": 600, "fleet": 600,
             "durability": 600, "globalfit": 600, "gbm-full": 1200}


def _stub_ok(name):
    def _fn():
        _emit(f"stub config {name}", 1.0, "units", 1.0, "stub")
    return _fn


def _stub_wedge():
    # a wedged backend: the child accepts work and never finishes
    time.sleep(3600)


def _stub_grid():
    """`grid` models/sec line without a backend: drives the model-batch
    PLANNER (shape buckets, canonical combo keys, the knob) over a
    synthetic numeric-only GBM grid, so the harness exercises the
    batched-path plumbing even where no accelerator exists."""
    from h2o3_tpu.parallel import model_batch
    combos = [{"learn_rate": lr, "sample_rate": sr, "max_depth": d}
              for lr in (0.05, 0.1) for sr in (0.8, 1.0)
              for d in (5, 12)]       # 8 combos, TWO depth buckets
    t0 = time.time()
    buckets = model_batch.plan_buckets("gbm", combos)
    assert len({model_batch.combo_key(c) for c in combos}) == len(combos)
    dt = max(time.time() - t0, 1e-6)
    _emit("grid GBM 8 combos (stub; bucket planner, no backend)",
          len(combos) / dt, "models/sec", 1.0, "stub",
          buckets=len(buckets),
          widths=sorted(b.width for b in buckets),
          batched=model_batch.enabled())


def _stub_cloud():
    """`cloud` line without a backend: drives the heartbeat monitor's
    miss/degrade/recover state machine via fault injection — the
    bootstrap + peer-health plumbing, no jax dispatches (rounds fail at
    the injection hook before touching a device)."""
    from h2o3_tpu.core import heartbeat, watchdog
    mon = heartbeat.HeartbeatMonitor()
    mon.interval_s, mon.miss_budget, mon.timeout_s = 0.01, 2, 5.0
    mon.peers = {0: {"last_seen": time.time(), "healthy": True}}
    watchdog.inject_fault("heartbeat", times=2)
    try:
        t0 = time.time()
        assert mon.round() is False and mon.healthy()
        assert mon.round() is False and not mon.healthy()
        detect_s = time.time() - t0
        # the flag now kills the next chunk, classified infra
        assert watchdog.is_infra_error(
            heartbeat.CloudUnhealthyError(mon.reason() or "down"))
    finally:
        watchdog.clear_faults()
    rounds = mon.rounds
    _emit("cloud heartbeat (stub; miss->degrade state machine, "
          "no backend)", rounds / max(detect_s, 1e-6), "rounds/sec",
          1.0, "stub", miss_budget=mon.miss_budget,
          detect_ms=round(detect_s * 1e3, 3))


def _stub_roofline():
    """`roofline` line without a backend: drives the peak table and the
    analytic per-algo estimators (telemetry/roofline.py) — mfu/hbm_util
    fields flow even where no accelerator exists, so the harness
    exercises the hardware-relative axis plumbing end to end."""
    from h2o3_tpu.telemetry import roofline
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["flops"] > 0 and peaks["hbm_bytes_per_s"] > 0
    est = roofline.analytic_tree_cost(rows=5_000_000, features=10,
                                      trees=100, depth=6, bins=65)
    seconds = 50.0                      # flagship-shaped pretend fit
    mfu = est["flops"] / (seconds * peaks["flops"])
    hbm = est["bytes"] / (seconds * peaks["hbm_bytes_per_s"])
    assert mfu > 0 and hbm > 0
    glm = roofline.analytic_glm_cost(rows=11_000_000, coefs=29,
                                     iterations=8)
    dl = roofline.analytic_dl_cost(1_000_000 * 8.0, [784, 200, 200, 10])
    assert glm["flops"] > 0 and dl["flops"] > 0
    _emit("roofline GBM flagship shape (stub; analytic estimators + "
          "peak table, no backend)", 100 * mfu, "mfu_pct", 1.0, "stub",
          mfu=round(mfu, 6), hbm_util=round(hbm, 6),
          peak_source=peaks["source"])


def _stub_treekernel():
    """`treekernel` line without a backend: drives the Pallas PLANNER —
    the pure knob/backend decision table and the VMEM tile sizing
    (ops/pallas.decide / tile_rows) — so the harness exercises the
    kernel-layer plumbing even where no accelerator (or no Pallas)
    exists."""
    from h2o3_tpu.ops import pallas as plx
    decisions = {}
    for knob in ("auto", "off", "interpret", "on"):
        for backend in ("tpu", "cpu"):
            mode, reason = plx.decide(knob, backend, 8, True)
            decisions[f"{knob}/{backend}"] = mode + (
                f" ({reason})" if reason else "")
    # unavailable pallas always resolves off, never raises
    assert plx.decide("auto", "tpu", 8, False)[0] == "off"
    rows = plx.tile_rows(10, 65, 32)
    assert rows % 8 == 0 and rows >= 8
    _emit("treekernel fused level (stub; knob/tile planner, no backend)",
          float(rows), "rows/tile", 1.0, "stub", decisions=decisions)


def _stub_checkpoint():
    """Backend-free FitCheckpointer state machine: snapshot cadence,
    atomic write, load, bit-flip quarantine (ISSUE 9)."""
    import tempfile

    from h2o3_tpu.core.recovery import FitCheckpointer
    d = tempfile.mkdtemp(prefix="h2o3tpu_stub_ckpt_")
    fc = FitCheckpointer(os.path.join(d, "gbm_stub.fitsnap"), "gbm", 5)
    t0 = time.time()
    n_snap = 0
    for unit in range(5, 55, 5):
        if fc.maybe_save(unit, lambda: {"done": unit,
                                        "payload": b"x" * 4096}):
            n_snap += 1
    dt = max(time.time() - t0, 1e-9)
    loaded = fc.load()
    assert loaded is not None and loaded[0] == 50, loaded
    with open(fc.path, "r+b") as f:       # bit-flip → quarantine
        f.seek(2)
        f.write(b"\xff\xff")
    assert fc.load() is None
    assert any(fn.endswith(".corrupt") for fn in os.listdir(d))
    fc.clear()
    _emit("checkpoint FitCheckpointer (stub; snapshot/load/quarantine "
          "state machine, no backend)", n_snap / dt, "snapshots/sec",
          1.0, "stub", snapshots=n_snap, quarantined=1)


def _stub_memgov():
    """Backend-free memory-governor admission state machine (ISSUE 11):
    budget resolution from the knob, the reservation ledger's
    admit→spill→reject walk, and the actionable rejection shape — no
    jax dispatches (the cold-frame spill hook is simulated)."""
    from h2o3_tpu.core import memgov
    gov = memgov.MemoryGovernor()
    resident = {"bytes": 96 << 20}
    spills = []

    def _spill(needed, exclude=None):
        # each "cold frame" releases 32MB until nothing cold remains
        if resident["bytes"] >= 32 << 20:
            resident["bytes"] -= 32 << 20
            spills.append(32 << 20)
            return 1
        return 0

    gov.bytes_in_use = lambda: resident["bytes"]
    gov.evict_for_admission = _spill
    os.environ["H2O3TPU_HBM_BUDGET_MB"] = "128"
    os.environ["H2O3TPU_MEMGOV_WAIT_S"] = "0.05"
    t0 = time.time()
    try:
        # ADMIT after one spill: 96 in use + 64 projected > 128 budget
        r1 = gov.reserve("fit-a", 64 << 20)
        assert spills, "admission must spill before admitting"
        # second fit: spills to the floor, then the ledger (fit-a's
        # 64MB hold) still blocks it -> bounded wait -> REJECT
        try:
            gov.reserve("fit-b", 96 << 20)
            raise AssertionError("over-budget fit must reject")
        except memgov.MemoryBudgetExceeded as e:
            assert e.projected == 96 << 20 and e.budget == 128 << 20
            assert "rejected before dispatch" in str(e)
        gov.release(r1)
        gov.release(gov.reserve("fit-b", 96 << 20))  # admits post-release
    finally:
        os.environ.pop("H2O3TPU_HBM_BUDGET_MB", None)
        os.environ.pop("H2O3TPU_MEMGOV_WAIT_S", None)
    dt = max(time.time() - t0, 1e-6)
    _emit("memgov admission (stub; admit->spill->reject ledger state "
          "machine, no backend)", 3 / dt, "admissions/sec", 1.0, "stub",
          spills=len(spills), rejected=1)


def _stub_ingest():
    """`ingest` line without a backend: drives the chunk PLANNER and the
    quote-aware byte-range splitter (io/chunking.py, jax-free) over a
    quoted CSV with embedded newlines/commas — every window must cut at
    a record boundary (even double-quote parity, never mid-field) and
    the windows must reassemble to the original byte stream."""
    import tempfile

    from h2o3_tpu.io import chunking
    rows = ["h1,h2"]
    for i in range(4000):
        rows.append(f'"va{i},x\ny",{i}' if i % 3 else f"v{i},{i}")
    data = ("\n".join(rows) + "\n").encode()
    d = tempfile.mkdtemp(prefix="h2o3tpu_stub_ingest_")
    path = os.path.join(d, "quoted.csv")
    with open(path, "wb") as f:
        f.write(data)
    t0 = time.time()
    windows = [w for w, _ in chunking.iter_line_chunks([path], 2048)]
    dt = max(time.time() - t0, 1e-9)
    assert b"".join(windows) == data, "splitter must be lossless"
    for w in windows:
        assert w.endswith(b"\n") and w.count(b'"') % 2 == 0, \
            "window cut mid-quote"
    plan = chunking.parse_plan([path], chunk_bytes=2048)
    assert plan["files"] == 1 and plan["est_chunks"] >= 1
    assert plan["mode"] in ("chunk-parallel", "sequential"), plan
    _emit("ingest splitter (stub; quote-aware chunk planner, no "
          "backend)", len(data) / dt / 1e6, "MB/sec", 1.0, "stub",
          windows=len(windows), mode=plan["mode"],
          workers=plan["workers"], est_chunks=plan["est_chunks"])


def _stub_serving():
    """`serving` line without a backend (ISSUE 14): drives the full
    row-parse + micro-batch queue/coalesce/scatter state machine
    (serving/rows.py + serving/batcher.py, both jax-free) — bounded
    queue saturation, deadline drops, and request coalescing — with a
    numpy dispatch standing in for the compiled scorer."""
    import threading

    from h2o3_tpu.serving.batcher import (MicroBatcher, PendingScore,
                                          QueueSaturated)
    from h2o3_tpu.serving.rows import concat_columns, parse_rows

    schema = [("x1", None), ("c1", ["a", "b", "c"])]
    widths = []

    def _dispatch(batch):
        cols = concat_columns([p.cols for p in batch])
        n = sum(p.n for p in batch)
        assert cols["x1"].shape[0] == n
        widths.append(len(batch))
        out = cols["x1"] * 2.0          # stand-in for the device program
        off = 0
        for p in batch:
            p.finish(result=out[off:off + p.n], batch_requests=len(batch))
            off += p.n

    mb = MicroBatcher("stub-model", _dispatch, max_rows=64, wait_ms=5.0,
                      queue_depth=8)
    n_clients, reqs = 4, 50
    errors = []

    def _client(cid):
        for i in range(reqs):
            cols = parse_rows(schema, [{"x1": cid + i, "c1": "b"},
                                       {"x1": None, "c1": "zzz"}])
            assert cols["c1"][0] == 1 and cols["c1"][1] == -1
            p = PendingScore(cols, 2)
            try:
                mb.submit(p)
            except QueueSaturated:
                time.sleep(0.001)
                continue
            assert p.wait(5.0) and p.error is None
            assert p.result.shape == (2,)

    t0 = time.time()
    threads = [threading.Thread(target=_client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = max(time.time() - t0, 1e-9)
    served = sum(widths)

    # saturation: an unserviced queue must 503, never block
    frozen = MicroBatcher("stub-frozen", lambda b: time.sleep(10),
                          max_rows=4, wait_ms=0.0, queue_depth=2)
    try:
        cols = parse_rows(schema, [{"x1": 1.0}])
        time.sleep(0.05)                # dispatcher is stuck in sleep
        for _ in range(2):
            frozen.submit(PendingScore(cols, 1))
        try:
            frozen.submit(PendingScore(cols, 1))
            raise AssertionError("full queue must raise QueueSaturated")
        except QueueSaturated:
            pass
        # expired deadline: failed in-queue, never dispatched
        late = PendingScore(cols, 1, deadline=time.monotonic() - 1.0)
        dead = MicroBatcher("stub-dead", _dispatch, max_rows=4,
                            wait_ms=0.0, queue_depth=4)
        try:
            dead.submit(late)
            assert late.wait(5.0)
            assert late.error is not None, "expired deadline must fail"
        finally:
            dead.close()
    finally:
        frozen.close(join=False)
    mb.close()
    _emit("serving micro-batch (stub; parse/coalesce/scatter + "
          "saturation state machine, no backend)", served / dt,
          "requests/sec", 1.0, "stub", served=served,
          dispatches=len(widths),
          mean_batch_width=round(served / max(len(widths), 1), 2),
          coalesced=any(w > 1 for w in widths))


def _stub_sched():
    """`sched` line without a backend (ISSUE 15): drives the
    scheduler's coordinator state machine (parallel/scheduler.py
    RunBoard) dry — lease → complete → dead-peer reassign → stale
    generation rejection — plus the chunked zlib+base64 blob transport
    every published result rides; no jax, no KV server."""
    from h2o3_tpu.parallel.scheduler import (RunBoard, _B64_CHUNK,
                                             _decode, _encode)
    n_items, procs = 64, [0, 1, 2, 3]
    t0 = time.time()
    board = RunBoard(n_items, procs, offset=1)
    # every item leased exactly once, rotated from the run offset
    leased = sorted(i for p in procs for i in board.assignments(p))
    assert leased == list(range(n_items))
    assert board.owner(0) == procs[1]          # offset rotation
    # half the items complete on their first owners
    for i in range(0, n_items, 2):
        assert board.on_result(i, board.owner(i), board.generation(i))
    # host 2 dies: its unresulted leases reassign over the alive hosts
    moved = board.on_dead(2)
    assert moved and all(p != 2 for _, p, _g in moved)
    assert board.on_dead(2) == []              # idempotent per host
    # a result published at the PRE-reassignment generation is ignored
    idx0, _new_pid, new_gen = moved[0]
    assert not board.on_result(idx0, 2, new_gen - 1)
    # the new owners drain everything that is left
    for p in board.alive():
        for i, g in sorted(board.assignments(p).items()):
            board.on_result(i, p, g)
    assert board.complete() and not board.pending()
    # chunked result-blob transport round-trips losslessly
    blob = os.urandom(300_000)
    b64 = _encode(blob)
    nparts = (len(b64) + _B64_CHUNK - 1) // _B64_CHUNK
    assert _decode(b64) == blob
    dt = max(time.time() - t0, 1e-6)
    _emit("sched RunBoard 64 items 4 hosts (stub; lease->complete->"
          "reassign state machine, no backend)", n_items / dt,
          "items/sec", 1.0, "stub", reassigned=len(moved),
          blob_parts=nparts)


def _stub_slo():
    """`slo` line without a backend (ISSUE 16): drives the burn-rate
    state machine (telemetry/slo.py SLOEngine) dry on a private
    registry with a fake clock — healthy → burning → alert → recovery
    → healthy, with burn-rate gauges published along the way; no jax,
    no server."""
    from h2o3_tpu.telemetry import slo
    from h2o3_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    clock = [1000.0]
    h = reg.histogram("predict_seconds", buckets=(0.1, 0.5, 1.0),
                      phase="device")

    rule = slo.RatioRule(
        "predict_p99_latency", objective=0.99,
        counts_fn=slo._predict_latency_counts,
        description="stub p99 rule")
    eng = slo.SLOEngine(registry=reg, rules=[rule],
                        now=lambda: clock[0])
    t0 = time.time()
    evals = 0

    def tick(dt=30.0):
        nonlocal evals
        clock[0] += dt
        evals += 1
        return eng.evaluate()

    # healthy: fast predictions only
    for _ in range(50):
        h.observe(0.01)
    out = tick()
    states = {r["slo"]: r["state"] for r in out["rules"]}
    assert states["predict_p99_latency"] == "healthy", states
    # fault-injected latency: a burst of slow predictions torches the
    # short AND long windows → burning → alert
    for _ in range(200):
        h.observe(2.0)
    saw = []
    for _ in range(12):
        out = tick()
        saw.append(out["rules"][0]["state"])
        if out["rules"][0]["state"] == "alert":
            break
    assert "alert" in saw, saw
    assert out["alerts"], "alerting rule missing from alerts list"
    # recovery: the error budget refills as fast traffic displaces the
    # burst beyond both windows
    for _ in range(80):
        for _ in range(500):
            h.observe(0.01)
        out = tick(120.0)
        if out["rules"][0]["state"] == "healthy":
            break
    assert out["rules"][0]["state"] == "healthy", out["rules"][0]
    assert not out["alerts"]
    burn = reg.find("slo_burn_rate")
    assert burn, "burn-rate gauges never published"
    trans = sum(int(c.value) for c
                in reg.find("slo_alert_transitions_total"))
    assert trans >= 2, trans  # at least alert entry + exit
    dt = max(time.time() - t0, 1e-6)
    _emit("slo burn-rate engine (stub; healthy->burning->alert->"
          "recovery on a fake clock, no backend)", evals / dt,
          "evals/sec", 1.0, "stub", transitions=trans,
          evaluations=evals)


def _stub_fleet():
    """`fleet` line without a backend (ISSUE 17): drives the replica
    router's routing/failover state machine (serving/fleet.py
    ReplicaRouter) dry on injected providers — least-loaded pick, local
    bias, heartbeat exclusion, bounded hedged failover, drain — plus
    the degradation contract (FleetUnavailable carries Retry-After);
    no jax, no sockets."""
    from h2o3_tpu.serving.fleet import (FleetUnavailable, ReplicaRouter,
                                        SERVE_LOCALLY)
    reps = {"m": {1: {}, 2: {}, 3: {}}}
    eps = {1: ("h", 1), 2: ("h", 2), 3: ("h", 3)}
    loads = {0: 0.0, 1: 5.0, 2: 1.0, 3: 9.0}
    dead, draining = set(), [False]
    r = ReplicaRouter(
        self_pid=0,
        replicas_fn=lambda mk: dict(reps.get(mk, {})),
        endpoints_fn=lambda: dict(eps),
        dead_fn=lambda: set(dead),
        loads_fn=lambda: dict(loads),
        draining_fn=lambda: draining[0],
        published_fn=lambda mk: mk == "m",
        local_bias=2.0)
    t0 = time.time()
    n_plans = 3000
    # steady state: least-loaded healthy replica wins every plan
    for _ in range(n_plans):
        p = r.plan("m", have_local=False)
        assert p.decision == "proxy" and p.pid == 2, vars(p)
    # the local bias: a swamped local replica routes away, a marginal
    # win stays local
    reps["m"][0] = {}
    loads[0] = 9.0
    assert r.plan("m", have_local=True).pid == 2
    loads[0] = 2.5
    assert r.plan("m", have_local=True).decision == "local"
    del reps["m"][0]
    # heartbeat exclusion: the best replica dies -> next-best, no probe
    dead.add(2)
    assert r.plan("m", have_local=False).pid == 1
    # bounded hedged failover: every hop down -> the fallback sentinel
    # (the caller installs the published binary), never a hang
    calls = []

    def down(pid, ep):
        calls.append(pid)
        raise ConnectionRefusedError("down")

    assert r.hedged("m", down, local_fallback=True) is SERVE_LOCALLY
    n_hedges = len(calls)
    assert n_hedges == 2            # 1 and 3 tried; 2 is dead
    # explicit degradation: no fallback -> retryable FleetUnavailable
    try:
        r.hedged("m", down)
        raise AssertionError("hedged never degraded")
    except FleetUnavailable as e:
        assert e.retry_after_s > 0
    # drain: the peer leaves routing, the published binary still
    # resolves for anyone else (install), a held copy still serves
    draining[0] = True
    assert r.plan("m", have_local=False).decision in ("proxy", "install")
    reps["m"].clear()
    assert r.plan("m", have_local=False).decision == "install"
    dt = max(time.time() - t0, 1e-6)
    _emit("fleet replica router (stub; route->bias->exclude->hedge->"
          "drain state machine, no backend)", n_plans / dt,
          "plans/sec", 1.0, "stub", hedged_hops=n_hedges)


def _stub_durability():
    """`durability` line without a backend (ISSUE 18): drives the
    registry/rebuild state machine (core/durability.py DurabilityBoard)
    dry — register → peer death → mirror-over-lineage rebuild plan on
    the least-loaded survivor → re-home acks → terminal LOST path for
    keys with neither leg — plus the chunked zlib+base64 blob transport
    mirrored frames ride over the coordination KV; no jax, no KV
    server."""
    from h2o3_tpu.core.durability import (DurabilityBoard, _B64_CHUNK,
                                          _decode, _encode)
    n_keys, procs = 64, [0, 1, 2, 3]
    t0 = time.time()
    board = DurabilityBoard(procs)
    for i in range(n_keys):
        board.register(f"frame_{i:03d}", pid=i % 4,
                       mirrored=(i % 3 != 0), lineage=(i % 3 == 0))
    # host 2 dies: every key it homed gets a rebuild plan — mirror
    # preferred over lineage, homed on the least-loaded survivor
    plan = board.on_dead(2, loads={0: 2.0, 1: 0.5, 3: 1.0})
    assert plan and all(t == 1 for _k, t, _s in plan)
    assert {s for _k, _t, s in plan} == {"mirror", "lineage"}
    assert board.on_dead(2) == []              # idempotent per host
    assert not board.complete()
    for k, t, _s in plan:
        board.on_rebuilt(k, t)
    assert board.complete()
    # a key with neither mirror nor lineage is terminally LOST on its
    # home's death — never under-replicated-forever, never a hang
    board.register("doomed", pid=3)
    plan2 = board.on_dead(3, loads={0: 0.1, 1: 9.0})
    assert all(k != "doomed" for k, _t, _s in plan2)
    assert board.lost() == ["doomed"]
    for k, t, _s in plan2:
        board.on_rebuilt(k, t)
    assert board.complete() and board.alive() == [0, 1]
    # chunked mirror-blob transport round-trips losslessly
    blob = os.urandom(300_000)
    b64 = _encode(blob)
    nparts = (len(b64) + _B64_CHUNK - 1) // _B64_CHUNK
    assert _decode(b64) == blob
    dt = max(time.time() - t0, 1e-6)
    _emit("durability board 64 frames 4 hosts (stub; register->dead->"
          "rebuild-plan->re-home state machine, no backend)",
          n_keys / dt, "frames/sec", 1.0, "stub",
          replanned=len(plan) + len(plan2), lost=len(board.lost()),
          blob_parts=nparts)


def _stub_globalfit():
    """`globalfit` line without a backend: the partitioned-ingest codec
    agreement (frame/partition.py) — per-host numeric facts / string
    levels merged deterministically must equal what one host computes
    from the concatenated rows, so every process picks the SAME column
    codec without ever seeing peer rows."""
    from h2o3_tpu.frame import partition as part
    r = np.random.RandomState(0)
    shards = [r.randn(2000) for _ in range(4)]
    for s in shards:
        s[::53] = np.nan
    ints = [np.arange(-100, 100, dtype=np.float64) * (i + 1)
            for i in range(4)]
    strs = [np.array(list("abcz"), dtype=object),
            np.array(list("bcd"), dtype=object)]
    t0 = time.time()
    n_merge = 0
    for _ in range(200):
        merged = part.merge_numeric_facts(
            [part.local_numeric_facts(s) for s in shards])
        whole = part.local_numeric_facts(np.concatenate(shards))
        assert (merged["integral"], merged["lo"], merged["hi"]) \
            == (whole["integral"], whole["lo"], whole["hi"])
        mi = part.merge_numeric_facts(
            [part.local_numeric_facts(s) for s in ints])
        assert mi["integral"] and mi["lo"] == -400.0 and mi["hi"] == 396.0
        lv = part.merge_str_levels(
            [{"levels": part.local_str_levels(s)} for s in strs])
        assert lv == part.local_str_levels(np.concatenate(strs))
        n_merge += len(shards) + len(ints) + len(strs)
    dt = max(time.time() - t0, 1e-6)
    _emit("globalfit ingest codec agreement (stub; per-host facts/"
          "levels merge == whole-rows decision, no backend)",
          n_merge / dt, "merges/sec", 1.0, "stub", rounds=200)


def _stub_stepprof():
    """`stepprof` line without a backend (ISSUE 20): the step-profiler
    phase partition + ring bound, the pure skew/straggler verdict on
    synthetic 2-peer snapshots, and scripts/benchdiff.py's pass/fail
    contract (identical pair passes, a 30% step-time regression fails)
    — all stdlib + registry, no jax."""
    import importlib.util
    import json as _json
    import tempfile
    from h2o3_tpu.telemetry import stepprof

    stepprof.reset()
    t0 = time.time()
    # -- ring bound + partition ---------------------------------------
    os.environ["H2O3TPU_STEPPROF_RING"] = "8"
    try:
        prof = stepprof.start("stub", nrows=1000)
        assert prof is not None
        for _ in range(50):
            stepprof.chunk_begin()
            stepprof.compute_done(None)
            stepprof.chunk_end()
        d = stepprof.finish(prof, model_key="stub_model", seconds=None)
    finally:
        os.environ.pop("H2O3TPU_STEPPROF_RING", None)
    assert len(d["ring"]) == 8, f"ring unbounded: {len(d['ring'])}"
    assert d["chunks"] == 50
    assert abs(sum(d["phases"].values()) - d["seconds"]) < 0.25, d
    assert stepprof.profile_for("stub_model")["algo"] == "stub"

    # -- skew verdict on synthetic 2-peer snapshots -------------------
    # peer 1 is the straggler: big SELF time, small collective wait;
    # peer 0 spent half its wall blocked at the barrier
    skew = stepprof.compute_skew({
        "0": {"proc": 0, "seconds": 10.0,
              "phases": {"host": 1.0, "compute": 4.0,
                         "collective": 5.0, "checkpoint": 0.0}},
        "1": {"proc": 1, "seconds": 10.0,
              "phases": {"host": 2.0, "compute": 7.5,
                         "collective": 0.5, "checkpoint": 0.0}}})
    assert skew["straggler_proc"] == 1, skew
    assert skew["skew_ratio"] > 1.5, skew
    assert skew["hosts"]["0"]["collective_share"] > \
        skew["hosts"]["1"]["collective_share"], skew

    # -- benchdiff pass/fail contract ---------------------------------
    bd_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "scripts", "benchdiff.py")
    spec = importlib.util.spec_from_file_location("benchdiff", bd_path)
    bd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bd)
    with tempfile.TemporaryDirectory() as tmp:
        old = os.path.join(tmp, "old.json")
        new = os.path.join(tmp, "new.json")
        base = [{"metric": "fit_step", "value": 1.0, "unit": "seconds",
                 "phases": {"host": 0.2, "compute": 0.8}},
                {"metric": "gbm_rows", "value": 1e6, "unit": "rows/sec"}]
        regressed = [{"metric": "fit_step", "value": 1.3,
                      "unit": "seconds",
                      "phases": {"host": 0.2, "compute": 1.1}},
                     {"metric": "gbm_rows", "value": 1e6,
                      "unit": "rows/sec"}]
        with open(old, "w") as f:
            _json.dump(base, f)
        with open(new, "w") as f:
            _json.dump(regressed, f)
        rc_same = bd.main([old, old])
        rc_reg = bd.main([old, new])
    assert rc_same == 0, f"identical pair must pass, rc={rc_same}"
    assert rc_reg == 1, f"30% regression must fail, rc={rc_reg}"

    dt = max(time.time() - t0, 1e-6)
    _emit("stepprof phase partition + skew verdict + benchdiff gate "
          "(stub; ring bound, straggler id on synthetic peers, "
          "regression pass/fail, no backend)",
          50 / dt, "chunks/sec", 1.0, "stub",
          ring_len=len(d["ring"]), straggler=skew["straggler_proc"],
          skew_ratio=skew["skew_ratio"],
          benchdiff_identical_rc=rc_same, benchdiff_regression_rc=rc_reg)


if STUB:
    CONFIGS = [("stub_a", _stub_ok("stub_a")),
               ("stub_wedge", _stub_wedge),
               ("grid", _stub_grid),
               ("treekernel", _stub_treekernel),
               ("cloud", _stub_cloud),
               ("roofline", _stub_roofline),
               ("checkpoint", _stub_checkpoint),
               ("memgov", _stub_memgov),
               ("ingest", _stub_ingest),
               ("serving", _stub_serving),
               ("sched", _stub_sched),
               ("slo", _stub_slo),
               ("fleet", _stub_fleet),
               ("durability", _stub_durability),
               ("globalfit", _stub_globalfit),
               ("stepprof", _stub_stepprof),
               ("stub_b", _stub_ok("stub_b"))]
    _MIN_NEED = {n: 1 for n, _ in CONFIGS}
    _HARD_CAP = {n: 30 for n, _ in CONFIGS}


def _hard_cap(name) -> float:
    env = float(os.environ.get("H2O3TPU_BENCH_CONFIG_TIMEOUT_S", "0") or 0)
    return env or float(_HARD_CAP.get(name, 600))


# ---------------------------------------------------------- child modes


def _emit_hardening(name: str) -> None:
    """Request-hardening counters for this config's process (ISSUE 3):
    how many requests were rejected by the admission gate and how many
    deadlines expired while the config ran. Non-zero numbers mean the
    measured wall times include overload shedding — the scoreboard must
    say so."""
    try:
        from h2o3_tpu import telemetry
        _emit_raw({
            "metric": f"request-hardening {name}",
            "rest_rejected_total":
                int(telemetry.REGISTRY.total("rest_rejected_total")),
            "request_deadline_exceeded_total": int(
                telemetry.REGISTRY.value("request_deadline_exceeded_total")),
            "rest_client_disconnects_total": int(
                telemetry.REGISTRY.value("rest_client_disconnects_total"))})
    except Exception:   # noqa: BLE001 - accounting must never fail a config
        pass


def _emit_trace(name: str) -> None:
    """Write this config's process trace (spans + timeline + compiles)
    as a Chrome trace-event artifact so a BENCH run is explorable in
    Perfetto — where the wall time of a slow config actually went
    (compile track vs chunk spans), not just its final number."""
    try:
        from h2o3_tpu.telemetry import trace_export
        out_dir = os.environ.get("H2O3TPU_BENCH_TRACE_DIR",
                                 "/tmp/h2o3tpu_bench_traces")
        path = os.path.join(out_dir, f"trace_{name}.json")
        trace = trace_export.process_trace()
        trace_export.write_trace(path, trace)
        _emit_raw({"metric": f"trace {name}", "trace_path": path,
                   "trace_events": len(trace["traceEvents"])})
    except Exception:   # noqa: BLE001 - artifacts must never fail a config
        pass


def _child_one(name: str) -> int:
    """Run exactly one config in THIS process (spawned by the parent).
    Metric lines go to stdout; failures leave a classified traceback on
    stderr for the parent and exit nonzero."""
    fn = dict(CONFIGS)[name]
    if not STUB:
        import h2o3_tpu
        h2o3_tpu.init()
    try:
        fn()
        _emit_hardening(name)
        _emit_trace(name)
        return 0
    except Exception as e:   # noqa: BLE001 - child boundary
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(f"# child-error {name}: {e!r}"[:300], file=sys.stderr)
        return 1


def _stub_probe() -> int:
    """STUB-mode probe without the package import. Replicates
    watchdog.maybe_fail("probe") + _consume_shared over the same env
    contract (H2O3TPU_FAULTS / H2O3TPU_FAULT_STATE) in pure stdlib:
    the harness tests spawn ~50 probe children per run, and each
    ``from h2o3_tpu.core import watchdog`` costs ~1s of package import
    to reach a hook that needs only os/time."""
    site = "probe"
    count, sign = 0, "UNAVAILABLE"
    for part in os.environ.get("H2O3TPU_FAULTS", "").split(","):
        bits = part.strip().split(":")
        if bits[0] != site:
            continue
        count = int(bits[1]) if len(bits) > 1 and bits[1] else 1
        if len(bits) > 2 and bits[2]:
            sign = bits[2]
        break
    if count <= 0:
        return 0
    state = os.environ.get("H2O3TPU_FAULT_STATE") or None
    fail = True         # a fresh process always has its budget left
    if state is not None:
        path = os.path.join(state, f"fault_{site}.count")
        os.makedirs(state, exist_ok=True)
        lock = path + ".lock"
        for _ in range(200):                      # ~2s worst case
            try:
                fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.close(fd)
                break
            except FileExistsError:
                time.sleep(0.01)
        try:
            consumed = 0
            if os.path.exists(path):
                with open(path) as f:
                    consumed = int(f.read().strip() or 0)
            fail = consumed < count
            if fail:
                with open(path, "w") as f:
                    f.write(str(consumed + 1))
        finally:
            try:
                os.unlink(lock)
            except OSError:
                pass
    if fail:
        print("# probe failed: InjectedFault(\"%s: injected fault at "
              "site '%s'\")" % (sign, site), file=sys.stderr)
        return 1
    return 0


def _child_probe() -> int:
    """Backend liveness probe in a fresh process (core/watchdog.py):
    jax.devices() + a tiny device_put round-trip. In stub mode only the
    fault-injection hook runs — the harness under test, not the chip."""
    if STUB:
        return _stub_probe()
    from h2o3_tpu.core import watchdog
    try:
        rt = watchdog.probe_backend()
        print(f"# probe ok ({rt:.2f}s)", file=sys.stderr)
        return 0
    except Exception as e:   # noqa: BLE001 - child boundary
        print(f"# probe failed: {e!r}"[:300], file=sys.stderr)
        return 1


# --------------------------------------------------------------- parent


def _spawn(args, timeout_s, extra_env=None):
    """Run a child; returns (rc, stdout, stderr_tail). rc=124 on
    timeout (child and its process group killed)."""
    import subprocess
    env = dict(os.environ)
    env.update(extra_env or {})
    try:
        p = subprocess.run([sys.executable, os.path.abspath(__file__)]
                           + args, env=env, capture_output=True,
                           text=True, timeout=timeout_s)
        return p.returncode, p.stdout, p.stderr[-2000:]
    except subprocess.TimeoutExpired as e:
        out = e.stdout or b""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        return 124, out, f"timeout after {timeout_s:.0f}s (child killed)"


def _passthrough(stdout: str) -> int:
    """Re-emit the child's metric lines from the parent (the driver
    tails PARENT stdout; the tail-proof summary needs them recorded
    here). Returns how many metric lines came through."""
    n = 0
    for ln in stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                _emit_raw(json.loads(ln))
                n += 1
                continue
            except ValueError:
                pass
        if ln:
            print(ln, flush=True)
    return n


def _last_line(err: str, cap: int = 160) -> str:
    """The final non-empty stderr line, bounded — the one-line summary
    of a failure (never the full backend traceback)."""
    lines = [ln for ln in err.strip().splitlines() if ln.strip()]
    return lines[-1][:cap] if lines else ""


def _preflight(name: str, policy):
    """Probe the backend from a fresh process under the shared retry
    policy. Returns ``None`` when healthy, else a one-line reason —
    backend dead after bounded backoff; fail fast on this config
    instead of feeding it to a corpse. Each failed attempt costs ONE
    bounded stderr note (the scoreboard contract: a dead backend is one
    ``{"metric", "error"}`` line per config, never traceback spam)."""
    reason = ""
    for attempt in range(1, policy.max_attempts + 1):
        budget = min(_hard_cap(name), max(_remaining(), 5.0)) + 30.0
        rc, _, err = _spawn(["--probe"], timeout_s=budget)
        if rc == 0:
            return None
        reason = _last_line(err) or f"probe rc={rc}"
        print(f"# preflight {name}: probe attempt {attempt}/"
              f"{policy.max_attempts} failed: {reason}",
              file=sys.stderr)
        if attempt < policy.max_attempts and _remaining() > 0:
            time.sleep(policy.delay(attempt))
    return reason or "probe failed"


def main():
    import atexit
    atexit.register(_print_summary)
    # policy only — the parent must NEVER touch the backend itself (a
    # wedged chip would take the whole scoreboard down with it)
    from h2o3_tpu.core import watchdog
    policy = watchdog.policy_from_config()
    filt = sys.argv[1] if len(sys.argv) > 1 else ""
    force_full = os.environ.get("H2O3TPU_BENCH_FULL") == "1"
    for name, _fn in CONFIGS:
        if filt:
            # explicit selection: substring match, except the escalation
            # config which must be named exactly ("gbm" must not also
            # kick off the 50M-row run)
            if name == "gbm-full":
                if filt != "gbm-full":
                    continue
            elif filt not in name:
                continue
        elif name == "gbm-full" and not force_full \
                and _remaining() < _MIN_NEED[name]:
            _emit_raw({"metric": name,
                       "skipped": f"budget ({_remaining():.0f}s left)"})
            continue
        elif name != "gbm-full" and _remaining() < _MIN_NEED.get(name, 60):
            _emit_raw({"metric": name,
                       "skipped": f"budget ({_remaining():.0f}s left)"})
            continue
        for attempt in range(1, policy.max_attempts + 1):
            probe_err = _preflight(name, policy)
            if probe_err is not None:
                _emit_raw({"metric": name,
                           "error": "backend dead (pre-flight probe "
                                    "failed after bounded backoff): "
                                    + probe_err})
                break
            cap = min(_hard_cap(name), max(_remaining(), 10.0))
            rc, out, err = _spawn(
                ["--one", name], timeout_s=cap,
                # child budget = what is left HERE, so in-config caps
                # (automl max_runtime_secs) see the parent's clock
                extra_env={"H2O3TPU_BENCH_BUDGET_S":
                           f"{max(_remaining(), 10.0):.0f}"})
            emitted = _passthrough(out)
            if rc == 0:
                if err.strip():     # child progress notes (ingest etc.)
                    sys.stderr.write(err if err.endswith("\n")
                                     else err + "\n")
                break
            if rc == 124:
                _emit_raw({"metric": name,
                           "error": f"wedged: killed after {cap:.0f}s "
                                    f"hard cap ({emitted} lines emitted)"})
                break   # a kill is a wedge, not a blip: don't re-feed it
            infra = any(s in err for s in _INFRA_SIGNS)
            if (not infra or attempt >= policy.max_attempts
                    or _remaining() < _MIN_NEED.get(name, 60)):
                # ONE bounded line each to stderr and the scoreboard —
                # never the child's full traceback (round-5 spam)
                summary = _last_line(err, 300) or f"child rc={rc}"
                print(f"# {name}: child failed: {summary}",
                      file=sys.stderr)
                _emit_raw({"metric": name, "error": summary})
                break
            d = policy.delay(attempt)
            print(f"# retrying {name} after infra error in {d:.0f}s "
                  f"(attempt {attempt}/{policy.max_attempts})",
                  file=sys.stderr)
            time.sleep(d)
    # left_s is clamped ≥ 0 (used_s stays honest about any overrun)
    _emit_raw({"metric": "budget",
               "budget_s": round(BUDGET_S, 1),
               "used_s": round(time.time() - _T0, 1),
               "left_s": round(_remaining(), 1)})
    _print_summary()


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        sys.exit(_child_one(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--probe":
        sys.exit(_child_probe())
    else:
        main()
