"""ISSUE 11 — MemoryGovernor: HBM as a governed resource.

Four legs, one contract (core/memgov.py + core/cleaner.py +
core/job.py + models/model.py + api/server.py):

- single budget truth: device ``bytes_limit`` / the
  ``H2O3TPU_HBM_BUDGET_MB`` knob feed ``ops/merge.py``'s out-size cap
  and ``core/cleaner.py``'s ``pressure()``;
- predictive admission: a fit's footprint is estimated and reserved
  BEFORE dispatch — spill cold frames first, then reject with an
  actionable error naming projected vs available bytes; concurrent
  fits share a reservation ledger (bounded wait, then reject);
- OOM escalation ladder: RESOURCE_EXHAUSTED walks purge-jit-cache →
  governor eviction → resume from the in-fit checkpoint, driven
  deterministically on CPU via the ``device_oom`` fault site;
- memory truth: /3/Cloud reports real free/max/swap bytes.

Satellites: the merge-budget regression, the spill/restore CAS races
(run UNDER the conftest leak check), tight-budget bit-identity.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import telemetry
from h2o3_tpu.core import config, memgov, recovery, watchdog
from h2o3_tpu.core.cleaner import SpilledFrame, cleaner
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.core.memgov import MemoryBudgetExceeded, governor
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.tree import Tree

REGISTRY = telemetry.REGISTRY


@pytest.fixture(autouse=True)
def _clean_governor(monkeypatch):
    """Every test starts ungoverned with fast retry backoff and ends
    with no planted faults and an empty reservation ledger."""
    for var in ("H2O3TPU_HBM_BUDGET_MB", "H2O3TPU_MEMGOV",
                "H2O3TPU_MEMGOV_WAIT_S", "H2O3TPU_MERGE_MAX_OUT_BYTES"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(config.ARGS, "infra_backoff_base_s", 0.001)
    monkeypatch.setattr(config.ARGS, "infra_backoff_max_s", 0.01)
    yield
    watchdog.clear_faults()
    assert governor.reserved_bytes() == 0, "reservation leaked"


def _ice_tmp(tmp_path, monkeypatch):
    """Point the hex:// ice driver at tmp_path (test_cleaner.py idiom:
    the driver captures the dir at import, so reload)."""
    monkeypatch.setenv("H2O3_TPU_ICE_DIR", str(tmp_path))
    import importlib

    from h2o3_tpu.io import persist
    importlib.reload(persist)


def _classif_frame(n=2000, seed=0, key=None):
    r = np.random.RandomState(seed)
    X = r.randn(n, 5)
    yv = (X[:, 0] + 0.3 * r.randn(n) > 0).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(5)}
    cols["y"] = np.array(["a", "b"], object)[yv]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"], key=key)


def _forests_equal(a: Tree, b: Tree):
    for f in Tree._fields:
        av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert av.shape == bv.shape, (f, av.shape, bv.shape)
        assert np.array_equal(av, bv), f


# --------------------------------------------------- budget truth


def test_budget_truth_env_knob(monkeypatch):
    """One budget source: the knob feeds the governor's limit, the
    Cleaner's pressure() and /3/Cloud's snapshot alike; without any
    source the process is ungoverned (pressure 0, never spill-happy)."""
    assert governor.device_limit_bytes() == 0        # CPU: no stats
    assert not governor.governed()
    assert governor.pressure() == 0.0
    assert cleaner.pressure() == 0.0                 # routes through
    Frame.from_numpy({"a": np.arange(50_000.0)})     # something resident
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB", "1000")
    assert governor.device_limit_bytes() == 1000 << 20
    assert governor.governed()
    assert governor.budget_bytes() == 1000 << 20
    assert 0.0 < governor.pressure() == cleaner.pressure()
    snap = governor.snapshot()
    assert snap["governed"] and snap["budget_bytes"] == 1000 << 20
    assert snap["free_bytes"] == (1000 << 20) - snap["bytes_in_use"]
    monkeypatch.setenv("H2O3TPU_MEMGOV", "off")      # kill switch
    assert not governor.governed()


def test_budget_knob_changes_merge_decision(monkeypatch):
    """Satellite regression: ops/merge.py no longer assumes a private
    16GB device — its out-size cap is half the governor budget, and the
    knob flips a real join between the device and host paths."""
    from h2o3_tpu.ops import merge as merge_mod
    assert merge_mod._merge_out_budget() == 2 << 30  # CPU mesh default
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB", "1000")
    assert merge_mod._merge_out_budget() == 500 << 20
    monkeypatch.delenv("H2O3TPU_HBM_BUDGET_MB")
    # the decision, not just the number: 70K rows x 3 cols ≈ 1.9MB of
    # join result — on device under the default, host path under a
    # 1MB budget (512KB cap)
    n = 70_000
    k = np.arange(n, dtype=np.int64)
    lf = Frame.from_numpy({"k": k, "v": np.arange(n, dtype=np.float64)})
    rf = Frame.from_numpy({"k": k, "w": np.arange(n, dtype=np.float64)})
    out = merge_mod.device_merge(lf, rf, ["k"], "inner")
    assert out is not None and out.nrows == n
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB", "1")
    assert merge_mod.device_merge(lf, rf, ["k"], "inner") is None


def test_estimate_fit_bytes_scales():
    fr = _classif_frame()
    x = [f"x{i}" for i in range(5)]
    est = memgov.estimate_fit_bytes("gbm", {"ntrees": 50}, fr, x)
    from h2o3_tpu.core.cleaner import _frame_nbytes
    assert est > _frame_nbytes(fr)        # frame + design matrix + work
    vf = _classif_frame(seed=1)
    est_v = memgov.estimate_fit_bytes("gbm", {"ntrees": 50}, fr, x,
                                      validation_frame=vf)
    assert est_v >= est + _frame_nbytes(vf)


@pytest.mark.parametrize("shards", [1, 4])
def test_a_device_is_held_to_its_share_of_the_fit(monkeypatch, shards):
    """Where the device reports its own bytes, the budget is one
    device's, and so is the projection: a fit's rows are sharded over
    the mesh's data axis (a 116M-row fit on four chips was refused as
    the whole fit against one chip)."""
    import jax
    from h2o3_tpu.core import cleaner
    from h2o3_tpu.parallel import mesh as mesh_mod
    fr = _classif_frame()
    x = [f"x{i}" for i in range(5)]
    whole = memgov.estimate_fit_bytes("gbm", {"ntrees": 50}, fr, x)
    monkeypatch.setattr(cleaner, "device_memory_stats", lambda: {
        "bytes_in_use": 0, "bytes_limit": 16 << 30})
    old = mesh_mod.get_mesh()
    mesh_mod.set_global_mesh(mesh_mod.make_mesh(jax.devices()[:shards],
                                                shards, 1))
    try:
        share = memgov.estimate_fit_bytes("gbm", {"ntrees": 50}, fr, x)
    finally:
        mesh_mod.set_global_mesh(old)
    assert share == -(-whole // shards)


# ---------------------------------------------- predictive admission


def test_tight_budget_gbm_bit_identical_spill_restore(tmp_path,
                                                      monkeypatch):
    """Acceptance: the same GBM under a budget tight enough to force
    admission spills completes bit-identical to the unlimited run, with
    ≥1 spill and ≥1 restore counted."""
    _ice_tmp(tmp_path, monkeypatch)
    fr = _classif_frame()
    kw = dict(ntrees=20, max_depth=3, seed=5)
    clean = GBMEstimator(**kw).train(fr, y="y")
    # three cold decoy frames the admission pass may spill (~1.6MB ea)
    # f32-exact values so spill→restore comparisons are bitwise
    decoys = [Frame.from_numpy(
        {"d": np.random.RandomState(i).randn(400_000)
         .astype(np.float32).astype(np.float64)}) for i in range(3)]
    decoy_vals = [d.col("d").to_numpy() for d in decoys]
    decoy_bytes = sum(d.col("d").data.nbytes for d in decoys)
    time.sleep(0.01)
    DKV.get(fr.key)                       # training frame is warmest
    b = GBMEstimator(**kw)
    proj = memgov.estimate_fit_bytes(
        "gbm", b.params, fr, [f"x{i}" for i in range(5)])
    # a budget the fit only fits under after ~half the decoys spill
    budget = governor.resident_bytes() + proj - decoy_bytes // 2
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB",
                       str((budget + (1 << 20) - 1) >> 20))
    s0 = REGISTRY.total("frame_spills_total")
    r0 = REGISTRY.total("frame_restores_total")
    m = b.train(fr, y="y")
    assert REGISTRY.total("frame_spills_total") >= s0 + 1
    assert any(getattr(DKV.get_raw(d.key), "_is_lazy_stub", False)
               for d in decoys), "admission never spilled a decoy"
    assert governor.spilled_bytes() > 0
    _forests_equal(clean.forest, m.forest)
    assert float(clean.training_metrics["logloss"]) == \
        float(m.training_metrics["logloss"])
    # transparent restore of a spilled decoy, bit-intact
    restored = DKV.get(decoys[0].key)
    assert isinstance(restored, Frame)
    assert REGISTRY.total("frame_restores_total") >= r0 + 1
    np.testing.assert_array_equal(restored.col("d").to_numpy(),
                                  decoy_vals[0])


def test_over_budget_fit_rejected_pre_dispatch(monkeypatch):
    """Acceptance: a fit that cannot fit rejects BEFORE dispatch with
    the actionable shape (projected vs available bytes), counts the
    rejection, and leaks neither a Job nor a reservation — the client
    never sees an opaque XLA RESOURCE_EXHAUSTED."""
    r = np.random.RandomState(0)
    cols = {f"x{i}": r.randn(100_000) for i in range(4)}
    cols["y"] = np.array(["a", "b"], object)[
        (r.randn(100_000) > 0).astype(int)]
    fr = Frame.from_numpy(cols, categorical=["y"])   # ~1.6MB resident
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB", "1")
    c0 = REGISTRY.total("fit_admission_rejections_total")
    keys0 = set(DKV.keys())
    with pytest.raises(MemoryBudgetExceeded) as ei:
        GBMEstimator(ntrees=5, max_depth=3, seed=1).train(fr, y="y")
    e = ei.value
    assert isinstance(e, ValueError)      # watchdog: never retried
    assert e.projected > 0 and e.budget == 1 << 20
    assert "rejected before dispatch" in str(e)
    assert f"{e.projected} bytes" in str(e)
    assert "H2O3TPU_HBM_BUDGET_MB" in str(e)         # actionable
    assert REGISTRY.total("fit_admission_rejections_total") == c0 + 1
    assert governor.reserved_bytes() == 0
    from h2o3_tpu.core.job import Job
    assert not [k for k in DKV.keys() if k not in keys0
                and isinstance(DKV.get_raw(k), Job)], "job leaked"


def test_reservation_ledger_contention_and_release(monkeypatch):
    """Two individually-admissible fits cannot jointly overshoot: the
    second waits (bounded) on the ledger, rejects with
    reason=contention, and admits once the first releases."""
    gov = memgov.MemoryGovernor()
    gov.bytes_in_use = lambda: 0          # isolate the ledger
    gov.evict_for_admission = lambda needed, exclude=None: 0
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB", "64")
    monkeypatch.setenv("H2O3TPU_MEMGOV_WAIT_S", "0.2")
    r1 = gov.reserve("fit-a", 48 << 20)
    c0 = REGISTRY.total("fit_admission_rejections_total")
    t0 = time.monotonic()
    with pytest.raises(MemoryBudgetExceeded) as ei:
        gov.reserve("fit-b", 48 << 20)
    assert time.monotonic() - t0 >= 0.15  # waited, then gave up
    assert "reason=contention" in str(ei.value)
    assert REGISTRY.total("fit_admission_rejections_total") == c0 + 1
    # release mid-wait → the blocked fit admits instead of rejecting
    monkeypatch.setenv("H2O3TPU_MEMGOV_WAIT_S", "10")
    rel = threading.Timer(0.05, gov.release, args=(r1,))
    rel.start()
    r2 = gov.reserve("fit-b", 48 << 20)
    assert gov.reserved_bytes() == 48 << 20
    gov.release(r2)
    assert gov.reserved_bytes() == 0


# ------------------------------------------------ OOM escalation ladder


def test_device_oom_ladder_recovers_via_resume(tmp_path):
    """Acceptance: an injected RESOURCE_EXHAUSTED at a chunk boundary
    walks the ladder — jit purge counted, fit resumed from its snapshot
    (exactly one resume) — and the job SUCCEEDS bit-identical."""
    fr = _classif_frame()
    kw = dict(ntrees=50, max_depth=3, seed=5, stopping_rounds=2,
              stopping_tolerance=0.0, score_tree_interval=5)
    clean = GBMEstimator(**kw).train(fr, y="y")
    watchdog.inject_fault("device_oom", times=1)     # → RESOURCE_EXHAUSTED
    o0 = REGISTRY.total("oom_recoveries_total")
    p0 = REGISTRY.value("oom_recoveries_total", stage="purge_jit")
    z0 = REGISTRY.value("oom_recoveries_total", stage="resume")
    r0 = REGISTRY.total("fit_resumes_total")
    b = GBMEstimator(**kw)
    with recovery.fit_checkpoint_scope(str(tmp_path)):
        m = b.train(fr, y="y")
    assert b._job.status == "DONE"
    assert REGISTRY.total("oom_recoveries_total") >= o0 + 1
    assert REGISTRY.value("oom_recoveries_total", stage="purge_jit") \
        == p0 + 1
    assert REGISTRY.value("oom_recoveries_total", stage="resume") \
        == z0 + 1
    assert REGISTRY.total("fit_resumes_total") == r0 + 1
    _forests_equal(clean.forest, m.forest)
    assert clean.output["scoring_history"] == m.output["scoring_history"]


def test_repeat_oom_escalates_to_eviction(tmp_path, monkeypatch):
    """Rung 2: a second consecutive OOM drops the per-frame device
    caches and spills cold frames — previously pinned for the process
    lifetime — and the fit still completes bit-identical."""
    _ice_tmp(tmp_path, monkeypatch)
    fr = _classif_frame(seed=7)
    kw = dict(ntrees=30, max_depth=3, seed=5, score_tree_interval=5)
    clean = GBMEstimator(**kw).train(fr, y="y")
    assert fr.device_cache_nbytes() > 0   # pinned bin/matrix caches
    watchdog.inject_fault("device_oom", times=2)
    e0 = REGISTRY.value("oom_recoveries_total", stage="evict")
    with recovery.fit_checkpoint_scope(str(tmp_path)):
        m = GBMEstimator(**kw).train(fr, y="y")
    assert REGISTRY.value("oom_recoveries_total", stage="evict") == e0 + 1
    _forests_equal(clean.forest, m.forest)


# --------------------------------------------- spill/restore CAS races


def test_spill_cas_never_loses_newer_put(tmp_path, monkeypatch):
    """Satellite: a put that lands while the Cleaner is writing ice
    must win — the spill's replace_if CAS refuses, the stale ice file
    is reclaimed, and the bytes-on-ice ledger never moves."""
    _ice_tmp(tmp_path, monkeypatch)
    from h2o3_tpu.io import persist as persist_mod
    fr = Frame.from_numpy({"a": np.arange(4000.0)}, key="cas_victim")
    newer = {}
    orig_save = persist_mod.save_frame

    def racing_save(f, uri):
        orig_save(f, uri)                 # ice written...
        newer["fr"] = Frame.from_numpy(   # ...then a newer put lands
            {"a": np.arange(4000.0) + 1.0}, key="cas_victim")

    monkeypatch.setattr(persist_mod, "save_frame", racing_save)
    def _ice_files():
        # spill uris are generation-suffixed (cas_victim.g<N>.npz) so a
        # stale stub's discard can never unlink a newer stub's ice
        return glob.glob(
            os.path.join(str(tmp_path), "spill", "cas_victim.g*.npz"))

    g0 = governor.spilled_bytes()
    assert cleaner.spill("cas_victim") is None       # CAS refused
    assert DKV.get_raw("cas_victim") is newer["fr"]  # newer put won
    assert governor.spilled_bytes() == g0            # ledger untouched
    assert not _ice_files()
    # and the stub-clobber path: put over a real stub reclaims its ice
    monkeypatch.setattr(persist_mod, "save_frame", orig_save)
    assert isinstance(cleaner.spill("cas_victim"), SpilledFrame)
    assert governor.spilled_bytes() > g0
    assert len(_ice_files()) == 1
    Frame.from_numpy({"a": np.arange(4000.0) + 2.0}, key="cas_victim")
    assert governor.spilled_bytes() == g0            # settled once
    assert not _ice_files()
    np.testing.assert_array_equal(
        DKV.get("cas_victim").col("a").to_numpy(),
        np.arange(4000.0) + 2.0)


def test_spill_restore_race_concurrent_gets(tmp_path, monkeypatch):
    """Satellite: N reader threads hammer DKV.get on a frame while the
    main thread spills it repeatedly — every reader always sees a live,
    bit-intact Frame (never a stub, never a torn restore), and the
    bytes-on-ice ledger settles back to its baseline. Runs UNDER the
    conftest leak check."""
    _ice_tmp(tmp_path, monkeypatch)
    vals = np.random.RandomState(11).randn(8000) \
        .astype(np.float32).astype(np.float64)   # f32-exact: bitwise RT
    fr = Frame.from_numpy({"a": vals}, key="race_fr")
    expect = fr.col("a").to_numpy()
    g0 = governor.spilled_bytes()
    s0 = REGISTRY.total("frame_spills_total")
    r0 = REGISTRY.total("frame_restores_total")
    stop = threading.Event()
    errs = []

    def reader():
        while not stop.is_set():
            try:
                v = DKV.get("race_fr")
                if v is None or getattr(v, "_is_lazy_stub", False):
                    errs.append(f"reader saw {v!r}")
                    return
            except Exception as exc:      # noqa: BLE001
                errs.append(f"reader raised {exc!r}")
                return

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    # generous deadline with an early exit: the fast path breaks out
    # after ~20 spills + 1 observed restore; the long tail covers a
    # loaded CI box where the reader threads are GIL-starved and take
    # seconds to see their first spilled state (the pre-ISSUE-14 flake:
    # a fixed 3.0s window sometimes closed with zero restores banked)
    deadline = time.time() + 15.0
    while time.time() < deadline:
        cleaner.spill("race_fr")
        time.sleep(0.001)
        if (REGISTRY.total("frame_spills_total") >= s0 + 20
                and REGISTRY.total("frame_restores_total") >= r0 + 1):
            break
    stop.set()
    for t in threads:
        t.join(10.0)
    assert not errs, errs[:3]
    assert REGISTRY.total("frame_spills_total") >= s0 + 1
    assert REGISTRY.total("frame_restores_total") >= r0 + 1
    final = DKV.get("race_fr")
    assert isinstance(final, Frame)
    np.testing.assert_array_equal(final.col("a").to_numpy(), expect)
    assert governor.spilled_bytes() == g0  # every ice byte reclaimed


# ------------------------------------------------------- memory truth


def test_cloud_reports_memory_truth(tmp_path, monkeypatch):
    """Satellite: GET /3/Cloud stops reporting free_mem/max_mem/swap_mem
    as 0 — free/max come from the governor budget, swap is the bytes
    the Cleaner holds on ice."""
    _ice_tmp(tmp_path, monkeypatch)
    monkeypatch.setenv("H2O3TPU_HBM_BUDGET_MB", "256")
    Frame.from_numpy({"a": np.arange(50_000.0)}, key="cloud_ice_fr")
    assert cleaner.spill("cloud_ice_fr") is not None
    on_ice = governor.spilled_bytes()
    assert on_ice > 0
    from h2o3_tpu.api.server import _cloud
    out = _cloud({}, "")
    nd = out["nodes"][0]
    assert nd["max_mem"] == 256 << 20
    assert 0 < nd["free_mem"] <= nd["max_mem"]
    assert nd["free_mem"] == nd["max_mem"] - nd["mem_value_size"]
    assert nd["swap_mem"] == on_ice
    # gauges refreshed on the way (flight-recorder capsule surface)
    assert REGISTRY.value("hbm_budget_bytes") == 256 << 20
    assert REGISTRY.value("frames_spilled_bytes") == on_ice
    restored = DKV.get("cloud_ice_fr")    # leave the DKV clean
    assert isinstance(restored, Frame)
