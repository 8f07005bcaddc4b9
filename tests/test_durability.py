"""Durable data plane (ISSUE 18): frame lineage, mirrored shards,
peer-loss rebuild, and whole-cloud checkpoint/restore.

Tiers:
* pure state machine (DurabilityBoard) + blob codec — jax-free logic;
* in-process lineage / mirror / rebuild / DataLostError contracts under
  the session's 8-virtual-device cloud;
* REST surface: lineage on ``GET /3/Frames/{id}``, ``POST
  /3/CloudCheckpoint``, the 410 DATA_LOST mapping;
* whole-cloud checkpoint → restore, in-process and into a FRESH
  process via ``init(restore_dir=)``;
* the 2-process SIGKILL acceptance test (tests/durability_worker.py):
  kill a peer mid-GBM-fit, survivor rebuilds its frames from mirror and
  resumes the fit bit-identical to an undisturbed reference.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu.core import durability
from h2o3_tpu.core.durability import DataLostError, DurabilityBoard
from h2o3_tpu.core.kv import DKV

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "durability_worker.py")
WORKER_TIMEOUT_S = float(os.environ.get("H2O3TPU_MP_TIMEOUT_S", "300"))


@pytest.fixture()
def dur_env(monkeypatch, tmp_path):
    """Mirror mode scoped to one test: private mirror dir, clean local
    durability state on both sides."""
    durability.reset()
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", "mirror")
    monkeypatch.setenv("H2O3TPU_DUR_DIR", str(tmp_path / "mirror"))
    yield str(tmp_path / "mirror")
    durability.reset()
    durability.sweep_debris()


def _small_frame(seed=0, n=200):
    r = np.random.RandomState(seed)
    return h2o3_tpu.Frame.from_numpy(
        {"a": r.randn(n), "b": r.randn(n), "y": r.randn(n)})


# ------------------------------------------------ knob + typed error


def test_mode_knob_defaults_off(monkeypatch):
    monkeypatch.delenv("H2O3TPU_DATA_DURABILITY", raising=False)
    assert durability.mode() == "off"
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", "bogus")
    assert durability.mode() == "off"
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", " Mirror ")
    assert durability.mode() == "mirror"
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", "lineage")
    assert durability.mode() == "lineage"


def test_data_lost_error_is_typed_and_non_retryable():
    e = DataLostError("frame_x", "peer died")
    assert e.key == "frame_x"
    assert str(e).startswith("DATA_LOST:")
    assert isinstance(e, RuntimeError)
    from h2o3_tpu.core import watchdog
    assert DataLostError in watchdog.NON_RETRYABLE


def test_blob_codec_roundtrip():
    data = os.urandom(300_000) + b"\x00" * 50_000
    enc = durability._encode(data)
    assert isinstance(enc, str)
    assert durability._decode(enc) == data


# ------------------------------------------- DurabilityBoard machine


def test_board_plans_mirror_over_lineage_on_least_loaded():
    b = DurabilityBoard([0, 1, 2])
    b.register("f1", pid=1, mirrored=True, lineage=True)
    b.register("f2", pid=1, mirrored=False, lineage=True)
    b.register("f3", pid=0, mirrored=True)
    plan = b.on_dead(1, loads={0: 5.0, 2: 1.0})
    # only pid 1's keys are planned; mirror preferred; home = least load
    assert plan == [("f1", 2, "mirror"), ("f2", 2, "lineage")]
    assert b.under_replicated() == ["f1", "f2"]
    assert not b.complete()
    for key, target, _src in plan:
        b.on_rebuilt(key, target)
    assert b.complete()
    assert b.home("f1") == 2 and b.home("f3") == 0
    assert b.on_dead(1) == []          # idempotent per pid


def test_board_marks_unrecoverable_keys_lost():
    b = DurabilityBoard([0, 1])
    b.register("gone", pid=1, mirrored=False, lineage=False)
    assert b.on_dead(1) == []
    assert b.lost() == ["gone"]
    assert b.complete()                # lost keys are terminal, not pending
    with pytest.raises(ValueError):
        b.register("late", pid=1)      # dead pids cannot home keys
    with pytest.raises(ValueError):
        b.on_rebuilt("gone", 1)


# --------------------------------------------------- lineage records


def test_upload_and_derived_lineage(monkeypatch):
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", "lineage")
    durability.reset()
    try:
        fr = _small_frame()
        lin = durability.lineage_of(fr)
        assert lin["kind"] == "upload"
        assert not lin["rebuildable_from_lineage"]
        sub = fr[["a", "y"]]
        dlin = durability.lineage_of(sub)
        assert dlin["kind"] == "derived"
        assert dlin["parent"] == fr.key
        assert dlin["ops"] == [{"op": "select",
                                "params": {"columns": ["a", "y"]}}]
        # upload-rooted derived frames are NOT lineage-rebuildable
        assert not dlin["rebuildable_from_lineage"]
        with pytest.raises(DataLostError):
            durability.rebuild_from_lineage("k", dlin)
    finally:
        durability.reset()


def test_source_lineage_rebuilds_bit_identical(monkeypatch, tmp_path):
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", "lineage")
    durability.reset()
    csv = tmp_path / "src.csv"
    r = np.random.RandomState(3)
    with open(csv, "w") as f:
        f.write("a,b,y\n")
        for _ in range(120):
            f.write(f"{r.randn():.9f},{r.randn():.9f},{r.randn():.9f}\n")
    try:
        fr = h2o3_tpu.import_file(str(csv))
        key = fr.key
        lin = durability.lineage_of(fr)
        assert lin["kind"] == "source"
        assert lin["rebuildable_from_lineage"]
        assert lin["paths"] == [str(csv)]
        assert lin.get("parse_plan", {}).get("format") == "csv"
        assert lin.get("format_digest") == [durability.file_digest(str(csv))]
        want = durability.frame_digest(fr)
        DKV.remove(key)
        rebuilt = durability.rebuild_from_lineage(key, lin)
        assert rebuilt.key == key and key in DKV
        assert durability.frame_digest(rebuilt) == want
        # a deleted source file makes the chain unreplayable — typed
        DKV.remove(key)
        os.unlink(csv)
        with pytest.raises(DataLostError):
            durability.rebuild_from_lineage(key, lin)
    finally:
        durability.reset()


# ------------------------------------------- mirroring + rebuild


def test_mirror_write_through_and_rebuild(dur_env):
    fr = _small_frame(seed=11)
    key = fr.key
    st = durability.stats()
    assert key in st["mirrored"] and key in st["registry"]
    assert st["mirrored_bytes"] > 0
    from h2o3_tpu.core import memgov
    assert memgov.governor.mirror_bytes() == st["mirrored_bytes"]
    entry = dict(durability.registry()[key])
    assert entry["gen"] == 1 and os.path.exists(entry["uri"])
    want = entry["digest"]
    # simulate peer loss: drop the frame WITHOUT the deliberate-delete
    # hook (which would take the mirror with it)
    with durability._lock:
        durability._registered.discard(key)
    DKV.remove(key)
    assert key not in DKV
    assert durability.rebuild_frame(key, entry)
    assert key in DKV
    assert durability.frame_digest(DKV.get(key)) == want
    from h2o3_tpu import telemetry
    assert telemetry.counter("frame_rebuilds_total",
                             source="mirror").value >= 1


def test_deliberate_remove_drops_mirror_and_registry(dur_env):
    fr = _small_frame(seed=12)
    key = fr.key
    uri = durability.registry()[key]["uri"]
    assert os.path.exists(uri)
    DKV.remove(key)
    assert key not in durability.registry()
    assert not os.path.exists(uri)
    assert durability.mirrored_bytes() == 0


def test_transient_frames_are_never_mirrored(dur_env):
    fr = _small_frame(seed=13)
    before = set(durability.stats()["registry"])
    with durability.suspended():
        tmp = _small_frame(seed=14)
    assert set(durability.stats()["registry"]) == before
    sl = fr.row_slice(0, 50)
    assert sl.key not in durability.stats()["registry"]
    DKV.remove(tmp.key)
    DKV.remove(sl.key)


def test_unrecoverable_key_fails_typed_not_hung(dur_env):
    key = "frame_without_legs"
    entry = {"pid": 0, "nrows": 1, "ncols": 1}    # no gen, no lineage
    assert not durability.rebuild_frame(key, entry)
    assert key in durability.lost_keys()
    with pytest.raises(DataLostError):
        durability.check_lost(key)
    # the data-access chokepoint raises too — jobs fail fast, never hang
    with pytest.raises(DataLostError):
        DKV.get(key)


def test_kv_transport_blob_roundtrip(dur_env, monkeypatch):
    monkeypatch.setenv("H2O3TPU_DUR_TRANSPORT", "kv")
    fr = _small_frame(seed=15)
    entry = dict(durability.registry()[fr.key])
    assert entry["where"] == "kv"
    entry.setdefault("key", fr.key)
    data = durability.fetch_mirror(entry)
    assert len(data) == entry["nbytes"]
    from h2o3_tpu.io.persist import frame_from_bytes
    with durability.suspended():
        fr2 = frame_from_bytes(data, key="kvrt_check")
    try:
        assert durability.frame_digest(fr2) == entry["digest"]
    finally:
        DKV.remove("kvrt_check")


def test_sweep_debris_and_local_keys(dur_env):
    fr = _small_frame(seed=16)
    live_uri = durability.registry()[fr.key]["uri"]
    d = durability.mirror_dir()
    orphan_tmp = os.path.join(d, "dead.framesnap.tmp")
    orphan_blob = os.path.join(d, "unreg_g1.framesnap")
    for p in (orphan_tmp, orphan_blob):
        with open(p, "wb") as f:
            f.write(b"x")
    assert durability.sweep_debris() == 2
    assert os.path.exists(live_uri)          # referenced blobs survive
    assert not os.path.exists(orphan_tmp)
    assert not os.path.exists(orphan_blob)
    # shutdown contract: this process's registry keys + mirrors go away
    durability.sweep_local_keys()
    assert durability.registry() == {}
    assert not os.path.exists(live_uri)
    DKV.remove(fr.key)


def test_sweep_debris_skips_blobs_when_registry_unreadable(
        dur_env, monkeypatch):
    """A flaky/unreachable KV must read as 'liveness unknowable', not
    'no live blobs' — a sweep then would delete other peers' mirrors
    out from under the rebuild path. Only .tmp debris goes."""
    fr = _small_frame(seed=18)
    d = durability.mirror_dir()
    live_uri = durability.registry()[fr.key]["uri"]
    peer_blob = os.path.join(d, "other_peer_g1.framesnap")
    half_tmp = os.path.join(d, "half.framesnap.tmp")
    for p in (peer_blob, half_tmp):
        with open(p, "wb") as f:
            f.write(b"x")

    class _DownKV:
        def key_value_dir_get(self, prefix):
            raise IOError("kv unreachable")

        def key_value_set(self, *a, **k):
            raise IOError("kv unreachable")

        def key_value_delete(self, *a):
            raise IOError("kv unreachable")

    monkeypatch.setattr(durability, "_kv", lambda: _DownKV())
    assert durability.sweep_debris() == 1        # only the tmp
    assert not os.path.exists(half_tmp)
    assert os.path.exists(peer_blob)             # spared: unknowable
    assert os.path.exists(live_uri)
    monkeypatch.undo()
    DKV.remove(fr.key)


def test_local_kv_delete_is_exact_plus_subtree():
    """Coordination-service directory semantics: deleting 'reg/0/iris'
    must not take 'reg/0/iris_test' (destination_frame keys commonly
    share prefixes) — only the exact key and its 'iris/' subtree."""
    kv = durability._LocalKV()
    kv.key_value_set("reg/0/iris", "a")
    kv.key_value_set("reg/0/iris_test", "b")
    kv.key_value_set("reg/0/iris/child", "c")
    kv.key_value_delete("reg/0/iris")
    assert dict(kv.key_value_dir_get("reg/0/")) == {"reg/0/iris_test": "b"}
    kv.key_value_delete("reg/0/")                # dir form still sweeps
    assert kv.key_value_dir_get("reg/0/") == []


def test_remove_spares_prefix_sharing_registrations(dur_env):
    r = np.random.RandomState(19)
    h2o3_tpu.Frame.from_numpy({"a": r.randn(50)}, key="iris")
    fr2 = h2o3_tpu.Frame.from_numpy({"a": r.randn(50)}, key="iris_test")
    uri2 = durability.registry()["iris_test"]["uri"]
    DKV.remove("iris")
    reg = durability.registry()
    assert "iris" not in reg
    assert "iris_test" in reg                    # registration survives
    assert os.path.exists(uri2)                  # mirror survives
    DKV.remove("iris_test")


def test_derived_lineage_rebuild_spares_recovered_parent(
        dur_env, tmp_path):
    """The maybe_rebuild walk recovers 'train' before 'train_sub'; the
    child's lineage replay must reuse the resident parent — not
    re-import and then delete it (mirror, registry row and all) — and
    the rebuilt child must re-register so it regains durability
    coverage on its new home."""
    csv = tmp_path / "par.csv"
    r = np.random.RandomState(7)
    with open(csv, "w") as f:
        f.write("a,b,y\n")
        for _ in range(80):
            f.write(f"{r.randn():.9f},{r.randn():.9f},{r.randn():.9f}\n")
    fr = h2o3_tpu.import_file(str(csv), destination_frame="train")
    sub = fr[["a", "y"]]
    sub_key = sub.key
    want_parent = durability.frame_digest(fr)
    want_child = durability.frame_digest(sub)
    child_entry = dict(durability.registry()[sub_key])
    # peer-loss style drop of the child (no deliberate-delete hooks),
    # then force the lineage leg: no mirror generation in the entry
    with durability._lock:
        durability._registered.discard(sub_key)
    DKV.remove(sub_key)
    for k in ("gen", "uri", "where", "nbytes", "digest"):
        child_entry.pop(k, None)
    assert durability.rebuild_frame(sub_key, child_entry)
    # the recovered parent survived the child's replay
    assert "train" in DKV
    assert durability.frame_digest(DKV.get("train")) == want_parent
    assert "train" in durability.registry()
    assert "train" in durability.stats()["mirrored"]
    # the child is digest-identical AND regained registry + mirror
    assert durability.frame_digest(DKV.get(sub_key)) == want_child
    assert sub_key in durability.registry()
    assert sub_key in durability.stats()["mirrored"]
    from h2o3_tpu import telemetry
    assert telemetry.counter("frame_rebuilds_total",
                             source="lineage").value >= 1
    DKV.remove(sub_key)
    DKV.remove("train")


def test_derived_lineage_rebuild_with_absent_parent(dur_env, tmp_path):
    """When the parent is genuinely gone the replay re-imports it as a
    suspended temporary: the child comes back digest-identical and the
    temporary leaves no DKV entry, registration, or mirror behind."""
    csv = tmp_path / "par2.csv"
    r = np.random.RandomState(8)
    with open(csv, "w") as f:
        f.write("a,y\n")
        for _ in range(60):
            f.write(f"{r.randn():.9f},{r.randn():.9f}\n")
    fr = h2o3_tpu.import_file(str(csv), destination_frame="train2")
    sub = fr.drop(["a"])
    sub_key = sub.key
    want_child = durability.frame_digest(sub)
    child_entry = dict(durability.registry()[sub_key])
    for key in (sub_key, "train2"):
        with durability._lock:
            durability._registered.discard(key)
            durability._mirrored.pop(key, None)
        durability._kv().key_value_delete(
            f"{durability.KV_PREFIX}reg/0/{key}")
        DKV.remove(key)
    for k in ("gen", "uri", "where", "nbytes", "digest"):
        child_entry.pop(k, None)
    assert durability.rebuild_frame(sub_key, child_entry)
    assert durability.frame_digest(DKV.get(sub_key)) == want_child
    assert sub_key in durability.registry()
    assert "train2" not in DKV                   # temp base removed
    assert "train2" not in durability.registry()
    DKV.remove(sub_key)


def test_lost_verdict_is_cluster_wide_and_registry_survives(
        dur_env, monkeypatch):
    """An unrecoverable key's verdict travels: the LOST marker is
    published through the KV (a peer with a cold local set still fails
    typed), and the dead peer's registry row is kept — rewritten
    ``lost: true`` — so frames_under_replicated keeps counting the
    loss instead of the cloud reporting healthy."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.core import heartbeat
    key = "frame_lost_cluster"
    dead_pid = 7
    entry = {"pid": dead_pid, "nrows": 1, "ncols": 1}
    durability._kv().key_value_set(
        f"{durability.KV_PREFIX}reg/{dead_pid}/{key}", json.dumps(entry))
    monkeypatch.setattr(heartbeat, "dead_peers", lambda: [dead_pid])
    monkeypatch.setattr(heartbeat, "healthy_peers", lambda: [0])
    durability._last_rebuild = 0.0
    assert durability.maybe_rebuild() == 0
    # verdict is cluster-wide: wipe the local cache, check_lost still
    # fails typed off the published marker
    with durability._lock:
        durability._lost.discard(key)
    with pytest.raises(DataLostError):
        durability.check_lost(key)
    assert key in durability.lost_keys()
    # the loss record survives in the registry and feeds the SLO gauge
    reg = durability.registry()
    assert reg[key].get("lost") is True
    assert telemetry.gauge("frames_under_replicated").value >= 1
    # later rounds skip the terminal row instead of retrying forever
    durability._last_rebuild = 0.0
    assert durability.maybe_rebuild() == 0
    assert durability.registry()[key].get("lost") is True
    # deliberate removal retires the verdict everywhere
    DKV.remove(key)
    assert key not in durability.lost_keys()
    durability.check_lost(key)                   # no longer raises
    telemetry.gauge("frames_under_replicated").set(0)


# ----------------------------------------------------- SLO + metrics


def test_data_durability_slo_rule():
    from h2o3_tpu import telemetry
    from h2o3_tpu.telemetry import slo
    rules = {r.name: r for r in slo.default_rules()}
    assert "data_durability_floor" in rules
    rule = rules["data_durability_floor"]
    telemetry.gauge("frames_under_replicated").set(0)
    ok, _ = rule.check_fn(telemetry.REGISTRY)
    assert ok
    telemetry.gauge("frames_under_replicated").set(2)
    ok, detail = rule.check_fn(telemetry.REGISTRY)
    assert not ok
    telemetry.gauge("frames_under_replicated").set(0)


# ------------------------------------------------------- REST surface


@pytest.fixture(scope="module")
def port():
    from h2o3_tpu.api.server import start_server, stop_server
    p = start_server(port=0, background=True)
    yield p
    stop_server()


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}") as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def _post(port, path):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=b"", method="POST")
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_rest_frame_carries_lineage(port):
    fr = _small_frame(seed=20)
    status, j = _get(port, f"/3/Frames/{fr.key}")
    assert status == 200
    frj = j["frames"][0]
    assert frj["lineage"]["kind"] == "upload"
    assert frj["lineage"]["mirrored"] is False
    assert frj["lineage"]["rebuildable_from_lineage"] is False


def test_rest_data_lost_maps_to_410(port, monkeypatch):
    monkeypatch.setenv("H2O3TPU_DATA_DURABILITY", "mirror")
    key = "frame_gone_410"
    with durability._lock:
        durability._lost.add(key)
    try:
        status, j = _get(port, f"/3/Frames/{key}")
        assert status == 410
        assert "DATA_LOST" in j["msg"]
        assert j["http_status"] == 410
        from h2o3_tpu import telemetry
        assert telemetry.counter("rest_rejected_total",
                                 reason="data_lost").value >= 1
    finally:
        with durability._lock:
            durability._lost.discard(key)


def test_rest_cloud_checkpoint_roundtrip(port, tmp_path):
    fr = _small_frame(seed=21)
    ckpt = tmp_path / "cloudsnap"
    status, manifest = _post(
        port, f"/3/CloudCheckpoint?dir={ckpt}&quiesce_s=5")
    assert status == 200
    assert manifest["magic"] == durability.CLOUD_MAGIC
    assert any(f["key"] == fr.key for f in manifest["frames"])
    assert manifest["jobs_still_running"] == []
    assert os.path.exists(ckpt / "manifest.json")
    # a checkpoint with no dir is a client error (412), not a 500
    status, j = _post(port, "/3/CloudCheckpoint")
    assert status == 412


# --------------------------------------- whole-cloud checkpoint/restore


def test_cloud_checkpoint_restore_bit_identical(tmp_path):
    from h2o3_tpu.models.gbm import GBMEstimator
    r = np.random.RandomState(31)
    n = 400
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": r.randn(n), "b": r.randn(n),
         "y": r.randn(n)})
    model = GBMEstimator(ntrees=5, max_depth=3, seed=1).train(fr, y="y")
    want_digest = durability.frame_digest(fr)
    want_pred = model.predict(fr).col("predict").to_numpy().copy()
    fkey, mkey = fr.key, model.key
    ckpt = str(tmp_path / "cloudsnap")
    manifest = durability.cloud_checkpoint(ckpt, quiesce_s=5)
    assert {f["key"] for f in manifest["frames"]} >= {fkey}
    assert {m["key"] for m in manifest["models"]} >= {mkey}
    # wipe, then reform — restore digest-verifies every frame itself
    DKV.remove(fkey)
    DKV.remove(mkey)
    restored = durability.cloud_restore(ckpt)
    assert restored["frames"] >= 1 and restored["models"] >= 1
    fr2, m2 = DKV.get(fkey), DKV.get(mkey)
    assert durability.frame_digest(fr2) == want_digest
    assert np.array_equal(
        m2.predict(fr2).col("predict").to_numpy(), want_pred)
    from h2o3_tpu import telemetry
    hists = telemetry.REGISTRY.find("cloud_restore_seconds")
    assert hists and sum(h.count for h in hists) >= 1


def test_cloud_restore_rejects_garbage(tmp_path):
    with pytest.raises(IOError):
        durability.cloud_restore(str(tmp_path / "nope"))
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "manifest.json").write_text(json.dumps({"magic": "wrong"}))
    with pytest.raises(IOError):
        durability.cloud_restore(str(bad))


@pytest.mark.multiprocess
def test_init_restore_dir_reforms_cloud_in_fresh_process(tmp_path):
    """The disaster-recovery entry point: a BRAND NEW process calls
    ``init(restore_dir=)`` and gets the checkpointed cloud back,
    bit-identical (frames digest-verified, model predictions equal)."""
    from h2o3_tpu.models.gbm import GBMEstimator
    r = np.random.RandomState(41)
    n = 300
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": r.randn(n), "b": r.randn(n), "y": r.randn(n)})
    model = GBMEstimator(ntrees=4, max_depth=3, seed=2).train(fr, y="y")
    ckpt = str(tmp_path / "cloudsnap")
    durability.cloud_checkpoint(ckpt, quiesce_s=5)
    expect = {
        "frame_key": fr.key, "model_key": model.key,
        "pred_head": [float(v) for v in
                      model.predict(fr).col("predict").to_numpy()[:16]],
    }
    with open(os.path.join(ckpt, "expect.json"), "w") as f:
        json.dump(expect, f)
    script = (
        "import os, sys, json\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import h2o3_tpu\n"
        f"info = h2o3_tpu.init(backend='cpu', restore_dir={ckpt!r})\n"
        "assert info['restored']['frames'] >= 1, info\n"
        "assert info['restored']['models'] >= 1, info\n"
        "from h2o3_tpu.core.kv import DKV\n"
        f"exp = json.load(open(os.path.join({ckpt!r}, 'expect.json')))\n"
        "fr = DKV.get(exp['frame_key'])\n"
        "m = DKV.get(exp['model_key'])\n"
        "import numpy as np\n"
        "pred = m.predict(fr).col('predict').to_numpy()[:16]\n"
        "assert [float(v) for v in pred] == exp['pred_head'], "
        "'restored model predictions differ'\n"
        "print('RESTORE-OK')\n"
        "h2o3_tpu.shutdown()\n")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("H2O3TPU_DATA_DURABILITY", None)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True,
                       timeout=WORKER_TIMEOUT_S)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "RESTORE-OK" in p.stdout


# -------------------------------------- 2-process SIGKILL acceptance


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.multiprocess
def test_sigkill_peer_frames_rebuilt_fit_resumes_bit_identical(
        tmp_path):
    """Kill -9 a peer mid-GBM-fit: the survivor rebuilds its frames
    from the mirror (bit-identical digest), re-homes them, resumes the
    fit from the dead peer's traveling snapshot, and the result equals
    an undisturbed reference fit exactly. tests/durability_worker.py
    holds the per-process script + assertions."""
    out = str(tmp_path / "result.json")
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.update({
        "H2O3TPU_DATA_DURABILITY": "mirror",
        "H2O3TPU_DUR_DIR": str(tmp_path / "mirror"),
        "H2O3TPU_DUR_REBUILD_S": "0.1",
        "H2O3TPU_FIT_CHECKPOINT_DIR": str(tmp_path / "fitsnap"),
        "H2O3TPU_FIT_CHECKPOINT_EVERY": "2",
        # slow the victim's fit around each snapshot so the kill lands
        # deterministically mid-fit (never after completion)
        "H2O3TPU_FIT_CHECKPOINT_HOLD_S": "0.25",
    })
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, coord, "2", str(i), out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    # SIGKILL the victim once its fit has published a snapshot
    deadline = time.time() + WORKER_TIMEOUT_S
    fitdir = str(tmp_path / "fitsnap")
    killed = False
    while time.time() < deadline:
        snaps = [f for f in (os.listdir(fitdir)
                             if os.path.isdir(fitdir) else [])
                 if f.endswith(".fitsnap")]
        if snaps:
            procs[1].kill()
            killed = True
            break
        if procs[1].poll() is not None or procs[0].poll() is not None:
            break                    # a worker died early — report below
        time.sleep(0.05)
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(
                timeout=max(deadline - time.time(), 1.0))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            stdout, _ = p.communicate()
            stdout = (stdout or "") + "\n[TIMEOUT]"
        logs.append(stdout or "")
    joined = "\n".join(f"--- worker {j} ---\n{lg[-3000:]}"
                       for j, lg in enumerate(logs))
    assert killed, f"no fit snapshot ever appeared:\n{joined}"
    assert procs[1].returncode == -9, joined
    assert procs[0].returncode == 0, joined
    with open(out) as f:
        result = json.load(f)
    assert result["digest_match"] is True
    assert result["rebuild_source"] == "mirror"
    assert result["mirror_rebuilds_total"] >= 1
    assert result["bit_identical_fit"] is True
    assert result["resumed_mse"] == result["fresh_mse"]
    assert result["under_replicated"] == 0
