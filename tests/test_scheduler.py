"""Cluster work scheduler (ISSUE 15, parallel/scheduler.py).

Three tiers:

- RunBoard unit tests: the coordinator's lease/complete/reassign state
  machine, dry (no KV, no backend).
- Inline-run tests: ``scheduler.run`` on the single-process pytest
  cloud degrades to the inline executor but still exercises the item
  execution path (failure capture, nesting guard, lease gauge).
- ``multiprocess`` tests: a REAL 2-process jax.distributed CPU cloud
  runs an 8-combo GBM grid through the scheduler; combos must execute
  on BOTH hosts and the result must be bit-identical to the
  single-process scheduler-off reference — including when one host is
  SIGKILLed mid-grid and its leases are reassigned.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from h2o3_tpu.parallel import scheduler
from h2o3_tpu.parallel.scheduler import RunBoard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "sched_worker.py")
WORKER_TIMEOUT_S = float(os.environ.get("H2O3TPU_MP_TIMEOUT_S", "300"))


# ------------------------------------------------- RunBoard state machine


def test_runboard_initial_leases_cover_all_items():
    b = RunBoard(8, [0, 1], offset=0)
    assert sorted(i for p in (0, 1) for i in b.assignments(p)) == \
        list(range(8))
    assert b.owner(0) == 0 and b.owner(1) == 1     # round-robin
    assert not b.complete() and b.pending() == list(range(8))


def test_runboard_offset_rotates_first_owner():
    assert RunBoard(4, [0, 1, 2], offset=1).owner(0) == 1
    assert RunBoard(4, [0, 1, 2], offset=2).owner(0) == 2


def test_runboard_result_requires_current_generation():
    b = RunBoard(2, [0, 1])
    assert b.on_result(0, 0, 1)
    assert not b.on_result(0, 0, 1)                # duplicate
    moved = b.on_dead(1)
    assert moved == [(1, 0, 2)]                    # item 1 -> host 0 gen 2
    assert not b.on_result(1, 1, 1)                # stale generation
    assert b.on_result(1, 0, 2)
    assert b.complete()


def test_runboard_dead_peer_reassigns_only_unresulted():
    b = RunBoard(6, [0, 1, 2])
    assert b.on_result(1, 1, 1)                    # host 1 finishes item 1
    moved = b.on_dead(1)
    assert [i for i, _, _ in moved] == [4]         # its other lease only
    assert all(p in (0, 2) for _, p, _ in moved)
    assert b.on_dead(1) == []                      # idempotent
    assert b.alive() == [0, 2]


def test_runboard_no_alive_hosts_raises():
    b = RunBoard(2, [0, 1])
    b.on_dead(0)
    with pytest.raises(RuntimeError):
        b.on_dead(1)


def test_runboard_rejects_degenerate_inputs():
    with pytest.raises(ValueError):
        RunBoard(0, [0])
    with pytest.raises(ValueError):
        RunBoard(1, [])


# ------------------------------------------------- inline (degenerate) run


def test_inline_run_executes_every_item_in_order():
    seen = []

    def execute(i):
        seen.append(i)
        return i * 10

    res = scheduler.run("test:inline", 4, execute)
    assert seen == [0, 1, 2, 3]
    assert {i: r["data"] for i, r in res.items()} == \
        {0: 0, 1: 10, 2: 20, 3: 30}
    assert all(r["ok"] for r in res.values())
    assert scheduler.leases_held() == 0


def test_inline_run_captures_failures_as_results():
    def execute(i):
        if i == 1:
            raise ValueError("boom on 1")
        return i

    res = scheduler.run("test:fail", 3, execute)
    assert res[0]["ok"] and res[2]["ok"]
    assert not res[1]["ok"] and "boom on 1" in res[1]["error"]


def test_nested_run_is_guarded():
    """Work inside a scheduled item runs on ONE host — a nested run()
    must see active() False (and degrade inline) instead of entering
    the SPMD protocol from a single process."""
    states = {}

    def inner(_i):
        return "inner"

    def outer(i):
        states["in_item"] = scheduler.in_item()
        states["active"] = scheduler.active()
        return scheduler.run("test:nested-inner", 1, inner)[0]["data"]

    res = scheduler.run("test:nested-outer", 1, outer)
    assert res[0]["ok"] and res[0]["data"] == "inner"
    assert states["in_item"] is True
    assert states["active"] is False
    assert not scheduler.in_item()


def test_mode_gate(monkeypatch):
    from h2o3_tpu.core import config as _cfg
    monkeypatch.setattr(_cfg.ARGS, "scheduler", "off")
    assert not scheduler.active()
    monkeypatch.setattr(_cfg.ARGS, "scheduler", "on")
    assert scheduler.active()
    monkeypatch.setattr(_cfg.ARGS, "scheduler", "auto")
    assert not scheduler.active()      # single-process pytest cloud


def test_snapshot_counts_runs_and_items():
    s0 = scheduler.snapshot()
    scheduler.run("test:count", 2, lambda i: i)
    s1 = scheduler.snapshot()
    assert s1["runs"] == s0["runs"] + 1
    assert s1["items_done"] == s0["items_done"] + 2


# ------------------------------------------------- real multiprocess cloud


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(mode, nproc, out):
    """Run one worker pod; returns (returncodes, logs)."""
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, coord, str(nproc), str(i), out, mode],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(nproc)
    ]
    logs = []
    deadline = time.time() + WORKER_TIMEOUT_S
    for p in procs:
        try:
            stdout, _ = p.communicate(
                timeout=max(deadline - time.time(), 1.0))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            stdout, _ = p.communicate()
            stdout = (stdout or "") + \
                f"\n[TIMEOUT after {WORKER_TIMEOUT_S:.0f}s]"
        logs.append(stdout)
    return [p.returncode for p in procs], logs


def _read(out, pid):
    with open(f"{out}.{pid}") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sched_results(tmp_path_factory):
    """Three legs over the same data + grid: the single-process
    scheduler-off reference, the 2-process scheduled run, and the
    2-process run where host 1 is SIGKILLed mid-grid."""
    tmp = tmp_path_factory.mktemp("sched")
    legs = {}
    for mode, nproc in (("ref", 1), ("run", 2), ("kill", 2)):
        out = str(tmp / f"{mode}.json")
        rcs, logs = _launch(mode, nproc, out)
        legs[mode] = {"rcs": rcs, "logs": logs, "out": out}
    return legs


def _assert_ok(leg, who="every worker"):
    assert all(rc == 0 for rc in leg["rcs"]), (
        f"{who} must exit 0 (rcs={leg['rcs']}):\n"
        + "\n".join(f"--- worker {i} log ---\n{lg[-3000:]}"
                    for i, lg in enumerate(leg["logs"])))


# slow: the three pod legs cost ~30s of 1-core wallclock, and tier-1's
# 870s cap has no room — run with `-m multiprocess` (the RunBoard +
# inline tests above keep the scheduler surface in every tier-1 run)
@pytest.mark.slow
@pytest.mark.multiprocess
def test_sched_grid_spreads_across_both_hosts(sched_results):
    leg = sched_results["run"]
    _assert_ok(leg)
    r0, r1 = _read(leg["out"], 0), _read(leg["out"], 1)
    # per-host lease metrics: combos executed on BOTH processes
    assert r0["items_completed_here"] > 0
    assert r1["items_completed_here"] > 0
    assert r0["items_completed_here"] + r1["items_completed_here"] == 8
    assert r0["sched"]["runs"] == r1["sched"]["runs"] == 1
    assert r0["sched"]["leases_held"] == r1["sched"]["leases_held"] == 0


@pytest.mark.slow
@pytest.mark.multiprocess
def test_sched_grid_bit_identical_to_single_process(sched_results):
    ref, run = sched_results["ref"], sched_results["run"]
    _assert_ok(ref)
    _assert_ok(run)
    grid_ref = _read(ref["out"], 0)["grid"]
    assert len(grid_ref) == 8
    # bit-identical: full-precision floats straight from json
    assert _read(run["out"], 0)["grid"] == grid_ref
    assert _read(run["out"], 1)["grid"] == grid_ref


@pytest.mark.slow
@pytest.mark.multiprocess
def test_sched_sigkill_mid_grid_reassigns_and_matches(sched_results):
    ref, kill = sched_results["ref"], sched_results["kill"]
    _assert_ok(ref)
    # worker 1 SIGKILLed itself mid-grid; worker 0 must still finish
    assert kill["rcs"][0] == 0, (
        "surviving worker failed:\n"
        + "\n".join(f"--- worker {i} log ---\n{lg[-3000:]}"
                    for i, lg in enumerate(kill["logs"])))
    assert kill["rcs"][1] == -signal.SIGKILL
    r0 = _read(kill["out"], 0)
    # the dead host's leases moved here and the result is bit-identical
    assert r0["sched"]["items_reassigned"] >= 1
    assert r0["grid"] == _read(ref["out"], 0)["grid"]
    # no RUNNING job leak: every job reached a terminal state
    assert "RUNNING" not in r0["job_statuses"], r0["job_statuses"]


@pytest.mark.slow
@pytest.mark.multiprocess
def test_sched_no_running_job_leak(sched_results):
    leg = sched_results["run"]
    _assert_ok(leg)
    for pid in (0, 1):
        statuses = _read(leg["out"], pid)["job_statuses"]
        assert "RUNNING" not in statuses, (pid, statuses)
