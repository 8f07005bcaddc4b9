"""``chip_smoke.py`` off the chip: its rehearsal runs end to end on the
CPU and says so, the real run refuses anything but a TPU, and the
compile cache lives where the contract says."""

import json
import os
import subprocess
import sys

import jax

from h2o3_tpu.core import cloud

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
PHASES = ["init", "ingest", "gbm", "glm", "dl", "score", "serve",
          "device_check"]


def _run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out")] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    return r, lines


def test_rehearsal_runs_every_phase_on_the_cpu_and_says_so(tmp_path):
    r, lines = _run(["--rehearse"], tmp_path, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert [ln["phase"] for ln in lines[:-1]] == PHASES
    assert all("seconds" in ln for ln in lines[:-1])
    assert lines[0]["device"]["platform"] == "cpu"
    assert lines[0]["compile_cache_dir"] == cloud.COMPILE_CACHE_DIR
    gbm = lines[2]
    assert gbm["pallas_mode"] == "interpret"      # the kernel code path
    assert gbm["pallas_kernel_launches_total"] > 0
    assert gbm["nbins_total"] == 126 and gbm["bins_shape"][1] == 10
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": lines[0]["device"]["kind"], "count": 1}}
    assert not (tmp_path / "out").exists()        # scratch is removed


def test_real_run_refuses_the_cpu(tmp_path):
    r, lines = _run([], tmp_path, timeout=300)
    assert r.returncode == 1
    assert lines == [lines[0]] and lines[0]["ok"] is False
    assert lines[0]["phase"] == "init"
    assert "tpu" not in r.stdout.replace("not a TPU", "").lower()


def test_compile_cache_follows_the_variable_or_the_checkout(tmp_path,
                                                            monkeypatch):
    # the session's own init() took the no-variable branch
    assert cloud.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        assert jax.config.jax_compilation_cache_dir == \
            cloud.COMPILE_CACHE_DIR
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cloud.setup_compile_cache() == cloud.COMPILE_CACHE_DIR
    # with the variable, a fresh process sets no directory in code: jax
    # itself reads it, and init() leaves it alone
    mine = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=mine)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, h2o3_tpu; h2o3_tpu.init(); "
         "from h2o3_tpu.core import cloud; "
         "print(jax.config.jax_compilation_cache_dir); "
         "print(cloud.setup_compile_cache())"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [mine, mine]
    assert not os.path.exists(os.path.join(mine, "..", ".jax_cache"))
