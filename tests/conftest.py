"""Test harness: force an 8-virtual-device CPU mesh.

The analogue of the reference's multi-JVM-on-localhost test clouds
(multiNodeUtils.sh + @CloudSize(n), water/runner/H2ORunner.java:27): tests
exercise the same sharded/psum code paths the TPU pod runs, on 8 virtual
CPU devices.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = _flags.strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# each xdist worker keeps a persistent compile cache of its own: workers
# that shared one loaded executables another had written, and XLA:CPU's
# loader crashed in one (a segfault in compilation_cache.
# get_executable_and_time, PR 39; the workers that aborted in the runs
# of PRs 37-38 shared the cache too). Set before jax is imported: jax
# reads the variable itself.
_worker = os.environ.get("PYTEST_XDIST_WORKER")
if _worker and "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache", "pytest-" + _worker)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """Under ``--dist load``, send a test module to ONE worker as a unit.

    Spread test by test, every worker that ran a test of a module built
    that module's fixtures again (a module-scoped fit, a two-process pod)
    and compiled the module's programs again into caches of its own.
    Kept whole, a module builds them once. The modules of
    ``SPLIT_BY_FUNCTION`` are too long for one worker and share no
    costly fixture: they go a test function at a time (its cases
    together, and the functions that read one module-scoped fixture
    together), or a test at a time where the table holds None."""
    if config.getvalue("dist") != "load":
        return None
    from xdist.scheduler import LoadScopeScheduling

    class ModuleScheduling(LoadScopeScheduling):
        def _split_scope(self, nodeid):
            path, _, rest = nodeid.partition("::")
            name = os.path.basename(path)
            if name not in SPLIT_BY_FUNCTION:
                return path
            units = SPLIT_BY_FUNCTION[name]
            if units is None:
                return nodeid
            function = rest.split("[", 1)[0]
            return path + "::" + units.get(function, function)

    return ModuleScheduling(config, log)


# module -> {test function: the module-scoped fixture it shares}, or None
SPLIT_BY_FUNCTION = {
    # a compile for a described chip in every test
    "test_chip_compile.py": {
        **dict.fromkeys(("test_gbm_boost_chunk",
                         "test_gbm_boost_chunk_at_the_benchmark_cell",
                         "test_predict_forest",
                         "test_predict_forest_at_the_benchmark_cell",
                         "test_serving_jit_with_donation"), "tiny_gbm"),
        **dict.fromkeys(("test_glm_irls_solve_on_codes",
                         "test_glm_irls_solve_on_codes_with_the_kernel"),
                        "codes_glm"),
        **dict.fromkeys(("test_dl_train_chunk",
                         "test_dl_train_chunk_at_the_benchmark_cell"),
                        "tiny_dl")},
    # the Pallas interpreter's programs, one or two a test
    "test_glm_cat_gram_kernel.py": None,
}


@pytest.fixture(autouse=True, scope="session")
def _cloud():
    """Boot the cloud once per session (stall_till_cloudsize analogue)."""
    import h2o3_tpu
    cpu = jax.devices("cpu")
    jax.config.update("jax_default_device", cpu[0])
    h2o3_tpu.init(backend="cpu")
    info = h2o3_tpu.cluster_info()
    assert info["cloud_size"] == 8, info
    yield
    h2o3_tpu.shutdown()


@pytest.fixture(autouse=True)
def _check_keys(request):
    """Leak check — the water/runner/CheckKeysTask analogue: every key a
    test (or its function-scoped fixtures) creates must be gone from the
    DKV when the test ends, and the Scope stack must balance.

    The fixture brackets the test in a Scope, so keys created on the
    test's own thread are swept automatically; anything still present
    afterwards (e.g. keys put by background threads, which thread-local
    Scope tracking cannot see) fails the test. Tests that intentionally
    leave keys — REST servers creating objects on handler threads,
    cross-test module state — opt out with @pytest.mark.allow_key_leak
    (which also skips the sweep)."""
    if request.node.get_closest_marker("allow_key_leak"):
        yield
        return
    from h2o3_tpu.core.kv import DKV
    from h2o3_tpu.core.scope import Scope, _stack
    baseline = set(DKV.keys())
    depth = len(_stack())
    Scope().__enter__()
    try:
        yield
    finally:
        # unwind this fixture's scope plus any scope the test entered
        # and failed to exit (each exit sweeps its tracked keys)
        unbalanced = len(_stack()) - depth - 1
        while len(_stack()) > depth:
            _stack()[-1].__exit__(None, None, None)
        # flight-recorder capsules (<job>_telemetry) are INTENTIONAL
        # retained artifacts — bounded by H2O3TPU_FLIGHT_RECORDER_KEEP,
        # created on worker threads the thread-local Scope cannot see.
        # Sweep them between tests but don't flag them as leaks (a
        # CANCELLED job's capsule is still asserted swept by its own
        # Scope in tests/test_flight_recorder.py).
        from h2o3_tpu.telemetry.flight_recorder import TELEMETRY_SUFFIX
        leaked = [k for k in DKV.keys() if k not in baseline
                  and not k.endswith(TELEMETRY_SUFFIX)]
        for k in list(DKV.keys()):
            if k not in baseline and k.endswith(TELEMETRY_SUFFIX):
                DKV.remove(k)
        # orphaned FitCheckpointer debris (ISSUE 9): a test that killed
        # or failed a checkpointed fit may leave *.fitsnap.tmp files or
        # an empty partial snapshot dir behind — sweep them so one
        # test's crash-sim cannot poison a later resume test
        from h2o3_tpu.core import recovery as _recovery
        _recovery.sweep_fit_checkpoints()
        # orphaned Cleaner ice files (ISSUE 11): a test that spilled a
        # frame and then removed or clobbered its key without touching
        # the stub leaves hex://spill/*.npz debris — sweep files no
        # live stub references so spills cannot accumulate across the
        # suite (mirrors the *.fitsnap.tmp sweep above)
        _sweep_orphan_spills(baseline)
        # orphaned mirror blobs (ISSUE 18): a durability-mode test that
        # crashed mid-write leaves *.framesnap.tmp debris, and a test
        # that dropped keys without the remove hook leaves unregistered
        # *.framesnap blobs — sweep both (mirrors the fitsnap.tmp and
        # spill-npz sweeps above)
        from h2o3_tpu.core import durability as _durability
        _durability.sweep_debris()
        for k in leaked:    # sweep so one leak cannot cascade
            # a leaked RUNNING job is a live worker thread that would
            # keep writing keys after the sweep — cancel it (observed
            # cooperatively at the next chunk boundary) and wait
            # briefly before removing its key
            v = DKV.get_raw(k)
            if getattr(v, "status", None) == "RUNNING" \
                    and hasattr(v, "cancel"):
                v.cancel()
                try:
                    v.join(10.0)
                except Exception:
                    pass
            DKV.remove(k)
    assert unbalanced <= 0, \
        f"{unbalanced} Scope(s) entered but never exited"
    assert not leaked, \
        f"{len(leaked)} DKV key(s) leaked: {sorted(leaked)[:10]}"


@pytest.fixture(autouse=True)
def _check_trace_context():
    """Trace-context leak check (ISSUE 16): a test that installs a
    TraceContext (trace_scope / install) must uninstall it — a leaked
    context would silently stamp every later test's spans with a stale
    trace id. Mirrors the DKV/Scope sweep: defensively reset, then
    fail the test that leaked."""
    from h2o3_tpu.telemetry import trace_context
    yield
    leaked = trace_context.current()
    trace_context._reset()
    assert leaked is None, \
        f"TraceContext leaked across test boundary: {leaked.to_dict()}"


def _sweep_orphan_spills(baseline) -> None:
    """Delete spill npz files in the ice dir that no in-DKV stub still
    references (hex://spill/* — io/persist.py _IceDriver layout)."""
    import glob
    import tempfile
    from h2o3_tpu.core.kv import DKV
    ice_root = os.environ.get(
        "H2O3_TPU_ICE_DIR",
        os.path.join(tempfile.gettempdir(), "h2o3_tpu_ice"))
    files = glob.glob(os.path.join(ice_root, "spill", "*.npz"))
    if not files:
        return
    live = set()
    for k in list(DKV.keys()):
        v = DKV.get_raw(k)
        uri = getattr(v, "uri", None)
        if getattr(v, "_is_lazy_stub", False) and uri:
            live.add(os.path.basename(uri))
        del v
    for p in files:
        if os.path.basename(p) not in live:
            try:
                os.unlink(p)
            except OSError:
                pass


@pytest.fixture()
def rng():
    return np.random.RandomState(42)


def make_classification(n=4000, f=8, seed=0, informative=4):
    """Synthetic binary problem with known signal (TestFrameCatalog role)."""
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    logits = X[:, :informative] @ r.uniform(0.5, 2.0, informative)
    p = 1 / (1 + np.exp(-logits))
    y = (r.rand(n) < p).astype(int)
    return X, y


def make_regression(n=4000, f=8, seed=0, noise=0.1):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = X[:, 0] * 2.0 + np.sin(X[:, 1] * 2) + 0.5 * X[:, 2] * X[:, 3]
    y = y + noise * r.randn(n)
    return X, y


@pytest.fixture()
def classif_frame():
    import h2o3_tpu
    X, y = make_classification()
    cols = {f"x{i}": X[:, i] for i in range(X.shape[1])}
    cols["y"] = np.array(["no", "yes"], dtype=object)[y]
    return h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])


@pytest.fixture()
def regress_frame():
    import h2o3_tpu
    X, y = make_regression()
    cols = {f"x{i}": X[:, i] for i in range(X.shape[1])}
    cols["y"] = y
    return h2o3_tpu.Frame.from_numpy(cols)
