"""The chip's compiler, without the chip: every program on the main path
of ``chip_smoke.py`` is compiled for a described TPU v5e (2x2) at the
smoke's real widths, in this process, with the installed libtpu.

What interpret mode cannot show, this does: a kernel Mosaic refuses, a
tile that outgrows the 16 MB of scoped VMEM, a program that outgrows
the chip's HBM, a missing all-reduce on the four-chip mesh. Nothing
runs — a compile that passes is not a chip run.

This is the ONE file of chip-compiler tests: only one process may hold
libtpu, so the topology is described inside a module-scoped fixture of
this file (never at import, in a skipif, in parametrize or in
conftest.py) and every compile happens in the test's own process with
the persistent compile cache off (a cache entry written for a described
device cannot be read back without one).

The programs are taken from the system itself: a tiny fit on the CPU
test mesh leaves each jitted entry point's call signature with the
compile observer; the test re-lowers that same call with the row axis
at the smoke's size, the arguments placed on the described devices, and
the tree-kernel mode that ``auto`` resolves to on a TPU.
"""

import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import h2o3_tpu
from h2o3_tpu.ops import pallas as plx
from h2o3_tpu.parallel import mesh as mesh_mod
from h2o3_tpu.telemetry import compile_observer

# the smoke's widths (chip_smoke.py): the airlines frame, binned
AIR_ROWS, F, B = 5_000_000, 10, 126
AIR_CATS = (False,) * 6 + (True,) * 3 + (False,)
GLM_ROWS, DL_ROWS, SCORE_ROWS = 2_000_000, 200_000, 100_000
HIGGS_ROWS = 11_000_000                  # benchmark cell glm-higgs.fit-11m
AIR48_ROWS = 48_000_000                  # cell gbm-airlines-d6.fit-48m (F, B)
MNIST1M_ROWS = 1_048_576                 # cell dl-mnist8m-200x200.fit-1m
CAT116_ROWS = 116_000_000                # cell glm-airlines-116m-cat.fit-116m
# that cell's factors (12, 31, 7, 29, 340, 340 levels) and two numerics
CAT_LEVELS = {"Month": 12, "DayofMonth": 31, "DayOfWeek": 7,
              "UniqueCarrier": 29, "Origin": 340, "Dest": 340}
TINY_ROWS = 3000
LEVELS = (0, 3, 5)                       # of depth-bucket 6

S = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]).reshape(n, 1),
                (mesh_mod.DATA_AXIS, mesh_mod.MODEL_AXIS))


@contextlib.contextmanager
def _as_global_mesh(mesh):
    """The jitted entry points read the process mesh while they trace;
    hand them the described chips for the length of one lowering."""
    old = mesh_mod.get_mesh()
    mesh_mod.set_global_mesh(mesh)
    try:
        yield mesh
    finally:
        mesh_mod.set_global_mesh(old)


def _auto_on_tpu(n_shards):
    mode, _ = plx.decide("auto", "tpu", n_shards, True)
    return mode


def _compiled_text(lowered):
    return lowered.compile().as_text()


# ------------------------------------------------------- the level pass


def _lower_level_pass(mesh, d):
    """One tree level as grow_tree runs it when ``auto`` resolves on a
    TPU: the kernels where the level fits a tile, else XLA."""
    from h2o3_tpu.models.tree import TreeScalars
    from h2o3_tpu.ops.pallas import treekernel as tk
    n = mesh_mod.padded_rows(AIR_ROWS, mesh)
    L = 2 ** d
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    rep = NamedSharding(mesh, P())
    kernels = (_auto_on_tpu(mesh.shape[mesh_mod.DATA_AXIS]) == "native"
               and plx.tile_rows(F, B, L) > 0)
    is_cat = jnp.asarray(np.array(AIR_CATS))
    sc = TreeScalars(jnp.float32(10.0), jnp.float32(1.0),
                     jnp.float32(1e-5), jnp.int32(6))
    kw = dict(d=d, n_nodes=L, n_bins=B, block_rows=4096, mesh=mesh)

    def level(bins, nid, stats, prev, cm, nb, lo, hi):
        if kernels:
            return tk.fused_level(bins, nid, stats, prev, cm, nb, is_cat,
                                  None, lo, hi, sc, interpret=False, **kw)
        return tk.xla_level(bins, nid, stats[0], stats[1], stats[2], prev,
                            cm, nb, is_cat, None, lo, hi, sc, **kw)

    prev = (S((L // 2, F, B, 3), jnp.float32, sharding=rep) if d else None)
    lowered = jax.jit(level).lower(
        S((n, F), jnp.int8, sharding=row), S((n,), jnp.int32, sharding=row),
        S((3, n), jnp.float32,
          sharding=NamedSharding(mesh, P(None, mesh_mod.DATA_AXIS))),
        prev, S((F,), jnp.bool_, sharding=rep),
        S((F,), jnp.int32, sharding=rep), S((L,), jnp.float32, sharding=rep),
        S((L,), jnp.float32, sharding=rep))
    return lowered, kernels


@pytest.mark.parametrize("d", LEVELS)
def test_level_pass_one_chip(topo, d):
    lowered, kernels = _lower_level_pass(_mesh(topo, 1), d)
    txt = _compiled_text(lowered)
    assert ("tpu_custom_call" in txt) == kernels


@pytest.mark.parametrize("d", LEVELS)
def test_level_pass_four_chips(topo, d):
    lowered, kernels = _lower_level_pass(_mesh(topo, 4), d)
    txt = _compiled_text(lowered)
    assert "all-reduce" in txt, "the histogram is not reduced over 'data'"
    assert ("tpu_custom_call" in txt) == kernels


@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_level_check(topo, chips):
    """chip_smoke.py's own kernels-vs-XLA program, as it runs there."""
    import chip_smoke
    mesh = _mesh(topo, chips)
    n = 65_536
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    vec = S((n,), jnp.float32, sharding=row)
    chip_smoke.level_check(mesh, B, np.array(AIR_CATS), interpret=False) \
        .lower(S((n, F), jnp.int8, sharding=row),
               S((F,), jnp.int32, sharding=NamedSharding(mesh, P())),
               vec, vec, vec).compile()


def test_auto_on_tpu_takes_the_kernels_at_the_smoke_widths():
    """What the two tests above compiled IS the kernel path."""
    assert _auto_on_tpu(1) == "native"
    assert all(plx.tile_rows(F, B, 2 ** d) > 0 for d in range(6))


# ------------------------------------------- every Pallas kernel, native


def _one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("d", LEVELS)
def test_tree_hist_kernel_native(topo, d):
    from h2o3_tpu.ops.pallas import treekernel as tk
    one, n, L = _one_chip(topo), 1 << 20, 2 ** d
    jax.jit(lambda b, i, s: tk._hist_call(
        b, i, s, d=d, n_nodes=L, n_bins=B,
        block_rows=plx.tile_rows(F, B, L), interpret=False)).lower(
        S((n, F), jnp.int8, sharding=one), S((1, n), jnp.int32, sharding=one),
        S((3, n), jnp.float32, sharding=one)).compile()


@pytest.mark.parametrize("d", LEVELS)
def test_tree_partition_kernel_native(topo, d):
    from h2o3_tpu.ops.pallas import treekernel as tk
    one, n, L = _one_chip(topo), 1 << 20, 2 ** d
    vec = lambda dt: S((L,), dt, sharding=one)   # noqa: E731
    jax.jit(lambda b, i, bf, bt, na, sp, cs, lm: tk._partition_call(
        b, i, bf, bt, na, sp, cs, lm, n_bins=B,
        block_rows=plx.tile_rows(F, B, L), interpret=False)).lower(
        S((F, n), jnp.int8, sharding=one), S((1, n), jnp.int32, sharding=one),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.bool_),
        vec(jnp.bool_), S((L, B - 1), jnp.bool_, sharding=one)).compile()


@pytest.mark.parametrize("n_stats,n_pieces", [(2, 1), (2, 3), (3, 3)])
def test_tree_frontier_hist_kernel_native(topo, n_stats, n_pieces):
    """The frontier levels' histogram kernel alone (no level sort: that
    compiles for 100 s) at the widths of drf-airlines-d20.fit-48m — 64
    nodes a block, 32 blocks a super-batch, 2^19 node slots — and at the
    operand heights its callers can ask for: 128 rows (the cell: two
    whole statistics), 384 (two real ones), 576 (three)."""
    from h2o3_tpu.models import frontier
    from h2o3_tpu.ops.histogram import piece_rows
    from h2o3_tpu.ops.pallas import treekernel as tk
    one = _one_chip(topo)
    lb, sb = frontier.NODE_BLOCK, frontier.SUPER_BLOCKS
    nblk = frontier.frontier_capacity(AIR48_ROWS, 20) // lb
    tile = plx.frontier_tile_rows(F, B, piece_rows(lb, n_stats, n_pieces),
                                  1 + 3 + n_stats)
    assert tile >= 1024
    n = AIR48_ROWS + frontier.CHUNK_ROWS
    n += -n % tile
    vec = lambda k, dt=jnp.int32: S((k,), dt, sharding=one)   # noqa: E731

    def call(step0, blk, tid, lo, hi, s, fid, w0, w1, w2, *stats):
        return tk.frontier_hist(
            (step0, blk, tid), lo, hi, s, fid, (w0, w1, w2), stats,
            lb=lb, sb=sb, n_features=F, n_bins=B, bits=8,
            n_pieces=n_pieces, tile=tile, interpret=False)
    # the schedule of a level between two sorts: a row in two ranges
    steps = frontier.range_blocks(frontier.SORT_PERIOD, lb) * (n // tile) \
        + nblk
    out = jax.jit(call).lower(
        vec(nblk + 1), vec(steps), vec(steps), vec(nblk), vec(nblk),
        S((), jnp.int32, sharding=one), vec(n),
        *[vec(n, jnp.uint32)] * 3, *[vec(n, jnp.float32)] * n_stats
    ).compile()
    # no operand of 48M rows is copied on its way into the kernel (the
    # temporaries are the kernel's [32, operand rows, 1280] products)
    assert out.memory_analysis().temp_size_in_bytes < 4 * n


def test_opt_in_histogram_kernel_native(topo):
    """ops/pallas_histogram.py, reachable only through its own switch,
    at the block size ops/histogram.py gives it."""
    from h2o3_tpu.ops.pallas_histogram import pallas_local_histogram
    one, n = _one_chip(topo), 1 << 16
    jax.jit(lambda b, i, s: pallas_local_histogram(
        b, i, s, 32, B, block_rows=512)).lower(
        S((n, F), jnp.int8, sharding=one), S((n,), jnp.int32, sharding=one),
        S((3, n), jnp.float32, sharding=one)).compile()


# --------------------------------- the fits' and the scorer's own programs


def _at_scale(tree, n_tiny, n_real, mesh):
    """A recorded call signature with the row axis grown to ``n_real``
    and every array placed on ``mesh`` (rows sharded over 'data')."""
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    rep = NamedSharding(mesh, P())

    def place(x):
        if not isinstance(x, S):
            return x
        if x.shape and x.shape[0] == n_tiny:
            return S((n_real,) + x.shape[1:], x.dtype, sharding=row)
        return S(x.shape, x.dtype, sharding=rep)

    return jax.tree_util.tree_map(place, tree)


def _lower_recorded(name, mesh, n_tiny, rows, gram_kernel=None,
                    **static_overrides):
    """``gram_kernel``: the factor Gram's mode a design held as codes
    carries into the lowering (the CPU fit that recorded it resolved
    ``off``)."""
    from h2o3_tpu.frame.datainfo import CodesDesign
    jit_fn, aargs, akwargs = compile_observer.aot_source(name)
    n_real = mesh_mod.padded_rows(rows, mesh)
    akwargs = dict(_at_scale(akwargs, n_tiny, n_real, mesh))
    akwargs.update(static_overrides)
    aargs = _at_scale(aargs, n_tiny, n_real, mesh)
    if gram_kernel is not None:
        aargs = [dataclasses.replace(a, gram_kernel=gram_kernel)
                 if isinstance(a, CodesDesign) else a for a in aargs]
    assert any(x.shape[:1] == (n_real,)
               for x in jax.tree_util.tree_leaves(aargs)
               if isinstance(x, S)), "no argument carries the row axis"
    with _as_global_mesh(mesh):
        return jit_fn.lower(*aargs, **akwargs)


@pytest.fixture(scope="module")
def tiny_gbm(tmp_path_factory):
    """A GBM fit on the smoke's schema, small, on the CPU test mesh."""
    from h2o3_tpu.io.stream import stream_import_csv
    from h2o3_tpu.models.gbm import GBMEstimator
    from h2o3_tpu.utils.synth import AIRLINES_RESPONSE, write_airlines_csv
    path = str(tmp_path_factory.mktemp("chipcompile") / "air.csv")
    write_airlines_csv(path, TINY_ROWS, seed=0)
    fr = stream_import_csv(path)
    model = GBMEstimator(ntrees=2, max_depth=6, seed=1).train(
        fr, y=AIRLINES_RESPONSE)
    assert model.bm.nbins_total == B and model.bm.bins.shape[1] == F
    assert tuple(bool(c) for c in model.bm.is_cat) == AIR_CATS
    yield model, fr.nrows_padded
    h2o3_tpu.DKV.remove(model.key)
    h2o3_tpu.DKV.remove(fr.key)


@pytest.mark.allow_key_leak          # the module-scoped fit above
@pytest.mark.parametrize("chips", [1, 4])
def test_gbm_boost_chunk(topo, tiny_gbm, chips):
    """The 25-tree compiled scan of the flagship fit, 5M rows."""
    _, n_tiny = tiny_gbm
    mesh = _mesh(topo, chips)
    tp = compile_observer.aot_source("gbm.boost_scan")[2]["tp"]
    txt = _compiled_text(_lower_recorded(
        "gbm.boost_scan", mesh, n_tiny, AIR_ROWS, ntrees=25,
        tp=dataclasses.replace(tp, pallas=_auto_on_tpu(chips))))
    assert "tpu_custom_call" in txt
    assert ("all-reduce" in txt) == (chips > 1)


@pytest.mark.allow_key_leak
def test_gbm_boost_chunk_at_the_benchmark_cell(topo, tiny_gbm):
    """The 2-tree scan of ``gbm-airlines-d6.fit-48m`` as the fit asks for
    it: 48M rows on one chip, depth 6, the row blocks ``_fit`` takes
    past 8M rows. Every level of both trees runs the two kernels (the
    scan's body holds them once), and the program fits the chip."""
    from h2o3_tpu.models.tree import kernel_levels
    _, n_tiny = tiny_gbm
    tp = compile_observer.aot_source("gbm.boost_scan")[2]["tp"]
    tp = dataclasses.replace(tp, pallas=_auto_on_tpu(1), block_rows=16384)
    assert tp.max_depth == 6 and all(kernel_levels(tp, F))
    txt = _compiled_text(_lower_recorded(
        "gbm.boost_scan", _mesh(topo, 1), n_tiny, AIR48_ROWS, ntrees=2,
        tp=tp))
    assert txt.count("tpu_custom_call") >= 2 * tp.max_depth
    assert "all-reduce" not in txt


@pytest.mark.allow_key_leak
def test_predict_forest(topo, tiny_gbm):
    from h2o3_tpu.models.tree import predict_forest
    model, _ = tiny_gbm
    one = _one_chip(topo)
    forest = jax.tree_util.tree_map(
        lambda a: S((50,) + a.shape[1:], a.dtype, sharding=one),
        model.forest)
    predict_forest.lower(forest, S((SCORE_ROWS, F), jnp.int8, sharding=one),
                         B=B).compile()


def _gather_operand_shapes(txt):
    """The shape of the table each ``gather`` of a compiled module reads
    (its first operand, looked up by name where it is defined)."""
    shape_of = dict(re.findall(
        r"%([\w.\-]+) = \w+\[([\d,]*)\]", txt))
    return [tuple(int(n) for n in shape_of[op].split(",") if n)
            for op in re.findall(r" gather\(%([\w.\-]+),", txt)]


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bool_, jnp.uint32])
def test_a_lookup_from_select_nodes_entries_is_selects(topo, dtype):
    """models/tree.SELECT_NODES is the chip compiler's rule, not ours:
    a per-row lookup from a table that wide compiles to selects."""
    from h2o3_tpu.models.tree import SELECT_NODES
    one = _one_chip(topo)
    txt = _compiled_text(jax.jit(lambda t, i: t[i]).lower(
        S((SELECT_NODES,), dtype, sharding=one),
        S((1 << 20,), jnp.int32, sharding=one)))
    assert not _gather_operand_shapes(txt)


@pytest.mark.allow_key_leak
def test_predict_forest_at_the_benchmark_cell(topo, tiny_gbm):
    """The end-of-fit re-scoring of ``gbm-airlines-d6.fit-48m``: 2 trees
    of depth 6 over the 48M resident rows, three categorical columns,
    four bitset words a node. Routing a level costs selects and nothing
    else: a per-row gather from the 2-D ``[Lmax, W]`` words table took
    12.4 ns a row, 27 s of a 35 s job (PERF.md §6, PR 30) — it must not
    come back through a refactor, nor as an ``[N, W]`` / ``[N, L]``
    intermediate, which the chip pads to 128 lanes (25 GB here)."""
    from h2o3_tpu.models.tree import predict_forest
    model, _ = tiny_gbm
    one = _one_chip(topo)
    forest = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype, sharding=one), model.forest)
    assert forest.left_words.shape == (2, 6, 32, (B - 1 + 31) // 32)
    n = mesh_mod.padded_rows(AIR48_ROWS, _mesh(topo, 1))
    compiled = predict_forest.lower(
        forest, S((n, F), jnp.int8, sharding=one), B=B).compile()
    tables = _gather_operand_shapes(compiled.as_text())
    assert not [t for t in tables if len(t) > 1], (
        f"per-row gathers from 2-D tables {tables}: the words table again?")
    # every table of these levels is small enough for the chip's
    # compiler to take the lookup as selects; a gather left here is a
    # lookup at ~12 ns a row where the others cost under one
    assert not tables, f"per-row gathers from tables of shape {tables}"
    # sixteen [N] vectors of 4 B live between a level's fusions (3.14 GB,
    # the parent's too); one [N, k] vector padded to 128 lanes is 6-25 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


@pytest.mark.allow_key_leak
def test_serving_jit_with_donation(topo, tiny_gbm, monkeypatch):
    """serving/engine.py's accelerator branch: the donated-input jit of
    the model's scoring program, at the 8-row bucket."""
    from h2o3_tpu.serving.engine import donating_jit
    model, _ = tiny_gbm
    # closed-over device arrays would pin the lowering to the CPU mesh
    monkeypatch.setattr(model, "forest", jax.tree_util.tree_map(
        np.asarray, model.forest))
    donating_jit(model).lower(
        S((8, F), jnp.int8, sharding=_one_chip(topo))).compile()


def _element_count(dims):
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def _row_sized_moves(txt, n):
    """The ``copy`` and ``dynamic-slice`` instructions of a compiled
    module whose operand holds at least ``n`` elements: a relayout of a
    row-sized array, or a block cut out of one."""
    shape_of = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", txt))
    return [(op, shape_of[src]) for op, src in re.findall(
        r" (copy|dynamic-slice)\(%([\w.\-]+)", txt)
        if src in shape_of and _element_count(shape_of[src]) >= n]


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("rows,batch", [(GLM_ROWS, None), (HIGGS_ROWS, None),
                                        (HIGGS_ROWS, 8)])
def test_glm_irls_solve(topo, chips, rows, batch):
    """The IRLS solve at the smoke's rows and at ``glm-higgs.fit-11m``'s,
    alone and ``vmap``-batched over 8 (alpha, lambda) lanes. The Gram
    reads the design matrix in the layout it arrives in: no relayout
    copy of it and no row block sliced out of it, and no temporaries as
    large as the matrix (a blocked scan held 2.69 GB of them at the
    cell's rows on one chip, against the matrix's 1.28 GB)."""
    from h2o3_tpu.models.glm import GLMEstimator, _irls_solve_batched
    r = np.random.RandomState(3)
    X = r.randn(TINY_ROWS, 28).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(28)}
    cols["y"] = np.array(["b", "s"], object)[(X[:, 0] > 0).astype(int)]
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["y"])
    GLMEstimator(family="binomial", solver="irlsm", lambda_=0.0,
                 max_iterations=2, standardize=True).train(fr, y="y")
    mesh = _mesh(topo, chips)
    n_real = mesh_mod.padded_rows(rows, mesh)
    if batch:
        # the recorded single solve's arguments, l1 / l2 / objective
        # epsilon stacked on the vmapped axis as fit_glm_batched does
        _, aargs, akwargs = compile_observer.aot_source("glm.irls_solve")
        aargs = list(_at_scale(aargs, fr.nrows_padded, n_real, mesh))
        aargs[5] = aargs[6] = aargs[13] = S(
            (batch,), jnp.float32, sharding=NamedSharding(mesh, P()))
        with _as_global_mesh(mesh):
            lowered = _irls_solve_batched.__wrapped__.lower(*aargs,
                                                           **akwargs)
    else:
        lowered = _lower_recorded("glm.irls_solve", mesh, fr.nrows_padded,
                                  rows)
    compiled = lowered.compile()
    txt = compiled.as_text()
    assert ("all-reduce" in txt) == (chips > 1)
    n_local = n_real // chips
    assert not _row_sized_moves(txt, n_local)
    x_bytes = n_local * 29 * 4                   # X1: 28 columns + intercept
    assert compiled.memory_analysis().temp_size_in_bytes < x_bytes


def _row_by_width_arrays(txt, n, width):
    """Shapes of a compiled module with a dimension of at least ``n``
    elements and ``width`` or more elements beside it."""
    out = []
    for dims in re.findall(r"\w+\[([\d,]+)\]", txt):
        d = [int(x) for x in dims.split(",") if x]
        if max(d) >= n and np.prod(d) // max(d) >= width:
            out.append(tuple(d))
    return out


@pytest.mark.parametrize("chips", [1, 4])
def test_glm_irls_solve_on_codes(topo, chips):
    """The IRLS solve of ``glm-airlines-116m-cat.fit-116m`` at its 116M
    rows: the design held as codes, 756 coefficients. No array of rows by
    coefficients (351 GB) is in the program, nor rows by any width past
    the line search's nine candidates — the factor Gram and the lookups
    walk a shard's rows a chunk at a time; the program fits the chip."""
    _solve_on_codes(topo, chips, None)


@pytest.mark.parametrize("chips", [1, 4])
def test_glm_irls_solve_on_codes_with_the_kernel(topo, chips):
    """The same solve with the factor Gram as the Pallas kernel
    (``ops/pallas/gramkernel.py``), the mode ``auto`` resolves to on a
    TPU: the same asserts, and the kernel in the program."""
    assert _auto_on_tpu(chips) == "native"
    txt = _solve_on_codes(topo, chips, "native")
    assert "tpu_custom_call" in txt and "glm_cat_gram" in txt


def _solve_on_codes(topo, chips, gram_kernel):
    from h2o3_tpu.models.glm import GLMEstimator
    r = np.random.RandomState(4)
    cols = {f: r.randint(0, L, TINY_ROWS) for f, L in CAT_LEVELS.items()}
    cols["DepTime"] = r.randint(0, 2400, TINY_ROWS)
    cols["Distance"] = r.randint(30, 4983, TINY_ROWS)
    cols["y"] = r.randint(0, 2, TINY_ROWS)
    doms = {f: [f"{f}{i:03d}" for i in range(L)]
            for f, L in CAT_LEVELS.items()}
    doms["y"] = ["NO", "YES"]
    fr = h2o3_tpu.Frame.from_numpy(cols, domains=doms)
    GLMEstimator(family="binomial", solver="irlsm", lambda_=0.0,
                 max_iterations=1).train(fr, y="y")
    mesh = _mesh(topo, chips)
    compiled = _lower_recorded("glm.irls_solve", mesh, fr.nrows_padded,
                               CAT116_ROWS, gram_kernel=gram_kernel).compile()
    txt = compiled.as_text()
    n_local = mesh_mod.padded_rows(CAT116_ROWS, mesh) // chips
    assert "gram.cat" in txt and "glm.eta" in txt
    assert ("all-reduce" in txt) == (chips > 1)
    assert not _row_by_width_arrays(txt, n_local, 10)
    mem = compiled.memory_analysis()
    # the frame's codes and the numerics are the arguments; what the
    # solve adds is row-sized vectors (3.3 GB on one chip)
    assert mem.temp_size_in_bytes < 40 * n_local
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    return txt


def _cat116_design(mesh, gram_kernel):
    """The cell's design as shapes: six factors (first levels dropped),
    DepTime, Distance and the intercept, 116M rows over ``mesh``."""
    from h2o3_tpu.frame.datainfo import CodesDesign
    n = mesh_mod.padded_rows(CAT116_ROWS, mesh)
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    factors, off = [], 0
    for card in CAT_LEVELS.values():
        factors.append((off, 1, card))
        off += card - 1
    X = CodesDesign(
        codes=tuple(S((n,), jnp.int32, sharding=row) for _ in factors),
        nas=tuple(S((n,), jnp.bool_, sharding=row) for _ in factors),
        dense=S((n, 3), jnp.float32, sharding=row), factors=tuple(factors),
        dense_cols=(off, off + 1, off + 2), p=off + 3,
        gram_kernel=gram_kernel)
    return X, S((n,), jnp.float32, sharding=row), n


@pytest.mark.parametrize("chips", [1, 4])
def test_glm_cat_gram_kernel_native(topo, chips):
    """The factor Gram's kernel at the cell's widths and rows: 116M rows
    on one chip, a quarter a chip on four. It compiles; within the
    kernel's VMEM budget (compiled with its operands as they arrive,
    held to ``vmem_limit_bytes`` = ``VMEM_BUDGET_BYTES``: the fused form
    is placed without that limit); its operands reach it as views, with
    their producers (the NA select, the weights' slices, the numerics'
    transpose) fused into it — no row-sized temporary."""
    from h2o3_tpu.ops import gram as gram_mod
    from h2o3_tpu.ops.pallas import gramkernel
    mesh = _mesh(topo, chips)
    X, vec, n = _cat116_design(mesh, "native")
    geo = gram_mod._kernel_geometry(X)
    assert geo.widths == (6, 11, 28, 30, 339, 339)
    assert gramkernel.fits(geo, gram_mod.CAT_KERNEL_ROWS)
    with _as_global_mesh(mesh):
        compiled = jax.jit(lambda X, w, z: gram_mod.gram(
            X, w, z, mesh=mesh)).lower(X, vec, vec).compile()
    txt = compiled.as_text()
    assert "glm_cat_gram" in txt
    assert ("all-reduce" in txt) == (chips > 1)
    n_local = n // chips
    assert compiled.memory_analysis().temp_size_in_bytes < n_local
    one = _one_chip(topo)
    rows = lambda *s: S(s, jnp.float32, sharding=one)   # noqa: E731
    unfused = jax.jit(lambda ks, w, wz, d: gramkernel.cat_gram_sums(
        ks, w, wz, d, geo=geo, block=gram_mod.CAT_KERNEL_ROWS,
        interpret=False, fuse_inputs=False)).lower(
        [S((n_local,), jnp.int32, sharding=one)] * 6, rows(n_local),
        rows(n_local), rows(n_local, 3)).compile()
    assert unfused.memory_analysis().temp_size_in_bytes < n_local


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("categorical", [True, False])
def test_glm_response_on_device(topo, categorical, chips):
    """models/model.py's response program at the benchmark cell's rows:
    elementwise on row-sharded columns, so no chip talks to another."""
    from h2o3_tpu.models.model import _response_program
    mesh = _mesh(topo, chips)
    n = mesh_mod.padded_rows(HIGGS_ROWS, mesh)
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    with _as_global_mesh(mesh):
        lowered = _response_program.lower(
            S((n,), jnp.int32 if categorical else jnp.float32, sharding=row),
            S((n,), jnp.bool_, sharding=row),
            S((n,), jnp.float32, sharding=row),
            categorical=categorical, dtype="float32")
    txt = _compiled_text(lowered)
    assert "all-gather" not in txt and "all-reduce" not in txt
    assert "collective-permute" not in txt and "all-to-all" not in txt


def _row_sized_constants(hlo_text, n):
    """Shapes of the program's literal constants with a dimension of at
    least ``n // 2`` (a folded row mask would be one)."""
    return [m for m in re.findall(r"= \w+\[([\d,]+)\]\S* constant\(",
                                  hlo_text)
            if max(int(d) for d in m.split(",")) >= n // 2]


def _assert_rows_stay_on_the_chip(txt, n):
    assert "callback" not in txt and "infeed" not in txt
    assert "outfeed" not in txt
    assert not _row_sized_constants(txt, n)
    # scalars may be all-reduced; nothing is gathered or moved
    assert "all-gather" not in txt and "all-to-all" not in txt
    assert "collective-permute" not in txt


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("response", ["binomial", "numeric_weighted",
                                      "seven_classes"])
def test_gbm_row_state_on_device(topo, response, chips):
    """models/model.py's row-state program at the rows of the cell
    gbm-airlines-d6.fit-48m: the row mask is an iota compared with a
    scalar (nothing row-sized is folded in or fed from the host), the
    rows never leave their chip — only block partials and scalars do —
    and the temporaries stay a few row vectors."""
    from h2o3_tpu.models.model import SUM_BLOCK_ROWS, _row_state_program
    mesh = _mesh(topo, chips)
    n = mesh_mod.padded_rows(AIR48_ROWS, mesh)
    assert n == 48_234_496
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    vec = lambda dt: S((n,), dt, sharding=row)   # noqa: E731
    weighted = response == "numeric_weighted"
    with _as_global_mesh(mesh):
        lowered = _row_state_program.lower(
            S((), jnp.int32),
            vec(jnp.float32 if weighted else jnp.int32), vec(jnp.bool_),
            vec(jnp.float32) if weighted else None,
            vec(jnp.bool_) if weighted else None,
            vec(jnp.float32) if weighted else None,
            categorical=not weighted,
            nclass=7 if response == "seven_classes" else 0)
    compiled = lowered.compile()
    _assert_rows_stay_on_the_chip(compiled.as_text(), n)
    w, y, summary = compiled.output_shardings
    assert w.is_equivalent_to(row, 1) and y.is_equivalent_to(row, 1)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, lowered.out_info[2])
    assert shapes["w"] == (n // SUM_BLOCK_ROWS,)
    # w and y (in the outputs), and beside them at most four row vectors
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 4 * n / chips


@pytest.mark.parametrize("chips", [1, 4])
def test_valid_mask_on_device(topo, chips):
    """parallel/mesh.py valid_mask at the same rows: made where it
    lies, from no argument but the row count."""
    mesh = _mesh(topo, chips)
    n = mesh_mod.padded_rows(AIR48_ROWS, mesh)
    row = NamedSharding(mesh, P(mesh_mod.DATA_AXIS))
    compiled = mesh_mod._valid_mask_program.lower(
        S((), jnp.int32), npad=n, sharding=row).compile()
    _assert_rows_stay_on_the_chip(compiled.as_text(), n)
    assert compiled.output_shardings.is_equivalent_to(row, 1)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n / chips


@pytest.fixture(scope="module")
def tiny_dl():
    """A DeepLearning fit at the MNIST widths, small, on the CPU test
    mesh: leaves ``dl.train_chunk``'s call with the compile observer."""
    from h2o3_tpu.models.deeplearning import DeepLearningEstimator
    r = np.random.RandomState(5)
    X = (r.rand(TINY_ROWS, 784) > 0.8).astype(np.float32)
    cols = {f"p{i}": X[:, i] for i in range(784)}
    cols["label"] = r.randint(0, 10, TINY_ROWS).astype(str)
    fr = h2o3_tpu.Frame.from_numpy(cols, categorical=["label"])
    model = DeepLearningEstimator(
        hidden=[200, 200], activation="rectifier", epochs=0.1,
        seed=1).train(fr, y="label")
    yield fr.nrows_padded
    h2o3_tpu.DKV.remove(model.key)
    h2o3_tpu.DKV.remove(fr.key)


@pytest.mark.allow_key_leak          # the module-scoped fit above
def test_dl_train_chunk(topo, tiny_dl):
    # the smoke's fit: 200k rows give a 2048-row batch, 200-step chunks
    _lower_recorded("dl.train_chunk", _mesh(topo, 1), tiny_dl,
                    DL_ROWS, n=DL_ROWS, batch=2048, nsteps=200).compile()


@pytest.mark.allow_key_leak
def test_dl_train_chunk_at_the_benchmark_cell(topo, tiny_dl):
    """The chunk of ``dl-mnist8m-200x200.fit-1m`` as the fit asks for
    it: the 1,048,576 x 784 float32 design matrix on one chip, batches
    of 16,384 rows, bfloat16 operands, 200 static steps. It lowers under
    the name the benchmark's readers look for, and what it asks of the
    chip beside the resident matrix stays a small part of it."""
    lowered = _lower_recorded(
        "dl.train_chunk", _mesh(topo, 1), tiny_dl, MNIST1M_ROWS,
        n=MNIST1M_ROWS, batch=16384, nsteps=200, bf16=True)
    assert "module @jit__train_steps_fused" in lowered.as_text()[:400]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    matrix = MNIST1M_ROWS * 784 * 4
    assert matrix <= mem.argument_size_in_bytes < matrix + 64e6
    # the compiler hoists the operands' rounding out of the scan: ONE
    # bfloat16 copy of the matrix (1.64 GB) a chunk, then a batch, its
    # activations and their gradients — never a second float32 matrix
    assert matrix // 2 <= mem.temp_size_in_bytes < matrix // 2 + 256e6
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
