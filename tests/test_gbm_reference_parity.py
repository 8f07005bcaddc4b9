"""The GBM fit against the benchmark's plain float32 reference, and the
precision contract under it, as far as a CPU can hold them.

The configuration ``benchmark/configs/gbm-airlines-d6.json`` states
"float32 row statistics and sums". On the chip the MXU multiplies
bfloat16, so the program splits every float32 statistic into three
bfloat16 pieces (``ops/histogram.split3``) and adds three one-pass
products. The chip run decides whether that holds there; here:

(a) both level-pass paths (kernels in interpret mode, XLA) come out
    ``correct`` by the configuration's own limits, and the reference's
    bfloat16 control does not;
(b) the pieces add up to the statistic bit for bit, and a histogram made
    from them equals the float32 one;
(c) no product of the level pass, of the leaf pass or of ``segment_sum``
    sends a float32 operand through the MXU in one bfloat16 pass, by
    leaving its precision out or by naming one under HIGHEST (but for
    the partition kernel's product of two indicators) — so dropping a
    piece or a ``precision=`` fails here, not only on the chip;
(d) a ``gbm.chunk`` span says which path ran the levels;
(e) the program's numeric bin edges are the reference's ``quantile_cuts``
    of every row, also above the 200,000 rows it once sampled, and a
    row weight k counts as k rows.
"""

import json
import os

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from benchmark.adapters import gbm as gbm_adapter
from benchmark.generators import airlines
from benchmark.references import gbm as gbm_reference
from h2o3_tpu import telemetry
from h2o3_tpu.frame.binning import _numeric_edges
from h2o3_tpu.models.gbm import GBMEstimator
from h2o3_tpu.models.tree import TreeParams, grow_tree
from h2o3_tpu.ops import histogram as hist_ops
from h2o3_tpu.ops.pallas import treekernel as tk
from h2o3_tpu.ops.segments import segment_sum
from h2o3_tpu.parallel.mesh import get_mesh, padded_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "gbm-airlines-d6.json")) as f:
    CONFIG = json.load(f)
ROWS, NTREES, SEED = 20_000, 3, 2900000029
PARAMS = dict(CONFIG["reference_params"], ntrees=NTREES)
PATHS = {"kernel": "interpret", "xla": "off"}      # H2O3TPU_PALLAS
HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def data():
    return airlines.generate(SEED, ROWS)


@pytest.fixture(scope="module")
def fits(data):
    """One fit a level-pass path on the generated frame, read out as the
    benchmark's adapter reads it, with the fit's ``gbm.chunk`` spans."""
    out = {}
    frame = h2o3_tpu.Frame.from_numpy(data["columns"],
                                      domains=data["domains"])
    for path, knob in PATHS.items():
        before = {s["id"] for s in telemetry.spans_snapshot(1 << 20)}
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("H2O3TPU_PALLAS", knob)
            model = GBMEstimator(
                **dict(CONFIG["estimator"]["params"], ntrees=NTREES,
                       seed=SEED % (2 ** 31 - 1))
            ).train(frame, y=data["response"])
        chunks = [s["meta"] for s in telemetry.spans_snapshot(1 << 20)
                  if s["name"] == "gbm.chunk" and s["id"] not in before]
        out[path] = {"outputs": gbm_adapter.read_outputs(model),
                     "chunks": chunks}
        h2o3_tpu.DKV.remove(model.key)
    h2o3_tpu.DKV.remove(frame.key)
    return out


# ---- (a) the fit against the plain reference ------------------------------

@pytest.mark.parametrize("path", sorted(PATHS))
def test_fit_is_correct_by_the_configurations_limits(data, fits, path):
    numbers = gbm_reference.check(data, fits[path]["outputs"], PARAMS)
    assert set(numbers) == set(gbm_reference.NAMES)
    over = {k: (v, CONFIG["limits"][k]) for k, v in numbers.items()
            if not v <= CONFIG["limits"][k]}
    assert not over, f"{path} path over its limits: {over}"


def test_both_paths_build_the_same_model(fits):
    a, b = (fits[p]["outputs"] for p in sorted(PATHS))
    for k in ("feat", "is_split", "value", "left_set", "leaf", "leaf_rows"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_the_bfloat16_control_is_not_correct(data):
    numbers = gbm_reference.check(
        data, gbm_reference.control(data, PARAMS), PARAMS)
    assert any(v > CONFIG["limits"][k] for k, v in numbers.items()), numbers


# ---- (b) the piece split ----------------------------------------------------

def _wide_floats(n, seed):
    r = np.random.RandomState(seed)
    v = (10.0 ** r.uniform(-30, 30, n)) * r.choice([-1.0, 1.0], n)
    return v.astype(np.float32)


def test_pieces_add_up_bit_for_bit():
    v = np.concatenate([_wide_floats(50_000, 1),
                        np.float32([0.0, -0.0, 1.0, -1.0, 1 / 3, 2 ** 24 - 1,
                                    16777217.0, 1e-30, -1e30])])
    hi, mid, lo = (np.asarray(p) for p in hist_ops.split3(jnp.asarray(v)))
    for p in (hi, mid, lo):                   # each piece IS a bfloat16
        np.testing.assert_array_equal(
            p, np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                          .astype(jnp.float32)))
    np.testing.assert_array_equal((hi + mid) + lo, v)
    np.testing.assert_array_equal(hi.astype(np.float64) + mid + lo,
                                  v.astype(np.float64))


def _highest_histogram(bins, nid, stats, L, B):
    """[3L, F·B] float32 sums, one HIGHEST product of plain one-hots."""
    N, F = bins.shape
    right = (bins[:, :, None] == jnp.arange(B)[None, None, :]) \
        .reshape(N, F * B).astype(jnp.float32)
    node = (nid[:, None] == jnp.arange(L)[None, :]).astype(jnp.float32)
    left = (node[:, :, None] * stats.T[:, None, :]).reshape(N, 3 * L)
    return jax.lax.dot_general(left.T, right, (((1,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


@pytest.mark.parametrize("kind", ["integers", "wide"])
def test_histogram_from_pieces_is_the_float32_histogram(kind):
    N, F, B, L = 2048, 3, 9, 4
    r = np.random.RandomState(7)
    bins = jnp.asarray(r.randint(0, B, (N, F)).astype(np.int32))
    nid = jnp.asarray(r.randint(0, L, N).astype(np.int32))
    if kind == "integers":
        # 17-bit integers, sums under 2^24: every float32 order is exact,
        # so the pieces have to give the HIGHEST product bit for bit —
        # and bfloat16-rounded statistics cannot
        stats = r.randint(-(1 << 16), 1 << 16, (3, N)).astype(np.float32)
    else:
        stats = _wide_floats(3 * N, 3).reshape(3, N) * 1e-10
    stats = jnp.asarray(stats)
    acc = hist_ops._block_hist(bins, nid[None, :], stats, L, B)
    assert acc.shape == (hist_ops.piece_rows(L), F * B)
    got = np.asarray(hist_ops.sum_pieces(acc, L))
    want = np.asarray(_highest_histogram(bins, nid, stats, L, B))
    rounded = np.asarray(_highest_histogram(
        bins, nid, stats.astype(jnp.bfloat16).astype(jnp.float32), L, B))
    if kind == "integers":
        np.testing.assert_array_equal(got, want)
        assert np.abs(rounded - want).max() > 0
        return
    # one-hot sums of float64 truth and of magnitudes, cell by cell
    s64 = np.asarray(stats, np.float64)
    right = np.asarray(bins)[:, :, None] == np.arange(B)[None, None, :]
    right = right.reshape(N, F * B).astype(np.float64)
    node = (np.asarray(nid)[:, None] == np.arange(L)[None, :])
    left = (node[:, :, None] * s64.T[:, None, :]).reshape(N, 3 * L)
    truth, mag = left.T @ right, np.abs(left).T @ right
    eps = np.finfo(np.float32).eps
    # a float32 accumulation of ~20 rows a cell: a few ulp of the sum of
    # magnitudes; one bfloat16 pass is off by 2^-9 of it
    assert (np.abs(got - truth) <= 8 * eps * mag).all()
    assert (np.abs(want - truth) <= 8 * eps * mag).all()
    assert (np.abs(rounded - truth) > 64 * eps * mag).any()


# ---- (c) the precision contract ---------------------------------------------

def _sub_jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _dots(jaxpr, kernel=None):
    """``(eqn, kernel, jaxpr)`` for every ``dot_general`` of a jaxpr and
    of what it calls (jit, shard_map, scan, ...); ``kernel`` is the name
    of the ``pallas_call`` whose body holds it, ``jaxpr`` that body."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn, kernel, jaxpr
        inside = eqn.params.get("name") \
            if eqn.primitive.name == "pallas_call" else kernel
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _dots(sub, inside)


def _is_indicator(var, jaxpr) -> bool:
    """0/1 by construction: a boolean, carried through changes of shape
    and dtype and through selects between literal 0 and 1."""
    if isinstance(var, jex_core.Literal):
        return var.val in (0, 1)
    if var.aval.dtype == jnp.bool_:
        return True
    made = [e for e in jaxpr.eqns if var in e.outvars]
    if not made:
        return False
    name, args = made[0].primitive.name, made[0].invars
    if name in ("convert_element_type", "broadcast_in_dim", "reshape",
                "transpose", "squeeze"):
        return _is_indicator(args[0], jaxpr)
    where = name == "select_n" or (name in ("jit", "pjit")
                                   and made[0].params["name"] == "_where")
    return where and all(_is_indicator(a, jaxpr) for a in args)


def _one_pass_by_name(found, dots) -> bool:
    """The allow-list of float32 products that may NAME a precision
    under HIGHEST, one entry long: the ``tree_partition`` kernel's only
    product, the left-set block times the node indicator — both 0/1,
    exact in the MXU's one bfloat16 pass, and HIGHEST there costs the
    kernel 2.6x on a v5e. No kernel that sums statistics is on it, so
    naming ``Precision.DEFAULT`` on one of their products fails here."""
    eqn, kernel, jaxpr = found
    return (kernel == "tree_partition"
            and sum(j is jaxpr for _, _, j in dots) == 1
            and _is_indicator(eqn.invars[1], jaxpr))


def _left_to_one_pass(found, dots) -> bool:
    """A float32 operand that reaches the MXU as one bfloat16 pass: no
    precision named at all, or one under HIGHEST named off the
    allow-list."""
    eqn = found[0]
    if not any(v.aval.dtype == jnp.float32 for v in eqn.invars):
        return False
    named = eqn.params.get("precision")
    if named is None:
        return True
    named = named if isinstance(named, (tuple, list)) else (named,)
    return (any(p != HIGHEST for p in named)
            and not _one_pass_by_name(found, dots))


def _assert_contract(fn, *args, at_least=1):
    dots = list(_dots(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(dots) >= at_least, "the walk found no product to check"
    bad = [str(d[0]) for d in dots if _left_to_one_pass(d, dots)]
    assert not bad, "float32 operands in one bfloat16 pass:\n" + \
        "\n".join(bad)


def _tree_inputs(n=640, F=4, B=17):
    r = np.random.RandomState(0)
    npad = padded_rows(n)
    bins = jnp.asarray(r.randint(0, B, (npad, F)).astype(np.int8))
    w = jnp.asarray((np.arange(npad) < n).astype(np.float32))
    g = jnp.asarray(r.randn(npad).astype(np.float32))
    h = jnp.asarray(r.rand(npad).astype(np.float32) + 0.1)
    return bins, jnp.full((F,), B - 1, jnp.int32), w, g, h


@pytest.mark.parametrize("pallas", ["interpret", "off"])
@pytest.mark.parametrize("cats", [False, True])
def test_no_product_of_grow_tree_is_left_to_the_default(pallas, cats):
    bins, nb, w, g, h = _tree_inputs()
    F = bins.shape[1]
    params = TreeParams(max_depth=3, nbins_total=17, block_rows=256,
                        pallas=pallas,
                        cat_feats=(True, False) * (F // 2) if cats else ())
    _assert_contract(
        lambda bins, nb, w, g, h: grow_tree(
            bins, nb, w, g, h, jnp.ones((F,), bool), params=params,
            mesh=get_mesh()),
        bins, nb, w, g, h,
        # a level's histogram (and the kernel path's two more) + the leaves
        at_least=params.max_depth + 1)


def test_the_walk_sees_a_dropped_precision():
    """The contract test itself: a plain float32 product is caught, also
    inside a jit inside a scan."""
    x = jnp.ones((8, 8), jnp.float32)

    def inner(a):
        return jax.lax.scan(lambda c, _: (jax.jit(jnp.dot)(c, a), None),
                            a, None, length=2)[0]

    with pytest.raises(AssertionError, match="one bfloat16 pass"):
        _assert_contract(inner, x)
    # naming the one pass does not excuse it, off the allow-list
    with pytest.raises(AssertionError, match="one bfloat16 pass"):
        _assert_contract(lambda a: jnp.dot(
            a, a, precision=jax.lax.Precision.DEFAULT), x)
    _assert_contract(lambda a: jnp.dot(a, a, precision=HIGHEST), x)
    _assert_contract(lambda a: jnp.dot(a.astype(jnp.bfloat16),
                                       a.astype(jnp.bfloat16)), x)


@pytest.mark.parametrize("kernel,excused", [("tree_partition", True),
                                            ("tree_hist", False)])
def test_a_named_one_pass_is_excused_in_the_partition_kernel_alone(
        kernel, excused):
    from jax.experimental import pallas as pl

    def body(a_ref, b_ref, o_ref):
        o_ref[:] = jax.lax.dot_general(
            a_ref[:], jnp.where(b_ref[:] == 1, 1.0, 0.0).astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    def call(a, b):
        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True, name=kernel)(a, b)

    args = jnp.ones((8, 8), jnp.float32), jnp.ones((8, 128), jnp.int32)
    if excused:
        _assert_contract(call, *args)
    else:
        with pytest.raises(AssertionError, match="one bfloat16 pass"):
            _assert_contract(call, *args)


def test_no_product_of_segment_sum_is_left_to_the_default():
    nid = jnp.zeros((1024,), jnp.int32)
    vals = jnp.ones((1024, 3), jnp.float32)
    _assert_contract(lambda i, v: segment_sum(i, v, n_nodes=8,
                                              mesh=get_mesh()), nid, vals)


@pytest.mark.parametrize("d", [0, 2])
def test_no_product_of_the_kernel_body_is_left_to_the_default(d):
    C, F, B = 256, 4, 17
    _assert_contract(
        lambda b, i, s: tk._hist_block(b, i, s, n_nodes_h=max(2 ** d // 2, 1),
                                       n_bins=B, d=d),
        jnp.zeros((C, F), jnp.int8), jnp.zeros((1, C), jnp.int32),
        jnp.ones((3, C), jnp.float32), at_least=2)


# ---- (d) the chunk span says which path ran the levels ----------------------

@pytest.mark.parametrize("path,kernel,xla", [("kernel", 6, 0),
                                             ("xla", 0, 6)])
def test_chunk_span_names_the_level_paths(fits, path, kernel, xla):
    chunks = fits[path]["chunks"]
    assert chunks and sum(c["trees"] for c in chunks) == NTREES
    for meta in chunks:
        assert (meta["levels_kernel"], meta["levels_xla"]) == (kernel, xla)
        # a binomial GBM's float gradient and hessian: 3 statistics x 3
        # bfloat16 pieces a node, the plan models/tree.stats_plan gives
        assert meta["hist_operand_rows"] == 9


# ---- (e) bin edges from every row, as the reference cuts them ---------------

def _edge_columns(n=300_000):
    r = np.random.RandomState(5)
    return {"many-valued": r.standard_normal(n).astype(np.float32),
            "few-valued": r.randint(0, 2400, n).astype(np.int32)}


@pytest.mark.parametrize("kind", sorted(_edge_columns(1)))
def test_numeric_edges_are_the_references_cuts(kind):
    x = _edge_columns()[kind]
    want = gbm_reference.quantile_cuts(x, 64)
    assert len(want) == 63
    np.testing.assert_array_equal(
        _numeric_edges(x.astype(np.float64), 64), want)
    np.testing.assert_array_equal(
        _numeric_edges(x.astype(np.float64), 64, w=np.full(x.size, 3.0)),
        want)


@pytest.mark.parametrize("kind", sorted(_edge_columns(1)))
def test_a_row_weight_counts_as_that_many_rows(kind):
    x = _edge_columns()[kind]
    k = np.random.RandomState(6).randint(0, 4, x.size)   # zeros: rows out
    np.testing.assert_array_equal(
        _numeric_edges(x.astype(np.float64), 64, w=k.astype(np.float64)),
        gbm_reference.quantile_cuts(np.repeat(x, k), 64))
