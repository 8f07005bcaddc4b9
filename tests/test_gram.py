"""ops/gram.gram — X'WX, X'Wz and the weight sum of a row-sharded design
matrix — against a float64 NumPy reference (the Gram.java role under
GLM's IRLS, GAM, GLRM's init and PCA)."""

import jax
import numpy as np
import pytest

from h2o3_tpu.ops.gram import gram
from h2o3_tpu.parallel import mesh as mesh_mod


def _mesh(data):
    return mesh_mod.make_mesh(jax.devices("cpu")[:data], data_axis=data,
                              model_axis=1)


def _gram(X, w, z, data):
    return [np.asarray(a, np.float64) for a in jax.jit(
        lambda X, w, z: gram(X, w, z, mesh=_mesh(data)))(X, w, z)]


@pytest.mark.parametrize("data", [1, 4])
@pytest.mark.parametrize("cols", [1, 29, 300])
@pytest.mark.parametrize("rows", [1, 7, 8193, 20_001])
def test_gram_matches_float64(rows, cols, data):
    """Row counts that are no multiple of 8, 128 or 8192; the trailing
    eighth of the rows weighted 0 with large values in them, as a
    frame's padding rows are; one chip's shard and four."""
    r = np.random.RandomState(rows * 1000 + cols)
    X = r.randn(rows, cols).astype(np.float32)
    w = r.rand(rows).astype(np.float32)
    z = r.randn(rows).astype(np.float32)
    pad = rows // 8
    if pad:
        X[-pad:] *= 1e3
        w[-pad:] = 0.0
    xtx, xtz, ws = _gram(X, w, z, data)

    X64, w64 = X.astype(np.float64), w.astype(np.float64)
    wz64 = w64 * z.astype(np.float64)
    assert xtx.shape == (cols, cols) and xtz.shape == (cols,)
    # float32 sums, held to the sum of their terms' magnitudes: an entry
    # that cancels to near 0 has no relative precision of its own
    aX = np.abs(X64)
    want = ((X64 * w64[:, None]).T @ X64, X64.T @ wz64, w64.sum())
    scale = ((aX * w64[:, None]).T @ aX, aX.T @ np.abs(wz64), w64.sum())
    for got, ref, s in zip((xtx, xtz, ws), want, scale):
        assert np.all(np.abs(got - ref) <= 1e-5 * s + 1e-30)
    if data > 1:
        # four shards' psum against one shard's sum. The CPU sums a 1x1
        # product's 20,001 terms one after another: that one-shard entry
        # is 1.8e-6 of its scale from float64, the four shards' 1.1e-8;
        # every other entry agrees to 6.3e-7
        for got, one, s in zip((xtx, xtz, ws), _gram(X, w, z, 1), scale):
            assert np.all(np.abs(got - one) <= 3e-6 * s + 1e-30)
