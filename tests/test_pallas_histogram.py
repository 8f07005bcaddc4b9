"""Pallas histogram kernel vs the XLA one-hot-matmul reference.

Runs the kernel in interpreter mode on CPU (the TPU path compiles the
same program natively)."""

import numpy as np
import jax.numpy as jnp
import pytest

from h2o3_tpu.ops.histogram import _local_histogram
from h2o3_tpu.ops.pallas_histogram import pallas_local_histogram


@pytest.mark.parametrize("L,B,F,N", [(1, 17, 4, 300), (8, 33, 7, 1000),
                                     (32, 65, 12, 2048)])
def test_pallas_matches_xla_histogram(L, B, F, N):
    r = np.random.RandomState(0)
    bins = jnp.asarray(r.randint(0, B, (N, F)).astype(np.int32))
    nid = jnp.asarray(r.randint(0, L, N).astype(np.int32))
    w = r.rand(N).astype(np.float32)
    w[r.rand(N) < 0.1] = 0.0   # padding-row zeros
    g = r.randn(N).astype(np.float32)
    h = r.rand(N).astype(np.float32)
    stats = jnp.stack([jnp.asarray(w), jnp.asarray(w * g),
                       jnp.asarray(w * h)], axis=1)
    ref = _local_histogram(bins, nid, stats.T, L, B, block_rows=256)
    out = pallas_local_histogram(bins, nid, stats.T, L, B, block_rows=256,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
