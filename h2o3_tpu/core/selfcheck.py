"""Startup self-benchmarks — device capability probes.

Reference: water/init/{Linpack,MemoryBandwidth,NetworkBench}.java — at
boot every node measures GFLOPS, memory bandwidth, and network
throughput so cluster health pages can flag slow nodes. TPU-native
probes: MXU matmul GFLOPS (Linpack role), HBM read bandwidth
(MemoryBandwidth role), host↔device transfer (NetworkBench role — the
PCIe link is the analogous bottleneck path), and a mesh psum
round-trip when more than one device is attached.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np


def run_self_bench(sizes: Dict[str, int] | None = None) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    sizes = sizes or {}
    M = int(sizes.get("matmul", 4096))
    V = int(sizes.get("membw", 64 * 1024 * 1024))   # elements (f32)
    T = int(sizes.get("transfer", 16 * 1024 * 1024))

    out: Dict[str, float] = {"device": str(jax.devices()[0]),
                             "backend": jax.default_backend()}

    # Linpack role: f32 and bf16 matmul GFLOPS
    for dtype, name in ((jnp.float32, "matmul_f32_gflops"),
                        (jnp.bfloat16, "matmul_bf16_gflops")):
        a = jnp.ones((M, M), dtype)
        b = jnp.ones((M, M), dtype)
        f = jax.jit(lambda x, y: (x @ y).sum())
        float(f(a, b))                    # compile + warm
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            s = f(a, b)
        float(s)
        dt = (time.time() - t0) / reps
        out[name] = round(2 * M ** 3 / dt / 1e9, 1)

    # MemoryBandwidth role: big-vector reduce (reads V*4 bytes)
    v = jnp.ones((V,), jnp.float32)
    g = jax.jit(lambda x: x.sum())
    float(g(v))
    t0 = time.time()
    reps = 10
    for _ in range(reps):
        s = g(v)
    float(s)
    dt = (time.time() - t0) / reps
    out["hbm_read_gbps"] = round(V * 4 / dt / 1e9, 1)

    # NetworkBench role: host→device and device→host throughput
    host = np.ones((T,), np.float32)
    t0 = time.time()
    dev = jax.device_put(host)
    dev.block_until_ready()
    out["h2d_gbps"] = round(T * 4 / (time.time() - t0) / 1e9, 2)
    t0 = time.time()
    _ = np.asarray(dev)
    out["d2h_gbps"] = round(T * 4 / (time.time() - t0) / 1e9, 2)

    # mesh collective probe (reduce-tree role) when a mesh exists
    try:
        from h2o3_tpu.parallel.mesh import DATA_AXIS, get_mesh
        from jax.sharding import PartitionSpec as P
        mesh = get_mesh()
        if mesh.shape[DATA_AXIS] > 1:
            import functools
            from jax import shard_map

            @jax.jit
            @functools.partial(shard_map, mesh=mesh, in_specs=P(DATA_AXIS),
                               out_specs=P(), check_vma=False)
            def _ps(x):
                return jax.lax.psum(x, DATA_AXIS)

            x = jnp.ones((mesh.shape[DATA_AXIS] * 1024,), jnp.float32)
            float(_ps(x).sum())
            t0 = time.time()
            for _ in range(10):
                s = _ps(x)
            float(s.sum())
            out["psum_us"] = round((time.time() - t0) / 10 * 1e6, 1)
    except Exception:
        pass
    return out
