"""RollupStats — lazily computed, cached per-column summary statistics.

Reference: water/fvec/RollupStats.java:30-40 — min/max/mean/sigma/NA
count/zero count + histogram, computed by an MRTask sweep on first access
and cached on the Vec. Here: one jitted masked reduction per column,
cached on the Column; the reduce over the data mesh axis is the psum that
replaces the rollup MRTask's node tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from h2o3_tpu.frame.column import Column, T_STR


@jax.jit
def _rollup_kernel(x: jax.Array, na: jax.Array) -> dict:
    valid = ~na
    w = valid.astype(jnp.float32)
    n = jnp.sum(w)
    xf = x.astype(jnp.float32)
    xz = jnp.where(valid, xf, 0.0)
    s = jnp.sum(xz)
    mean = s / jnp.maximum(n, 1.0)
    ss = jnp.sum(jnp.where(valid, (xf - mean) ** 2, 0.0))
    big = jnp.float32(jnp.inf)
    return {
        "rows": n,
        "na_count": jnp.sum(na.astype(jnp.int32)),
        "mean": mean,
        "sigma": jnp.sqrt(ss / jnp.maximum(n - 1.0, 1.0)),
        "min": jnp.min(jnp.where(valid, xf, big)),
        "max": jnp.min(jnp.where(valid, -xf, big)) * -1.0,
        "zero_count": jnp.sum(jnp.where(valid, (x == 0).astype(jnp.float32), 0.0)),
        "sum": s,
    }


def prefetch_rollups(cols) -> None:
    """Fill many columns' rollup caches with ONE device→host fetch.

    N sequential rollups() calls block on N host round trips; a
    1000-column frame summary (pyunit_create_frame shape) pays for each.
    Dispatch every column's kernel asynchronously, then device_get the
    whole list."""
    todo = [c for c in cols
            if c._rollups is None and c.type != T_STR and c.data is not None]
    if not todo:
        return
    # opened for the fetch that is really made: a column's rollups are
    # kept, so a frame's later summaries and design builds open none
    from h2o3_tpu.telemetry.spans import span
    with span("frame.rollups", columns=len(todo), fetches=1):
        fetched = jax.device_get([_rollup_kernel(c.data, c.na_mask)
                                  for c in todo])
    for c, stats in zip(todo, fetched):
        out = {k: float(v) for k, v in stats.items()}
        out["rows"] = int(out["rows"])
        n_padding = c.data.shape[0] - c.nrows
        out["na_count"] = int(out["na_count"]) - n_padding
        out["zero_count"] = int(out["zero_count"])
        c._rollups = out


def rollups(col: Column) -> dict:
    """Compute-once stats (RollupStats.get semantics)."""
    if col._rollups is not None:
        return col._rollups
    if col.type == T_STR or col.data is None:
        col._rollups = {"rows": col.nrows, "na_count": 0}
        return col._rollups
    prefetch_rollups([col])
    return col._rollups
