"""Pallas TPU kernel layer — knob resolution, fallback policy, telemetry.

The fused tree kernels live in ``ops/pallas/treekernel.py``; this module
is the POLICY layer and deliberately imports neither jax nor the kernels
at module scope, so it stays importable (and testable) where
``jax.experimental.pallas`` does not exist at all — the import-guard
contract: a missing Pallas can only ever mean "XLA path, one logged
fallback", never an ImportError in a training run.

Knob (``H2O3TPU_PALLAS`` env / ``Config.pallas``):

    auto       Pallas on TPU backends, XLA everywhere else (default)
    off        always XLA
    on         force native Pallas (TPU only in practice)
    interpret  force the kernels through the Pallas interpreter — the
               CPU tier-1 parity mode (bit-exact vs the XLA path)

Every fallback decision increments ``pallas_fallbacks_total{reason=}``
and logs ONCE per reason per process (no per-tree spam); every kernel
program instantiation increments ``pallas_kernel_launches_total{kernel=}``
at trace time (compiled programs re-run without touching Python, so the
counter reads as "distinct kernel builds", not per-step executions).
Both flow into each job's flight-recorder capsule via the start→end
counter deltas like any other counter.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

_AVAILABLE: Optional[bool] = None
# bin ids the kernels' bf16 one-hot expansion holds exactly (int8 binned
# matrices, the default 64-bin histograms, stay far below it)
MAX_KERNEL_BINS = 256
# what a kernel may take of Mosaic's 16 MB scoped-VMEM limit
VMEM_BUDGET_BYTES = 12 << 20
_LOGGED_REASONS = set()        # single logged fallback per reason/process


def available() -> bool:
    """True when ``jax.experimental.pallas`` imports (cached)."""
    global _AVAILABLE
    if _AVAILABLE is None:
        try:
            import jax.experimental.pallas  # noqa: F401
            _AVAILABLE = True
        except Exception:      # noqa: BLE001 - any import failure = absent
            _AVAILABLE = False
    return _AVAILABLE


def knob_value() -> str:
    """The H2O3TPU_PALLAS knob (env wins over Config default)."""
    env = os.environ.get("H2O3TPU_PALLAS")
    if env:
        return env
    try:
        from h2o3_tpu.core.config import ARGS
        return getattr(ARGS, "pallas", "auto") or "auto"
    except Exception:          # noqa: BLE001 - config must never gate this
        return "auto"


def decide(knob: str, backend: str, data_shards: int,
           avail: bool) -> Tuple[str, Optional[str]]:
    """Pure decision table: (mode, fallback_reason).

    mode is 'off' | 'native' | 'interpret'; reason is None when Pallas
    was selected. ``data_shards`` rides along for the bench stub's
    planner line — the kernels shard over 'data' like the XLA path, so
    shard count never forces a fallback.
    """
    knob = (knob or "auto").strip().lower()
    if knob in ("off", "0", "false", "xla"):
        return "off", "knob_off"
    if not avail:
        return "off", "pallas_unavailable"
    if knob == "interpret":
        return "interpret", None
    if knob in ("on", "native", "1", "force"):
        return "native", None
    if knob == "auto":
        if backend != "tpu":
            return "off", "non_tpu_backend"
        return "native", None
    return "off", "unknown_knob"


def resolve_tree_mode() -> str:
    """Resolve the kernel mode for a fit (counts + logs fallbacks).

    Called once per model fit by the tree builders and by a GLM whose
    design is held as codes (``ops/gram.with_gram_kernel``, where the
    result rides in ``CodesDesign.gram_kernel``); the trees' rides in
    ``TreeParams.pallas`` (a STATIC jit field), so flipping the knob
    mid-process compiles a fresh boosting program instead of silently
    reusing a cached one with the old decision.
    """
    import jax
    mode, reason = decide(knob_value(), jax.default_backend(), 1,
                          available())
    if reason is not None:
        record_fallback(reason)
    return mode


def record_fallback(reason: str) -> None:
    """Count a Pallas→XLA fallback; log once per reason per process."""
    from h2o3_tpu import telemetry
    telemetry.counter("pallas_fallbacks_total", reason=reason).inc()
    if reason not in _LOGGED_REASONS:
        _LOGGED_REASONS.add(reason)
        from h2o3_tpu.utils.log import get_logger
        get_logger("h2o3_tpu.ops.pallas").info(
            "Pallas kernels falling back to XLA (%s); further "
            "occurrences counted in pallas_fallbacks_total, not logged",
            reason)


def record_launch(kernel: str) -> None:
    """Count a pallas_call instantiation (trace time)."""
    from h2o3_tpu import telemetry
    telemetry.counter("pallas_kernel_launches_total", kernel=kernel).inc()


def _up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _tile_of(rows: int) -> int:
    """The largest power of two up to ``rows``, or 0 under 128: a power
    of two divides a frame's padded row count (a multiple of a large
    power of two), so a kernel's operands need no padded copy — at 48M
    rows a level with a 1,536- or 384-row tile made its own copies of
    the bins (twice), the node ids and the statistics, 2 GB a level
    (PERF.md §6, PR 35)."""
    return 1 << (rows.bit_length() - 1) if rows >= 128 else 0


def tile_rows(n_features: int, n_bins: int, n_nodes: int,
              budget_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Rows per tile at which BOTH level kernels (histogram, partition)
    fit ``budget_bytes`` of scoped VMEM, as a power of two from 128 to
    2048 — or 0 when the level cannot run in the kernels at all (bin
    ids the bf16 expansion cannot hold exactly, or a histogram
    accumulator that outgrows VMEM before a single tile is added: deep
    levels). A level that gets 0 takes the XLA composition. Pure math,
    no backend.

    The costs are what Mosaic allocated in compile sweeps for a v5e
    (libtpu 0.0.34) over F in 4..60, B in 17..256, levels 0..8: the
    largest tile it accepted was twice this or more at every shape.
    The histogram kernel keeps one float32 accumulator of
    ``piece_rows`` x F·B (three bfloat16 pieces a statistic,
    ops/histogram.stat_rows: three times the rows of the sums it
    yields) and pays per tile row the bfloat16 [F·B] one-hot row and
    its column of the [piece_rows, C] statistics operand; the
    partition kernel keeps rows on the lanes and costs the sublanes of
    its [L, C], [B-1, C] and [F, C] blocks.
    """
    if n_bins > MAX_KERNEL_BINS:
        return 0
    fb = 4 * _up(n_features * n_bins, 128)           # one f32 [., F·B] row
    pieces = _up(9 * max(n_nodes // 2, 1), 16)  # ops/histogram.piece_rows
    hist_row = (5 * (fb // 2 + 6 * pieces + 1024)) // 4
    hist_fixed = (9 * pieces * fb) // 8
    part_row = 4 * (2 * _up(n_nodes, 8) + 2 * _up(n_bins - 1, 8)
                    + 2 * _up(n_features, 32) + 32)
    # double-buffered [B-1, L] f32 left-set block
    part_fixed = 2 * _up(n_bins - 1, 8) * 4 * _up(n_nodes, 128)
    rows = min((int(budget_bytes) - hist_fixed) // hist_row,
               (int(budget_bytes) - part_fixed) // part_row, 2048)
    return _tile_of(rows)


def frontier_bin_rows(n_bins: int) -> int:
    """Sublanes a feature's bins take in the indicator of the frontier's
    histogram kernel: up to the 16 of a bfloat16 tile, so that the
    features' blocks stack without a shuffle."""
    return _up(n_bins, 16)


def frontier_tile_bytes(n_features: int, n_bins: int, operand_rows: int,
                        n_inputs: int) -> Tuple[int, int]:
    """(fixed, per tile row) bytes of scoped VMEM the frontier's
    histogram kernel (treekernel.frontier_hist) is counted at: the
    float32 accumulator [operand_rows, F·Bp] — the output block, so two
    buffers of it — and per row of the tile its bfloat16 indicator
    column [F·Bp], its column of the statistics operand (bfloat16, and
    the float32 it is selected from) and the ``n_inputs`` [1, tile] rows
    it is read from (8 sublanes each, two buffers)."""
    fbp = _up(n_features * frontier_bin_rows(n_bins), 128)
    return 2 * 4 * operand_rows * fbp, 2 * fbp + 6 * operand_rows \
        + 2 * 32 * n_inputs


def frontier_tile_rows(n_features: int, n_bins: int, operand_rows: int,
                       n_inputs: int,
                       budget_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Rows per tile of the frontier's histogram kernel, a power of two
    from 128 to 2048 as ``tile_rows`` gives them, or 0 where even 128
    rows do not fit beside the accumulator (a frontier level then takes
    the XLA chunk product). Pure math, no backend. On a v5e (PR 36) a
    pass is bound by the MXU rows of the operand, not by the tile: 1,024
    to 4,096 rows read within 10% of each other, and a level of many
    small blocks pays by the step, so the tile stops at 2,048."""
    fixed, row = frontier_tile_bytes(n_features, n_bins, operand_rows,
                                     n_inputs)
    return _tile_of(min((int(budget_bytes) - fixed) // row, 2048))
