"""Seconds of set-up the host spent tracing functions to jaxprs and
lowering jaxprs to MLIR modules — Python that no compile cache saves,
paid by every process for every program it readies:
``xla_stage_seconds_total{stage=trace}`` + ``{stage=lower}``, each second
once (a ``jit`` traced inside a ``jit`` is part of the outer trace)."""

from benchmark.layer_metrics.setup_parts import stage_seconds


def read(r):
    return stage_seconds(r, "trace", "lower")
