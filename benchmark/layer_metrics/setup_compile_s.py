"""Seconds of set-up inside the backend's compiler for programs that did
NOT come from the persistent cache: ``xla_stage_seconds_total{stage=
compile}``. With a warm cache these are the programs under the cache's
threshold (``core/cloud.setup_compile_cache``), compiled again in every
process."""

from benchmark.layer_metrics.setup_parts import stage_seconds


def read(r):
    return stage_seconds(r, "compile")
