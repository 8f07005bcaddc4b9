"""Seconds of set-up spent getting executables out of the persistent
compile cache (reading, deserializing, loading onto the device):
``xla_stage_seconds_total{stage=cache_load}``."""

from benchmark.layer_metrics.setup_parts import stage_seconds


def read(r):
    return stage_seconds(r, "cache_load")
