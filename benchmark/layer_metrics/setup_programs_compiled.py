"""Programs set-up compiled in this process although a persistent cache
is configured: ``xla_programs_total{source=compile}`` (``{source=cache}``
counts the loads). ``telemetry.programs_snapshot()`` names them."""

from benchmark.layer_metrics.setup_parts import programs_compiled


def read(r):
    return programs_compiled(r)
