"""Share of the traced window's device-idle time that lies under no
program span at all (any ``h2o3.*`` name): what the program's spans
cannot yet explain."""

from benchmark import program_trace


def read(r):
    acc = program_trace.idle_by_span(r)
    if acc is None:
        return None
    idle = sum(acc.values())
    if idle <= 0:
        return None
    return r.share_pct(acc[program_trace.UNATTRIBUTED], idle,
                       "idle_unattributed_pct")
