"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""


def read(r):
    idle = r.tr.idle_share(r.trace, *r.window_ns)
    return None if idle is None else 100.0 * idle
