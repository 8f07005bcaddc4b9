"""Share of ``setup_s`` that nothing the program or the harness counts
accounts for: ``setup_s`` less the harness's ``setup_data`` (data from
the seed: the program cannot see it), less the package's import
(``process_import_seconds``), less every own second of the set-up spans
(``setup_parts.SPANS``, ``cloud.init`` and ``cloud.backend`` among them —
so NOT the harness's ``setup_init``, which covers those), less all four
XLA stages, less one warm job (``fit_s``). Never clipped: a negative
reading is a second counted twice, a fault of these readers."""

from benchmark.layer_metrics import setup_parts


def read(r):
    parts = (setup_parts.import_seconds(r),
             setup_parts.own_seconds(r, *setup_parts.SPANS),
             setup_parts.stage_seconds(r, *setup_parts.STAGES))
    if None in parts or "fit_s" not in r.end_to_end:
        return None
    setup_s = r.end_to_end["setup_s"]
    known = r.setup_seconds["setup_data"] + sum(parts) + \
        r.end_to_end["fit_s"]
    return 100.0 * (setup_s - known) / setup_s
