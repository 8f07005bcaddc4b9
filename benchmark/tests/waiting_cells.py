"""Cells whose files are all under ``benchmark/`` but whose entries are
not in ``BENCHMARK.json`` yet (PERF.md section 7, rows 0a and 0b: on the
chip the program is off the precision they state), so that the
benchmark's own tests keep rehearsing them: the entries the PR that lands
the cell appends, and ``merged`` — ``BENCHMARK.json`` with them appended.
"""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GBM = "gbm-airlines-d6.fit-48m"

# ``joins``: per-layer metrics already in BENCHMARK.json whose
# ``workloads`` gain the cell
WAITING = {GBM: {'configs': [{'name': 'gbm-airlines-d6',
                  'source': 'https://github.com/h2oai/h2o-3 airlines GBM '
                            'benchmark (BASELINE.json configs[0],[3]): '
                            'binomial GBM, 50 trees, depth 6, Airlines-116M '
                            'schema',
                  'file': 'benchmark/configs/gbm-airlines-d6.json',
                  'reduced': ['rows'],
                  'why': 'flagship tree fit: kernel level pass, split scan and '
                         'forest scoring on a 48M-row resident frame'}],
     'workloads': [{'name': 'gbm-airlines-d6.fit-48m',
                    'config': 'gbm-airlines-d6',
                    'traffic': 'fit-loop-2trees',
                    'chips': 1,
                    'why': 'closed loop, 1 caller, whole 2-tree depth-6 fits '
                           'on the resident 48M-row frame (published 50 trees: '
                           'per-fit costs weigh 25x more): scorer and tree '
                           'kernels do the work; warm jobs'}],
     'per_layer': [{'name': 'boost_chunk_share_pct',
                    'unit': '%',
                    'better': 'lower',
                    'source': 'device_trace',
                    'layer': 'boost chunk (models/gbm.py _boost_scan)',
                    'moves': 'fit_s',
                    'workloads': ['gbm-airlines-d6.fit-48m']},
                   {'name': 'forest_scoring_share_pct',
                    'unit': '%',
                    'better': 'lower',
                    'source': 'device_trace',
                    'layer': 'forest scoring (models/tree.py predict_forest)',
                    'moves': 'fit_s',
                    'workloads': ['gbm-airlines-d6.fit-48m']}],
     'joins': ['compiles_in_window',
               'device_idle_pct',
               'hbm_peak_gb',
               'setup_frame_s',
               'setup_warmup_s',
               'step_mfu']}}


BTAG = "glm-higgs-btag.fit-11m"

WAITING[BTAG] = {
    'configs': [{'name': 'glm-higgs-btag',
                 'source': 'https://archive.ics.uci.edu/dataset/280/higgs '
                           '(BASELINE.json configs[1]): GLM binomial IRLS on '
                           'HIGGS, 11M rows x 28 features, the four jet '
                           'b-tag columns as three-level columns',
                 'file': 'benchmark/configs/glm-higgs-btag.json',
                 'reduced': [],
                 'why': 'the GLM fit on columns whose values rows share: '
                        'rounding a product operand moves a coefficient'}],
    'workloads': [{'name': BTAG,
                   'config': 'glm-higgs-btag',
                   'traffic': 'fit-loop-default',
                   'chips': 1,
                   'why': 'closed loop, 1 caller, whole IRLS fits on the '
                          'resident 11M x 28 frame with 4 three-level '
                          'columns: the same work as glm-higgs.fit-11m, '
                          'data that shows the products\' precision'}],
    'per_layer': [],
    'joins': ['compiles_in_window', 'device_idle_pct', 'glm_solve_share_pct',
              'gram_roofline', 'hbm_peak_gb', 'setup_frame_s',
              'setup_warmup_s', 'step_mfu'],
}


def merged(bench: dict) -> dict:
    out = dict(bench)
    for cell, add in WAITING.items():
        for key in ("configs", "workloads", "per_layer"):
            out[key] = list(out[key]) + add[key]
        out["per_layer"] = [
            dict(m, workloads=m["workloads"] + [cell])
            if m["name"] in add["joins"] else m for m in out["per_layer"]]
    return out


def merged_checkout(tmp_path) -> str:
    """A directory that holds ``benchmark/`` and the merged
    ``BENCHMARK.json`` (the program comes from ``PYTHONPATH``)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = merged(json.load(f))
    with open(os.path.join(tmp_path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp_path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return str(tmp_path)
