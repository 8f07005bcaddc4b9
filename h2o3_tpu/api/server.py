"""REST API server — the water.api surface.

Reference: water/api/RequestServer.java:56 (route tree, dispatch at
:371-388), versioned Schema wire contract (water/api/Schema.java),
handlers per endpoint (CloudHandler, ParseHandler, ModelBuilderHandler,
JobsHandler, FramesHandler, RapidsHandler, ...). The reference serves
/3/* (stable) and /99/* (experimental: Rapids, AutoML); clients poll
GET /3/Jobs/{key} for async work.

This server keeps the same URI shapes and JSON field names that h2o-py
relies on (h2o-py/h2o/backend/connection.py), implemented on Python's
threading HTTP server — the web tier is control-plane only; all data
stays in device HBM, responses carry keys + small previews.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np

from h2o3_tpu.core import cloud as cloud_mod
from h2o3_tpu.core import request_ctx
from h2o3_tpu.core.job import Job, list_jobs
from h2o3_tpu.core.kv import DKV
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import all_algos, get_builder
from h2o3_tpu.models.model import Model
from h2o3_tpu.core.durability import DataLostError
from h2o3_tpu.serving.batcher import BatcherDraining, QueueSaturated
from h2o3_tpu.serving.fleet import FleetUnavailable
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.api")
_RMALL_COUNT = 0   # remove_all calls since boot (jit-cache clear cadence)

ROUTES: List[Tuple[str, re.Pattern, Callable]] = []


def route(method: str, pattern: str):
    rx = re.compile("^" + pattern + "$")

    def deco(fn):
        ROUTES.append((method, rx, fn))
        return fn
    return deco


def _register_metadata_routes():
    from h2o3_tpu.api import metadata
    metadata.register(route)


def _coerce(v: str) -> Any:
    """Form-value → python (the Schema fillFromParms coercion)."""
    if not isinstance(v, str):
        return v
    s = v.strip()
    if s.lower() in ("true", "false"):
        return s.lower() == "true"
    if s.lower() in ("null", "none", ""):
        return None
    if s.startswith("[") or s.startswith("{"):
        try:
            return json.loads(s.replace("'", '"'))
        except json.JSONDecodeError:
            pass
    try:
        f = float(s)
        return int(f) if f == int(f) and "." not in s and "e" not in s.lower() else f
    except ValueError:
        return s


def _unquote(s):
    """Strip the client-side quoted() wrapper (h2o-py sends frame ids and
    type names wrapped in literal double quotes)."""
    if isinstance(s, str) and len(s) >= 2 and s[0] == s[-1] and s[0] in "\"'":
        return s[1:-1]
    return s


_WIRE_TYPES = {"numeric": "real", "categorical": "enum",
               "time": "time", "string": "string", "uuid": "uuid"}


def _col_json(fr: Frame, name: str, row_offset: int, rows: int,
              summ: Optional[dict] = None) -> dict:
    """ColV3 wire shape (water/api/schemas3/FrameV3.java ColV3).

    The real h2o-py pops __meta / domain_cardinality / string_data
    unconditionally (h2o-py/h2o/expr.py:381-385), so those keys are
    mandatory."""
    c = fr.col(name)
    lo, hi = row_offset, min(row_offset + rows, fr.nrows)
    wire_type = _WIRE_TYPES.get(c.type, c.type)
    data, string_data, domain = None, None, None
    if c.type in ("string", "uuid"):
        vals = c.host_view()[lo:hi]
        string_data = [None if v is None else str(v) for v in vals]
        data = []
    elif c.is_categorical:
        domain = list(c.domain or [])
        # cached host view (prefetch_host batched the fetch): f64 codes
        # with NaN at NA. NA cells ride as JSON NaN (json.dumps
        # allow_nan): the client probes math.isnan(cell) before
        # indexing the domain (h2o-py/h2o/expr.py:416 _tabulate)
        codes = c.host_view()[lo:hi]
        data = [float("nan") if np.isnan(v) else int(v) for v in codes]
    else:
        vals = np.asarray(c.host_view()[lo:hi], np.float64)
        if wire_type == "real" and vals.size and \
                np.all(np.isnan(vals) | (vals == np.round(vals))) and \
                np.nanmax(np.abs(vals), initial=0) < 2**53:
            wire_type = "int"
        data = [float("nan") if np.isnan(v) else
                (int(v) if wire_type in ("int", "time") else float(v))
                for v in vals]
    try:
        s = (summ if summ is not None else fr.summary()).get(name, {})
    except Exception:
        s = {}
    mean = s.get("mean")
    sigma = s.get("sigma")
    mins = [s.get("min")] if s.get("min") is not None else []
    maxs = [s.get("max")] if s.get("max") is not None else []
    return {
        "__meta": {"schema_version": 3, "schema_name": "ColV3",
                   "schema_type": "Vec"},
        "label": name, "type": wire_type,
        "missing_count": int(s.get("na_count", 0) or 0),
        "zero_count": int(s.get("zero_count", 0) or 0),
        "positive_infinity_count": 0, "negative_infinity_count": 0,
        "mins": [None if (isinstance(v, float) and np.isnan(v)) else v
                 for v in mins],
        "maxs": [None if (isinstance(v, float) and np.isnan(v)) else v
                 for v in maxs],
        "mean": None if mean is None or (isinstance(mean, float)
                                         and np.isnan(mean)) else mean,
        "sigma": None if sigma is None or (isinstance(sigma, float)
                                           and np.isnan(sigma)) else sigma,
        "persist_type": "HBM", "precision": -1,
        "domain": domain,
        "domain_cardinality": len(domain) if domain else 0,
        "data": data, "string_data": string_data,
        "histogram_bins": None, "histogram_base": 0,
        "histogram_stride": 0, "percentiles": None,
    }


def _frame_json(fr: Frame, rows: int = 10, row_offset: int = 0) -> dict:
    """FrameV3 wire shape (water/api/schemas3/FrameV3.java)."""
    rows = min(rows, fr.nrows)
    # one batched host fetch for every column's preview data — a
    # 1000-column frame (pyunit_create_frame) otherwise pays a blocking
    # host round trip per column
    from h2o3_tpu.frame.column import prefetch_host
    prefetch_host([fr.col(n) for n in fr.names])
    try:
        summ = fr.summary()
    except Exception:
        summ = {}
    cols = [_col_json(fr, n, row_offset, rows, summ) for n in fr.names]
    return {"__meta": {"schema_version": 3, "schema_name": "FrameV3",
                       "schema_type": "Frame"},
            "frame_id": {"name": fr.key, "type": "Key<Frame>",
                         "URL": f"/3/Frames/{fr.key}"},
            "byte_size": 0, "is_text": False,
            "row_offset": row_offset, "row_count": rows,
            "column_offset": 0, "column_count": fr.ncols,
            "full_column_count": fr.ncols, "total_column_count": fr.ncols,
            "checksum": 0,
            "rows": fr.nrows, "num_columns": fr.ncols,
            "default_percentiles": [0.001, 0.01, 0.1, 0.25, 0.333, 0.5,
                                    0.667, 0.75, 0.9, 0.99, 0.999],
            "column_names": fr.names,
            "columns": cols, "compatible_models": [],
            "chunk_summary": None, "distribution_summary": None}


# ------------------------------------------------------------- handlers


def _local_sched_snapshot(pidx) -> dict:
    """This node's live scheduler counters for /3/Cloud — only for the
    serving process itself; peers without a published ``sched`` field
    (snapshot predates the scheduler) show ``{}``."""
    try:
        import jax
        if int(pidx) != jax.process_index():
            return {}
        from h2o3_tpu.parallel import scheduler
        return scheduler.snapshot()
    except Exception:   # noqa: BLE001 - occupancy is best-effort
        return {}


@route("GET", "/3/Cloud")
def _cloud(params, body):
    """Cluster status (water/api/CloudHandler, schemas3/CloudV3.java).

    ``healthy``/``last_ping`` per node come from the heartbeat monitor
    (core/heartbeat.py) when it runs — the HeartBeatThread → CloudV3
    wiring of the reference — and degrade to the formation-time verdict
    when it does not (single-process cloud, monitor off)."""
    import os
    info = cloud_mod.cluster_info()
    hb = info.get("heartbeat", {})
    peers = hb.get("peers", {})
    now = int(__import__("time").time() * 1000)
    mesh_devs = list(cloud_mod.mesh_mod.get_mesh().devices.flat)
    # published identity + per-node load from the cluster fan-in
    # snapshots (telemetry/cluster.py) — replaces the old default-0
    # process_index attribute guess; single-process clouds still get
    # their own (live) summary
    owner_map, summaries = {}, {}
    try:
        from h2o3_tpu.telemetry import cluster as _cluster
        col = _cluster.collect()
        owner_map = _cluster.device_owner_map(col)
        summaries = _cluster.node_summaries(col)
    except Exception:   # noqa: BLE001 - summaries are best-effort
        pass
    from h2o3_tpu.telemetry import roofline as _roofline
    peaks = _roofline.device_peaks()
    # this node's memory truth (core/memgov.py) — the fallback when a
    # peer's published snapshot predates the hbm field or is absent
    from h2o3_tpu.core.memgov import governor as _governor
    _governor.refresh_gauges()
    local_hbm = _governor.snapshot()
    nodes = []
    for i, d in enumerate(info["devices"]):
        # device i belongs to a process: published identity first, the
        # device's own process_index attribute as the fallback
        pidx = owner_map.get(
            d, getattr(mesh_devs[i], "process_index", 0))
        pst = peers.get(str(pidx))
        healthy = bool(pst["healthy"]) if pst else info["cloud_healthy"]
        last_ping = (int(pst["last_seen"] * 1000) if pst else now)
        summ = summaries.get(int(pidx), {})
        hbm = summ.get("hbm") or {}
        if not hbm:
            hbm = {"budget": local_hbm["budget_bytes"],
                   "in_use": local_hbm["bytes_in_use"],
                   "free": local_hbm["free_bytes"],
                   "spilled": local_hbm["spilled_bytes"]}
        nodes.append({
            "h2o": d, "ip_port": f"127.0.0.1:{54321 + i}",
            "healthy": healthy and not summ.get("stale", False),
            "last_ping": last_ping,
            "pid": summ.get("pid", os.getpid()),
            "num_cpus": os.cpu_count(),
            "cpus_allowed": os.cpu_count(), "nthreads": os.cpu_count(),
            "sys_load": 0.0, "my_cpu_pct": 0, "sys_cpu_pct": 0,
            # real memory truth from the governor: free/max against the
            # HBM budget, swap = bytes the Cleaner holds on ice
            "mem_value_size": hbm.get("in_use", 0), "pojo_mem": 0,
            "free_mem": hbm.get("free", 0),
            "max_mem": hbm.get("budget", 0),
            "swap_mem": hbm.get("spilled", 0),
            "num_keys": len(list(DKV.keys())),
            "free_disk": 0, "max_disk": 0, "rpcs_active": 0,
            "fjthrds": [], "fjqueue": [], "tcps_active": 0,
            "open_fds": -1,
            # 0 = no published peak for this device (not measured)
            "gflops": (peaks["flops"] or 0.0) / 1e9,
            "mem_bw": peaks["hbm_bytes_per_s"] or 0.0,
            "process_index": int(pidx),
            "metrics_summary": {
                "jobs_inflight": summ.get("jobs_inflight", 0),
                "last_publish_age_s": summ.get("last_publish_age_s", 0.0),
                "peak_hbm": summ.get("peak_hbm", 0),
                "stale": summ.get("stale", False),
            },
            # work-scheduler occupancy (parallel/scheduler.py): leases
            # this host currently holds plus lifetime item counters —
            # peers via their published snapshot, this node live
            "sched": summ.get("sched") or _local_sched_snapshot(pidx),
        })
    return {"__meta": {"schema_version": 3, "schema_name": "CloudV3",
                       "schema_type": "Iced"},
            "version": info["version"], "branch_name": "tpu-native",
            "last_commit_hash": "", "describe": "h2o3-tpu",
            "compiled_by": "h2o3-tpu", "compiled_on": "",
            "build_number": "0", "build_age": "0 days",
            "build_too_old": False, "node_idx": 0,
            "cloud_name": info["cloud_name"],
            "cloud_size": info["cloud_size"],
            "platform": info["platform"],
            "cloud_uptime_millis": info["cloud_uptime_ms"],
            "cloud_internal_timezone": "UTC",
            "datafile_parser_timezone": "UTC",
            "cloud_healthy": info["cloud_healthy"],
            "bad_nodes": sum(1 for n in nodes if not n["healthy"]),
            "consensus": info["cloud_healthy"],
            "locked": True, "is_client": False,
            "heartbeat": hb,
            "nodes": nodes, "internal_security_enabled": False,
            "web_ip": "127.0.0.1"}


@route("GET", "/3/Ping")
def _ping(params, body):
    return {"status": "running"}


_SESSIONS: set = set()


@route("POST", "/4/sessions")
def _new_session(params, body):
    """Issue a Rapids session id (water/api/InitIDHandler)."""
    import uuid
    sid = "_sid_" + uuid.uuid4().hex[:12]
    _SESSIONS.add(sid)
    return {"__meta": {"schema_version": 4, "schema_name": "SessionIdV4",
                       "schema_type": "Iced"},
            "session_key": sid}


@route("POST", "/3/InitID")
def _init_id(params, body):
    import uuid
    sid = "_sid_" + uuid.uuid4().hex[:12]
    _SESSIONS.add(sid)
    return {"__meta": {"schema_version": 3, "schema_name": "InitIDV3",
                       "schema_type": "Iced"},
            "session_key": sid}


@route("DELETE", r"/4/sessions/(?P<sid>[^/]+)")
def _end_session(params, body, sid=None):
    _SESSIONS.discard(sid)
    return {"session_key": sid}


@route("GET", "/3/Capabilities")
def _capabilities(params, body):
    caps = [{"name": n} for n in
            ("AutoML", "Algos", "TargetEncoder", "TPU")]
    return {"capabilities": caps}


@route("GET", "/3/Capabilities/Core")
def _capabilities_core(params, body):
    return {"capabilities": [{"name": "TPU"}, {"name": "Algos"}]}


@route("GET", "/3/Capabilities/API")
def _capabilities_api(params, body):
    return {"capabilities": [{"name": "AutoML"},
                             {"name": "TargetEncoder"}]}


@route("GET", "/3/Cleaner")
def _cleaner_status(params, body):
    """Spill/restore counters + HBM pressure (the Cleaner observability
    the reference exposes via water meters)."""
    from h2o3_tpu.core.cleaner import cleaner
    return cleaner.status()


@route("GET", "/3/About")
def _about(params, body):
    info = cloud_mod.cluster_info()
    return {"entries": [{"name": "Build version", "value": info["version"]},
                        {"name": "Backend", "value": info["platform"]}]}


def _wire_list(src) -> List[str]:
    """Decode h2o-py's stringify_list wire format: '[a,b]' where items
    may or may not be individually double-quoted (shared_utils.py:171 —
    bare for paths, quoted() for frame ids)."""
    if isinstance(src, list):
        items = src
    else:
        s = str(src).strip()
        if s.startswith("[") and s.endswith("]"):
            s = s[1:-1]
        items = s.split(",") if s else []
    out = []
    for it in items:
        if isinstance(it, dict):
            it = it.get("name")
        out.append(_unquote(str(it).strip()))
    return out


def _wire_nested_list(src):
    """Decode stringify_list of a list-of-lists — the na_strings wire
    format: '[["NA","x"],[],[""]]' with each item quoted() by the client
    (h2o-py/h2o/h2o.py:925 builds it, shared_utils.py:171 stringifies).
    Returns a list of per-column string lists, or None if unparseable.
    A flat list (h2o-py list-form semantics: same tokens for EVERY
    column) returns [tokens] and the caller broadcasts; null/None per
    column means 'no NA strings for that column'."""
    def _norm(lst):
        if not isinstance(lst, list):
            return None
        if all(x is None or isinstance(x, str) for x in lst) and \
                not any(isinstance(x, list) for x in lst):
            flat = [_unquote(x) for x in lst if isinstance(x, str)]
            return [flat] if flat else None
        out = []
        for inner in lst:
            if inner is None:
                out.append([])
            elif isinstance(inner, list):
                out.append([_unquote(str(x)) for x in inner
                            if x is not None])
            else:
                out.append([_unquote(str(inner))])
        return out
    if isinstance(src, list):
        return _norm(src)
    s = str(src).strip()
    try:
        import json as _json
        parsed = _json.loads(s)
        if isinstance(parsed, list):
            return _norm(parsed)
    except ValueError:
        pass                      # stringify_list fallback below
    if not (s.startswith("[") and s.endswith("]")):
        return None
    s, out, i, n = s[1:-1], [], 0, len(s) - 2
    while i < n:
        if s[i] != "[":
            i += 1
            continue
        j, inq = i + 1, False
        while j < n and (inq or s[j] != "]"):
            if s[j] == '"':
                inq = not inq
            j += 1
        inner = s[i + 1:j]
        items, cur, inq = [], [], False
        for ch in inner:
            if ch == '"':
                inq = not inq
                cur.append(ch)
            elif ch == "," and not inq:
                items.append("".join(cur))
                cur = []
            else:
                cur.append(ch)
        if cur or items:
            items.append("".join(cur))
        out.append([_unquote(t.strip()) for t in items])
        i = j + 1
    return out


def _wire_map(s: str) -> dict:
    """Decode stringify_dict_as_map output: near-JSON where bare words
    (enum/string values like bernoulli) arrive unquoted
    (h2o-py/h2o/utils/shared_utils.py:167)."""
    s = s.replace("'", '"')
    # python-repr literals (h2o-py stringifies dicts with repr(): the
    # kmeans-grid pyunit ships standardize: [True, False]) must become
    # JSON booleans, NOT get caught by the bare-identifier quoting below
    # — a wire "False" string breaks expect_model_param's coercion
    # quote guards confine the rewrite to BARE literals — a quoted
    # string value that happens to be "True"/"None" must survive intact
    s = re.sub(r'(?<!")\bTrue\b(?!")', "true", s)
    s = re.sub(r'(?<!")\bFalse\b(?!")', "false", s)
    s = re.sub(r'(?<!")\bNone\b(?!")', "null", s)
    # quote bare identifiers that aren't JSON literals
    s = re.sub(
        r'(?<![\w"])(?!true\b|false\b|null\b)'
        r'([A-Za-z_][A-Za-z0-9_.\-]*)(?!["\w])(?=\s*[,\]\}])',
        r'"\1"', s)
    return json.loads(s)


def _src_list(params) -> List[str]:
    """source_frames / paths param → clean list of path strings."""
    src = params.get("source_frames") or params.get("paths") or \
        params.get("path")
    return _wire_list(src)


@route("POST", "/3/ImportFiles")
def _import_files(params, body):
    path = _unquote(params.get("path"))
    import os
    if not os.path.exists(path) and not any(c in path for c in "*?["):
        return {"files": [], "destination_frames": [], "fails": [path],
                "dels": []}
    return {"files": [path], "destination_frames": [path], "fails": [],
            "dels": []}


@route("POST", "/3/ImportFilesMulti")
def _import_files_multi(params, body):
    """Multi-path import (water/api/ImportFilesHandler) — the real
    h2o-py always goes through this (h2o-py/h2o/h2o.py:336)."""
    import os
    paths = _src_list(params)
    files, fails = [], []
    for p in paths:
        if os.path.exists(p) or any(c in p for c in "*?["):
            files.append(p)
        else:
            fails.append(p)
    return {"files": files, "destination_frames": files, "fails": fails,
            "dels": []}


# ParseSetupV3 column-type enum names (water/parser/ParseSetup)
_SETUP_TYPES = {"numeric": "Numeric", "categorical": "Enum",
                "string": "String", "time": "Time"}
_SETUP_TYPES_BACK = {"numeric": "numeric", "enum": "categorical",
                     "factor": "categorical", "categorical": "categorical",
                     "string": "string", "time": "time", "int": "numeric",
                     "real": "numeric", "float": "numeric",
                     "uuid": "string"}


@route("POST", "/3/ParseSetup")
def _parse_setup(params, body):
    from h2o3_tpu.io.parser import parse_setup
    srcs = _src_list(params)
    ch = params.get("check_header")
    hint = None
    if ch is not None:
        ch = int(float(ch))
        hint = True if ch == 1 else (False if ch == -1 else None)
    setup = parse_setup(srcs[0], header=hint)
    dest = srcs[0].split("/")[-1]
    for ext in (".zip", ".gz", ".csv", ".parquet", ".pq", ".xlsx",
                ".arff", ".svm", ".svmlight"):
        if dest.endswith(ext):
            dest = dest[: -len(ext)]
    return {"__meta": {"schema_version": 3, "schema_name": "ParseSetupV3",
                       "schema_type": "ParseSetup"},
            "source_frames": [{"name": s} for s in srcs],
            "destination_frame": dest + ".hex",
            "parse_type": "CSV",
            "column_names": setup["columns"],
            "column_types": [_SETUP_TYPES.get(setup["types"][c], "Numeric")
                             for c in setup["columns"]],
            "na_strings": None,
            "warnings": [],
            "separator": ord(setup["separator"]),
            "single_quotes": False,
            "check_header": 1 if setup["header"] else 0,
            "number_columns": len(setup["columns"]),
            "chunk_size": 1 << 22,
            # how the ingest pipeline would run: chunk-parallel vs
            # sequential vs arrow-columnar, worker count, window size
            "parse_plan": _chunk_plan(srcs),
            "total_filtered_column_count": len(setup["columns"])}


def _chunk_plan(srcs):
    from h2o3_tpu.io.chunking import parse_plan
    try:
        return parse_plan(srcs)
    except Exception:            # plan reporting must never fail a parse
        return None


@route("POST", "/3/Parse")
def _parse(params, body):
    from h2o3_tpu.io.parser import import_file
    srcs = _src_list(params)
    dest = _unquote(params.get("destination_frame")) or None
    names = _wire_list(params["column_names"]) \
        if params.get("column_names") else None
    types = _wire_list(params["column_types"]) \
        if params.get("column_types") else None
    col_types = None
    if types and names:
        # type names arrive in either ParseSetup casing ("Enum") or the
        # client's lowercase coltype vocabulary ("enum"); unknowns are
        # left to the parser's own guess rather than forced numeric
        col_types = {}
        for n, t in zip(names, types):
            mapped = _SETUP_TYPES_BACK.get(str(t).lower())
            if mapped:
                col_types[n] = mapped
    # na_strings: column-indexed list of lists (water/parser/ParseSetup
    # naStrings contract — tokens matched BEFORE type inference).
    # Passed POSITIONALLY: keying by the client's column_names breaks
    # when those rename the file's own header columns.
    na_map = None
    if params.get("na_strings"):
        nested = _wire_nested_list(params["na_strings"])
        if nested and any(nested):
            if len(nested) == 1 and names and len(names) > 1:
                # flat-list form: the same tokens apply to every column
                nested = nested * len(names)
            na_map = [lst or None for lst in nested]
    job = Job(f"parse {srcs[0]}", dest=dest)

    ch = params.get("check_header")
    header = None
    if ch is not None:
        ch = int(float(ch))
        header = True if ch == 1 else (False if ch == -1 else None)

    def _run(j):
        if len(srcs) == 1:
            fr = import_file(srcs[0], destination_frame=dest,
                             col_types=col_types, header=header,
                             na_strings=na_map)
            if names and len(names) == fr.ncols and \
                    list(names) != list(fr.names):
                fr.rename_columns(list(names))
        else:
            import pandas as pd
            parts = []
            for s in srcs:
                part = import_file(s, col_types=col_types, header=header,
                                   na_strings=na_map)
                parts.append(part.to_pandas())
                DKV.remove(part.key)     # intermediate per-file frames
            fr = Frame.from_pandas(pd.concat(parts, ignore_index=True),
                                   key=dest)
            DKV.put(fr.key, fr)
        j.update(1.0, "parsed")
        return fr

    job.start(_run, background=True)
    return {"job": job.to_dict(), "parse_plan": _chunk_plan(srcs)}


@route("GET", "/3/Frames")
def _frames(params, body):
    out = []
    for k in DKV.keys():
        # get_raw: listing must NOT materialize lazy/spilled stubs — a
        # catalog poll would otherwise parse every lazy import and
        # un-evict everything the Cleaner just spilled
        v = DKV.get_raw(k)
        if isinstance(v, Frame):
            out.append({"frame_id": {"name": k}, "rows": v.nrows,
                        "num_columns": v.ncols})
        elif getattr(v, "_is_lazy_stub", False):
            out.append({"frame_id": {"name": k},
                        "rows": getattr(v, "nrows", None) or 0,
                        "num_columns": len(getattr(v, "names", []) or [])})
    return {"frames": out}


@route("GET", r"/3/Frames/(?P<fid>[^/]+)/summary")
def _frame_summary(params, body, fid=None):
    fr = DKV.get(fid)
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {fid} not found")
    summ = fr.summary()
    j = _frame_json(fr)
    for c in j["columns"]:
        s = summ.get(c["label"], {})
        c.update({k: (None if v is None or (isinstance(v, float) and np.isnan(v)) else v)
                  for k, v in s.items() if k in
                  ("min", "max", "mean", "sigma", "na_count", "zero_count",
                   "cardinality", "type")})
    return {"frames": [j]}


@route("GET", "/3/DownloadDataset")
def _download_dataset(params, body):
    """Frame → CSV stream (water/api/DownloadDataHandler) — h2o-py's
    as_data_frame()/frame download path."""
    fid = _unquote(params.get("frame_id"))
    fr = DKV.get(fid)
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {fid} not found")
    import io
    buf = io.StringIO()
    fr.to_pandas().to_csv(buf, index=False)
    data = buf.getvalue().encode()
    return {"__bytes__": data, "__ctype__": "text/csv",
            "__headers__": {
                "Content-Disposition":
                    f'attachment; filename="{fid}.csv"'}}


@route("GET", r"/3/Frames/(?P<fid>[^/]+)/light")
def _frame_light(params, body, fid=None):
    return _frame_one(params, body, fid=fid)


@route("GET", r"/3/Frames/(?P<fid>[^/]+)")
def _frame_one(params, body, fid=None):
    fr = DKV.get(fid)
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {fid} not found")
    rows = int(float(params.get("row_count") or 10))
    if rows < 0:
        rows = fr.nrows
    offset = int(float(params.get("row_offset") or 0))
    j = _frame_json(fr, rows=rows, row_offset=offset)
    # provenance surface (ISSUE 18): source paths + parse plan, derived
    # op chains, mirror status — what the durability layer would replay
    # to re-materialize this frame after a peer loss
    from h2o3_tpu.core import durability as _durability
    j["lineage"] = _durability.lineage_of(fr)
    return {"frames": [j]}


@route("DELETE", r"/3/Frames/(?P<fid>[^/]+)")
def _frame_del(params, body, fid=None):
    DKV.remove(fid)
    return {}


@route("DELETE", "/3/DKV")
def _dkv_del_all(params, body):
    """h2o.remove_all(): clear every key except retained models/frames;
    a retained MODEL also keeps its training/validation frames
    (water/api/RemoveAllHandler → DKVManager.retain model→frame)."""
    retained = set(_wire_list(params.get("retained_keys") or []))
    from h2o3_tpu.models.model import Model as _Model
    for k in list(retained):
        v = DKV.get_raw(k)
        if isinstance(v, _Model):
            for fk in (v.output.get("training_frame"),
                       v.output.get("validation_frame")):
                if fk:
                    retained.add(str(fk))
    for k in list(DKV.keys()):
        if k not in retained:
            DKV.remove(k)
    # release dropped device buffers NOW: deferred GC lets HBM pile up
    # across many remove_all cycles (the conformance suite exhausted the
    # chip after ~60 pyunits without this)
    import gc
    gc.collect()
    # compiled executables pin HBM too (program binaries + baked
    # constants live on chip, and jit caches keep them forever): drop
    # the caches when the device nears full — or, where the backend
    # reports no memory stats (the CPU returns None), every 15th clear;
    # the conformance tail ResourceExhausted around remove_all #55
    # without this, and a periodic recompile beats a dead suite
    try:
        import jax
        global _RMALL_COUNT
        _RMALL_COUNT += 1
        st = jax.devices()[0].memory_stats() or {}
        used = int(st.get("bytes_in_use", 0) or 0)
        cap = int(st.get("bytes_limit", 0) or 0)
        if (cap and used > 0.8 * cap) or \
                (not cap and _RMALL_COUNT % 10 == 0):
            from h2o3_tpu.core.job import free_device_memory
            free_device_memory(f"remove_all #{_RMALL_COUNT}, HBM "
                               f"{used / 1e9:.1f}/{cap / 1e9:.1f} GB")
    except Exception:
        pass
    return {}


@route("DELETE", r"/3/DKV/(?P<key>[^/]+)")
def _dkv_del(params, body, key=None):
    DKV.remove(key)
    return {}


@route("POST", "/3/LogAndEcho")
def _log_and_echo(params, body):
    log.info("client: %s", params.get("message") or "")
    return {"message": params.get("message") or ""}


@route("GET", "/3/ModelBuilders")
def _builders(params, body):
    out = {}
    for algo in all_algos():
        cls = get_builder(algo)
        defaults = getattr(cls, "DEFAULTS", {})
        out[algo] = {"algo": algo, "algo_full_name": cls.__name__,
                     "parameters": [
                         {"name": k, "default_value": defaults.get(k),
                          "type": type(defaults.get(k)).__name__}
                         for k in sorted(cls.accepted_params())]}
    return {"model_builders": out}


@route("POST", r"/3/ModelBuilders/(?P<algo>[^/]+)")
def _train(params, body, algo=None):
    cls = get_builder(algo)
    p = {k: _coerce(v) for k, v in params.items()}
    frame_key = p.pop("training_frame", None)
    y = p.pop("response_column", None)
    valid_key = p.pop("validation_frame", None)
    model_id = p.pop("model_id", None)
    ignored = p.pop("ignored_columns", None)
    fr = DKV.get(str(frame_key))
    if not isinstance(fr, Frame):
        raise KeyError(f"training_frame {frame_key} not found")
    vf = DKV.get(str(valid_key)) if valid_key else None
    known = cls.accepted_params()
    builder_params = {k: v for k, v in p.items() if k in known}
    if ignored is not None:
        builder_params["ignored_columns"] = ignored
    builder = cls(**builder_params)
    # the one ModelBuilder.train lifecycle (CV dispatch, run_time, logs)
    job = builder.train(fr, y=y, validation_frame=vf, background=True,
                        dest_key=model_id)
    # ModelBuilderSchema shape: job + validation messages
    # (h2o-py/h2o/estimators/estimator_base.py:190 reads "messages")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelBuilderSchema",
                       "schema_type": "ModelBuilder"},
            "job": job.to_dict(), "messages": [], "error_count": 0}


@route("GET", r"/3/Jobs/(?P<key>[^/]+)")
def _job(params, body, key=None):
    j = DKV.get(key)
    if not isinstance(j, Job):
        raise KeyError(f"job {key} not found")
    d = j.to_dict()
    # h2o-py expects job.status in {CREATED,RUNNING,DONE,FAILED,CANCELLED}
    if j.status == "DONE" and j.result is not None and \
            isinstance(j.result, Model):
        d["dest"] = {"name": j.result.key, "type": "Key<Model>"}
    return {"jobs": [d]}


@route("POST", r"/3/Jobs/(?P<key>[^/]+)/cancel")
def _job_cancel(params, body, key=None):
    j = DKV.get(key)
    if isinstance(j, Job):
        j.cancel()
    return {}


@route("GET", "/3/Jobs")
def _jobs(params, body):
    """Job list (water/api/JobsHandler). ``?cluster=1`` on a
    multi-process cloud merges every peer's job list from the telemetry
    fan-in (telemetry/cluster.py) — each entry stamped with its owning
    ``node`` (job keys are process-local counters, so same-key entries
    on different nodes are distinct jobs, never deduped)."""
    if _cluster_requested(params):
        from h2o3_tpu.telemetry import cluster
        return cluster.merged_jobs()
    return {"jobs": list_jobs()}


@route("GET", "/3/Models")
def _models(params, body):
    out = []
    for k in DKV.keys():
        v = DKV.get(k)
        if isinstance(v, Model):
            out.append(v.to_dict())
    return {"models": out}


@route("GET", r"/3/Models/(?P<mid>[^/]+)")
def _model_one(params, body, mid=None):
    from h2o3_tpu.api.model_schema import model_to_v3
    m = DKV.get(mid)
    if not isinstance(m, Model):
        raise KeyError(f"model {mid} not found")
    return {"models": [model_to_v3(m)]}


@route("GET", r"/3/Models/(?P<mid>[^/]+)/profile")
def _model_profile(params, body, mid=None):
    """Per-fit step profile (telemetry/stepprof.py): phase totals,
    per-chunk ring, collective-wait share. ``?cluster=1`` merges every
    host's profile of a pod-global fit into the skew/straggler verdict
    (pod_step_skew_ratio / pod_straggler_host)."""
    from h2o3_tpu.telemetry import stepprof
    out = stepprof.profile_for(mid)       # KeyError -> 404
    if _cluster_requested(params):
        out["cluster"] = stepprof.cluster_profile(mid)
    return out


@route("DELETE", r"/3/Models/(?P<mid>[^/]+)")
def _model_del(params, body, mid=None):
    DKV.remove(mid)
    return {}


@route("POST", r"/3/Predictions/models/(?P<mid>[^/]+)/frames/(?P<fid>[^/]+)")
def _predict(params, body, mid=None, fid=None):
    m = DKV.get(mid)
    fr = DKV.get(fid)
    if not isinstance(m, Model):
        # bulk predicts route through the fleet too (ISSUE 17): a model
        # this node never trained can still be answered here — proxy or
        # 307 to a healthy replica, or install the published binary
        from h2o3_tpu.serving import fleet
        hop = str(params.pop("_fleet_hop", "")).lower() in ("1", "true")
        plan = fleet.plan_route(mid, have_local=False, hop=hop)
        bulk_path = (f"/3/Predictions/models/"
                     f"{urllib.parse.quote(str(mid), safe='')}/frames/"
                     f"{urllib.parse.quote(str(fid), safe='')}")
        if plan.decision == "redirect":
            return {"__redirect__": fleet.redirect_url(plan, bulk_path)}
        if plan.decision == "proxy":
            payload = {k: v for k, v in params.items()
                       if not str(k).startswith("_")}
            res = fleet.proxy_predict(
                plan, bulk_path, payload, mid,
                local_fallback=fleet.published(mid) is not None)
            if res is not fleet.SERVE_LOCALLY:
                return res
        if plan.decision == "none":
            raise KeyError(f"model {mid} not found")
        m = fleet.install_published(mid)
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {fid} not found")
    dest = params.get("predictions_frame") or f"predictions_{mid}_{fid}"
    def _flag(name):
        return str(params.get(name, "")).lower() in ("1", "true", "yes")
    for flag, meth in (("leaf_node_assignment", "predict_leaf_node_assignment"),
                       ("predict_staged_proba", "staged_predict_proba"),
                       ("feature_frequencies", "feature_frequencies"),
                       ("predict_contributions", "predict_contributions")):
        if _flag(flag):
            fn = getattr(m, meth, None)
            if fn is None:
                raise ValueError(f"{flag} is not supported for "
                                 f"algo '{m.algo}'")
            preds = fn(fr)
            break
    else:
        preds = m.predict(fr)
    DKV.remove(preds.key)
    preds.key = str(dest)
    DKV.put(preds.key, preds)
    # scoring computes metrics when the response is present (the
    # reference's BigScore fills a MetricBuilder during predict; the
    # client's multinomial confusion_matrix(data=...) reads
    # model_metrics[0].cm from THIS response)
    metrics_list = [{}]
    try:
        resp = m.output.get("response")
        if resp and resp in fr:
            from h2o3_tpu.api.model_schema import metrics_v3
            metrics_list = [metrics_v3(m.model_performance(fr), m,
                                       frame_key=fr.key)]
    except Exception:
        pass
    return {"predictions_frame": {"name": preds.key},
            "model_metrics": metrics_list}


@route("POST", r"/4/Predictions/models/(?P<mid>[^/]+)/frames/(?P<fid>[^/]+)")
def _predict_async(params, body, mid=None, fid=None):
    """Async bulk scoring (water/api/ModelMetricsHandler.predictAsync —
    returns a bare JobV3; the real h2o-py polls it then fetches
    job.dest as the predictions frame)."""
    m = DKV.get(mid)
    fr = DKV.get(fid)
    if not isinstance(m, Model):
        raise KeyError(f"model {mid} not found")
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {fid} not found")
    dest = f"prediction_{mid}_on_{fid}"
    job = Job(f"predict {mid}", dest=dest)

    def _run(j):
        # chunked BigScore: cancel_point at every chunk boundary, so a
        # cancelled or deadline-expired bulk predict frees its worker
        # within one chunk like training does (models/model.py)
        preds = m.predict_in_chunks(fr, job=j)
        DKV.remove(preds.key)
        preds.key = dest
        DKV.put(dest, preds)
        j.update(1.0, "scored")
        return preds

    job.start(_run, background=True)
    return job.to_dict()


@route("POST", r"/3/Predictions/models/(?P<mid>[^/]+)")
def _predict_rows(params, body, mid=None):
    """Row-payload predict fast path (README §Serving): inline JSON
    rows — no DKV frame round trip — scored through the serving tier's
    compiled-scorer cache and continuous micro-batcher, bit-identical
    to ``Model.predict`` on the same rows. Body:
    ``{"rows": [{"col": value, ...}, ...]}``; missing keys are NAs.

    Fleet-routed (ISSUE 17): the request resolves against the replica
    registry — heartbeat-dead peers excluded, least-loaded healthy
    replica wins — and either serves locally, proxies (with hedged
    failover within the deadline budget), or 307-redirects.
    ``_fleet_hop=1`` marks an already-routed request (never re-routed)."""
    from h2o3_tpu.serving import fleet
    hop = str(params.pop("_fleet_hop", "")).lower() in ("1", "true")
    rows = params.get("rows")
    if isinstance(rows, str):
        try:
            rows = json.loads(rows)
        except json.JSONDecodeError as e:
            raise ValueError(f"malformed 'rows' JSON: {e}") from None
    if rows is None:
        raise ValueError("missing 'rows': POST a JSON body "
                         '{"rows": [{"col": value, ...}, ...]}')
    m = DKV.get(mid)
    have_local = isinstance(m, Model)
    plan = fleet.plan_route(mid, have_local=have_local, hop=hop)
    if plan.decision == "none":
        raise KeyError(f"model {mid} not found")
    if plan.decision == "redirect":
        return {"__redirect__": plan.url}
    if plan.decision == "proxy":
        res = fleet.proxy_predict(
            plan,
            f"/3/Predictions/models/"
            f"{urllib.parse.quote(str(mid), safe='')}",
            {"rows": rows}, mid,
            local_fallback=(have_local
                            or fleet.published(mid) is not None))
        if res is not fleet.SERVE_LOCALLY:
            return res
    if not have_local:
        # routed here (or every remote hop failed) without a local
        # copy: install + pre-warm from the published binary
        m = fleet.install_published(mid)
    from h2o3_tpu.serving import ServingUnsupported
    from h2o3_tpu.serving.engine import engine
    try:
        out, domains, meta = engine.score_rows(m, rows)
    except ServingUnsupported as e:
        raise ValueError(str(e)) from None
    preds = {}
    for name, arr in out.items():
        vals = arr.tolist()
        dom = domains.get(name)
        if dom is not None:
            # label the predict column with the training response
            # domain (what the predictions-frame download shows)
            vals = [dom[int(v)] if 0 <= int(v) < len(dom) else None
                    for v in vals]
        preds[name] = vals
    return {"model_id": mid, "rows_scored": len(rows),
            "predictions": preds, "batch": meta}


@route("GET", r"/3/Models/(?P<mid>[^/]+)/mojo")
def _model_mojo(params, body, mid=None):
    """Stream the MOJO zip (h2o-py download_mojo GET endpoint)."""
    from h2o3_tpu.genmodel.export import mojo_artifacts
    from h2o3_tpu.genmodel.mojo import mojo_bytes
    m = DKV.get(mid)
    if not isinstance(m, Model):
        raise KeyError(f"model {mid} not found")
    return {"__bytes__": mojo_bytes(*mojo_artifacts(m)),
            "__ctype__": "application/zip"}


@route("GET", r"/3/Models\.java/(?P<mid>[^/]+)")
def _model_pojo(params, body, mid=None):
    """Generated-source scorer download (water/api Models.java POJO
    endpoint). gbm/drf/glm return compilable Java implementing
    hex.genmodel.GenModel.score0 (hex/genmodel/GenModel.java:363);
    other algos ship the stdlib-Python scorer module."""
    m = DKV.get(mid)
    if not isinstance(m, Model):
        raise KeyError(f"model {mid} not found")
    if getattr(m, "algo", None) in ("gbm", "drf", "glm"):
        from h2o3_tpu.genmodel.pojo_java import java_pojo_source
        src = java_pojo_source(m, class_name=str(mid))
        ctype = "text/x-java; charset=utf-8"
    else:
        from h2o3_tpu.genmodel.pojo import pojo_source
        src = pojo_source(m, modname=str(mid))
        ctype = "text/plain; charset=utf-8"
    return {"__bytes__": src.encode(), "__ctype__": ctype}


@route("POST", r"/3/ModelMetrics/models/(?P<mid>[^/]+)/frames/(?P<fid>[^/]+)")
def _model_metrics(params, body, mid=None, fid=None):
    """Score a frame and return its metrics (water/api/ModelMetricsHandler
    — the model_performance(test_data) wire call)."""
    m = DKV.get(mid)
    fr = DKV.get(fid)
    if not isinstance(m, Model):
        raise KeyError(f"model {mid} not found")
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {fid} not found")
    from h2o3_tpu.api.model_schema import metrics_v3
    mm_ = m.model_performance(fr)
    return {"model_metrics": [metrics_v3(mm_, m, frame_key=fid)]}


@route("POST", "/3/CreateFrame")
def _create_frame(params, body):
    """Synthetic frame generator (water/api/CreateFrameHandler →
    hex/createframe/): randomized numeric/categorical/integer/binary/
    time/string columns with missing values and optional response."""
    p = {k: _coerce(v) for k, v in params.items()}
    dest = _unquote(str(p.get("dest") or p.get("destination_frame")
                        or "createframe.hex"))
    rows = int(p.get("rows") or 100)
    cols_n = int(p.get("cols") or 10)
    seed = int(p.get("seed") or -1)
    r = np.random.RandomState(seed & 0x7FFFFFFF if seed >= 0 else None)
    cat_f = float(p.get("categorical_fraction") or 0.0)
    int_f = float(p.get("integer_fraction") or 0.0)
    bin_f = float(p.get("binary_fraction") or 0.0)
    time_f = float(p.get("time_fraction") or 0.0)
    str_f = float(p.get("string_fraction") or 0.0)
    miss_f = float(p.get("missing_fraction") or 0.0)
    factors = int(p.get("factors") or 100)
    real_range = float(p.get("real_range") or 100.0)
    int_range = int(p.get("integer_range") or 100)
    bin_ones = float(p.get("binary_ones_fraction") or 0.02)
    counts = {
        "cat": int(round(cols_n * cat_f)),
        "int": int(round(cols_n * int_f)),
        "bin": int(round(cols_n * bin_f)),
        "time": int(round(cols_n * time_f)),
        "str": int(round(cols_n * str_f)),
    }
    counts["real"] = max(cols_n - sum(counts.values()), 0)
    job = Job("create frame", dest=dest)

    def _run(j):
        arrays, cats, strs, times = {}, [], [], []
        ci = 0
        for kind, cnt in counts.items():
            for _ in range(cnt):
                name = f"C{ci + 1}"
                ci += 1
                if kind == "cat":
                    arrays[name] = np.array(
                        [f"c{ci}.l{v}" for v in
                         r.randint(0, max(factors, 1), rows)], object)
                    cats.append(name)
                elif kind == "int":
                    arrays[name] = r.randint(-int_range, int_range + 1,
                                             rows).astype(np.float64)
                elif kind == "bin":
                    arrays[name] = (r.rand(rows) < bin_ones
                                    ).astype(np.float64)
                elif kind == "time":
                    arrays[name] = r.randint(0, 2 ** 40,
                                             rows).astype(np.float64)
                    times.append(name)
                elif kind == "str":
                    arrays[name] = np.array(
                        [f"s{v}" for v in r.randint(0, 10 ** 6, rows)],
                        object)
                    strs.append(name)
                else:
                    arrays[name] = r.uniform(-real_range, real_range, rows)
        if miss_f > 0:
            for name, arr in arrays.items():
                mask = r.rand(rows) < miss_f
                if name in strs or name in cats:
                    a = arr.astype(object)
                    a[mask] = None
                    arrays[name] = a
                else:
                    arr[mask] = np.nan
        if str(p.get("has_response", "")).lower() in ("1", "true"):
            rf = int(p.get("response_factors") or 2)
            if rf <= 1:
                arrays["response"] = r.randn(rows)
            else:
                arrays["response"] = np.array(
                    [f"resp.l{v}" for v in r.randint(0, rf, rows)], object)
                cats.append("response")
        fr = Frame.from_numpy(arrays, categorical=cats, strings=strs,
                              times=times, key=dest)
        DKV.put(dest, fr)
        j.update(1.0)
        return fr

    job.start(_run, background=True)
    return {"job": job.to_dict()}


@route("POST", "/3/Interaction")
def _interaction_ep(params, body):
    """Categorical interaction features (water/api/InteractionHandler →
    hex/Interaction: pairwise or full combination of factor columns,
    capped at max_factors levels by occurrence)."""
    p = {k: _coerce(v) for k, v in params.items()}
    src = DKV.get(_unquote(str(p.get("source_frame"))))
    if not isinstance(src, Frame):
        raise KeyError(f"frame {p.get('source_frame')} not found")
    dest = _unquote(str(p.get("dest") or "interaction.hex"))
    factors = [_unquote(f) for f in _wire_list(p.get("factor_columns"))]
    pairwise = str(p.get("pairwise", "")).lower() in ("1", "true")
    max_factors = int(p.get("max_factors") or 100)
    min_occ = int(p.get("min_occurrence") or 1)
    job = Job("interaction", dest=dest)

    def _run(j):
        import itertools
        from h2o3_tpu.frame.column import T_CAT
        groups = (list(itertools.combinations(factors, 2)) if pairwise
                  else [tuple(factors)])
        arrays, cats, doms = {}, [], {}
        for grp in groups:
            name = "_".join(grp)
            codes = None
            labels = None
            for g in grp:
                c = src.col(g)
                cc = _fetch_np(c.data)[: src.nrows].astype(np.int64)
                cna = _fetch_np(c.na_mask)[: src.nrows]
                lab = np.array([c.domain[v] if 0 <= v < len(c.domain)
                                else "NA" for v in cc], object)
                lab[cna] = "NA"
                labels = lab if labels is None else \
                    np.char.add(np.char.add(labels.astype(str), "_"),
                                lab.astype(str))
            vals, cnts = np.unique(labels, return_counts=True)
            keep = vals[cnts >= min_occ]
            if len(keep) > max_factors:
                keep = vals[np.argsort(-cnts)][:max_factors]
            keep_set = set(keep.tolist())
            out = np.array([v if v in keep_set else "other"
                            for v in labels], object)
            arrays[name] = out
            cats.append(name)
        fr = Frame.from_numpy(arrays, categorical=cats, key=dest)
        DKV.put(dest, fr)
        j.update(1.0)
        return fr

    job.start(_run, background=True)
    return {"job": job.to_dict()}


@route("POST", "/3/MissingInserter")
def _missing_inserter(params, body):
    """Insert missing values into a frame in place
    (water/api/MissingInserterHandler)."""
    p = {k: _coerce(v) for k, v in params.items()}
    key = _unquote(str(p.get("dataset")))
    fr = DKV.get(key)
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {key} not found")
    frac = float(p.get("fraction") or 0.0)
    seed = int(p.get("seed") or -1)
    job = Job("insert missing", dest=key)

    def _run(j):
        r = np.random.RandomState(seed & 0x7FFFFFFF if seed >= 0 else None)
        arrays, cats, doms, strs = {}, [], {}, []
        for n in fr.names:
            c = fr.col(n)
            if c.type == "string":
                a = c.to_numpy().astype(object).copy()
                a[r.rand(fr.nrows) < frac] = None
                arrays[n] = a
                strs.append(n)
            elif c.is_categorical:
                codes = _fetch_np(c.data)[: fr.nrows].astype(np.int32)
                codes[_fetch_np(c.na_mask)[: fr.nrows]] = -1
                codes[r.rand(fr.nrows) < frac] = -1
                arrays[n] = codes
                cats.append(n)
                doms[n] = c.domain
            else:
                a = c.to_numpy()
                a[r.rand(fr.nrows) < frac] = np.nan
                arrays[n] = a
        new = Frame.from_numpy(arrays, categorical=cats, domains=doms,
                               strings=strs, key=key)
        DKV.put(key, new)
        j.update(1.0)
        return new

    job.start(_run, background=True)
    return job.to_dict()


@route("GET", r"/3/Typeahead/files")
def _typeahead(params, body):
    """File-path completion (water/api/TypeaheadHandler)."""
    import glob as _g
    import os
    src = _unquote(str(params.get("src") or ""))
    limit = int(float(params.get("limit") or 100))
    if os.path.isdir(src):
        pattern = os.path.join(src, "*")
    else:
        pattern = src + "*"
    matches = sorted(_g.glob(pattern))[:limit]
    return {"src": src, "limit": limit, "matches": matches}


@route("GET", "/3/NetworkTest")
def _network_test(params, body):
    """Collective micro-bench over the mesh (water/init/NetworkBench):
    times a small psum across devices — the ICI/DCN path."""
    import time as _t
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.parallel.mesh import get_mesh
    from h2o3_tpu.ops.segments import segment_sum
    mesh = get_mesh()
    x = jnp.ones((8192,), jnp.float32)
    t0 = _t.time()
    s = segment_sum(jnp.zeros((8192,), jnp.int32), x[:, None],
                    n_nodes=1, mesh=mesh)
    float(jnp.sum(s))
    dt = _t.time() - t0
    return {"table": [{"op": "psum-32KB",
                       "devices": len(jax.devices()),
                       "seconds": round(dt, 5)}],
            "nodes": [str(d) for d in mesh.devices.flat]}


@route("POST", "/3/PartialDependence")
def _pdp(params, body):
    """water/api/PartialDependenceHandler: grid sweep per feature."""
    m = DKV.get(str(params.get("model_id")))
    fr = DKV.get(str(params.get("frame_id")))
    if not isinstance(m, Model):
        raise KeyError(f"model {params.get('model_id')} not found")
    if not isinstance(fr, Frame):
        raise KeyError(f"frame {params.get('frame_id')} not found")
    cols = _coerce(params.get("cols") or "[]")
    if isinstance(cols, str):
        cols = [cols]
    nbins = int(params.get("nbins") or 20)
    from h2o3_tpu.ml.explain import partial_dependence
    return {"partial_dependence_data": partial_dependence(
        m, fr, cols or m.output.get("names", []), nbins=nbins)}


@route("POST", "/99/Rapids")
def _rapids_ep(params, body):
    """Rapids eval (water/api/RapidsHandler). The real h2o-py reads
    key/num_rows/num_cols for frames, scalar, string, map_keys/frames
    (h2o-py/h2o/expr.py:116-128); errors must be H2OErrorV3."""
    from h2o3_tpu.rapids import rapids
    expr = params.get("ast") or ""
    try:
        val = rapids(expr)
    except Exception as e:   # noqa: BLE001
        # HBM pressure can show up as RESOURCE_EXHAUSTED before any
        # memory gauge moves: purge jit caches and retry once
        if "RESOURCE_EXHAUSTED" not in f"{e}":
            raise
        from h2o3_tpu.core.job import free_device_memory
        free_device_memory("rapids RESOURCE_EXHAUSTED retry")
        val = rapids(expr)
    if isinstance(val, Frame):
        return {"__meta": {"schema_version": 3,
                           "schema_name": "RapidsFrameV3",
                           "schema_type": "RapidsFrame"},
                "key": {"name": val.key},
                "num_rows": val.nrows, "num_cols": val.ncols,
                "frame": _frame_json(val, rows=5)}
    if isinstance(val, (bool, np.bool_)):
        return {"scalar": bool(val)}
    if isinstance(val, (int, float, np.generic)):
        return {"scalar": float(val)}
    if val is None:
        return {"scalar": None}
    if isinstance(val, (list, np.ndarray)):
        return {"scalar": [float(x) for x in np.asarray(val).ravel()]}
    return {"string": str(val)}


@route("POST", r"/99/Grid/(?P<algo>[^/]+)")
def _grid_build(params, body, algo=None):
    """Grid search build (water/api/GridSearchHandler +
    hex/grid/GridSearch.java:70). The real h2o-py posts
    hyper_parameters as a stringified map and polls the returned job
    (h2o-py/h2o/grid/grid_search.py:414)."""
    from h2o3_tpu.ml.grid import GridSearch
    cls = get_builder(algo)
    p = {k: _coerce(v) for k, v in params.items()}
    hyper = p.pop("hyper_parameters", None) or {}
    if isinstance(hyper, str):
        hyper = _wire_map(hyper)
    criteria = p.pop("search_criteria", None)
    if isinstance(criteria, str):
        criteria = _wire_map(criteria)
    frame_key = str(p.pop("training_frame", None))
    y = p.pop("response_column", None)
    valid_key = p.pop("validation_frame", None)
    grid_id = p.pop("grid_id", None)
    ignored = p.pop("ignored_columns", None)
    if isinstance(ignored, str):
        ignored = _wire_list(ignored)
    fr = DKV.get(frame_key)
    if not isinstance(fr, Frame):
        raise KeyError(f"training_frame {frame_key} not found")
    vf = DKV.get(str(valid_key)) if valid_key else None
    known = cls.accepted_params()
    fixed = {k: v for k, v in p.items() if k in known and k not in hyper}
    if ignored:
        fixed["ignored_columns"] = [_unquote(c) for c in ignored]
    gs = GridSearch(cls, hyper, search_criteria=criteria,
                    grid_id=grid_id, **fixed)
    job = Job(f"grid {algo}", dest=gs.grid_id)

    def _run(j):
        grid = gs.train(fr, y=y, validation_frame=vf)
        j.update(1.0, "grid done")
        return grid

    job.start(_run, background=True)
    return {"__meta": {"schema_version": 99,
                       "schema_name": "GridSearchSchema",
                       "schema_type": "GridSearch"},
            "job": job.to_dict(), "messages": [], "error_count": 0}


def _grid_json(grid, sort_by=None, decreasing=None):
    from h2o3_tpu.api.model_schema import twodim
    metric = sort_by or grid.sort_metric
    try:
        models = grid.sorted_models(metric)
    except Exception:
        models = list(grid.models)
    if decreasing is not None and str(decreasing).lower() == "true":
        models = models[::-1]
    hyper_names = sorted({k for m in models
                          for k in (m.output.get("grid_params") or {})})
    rows = []
    for m in models:
        gp = m.output.get("grid_params") or {}
        mm_ = m.default_metrics
        val = None
        if mm_ is not None:
            try:
                val = float(mm_[metric.upper()
                                if metric.lower() == "auc" else metric])
            except Exception:
                try:
                    val = float(mm_["MSE"])
                except Exception:
                    val = None
        rows.append([str(gp.get(h)) for h in hyper_names] +
                    [m.key, val])
    summary = twodim(
        "Hyper-Parameter Search Summary",
        hyper_names + ["model_ids", metric],
        ["string"] * len(hyper_names) + ["string", "float64"], rows)
    return {
        "__meta": {"schema_version": 99, "schema_name": "GridSchemaV99",
                   "schema_type": "Grid"},
        "grid_id": {"name": grid.grid_id, "type": "Key<Grid>"},
        "model_ids": [{"name": m.key, "type": "Key<Model>"}
                      for m in models],
        "hyper_names": hyper_names,
        "failure_details": [f["error"] for f in grid.failures],
        "failure_stack_traces": [f.get("stacktrace", f["error"])
                                 for f in grid.failures],
        "failed_params": [f["params"] for f in grid.failures],
        "warning_details": [],
        "export_checkpoints_dir": None,
        "summary_table": summary,
    }


@route("GET", r"/99/Grids/(?P<gid>[^/]+)")
def _grid_get(params, body, gid=None):
    from h2o3_tpu.ml.grid import Grid
    g = DKV.get(gid)
    if not isinstance(g, Grid):
        raise KeyError(f"grid {gid} not found")
    return _grid_json(g, sort_by=params.get("sort_by"),
                      decreasing=params.get("decreasing"))


@route("GET", "/99/Grids")
def _grids_list(params, body):
    from h2o3_tpu.ml.grid import Grid
    out = []
    for k in list(DKV.keys()):
        g = DKV.get_raw(k)
        if isinstance(g, Grid):
            out.append({"name": g.grid_id, "type": "Key<Grid>"})
    return {"grids": out}


@route("GET", r"/99/Models/(?P<mid>[^/]+)")
def _model_one_v99(params, body, mid=None):
    return _model_one(params, body, mid=mid)


@route("POST", "/99/AutoMLBuilder")
def _automl(params, body):
    from h2o3_tpu.automl import H2OAutoML
    p = {k: _coerce(v) for k, v in params.items()}
    # h2o-py ships nested specs (h2o-py/h2o/automl/_estimator.py):
    # build_control{project_name,nfolds,stopping_criteria{...}},
    # input_spec{training_frame,response_column}, build_models{*_algos}
    ctl = p.get("build_control") or {}
    if isinstance(ctl, str):
        ctl = json.loads(ctl)
    crit = ctl.get("stopping_criteria") or {}
    inp = p.get("input_spec") or {}
    if isinstance(inp, str):
        inp = json.loads(inp)
    bm = p.get("build_models") or {}
    if isinstance(bm, str):
        bm = json.loads(bm)
    frame_key = inp.get("training_frame") or p.get("training_frame")
    y = inp.get("response_column") or p.get("response_column")
    if isinstance(y, dict):
        y = y.get("column_name")
    fr = DKV.get(str(frame_key))
    ignored = inp.get("ignored_columns")
    x_cols = ([n for n in fr.names if n not in set(ignored) and n != y]
              if ignored and isinstance(fr, Frame) else None)
    aml = H2OAutoML(
        max_models=int(crit.get("max_models") or p.get("max_models") or 0),
        max_runtime_secs=float(crit.get("max_runtime_secs")
                               or p.get("max_runtime_secs") or 3600),
        seed=int(crit.get("seed") or p.get("seed") or -1),
        nfolds=int(next(v for v in (ctl.get("nfolds"), p.get("nfolds"), 5)
                        if v is not None)),
        include_algos=bm.get("include_algos"),
        exclude_algos=bm.get("exclude_algos"),
        project_name=ctl.get("project_name") or p.get("project_name"))
    job = Job("automl", dest=aml.project_name)

    def _run(j):
        aml.train(y=y, training_frame=fr, x=x_cols)
        j.update(1.0, "done")
        DKV.put(f"leaderboard_{aml.project_name}_result", aml)
        return aml

    job.start(_run, background=True)
    return {"job": job.to_dict(), "project_name": aml.project_name,
            "build_control": {"project_name": aml.project_name}}


def _automl_tables(aml):
    """leaderboard_table + event_log_table TwoDimTables the real h2o-py
    parses into H2OFrames (h2o-py/h2o/automl/_base.py:333 _fetch_state)."""
    from h2o3_tpu.api.model_schema import twodim
    rows = []
    tab = aml.leaderboard.as_table()
    metric_cols = [k for k in (tab[0].keys() if tab else [])
                   if k != "model_id"]
    if not metric_cols:
        # an empty leaderboard must still carry the metric columns: the
        # client slices fr[1:] off the parsed table
        # (h2o-py/h2o/automl/_base.py:328), which asserts on ncol == 1.
        # Column set follows the task's sort metric.
        sm = (getattr(aml.leaderboard, "sort_metric", None) or "auc").lower()
        if sm in ("auc", "logloss", "aucpr"):
            metric_cols = ["auc", "logloss", "aucpr",
                           "mean_per_class_error", "rmse", "mse"]
        elif sm == "mean_per_class_error":
            metric_cols = ["mean_per_class_error", "logloss", "rmse", "mse"]
        else:
            metric_cols = ["mean_residual_deviance", "rmse", "mse",
                           "mae", "rmsle"]
    for r in tab:
        rows.append([str(r.get("model_id"))] +
                    [r.get(k) for k in metric_cols])
    # col_types feed straight into H2OFrame(column_types=...), whose
    # vocabulary is "double"/"string" (h2o-py _fetch_table)
    lb_table = twodim(
        "Leaderboard", ["model_id"] + metric_cols,
        ["string"] + ["double"] * len(metric_cols), rows)
    ev_rows = [[str(e.get("timestamp", "")), "info",
                e.get("stage", ""), e.get("message", ""), "", ""]
               for e in getattr(aml, "event_log", [])]
    ev_table = twodim(
        "Event Log",
        ["timestamp", "level", "stage", "message", "name", "value"],
        ["string"] * 6, ev_rows)
    return lb_table, ev_table


@route("GET", r"/99/AutoML/(?P<project>[^/]+)")
def _automl_state(params, body, project=None):
    """AutoML state fetch (water/api + ai/h2o/automl AutoMLV99): the
    real client reads project_name, leaderboard.models,
    leaderboard_table and event_log_table."""
    aml = DKV.get(f"leaderboard_{project}_result")
    if aml is None:
        raise KeyError(f"automl project {project} not found")
    lb_table, ev_table = _automl_tables(aml)
    return {"project_name": aml.project_name,
            "leaderboard": {"models": [
                {"name": m.key, "type": "Key<Model>"}
                for m in aml.leaderboard.sorted_models()]},
            "leaderboard_table": lb_table,
            "event_log_table": ev_table,
            "training_info": {}}


@route("GET", r"/99/Leaderboards/(?P<project>[^/]+)")
def _leaderboard(params, body, project=None):
    aml = DKV.get(f"leaderboard_{project}_result")
    if aml is None:
        raise KeyError(f"automl project {project} not found")
    lb_table, _ = _automl_tables(aml)
    return {"project_name": project,
            "models": [{"name": m.key, "type": "Key<Model>"}
                       for m in aml.leaderboard.sorted_models()],
            "table": lb_table,
            "leaderboard_table": aml.leaderboard.as_table()}


@route("GET", r"/flow(/index\.html)?/?")
def _flow(params, body, **_):
    """The Flow notebook UI (h2o-web role) — served from the node at
    /flow/index.html like the reference."""
    from h2o3_tpu.api.flow import FLOW_HTML
    return {"__html__": FLOW_HTML}


@route("GET", "/")
def _index(params, body):
    """Minimal landing page (the h2o-web Flow-serving role: the node
    itself answers a browser with a live cluster view)."""
    info = cloud_mod.cluster_info()
    frames = sum(1 for k in DKV.keys()
                 if isinstance(DKV.get_raw(k), Frame)
                 or getattr(DKV.get_raw(k), "_is_lazy_stub", False))
    models = sum(1 for k in DKV.keys()
                 if isinstance(DKV.get_raw(k), Model))
    html = f"""<!doctype html><html><head><title>h2o3-tpu</title></head>
<body style="font-family:monospace">
<h2>h2o3-tpu cloud '{info["cloud_name"]}'</h2>
<p>{info["cloud_size"]} device(s) on {info["platform"]} —
healthy: {info["cloud_healthy"]}</p>
<p>{frames} frame(s), {models} model(s),
{len(all_algos())} algorithms registered</p>
<p><a href="/flow/index.html"><b>Open Flow (notebook UI)</b></a></p>
<p>REST: <a href="/3/Cloud">/3/Cloud</a> ·
<a href="/3/Frames">/3/Frames</a> ·
<a href="/3/Models">/3/Models</a> ·
<a href="/3/ModelBuilders">/3/ModelBuilders</a> ·
<a href="/3/Jobs">/3/Jobs</a> ·
<a href="/3/Timeline">/3/Timeline</a> ·
<a href="/3/Metrics">/3/Metrics</a> ·
<a href="/3/Trace">/3/Trace</a> ·
<a href="/3/Logs">/3/Logs</a> ·
<a href="/3/SelfBench">/3/SelfBench</a></p>
</body></html>"""
    return {"__html__": html}


def _cluster_requested(params) -> bool:
    """``?cluster=1`` opt-in, honored only on a multi-process cloud —
    with process_count()==1 every cluster view IS the local view
    (bit-identical by construction, asserted in tier-1)."""
    if str(params.get("cluster") or "").lower() not in ("1", "true",
                                                        "yes"):
        return False
    try:
        import jax
        return jax.process_count() > 1
    except Exception:   # noqa: BLE001 - no backend → local view
        return False


@route("GET", "/3/Metrics")
def _metrics(params, body):
    """Runtime telemetry snapshot (h2o3_tpu/telemetry): registry
    counters/gauges/histograms + recent spans. ``?format=prometheus``
    returns text exposition 0.0.4 for a scraping agent; the JSON shape
    additionally carries the span ring, the per-span-name aggregate and
    the compile observer's ledger by program (``programs``: traces,
    lowerings, compiles and persistent-cache loads with their seconds).
    ``?cluster=1`` on a multi-process cloud merges every peer's fan-in
    snapshot (telemetry/cluster.py): counters summed across nodes,
    gauges/histograms per-node with a ``node=`` label, peers past their
    publish window served stale-but-labeled (``stale_nodes``)."""
    from h2o3_tpu import telemetry
    # refresh the slo_* burn-rate gauges so every scrape carries the
    # current objective health (telemetry/slo.py — best-effort: the
    # scrape must survive a broken rule)
    try:
        from h2o3_tpu.telemetry import slo as _slo
        _slo.evaluate()
    except Exception:   # noqa: BLE001 - scrape over alerting
        pass
    fmt = str(params.get("format") or "").lower()
    if _cluster_requested(params):
        from h2o3_tpu.telemetry import cluster
        col = cluster.collect()
        if fmt in ("prometheus", "prom", "text"):
            return {"__bytes__": cluster.merged_prometheus(col).encode(),
                    "__ctype__":
                        "text/plain; version=0.0.4; charset=utf-8"}
        summaries = cluster.node_summaries(col)
        return {"metrics": cluster.merged_metrics(col),
                "spans": telemetry.spans_snapshot(50),
                "span_aggregate": telemetry.spans_aggregate(),
                "programs": telemetry.programs_snapshot(),
                "cluster": {
                    "process_count": col["process_count"],
                    "stale_nodes": col["stale_nodes"],
                    "nodes": [summaries[n] for n in sorted(summaries)],
                }}
    if fmt in ("prometheus", "prom", "text"):
        return {"__bytes__": telemetry.to_prometheus().encode(),
                "__ctype__": "text/plain; version=0.0.4; charset=utf-8"}
    try:
        nspans = int(float(params.get("spans") or 50))
    except (TypeError, ValueError):
        nspans = 50
    return {"metrics": telemetry.snapshot(),
            "spans": telemetry.spans_snapshot(nspans),
            "span_aggregate": telemetry.spans_aggregate(),
            "programs": telemetry.programs_snapshot()}


@route("GET", "/3/Alerts")
def _alerts(params, body):
    """SLO burn-rate evaluation (telemetry/slo.py): every declarative
    objective's state (healthy/burning/alert/recovery), 5m/1h burn
    rates, and the currently-firing alerts. ``?cluster=1`` on a
    multi-process cloud merges every peer's published alert view
    (telemetry/cluster.py fan-in), each entry stamped with its
    ``node``."""
    from h2o3_tpu.telemetry import slo
    if _cluster_requested(params):
        from h2o3_tpu.telemetry import cluster
        return cluster.merged_alerts()
    return slo.evaluate()


@route("GET", "/3/WaterMeterCpuTicks")
def _water_meter(params, body):
    """Per-core cpu tick counters (water/util/WaterMeterCpuTicks.java).
    Wire layout per LinuxProcFileReader: [user+nice, system, other(io),
    idle]."""
    ticks = []
    try:
        with open("/proc/stat") as f:
            for line in f:
                if line.startswith("cpu") and line[3].isdigit():
                    p = line.split()   # cpuN user nice system idle iowait…
                    ticks.append([int(p[1]) + int(p[2]), int(p[3]),
                                  int(p[5]), int(p[4])])
    except OSError:
        pass
    if not ticks:
        # no /proc (macOS, sandboxes): synthesize one pseudo-core from
        # the process's own rusage so the endpoint still reports REAL
        # collected data instead of an empty stub
        import os as _os
        t = _os.times()
        hz = 100.0
        ticks = [[int(t.user * hz), int(t.system * hz), 0,
                  int(max(t.elapsed - t.user - t.system, 0) * hz)]]
    return {"cpu_ticks": ticks}


@route("GET", "/3/Timeline")
def _timeline(params, body):
    from h2o3_tpu.utils.timeline import snapshot
    return {"events": snapshot(last=params.get("last"))}


@route("GET", "/3/JStack")
def _jstack(params, body):
    """Thread stack dump (water/api/JStackHandler role)."""
    import sys
    import traceback
    frames = sys._current_frames()
    threads = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in frames.items():
        out.append({"thread": threads.get(tid, str(tid)),
                    "stack": traceback.format_stack(frame)})
    return {"traces": out}


@route("GET", "/3/Profiler")
def _profiler(params, body):
    """Statistical CPU profile (water/api/ProfilerHandler): sample every
    thread's Python stack `depth` times at short intervals and count
    identical stacks — the reference aggregates JVM stack samples the
    same way."""
    import sys
    import time as _t
    import traceback
    depth = int(float(params.get("depth") or 10))
    counts: Dict[str, int] = {}
    for _ in range(max(1, min(depth, 100))):
        for tid, frame in sys._current_frames().items():
            sig = "".join(traceback.format_stack(frame)[-6:])
            counts[sig] = counts.get(sig, 0) + 1
        _t.sleep(0.01)
    nodes = [{"entries": [
        {"stacktrace": sig, "count": cnt}
        for sig, cnt in sorted(counts.items(), key=lambda kv: -kv[1])[:30]
    ]}]
    # span-level profile rides along: where the RUNTIME's structured
    # phases (jobs, fits, chunks, parses) actually spent wall time —
    # complements the raw stack samples the same way the reference's
    # Timeline complements its Profiler
    from h2o3_tpu import telemetry
    return {"nodes": nodes, "depth": depth,
            "spans": telemetry.spans_aggregate()}


@route("GET", "/3/SelfBench")
def _selfbench(params, body):
    """Node capability probes (water/init/{Linpack,MemoryBandwidth,
    NetworkBench} role)."""
    from h2o3_tpu.core.selfcheck import run_self_bench
    return run_self_bench()


@route("GET", "/3/Logs")
def _logs(params, body):
    """Recent log lines (water/api/LogsHandler role) from the structured
    pipeline's ring buffers: ``?level=ERROR`` selects a per-level ring,
    ``?last=N`` bounds the tail. ``?cluster=1`` on a multi-process
    cloud merges every peer's published tail, timestamp-ordered, each
    line prefixed with its node id."""
    from h2o3_tpu.utils.log import level_counts, log_buffer, log_file_path
    level = params.get("level")
    try:
        last = int(float(params.get("last") or 0)) or None
    except (TypeError, ValueError):
        last = None
    if _cluster_requested(params):
        from h2o3_tpu.telemetry import cluster
        merged = cluster.merged_logs(level=level, last=last)
        return {"log": "\n".join(merged["lines"]),
                "lines": merged["lines"],
                "level": (level or "ALL").upper(),
                "level_counts": level_counts(),
                "file": log_file_path() or "",
                "cluster": {"process_count": merged["process_count"],
                            "stale_nodes": merged["stale_nodes"]}}
    lines = log_buffer(level=level, last=last)
    return {"log": "\n".join(lines),
            "lines": lines,
            "level": (level or "ALL").upper(),
            "level_counts": level_counts(),
            "file": log_file_path() or ""}


@route("GET", "/3/Logs/download")
def _logs_download(params, body):
    """The whole log as a text attachment (h2o.download_all_logs role).
    Serves the rotating file sink when H2O3TPU_LOG_DIR is active,
    otherwise the in-memory ring — never again the empty stub."""
    from h2o3_tpu.utils.log import log_buffer, log_file_path
    path = log_file_path()
    data = None
    if path:
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            data = None
    if data is None:
        data = ("\n".join(log_buffer()) + "\n").encode()
    return {"__bytes__": data, "__ctype__": "text/plain; charset=utf-8",
            "__headers__": {
                "Content-Disposition":
                    'attachment; filename="h2o3tpu.log"'}}


@route("GET", r"/3/Jobs/(?P<key>[^/]+)/trace")
def _job_trace(params, body, key=None):
    """One job's flight-recorder capsule as Chrome trace-event JSON —
    load it in https://ui.perfetto.dev (telemetry/trace_export.py)."""
    from h2o3_tpu.telemetry import flight_recorder, trace_export
    cap = flight_recorder.get_capsule(key)
    if cap is None:
        raise KeyError(
            f"no telemetry capsule for job {key} (cancelled capsules "
            f"are swept; completed ones are retained for the last "
            f"{flight_recorder.keep_count()} jobs — "
            f"H2O3TPU_FLIGHT_RECORDER_KEEP)")
    return trace_export.capsule_trace(cap)


@route("GET", r"/3/Jobs/(?P<key>[^/]+)/telemetry")
def _job_telemetry(params, body, key=None):
    """The raw capsule (spans/events/compiles/logs/metric deltas)."""
    from h2o3_tpu.telemetry import flight_recorder
    cap = flight_recorder.get_capsule(key)
    if cap is None:
        raise KeyError(f"no telemetry capsule for job {key}")
    return cap.to_dict()


@route("GET", "/3/Trace")
def _process_trace(params, body):
    """The whole process ring (spans + timeline + compiles) as Chrome
    trace JSON — the zoomed-out view when no single job is suspect.
    ``?cluster=1`` on a multi-process cloud merges every peer's
    published ring tails into ONE trace with ``pid`` = process_index,
    so Perfetto renders one track group per host.
    ``?trace_id=`` instead stitches ONE request's spans — from every
    host that published them — into a single causal trace (cross-
    process parent links, not pid-grouped tracks): the distributed-
    tracing read side (ISSUE 16)."""
    from h2o3_tpu.telemetry import trace_export
    trace_id = params.get("trace_id")
    if trace_id:
        from h2o3_tpu.telemetry import cluster
        return cluster.stitched_trace(trace_id)
    if _cluster_requested(params):
        from h2o3_tpu.telemetry import cluster
        return cluster.merged_trace()
    try:
        nspans = int(float(params.get("spans") or 2048))
        nevents = int(float(params.get("events") or 2048))
    except (TypeError, ValueError):
        nspans, nevents = 2048, 2048
    return trace_export.process_trace(last_spans=nspans,
                                      last_events=nevents)


@route("POST", "/3/Profiler/capture")
def _profiler_capture(params, body):
    """Bounded jax.profiler window (the /3/JProfile analogue): captures
    a TensorBoard-loadable device trace for ``duration_ms`` (capped at
    10s) into ``log_dir``. Degrades gracefully — a backend that cannot
    profile answers with supported=false, not a 500."""
    import os
    import tempfile
    try:
        dur_ms = float(params.get("duration_ms") or 1000.0)
    except (TypeError, ValueError):
        raise ValueError(
            f"malformed duration_ms {params.get('duration_ms')!r}")
    dur_s = min(max(dur_ms, 1.0), 10_000.0) / 1000.0
    log_dir = _unquote(str(params.get("log_dir") or "")) or \
        tempfile.mkdtemp(prefix="h2o3tpu_jprofile_")
    started = False
    try:
        import jax
        jax.profiler.start_trace(log_dir)
        started = True
        time.sleep(dur_s)
    except Exception as e:   # noqa: BLE001 - degrade, don't 500
        return {"supported": False, "error": str(e)[:500],
                "log_dir": log_dir if started else None}
    finally:
        if started:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:   # noqa: BLE001
                pass
    files = []
    for root, _dirs, names in os.walk(log_dir):
        files.extend(os.path.join(root, n) for n in names)
    return {"supported": True, "log_dir": log_dir,
            "duration_ms": dur_s * 1000.0, "files": sorted(files)[:100]}


@route("POST", "/3/CloudCheckpoint")
def _cloud_checkpoint(params, body):
    """Whole-cloud checkpoint (ISSUE 18): quiesce RUNNING jobs
    (bounded), persist the DKV — frames as device-independent blocks,
    models as device-lowered binaries — under ``dir``, manifest written
    last. ``init(restore_dir=<dir>)`` reforms the cloud bit-identically
    (core/durability.py)."""
    d = params.get("dir") or params.get("directory") or \
        (body.get("dir") if isinstance(body, dict) else None)
    if not d:
        raise ValueError("CloudCheckpoint requires a 'dir' parameter")
    quiesce_s = float(params.get("quiesce_s") or 30.0)
    from h2o3_tpu.core import durability as _durability
    return _durability.cloud_checkpoint(str(d), quiesce_s=quiesce_s)


@route("POST", "/3/Shutdown")
def _shutdown(params, body):
    threading.Thread(target=lambda: _SERVER and _SERVER.shutdown(),
                     daemon=True).start()
    return {}


# ------------------------------------------------------------- plumbing


class AdmissionGate:
    """Bounded in-flight request gate (the reference's bounded Jetty
    pool role, water/api/RequestServer): at most ``max_inflight``
    requests execute handlers concurrently; up to ``queue_depth`` more
    wait for a slot (bounded by ``queue_wait_s`` or their own request
    deadline, whichever is sooner); everything past that fails fast
    with 503 + Retry-After so overload degrades into clean client
    retries instead of an unbounded handler-thread pile-up."""

    def __init__(self, max_inflight: int, queue_depth: int,
                 queue_wait_s: float):
        self.max_inflight = max(1, int(max_inflight))
        self.queue_depth = max(0, int(queue_depth))
        self.queue_wait_s = float(queue_wait_s)
        self._inflight = 0
        self._waiting = 0
        self._cond = threading.Condition()

    def enter(self, deadline: Optional[float] = None) -> bool:
        """True = admitted (caller MUST pair with leave()); False =
        saturated, answer 503."""
        from h2o3_tpu import telemetry
        gauge = telemetry.gauge("rest_inflight_requests")
        with self._cond:
            if self._inflight < self.max_inflight:
                self._inflight += 1
                gauge.set(self._inflight)
                return True
            if self._waiting >= self.queue_depth:
                return False
            t_q = time.monotonic()
            limit = t_q + self.queue_wait_s
            if deadline is not None:
                limit = min(limit, deadline)
            self._waiting += 1
            try:
                while self._inflight >= self.max_inflight:
                    left = limit - time.monotonic()
                    if left <= 0:
                        return False
                    self._cond.wait(left)
                self._inflight += 1
                gauge.set(self._inflight)
                return True
            finally:
                self._waiting -= 1
                # queue-wait leg of the RED surface: how long admitted
                # AND timed-out requests sat waiting for a slot
                telemetry.histogram("rest_queue_wait_seconds").observe(
                    time.monotonic() - t_q)

    def leave(self) -> None:
        from h2o3_tpu import telemetry
        with self._cond:
            self._inflight -= 1
            telemetry.gauge("rest_inflight_requests").set(self._inflight)
            self._cond.notify()


def _gate_from_config() -> AdmissionGate:
    """Build the gate from config.ARGS with H2O3TPU_REST_* env overrides
    on top (same pattern as watchdog.policy_from_config: servers booted
    without init() still honor the knobs)."""
    import os
    from h2o3_tpu.core import config as _cfg
    env = os.environ.get
    a = _cfg.ARGS
    return AdmissionGate(
        max_inflight=int(env("H2O3TPU_REST_MAX_INFLIGHT",
                             a.rest_max_inflight)),
        queue_depth=int(env("H2O3TPU_REST_QUEUE_DEPTH",
                            a.rest_queue_depth)),
        queue_wait_s=float(env("H2O3TPU_REST_QUEUE_WAIT_S",
                               a.rest_queue_wait_s)))


def _max_body_bytes() -> int:
    import os
    from h2o3_tpu.core import config as _cfg
    mb = int(os.environ.get("H2O3TPU_REST_MAX_BODY_MB",
                            _cfg.ARGS.rest_max_body_mb))
    return mb << 20


# health checks, the metrics scrape, and job polling/cancel must keep
# answering while the gate rejects work — an overloaded node that stops
# ping/poll responses looks dead to every client and orchestrator
_EXEMPT_PREFIXES = ("/3/Ping", "/3/Metrics", "/3/Jobs")


def _admission_exempt(path: str) -> bool:
    return any(path == p or path.startswith(p + "/")
               for p in _EXEMPT_PREFIXES)


_UPLOAD_CHUNK = 1 << 20      # /3/PostFile disk-streaming block


def _job_key_of(out) -> Optional[str]:
    """Job key inside a handler response: ModelBuilderSchema-style
    {"job": JobV3} or a bare JobV3 at the root."""
    if not isinstance(out, dict):
        return None
    jd = out.get("job")
    if isinstance(jd, dict) and isinstance(jd.get("key"), dict):
        return jd["key"].get("name")
    meta = out.get("__meta")
    if isinstance(meta, dict) and meta.get("schema_name") == "JobV3" \
            and isinstance(out.get("key"), dict):
        return out["key"].get("name")
    return None


def _await_job_deadline(out, deadline: float, path: str):
    """A deadlined request that spawned a background job blocks until
    the job finishes or the deadline passes. Expiry cancels the job —
    the cooperative checks (Job.update / map_reduce cancel_point) stop
    it at the next chunk boundary, the job ends CANCELLED, and the
    client gets 408 instead of a leaked RUNNING job."""
    jk = _job_key_of(out)
    if not jk:
        return out, 200
    from h2o3_tpu import telemetry
    j = DKV.get(jk)
    while isinstance(j, Job) and j.status in ("CREATED", "RUNNING"):
        if time.monotonic() >= deadline:
            j.cancel()
            j.join(5.0)      # grace: one chunk boundary away
            telemetry.counter("request_deadline_exceeded_total").inc()
            err = _error_json(path, request_ctx.DeadlineExceeded(
                f"request deadline exceeded; job {jk} cancelled"), 408)
            err["values"] = {"job": jk,
                            "job_status": getattr(j, "status", "?")}
            return err, 408
        time.sleep(0.02)
        j = DKV.get(jk)
    if isinstance(j, Job):
        # finished inside the deadline: refresh the snapshot the client
        # sees (it was RUNNING when the handler returned)
        if isinstance(out.get("job"), dict):
            out["job"] = j.to_dict()
        elif _job_key_of(out) == jk and out.get("__meta", {}).get(
                "schema_name") == "JobV3":
            out = j.to_dict()
    return out, 200


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):   # route to our logger
        log.debug("http: " + fmt, *args)

    def _dispatch(self, method: str):
        try:
            self._dispatch_inner(method)
        except (BrokenPipeError, ConnectionResetError) as e:
            # the client hung up mid-request/mid-response — a normal
            # event under load, not a handler crash worth a traceback
            from h2o3_tpu import telemetry
            telemetry.counter("rest_client_disconnects_total").inc()
            log.info("client disconnected on %s %s: %r",
                     method, self.path, e)
            self.close_connection = True

    _DRAIN_CAP = 8 << 20

    def _drain(self, length: int) -> bool:
        """Consume a modest unread request body so an early error
        response can be read reliably and the connection stays usable;
        oversized bodies are left unread (the caller then closes the
        connection instead of swallowing gigabytes)."""
        if length > self._DRAIN_CAP:
            return False
        left = length
        while left > 0:
            chunk = self.rfile.read(min(_UPLOAD_CHUNK, left))
            if not chunk:
                break
            left -= len(chunk)
        return True

    def _respond(self, code: int, out, extra_headers: Optional[dict] = None,
                 close: bool = False):
        if isinstance(out, dict) and "__bytes__" in out:
            payload = out["__bytes__"]
            ctype = out.get("__ctype__", "application/octet-stream")
            extra_headers = {**(out.get("__headers__") or {}),
                             **(extra_headers or {})}
        elif isinstance(out, dict) and "__html__" in out:
            payload = out["__html__"].encode()
            ctype = "text/html; charset=utf-8"
        else:
            payload = json.dumps(_json_sanitize(out),
                                 default=_json_default).encode()
            ctype = "application/json"
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(payload)))
        tc = getattr(self, "_trace_ctx", None)
        if tc is not None:
            # every response names its trace — the client's handle into
            # GET /3/Trace?trace_id= (ISSUE 16)
            self.send_header("X-H2O-Trace-Id", tc.trace_id)
        for hk, hv in (extra_headers or {}).items():
            self.send_header(hk, hv)
        if close:
            # the body was not (fully) read: the connection cannot be
            # reused — the leftover bytes would parse as a new request
            self.send_header("Connection", "close")
            self.close_connection = True
        self.end_headers()
        self.wfile.write(payload)

    def _dispatch_inner(self, method: str):
        from h2o3_tpu import telemetry
        from h2o3_tpu.telemetry import trace_context
        parsed = urllib.parse.urlparse(self.path)
        path = parsed.path
        params: Dict[str, str] = {
            k: v[0] for k, v in urllib.parse.parse_qs(parsed.query).items()}

        # -- distributed trace ingress (traceparent header) ------------
        # an incoming W3C-style traceparent joins the client's trace
        # (malformed → fresh trace, never a 4xx: tracing is telemetry);
        # _respond echoes the id as X-H2O-Trace-Id on EVERY response
        tc = trace_context.parse_traceparent(
            self.headers.get("traceparent"))
        self._trace_ctx = tc if tc is not None \
            else trace_context.new_context()

        # -- request deadline (?_timeout_ms= / X-H2O-Deadline-Ms) ------
        deadline = None
        tmo = params.pop("_timeout_ms", None)
        if tmo is None:
            tmo = self.headers.get("X-H2O-Deadline-Ms")
        if tmo is not None:
            try:
                tmo_ms = float(tmo)
            except (TypeError, ValueError):
                return self._respond(400, _error_json(path, ValueError(
                    f"malformed deadline {tmo!r} "
                    f"(expected milliseconds)"), 400))
            if tmo_ms > 0:
                deadline = time.monotonic() + tmo_ms / 1000.0

        # -- Content-Length must be a clean non-negative integer -------
        raw_len = self.headers.get("Content-Length")
        try:
            length = int(raw_len) if raw_len else 0
            if length < 0:
                raise ValueError(raw_len)
        except (TypeError, ValueError):
            telemetry.counter("rest_rejected_total",
                              reason="bad_content_length").inc()
            return self._respond(400, _error_json(path, ValueError(
                f"malformed Content-Length: {raw_len!r}"), 400),
                close=True)

        # -- admission control (exempt: ping/metrics/job polling) ------
        exempt = _admission_exempt(path)
        if not exempt and not _GATE.enter(deadline=deadline):
            telemetry.counter("rest_rejected_total",
                              reason="saturated").inc()
            drained = self._drain(length)
            return self._respond(503, _error_json(path, RuntimeError(
                f"server saturated ({_GATE.max_inflight} in flight, "
                f"{_GATE.queue_depth} queued); retry later"), 503),
                extra_headers={"Retry-After": "1"}, close=not drained)
        try:
            self._handle(method, path, params, length, deadline)
        finally:
            if not exempt:
                _GATE.leave()

    def _post_file(self, path: str, length: int):
        """Raw file-body upload (h2o-py sends the file bytes as the
        request body, h2o-py/h2o/backend/connection.py:473) — streamed
        to disk in 1 MiB blocks so a multi-GB upload never buffers in
        handler memory."""
        import tempfile
        first = self.rfile.read(min(length, _UPLOAD_CHUNK)) \
            if length else b""
        # the client sends no filename: sniff the container format so
        # the extension-dispatching parser picks the right reader
        if first[:4] == b"PK\x03\x04":
            suffix = ".zip"
        elif first[:2] == b"\x1f\x8b":
            suffix = ".csv.gz"
        elif first[:4] == b"PAR1":
            suffix = ".parquet"
        else:
            suffix = ".csv"
        fd, tmp = tempfile.mkstemp(prefix="h2o3tpu_upload_",
                                   suffix=suffix)
        total = len(first)
        with open(fd, "wb") as f:
            f.write(first)
            while total < length:
                chunk = self.rfile.read(min(_UPLOAD_CHUNK,
                                            length - total))
                if not chunk:
                    break
                f.write(chunk)
                total += len(chunk)
        self._respond(200, {"destination_frame": tmp,
                            "total_bytes": total})

    def _handle(self, method: str, path: str, params: Dict[str, str],
                length: int, deadline: Optional[float]):
        from h2o3_tpu import telemetry
        if path.startswith("/3/PostFile"):
            return self._post_file(path, length)
        max_body = _max_body_bytes()
        if length > max_body:
            telemetry.counter("rest_rejected_total",
                              reason="body_too_large").inc()
            drained = self._drain(length)
            return self._respond(413, _error_json(path, ValueError(
                f"request body of {length} bytes exceeds the "
                f"{max_body >> 20} MB cap (H2O3TPU_REST_MAX_BODY_MB); "
                f"use /3/PostFile for large uploads"), 413),
                close=not drained)
        raw = self.rfile.read(length) if length else b""
        body = raw.decode("utf-8", "replace")
        ctype = self.headers.get("Content-Type", "")
        if "json" in ctype and body:
            try:
                params.update(json.loads(body))
            except json.JSONDecodeError as e:
                # a body the client MARKED as JSON but that does not
                # parse must fail loudly — silently ignoring it ran
                # handlers with half the intended parameters
                return self._respond(400, _error_json(path, ValueError(
                    f"malformed JSON body: {e}"), 400))
        elif body:
            params.update({k: v[0]
                           for k, v in urllib.parse.parse_qs(body).items()})
        # h2o-py style clients ship every parameter form-encoded in the
        # body: honor a _timeout_ms that arrived there too (query-string
        # and header deadlines were already parsed pre-admission)
        tmo = params.pop("_timeout_ms", None)
        if tmo is not None and deadline is None:
            try:
                tmo_ms = float(tmo)
            except (TypeError, ValueError):
                return self._respond(400, _error_json(path, ValueError(
                    f"malformed deadline {tmo!r} "
                    f"(expected milliseconds)"), 400))
            if tmo_ms > 0:
                deadline = time.monotonic() + tmo_ms / 1000.0
        from h2o3_tpu.utils.timeline import record as _tl_record
        for m, rx, fn in ROUTES:
            if m != method:
                continue
            match = rx.match(path)
            if match:
                # endpoint label = the route PATTERN (bounded
                # cardinality), not the raw path with its keys
                endpoint = rx.pattern.strip("^$")
                telemetry.counter("rest_requests_total", method=method,
                                  endpoint=endpoint).inc()
                t_req = time.monotonic()
                retry_after = "1"
                redirect_loc = None
                try:
                    # the deadline and trace context ride contextvars:
                    # any Job the handler creates captures both
                    # (core/job.py), the cooperative checks enforce the
                    # deadline at chunk boundaries, and every span the
                    # handler opens is stamped with the request's trace
                    from h2o3_tpu.telemetry import trace_context
                    with request_ctx.deadline_scope(deadline), \
                            trace_context.trace_scope(
                                getattr(self, "_trace_ctx", None)), \
                            telemetry.span("rest", method=method,
                                           endpoint=endpoint):
                        # recorded INSIDE the span so the Timeline event
                        # carries this request's span id
                        _tl_record("rest", f"{method} {path}")
                        out = fn(params, body, **match.groupdict())
                    code = 200
                except request_ctx.DeadlineExceeded as e:
                    out = _error_json(path, e, 408)
                    code = 408
                except KeyError as e:
                    out = _error_json(path, e, 404)
                    code = 404
                except ValueError as e:
                    # user-input errors → 412 + H2OErrorV3, which the
                    # real h2o-py maps to H2OResponseError
                    # (EnvironmentError) — raw 500s become
                    # H2OServerError and break every pyunit that
                    # asserts on invalid parameters
                    # (water/api/RequestServer.java:371 error path).
                    # Logged with traceback: an internal bug surfacing
                    # as ValueError must stay diagnosable server-side.
                    log.warning("412 on %s %s: %s", method, path, e,
                                exc_info=True)
                    out = _error_json(path, e, 412)
                    code = 412
                except QueueSaturated as e:
                    # per-model predict queue full: the AdmissionGate
                    # overload contract applied to the scoring queue
                    telemetry.counter("rest_rejected_total",
                                      reason="predict_queue_full").inc()
                    out = _error_json(path, e, 503)
                    code = 503
                except BatcherDraining as e:
                    # serving tier shutting down: queued/new predicts
                    # fail fast 503 instead of hanging on a closing
                    # dispatcher (ISSUE 17 graceful drain)
                    telemetry.counter("rest_rejected_total",
                                      reason="draining").inc()
                    out = _error_json(path, e, 503)
                    code = 503
                except DataLostError as e:
                    # a frame proven unrecoverable (peer death, no
                    # mirror or replayable lineage): 410 Gone in
                    # H2OErrorV3 shape — typed and terminal, a retry
                    # cannot bring the data back (core/durability.py)
                    telemetry.counter("rest_rejected_total",
                                      reason="data_lost").inc()
                    out = _error_json(path, e, 410)
                    code = 410
                except FleetUnavailable as e:
                    # every replica unhealthy: explicit degradation —
                    # 503 + Retry-After in H2OErrorV3 shape, never a
                    # hang (serving/fleet.py routing contract)
                    telemetry.counter("rest_rejected_total",
                                      reason="fleet_unavailable").inc()
                    retry_after = str(max(
                        1, int(round(e.retry_after_s))))
                    out = _error_json(path, e, 503)
                    code = 503
                except Exception as e:   # noqa: BLE001 - request boundary
                    log.exception("handler error on %s %s", method, path)
                    out = _error_json(path, e, 500)
                    code = 500
                if code == 200 and isinstance(out, dict) \
                        and "__redirect__" in out:
                    # fleet 307: same-method redirect at the chosen
                    # replica (serving/fleet.py routing contract)
                    redirect_loc = out["__redirect__"]
                    out = {"location": redirect_loc}
                    code = 307
                if code == 200 and deadline is not None:
                    out, code = _await_job_deadline(out, deadline, path)
                # RED per-route latency: the duration leg next to the
                # rest_requests_total rate leg (route = bounded pattern,
                # status = final HTTP code incl. the 408 deadline path)
                telemetry.histogram("rest_request_seconds",
                                    route=endpoint,
                                    status=str(code)).observe(
                    time.monotonic() - t_req)
                extra = None
                if code == 503:
                    extra = {"Retry-After": retry_after}
                elif code == 307:
                    extra = {"Location": redirect_loc}
                return self._respond(code, out, extra_headers=extra)
        _tl_record("rest", f"{method} {path}", status=404)
        telemetry.counter("rest_requests_total", method=method,
                          endpoint="(no_route)").inc()
        self._respond(404, {"msg": f"no route {method} {path}"})

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")


_GATE = _gate_from_config()


def _error_json(path: str, e: Exception, status: int) -> dict:
    """H2OErrorV3 wire shape (water/api/schemas3/H2OErrorV3.java) — the
    real h2o-py turns this into an H2OResponseError with .msg etc."""
    import time
    import traceback
    return {"__meta": {"schema_version": 3, "schema_name": "H2OErrorV3",
                       "schema_type": "H2OError"},
            "timestamp": int(time.time() * 1000),
            "error_url": path, "msg": str(e),
            "dev_msg": str(e), "http_status": status, "values": {},
            "exception_type": type(e).__name__,
            "exception_msg": str(e),
            "stacktrace": traceback.format_exc().splitlines()[-10:]}


def _json_default(o):
    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    if isinstance(o, float) and np.isnan(o):
        return None
    return str(o)


def _nan_str_list(vals):
    """ColV3 data cells: NaN→"NaN", ±inf→"Infinity"/"-Infinity"
    (AutoBuffer JSON_NAN/JSON_POS_INF strings)."""
    out = []
    for v in vals:
        if isinstance(v, np.generic):
            v = v.item()
        if isinstance(v, float):
            if np.isnan(v):
                v = "NaN"
            elif np.isinf(v):
                v = "Infinity" if v > 0 else "-Infinity"
        out.append(v)
    return out


def _json_sanitize(o):
    """Strict-JSON cleanup: NaN/Infinity become null everywhere EXCEPT
    ColV3 ``data`` arrays — there NA cells ride as the STRING "NaN",
    exactly the reference wire (AutoBuffer.putJSON8d emits the quoted
    JSON_NAN string, water/AutoBuffer.java:2006); h2o-py decodes
    'x == "NaN"' back to float nan (h2o-py/h2o/expr.py:392) before
    probing math.isnan (expr.py:416)."""
    if isinstance(o, dict):
        meta = o.get("__meta")
        if isinstance(meta, dict) and meta.get("schema_name") == "ColV3":
            return {k: (_nan_str_list(o[k]) if k == "data" and o[k]
                        else _json_sanitize(v))
                    for k, v in o.items()}
        return {k: _json_sanitize(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_json_sanitize(v) for v in o]
    if isinstance(o, np.generic):
        o = o.item()
    if isinstance(o, float) and (np.isnan(o) or np.isinf(o)):
        return None
    return o


_SERVER: Optional[ThreadingHTTPServer] = None
_THREAD: Optional[threading.Thread] = None


def start_server(port: int = 54321, background: bool = True) -> int:
    """Start the REST server (water.api.RequestServer.start).

    Returns the bound port (0 picks an ephemeral port)."""
    global _SERVER, _THREAD, _GATE
    # rebuild the admission gate at boot: init() rebinds config.ARGS and
    # H2O3TPU_REST_* env knobs set after import must take effect
    _GATE = _gate_from_config()
    _SERVER = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
    actual = _SERVER.server_address[1]
    log.info("REST server on http://127.0.0.1:%d (/3, /99)", actual)
    # publish this node's REST edge in the fleet registry: peers route
    # predictions here by ACTUAL bound port (ephemeral binds included)
    try:
        from h2o3_tpu.serving import fleet
        fleet.set_local_endpoint(actual)
    except Exception as e:   # noqa: BLE001 - registry is best-effort
        log.debug("fleet endpoint publish failed: %s", e)
    if background:
        _THREAD = threading.Thread(target=_SERVER.serve_forever, daemon=True)
        _THREAD.start()
    else:
        _SERVER.serve_forever()
    return actual


def stop_server():
    global _SERVER
    try:
        from h2o3_tpu.serving import fleet
        fleet.clear_local_endpoint()
    except Exception:        # noqa: BLE001
        pass
    if _SERVER is not None:
        _SERVER.shutdown()
        _SERVER = None


# schema-metadata endpoints live in api/metadata.py; register them into the
# same ROUTES table at import time
_register_metadata_routes()
