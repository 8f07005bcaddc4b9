"""Native runtime components (C++), loaded via ctypes.

The reference's native layer is the XGBoost JNI bridge
(h2o-extensions/xgboost, SURVEY §2.3); ours is a small C++ library for
the host-side hot paths that JAX/XLA doesn't cover — currently the
chunk-parallel CSV tokenizer (csv_parser.cpp, the water/parser role).

The shared object is compiled on first use with g++ and cached next to
the source under a name keyed by a hash of ``csv_parser.cpp``
(``_csv_parser.<sha>.so``, ignored by git), so a binary left on disk
from other source is never loaded; every consumer must degrade
gracefully when no toolchain is available (`load_csv_parser()` returns
None).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional

import numpy as np

from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "csv_parser.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def library_path() -> str:
    """Where the tokenizer built from the CURRENT source lives."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_csv_parser.{digest}.so")


def _build(so: str) -> bool:
    # build under a per-process name, then rename: several processes
    # (xdist workers, pod workers) may build at once and none may load
    # a half-written file (the name still ends in .so: git ignores it)
    tmp = f"{so[:-3]}.{os.getpid()}.tmp.so"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", tmp]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
        if r.returncode != 0:
            log.warning("native csv build failed: %s",
                        r.stderr.decode()[:500])
            return False
        os.replace(tmp, so)
        log.info("built native csv tokenizer %s", so)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native csv build unavailable: %s", e)
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_csv_parser() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native tokenizer; None on failure."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            so = library_path()
            if not os.path.exists(so) and not _build(so):
                _lib_failed = True
                return None
            lib = ctypes.CDLL(so)
            lib.csv_parse.restype = ctypes.c_void_p
            lib.csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                      ctypes.c_char, ctypes.c_int,
                                      ctypes.c_int]
            lib.csv_nrows.restype = ctypes.c_long
            lib.csv_nrows.argtypes = [ctypes.c_void_p]
            lib.csv_ncols.restype = ctypes.c_int
            lib.csv_ncols.argtypes = [ctypes.c_void_p]
            lib.csv_colname.restype = ctypes.c_char_p
            lib.csv_colname.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.csv_coltype.restype = ctypes.c_int
            lib.csv_coltype.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.csv_numeric.restype = None
            lib.csv_numeric.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                        ctypes.POINTER(ctypes.c_double)]
            lib.csv_codes.restype = None
            lib.csv_codes.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                      ctypes.POINTER(ctypes.c_int)]
            lib.csv_card.restype = ctypes.c_int
            lib.csv_card.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.csv_level.restype = ctypes.c_char_p
            lib.csv_level.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                      ctypes.c_int]
            lib.csv_free.restype = None
            lib.csv_free.argtypes = [ctypes.c_void_p]
            _lib = lib
        except OSError as e:
            log.warning("native csv load failed: %s", e)
            _lib_failed = True
    return _lib


def parse_csv_bytes(data: bytes, sep: str = ",", header: bool = True,
                    nthreads: Optional[int] = None, decode: bool = True):
    """Tokenize a CSV buffer natively.

    Returns (columns dict name→ndarray, domains dict name→levels) or
    None when the native library is unavailable. Numeric columns come
    back float64 with NaN NAs. Categorical columns: with decode=True,
    object arrays of level strings (None for NA); with decode=False,
    raw int32 code arrays (-1 = NA) to feed straight into
    Frame.from_numpy(domains=...) without re-interning — the fast path.
    """
    lib = load_csv_parser()
    if lib is None:
        return None
    if nthreads is None:
        nthreads = min(os.cpu_count() or 4, 16)
    h = lib.csv_parse(data, len(data), sep.encode()[:1], int(header),
                      int(nthreads))
    if not h:
        return None
    try:
        n = lib.csv_nrows(h)
        nc = lib.csv_ncols(h)
        cols: Dict[str, np.ndarray] = {}
        domains: Dict[str, list] = {}
        for j in range(nc):
            name = lib.csv_colname(h, j).decode()
            if lib.csv_coltype(h, j) == 0:
                buf = np.empty(n, dtype=np.float64)
                lib.csv_numeric(h, j, buf.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_double)))
                cols[name] = buf
            else:
                codes = np.empty(n, dtype=np.int32)
                lib.csv_codes(h, j, codes.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int)))
                levels = [lib.csv_level(h, j, k).decode()
                          for k in range(lib.csv_card(h, j))]
                domains[name] = levels
                if decode:
                    vals = np.empty(n, dtype=object)
                    ok = codes >= 0
                    lv = np.asarray(levels, dtype=object)
                    vals[ok] = lv[codes[ok]]
                    vals[~ok] = None
                    cols[name] = vals
                else:
                    cols[name] = codes
        return cols, domains
    finally:
        lib.csv_free(h)
