"""Rapids — the Lisp-like dataframe expression language.

Reference: water/rapids/Rapids.java:27 (parser), water/rapids/Env.java
(scopes + Val types Frame/Num/Str/Seq), ~100 primitives under
water/rapids/ast/prims/{mungers,math,matrix,reducers,operators,...}.
h2o-py builds these expression strings client-side (h2o-py/h2o/expr.py)
and ships them to POST /99/Rapids; this module is the server-side
interpreter.

Execution is eager: structural ops manipulate Column/Frame metadata;
group-by aggregates run as one segment_sum per aggregate over the mesh
(the AstGroup MRTask role). Host numpy carries the remaining munging ops
— they are metadata-scale, not the benchmark hot path, mirroring the
reference's driver-node finalization for merge/sort.

Grammar (Rapids.java:27-52):
  expr := '(' op expr* ')' | number | "string" | id | '[' elems ']'
"""

from __future__ import annotations

import math
import os as _os
import re as _re
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from h2o3_tpu.parallel.mesh import fetch_replicated as _fetch_np

from h2o3_tpu.core.kv import DKV
from h2o3_tpu.frame.column import Column, T_CAT, T_NUM, T_STR, T_UUID
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.parallel import mesh as mesh_mod

# ---------------------------------------------------------------- parser


class _Parser:
    def __init__(self, s: str):
        self.s = s
        self.i = 0

    def peek(self):
        while self.i < len(self.s) and self.s[self.i].isspace():
            self.i += 1
        return self.s[self.i] if self.i < len(self.s) else ""

    def parse(self):
        c = self.peek()
        if c == "(":
            self.i += 1
            items = []
            while self.peek() not in (")", ""):
                items.append(self.parse())
            self.i += 1
            return items
        if c == "[":
            self.i += 1
            items = []
            while self.peek() not in ("]", ""):
                items.append(self.parse())
            self.i += 1
            return ("list", items)
        if c in ("'", '"'):
            quote = c
            self.i += 1
            out = []
            while self.i < len(self.s) and self.s[self.i] != quote:
                ch = self.s[self.i]
                if ch == "\\":
                    self.i += 1
                    ch = self.s[self.i]
                out.append(ch)
                self.i += 1
            self.i += 1
            return ("str", "".join(out))
        j = self.i
        while (j < len(self.s)
               and not self.s[j].isspace() and self.s[j] not in "()[]"):
            j += 1
        tok = self.s[self.i:j]
        self.i = j
        if tok in ("TRUE", "True", "true"):
            return ("num", 1.0)
        if tok in ("FALSE", "False", "false"):
            return ("num", 0.0)
        try:
            return ("num", float(tok))
        except ValueError:
            pass
        # 'lo:cnt[:step]' range inside number lists (AstNumList range
        # syntax: cnt elements starting at lo, stride step; cnt may be
        # 'nan' = through the end — h2o-py serializes Python slices this
        # way, h2o-py/h2o/expr.py _arg_to_expr)
        m = _re.fullmatch(
            r"(-?\d+(?:\.\d+)?):(nan|-?\d+(?:\.\d+)?)(?::(-?\d+))?", tok)
        if m:
            return ("range", float(m.group(1)), float(m.group(2)),
                    int(m.group(3) or 1))
        return ("id", tok)


def parse(expr: str):
    return _Parser(expr).parse()




# ---------------------------------------------------------------- session


class Session:
    """Rapids session: tmp-frame scope (water/rapids/Session.java)."""

    def __init__(self):
        self.tmp: Dict[str, Any] = {}

    def lookup(self, name: str):
        if name in self.tmp:
            return self.tmp[name]
        v = DKV.get(name)
        if v is None:
            raise KeyError(f"Rapids: unknown id '{name}'")
        return v

    def assign(self, name: str, val):
        self.tmp[name] = val
        if isinstance(val, Frame):
            DKV.put(name, val)

    def rm(self, name: str):
        self.tmp.pop(name, None)
        DKV.remove(name)


# --------------------------------------------------------- value helpers


def _as_frame(v) -> Frame:
    if isinstance(v, Frame):
        return v
    if isinstance(v, (int, float)):
        return Frame.from_numpy({"C1": np.array([float(v)])})
    raise TypeError(f"expected frame, got {type(v)}")


def _col_np(frame: Frame, name: str) -> np.ndarray:
    return frame.col(name).to_numpy()


def _cat_codes(frame: Frame, name: str) -> np.ndarray:
    c = frame.col(name)
    codes = _fetch_np(c.data)[: frame.nrows].astype(np.int32).copy()
    codes[_fetch_np(c.na_mask)[: frame.nrows]] = -1
    return codes


def _rebuild(frame: Frame, arrays: Dict[str, np.ndarray],
             keep_domains: bool = True) -> Frame:
    cats, doms = [], {}
    for n in arrays:
        if keep_domains and n in frame and frame.col(n).is_categorical \
                and arrays[n].dtype.kind not in "OUS":
            cats.append(n)
            doms[n] = frame.col(n).domain
        elif arrays[n].dtype == object:
            cats.append(n)
    return Frame.from_numpy(arrays, categorical=cats, domains=doms)


def _take_rows(f: Frame, idx: np.ndarray) -> Frame:
    arrays, cats, doms = {}, [], {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            arrays[n] = _cat_codes(f, n)[idx]
            cats.append(n)
            doms[n] = c.domain
        elif c.type == "string":
            arrays[n] = c.to_numpy()[idx]
        else:
            arrays[n] = _col_np(f, n)[idx]
    return Frame.from_numpy(arrays, categorical=cats, domains=doms)


def _broadcast2(l, r):
    if isinstance(l, Frame) and isinstance(r, Frame):
        if l.ncols == 1 and r.ncols > 1:
            a = _col_np(l, l.names[0])
            return {n: (a, _col_np(r, n)) for n in r.names}
        if r.ncols == 1 and l.ncols > 1:
            b = _col_np(r, r.names[0])
            return {n: (_col_np(l, n), b) for n in l.names}
        assert l.ncols == r.ncols, "ncols mismatch"
        return {n: (_col_np(l, n), _col_np(r, m))
                for n, m in zip(l.names, r.names)}
    if isinstance(l, Frame):
        return {n: (_col_np(l, n), r) for n in l.names}
    if isinstance(r, Frame):
        return {n: (l, _col_np(r, n)) for n in r.names}
    return {"C1": (l, r)}


# ------------------------------------------------- device elementwise
#
# Elementwise prims on frames at or above this row count run on the
# device mesh instead of fetching to the controller (the reference runs
# every prim as an MRTask — water/rapids/ast/prims/mungers/AstGroup.java
# pattern; at 116M rows a controller fetch per op is the difference
# between an in-HBM pipeline and shipping the frame over the wire).
# Below the threshold the exact host-float64 path runs: reference
# pyunits assert f64-exact results that f32 device math can miss.
_DEV_MIN_ROWS = int(_os.environ.get("H2O3TPU_RAPIDS_DEVICE_ROWS", "1000000"))

DEV_OPS = 0      # observability: prims served by the device path (tests
#                  assert scale ops don't silently fall back to host)


def _dev_hit():
    global DEV_OPS
    DEV_OPS += 1
    from h2o3_tpu import telemetry
    telemetry.counter("rapids_device_ops_total").inc()

# dtypes safe in the f32 device path: values exact in a 24-bit mantissa.
# int32/time columns can exceed 2^24 (epoch millis certainly do) and
# stay on the host f64 path; cat codes are always < 2^24.
_DEV_SAFE_DTYPES = ("int8", "int16", "float32", "bfloat16", "uint8")


def _dev_col_ok(c: Column) -> bool:
    if c.type == T_CAT:
        return True
    if c.type != T_NUM or c.data is None:
        return False
    return str(c.data.dtype) in _DEV_SAFE_DTYPES


def _dev_eligible(*vals) -> bool:
    """True when every Frame operand is large, same-shape, and device-safe."""
    frames = [v for v in vals if isinstance(v, Frame)]
    if not frames or any(f.nrows < _DEV_MIN_ROWS for f in frames):
        return False
    if len({f.nrows for f in frames}) > 1:
        return False
    shapes = set()
    for f in frames:
        for n in f.names:
            c = f.col(n)
            if not _dev_col_ok(c):
                return False
            shapes.add(int(c.data.shape[0]))
    return len(shapes) == 1


import functools as _functools


def _kernel_view(d, m):
    """NaN-injected f32 view — same NA encoding as the host f64 path, so
    every ufunc reproduces host semantics (NaN propagation in arithmetic,
    False comparisons on NA) on device. Trace-time helper: only ever
    called inside the jitted kernels below."""
    import jax.numpy as jnp
    return jnp.where(m, jnp.nan, d.astype(jnp.float32))


def _kernel_seal(out, nrows):
    """(data, mask) result pair: NA where NaN, plus the padding tail —
    comparisons map NaN-injected padding back to 0.0 (NaN < x is False),
    which would otherwise read as valid rows."""
    import jax.numpy as jnp
    out = jnp.asarray(out, jnp.float32)
    pad = jnp.arange(out.shape[0], dtype=jnp.int32) >= nrows
    return out, jnp.isnan(out) | pad


@_functools.lru_cache(maxsize=None)
def _binop_kernel(name: str, kind: str):
    """ONE jitted program per (op, operand-kind): the whole
    view→op→seal chain fuses, so each prim costs one compile per shape
    instead of ~5 eager sub-op compiles (the 10M-row scale test was
    compile-bound, not compute-bound)."""
    import jax
    op = _jnp_binops()[0][name]
    if kind == "ff":
        def k(da, ma, db, mb, nrows):
            return _kernel_seal(op(_kernel_view(da, ma),
                                   _kernel_view(db, mb)), nrows)
    elif kind == "fs":
        def k(da, ma, s, nrows):
            return _kernel_seal(op(_kernel_view(da, ma), s), nrows)
    else:
        def k(s, db, mb, nrows):
            return _kernel_seal(op(s, _kernel_view(db, mb)), nrows)
    return jax.jit(k)


@_functools.lru_cache(maxsize=None)
def _unop_kernel(name: str):
    import jax
    op = _jnp_binops()[1][name]

    def k(d, m, nrows):
        return _kernel_seal(op(_kernel_view(d, m)), nrows)
    return jax.jit(k)


@_functools.lru_cache(maxsize=None)
def _isna_kernel():
    import jax
    import jax.numpy as jnp

    def k(m, nrows):
        pad = jnp.arange(m.shape[0], dtype=jnp.int32) >= nrows
        return m.astype(jnp.float32), pad
    return jax.jit(k)


@_functools.lru_cache(maxsize=None)
def _ifelse_kernel(ykind: str, nkind: str):
    """kinds: 'f' frame (data+mask args) or 's' numeric scalar."""
    import jax
    import jax.numpy as jnp

    def k(td, tm, *rest):
        i = 0
        tv = _kernel_view(td, tm)
        if ykind == "f":
            yv = _kernel_view(rest[0], rest[1]); i = 2
        else:
            yv = rest[0]; i = 1
        if nkind == "f":
            nv = _kernel_view(rest[i], rest[i + 1]); i += 2
        else:
            nv = rest[i]; i += 1
        nrows = rest[i]
        o = jnp.where(jnp.nan_to_num(tv) != 0, yv, nv)
        o = jnp.where(jnp.isnan(tv), jnp.nan, o)
        return _kernel_seal(o, nrows)
    return jax.jit(k)


@_functools.lru_cache(maxsize=None)
def _reduce_kernel(name: str):
    import jax
    import jax.numpy as jnp

    def k(d, m, nrows):
        logical = jnp.arange(d.shape[0], dtype=jnp.int32) < nrows
        valid = logical & ~m
        x = d.astype(jnp.float32)
        # counts stay int32: an f32 ones-sum saturates at 2^24 rows,
        # understating the mean denominator on 100M-row frames
        n_na = jnp.sum((m & logical).astype(jnp.int32))
        cnt = jnp.sum(valid.astype(jnp.int32))
        if name in ("sum", "mean"):
            part = jnp.sum(jnp.where(valid, x, 0.0))
        elif name == "min":
            part = jnp.min(jnp.where(valid, x, jnp.inf))
        else:
            part = jnp.max(jnp.where(valid, x, -jnp.inf))
        return part, cnt, n_na
    return jax.jit(k)


def _dev_frame(nrows: int, outs: Dict[str, Any]) -> Frame:
    """Frame from (data, mask) device result pairs."""
    _dev_hit()
    cols = [Column(name=n, type=T_NUM, data=d, na_mask=m, nrows=nrows)
            for n, (d, m) in outs.items()]
    return Frame(cols, nrows)


def _jnp_binops():
    """name → jnp callable. Built lazily (jax import cost) and cached.
    numpy ufuncs applied to jax arrays materialize to HOST numpy (no
    __array_ufunc__ dispatch), so the device path needs its own table."""
    global _JNP_BINOPS, _JNP_UNOPS
    if _JNP_BINOPS is not None:
        return _JNP_BINOPS, _JNP_UNOPS
    import jax.numpy as jnp
    from jax import lax

    def _f32(x):
        return x.astype(jnp.float32)

    _JNP_BINOPS = {
        "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
        "/": jnp.divide, "^": jnp.power, "%": jnp.mod, "%%": jnp.mod,
        "intDiv": jnp.floor_divide, "%/%": jnp.floor_divide,
        "==": lambda a, b: _f32(jnp.equal(a, b)),
        "!=": lambda a, b: _f32(jnp.not_equal(a, b)),
        "<": lambda a, b: _f32(jnp.less(a, b)),
        "<=": lambda a, b: _f32(jnp.less_equal(a, b)),
        ">": lambda a, b: _f32(jnp.greater(a, b)),
        ">=": lambda a, b: _f32(jnp.greater_equal(a, b)),
        "&": lambda a, b: _f32((a != 0) & (b != 0)),
        "|": lambda a, b: _f32((a != 0) | (b != 0)),
    }
    _JNP_UNOPS = {
        "abs": jnp.abs, "ceiling": jnp.ceil, "floor": jnp.floor,
        "trunc": jnp.trunc, "exp": jnp.exp, "log": jnp.log,
        "log10": jnp.log10, "log1p": jnp.log1p, "log2": jnp.log2,
        "sqrt": jnp.sqrt, "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
        "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
        "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
        "sign": jnp.sign,
        "not": lambda a: _f32(a == 0), "!": lambda a: _f32(a == 0),
        "cumsum": jnp.cumsum, "cumprod": jnp.cumprod,
        "cummax": lax.cummax, "cummin": lax.cummin,
    }
    return _JNP_BINOPS, _JNP_UNOPS


_JNP_BINOPS = None
_JNP_UNOPS = None


def _dev_binop(name, l, r):
    """Device path for frame⊗frame / frame⊗scalar elementwise binops.
    Returns None when ineligible (caller falls back to host f64)."""
    if name not in _jnp_binops()[0] or not _dev_eligible(l, r):
        return None
    outs = {}
    if isinstance(l, Frame) and isinstance(r, Frame):
        k = _binop_kernel(name, "ff")
        if l.ncols == 1 and r.ncols > 1:
            cl = l.col(l.names[0])
            for n in r.names:
                cr = r.col(n)
                outs[n] = k(cl.data, cl.na_mask, cr.data, cr.na_mask,
                            l.nrows)
        elif r.ncols == 1 and l.ncols > 1:
            cr = r.col(r.names[0])
            for n in l.names:
                cl = l.col(n)
                outs[n] = k(cl.data, cl.na_mask, cr.data, cr.na_mask,
                            l.nrows)
        elif l.ncols == r.ncols:
            for n, m in zip(l.names, r.names):
                cl, cr = l.col(n), r.col(m)
                outs[n] = k(cl.data, cl.na_mask, cr.data, cr.na_mask,
                            l.nrows)
        else:
            return None
    elif isinstance(l, Frame):
        k = _binop_kernel(name, "fs")
        for n in l.names:
            cl = l.col(n)
            outs[n] = k(cl.data, cl.na_mask, float(r), l.nrows)
    else:
        k = _binop_kernel(name, "sf")
        for n in r.names:
            cr = r.col(n)
            outs[n] = k(float(l), cr.data, cr.na_mask, r.nrows)
    base = l if isinstance(l, Frame) else r
    return _dev_frame(base.nrows, outs)


def _dev_unop(name, v: Frame):
    if name not in _jnp_binops()[1] or not isinstance(v, Frame) \
            or not _dev_eligible(v):
        return None
    k = _unop_kernel(name)
    outs = {}
    for n in v.names:
        c = v.col(n)
        outs[n] = k(c.data, c.na_mask, v.nrows)
    return _dev_frame(v.nrows, outs)


# ---------------------------------------------------------------- prims

PRIMS: Dict[str, Callable] = {}


def prim(*names):
    def deco(fn):
        for n in names:
            PRIMS[n] = fn
        return fn
    return deco


def _cmp_str(fr: Frame, s: str, negate: bool) -> Frame:
    """Categorical/string column vs string literal — the wire form of
    ``fr['g'] == 'x'``; matches against the domain, NA rows → NA."""
    out = {}
    for n in fr.names:
        c = fr.col(n)
        if c.is_categorical:
            try:
                code = (c.domain or []).index(s)
            except ValueError:
                code = -2
            codes = _cat_codes(fr, n).astype(np.float64)
            eq = (codes == code).astype(np.float64)
            eq[codes < 0] = np.nan
        elif c.type == "string":
            eq = np.array([np.nan if v is None else float(v == s)
                           for v in c.to_numpy()])
        else:
            eq = np.zeros(fr.nrows)   # numeric vs string: never equal
        out[n] = (1.0 - eq) if negate else eq
    return _rebuild(fr, out, keep_domains=False)


def _binop(op, name: str = ""):
    def fn(env, l, r):
        l, r = env.ev(l), env.ev(r)
        if name in ("==", "!=") and (isinstance(l, str) or isinstance(r, str)):
            fr = l if isinstance(l, Frame) else r
            s = r if isinstance(r, str) else l
            if isinstance(fr, Frame) and isinstance(s, str):
                return _cmp_str(fr, s, negate=(name == "!="))
            return float((l == r) if name == "==" else (l != r))
        if not isinstance(l, Frame) and not isinstance(r, Frame):
            return float(op(l, r))
        dv = _dev_binop(name, l, r)
        if dv is not None:
            return dv
        pairs = _broadcast2(l, r)
        out = {}
        for n, (a, b) in pairs.items():
            # equality against literals is exact in f64: columns carry
            # a seeded float64 host view (frame/column.py host cache),
            # so `5.1 in fr` compares the original parsed values
            with np.errstate(all="ignore"):
                out[n] = np.asarray(
                    op(np.asarray(a, np.float64), np.asarray(b, np.float64)),
                    np.float64)
        return _rebuild(l if isinstance(l, Frame) else r, out,
                        keep_domains=False)
    return fn


for _name, _op in [("+", np.add), ("-", np.subtract), ("*", np.multiply),
                   ("/", np.divide), ("^", np.power), ("%", np.mod),
                   ("%%", np.mod),
                   ("==", lambda a, b: np.equal(a, b).astype(float)),
                   ("!=", lambda a, b: np.not_equal(a, b).astype(float)),
                   ("<", lambda a, b: np.less(a, b).astype(float)),
                   ("<=", lambda a, b: np.less_equal(a, b).astype(float)),
                   (">", lambda a, b: np.greater(a, b).astype(float)),
                   (">=", lambda a, b: np.greater_equal(a, b).astype(float)),
                   ("&", lambda a, b: ((a != 0) & (b != 0)).astype(float)),
                   ("|", lambda a, b: ((a != 0) | (b != 0)).astype(float)),
                   ("intDiv", np.floor_divide), ("%/%", np.floor_divide)]:
    PRIMS[_name] = _binop(_op, _name)


def _unop(op, name: str = ""):
    def fn(env, x):
        v = env.ev(x)
        if not isinstance(v, Frame):
            return float(op(v))
        dv = _dev_unop(name, v)
        if dv is not None:
            return dv
        with np.errstate(all="ignore"):
            out = {n: np.asarray(op(_col_np(v, n).astype(np.float64)))
                   for n in v.names}
        return _rebuild(v, out, keep_domains=False)
    return fn


for _name, _op in [("abs", np.abs), ("ceiling", np.ceil), ("floor", np.floor),
                   ("trunc", np.trunc), ("exp", np.exp), ("log", np.log),
                   ("log10", np.log10), ("log1p", np.log1p), ("log2", np.log2),
                   ("sqrt", np.sqrt), ("sin", np.sin), ("cos", np.cos),
                   ("tan", np.tan), ("asin", np.arcsin), ("acos", np.arccos),
                   ("atan", np.arctan), ("sinh", np.sinh), ("cosh", np.cosh),
                   ("tanh", np.tanh), ("sign", np.sign),
                   ("not", lambda a: np.asarray(a == 0, float)),
                   ("!", lambda a: np.asarray(a == 0, float)),
                   ("lgamma", np.vectorize(math.lgamma)),
                   ("gamma", np.vectorize(math.gamma)),
                   ]:
    PRIMS[_name] = _unop(_op, _name)


@prim("is.na")
def _is_na(env, x):
    """AstIsNa — per-cell 0/1; string columns test None (the numeric
    _unop path would try float('oneteen'))."""
    v = env.ev(x)
    if not isinstance(v, Frame):
        if isinstance(v, str):
            return 0.0            # a string scalar is a value, not NA
        try:
            return float(np.isnan(float(v)))
        except (TypeError, ValueError):
            return 1.0 if v is None else 0.0
    if _dev_eligible(v):
        # the NA answer is the mask itself — no values ever leave HBM
        _dev_hit()
        k = _isna_kernel()
        cols = []
        for n in v.names:
            c = v.col(n)
            d, m = k(c.na_mask, v.nrows)
            cols.append(Column(name=f"isNA({n})", type=T_NUM,
                               data=d, na_mask=m, nrows=v.nrows))
        return Frame(cols, v.nrows)
    out = {}
    for n in v.names:
        c = v.col(n)
        if c.type in ("string", "uuid"):
            flags = np.asarray([1.0 if s is None else 0.0
                                for s in c.to_numpy()])
        elif c.is_categorical:
            flags = (_cat_codes(v, n) < 0).astype(np.float64)
        else:
            flags = np.isnan(_col_np(v, n)).astype(np.float64)
        out[f"isNA({n})"] = flags         # AstIsNa output naming
    return Frame.from_numpy(out)


@prim("round")
def _round(env, x, digits=("num", 0)):
    v, d = env.ev(x), int(env.ev(digits))
    if not isinstance(v, Frame):
        return float(np.round(v, d))
    return _rebuild(v, {n: np.round(_col_np(v, n), d) for n in v.names},
                    keep_domains=False)


@prim("signif")
def _signif(env, x, digits=("num", 6)):
    v, d = env.ev(x), int(env.ev(digits))

    def sig(a):
        a = np.asarray(a, np.float64)
        with np.errstate(all="ignore"):
            mag = 10.0 ** (d - 1 - np.floor(np.log10(np.abs(a))))
            out = np.round(a * mag) / mag
        return np.where(a == 0, 0.0, out)

    if not isinstance(v, Frame):
        return float(sig(v))
    return _rebuild(v, {n: sig(_col_np(v, n)) for n in v.names}, False)


# ---- reducers (ast/prims/reducers) ----------------------------------


def _dev_reduce(name: str, v: Frame, na_rm: bool):
    """Device-resident sum/min/max/mean over all columns: per-column
    scalar partials leave the device, never the rows (AstSumAxis-at-scale
    role). None → host fallback. f32 accumulation (XLA tree-reduces, so
    error ~log n · eps) — only taken above _DEV_MIN_ROWS where the exact
    client oracles of the small pyunits never go."""
    if name not in ("sum", "min", "max", "mean") or not _dev_eligible(v):
        return None
    _dev_hit()
    # per-column 0-d partials accumulate ON DEVICE (one jitted kernel
    # per reduce); ONE batched scalar fetch ends the reduce (three
    # float() syncs per column would each pay a host round trip — the
    # cost this path exists to avoid)
    k = _reduce_kernel(name)
    parts, counts, n_nas = [], [], []
    for n in v.names:
        c = v.col(n)
        part, cnt, n_na = k(c.data, c.na_mask, v.nrows)
        parts.append(part)
        counts.append(cnt)
        n_nas.append(n_na)
    parts, counts, n_nas = _fetch_np((parts, counts, n_nas))
    if not na_rm and np.sum(n_nas) > 0:
        return float("nan")
    if name == "sum":
        return float(np.sum(parts))
    if name == "mean":
        tot = float(np.sum(counts))
        # all values NA with na.rm: the host path (np.nanmean) yields
        # NaN — a clamped denominator would silently return 0.0 here
        return float(np.sum(parts) / tot) if tot > 0 else float("nan")
    return float(np.min(parts) if name == "min" else np.max(parts))


def _reducer(np_fn, na_fn, name: str = ""):
    def fn(env, *args):
        vals = [env.ev(a) for a in args]
        na_rm = False
        if len(vals) > 1 and isinstance(vals[-1], (bool, float, int)):
            na_rm = bool(vals[-1])
            vals = vals[:-1]
        if len(vals) == 1 and isinstance(vals[0], Frame):
            dv = _dev_reduce(name, vals[0], na_rm)
            if dv is not None:
                return dv
        acc = []
        for v in vals:
            if isinstance(v, Frame):
                # f64 accumulation: the client recomputes oracles in
                # float64 over the same (f32-parsed) values, so an f32
                # running product/sum would diverge at ~1e-7 relative
                acc += [_col_np(v, n).astype(np.float64)
                        for n in v.names]
            else:
                acc.append(np.array([float(v)]))
        flat = np.concatenate(acc)
        return float(na_fn(flat) if na_rm else np_fn(flat))
    return fn


for _name, _f, _fna in [
        ("sum", np.sum, np.nansum), ("min", np.min, np.nanmin),
        ("max", np.max, np.nanmax), ("mean", np.mean, np.nanmean),
        ("median", np.median, np.nanmedian),
        ("sd", lambda a: np.std(a, ddof=1), lambda a: np.nanstd(a, ddof=1)),
        ("var", lambda a: np.var(a, ddof=1), lambda a: np.nanvar(a, ddof=1)),
        ("prod", np.prod, np.nanprod),
        ("any", lambda a: float(np.any(a != 0)),
         lambda a: float(np.any(a[~np.isnan(a)] != 0))),
        ("all", lambda a: float(np.all(a != 0)),
         lambda a: float(np.all(a[~np.isnan(a)] != 0)))]:
    PRIMS[_name] = _reducer(_f, _fna, _name)


# NA-skipping scalar rollups (AstNaRollupOp subclasses: sumNA/minNA/
# maxNA/prodNA — h2o-py sends these for skipna=True, its default)
for _name, _fna in [("sumNA", np.nansum), ("minNA", np.nanmin),
                    ("maxNA", np.nanmax), ("prodNA", np.nanprod)]:
    PRIMS[_name] = _reducer(_fna, _fna)


@prim("flatten")
def _flatten_prim(env, x):
    """1x1 frame → scalar Val (AstFlatten.java:16); anything else
    passes through unchanged — the client's _eager_scalar path."""
    v = env.ev(x)
    if not isinstance(v, Frame) or v.ncols != 1 or v.nrows != 1:
        return v
    c = v.col(v.names[0])
    if c.is_categorical:
        k = int(_cat_codes(v, v.names[0])[0])
        return "NA" if k < 0 else str((c.domain or [])[k])
    val = c.to_numpy()[0]
    if c.type in ("string", "uuid"):
        return "NA" if val is None else str(val)
    return float(val)


def _cumop(op, axis1_op, name: str = ""):
    def fn(env, x, axis=0):
        v = env.ev(x)
        ax = int(env.ev(axis)) if not isinstance(axis, (int, float)) \
            else int(axis)
        if ax == 0:
            # padding rows sit AFTER the logical rows, so a prefix scan
            # over the padded array is exact on the logical prefix
            dv = _dev_unop(name, v)
            if dv is not None:
                return dv
            return _rebuild(v, {n: op(_col_np(v, n)) for n in v.names},
                            False)
        # axis=1: accumulate across columns within each row (AstCumu)
        m = np.stack([_col_np(v, n) for n in v.names], axis=1)
        acc = axis1_op(m)
        return _rebuild(v, {n: acc[:, j]
                            for j, n in enumerate(v.names)}, False)
    return fn


for _name, _op, _op1 in [
        ("cumsum", np.cumsum, lambda m: np.cumsum(m, axis=1)),
        ("cumprod", np.cumprod, lambda m: np.cumprod(m, axis=1)),
        ("cummax", np.maximum.accumulate,
         lambda m: np.maximum.accumulate(m, axis=1)),
        ("cummin", np.minimum.accumulate,
         lambda m: np.minimum.accumulate(m, axis=1))]:
    PRIMS[_name] = _cumop(_op, _op1, _name)


# ---- structural (ast/prims/mungers) ---------------------------------


def _num_list_indices(sel, n: Optional[int] = None) -> Optional[List[int]]:
    """Flatten a numeric selector (num / range / list of those) to ints;
    None when the selector isn't purely numeric. ``n`` resolves
    open-ended ('lo:nan') ranges."""
    if isinstance(sel, tuple) and sel[0] == "num":
        return [int(sel[1])]
    if isinstance(sel, tuple) and sel[0] == "range":
        lo = int(sel[1])
        step = int(sel[3]) if len(sel) > 3 else 1
        if math.isnan(sel[2]):
            if n is None:
                raise ValueError("open range needs a bound")
            return list(range(lo, n, step))
        return list(range(lo, lo + int(sel[2]) * step, step))
    if isinstance(sel, tuple) and sel[0] == "list":
        out: List[int] = []
        for it in sel[1]:
            sub = _num_list_indices(it, n)
            if sub is None:
                return None
            out.extend(sub)
        return out
    if isinstance(sel, (int, float)):
        return [int(sel)]
    return None


def _is_empty_list(sel) -> bool:
    return isinstance(sel, tuple) and sel[0] == "list" and not sel[1]


def _resolve_cols(frame: Frame, sel) -> List[str]:
    nums = _num_list_indices(sel, frame.ncols)
    if nums is not None:
        # all-negative numeric selector = COMPLEMENT: h2o-py's pop/del
        # send -(i+1) meaning "every column except i"
        # (h2o-py/h2o/frame.py pop/drop wire format)
        if nums and all(v < 0 for v in nums):
            drop = {-(v) - 1 for v in nums}
            return [n for i, n in enumerate(frame.names) if i not in drop]
        return [frame.names[v] for v in nums]
    if isinstance(sel, tuple) and sel[0] == "list":
        out = []
        for it in sel[1]:
            out.extend(_resolve_cols(frame, it))
        return out
    if isinstance(sel, tuple) and sel[0] in ("str", "id"):
        return [sel[1]]
    if isinstance(sel, str):
        return [sel]
    raise ValueError(f"bad column selector {sel!r}")


@prim("cols", "cols_py")
def _cols(env, fr, sel):
    f = _as_frame(env.ev(fr))
    return f[_resolve_cols(f, sel)]


def _row_indices(f: Frame, sel, env) -> np.ndarray:
    nums = _num_list_indices(sel, f.nrows)
    if nums is not None:
        idx = np.asarray(nums, np.int64)
        if len(idx) and (idx < 0).all():
            # negative row list = complement (AstNumList semantics)
            drop = set((-idx - 1).tolist())
            return np.asarray([i for i in range(f.nrows) if i not in drop],
                              np.int64)
        return idx
    mask_fr = _as_frame(env.ev(sel))
    m = _col_np(mask_fr, mask_fr.names[0])
    return np.flatnonzero(np.nan_to_num(m) != 0)


@prim("rows")
def _rows(env, fr, sel):
    f = _as_frame(env.ev(fr))
    return _take_rows(f, _row_indices(f, sel, env))


@prim("append", "cbind")
def _append(env, *args):
    # (append fr value "name"): h2o-py's new-column assignment
    # fr["new"] = value (h2o-py/h2o/frame.py:2251) — value may be a
    # scalar (broadcast) or a 1-col frame; the string names the column
    if len(args) == 3 and isinstance(args[2], tuple) and args[2][0] == "str":
        base = _as_frame(env.ev(args[0]))
        val = env.ev(args[1])
        name = args[2][1]
        out_arrays, cats, doms = {}, [], {}
        for n in base.names:
            c = base.col(n)
            if c.is_categorical:
                out_arrays[n] = _cat_codes(base, n)
                cats.append(n)
                doms[n] = c.domain
            else:
                out_arrays[n] = _col_np(base, n)
        if isinstance(val, Frame):
            vc = val.col(val.names[0])
            if vc.is_categorical:
                out_arrays[name] = _cat_codes(val, val.names[0])
                cats.append(name)
                doms[name] = vc.domain
            else:
                out_arrays[name] = _col_np(val, val.names[0])
        elif isinstance(val, str):
            out_arrays[name] = np.zeros(base.nrows, np.int32)
            cats.append(name)
            doms[name] = [val]
        else:
            out_arrays[name] = np.full(base.nrows, float(val), np.float64)
        return Frame.from_numpy(out_arrays, categorical=cats, domains=doms)
    frames = [_as_frame(env.ev(a)) for a in args
              if not (isinstance(a, tuple) and a[0] == "str")]
    out_arrays, cats, doms = {}, [], {}
    seen = set()
    for f in frames:
        for n in f.names:
            # duplicate names take integer suffixes FROM ZERO:
            # Frame.uniquify (water/fvec/Frame.java:227) appends cnt++
            # per collision — colgroup → colgroup0, colgroup2 →
            # colgroup20 → colgroup21 when colgroup20 is taken
            nm, k = n, 0
            while nm in seen:
                nm = f"{n}{k}"
                k += 1
            seen.add(nm)
            c = f.col(n)
            if c.is_categorical:
                out_arrays[nm] = _cat_codes(f, n)
                cats.append(nm)
                doms[nm] = c.domain
            else:
                out_arrays[nm] = _col_np(f, n)
    return Frame.from_numpy(out_arrays, categorical=cats, domains=doms)


@prim("rbind")
def _rbind(env, *args):
    frames = [_as_frame(env.ev(a)) for a in args]
    base = frames[0]
    arrays, cats, doms = {}, [], {}
    for n in base.names:
        if base.col(n).is_categorical:
            dom: List[str] = []
            for f in frames:
                for lvl in (f.col(n).domain or []):
                    if lvl not in dom:
                        dom.append(lvl)
            parts = []
            for f in frames:
                lut = {lvl: i for i, lvl in enumerate(dom)}
                mapping = np.array(
                    [lut[lvl] for lvl in (f.col(n).domain or [])], np.int32)
                codes = _cat_codes(f, n)
                ok = codes >= 0
                if len(mapping):
                    codes[ok] = mapping[codes[ok]]
                parts.append(codes)
            arrays[n] = np.concatenate(parts)
            cats.append(n)
            doms[n] = dom
        else:
            arrays[n] = np.concatenate([_col_np(f, n) for f in frames])
    return Frame.from_numpy(arrays, categorical=cats, domains=doms)


@prim("nrow")
def _nrow(env, fr):
    return float(_as_frame(env.ev(fr)).nrows)


@prim("ncol")
def _ncol(env, fr):
    return float(_as_frame(env.ev(fr)).ncols)


@prim("colnames=")
def _colnames(env, fr, idxs, names):
    f = _as_frame(env.ev(fr))
    cols = _resolve_cols(f, idxs)
    new = ([n[1] for n in names[1]]
           if isinstance(names, tuple) and names[0] == "list" else [names[1]])
    ren = dict(zip(cols, new))
    out, cats, doms = {}, [], {}
    for n in f.names:
        nm = ren.get(n, n)
        c = f.col(n)
        if c.is_categorical:
            out[nm] = _cat_codes(f, n)
            cats.append(nm)
            doms[nm] = c.domain
        else:
            out[nm] = _col_np(f, n)
    return Frame.from_numpy(out, categorical=cats, domains=doms)


@prim("tmp=", "assign")
def _assign(env, name, expr, *rest):
    nm = name[1] if isinstance(name, tuple) else str(name)
    val = env.ev(expr)
    env.session.assign(nm, val)
    return val


@prim(":=")
def _rect_assign(env, dst, src, col_sel, row_sel):
    """Rectangle assign (water/rapids/ast/prims/assign/AstRectangleAssign
    role): h2o-py `fr[rows, col] = value` ships
    ``(:= <frame> <value> <col> <rows>)`` with '[]' = all rows/cols
    (h2o-py/h2o/frame.py:2242, expr.py _arg_to_expr None → '[]')."""
    f = _as_frame(env.ev(dst))
    cols = (f.names if _is_empty_list(col_sel)
            else _resolve_cols(f, col_sel))
    rows = (np.arange(f.nrows)
            if _is_empty_list(row_sel) or row_sel is None
            else _row_indices(f, row_sel, env))
    val = env.ev(src)

    arrays, cats, doms, strs = {}, [], {}, []
    for i, n in enumerate(f.names):
        c = f.col(n)
        if c.is_categorical:
            arr = _cat_codes(f, n).astype(np.float64)
            arr[arr < 0] = np.nan
            dom = list(c.domain or [])
        elif c.type == "string":
            arr = c.to_numpy().copy()
            dom = None
        else:
            arr = _col_np(f, n).copy()
            dom = None
        if n in cols:
            if isinstance(val, Frame):
                j = cols.index(n) if val.ncols > 1 else 0
                vc = val.col(val.names[j])
                if vc.is_categorical:
                    # NA codes are -1; as float they must become NaN
                    # BEFORE the domain remap or mp[-1] silently maps
                    # every NA row to the LAST level
                    v = _cat_codes(val, val.names[j]).astype(np.float64)
                    v[v < 0] = np.nan
                else:
                    v = vc.to_numpy()
                full = len(rows) == f.nrows
                if vc.type in ("string", "uuid"):
                    # string-typed source (AstRectangleAssign string
                    # path): a full-column replace converts the dest to
                    # T_STR; a partial assign into an enum interns the
                    # labels into the destination domain
                    v = np.asarray(v, dtype=object)
                    if full:
                        dom = None
                        arr = np.empty(f.nrows, dtype=object)
                    elif dom is not None:
                        lut = {lvl: k for k, lvl in enumerate(dom)}
                        vv = np.full(len(v), np.nan)
                        for k2, s in enumerate(v):
                            # non-strings (None, float NaN cells a
                            # numeric assign left in a T_STR column)
                            # stay NA, never become levels
                            if not isinstance(s, str):
                                continue
                            if s not in lut:
                                lut[s] = len(dom)
                                dom.append(s)
                            vv[k2] = lut[s]
                        v = vv
                    elif c.type != "string":
                        raise ValueError(
                            f"cannot assign string rows into numeric "
                            f"column '{n}'")
                elif full and vc.is_categorical and dom is None:
                    # whole-column replace with a factor: the column
                    # BECOMES categorical (fr["y"] = fr["y"].asfactor())
                    dom = list(vc.domain or [])
                    arr = np.full(f.nrows, np.nan)
                elif full and not vc.is_categorical and dom is not None \
                        and c.type != "string":
                    # whole-column replace with numeric: drops the factor
                    dom = None
                    arr = np.full(f.nrows, np.nan)
                if vc.is_categorical and dom is not None:
                    # remap source codes into the destination domain
                    lut = {lvl: k for k, lvl in enumerate(dom)}
                    src_dom = vc.domain or []
                    for lvl in src_dom:
                        if lvl not in lut:
                            lut[lvl] = len(dom)
                            dom.append(lvl)
                    mp = np.array([lut[lvl] for lvl in src_dom], np.float64)
                    ok = ~np.isnan(v)
                    v = v.copy()
                    v[ok] = mp[v[ok].astype(np.int64)]
                v = v[: f.nrows] if len(v) >= f.nrows else v
                arr[rows] = v[rows] if len(v) == f.nrows else v[: len(rows)]
            elif isinstance(val, str):
                if dom is not None:
                    if val not in dom:
                        dom.append(val)
                    arr[rows] = float(dom.index(val))
                elif c.type == "string":
                    arr[rows] = val
                else:
                    raise ValueError(
                        f"cannot assign string into numeric column '{n}'")
            else:
                arr[rows] = float(val)
        if dom is not None:
            na = np.isnan(arr)
            arr = np.where(na, -1, arr).astype(np.int32)
            arrays[n] = arr
            cats.append(n)
            doms[n] = dom
        else:
            arrays[n] = arr
            if arr.dtype == object:
                # string columns must stay T_STR — from_numpy would
                # otherwise re-intern the object array into an enum
                strs.append(n)
    out = Frame.from_numpy(arrays, categorical=cats, domains=doms,
                           strings=strs)
    # preserve column order
    return out[f.names]


@prim("rm")
def _rm(env, name):
    nm = name[1] if isinstance(name, tuple) else str(name)
    env.session.rm(nm)
    return 0.0


@prim("ifelse")
def _ifelse(env, test, yes, no):
    t, y, n = env.ev(test), env.ev(yes), env.ev(no)
    if isinstance(t, Frame) and _dev_eligible(t, y, n) \
            and not isinstance(y, str) and not isinstance(n, str):
        # string yes/no branches intern as categoricals — host path only
        tc = t.col(t.names[0])
        args = [tc.data, tc.na_mask]
        ykind = "f" if isinstance(y, Frame) else "s"
        nkind = "f" if isinstance(n, Frame) else "s"
        if ykind == "f":
            yc = y.col(y.names[0])
            args += [yc.data, yc.na_mask]
        else:
            args.append(float(y))
        if nkind == "f":
            nc = n.col(n.names[0])
            args += [nc.data, nc.na_mask]
        else:
            args.append(float(n))
        args.append(t.nrows)
        out = _ifelse_kernel(ykind, nkind)(*args)
        return _dev_frame(t.nrows, {"C1": out})
    tv = _col_np(t, t.names[0]) if isinstance(t, Frame) else t
    if not isinstance(tv, np.ndarray):
        return y if tv else n
    yv = _col_np(y, y.names[0]) if isinstance(y, Frame) else y
    nv = _col_np(n, n.names[0]) if isinstance(n, Frame) else n
    out = np.where(np.nan_to_num(tv) != 0, yv, nv)
    out = np.where(np.isnan(tv), np.nan, out)
    base = t if isinstance(t, Frame) else (y if isinstance(y, Frame) else n)
    return _rebuild(base, {"C1": out}, False)


@prim("as.factor", "as_factor")
def _as_factor(env, x):
    f = _as_frame(env.ev(x))
    out, cats, doms = {}, [], {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            out[n] = _cat_codes(f, n)
            cats.append(n)
            doms[n] = c.domain
        else:
            v = _col_np(f, n)
            uniq = np.unique(v[~np.isnan(v)])
            dom = [str(int(u)) if u == int(u) else str(u) for u in uniq]
            lut = {u: i for i, u in enumerate(uniq)}
            codes = np.array([lut[x_] if not np.isnan(x_) and x_ in lut else -1
                              for x_ in v], np.int32)
            out[n] = codes
            cats.append(n)
            doms[n] = dom
    return Frame.from_numpy(out, categorical=cats, domains=doms)


@prim("as.numeric", "as_numeric")
def _as_numeric(env, x):
    f = _as_frame(env.ev(x))
    out = {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            dom = c.domain or []
            try:
                dv = np.array([float(s) for s in dom])
            except ValueError:
                dv = np.arange(len(dom), dtype=np.float64)
            codes = _fetch_np(c.data)[: f.nrows].astype(np.int64)
            v = dv[codes] if len(dom) else codes.astype(np.float64)
            v = v.copy()
            v[_fetch_np(c.na_mask)[: f.nrows]] = np.nan
            out[n] = v
        else:
            out[n] = _col_np(f, n)
    return Frame.from_numpy(out)


@prim("as.character")
def _as_character(env, x):
    f = _as_frame(env.ev(x))
    out = {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            dom = np.array((c.domain or []) + [None], dtype=object)
            codes = _fetch_np(c.data)[: f.nrows].astype(np.int64)
            codes = np.where(_fetch_np(c.na_mask)[: f.nrows],
                             len(dom) - 1, codes)
            out[n] = dom[codes]
        else:
            out[n] = np.array([str(v) for v in _col_np(f, n)], dtype=object)
    # as.character yields STRING columns (AstAsCharacter → Vec.T_STR),
    # not a re-interned enum — isstring()/ischaracter() observe the type
    return Frame.from_numpy(out, strings=list(out))


@prim("unique")
def _unique(env, x, *rest):
    """AstUnique; optional include_nas flag appends one NA row when the
    column has missing values (h2o-py unique(include_nas=True))."""
    include_nas = any(bool(a[1] if isinstance(a, tuple) else env.ev(a))
                      for a in rest)
    f = _as_frame(env.ev(x))
    n = f.names[0]
    c = f.col(n)
    if c.is_categorical:
        codes = _cat_codes(f, n)
        u = np.unique(codes[codes >= 0]).astype(np.float64)
        if include_nas and (codes < 0).any():
            u = np.concatenate([u, [np.nan]])
        out = Frame.from_numpy({n: u}, categorical=[n],
                               domains={n: c.domain})
        return out
    v = _col_np(f, n)
    u = np.unique(v[~np.isnan(v)])
    if include_nas and np.isnan(v).any():
        u = np.concatenate([u, [np.nan]])
    out = Frame.from_numpy({n: u},
                           times=[n] if c.type == "time" else ())
    return out


def _table_values(fr, nm):
    c = fr.col(nm)
    if c.is_categorical:
        dom = np.asarray(list(c.domain or []), dtype=object)
        codes = _cat_codes(fr, nm)
        return np.asarray([dom[k] if k >= 0 else None for k in codes],
                          dtype=object)
    return _col_np(fr, nm)


@prim("table")
def _table(env, x, *rest):
    """AstTable: single-column counts, or a two-column cross tabulation
    — dense=True emits (v1, v2, Counts) rows, dense=False a wide
    cross-tab whose columns are the second variable's levels."""
    f = _as_frame(env.ev(x))
    f2, dense = None, True
    for a in rest:
        v = a[1] if isinstance(a, tuple) else env.ev(a)
        if isinstance(v, Frame):
            f2 = v
        elif isinstance(v, (bool, int, float)):
            dense = bool(v)
    if f2 is not None:
        pairs = ((f, f.names[0]), (f2, f2.names[0]))
    elif f.ncols == 2:
        pairs = ((f, f.names[0]), (f, f.names[1]))
    else:
        n = f.names[0]
        c = f.col(n)
        if c.is_categorical:
            codes = _cat_codes(f, n)
            cnt = np.bincount(codes[codes >= 0],
                              minlength=len(c.domain or []))
            return Frame.from_numpy(
                {n: np.arange(len(cnt), dtype=np.int32),
                 "Count": cnt.astype(np.float64)},
                categorical=[n], domains={n: c.domain})
        v = _col_np(f, n)
        u, cnt = np.unique(v[~np.isnan(v)], return_counts=True)
        return Frame.from_numpy({n: u, "Count": cnt.astype(np.float64)})

    (fr1, n1), (fr2, n2) = pairs
    a1, a2 = _table_values(fr1, n1), _table_values(fr2, n2)
    from collections import Counter
    cnt = Counter((v1, v2) for v1, v2 in zip(a1, a2)
                  if v1 is not None and v2 is not None
                  and not (isinstance(v1, float) and np.isnan(v1))
                  and not (isinstance(v2, float) and np.isnan(v2)))
    u1 = sorted({k[0] for k in cnt})
    u2 = sorted({k[1] for k in cnt})
    if n2 == n1:
        n2 = n2 + "2"
    if dense:
        rows = sorted(cnt.items())
        c1 = np.asarray([r[0][0] for r in rows], dtype=object)
        c2 = np.asarray([r[0][1] for r in rows], dtype=object)
        counts = np.asarray([r[1] for r in rows], np.float64)
        out = {}
        for nm, arr in ((n1, c1), (n2, c2)):
            if all(isinstance(v, (int, float, np.floating, np.integer))
                   for v in arr):
                out[nm] = arr.astype(np.float64)
            else:
                out[nm] = arr
        out["Counts"] = counts
        return Frame.from_numpy(out)
    # wide cross-tab: one row per u1 value, one column per u2 level
    out = {n1: (np.asarray(u1, np.float64)
                if all(isinstance(v, (int, float, np.floating,
                                      np.integer)) for v in u1)
                else np.asarray(u1, dtype=object))}
    for lvl in u2:
        out[str(lvl)] = np.asarray(
            [float(cnt.get((v1, lvl), 0)) for v1 in u1], np.float64)
    return Frame.from_numpy(out)


@prim("naCnt", "na_cnt")
def _na_cnt(env, fr):
    """Per-column NA counts (ast/prims/advmath AstNaCnt)."""
    f = _as_frame(env.ev(fr))
    out = []
    for n in f.names:
        c = f.col(n)
        if c.type == "string":
            out.append(int(sum(v is None for v in c.to_numpy())))
        else:
            out.append(int(_fetch_np(c.na_mask)[: f.nrows].sum()))
    return out


@prim("h2o.runif")
def _runif(env, fr, seed):
    f = _as_frame(env.ev(fr))
    s = int(env.ev(seed))
    rng = np.random.RandomState(s if s >= 0 else None)
    return Frame.from_numpy({"rnd": rng.rand(f.nrows)})


@prim("quantile")
def _quantile(env, fr, probs, method=("str", "interpolate"), *rest):
    from h2o3_tpu.frame.quantiles import column_quantiles
    f = _as_frame(env.ev(fr))
    plist = (probs[1] if isinstance(probs, tuple) and probs[0] == "list"
             else [probs])
    pr = [p[1] if isinstance(p, tuple) else float(p) for p in plist]
    meth = method[1] if isinstance(method, tuple) else str(method)
    out = {"Probs": np.asarray(pr, np.float64)}
    for n in f.names:
        c = f.col(n)
        if not c.is_categorical and c.type != "string":
            out[n + "Quantiles"] = column_quantiles(c, pr, combine_method=meth)
    return Frame.from_numpy(out)


@prim("sort")
def _sort(env, fr, cols_sel, *asc):
    f = _as_frame(env.ev(fr))
    names = _resolve_cols(f, cols_sel)
    # h2o-py encodes direction as +1 (asc) / -1 (desc), never 0
    # (h2o-py/h2o/frame.py sort(): ascendingI[index]=1 if ... else -1),
    # so bool() is wrong — bool(-1) is True. Sign is the contract.
    if asc and isinstance(asc[0], tuple) and asc[0][0] == "list":
        ascending = [float(a[1]) > 0 for a in asc[0][1]]
    else:
        ascending = [float(env.ev(a)) > 0 for a in asc]
    ascending = ascending or [True] * len(names)
    # device radix-order path (water/rapids/RadixOrder.java role): sort
    # permutation + column gathers stay on the mesh; the controller
    # never holds the data. Host lexsort remains the tiny-frame path.
    from h2o3_tpu.ops.sort import device_sort
    df = device_sort(f, names, ascending)
    if df is not None:
        return df
    keys = []
    for n, a in list(zip(names, ascending))[::-1]:
        c = f.col(n)
        v = (_cat_codes(f, n).astype(np.float64) if c.is_categorical
             else _col_np(f, n))
        keys.append(v if a else -v)
    order = np.lexsort(keys)
    return _take_rows(f, order)


_GB_AGGS = {"sum": "sum", "mean": "mean", "min": "min", "max": "max",
            "count": "count", "nrow": "count", "sd": "sd", "sdev": "sd",
            "var": "var", "sumSquares": "ss",
            "median": "median", "mode": "mode"}


@prim("GB", "group-by", "groupby")
def _groupby(env, fr, by_sel, *aggs):
    """(GB frame [by...] agg col na_handling ...) — AstGroup
    (ast/prims/mungers/AstGroup.java). Device path: dense group ids →
    one segment_sum per moment aggregate over the mesh."""
    import jax.numpy as jnp
    import pandas as pd
    from h2o3_tpu.ops.segments import segment_sum
    f = _as_frame(env.ev(fr))
    by = _resolve_cols(f, by_sel)
    key_cols = []
    for n in by:
        c = f.col(n)
        v = (_cat_codes(f, n).astype(np.int64) if c.is_categorical
             else _col_np(f, n))
        key_cols.append(v)
    kdf = pd.DataFrame({i: k for i, k in enumerate(key_cols)})
    gid, uniq = pd.factorize(pd.MultiIndex.from_frame(kdf), sort=True)
    G = len(uniq)
    out: Dict[str, np.ndarray] = {}
    cats, doms = [], {}
    for i, n in enumerate(by):
        c = f.col(n)
        vals = np.asarray([u[i] if isinstance(u, tuple) else u for u in uniq])
        if c.is_categorical:
            out[n] = vals.astype(np.int32)
            cats.append(n)
            doms[n] = c.domain
        else:
            out[n] = vals.astype(np.float64)
    gid_pad = np.zeros(f.nrows_padded, np.int32)
    gid_pad[: f.nrows] = gid
    gid_dev = jnp.asarray(gid_pad)
    valid = np.zeros(f.nrows_padded, np.float32)
    valid[: f.nrows] = 1.0
    valid_dev = jnp.asarray(valid)
    it = list(aggs)
    triplets = []
    while it:
        a = it.pop(0)
        aname = a[1] if isinstance(a, tuple) else str(a)
        col = it.pop(0) if it else None
        if it:
            it.pop(0)   # na-handling token (all/rm/ignore); NAs excluded
        triplets.append((aname.strip('"'), col))
    for aname, colsel in triplets:
        aname = _GB_AGGS.get(aname, aname)
        cname = _resolve_cols(f, colsel)[0] if colsel is not None else by[0]
        c = f.col(cname)
        label = f"{aname}_{cname}" if aname != "count" else "nrow"
        if aname in ("count", "sum", "mean", "var", "sd", "ss"):
            v = c.numeric_view()
            okv = ~jnp.isnan(v)
            w = valid_dev * okv.astype(jnp.float32)
            v0 = jnp.where(okv, v, 0.0)
            sums = segment_sum(gid_dev,
                               jnp.stack([w, w * v0, w * v0 * v0], axis=1),
                               n_nodes=G, mesh=mesh_mod.get_mesh())
            cnt = np.asarray(sums[:, 0], np.float64)
            s1 = np.asarray(sums[:, 1], np.float64)
            s2 = np.asarray(sums[:, 2], np.float64)
            if aname == "count":
                out[label] = cnt
            elif aname == "sum":
                out[label] = s1
            elif aname == "ss":
                out[label] = s2
            elif aname == "mean":
                out[label] = s1 / np.maximum(cnt, 1e-12)
            else:
                m = s1 / np.maximum(cnt, 1e-12)
                var = (s2 / np.maximum(cnt, 1e-12) - m * m) \
                    * cnt / np.maximum(cnt - 1, 1e-12)
                out[label] = (np.sqrt(np.maximum(var, 0))
                              if aname == "sd" else var)
        elif aname in ("min", "max", "median", "mode"):
            if aname == "mode":
                vv = _cat_codes(f, cname).astype(np.float64)
                vv[vv < 0] = np.nan
            else:
                vv = _col_np(f, cname)
            s = pd.Series(vv).groupby(gid)
            agg = (s.agg(lambda g: g.value_counts().idxmax())
                   if aname == "mode" else getattr(s, aname)())
            out[label] = agg.reindex(range(G)).to_numpy()
        else:
            raise ValueError(f"unknown group-by agg '{aname}'")
    return Frame.from_numpy(out, categorical=cats, domains=doms)


@prim("merge")
def _merge(env, l, r, all_left=("num", 0), all_right=("num", 0),
           by_x=None, by_y=None, method=None):
    """Equi-join (water/rapids/Merge.java + BinaryMerge.java roles).

    h2o-py always ships by_x/by_y as column-index lists (defaulting to
    all shared names, h2o-py/h2o/frame.py merge()). Large frames with
    same-named keys run fully on device (ops/merge.py sort-merge join);
    everything else — string keys, right/outer, renamed key pairs,
    tiny frames — takes the host hash join."""
    lf = _as_frame(env.ev(l))
    rf = _as_frame(env.ev(r))
    how = "inner"
    if int(env.ev(all_left)):
        how = "left"
    if int(env.ev(all_right)):
        how = "outer" if how == "left" else "right"
    shared = [n for n in lf.names if n in set(rf.names)]
    bx = by = shared
    if by_x is not None and isinstance(by_x, tuple) \
            and by_x[0] == "list" and by_x[1]:
        bx = _resolve_cols(lf, by_x)
        by = _resolve_cols(rf, by_y) if by_y is not None else bx
    if bx == by:
        from h2o3_tpu.ops.merge import device_merge
        dm = device_merge(lf, rf, bx, how)
        if dm is not None:
            return dm
    ldf = lf.to_pandas()
    rdf = rf.to_pandas()
    # NA keys never match (reference Merge.java / SQL semantics; pandas
    # would join NaN==NaN): drop NA-key rows from the non-preserved side
    if bx:
        if how in ("inner", "left"):
            rdf = rdf.dropna(subset=by)
        if how in ("inner", "right"):
            ldf = ldf.dropna(subset=bx)
    if how == "outer" and bx:
        # both sides preserved: join the non-NA-key rows, then append
        # each side's NA-key rows unmatched (pandas would pair NaN==NaN).
        # Appended slices must carry the SAME schema as the merge result:
        # colliding non-key columns take pandas' _x/_y suffixes and
        # renamed right keys fold under the left key names.
        import pandas as _pd
        lna = ldf[bx].isna().any(axis=1)
        rna = rdf[by].isna().any(axis=1)
        if bx == by:
            m = ldf[~lna].merge(rdf[~rna], how="outer", on=bx)
        else:
            m = ldf[~lna].merge(rdf[~rna], how="outer",
                                left_on=bx, right_on=by)
            m = m.drop(columns=[c for c in by if c not in bx and c in m])
        collide = {c for c in rdf.columns
                   if c not in by and c in set(ldf.columns) - set(bx)}
        l_tail = ldf[lna].rename(
            columns={c: c + "_x" for c in collide})
        r_tail = rdf[rna].rename(columns={**dict(zip(by, bx)),
                                          **{c: c + "_y" for c in collide}})
        r_tail = r_tail.loc[:, [c for c in r_tail.columns if c in m.columns]]
        m = _pd.concat([m, l_tail, r_tail], ignore_index=True)
        return Frame.from_pandas(m)
    if bx == by:
        m = ldf.merge(rdf, how=how, on=bx or None)
    else:
        # renamed key pairs: the reference keeps ONE key column under
        # the left frame's names (BinaryMerge result layout)
        m = ldf.merge(rdf, how=how, left_on=bx, right_on=by)
        m = m.drop(columns=[c for c in by if c not in bx and c in m])
    return Frame.from_pandas(m)


def _device_merge(lf: Frame, rf: Frame, how: str) -> Optional[Frame]:
    """Back-compat shim over ops/merge.py device_merge (joins on all
    shared column names, like the h2o-py default)."""
    from h2o3_tpu.ops.merge import device_merge
    shared = [n for n in lf.names if n in set(rf.names)]
    if not shared:
        return None
    return device_merge(lf, rf, shared, how)


@prim("na.omit")
def _na_omit(env, fr):
    f = _as_frame(env.ev(fr))
    keep = np.ones(f.nrows, bool)
    for n in f.names:
        keep &= ~_fetch_np(f.col(n).na_mask)[: f.nrows]
    return _take_rows(f, np.flatnonzero(keep))


@prim("h2o.impute", "impute")
def _impute(env, fr, col_idx, method=("str", "mean"), *rest):
    f = _as_frame(env.ev(fr))
    all_cols = (isinstance(col_idx, tuple) and col_idx[0] == "num"
                and col_idx[1] < 0)
    names = f.names if all_cols else _resolve_cols(f, col_idx)
    meth = method[1] if isinstance(method, tuple) else str(method)
    arrays, cats, doms = {}, [], {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            codes = _cat_codes(f, n)
            na = codes < 0
            if n in names and meth == "mode" and (~na).any():
                codes[na] = np.bincount(codes[~na]).argmax()
            arrays[n] = codes
            cats.append(n)
            doms[n] = c.domain
        else:
            v = _col_np(f, n).copy()
            if n in names and np.isnan(v).any():
                fill = (np.nanmean(v) if meth == "mean"
                        else np.nanmedian(v) if meth == "median" else np.nan)
                v[np.isnan(v)] = fill
            arrays[n] = v
    return Frame.from_numpy(arrays, categorical=cats, domains=doms)


@prim("scale")
def _scale(env, fr, center=("num", 1), scale_=("num", 1)):
    f = _as_frame(env.ev(fr))
    out = {}
    for n in f.names:
        v = _col_np(f, n)
        if int(env.ev(center)):
            v = v - np.nanmean(v)
        if int(env.ev(scale_)):
            sd = np.nanstd(v, ddof=1)
            v = v / (sd if sd > 0 else 1.0)
        out[n] = v
    return Frame.from_numpy(out)


# ---- string ops (ast/prims/string) ----------------------------------


def _strop(fn):
    def wrapper(env, x, *args):
        f = _as_frame(env.ev(x))
        extra = [a[1] if isinstance(a, tuple) else env.ev(a) for a in args]
        if f.nrows >= _DEV_MIN_ROWS and all(
                f.col(n).is_categorical and f.col(n).domain
                for n in f.names):
            # scale path: transform the DOMAIN on host (O(cardinality))
            # and remap codes on device via a LUT gather — the rows
            # never leave HBM (AstStrOp over CStrChunk becomes a
            # dictionary rewrite at TPU scale)
            import jax.numpy as jnp
            _dev_hit()
            cols = []
            for n in f.names:
                c = f.col(n)
                dom = [fn(s, *extra) for s in (c.domain or [])]
                uniq = sorted(set(dom))
                remap = {s: i for i, s in enumerate(uniq)}
                lut = np.array([remap[s] for s in dom], np.int32)
                codes = jnp.take(jnp.asarray(lut),
                                 c.data.astype(jnp.int32),
                                 mode="clip")
                cols.append(Column(name=n, type=T_CAT, data=codes,
                                   na_mask=c.na_mask, nrows=f.nrows,
                                   domain=uniq))
            return Frame(cols, f.nrows)
        out, cats, strs = {}, [], []
        for n in f.names:
            c = f.col(n)
            if c.is_categorical:
                # transformed labels re-intern: duplicates collapse.
                # '' stays a REAL level — AstSubstring keeps a {""}
                # domain server-side and h2o-py levels() filters ''
                # client-side (h2o-py/h2o/frame.py levels()).
                dom = [fn(s, *extra) for s in (c.domain or [])]
                codes = _fetch_np(c.data)[: f.nrows].astype(np.int64)
                codes = np.where(_fetch_np(c.na_mask)[: f.nrows],
                                 len(dom), codes)
                out[n] = np.array(dom + [None], dtype=object)[codes]
                cats.append(n)
            elif c.type == "string":
                out[n] = np.array([fn(s, *extra) if s is not None else None
                                   for s in c.to_numpy()], dtype=object)
                strs.append(n)   # string in, string out (AstStrOp)
            else:
                out[n] = c.to_numpy()
        return Frame.from_numpy(out, categorical=cats, strings=strs)
    return wrapper


PRIMS["tolower"] = _strop(lambda s, *a: s.lower())
PRIMS["toupper"] = _strop(lambda s, *a: s.upper())
PRIMS["trim"] = _strop(lambda s, *a: s.strip())
PRIMS["sub"] = _strop(
    lambda s, pat, rep, *a: _re.sub(str(pat), str(rep), s, count=1))
PRIMS["gsub"] = _strop(lambda s, pat, rep, *a: _re.sub(str(pat), str(rep), s))
PRIMS["replacefirst"] = PRIMS["sub"]
PRIMS["replaceall"] = PRIMS["gsub"]


@prim("nchar", "strlen")
def _nchar(env, x):
    """String length (AstStrLength, str()='strlen' — the op h2o-py
    nchar() actually sends; 'nchar' kept as a courtesy alias)."""
    f = _as_frame(env.ev(x))
    out = {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            dom = c.domain or []
            lens = np.array([float(len(s)) for s in dom] + [np.nan])
            codes = _fetch_np(c.data)[: f.nrows].astype(np.int64)
            codes = np.where(_fetch_np(c.na_mask)[: f.nrows], len(dom), codes)
            out[n] = lens[codes]
        elif c.type == "string":
            out[n] = np.array([float(len(s)) if s is not None else np.nan
                               for s in c.to_numpy()])
        else:
            out[n] = c.to_numpy()
    return Frame.from_numpy(out)


@prim("substring")
def _substring(env, x, start, end=("num", 1e9)):
    """AstSubstring: start clamps to 0; end sent as an empty AstNumList
    ([] — h2o-py substring(end_index=None)) means MAX; start >= end
    yields '' for every row (the reference's {\"\"} domain), so a
    negative end must NOT fall through to Python negative slicing."""
    s0 = int(env.ev(start))
    if isinstance(end, tuple) and end[0] == "list":
        e0 = int(1e9)                       # [] → Integer.MAX_VALUE
    else:
        ev = env.ev(end)
        e0 = int(1e9) if (isinstance(ev, float) and np.isnan(ev)) \
            else int(min(ev, 1e9))
    s0 = max(s0, 0)
    if e0 <= s0:
        return _strop(lambda s: "")(env, x)
    return _strop(lambda s: s[s0:e0])(env, x)


# ---------------------------------------------------------------- env


# ---- matching / introspection (ast/prims/{mungers,misc}) -------------

@prim("match")
def _match(env, x, table, nomatch=("num", float("nan")), *rest):
    """Value → 1-based index into ``table`` (AstMatch semantics)."""
    f = _as_frame(env.ev(x))
    tbl = env.ev(table)
    if isinstance(tbl, tuple) and tbl[0] == "list":
        tbl = [t[1] for t in tbl[1]]
    elif not isinstance(tbl, (list, np.ndarray)):
        tbl = [tbl]
    nm = env.ev(nomatch)
    lut = {str(v): i + 1 for i, v in enumerate(tbl)}
    out = {}
    for n in f.names:
        c = f.col(n)
        if c.is_categorical:
            dom_map = np.asarray([lut.get(lvl, np.nan)
                                  for lvl in (c.domain or [])] + [np.nan])
            codes = _cat_codes(f, n)
            vals = dom_map[np.where(codes < 0, len(dom_map) - 1, codes)]
        else:
            vals = np.asarray([lut.get(str(v), np.nan)
                               for v in c.to_numpy()])
        out[n] = np.where(np.isnan(vals), nm, vals)
    return _rebuild(f, out, keep_domains=False)


@prim("h2o.which")
def _which(env, x):
    """Row numbers (0-based) where the predicate column is non-zero;
    NA predicate rows are excluded (R which() semantics)."""
    f = _as_frame(env.ev(x))
    v = _col_np(f, f.names[0])
    hit = np.where(~np.isnan(v) & (v != 0))[0]
    return Frame.from_numpy({"which": hit.astype(np.float64)})


def _which_extreme(best_of):
    def fn(env, x, na_rm=("num", 1), axis=("num", 0)):
        """idxmax/idxmin (h2o-py frame.py): axis=0 → per-column max-row
        index (1-row frame); axis=1 → per-row argmax across columns.
        All-NaN slices yield NA instead of raising."""
        f = _as_frame(env.ev(x))
        ax = int(env.ev(axis))
        M = np.stack([_col_np(f, n) for n in f.names], axis=1)
        fill = -np.inf if best_of == "max" else np.inf
        Mf = np.where(np.isnan(M), fill, M)
        pick = np.argmax(Mf, axis=ax) if best_of == "max" \
            else np.argmin(Mf, axis=ax)
        all_na = np.isnan(M).all(axis=ax)
        out = np.where(all_na, np.nan, pick.astype(float))
        name = f"which.{best_of}"
        if ax == 0:
            return Frame.from_numpy({n: np.asarray([out[j]])
                                     for j, n in enumerate(f.names)})
        return Frame.from_numpy({name: out})
    return fn


PRIMS["which.max"] = PRIMS["which_max"] = _which_extreme("max")
PRIMS["which.min"] = PRIMS["which_min"] = _which_extreme("min")


@prim("levels")
def _levels(env, x):
    f = _as_frame(env.ev(x))
    dom = f.col(f.names[0]).domain or []
    return Frame.from_numpy({"levels": np.asarray(dom, dtype=object)},
                            categorical=["levels"])


@prim("nlevels")
def _nlevels(env, x):
    f = _as_frame(env.ev(x))
    return float(f.col(f.names[0]).cardinality)


def _per_column_flags(f, pred):
    """Per-column 0/1 list — h2o-py's isfactor()/isnumeric()/isstring()
    iterate the scalar result (h2o-py/h2o/frame.py:1820)."""
    return [float(pred(f.col(n))) for n in f.names]


@prim("is.factor")
def _is_factor(env, x):
    f = _as_frame(env.ev(x))
    return _per_column_flags(f, lambda c: c.is_categorical)


@prim("is.numeric")
def _is_numeric(env, x):
    f = _as_frame(env.ev(x))
    return _per_column_flags(f, lambda c: c.is_numeric)


@prim("is.character")
def _is_character(env, x):
    f = _as_frame(env.ev(x))
    return _per_column_flags(f, lambda c: c.type == "string")


@prim("anyfactor")
def _anyfactor(env, x):
    f = _as_frame(env.ev(x))
    return float(any(f.col(n).is_categorical for n in f.names))


@prim("any.na")
def _any_na(env, x):
    f = _as_frame(env.ev(x))
    for n in f.names:
        c = f.col(n)
        if c.type == "string":
            if any(v is None for v in c.to_numpy()):
                return 1.0
        elif bool(_fetch_np(c.na_mask)[: f.nrows].any()):
            return 1.0
    return 0.0


@prim("cor")
def _cor(env, x, y=None, use=("str", "everything"), *rest):
    """Pearson correlation (AstCorrelation). use='everything' propagates
    NaN; 'complete.obs'/'all.obs' drop NA rows first."""
    fx = _as_frame(env.ev(x))
    fy = _as_frame(env.ev(y)) if y is not None else fx
    mode = str(env.ev(use)).lower()
    a = np.stack([_col_np(fx, n) for n in fx.names], axis=1)
    b = np.stack([_col_np(fy, n) for n in fy.names], axis=1)
    if mode != "everything":
        ok = ~(np.isnan(a).any(axis=1) | np.isnan(b).any(axis=1))
        a, b = a[ok], b[ok]
    am = a - a.mean(axis=0)
    bm = b - b.mean(axis=0)
    cov = am.T @ bm / max(len(a) - 1, 1)
    sa = a.std(axis=0, ddof=1)
    sb = b.std(axis=0, ddof=1)
    cmat = cov / np.maximum(np.outer(sa, sb), 1e-300)
    if cmat.size == 1:
        return float(cmat[0, 0])
    return Frame.from_numpy({n: cmat[:, j] for j, n in enumerate(fy.names)})


@prim("skewness")
def _skewness(env, x, na_rm=("num", 1)):
    f = _as_frame(env.ev(x))
    v = _col_np(f, f.names[0])
    v = v[~np.isnan(v)]
    s = v.std(ddof=1)
    return float(((v - v.mean()) ** 3).mean() / max(s ** 3, 1e-300))


@prim("kurtosis")
def _kurtosis(env, x, na_rm=("num", 1)):
    f = _as_frame(env.ev(x))
    v = _col_np(f, f.names[0])
    v = v[~np.isnan(v)]
    s = v.std(ddof=1)
    return float(((v - v.mean()) ** 4).mean() / max(s ** 4, 1e-300))


def _str_values(f: Frame, name: str):
    """Column → list of Python strings (None for NA) for string prims."""
    c = f.col(name)
    if c.is_categorical:
        dom = np.asarray(c.domain or [], dtype=object)
        return [None if k < 0 or k >= len(dom) else dom[k]
                for k in _cat_codes(f, name)]
    return list(c.to_numpy())


@prim("strsplit")
def _strsplit(env, x, pattern):
    """Split a string/cat column → multi-column frame (AstStrSplit)."""
    f = _as_frame(env.ev(x))
    pat = env.ev(pattern)
    c = f.col(f.names[0])
    if c.is_categorical:
        dom = np.asarray(c.domain or [], dtype=object)
        codes = _cat_codes(f, f.names[0])
        vals = [None if k < 0 else dom[k] for k in codes]
    else:
        vals = list(c.to_numpy())
    def _split(v):
        if not isinstance(v, str):
            return []
        p = _re.split(pat, v)
        while p and p[-1] == "":   # Java String.split drops trailing empties
            p.pop()
        return p

    parts = [_split(v) for v in vals]
    width = max((len(p) for p in parts), default=1)
    out = {}
    for j in range(width):
        out[f"C{j + 1}"] = np.asarray(
            [p[j] if j < len(p) else None for p in parts], dtype=object)
    return Frame.from_numpy(out, categorical=list(out))


@prim("countmatches")
def _countmatches(env, x, patterns):
    f = _as_frame(env.ev(x))
    pats = env.ev(patterns)
    if isinstance(pats, tuple) and pats[0] == "list":
        pats = [p[1] for p in pats[1]]
    elif not isinstance(pats, list):
        pats = [pats]
    vals = _str_values(f, f.names[0])
    cnt = np.asarray([np.nan if not isinstance(v, str)
                      else float(sum(v.count(str(p)) for p in pats))
                      for v in vals])
    return Frame.from_numpy({f.names[0]: cnt})


@prim("entropy")
def _entropy(env, x):
    """Per-string Shannon entropy over characters (AstEntropy)."""
    f = _as_frame(env.ev(x))
    vals = _str_values(f, f.names[0])

    def ent(s):
        if not isinstance(s, str):
            return np.nan
        if not s:
            return 0.0           # AstEntropy: empty string = 0 bits
        _, cnt = np.unique(list(s), return_counts=True)
        p = cnt / cnt.sum()
        return float(-(p * np.log2(p)).sum())

    return Frame.from_numpy({f.names[0]: np.asarray([ent(v) for v in vals])})


@prim("difflag1")
def _difflag1(env, x):
    """First difference x[i] - x[i-1] (ast/prims/timeseries AstDiffLag1)."""
    f = _as_frame(env.ev(x))
    v = _col_np(f, f.names[0])
    out = np.empty_like(v)
    out[0] = np.nan
    out[1:] = v[1:] - v[:-1]
    return Frame.from_numpy({f.names[0]: out})


def _timeop(extract):
    def fn(env, x):
        f = _as_frame(env.ev(x))
        import datetime as _dt
        out = {}
        for n in f.names:
            ms = _col_np(f, n)
            vals = np.full(len(ms), np.nan)
            ok = ~np.isnan(ms)
            vals[ok] = [extract(_dt.datetime.fromtimestamp(
                m / 1000.0, _dt.timezone.utc)) for m in ms[ok]]
            out[n] = vals
        return _rebuild(f, out, keep_domains=False)
    return fn


PRIMS["year"] = _timeop(lambda d: d.year)
PRIMS["month"] = _timeop(lambda d: d.month)
PRIMS["day"] = _timeop(lambda d: d.day)
PRIMS["hour"] = _timeop(lambda d: d.hour)
PRIMS["minute"] = _timeop(lambda d: d.minute)
PRIMS["second"] = _timeop(lambda d: d.second)
PRIMS["dayOfWeek"] = _timeop(lambda d: d.weekday())
PRIMS["week"] = _timeop(lambda d: d.isocalendar()[1])


@prim("relevel")
def _relevel(env, x, level):
    """Move ``level`` to the front of the domain (AstRelevel)."""
    f = _as_frame(env.ev(x))
    lvl = str(env.ev(level))
    n = f.names[0]
    c = f.col(n)
    dom = list(c.domain or [])
    if lvl not in dom:
        raise ValueError(f"level '{lvl}' not in domain")
    new_dom = [lvl] + [d for d in dom if d != lvl]
    remap = np.asarray([new_dom.index(d) for d in dom])
    codes = _cat_codes(f, n)
    new_codes = np.where(codes < 0, -1, remap[np.maximum(codes, 0)])
    return Frame.from_numpy({n: new_codes.astype(np.int32)},
                            categorical=[n], domains={n: new_dom})


class Env:
    """Evaluation environment (water/rapids/Env.java)."""

    def __init__(self, session: Session):
        self.session = session

    def ev(self, node):
        if isinstance(node, tuple):
            tag, v = node
            if tag in ("num", "str"):
                return v
            if tag == "id":
                return self.session.lookup(v)
            if tag == "list":
                return node
            raise ValueError(f"bad node {node!r}")
        if isinstance(node, list):
            if not node:
                return None
            head = node[0]
            opname = head[1] if isinstance(head, tuple) else str(head)
            if opname not in PRIMS:
                raise ValueError(f"Rapids: unknown op '{opname}'")
            return PRIMS[opname](self, *node[1:])
        return node


_SESSION: Optional[Session] = None


def _default_session() -> Session:
    global _SESSION
    if _SESSION is None:
        _SESSION = Session()
    return _SESSION


def rapids(expr: str, session: Optional[Session] = None):
    """Parse + evaluate one Rapids expression (POST /99/Rapids)."""
    session = session or _default_session()
    return Env(session).ev(parse(expr))


# ------------------------------------------------------- extended prims
# (matrix, advmath, repeaters, filters, reshape — the remaining
# water/rapids/ast/prims families; wire names match the reference)

def _as_pylist(env, node):
    """('list', [...]) AST → python values; scalar → [scalar]."""
    if isinstance(node, tuple) and node[0] == "list":
        return [x[1] if isinstance(x, tuple) else x for x in node[1]]
    v = env.ev(node)
    return None if v is None else [v]


def _num_matrix(f: Frame) -> np.ndarray:
    # f64: matrix ops feed pyunit oracles computed in float64
    return np.stack([_col_np(f, n).astype(np.float64)
                     for n in f.names], axis=1)


@prim("t")
def _transpose(env, fr):
    """matrix/AstTranspose."""
    f = _as_frame(env.ev(fr))
    M = _num_matrix(f).T
    return Frame.from_numpy({f"C{i + 1}": M[:, i] for i in range(M.shape[1])})


@prim("x")
def _mmult(env, l, r):
    """matrix/AstMMult: frame-as-matrix product."""
    A = _num_matrix(_as_frame(env.ev(l)))
    B = _num_matrix(_as_frame(env.ev(r)))
    M = A @ B
    return Frame.from_numpy({f"C{i + 1}": M[:, i] for i in range(M.shape[1])})


@prim("hist")
def _hist(env, fr, breaks=("str", "sturges")):
    """advmath/AstHist: breaks/counts/mids frame (h2o-py frame.hist)."""
    f = _as_frame(env.ev(fr))
    v = _col_np(f, f.names[0])
    v = v[~np.isnan(v)]
    b = breaks[1] if isinstance(breaks, tuple) and breaks[0] in ("num", "str") \
        else breaks
    lst = _as_pylist(env, breaks) if isinstance(breaks, tuple) and \
        breaks[0] == "list" else None
    if lst is not None:
        edges = np.asarray(lst, np.float64)
    elif isinstance(b, (int, float)) and not isinstance(b, bool):
        edges = np.linspace(v.min(), v.max(), int(b) + 1) if v.size else \
            np.array([0.0, 1.0])
    else:   # sturges / rice / sqrt / doane / scott / fd
        rule = str(b).lower()
        n = max(v.size, 1)
        if rule == "rice":
            k = int(np.ceil(2 * n ** (1 / 3)))
        elif rule == "sqrt":
            k = int(np.ceil(np.sqrt(n)))
        else:   # sturges default
            k = int(np.ceil(np.log2(n))) + 1
        edges = np.linspace(v.min(), v.max(), max(k, 1) + 1) if v.size else \
            np.array([0.0, 1.0])
    counts, edges = np.histogram(v, bins=edges)
    widths = np.diff(edges)
    dens = counts / np.maximum(widths * max(v.size, 1), 1e-300)
    mids = 0.5 * (edges[:-1] + edges[1:])
    pad = lambda a: np.concatenate([[np.nan], a])
    return Frame.from_numpy({
        "breaks": edges.astype(np.float64),
        "counts": pad(counts.astype(np.float64)),
        "mids_true": pad(mids), "mids": pad(mids),
        "density": pad(dens)})


@prim("cut")
def _cut(env, fr, breaks, labels=None, include_lowest=("num", 0),
         right=("num", 1), dig_lab=("num", 3)):
    """mungers/AstCut: numeric → categorical by bin edges."""
    f = _as_frame(env.ev(fr))
    edges = np.asarray(_as_pylist(env, breaks), np.float64)
    labs = _as_pylist(env, labels) if labels is not None else None
    inc_low = bool(env.ev(include_lowest))
    rgt = bool(env.ev(right))
    dig = int(env.ev(dig_lab))
    v = _col_np(f, f.names[0])
    if labs:
        dom = [str(x) for x in labs]
    elif rgt:
        dom = [f"({round(edges[i], dig)}, {round(edges[i + 1], dig)}]"
               for i in range(len(edges) - 1)]
    else:
        dom = [f"[{round(edges[i], dig)}, {round(edges[i + 1], dig)})"
               for i in range(len(edges) - 1)]
    if rgt:
        codes = np.searchsorted(edges, v, side="left") - 1
        if inc_low:
            codes[v == edges[0]] = 0
    else:
        codes = np.searchsorted(edges, v, side="right") - 1
    codes = codes.astype(np.int32)
    bad = np.isnan(v) | (codes < 0) | (codes >= len(dom))
    codes[bad] = -1
    return Frame.from_numpy({f.names[0]: codes}, categorical=[f.names[0]],
                            domains={f.names[0]: dom})


@prim("h2o.fillna", "fillna")
def _fillna(env, fr, method=("str", "forward"), axis=("num", 0),
            maxlen=("num", 1)):
    """mungers/AstFillNA: directional NA fill with a run cap.

    Vectorized: last-valid-index propagation via maximum.accumulate +
    a run-length cap; column order is preserved; strings pass through.
    """
    f = _as_frame(env.ev(fr))
    meth = str(env.ev(method)).lower()
    ax = int(env.ev(axis))
    cap = int(env.ev(maxlen))
    forward = meth == "forward"

    def capped_fill(M):
        """Fill along axis 1 of a [n, m] float matrix."""
        if not forward:
            M = M[:, ::-1]
        valid = ~np.isnan(M)
        m = M.shape[1]
        idx = np.arange(m)[None, :]
        last = np.maximum.accumulate(np.where(valid, idx, -1), axis=1)
        rows = np.arange(M.shape[0])[:, None]
        src = M[rows, np.maximum(last, 0)]
        fill = ~valid & (last >= 0) & (idx - last <= cap)
        out = np.where(fill, src, M)
        return out[:, ::-1] if not forward else out

    out, cats, doms, strs = {}, [], {}, []
    if ax == 0:     # along rows, per column
        for n in f.names:
            c = f.col(n)
            if c.type == "string":
                out[n] = c.to_numpy()
                strs.append(n)
                continue
            v = (_cat_codes(f, n).astype(np.float64) if c.is_categorical
                 else _col_np(f, n))
            if c.is_categorical:
                v = np.where(v < 0, np.nan, v)
            v = capped_fill(v[None, :])[0]
            if c.is_categorical:
                out[n] = np.where(np.isnan(v), -1, v).astype(np.int32)
                cats.append(n)
                doms[n] = c.domain
            else:
                out[n] = v
    else:           # along columns, per row (numeric columns only)
        num_names = [n for n in f.names if not f.col(n).is_categorical
                     and f.col(n).type != "string"]
        M = (np.stack([_col_np(f, n) for n in num_names], axis=1)
             if num_names else None)
        if M is not None:
            M = capped_fill(M)
        for n in f.names:          # original order preserved
            c = f.col(n)
            if c.type == "string":
                out[n] = c.to_numpy()
                strs.append(n)
            elif c.is_categorical:
                out[n] = _cat_codes(f, n)
                cats.append(n)
                doms[n] = c.domain
            else:
                out[n] = M[:, num_names.index(n)]
    return Frame.from_numpy(out, categorical=cats, domains=doms,
                            strings=strs)


@prim("kfold_column")
def _kfold_column(env, fr, nfolds, seed=("num", -1)):
    """advmath/AstKFold: uniform random fold ids."""
    f = _as_frame(env.ev(fr))
    k = int(env.ev(nfolds))
    s = int(env.ev(seed))
    # seed==-1 means "draw a fresh random seed" in the reference, not a
    # fixed constant (AstKFold)
    r = np.random.RandomState(
        s if s >= 0 else np.random.SeedSequence().entropy % (2**32))
    return Frame.from_numpy(
        {"fold": r.randint(0, k, f.nrows).astype(np.float64)})


@prim("modulo_kfold_column")
def _modulo_kfold(env, fr, nfolds):
    f = _as_frame(env.ev(fr))
    k = int(env.ev(nfolds))
    return Frame.from_numpy(
        {"fold": (np.arange(f.nrows) % k).astype(np.float64)})


@prim("stratified_kfold_column")
def _strat_kfold(env, fr, nfolds, seed=("num", -1)):
    """advmath/AstStratifiedKFold: per-class round-robin after shuffle —
    every fold sees ~the same class distribution."""
    f = _as_frame(env.ev(fr))
    k = int(env.ev(nfolds))
    s = int(env.ev(seed))
    r = np.random.RandomState(
        s if s >= 0 else np.random.SeedSequence().entropy % (2**32))
    y = _cat_codes(f, f.names[0]) if f.col(f.names[0]).is_categorical \
        else _col_np(f, f.names[0])
    fold = np.zeros(f.nrows, np.float64)
    for cls in np.unique(y[~np.isnan(np.asarray(y, np.float64))]):
        idx = np.where(y == cls)[0]
        r.shuffle(idx)
        fold[idx] = np.arange(len(idx)) % k
    return Frame.from_numpy({"fold": fold})


@prim("h2o.random_stratified_split")
def _strat_split(env, fr, test_frac=("num", 0.25), seed=("num", -1)):
    """advmath/AstStratifiedSplit: per-class train/test tagging."""
    f = _as_frame(env.ev(fr))
    frac = float(env.ev(test_frac))
    s = int(env.ev(seed))
    r = np.random.RandomState(s if s >= 0 else 0x57A7)
    y = _cat_codes(f, f.names[0]) if f.col(f.names[0]).is_categorical \
        else _col_np(f, f.names[0])
    codes = np.zeros(f.nrows, np.int32)
    for cls in np.unique(y):
        idx = np.where(y == cls)[0]
        r.shuffle(idx)
        ntest = int(round(len(idx) * frac))
        codes[idx[:ntest]] = 1
    return Frame.from_numpy({"test_train_split": codes},
                            categorical=["test_train_split"],
                            domains={"test_train_split": ["train", "test"]})


@prim("seq_len")
def _seq_len(env, n):
    """repeaters/AstSeqLen: 1..n."""
    return Frame.from_numpy(
        {"C1": np.arange(1, int(env.ev(n)) + 1, dtype=np.float64)})


@prim("seq")
def _seq(env, fro, to, by=("num", 1)):
    a, b, st = float(env.ev(fro)), float(env.ev(to)), float(env.ev(by))
    # extend the stop by half a step IN the step direction so the
    # endpoint is included for both signs (R-style seq)
    return Frame.from_numpy(
        {"C1": np.arange(a, b + st / 2, st, dtype=np.float64)})


@prim("rep_len")
def _rep_len(env, x, length):
    """AstRepLen: single column → repeat ROWS to length; multi-column
    frame → repeat COLUMNS cyclically to length columns."""
    n = int(env.ev(length))
    v = env.ev(x)
    if not isinstance(v, Frame):
        return Frame.from_numpy({"C1": np.full(n, float(v))})
    if v.ncols == 1:
        # output vec is wrapped in an UNNAMED frame → default name C1
        # (AstRepLen.java:50 `new Frame(vec)`)
        nm = v.names[0]
        c = v.col(nm)
        if c.is_categorical:
            return Frame.from_numpy(
                {"C1": np.resize(_cat_codes(v, nm), n)},
                categorical=["C1"], domains={"C1": c.domain})
        return Frame.from_numpy(
            {"C1": np.resize(_col_np(v, nm), n).astype(np.float64)})
    out, cats, doms = {}, [], {}
    for i in range(n):
        src = v.names[i % v.ncols]
        nm = f"C{i + 1}"
        c = v.col(src)
        if c.is_categorical:
            out[nm] = _cat_codes(v, src)
            cats.append(nm)
            doms[nm] = c.domain
        else:
            out[nm] = _col_np(v, src)
    return Frame.from_numpy(out, categorical=cats, domains=doms)


@prim("distance")
def _distance(env, l, r, measure=("str", "l2")):
    """advmath/AstDistance: pairwise row distances [n_l x n_r]."""
    A = _num_matrix(_as_frame(env.ev(l)))
    B = _num_matrix(_as_frame(env.ev(r)))
    m = str(env.ev(measure)).lower()
    if m in ("l2", "euclidean"):
        D = np.sqrt(np.maximum(
            (A ** 2).sum(1)[:, None] + (B ** 2).sum(1)[None, :]
            - 2 * A @ B.T, 0.0))
    elif m == "l1":
        D = np.abs(A[:, None, :] - B[None, :, :]).sum(axis=2)
    elif m in ("cosine", "cosine_sq"):
        na = np.linalg.norm(A, axis=1)
        nb = np.linalg.norm(B, axis=1)
        C = (A @ B.T) / np.maximum(na[:, None] * nb[None, :], 1e-300)
        D = C ** 2 if m == "cosine_sq" else C
    else:
        raise ValueError(f"unknown distance measure '{m}'")
    return Frame.from_numpy({f"C{i + 1}": D[:, i] for i in range(D.shape[1])})


@prim("dropdup")
def _dropdup(env, fr, cols_sel, keep=("str", "first")):
    """filters/dropduplicates AstDropDuplicatesByColumns."""
    f = _as_frame(env.ev(fr))
    names = _resolve_cols(f, cols_sel)
    kp = str(env.ev(keep)).lower()

    def keycol(n):
        c = f.col(n)
        if c.is_categorical:
            return _cat_codes(f, n).astype(np.float64)
        if c.type == "string":
            # intern strings to codes so keys stay numeric (None -> nan)
            vals = c.to_numpy()
            lut = {}
            return np.array(
                [np.nan if v is None else lut.setdefault(v, len(lut))
                 for v in vals], np.float64)
        return _col_np(f, n)

    keyarr = np.stack([keycol(n) for n in names], axis=1)
    seen = {}
    order = range(f.nrows) if kp == "first" else range(f.nrows - 1, -1, -1)
    nan_mask = np.isnan(keyarr)
    key_vals = np.where(nan_mask, 0.0, keyarr)
    for i in order:
        # NaN != NaN, so carry the NA pattern separately to make
        # NA-keyed duplicates compare equal
        key = (tuple(key_vals[i].tolist()), tuple(nan_mask[i].tolist()))
        seen.setdefault(key, i)
    idx = np.array(sorted(seen.values()), dtype=np.int64)
    return _take_rows(f, idx)


@prim("grep")
def _grep(env, fr, regex, ignore_case=("num", 0), invert=("num", 0),
          output_logical=("num", 0)):
    """string/AstGrep: match rows of a string/categorical column."""
    f = _as_frame(env.ev(fr))
    pat = str(env.ev(regex))
    flags = _re.IGNORECASE if env.ev(ignore_case) else 0
    rx = _re.compile(pat, flags)
    c = f.col(f.names[0])
    if c.is_categorical:
        dom = c.domain or []
        dom_hit = np.array([bool(rx.search(s)) for s in dom])
        codes = _cat_codes(f, f.names[0])
        hit = np.where(codes >= 0, dom_hit[np.maximum(codes, 0)], False)
    else:
        hit = np.array([bool(rx.search(str(v))) if v is not None else False
                        for v in c.to_numpy()])
    if env.ev(invert):
        hit = ~hit
    if env.ev(output_logical):
        return Frame.from_numpy({"C1": hit.astype(np.float64)})
    return Frame.from_numpy(
        {"C1": np.where(hit)[0].astype(np.float64)})


def _strip_prim(side):
    def fn(env, fr, chars=("str", " ")):
        f = _as_frame(env.ev(fr))
        cs = str(env.ev(chars))
        out, cats, doms = {}, [], {}
        for n in f.names:
            c = f.col(n)
            if c.is_categorical:
                dom = [s.lstrip(cs) if side == "l" else s.rstrip(cs)
                       for s in (c.domain or [])]
                # re-intern: stripping may merge levels
                uniq = sorted(set(dom))
                remap = np.array([uniq.index(d) for d in dom], np.int32)
                codes = _cat_codes(f, n)
                out[n] = np.where(codes >= 0, remap[np.maximum(codes, 0)],
                                  -1).astype(np.int32)
                cats.append(n)
                doms[n] = uniq
            elif c.type == "string":
                out[n] = np.array(
                    [None if v is None else
                     (v.lstrip(cs) if side == "l" else v.rstrip(cs))
                     for v in c.to_numpy()], dtype=object)
            else:
                out[n] = _col_np(f, n)
        return Frame.from_numpy(out, categorical=cats, domains=doms)
    return fn


PRIMS["lstrip"] = _strip_prim("l")
PRIMS["rstrip"] = _strip_prim("r")


@prim("melt")
def _melt(env, fr, id_vars, value_vars=None, var_name=("str", "variable"),
          value_name=("str", "value"), skipna=("num", 0)):
    """mungers/AstMelt: wide → long."""
    f = _as_frame(env.ev(fr))
    ids = _resolve_cols(f, id_vars)
    vals = _resolve_cols(f, value_vars) if value_vars is not None and \
        not (isinstance(value_vars, tuple) and value_vars[1] is None) else \
        [n for n in f.names if n not in ids]
    vname = str(env.ev(var_name))
    vvalue = str(env.ev(value_name))
    skip = bool(env.ev(skipna))
    n = f.nrows
    id_cols = {k: [] for k in ids}
    var_codes, values = [], []
    id_data = {k: (_cat_codes(f, k) if f.col(k).is_categorical
                   else _col_np(f, k)) for k in ids}
    for vi, vn in enumerate(vals):
        col = _col_np(f, vn)
        keep = ~np.isnan(col) if skip else np.ones(n, bool)
        for k in ids:
            id_cols[k].append(np.asarray(id_data[k])[keep])
        var_codes.append(np.full(keep.sum(), vi, np.int32))
        values.append(col[keep])
    out, cats, doms = {}, [], {}
    for k in ids:
        merged = np.concatenate(id_cols[k])
        if f.col(k).is_categorical:
            out[k] = merged.astype(np.int32)
            cats.append(k)
            doms[k] = f.col(k).domain
        else:
            out[k] = merged.astype(np.float64)
    out[vname] = np.concatenate(var_codes)
    cats.append(vname)
    doms[vname] = list(vals)
    out[vvalue] = np.concatenate(values)
    return Frame.from_numpy(out, categorical=cats, domains=doms)


@prim("pivot")
def _pivot(env, fr, index, column, value):
    """mungers/AstPivot: long → wide (first value per cell)."""
    f = _as_frame(env.ev(fr))
    inames = _resolve_cols(f, index)
    cname = _resolve_cols(f, column)[0]
    vname = _resolve_cols(f, value)[0]
    iname = inames[0]
    icol_cat = f.col(iname).is_categorical
    ivals = _cat_codes(f, iname) if icol_cat else _col_np(f, iname)
    cc = f.col(cname)
    if cc.is_categorical:
        levels = list(cc.domain or [])
        ccode = _cat_codes(f, cname)
    else:
        raw = _col_np(f, cname)
        lv = np.unique(raw[~np.isnan(raw)])
        levels = [str(x) for x in lv]
        ccode = np.searchsorted(lv, raw)
    vvals = _col_np(f, vname)
    uniq = np.unique(np.asarray(ivals, np.float64))
    uniq = uniq[~np.isnan(uniq)]
    pos = {u: i for i, u in enumerate(uniq)}
    M = np.full((len(uniq), len(levels)), np.nan)
    for i in range(f.nrows):
        iv = float(ivals[i])
        if np.isnan(iv) or ccode[i] < 0 or ccode[i] >= len(levels):
            continue
        r_ = pos[iv]
        if np.isnan(M[r_, ccode[i]]):
            M[r_, ccode[i]] = vvals[i]
    out, cats, doms = {}, [], {}
    if icol_cat:
        out[iname] = uniq.astype(np.int32)
        cats.append(iname)
        doms[iname] = f.col(iname).domain
    else:
        out[iname] = uniq
    for j, lev in enumerate(levels):
        out[str(lev)] = M[:, j]
    return Frame.from_numpy(out, categorical=cats, domains=doms)
