"""Plain reference for the random forest the configuration states.

A forest of ``ntrees`` independent classification trees on a binary
response, grown level by level to ``max_depth``. numpy in float64, one
``bincount`` a feature and level, no kernels; imports nothing of the
program (``quantile_cuts`` and ``auc`` are the benchmark's own).

What is written down here, each in its own lines below:

* **Candidates** (as ``references/gbm.py``): a numeric feature offers
  ``nbins`` global quantile cuts from ALL rows (``quantile_cuts``), a
  row's candidate bin the number of cuts at or below its value; a
  categorical feature offers every proper prefix of its LIVE levels
  ordered by the node's mean response, ascending, ties by level code
  (the subset split of DTree.findBestSplitPoint).
* **The bag** (``replay_keys``, ``bag_of``): ``PRNGKey(seed)``; a tree
  takes ``key, sub = split(key)``, then ``kb, _, _, kt = split(sub, 4)``,
  its rows are ``bernoulli(kb, sample_rate, (rows_padded,))`` cut to the
  real rows — the padded row count is the program's, handed over by the
  adapter, because the draw's values depend on the shape — and its
  column draws use ``split(kt)[1]`` (one response class). ``jax.random``
  on the CPU stands for Java's generator (the configuration's
  ``assumed``).
* **A node's columns** (``draws``): the ``mtries`` columns that ``F``
  uniforms from ``fold_in(tree key, 2^level + path)`` rank lowest;
  ``path`` is the node's position in its complete level.
* **A split**: over the node's in-bag rows, sums ``W`` (rows) and ``Y``
  (responses) left and right; gain ``YL²/WL + YR²/WR − Y²/W``; both
  children keep ``min_rows`` rows; the best candidate of the drawn
  columns is taken where its gain exceeds ``min_split_improvement`` and
  the level is under ``max_depth``; a node that does not split is a
  leaf valued ``Y / W``. There are no missing values and the reference
  refuses any.
* **Out of bag**: a row a tree's bag left out is scored by that tree's
  leaf; a row's prediction is the mean over the trees that left it out,
  clipped to [0, 1]; logloss over the rows some tree left out, its
  probabilities clipped to [1e-7, 1 − 1e-7] as float32 values (the
  metric's stated rule), and the exact AUC.

``check`` FOLLOWS a model (teacher forcing): it replays bag and draws,
routes every row by the model's own raw-value rules node by node, and at
every node of a level under ``max_depth`` — leaves too — computes the
float64 best among the drawn columns. It returns ``depth_gap`` (levels
by which the forest stops short of where a node still had a split to
take: a forest capped above ``max_depth`` reads ≥ 1), ``mtries_gap``
(splits on a column outside the node's draw), ``gain_gap`` (worst
shortfall of a node's split, its gain from its children's float64 sums,
under that best — as a share of the node's own squared error
``Y − Y²/W``, the most any split of it can gain: a relative gap would
read the float32 rounding of ``YL²/WL + YR²/WR − Y²/W`` at a node of
10⁷ rows, ±1 beside terms of 10⁷, as tenths wherever the node's drawn
columns are all weak; a leaf that had a split to take reads that
split's share),
``leaf_gap`` (worst absolute distance of a leaf from ``Y / W``),
``leaf_rows_gap`` (worst leaf's row count, over the rows),
``oob_logloss_gap`` (relative) and ``oob_auc_gap`` (absolute) against
the model's reported metrics — scored with the model's own leaves — and
the counted facts ``_depth_reached``, ``_leaves``. ``control`` grows the
reference's own forest by the same rule with every sum held in bfloat16
(one 8-bit pass), in the adapter's format.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.references.gbm import quantile_cuts
from benchmark.references.ranking import auc

NAMES = ("depth_gap", "mtries_gap", "gain_gap", "leaf_gap",
         "leaf_rows_gap", "oob_logloss_gap", "oob_auc_gap")
THREADS = 8         # building the candidate bins
PROCS = 12          # worker processes of the walk
CHUNKS = 24         # row slices they share
FORK_ROWS = 1_000_000
CAT_PARTS = 4       # runs of nodes a categorical column is cut into


def _bf16(v):
    import ml_dtypes
    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def replay_keys(seed: int, ntrees: int):
    """Per tree ``(bag key, column-draw key)`` by the recipe above."""
    import jax
    cpu = jax.devices("cpu")[0]
    out = []
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(seed if seed >= 0 else 0xD2F)
        for _ in range(ntrees):
            key, sub = jax.random.split(key)
            kb, _, _, kt = jax.random.split(sub, 4)
            out.append((kb, jax.random.split(kt)[1]))
    return out


def bag_of(kb, rate: float, rows_padded: int, n: int) -> np.ndarray:
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(jax.random.bernoulli(
            kb, rate, shape=(rows_padded,)))[:n]


def draws(key, level: int, path: np.ndarray, F: int, mtries: int):
    """``[L, F]`` bool: the columns each node may split on."""
    if not 0 < mtries < F:
        return np.ones((path.shape[0], F), bool)
    import jax
    import jax.numpy as jnp
    L = path.shape[0]
    padded = np.zeros(max(1024, 1 << (L - 1).bit_length()), np.int32)
    padded[:L] = path + 2 ** level      # few shapes, so few compilations
    with jax.default_device(jax.devices("cpu")[0]):
        u = jax.vmap(lambda h: jax.random.uniform(
            jax.random.fold_in(key, h), (F,)))(jnp.asarray(padded))
        u = np.asarray(u)[:L]
    rank = np.argsort(np.argsort(u, axis=1, kind="stable"), axis=1,
                      kind="stable")
    return rank < mtries


def _gains(cw, cy, tw, ty, min_rows):
    """Gain of every prefix ``[L, nb-1]`` from cumulative sums."""
    with np.errstate(divide="ignore", invalid="ignore"):
        g = (cy * cy / cw + (ty[:, None] - cy) ** 2 / (tw[:, None] - cw)
             - (ty * ty / tw)[:, None])
    ok = (cw >= min_rows) & (tw[:, None] - cw >= min_rows) & np.isfinite(g)
    return np.where(ok, g, -np.inf)


def _best_of_feature(hw, hy, is_cat: bool, min_rows: float, want_split):
    """Best candidate per node of one feature from its histograms
    ``[L, nb]``: ``(gain [L], left [L, nb] bool or None)``."""
    L, nb = hw.shape
    if nb < 2 or L == 0:
        return np.full(L, -np.inf), None
    tw, ty = hw.sum(axis=1), hy.sum(axis=1)
    if is_cat:
        with np.errstate(divide="ignore", invalid="ignore"):
            key = np.where(hw > 0, hy / hw, np.inf)
        order = np.argsort(key, axis=1, kind="stable")
        sw = np.take_along_axis(hw, order, axis=1)
        sy = np.take_along_axis(hy, order, axis=1)
    else:
        order, sw, sy = None, hw, hy
    g = _gains(np.cumsum(sw, axis=1)[:, :-1], np.cumsum(sy, axis=1)[:, :-1],
               tw, ty, min_rows)
    t = np.argmax(g, axis=1)
    best = g[np.arange(L), t]
    if not want_split:
        return best, None
    pos = np.arange(nb)[None, :] <= t[:, None]          # sorted positions
    if order is None:
        return best, pos
    left = np.zeros((L, nb), bool)
    np.put_along_axis(left, order, pos, axis=1)
    return best, left


class _Data:
    """The columns as the walk needs them: candidate bins, response."""

    def __init__(self, data: dict, params: dict, names=None):
        cols, self.resp = data["columns"], data["response"]
        self.names = [n for n in cols if n != self.resp]
        if names is not None and list(names) != self.names:
            raise ValueError(f"model features {names} != {self.names}")
        for name in cols:
            if cols[name].dtype.kind == "f" and np.isnan(cols[name]).any():
                raise ValueError(f"reference: missing values in {name!r}")
        self.cols = [cols[n] for n in self.names]
        self.n = len(cols[self.resp])
        self.y = np.asarray(cols[self.resp], np.float64)
        self.is_cat = [n in data["domains"] for n in self.names]
        nlev = [len(data["domains"][n]) if c else 0
                for n, c in zip(self.names, self.is_cat)]
        with ThreadPoolExecutor(THREADS) as pool:
            self.cuts = list(pool.map(
                lambda f: np.zeros(0, np.float32) if self.is_cat[f]
                else quantile_cuts(self.cols[f], int(params["nbins"])),
                range(len(self.names))))
            self.bins = list(pool.map(self._candidate_bins,
                                      range(len(self.names))))
        self.nb = [nlev[f] if self.is_cat[f] else len(self.cuts[f]) + 1
                   for f in range(len(self.names))]

    def _candidate_bins(self, f):
        x = self.cols[f]
        if self.is_cat[f]:
            return x.astype(np.uint16)
        return np.searchsorted(self.cuts[f].astype(np.float64), x,
                               side="right").astype(np.uint16)


# ---- the walk's row passes, in worker processes ------------------------------
# numpy holds the interpreter lock through fancy indexing and ``bincount``,
# so threads do not share this work. ``_walk`` forks ``PROCS`` workers once
# the static data stands; they see it (and the row state below, in shared
# anonymous memory) as globals, take small per-level tables as arguments
# and hand back per-node results.

_W = {}     # d, cuts_at, and the shared arrays, set by _walk before the fork


class _Here:
    """The pool's part for a frame too small to fork for."""

    def map(self, fn, tasks, chunksize=None):
        return [fn(t) for t in tasks]

    def close(self):
        pass

    join = close


def _shared(n: int, dtype):
    import mmap
    size = max(int(n), 1) * np.dtype(dtype).itemsize
    return np.frombuffer(mmap.mmap(-1, size), dtype=dtype)[: max(int(n), 1)]


def _t_in_bag(args):
    """Slice ``c``'s live rows that are in the tree's bag, listed once
    under each column their node drew (``mask`` [L, F])."""
    c, mask = args
    s = int(_W["cuts_at"][c])
    k = int(_W["n_live"][c])
    r, l = _W["rows"][s:s + k], _W["loc"][s:s + k]
    m = _W["keep"][r]
    r, l = r[m], l[m]
    at = _W["per_row"] * s
    for f in range(mask.shape[1]):
        sel = np.nonzero(mask[l, f])[0]
        _W["rows_f"][at:at + sel.size] = r[sel]
        _W["loc_f"][at:at + sel.size] = l[sel]
        _W["at_f"][c, f], _W["n_f"][c, f] = at, sel.size
        at += sel.size


def _t_feature(args):
    """One feature's histograms over the in-bag rows of the nodes that
    drew it — the ``part``-th of ``parts`` runs of those nodes — and
    each such node's best candidate."""
    f, drawn, part, parts, min_rows, want_split, bf16 = args
    d = _W["d"]
    nodes = np.nonzero(drawn)[0]
    compact = (np.cumsum(drawn) - 1).astype(np.int64)
    lo, hi = (nodes.shape[0] * part // parts,
              nodes.shape[0] * (part + 1) // parts)
    nodes = nodes[lo:hi]
    nbf = d.nb[f]
    size = (hi - lo) * nbf
    keys, ys = [], []
    for c in range(_W["at_f"].shape[0]):
        at, k = int(_W["at_f"][c, f]), int(_W["n_f"][c, f])
        r_s, at_node = _W["rows_f"][at:at + k], compact[_W["loc_f"][at:at + k]]
        if parts > 1:
            mine = np.nonzero((at_node >= lo) & (at_node < hi))[0]
            r_s, at_node = r_s[mine], at_node[mine]
        keys.append((at_node - lo) * nbf + d.bins[f][r_s])
        ys.append(d.y[r_s])
    key = np.concatenate(keys)
    hw = np.bincount(key, minlength=size).astype(np.float64)
    hy = np.bincount(key, weights=np.concatenate(ys), minlength=size)
    if bf16:
        hw, hy = _bf16(hw), _bf16(hy)
    g, left = _best_of_feature(hw.reshape(-1, nbf), hy.reshape(-1, nbf),
                               d.is_cat[f], min_rows, want_split)
    return f, nodes, g, left


def _t_route(args):
    """Slice ``c``: rows of leaves are scored if out of bag and dropped;
    the others go to their children by the raw-value rules. Returns the
    children's in-bag (rows, responses) sums from this slice."""
    c, leaf, val, feat, kid, value, words, L2 = args
    d = _W["d"]
    s = int(_W["cuts_at"][c])
    k = int(_W["n_live"][c])
    r, l = _W["rows"][s:s + k].copy(), _W["loc"][s:s + k].copy()
    done = leaf[l]
    out = done & ~_W["keep"][r]
    _W["oob_sum"][r[out]] += val[l[out]]            # a row once a tree
    _W["oob_cnt"][r[out]] += 1.0
    r, l = r[~done], l[~done]
    f_r = feat[l]
    right = np.zeros(r.shape[0], bool)
    for f in range(len(d.cols)):
        m = np.nonzero(f_r == f)[0]
        if not m.size:
            continue
        x = d.cols[f][r[m]]
        if d.is_cat[f]:
            w_r = words[l[m], x >> 5]
            right[m] = ((w_r >> (x & 31).astype(np.uint32)) & 1) == 0
        else:
            right[m] = ~(x < value[l[m]])
    l = 2 * kid[l] + right
    k = r.shape[0]
    _W["rows"][s:s + k], _W["loc"][s:s + k] = r, l
    _W["n_live"][c] = k
    m = _W["keep"][r]
    return (np.bincount(l[m], minlength=L2),
            np.bincount(l[m], weights=d.y[r[m]], minlength=L2))


def _walk(d: _Data, params: dict, follow: dict | None, bf16: bool):
    """Follow ``follow`` (the adapter's dict) or grow the reference's
    own forest. Returns ``(numbers, model)``."""
    import multiprocessing
    p = params
    n, F = d.n, len(d.names)
    D = int(p["max_depth"])
    min_rows, msi = float(p["min_rows"]), float(p["min_split_improvement"])
    mtries = int(p["mtries"])
    if mtries == -1:
        mtries = max(1, int(np.sqrt(F)))
    elif mtries <= 0:
        mtries = F
    seed = int(follow["seed"] if follow is not None else p["seed"])
    npad = int(follow["rows_padded"] if follow is not None
               else p.get("rows_padded", n))
    T = len(follow["trees"]) if follow is not None else int(p["ntrees"])
    rnd = _bf16 if bf16 else (lambda v: v)
    W = max(1, (max(d.nb) + 31) // 32)
    # a small frame is walked here, in one slice: forking pays from
    # about a million rows
    chunks = CHUNKS if n >= FORK_ROWS else 1
    cuts_at = np.linspace(0, n, chunks + 1).astype(np.int64)
    per_row = mtries if 0 < mtries < F else F   # columns a node draws
    _W.clear()
    _W.update(d=d, cuts_at=cuts_at,
              rows=_shared(n, np.int32), loc=_shared(n, np.int32),
              per_row=per_row, rows_f=_shared(per_row * n, np.int32),
              loc_f=_shared(per_row * n, np.int32),
              at_f=_shared(chunks * F, np.int64).reshape(chunks, F),
              n_f=_shared(chunks * F, np.int64).reshape(chunks, F),
              keep=_shared(n, bool), oob_sum=_shared(n, np.float64),
              oob_cnt=_shared(n, np.float64),
              n_live=_shared(chunks, np.int64))
    _W["oob_sum"][:] = 0.0
    _W["oob_cnt"][:] = 0.0
    pool = multiprocessing.get_context("fork").Pool(
        min(PROCS, os.cpu_count() or 1)) if chunks > 1 else _Here()

    worst = dict(gain_gap=0.0, leaf_gap=0.0, leaf_rows_gap=0.0)
    mtries_gap = 0
    reached = needed = leaves = 0
    grown = {}
    is_cat = np.asarray(d.is_cat)

    for t, (kb, kt) in enumerate(replay_keys(seed, T)):
        keep = bag_of(kb, float(p["sample_rate"]), npad, n)
        _W["keep"][:] = keep
        tree = follow["trees"][f"t{t}"] if follow is not None else None
        made = []                       # own mode: per-level node arrays
        # per slice: the rows not in a leaf yet and their node in the level
        _W["rows"][:] = np.arange(n, dtype=np.int32)
        _W["loc"][:] = 0
        _W["n_live"][:] = np.diff(cuts_at)
        path = np.zeros(1, np.int64)
        lo = 0                          # follow: the level's list offset
        tw = rnd(np.array([float(keep.sum())]))
        ty = rnd(np.array([float(d.y[keep].sum())]))
        for level in range(D + 1):
            L = path.shape[0]
            if L == 0:
                break
            if follow is not None:
                sl = slice(lo, lo + L)
                assert np.array_equal(tree["level"][sl],
                                      np.full(L, level)), "level order"
                assert np.array_equal(tree["path"][sl], path), "paths"
                is_split = tree["is_split"][sl].copy()
                feat = tree["feat"][sl].astype(np.int16)
                value = tree["value"][sl]
                words = tree["left_words"][sl]
            else:
                is_split = np.zeros(L, bool)
                feat = np.zeros(L, np.int16)
                value = np.full(L, np.inf, np.float32)
                words = np.zeros((L, W), np.uint32)
            ref_best = np.full(L, -np.inf)
            if level < D:
                mask = draws(kt, level, path, F, mtries)
                pool.map(_t_in_bag, [(c, mask) for c in range(chunks)])
                # a categorical column's nodes are ordered one by one:
                # its work is cut into runs of nodes, the heaviest first
                per_f = pool.map(_t_feature, [
                    (f, np.ascontiguousarray(mask[:, f]), part, parts,
                     min_rows, follow is None, bf16)
                    for parts, f in sorted(
                        ((CAT_PARTS if d.is_cat[f] and chunks > 1 else 1, f)
                         for f in range(F)), reverse=True)
                    for part in range(parts)], chunksize=1)
                per_f.sort(key=lambda got: got[0])   # ties: the lowest column
                best_f = np.zeros(L, np.int16)
                for f, nodes, g, _ in per_f:
                    better = g > ref_best[nodes]
                    ref_best[nodes[better]] = g[better]
                    best_f[nodes[better]] = f
                if np.any(ref_best > msi):
                    needed = max(needed, level + 1)
                if follow is None:
                    is_split = ref_best > msi
                    feat = np.where(is_split, best_f, 0).astype(np.int16)
                    for f, nodes, _, left in per_f:
                        if left is None:
                            continue
                        mine = is_split[nodes] & (feat[nodes] == f)
                        at, lm = nodes[mine], left[mine]
                        if d.is_cat[f]:
                            bits = np.zeros((at.shape[0], W * 32), bool)
                            bits[:, : d.nb[f]] = lm
                            words[at] = (
                                bits.reshape(-1, W, 32).astype(np.uint32)
                                << np.arange(32, dtype=np.uint32)).sum(
                                    axis=2, dtype=np.uint32)
                        elif at.size:
                            value[at] = d.cuts[f][lm.sum(axis=1) - 1]
                else:
                    off = is_split & ~mask[np.arange(L), feat]
                    mtries_gap += int(off.sum())
                del per_f

            # ---- the level's leaves -----------------------------------
            leaf = ~is_split
            with np.errstate(divide="ignore", invalid="ignore"):
                ref_leaf = np.where(tw > 0, ty / tw, 0.0)
            if follow is not None:
                val = tree["leaf"][lo:lo + L].astype(np.float64)
                if leaf.any():
                    worst["leaf_gap"] = max(worst["leaf_gap"], float(
                        np.max(np.abs(val - ref_leaf)[leaf])))
                    worst["leaf_rows_gap"] = max(
                        worst["leaf_rows_gap"], float(np.max(np.abs(
                            tree["leaf_rows"][lo:lo + L] - tw)[leaf])) / n)
                first = np.where(is_split, tree["child"][lo:lo + L]
                                 - (lo + L), 0)
            else:
                val = ref_leaf
            leaves += int(leaf.sum())
            if is_split.any():
                reached = max(reached, level + 1)

            # ---- route the rows of the nodes that split -----------------
            kid = (np.cumsum(is_split) - 1).astype(np.int32)  # rank of split
            if follow is not None:
                assert np.array_equal(first[is_split], 2 * kid[is_split]), \
                    "children in the order their parents split"
            L2 = 2 * int(is_split.sum())
            parts = pool.map(_t_route, [
                (c, leaf, val, feat, kid, value, words, L2)
                for c in range(chunks)])
            tw2 = rnd(np.sum([a for a, _ in parts], axis=0, dtype=np.float64))
            ty2 = rnd(np.sum([b for _, b in parts], axis=0))
            del parts
            nxt_path = np.empty(L2, np.int64)
            nxt_path[0::2] = 2 * path[is_split]
            nxt_path[1::2] = 2 * path[is_split] + 1

            # ---- how far each node's choice lies under the best --------
            if follow is not None and level < D:
                wl, wr = tw2[0::2], tw2[1::2]
                yl, yr = ty2[0::2], ty2[1::2]
                with np.errstate(divide="ignore", invalid="ignore"):
                    g = yl * yl / wl + yr * yr / wr \
                        - (ty * ty / tw)[is_split]
                    sse = ty - ty * ty / tw     # of a 0/1 response
                g = np.where((wl >= min_rows) & (wr >= min_rows)
                             & np.isfinite(g), g, -np.inf)
                got = np.full(L, msi)
                got[is_split] = g
                had = ref_best > msi
                if had.any():
                    worst["gain_gap"] = max(worst["gain_gap"], float(np.max(
                        (ref_best[had] - got[had]) / sse[had])))
            if follow is None:
                made.append(dict(
                    level=np.full(L, level, np.int8),
                    path=path.astype(np.int32), is_split=is_split,
                    feat=feat, cat_split=is_split & is_cat[feat],
                    value=value, na_left=np.zeros(L, bool),
                    left_words=words,
                    first=np.where(is_split, 2 * kid, -1),
                    leaf=val.astype(np.float32), leaf_rows=tw))
            lo += L
            path, tw, ty = nxt_path, tw2, ty2
        if follow is None:
            starts = np.cumsum([0] + [m["path"].shape[0] for m in made])
            for m, s in zip(made, starts[1:]):
                m["child"] = np.where(m["first"] >= 0, m.pop("first") + s,
                                      -1).astype(np.int32)
            grown[f"t{t}"] = {k: np.concatenate([m[k] for m in made])
                              for k in made[0]}
    pool.close()
    pool.join()
    oob_sum, oob_cnt = _W["oob_sum"].copy(), _W["oob_cnt"].copy()
    _W.clear()

    # ---- out-of-bag metrics ---------------------------------------------
    seen = oob_cnt > 0
    p1 = np.clip(oob_sum[seen] / oob_cnt[seen], 0.0, 1.0)
    pc = np.clip(p1.astype(np.float32), np.float32(1e-7),
                 np.float32(1 - 1e-7))
    ys = d.y[seen]
    ll = -(ys * np.log(pc.astype(np.float64)) + (1 - ys)
           * np.log((np.float32(1) - pc).astype(np.float64)))
    metrics = {"logloss": float(ll.mean()), "AUC": auc(p1, ys),
               "MSE": float(np.mean((p1 - ys) ** 2))}
    if follow is None:
        return None, {"trees": grown, "names": list(d.names),
                      "rows_padded": npad, "seed": seed, "metrics": metrics}
    got = follow["metrics"]
    numbers = {
        "depth_gap": float(max(0, needed - reached)),
        "mtries_gap": float(mtries_gap),
        "oob_logloss_gap": abs(got["logloss"] - metrics["logloss"])
        / metrics["logloss"],
        "oob_auc_gap": abs(got["AUC"] - metrics["AUC"]),
        "_depth_reached": float(reached),
        "_leaves": float(leaves),
    }
    numbers.update(worst)
    return numbers, None


def check(data: dict, outputs: dict, params: dict) -> dict:
    """The numbers compared for one finished job: ``{name: value}``."""
    d = _Data(data, params, names=outputs["names"])
    return _walk(d, params, outputs, bf16=False)[0]


def control(data: dict, params: dict) -> dict:
    """The lower-precision control, in the adapter's format: the
    reference's own forest grown with every sum held in bfloat16."""
    return _walk(_Data(data, params), params, None, bf16=True)[1]
