"""Plain reference for the DeepLearning fit the configuration states: a
multilayer perceptron trained by mini-batch ADADELTA, replayed step by
step. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: one jitted function per
step, no scan over steps, no masking, no kernels; imports nothing of the
program. It keeps the pixel matrix as the integers it was generated as
(uint8, 0.8 GB at 1,048,576 x 784) and standardises a block of rows when
it reads it, so it fits beside whatever the program has not released.

What is written down here, each in its own lines below:

* **Standardisation** (``standardise``): each column minus its mean over
  its sample standard deviation (``n - 1``), both from float64 sums over
  the real rows. A constant column has deviation 0; the program's rule
  is to divide by 1 then, so the column becomes all zeros, and this file
  follows it. There are no missing values; rows beyond ``n`` (padding)
  are zeros with weight 0.
* **Initial weights** (``initial_weights``): layer ``l`` uniform in
  ±sqrt(6 / (fan_in + fan_out)), biases 0, from ``jax.random`` keys
  derived from the job's seed by the recipe the configuration states
  under ``assumed``: ``PRNGKey(seed)`` → ``split`` → second key → one
  ``split`` a layer, the second half drawing the layer.
* **One step** ``t`` (``Replay.step``): rows ``[start, start + B)``,
  ``start = (t · B) mod n`` clamped to ``N - B`` (``N`` the padded row
  count; what ``dynamic_slice`` does at the tail — a departure from
  "every row once an epoch" for the last ``n mod B`` rows, which is 0 at
  the configuration's size); ``z1 = X W1 + b1``, ``h1 = max(z1, 0)``,
  ``z2 = h1 W2 + b2``, ``h2 = max(z2, 0)``, ``o = h2 W3 + b3``;
  ``loss = Σ w·(−log softmax(o)[y]) / Σ w``; gradients by ``jax.grad``;
  ADADELTA for every weight and bias: ``Eg² ← ρ Eg² + (1−ρ) g²``,
  ``Δ = −sqrt(EΔ² + ε) / sqrt(Eg² + ε) · g``,
  ``EΔ² ← ρ EΔ² + (1−ρ) Δ²``, ``θ ← θ + Δ``.
* ``total_steps = floor(epochs · n / B)`` steps; ``B`` is
  ``mini_batch_size``, or for ``mini_batch_size`` 1 the program's
  default, restated in ``default_batch``.
* The loss over all rows at the steps where the job scored, the final
  weights, and logloss and classification error on the fixed block of
  rows ``[0, 65,536)`` — for the replay's weights and for the job's
  weights, both through this file's forward pass (never the job's own
  10,000-row sample metric, whose rows the program picks).

Departures from upstream H2O, also in the configuration's ``assumed``:
mini-batches of contiguous rows with one synchronous update each, in
place of HOGWILD row-at-a-time updates with model averaging between
nodes (the program's design, ``models/deeplearning.py``; why upstream's
2.1% test error on the real images is no limit here); ``jax.random`` in
place of Java's generator; the generated pixels in place of the images.

What ``check`` returns: ``loss_gap`` (worst relative gap of the job's
scored losses to the replay's at the same steps; infinite where the job
did not score its last step), ``weight_gap`` (worst layer's
‖θ − θ_ref‖₂ / ‖θ_ref‖₂ over its weights and biases — a norm, not a
per-element maximum: ADADELTA's first steps are ±1e-3 by the gradient's
sign alone, so single elements flip on rounding), ``logloss_gap`` and
``error_gap`` (absolute, on the fixed block), ``steps_gap`` (the job's
effective steps against ``total_steps``), and counted facts under
``_``-keys. ``control`` is the same replay one precision down: weights,
biases and both ADADELTA moments are kept in bfloat16 between steps.
"""

from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "weight_gap", "logloss_gap", "error_gap", "steps_gap")
FIXED_BLOCK = 65536         # rows [0, FIXED_BLOCK): logloss and error
LOSS_BLOCK = 65536          # rows a full-data loss reads at a time


def default_batch(n: int, padded: int) -> int:
    """The program's batch for ``mini_batch_size`` 1: rows / 64 between
    256 and 16,384, at least 16 steps an epoch on small frames, lowered
    to a power of two."""
    b = min(16384, max(256, n // 64), padded)
    b = min(b, max(32, n // 16))
    return 1 << (b.bit_length() - 1)


def matrix(data: dict):
    """``(X [n, P] as generated, y [n] int32)``, row-major, in blocks."""
    cols, resp = data["columns"], data["response"]
    names = [c for c in cols if c != resp]
    n = len(cols[resp])
    X = np.empty((n, len(names)), cols[names[0]].dtype)
    for lo in range(0, n, 65536):
        hi = min(lo + 65536, n)
        X[lo:hi] = np.stack([cols[c][lo:hi] for c in names], axis=1)
    return X, np.asarray(cols[resp], np.int32)


def standardise(X: np.ndarray):
    """``(mean, deviation)`` of each column as float32, from float64
    sums in two passes; deviation 1 where the column is constant."""
    n = X.shape[0]
    total = np.zeros(X.shape[1], np.float64)
    for lo in range(0, n, 16384):
        total += X[lo:lo + 16384].sum(axis=0, dtype=np.float64)
    mean = total / n
    sq = np.zeros(X.shape[1], np.float64)
    for lo in range(0, n, 16384):
        d = X[lo:lo + 16384].astype(np.float64) - mean
        sq += (d * d).sum(axis=0)
    sd = np.sqrt(sq / max(n - 1, 1))
    sd[sd == 0] = 1.0
    return mean.astype(np.float32), sd.astype(np.float32)


def initial_weights(seed: int, sizes):
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(int(seed))
    _, key = jax.random.split(key)
    theta = []
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        key, sub = jax.random.split(key)
        lim = math.sqrt(6.0 / (fan_in + fan_out))
        theta.append({
            "W": jax.random.uniform(sub, (fan_in, fan_out), jnp.float32,
                                    -lim, lim),
            "b": jnp.zeros((fan_out,), jnp.float32)})
    return theta


def forward(theta, X):
    import jax.numpy as jnp
    h = X
    for layer in theta[:-1]:
        h = jnp.maximum(h @ layer["W"] + layer["b"], 0.0)
    return h @ theta[-1]["W"] + theta[-1]["b"]


def weighted_nll(theta, X, y, w):
    """Σ w·(−log softmax(o)[y]) over the rows given."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(forward(theta, X), axis=1)
    return -jnp.sum(w * jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0])


class Replay:
    """The data on the device as generated, and the jitted pieces."""

    def __init__(self, data: dict, params: dict):
        import jax
        import jax.numpy as jnp
        X, y = matrix(data)
        self.n, self.inputs = X.shape
        self.padded = int(params.get("padded_rows") or self.n)
        mean, sd = standardise(X)
        pad = self.padded - self.n
        # the data, handed to every jitted piece as an argument (a closed-
        # over array would be baked into each program as a constant)
        self.d = {"X": jnp.asarray(np.pad(X, ((0, pad), (0, 0)))
                                   if pad else X),
                  "y": jnp.asarray(np.pad(y, (0, pad))),
                  "w": jnp.asarray(np.pad(np.ones(self.n, np.float32),
                                          (0, pad))),
                  "mean": jnp.asarray(mean), "sd": jnp.asarray(sd)}
        self.classes = len(data["domains"][data["response"]])
        self.sizes = [self.inputs] + [int(h) for h in params["hidden"]] \
            + [self.classes]
        batch = int(params.get("mini_batch_size", 1))
        self.batch = batch if batch > 1 else default_batch(self.n,
                                                           self.padded)
        self.total_steps = max(1, int(float(params["epochs"]) * self.n
                                      / self.batch))
        self.rho = float(params["rho"])
        self.eps = float(params["epsilon"])

        def rows(d, start, count):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, start, count)
            xb = (cut(d["X"]).astype(jnp.float32) - d["mean"]) / d["sd"]
            wb = cut(d["w"])
            return jnp.where(wb[:, None] > 0, xb, 0.0), cut(d["y"]), wb

        def step(d, theta, state, start, *, keep):
            xb, yb, wb = rows(d, start, self.batch)
            grads = jax.grad(lambda q: weighted_nll(q, xb, yb, wb)
                             / jnp.maximum(jnp.sum(wb), 1e-12))(theta)

            def adadelta(p, g, s):
                eg2 = self.rho * s["eg2"] + (1 - self.rho) * g * g
                delta = -jnp.sqrt(s["ed2"] + self.eps) \
                    / jnp.sqrt(eg2 + self.eps) * g
                ed2 = self.rho * s["ed2"] + (1 - self.rho) * delta * delta
                return p + delta, {"eg2": eg2, "ed2": ed2}

            out = [{k: adadelta(p[k], g[k], s[k]) for k in ("W", "b")}
                   for p, g, s in zip(theta, grads, state)]
            # ``reduce_precision`` and not a pair of casts: the chip's
            # compiler may keep the excess precision of a cast down and
            # straight back up, and then the control is the replay itself
            bits = {"float32": (8, 23), "bfloat16": (8, 7)}[keep]
            held = lambda a: jax.lax.reduce_precision(a, *bits)  # noqa: E731
            return (jax.tree_util.tree_map(
                        held, [{k: v[0] for k, v in l.items()} for l in out]),
                    jax.tree_util.tree_map(
                        held, [{k: v[1] for k, v in l.items()} for l in out]))

        def block_nll(d, theta, start, *, count):
            xb, yb, wb = rows(d, start, count)
            return weighted_nll(theta, xb, yb, wb), jnp.sum(wb)

        def block_errors(d, theta, start, *, count):
            xb, yb, wb = rows(d, start, count)
            wrong = jnp.argmax(forward(theta, xb), axis=1) != yb
            return jnp.sum(wb * wrong)

        self.step = jax.jit(step, static_argnames=("keep",))
        self.block_nll = jax.jit(block_nll, static_argnames=("count",))
        self.block_errors = jax.jit(block_errors, static_argnames=("count",))

    def start_of(self, t: int) -> int:
        return min((t * self.batch) % self.n, self.padded - self.batch)

    def loss(self, theta) -> float:
        """Σ w·nll / Σ w over all rows; block sums added in float64."""
        num = den = 0.0
        for lo in range(0, self.padded, LOSS_BLOCK):
            part, ws = self.block_nll(
                self.d, theta, lo, count=min(LOSS_BLOCK, self.padded - lo))
            num, den = num + float(part), den + float(ws)
        return num / max(den, 1e-12)

    def fixed_block(self, theta):
        """``(logloss, error)`` on rows ``[0, FIXED_BLOCK)``."""
        count = min(FIXED_BLOCK, self.n)
        part, ws = self.block_nll(self.d, theta, 0, count=count)
        wrong = self.block_errors(self.d, theta, 0, count=count)
        return float(part) / float(ws), float(wrong) / float(ws)

    def run(self, seed: int, score_steps, keep: str = "float32") -> dict:
        """``total_steps`` steps from the seed's initial weights; the
        state is held as ``keep`` between steps."""
        import jax
        import jax.numpy as jnp
        theta = initial_weights(seed, self.sizes)
        zeros = lambda a: {"eg2": jnp.zeros_like(a),       # noqa: E731
                           "ed2": jnp.zeros_like(a)}
        state = [{k: zeros(l[k]) for k in ("W", "b")} for l in theta]
        want, losses = set(int(s) for s in score_steps), {}
        for t in range(self.total_steps):
            theta, state = self.step(self.d, theta, state,
                                     self.start_of(t), keep=keep)
            if t + 1 in want:
                losses[t + 1] = self.loss(theta)
        return {"theta": jax.device_get(theta), "losses": losses}


def weights_of(outputs: dict):
    """The job's layers in order, from ``{"weights": {"0": W, ...},
    "biases": {"0": b, ...}}``."""
    import jax.numpy as jnp
    order = sorted(outputs["weights"], key=int)
    return [{"W": jnp.asarray(outputs["weights"][i], jnp.float32),
             "b": jnp.asarray(outputs["biases"][i], jnp.float32)}
            for i in order]


def by_layer(theta, key: str) -> dict:
    return {str(i): np.asarray(l[key], np.float32)
            for i, l in enumerate(theta)}


def check(data: dict, outputs: dict, params: dict) -> dict:
    import jax
    with jax.default_matmul_precision("highest"):
        rp = Replay(data, params)
        steps = [int(s) for s in outputs["score_steps"]]
        ref = rp.run(int(outputs["seed"]), steps)
        got = weights_of(outputs)
        loss_gap = float("inf")
        if steps and steps[-1] == int(outputs["steps"]) and \
                all(s in ref["losses"] for s in steps):
            loss_gap = max(abs(v - ref["losses"][s]) / ref["losses"][s]
                           for s, v in zip(steps, outputs["score_losses"]))
        weight_gap = float("inf")
        shapes = [(l["W"].shape, l["b"].shape) for l in ref["theta"]]
        if [(l["W"].shape, l["b"].shape) for l in got] == shapes:
            flat = lambda l: np.concatenate(           # noqa: E731
                [np.asarray(l["W"], np.float64).ravel(),
                 np.asarray(l["b"], np.float64).ravel()])
            weight_gap = max(
                float(np.linalg.norm(flat(g) - flat(r))
                      / np.linalg.norm(flat(r)))
                for g, r in zip(got, ref["theta"]))
            ll, err = rp.fixed_block(got)
        else:
            ll = err = float("inf")
        ref_ll, ref_err = rp.fixed_block(ref["theta"])
    return {"loss_gap": loss_gap, "weight_gap": weight_gap,
            "logloss_gap": abs(ll - ref_ll), "error_gap": abs(err - ref_err),
            "steps_gap": float(abs(int(outputs["steps"]) - rp.total_steps)),
            "_steps": rp.total_steps, "_batch": rp.batch,
            "_weights": sum(a * b + b for a, b in
                            zip(rp.sizes[:-1], rp.sizes[1:])),
            "_ref_error": ref_err, "_ref_logloss": ref_ll}


def control(data: dict, params: dict) -> dict:
    """What a fit one precision down hands to ``check``: the replay
    with weights, biases and both ADADELTA moments kept in bfloat16
    between steps (products still float32 at ``highest``), scored at
    every tenth of the fit and at its end."""
    import jax
    seed = int(params.get("seed", 1))
    with jax.default_matmul_precision("highest"):
        rp = Replay(data, params)
        tenth = max(1, -(-rp.total_steps // 10))
        steps = sorted({min(s, rp.total_steps) for s in
                        range(tenth, rp.total_steps + tenth, tenth)})
        out = rp.run(seed, steps, keep="bfloat16")
    return {"seed": seed, "steps": rp.total_steps,
            "weights": by_layer(out["theta"], "W"),
            "biases": by_layer(out["theta"], "b"),
            "score_steps": steps,
            "score_losses": [out["losses"][s] for s in steps]}
