"""Plain reference for ``Incremental(SGDClassifier(loss="log_loss",
penalty="l2"))`` trained by ``partial_fit`` passes over a table's blocks.

The blocks are rows ``[k c, min((k + 1) c, n))`` for ``c = chunk_size``,
visited on every pass in the order ``numpy.random.RandomState(
random_state)`` shuffles their list (in their own order where
``shuffle_blocks`` is false).  For block ``b`` with real rows ``R_b``,
``n_b = |R_b|``, ``s_i = +1`` where ``y_i`` is the second class and
``-1`` where it is the first, and the state ``(w, c, t)``:

    m_i   = x_i . w + c
    g_w   = (1/n_b) sum_{i in R_b} -s_i sigma(-s_i m_i) x_i + alpha w
    g_c   = (1/n_b) sum_{i in R_b} -s_i sigma(-s_i m_i)
    eta_t = 1 / (alpha (t0 + t)),   t0 = 1 / (alpha eta0)
    w <- w - eta_t g_w,   c <- c - eta_t g_c,   t <- t + 1

one step a block (the package's minibatch semantics: scikit-learn's SGD
takes one a row), from ``w = 0, c = 0, t = 0``, the state carried from
pass to pass.

One block at a time on the device: the step takes its block's rows out
of the table, whole blocks and the last, short one each at its own
length, and computes in float32 with both products at ``highest``.
Imports nothing of ``dask_ml_tpu`` and takes nothing it made.  Written
for a table on one chip, as the cell's is.

``precision="float32"`` is the reference.  ``precision="bfloat16"`` is
the control: the same passes with the block, the state and the
residuals rounded to bfloat16 before each product and the state after
each step (float32 accumulation), by ``lax.reduce_precision``, which no
compiler may drop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST

#: what the equations above state; any other argument is another model
_STATED = {"loss": "log_loss", "penalty": "l2", "learning_rate": "optimal",
           "fit_intercept": True}


def _bf16(x):
    """Round to bfloat16's 8 exponent and 7 mantissa bits, kept float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("size", "bf16"))
def _step(w, c, t, X, y, start, positive, alpha, t0, *, size, bf16):
    xb = jax.lax.dynamic_slice_in_dim(X, start, size, 0)
    s = jnp.where(jax.lax.dynamic_slice_in_dim(y, start, size, 0) == positive,
                  1.0, -1.0)
    if bf16:
        xb, w, c = _bf16(xb), _bf16(w), _bf16(c)
    m = jnp.dot(xb, w, precision=_HI) + c
    r = -s * jax.nn.sigmoid(-s * m)
    if bf16:
        r = _bf16(r)
    g_w = jnp.dot(r, xb, precision=_HI) / size + alpha * w
    g_c = jnp.sum(r) / size
    eta = 1.0 / (alpha * (t0 + t))
    w, c = w - eta * g_w, c - eta * g_c
    if bf16:
        w, c = _bf16(w), _bf16(c)
    return w, c, t + 1.0


def blocks(n: int, est_args: dict):
    """The blocks ``(lo, hi)`` of one pass, in their visit order."""
    chunk = int(est_args["chunk_size"])
    spans = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]
    if est_args.get("shuffle_blocks", True):
        np.random.RandomState(int(est_args["random_state"])).shuffle(spans)
    return spans


def _model(est_args: dict):
    args = dict(est_args["estimator"])
    for key, value in _STATED.items():
        if args.pop(key, value) != value:
            raise ValueError(f"the reference states {key}={value!r}")
    alpha, eta0 = float(args.pop("alpha", 1e-4)), float(args.pop("eta0", 0.01))
    if args:
        raise ValueError(f"the reference does not state {sorted(args)}")
    return alpha, 1.0 / (alpha * eta0)


def build(data, est_args: dict, precision: str = "float32"):
    """The state ``passes`` passes leave: ``w``, ``c``, ``t``."""
    bf16 = {"float32": False, "bfloat16": True}[precision]
    X, y = data["X"], data["y"]
    alpha, t0 = _model(est_args)
    positive = jnp.float32(est_args["classes"][1])
    w, c, t = (jnp.zeros((X.shape[1],), jnp.float32), jnp.float32(0.0),
               jnp.float32(0.0))
    order = blocks(X.shape[0], est_args)
    with jax.default_matmul_precision("highest"):
        for _ in range(int(est_args["passes"])):
            for lo, hi in order:
                w, c, t = _step(w, c, t, X, y, jnp.int32(lo), positive,
                                jnp.float32(alpha), jnp.float32(t0),
                                size=hi - lo, bf16=bf16)
    return {"w": np.asarray(w, np.float64), "c": float(c), "t": float(t)}


def control_estimator(precision: str):
    """The reference in ``precision`` in the shape of the cell's
    estimator, which ``control_sgd.py`` puts in the program's place under
    the timed path: ``fit`` takes the program's row-sharded table
    (``.data``, ``.n_samples``) and leaves the attributes the harness
    fetches."""

    class Control:
        def __init__(self, **est_args):
            self.est_args = est_args

        def fit(self, X, y):
            n = X.n_samples
            data = {"X": X.data if X.data.shape[0] == n else X.data[:n],
                    "y": y.data if y.data.shape[0] == n else y.data[:n]}
            ref = build(data, self.est_args, precision)
            self.coef_ = ref["w"][None, :].astype(np.float32)
            self.intercept_ = np.array([ref["c"]], np.float32)
            self.t_ = ref["t"]
            self.n_passes_ = int(self.est_args["passes"])
            return self

    return Control


def compare(ref, data, answer: dict, last: dict) -> dict:
    """Numbers compared for one fitted answer (smaller is closer):
    ``coef_gap``, the largest distance of a weight or the intercept from
    the reference's over the reference's largest; ``steps_gap``, the
    steps taken less the reference's (one a block a pass)."""
    beta = np.concatenate([np.ravel(answer["coef_"]),
                           np.ravel(answer["intercept_"])]).astype(np.float64)
    best = np.concatenate([ref["w"], [ref["c"]]])
    if beta.shape != best.shape or not np.isfinite(beta).all():
        return {"coef_gap": float("inf"), "steps_gap": float("inf")}
    return {"coef_gap": float(np.abs(beta - best).max() / np.abs(best).max()),
            "steps_gap": abs(float(answer["t_"]) - ref["t"])}
