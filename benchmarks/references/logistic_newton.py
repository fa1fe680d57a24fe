"""Plain reference for the L2-regularised logistic fit.

The objective ``LogisticRegression(penalty="l2", C=C)`` states, written
out::

    F(beta) = sum_i [log(1 + exp(eta_i)) - y_i * eta_i] + |beta|^2 / (2 C)
    eta = X w + b,   beta = (w, b)   (the intercept is penalised too,
                                      as the ones-column form does)

minimised by damped Newton steps.  Value, gradient and Hessian are taken
over row blocks so that nothing of the table's size is ever made beside
the table; the 29x29 system is solved on the host in float64.  Imports
nothing of ``dask_ml_tpu`` and takes nothing that it made.

``precision="float32"`` is the reference (float32, every product at
``highest``).  ``precision="bfloat16"`` is the control: the same solve
with the table, the parameters and the residuals rounded to bfloat16
before every product (float32 accumulation), the one-pass arithmetic a
later PR would be tempted by.  The rounding is ``lax.reduce_precision``,
which no compiler may drop (a plain ``astype`` pair is elided on the TPU
under XLA's excess-precision rule: measured, PR 25).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1_250_000  # per chip
_HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    """Round to bfloat16's 8 exponent and 7 mantissa bits, kept float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _local_pass(X, y, beta, *, bf16: bool, block: int):
    """Loss, gradient and Hessian summed over this device's rows, one
    block of rows after the other (a loop, so one block's temporaries are
    all that is ever made beside the table)."""
    d = X.shape[1]
    nb, tail = divmod(X.shape[0], block)

    def one(acc, start, size):
        xb = jax.lax.dynamic_slice_in_dim(X, start, size, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, start, size, 0)
        xi = jnp.concatenate([xb, jnp.ones((size, 1), xb.dtype)], axis=1)
        if bf16:
            xi = _bf16(xi)
        eta = jnp.dot(xi, _bf16(beta) if bf16 else beta, precision=_HI)
        p = jax.nn.sigmoid(eta)
        r, s = p - yb, p * (1.0 - p)
        if bf16:
            r = _bf16(r)
        f, g, H = acc
        return (f + jnp.sum(jnp.logaddexp(0.0, eta) - yb * eta),
                g + jnp.dot(xi.T, r, precision=_HI),
                H + jnp.dot((xi * s[:, None]).T, xi, precision=_HI))

    acc = (jnp.zeros((), jnp.float32), jnp.zeros((d + 1,), jnp.float32),
           jnp.zeros((d + 1, d + 1), jnp.float32))
    if nb:
        acc = jax.lax.fori_loop(
            0, nb, lambda i, a: one(a, i * block, block), acc)
    if tail:
        acc = one(acc, nb * block, tail)
    return acc


@partial(jax.jit, static_argnames=("bf16", "mesh", "block"))
def _pass(X, y, beta, *, bf16: bool, mesh, block: int = BLOCK_ROWS):
    """Sum over all rows of the loss, its gradient and its Hessian at
    beta.  The table is row-sharded over ``mesh``'s one axis: every chip
    walks its own rows block by block, and one ``psum`` adds them up."""
    axis = mesh.axis_names[0]
    rows = jax.sharding.PartitionSpec(axis)

    def local(xs, ys, b):
        return jax.lax.psum(
            _local_pass(xs, ys, b, bf16=bf16, block=block), axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(rows, rows, jax.sharding.PartitionSpec()),
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False)(X, y, beta)  # the loop's carry starts unvarying


def _objective(X, y, beta, lam, bf16):
    f, g, H = _pass(X, y, jnp.asarray(beta, jnp.float32), bf16=bf16,
                    mesh=X.sharding.mesh)
    beta = np.asarray(beta, np.float64)
    f = float(f) + 0.5 * lam * float(beta @ beta)
    g = np.asarray(g, np.float64) + lam * beta
    H = np.asarray(H, np.float64) + lam * np.eye(beta.size)
    return f, g, H


def solve(X, y, C: float, *, bf16: bool = False, max_steps: int = 40):
    """Minimise F; returns beta (features + 1,) as float64 on the host."""
    lam = 1.0 / float(C)
    beta = np.zeros(X.shape[1] + 1)
    f, g, H = _objective(X, y, beta, lam, bf16)
    for _ in range(max_steps):
        step = np.linalg.solve(H, g)
        t = 1.0
        while True:
            cand = beta - t * step
            f_c, g_c, H_c = _objective(X, y, cand, lam, bf16)
            if f_c <= f + 1e-6 * abs(f) or t < 1e-3:
                break
            t *= 0.5
        moved = np.linalg.norm(cand - beta) / max(np.linalg.norm(cand), 1e-30)
        beta, f, g, H = cand, f_c, g_c, H_c
        if moved < 1e-7:
            break
    return beta


def build(data, est_args: dict, precision: str = "float32"):
    """The reference's answer for this table, and what ``compare`` needs."""
    bf16 = {"float32": False, "bfloat16": True}[precision]
    X, y = data["X"], data["y"]
    C = float(est_args.get("C", 1.0))
    beta = solve(X, y, C, bf16=bf16)
    _, g0, _ = _objective(X, y, np.zeros_like(beta), 1.0 / C, False)
    return {"beta": beta, "C": C, "grad0_norm": float(np.linalg.norm(g0))}


def control_estimator(precision: str):
    """The reference in ``precision`` in the shape of an estimator, which
    ``control.py`` puts in the program's place under the timed path: ``fit``
    takes the program's row-sharded table (``.data``, ``.n_samples``: the
    rows as ``shard_rows`` laid them out) and leaves the attributes a fit
    leaves."""

    class Control:
        def __init__(self, **est_args):
            self.est_args = est_args

        def fit(self, X, y):
            n = X.n_samples
            data = {"X": X.data if X.data.shape[0] == n else X.data[:n],
                    "y": y.data if y.data.shape[0] == n else y.data[:n]}
            beta = build(data, self.est_args, precision)["beta"]
            self.coef_ = beta[:-1].astype(np.float32)
            self.intercept_ = np.float32(beta[-1])
            self.n_iter_ = 0
            return self

    return Control


def compare(ref, data, answer: dict, last: dict) -> dict:
    """Numbers compared for one fitted answer (smaller is closer)."""
    beta = np.concatenate([np.asarray(answer["coef_"], np.float64).ravel(),
                           [float(answer["intercept_"])]])
    bad = {"newton_gap": float("inf"), "grad_gap": float("inf")}
    if beta.shape != ref["beta"].shape or not np.isfinite(beta).all():
        return bad
    _, g, H = _objective(data["X"], data["y"], beta, 1.0 / ref["C"], False)
    step = np.linalg.solve(H, g)  # the Newton step back to the optimum
    return {
        # that step's length in the loss's own metric (the Newton
        # decrement, sqrt(g' H^-1 g)) against the optimum's length in it:
        # twice the excess loss, as a relative distance.  Unlike the plain
        # distance it does not grow along directions the loss barely sees.
        "newton_gap": float(np.sqrt(max(g @ step, 0.0))
                            / np.sqrt(ref["beta"] @ H @ ref["beta"])),
        # the reference's gradient at the program's answer (nought at the
        # optimum), against its gradient at the start, beta = 0
        "grad_gap": float(np.linalg.norm(g) / ref["grad0_norm"]),
    }

