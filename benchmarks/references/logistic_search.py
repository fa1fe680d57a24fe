"""Plain reference for a cross-validated search over ``C`` of the
L2-regularised logistic fit, and the refit of the ``C`` it chose.

The search ``GridSearchCV(LogisticRegression(C=C), {"C": [...]}, cv=k)``
states, written out.  The folds are contiguous: with ``b = linspace(0, n,
k + 1)`` cut to integers, fold ``i`` holds out rows ``[b[i], b[i + 1])``
and trains on the others.  For each fold and each ``C`` the objective

    F(beta) = sum_{i in train} [log(1 + exp(eta_i)) - y_i * eta_i]
              + |beta|^2 / (2 C),      eta = X w + b,  beta = (w, b)

(the intercept is penalised too) is minimised by damped Newton steps,
warm-started along the grid (from the optimum of the next smaller ``C``:
the same optima in fewer steps); the score is the share of the held-out
rows with ``(eta_i > 0) == (y_i == 1)``.  The refit minimises ``F`` over
all rows at the ``C`` the program chose.

A fold is a pair of bounds, never a copy: every pass walks the whole
table block by block and weighs a row 1 or 0 by its index, so that
nothing of the table's size is made beside the table.  Sums are taken in
float32 at ``highest``; the 29 x 29 system is solved in float64 on the
host, and a score is a count of rows over a count of rows, in float64.
Imports nothing of ``dask_ml_tpu`` and nothing of scikit-learn's search,
and takes nothing that either made.

``precision="float32"`` is the reference.  ``precision="bfloat16"`` is
the control: the same search with the table, the parameters and the
residuals rounded to bfloat16 before every product (float32
accumulation), by ``lax.reduce_precision``, which no compiler may drop.
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


def _blocks(X, y, first_row, block, init, one):
    """``one(acc, xi, yb, row)`` over this device's rows, a block after
    the other (a loop: one block's temporaries are all that is ever made
    beside the table).  ``xi`` is the block with a column of ones, ``row``
    its rows' indices in the whole table."""
    nb, tail = divmod(X.shape[0], block)

    def at(acc, start, size):
        xb = jax.lax.dynamic_slice_in_dim(X, start, size, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, start, size, 0)
        xi = jnp.concatenate([xb, jnp.ones((size, 1), xb.dtype)], axis=1)
        return one(acc, xi, yb, first_row + start + jnp.arange(size))

    acc = init
    if nb:
        acc = jax.lax.fori_loop(
            0, nb, lambda i, a: at(a, i * block, block), acc)
    if tail:
        acc = at(acc, nb * block, tail)
    return acc


def _over_rows(local, X, y, *args, mesh):
    """``local(X_rows, y_rows, first_row, *args)`` on every chip's own
    rows of the row-sharded table, summed over the chips."""
    axis = mesh.axis_names[0]
    rows, whole = jax.sharding.PartitionSpec(axis), jax.sharding.PartitionSpec()

    def on_chip(xs, ys, *rest):
        first_row = jax.lax.axis_index(axis) * xs.shape[0]
        return jax.lax.psum(local(xs, ys, first_row, *rest), axis)

    return jax.shard_map(
        on_chip, mesh=mesh, in_specs=(rows, rows) + (whole,) * len(args),
        out_specs=whole, check_vma=False)(X, y, *args)


@partial(jax.jit, static_argnames=("bf16", "mesh", "block"))
def _pass(X, y, beta, lo, hi, *, bf16: bool, mesh, block: int = BLOCK_ROWS):
    """Loss, gradient and Hessian at beta, summed over the rows OUTSIDE
    ``[lo, hi)`` (all rows where ``lo == hi``)."""
    d = X.shape[1]

    def local(xs, ys, first_row, b, lo, hi):
        def one(acc, xi, yb, row):
            w = ((row < lo) | (row >= hi)).astype(jnp.float32)
            if bf16:
                xi = _bf16(xi)
            eta = jnp.dot(xi, _bf16(b) if bf16 else b, precision=_HI)
            p = jax.nn.sigmoid(eta)
            r, s = (p - yb) * w, p * (1.0 - p) * w
            if bf16:
                r = _bf16(r)
            f, g, H = acc
            return (f + jnp.sum((jnp.logaddexp(0.0, eta) - yb * eta) * w),
                    g + jnp.dot(xi.T, r, precision=_HI),
                    H + jnp.dot((xi * s[:, None]).T, xi, precision=_HI))

        init = (jnp.zeros((), jnp.float32), jnp.zeros((d + 1,), jnp.float32),
                jnp.zeros((d + 1, d + 1), jnp.float32))
        return _blocks(xs, ys, first_row, block, init, one)

    return _over_rows(local, X, y, beta, lo, hi, mesh=mesh)


@partial(jax.jit, static_argnames=("bf16", "mesh", "block"))
def _hits(X, y, B, lo, hi, *, bf16: bool, mesh, block: int = BLOCK_ROWS):
    """For each row of ``B`` (one beta a candidate), the number of rows
    in ``[lo, hi)`` it classifies as their label says."""

    def local(xs, ys, first_row, betas, lo, hi):
        if bf16:
            betas = _bf16(betas)

        def one(acc, xi, yb, row):
            inside = (row >= lo) & (row < hi)
            eta = jnp.dot(_bf16(xi) if bf16 else xi, betas.T,
                          precision=_HI)  # (rows, candidates)
            hit = (eta > 0) == (yb > 0.5)[:, None]
            return acc + jnp.sum(hit & inside[:, None], axis=0,
                                 dtype=jnp.int32)

        return _blocks(xs, ys, first_row, block,
                       jnp.zeros((betas.shape[0],), jnp.int32), one)

    return _over_rows(local, X, y, B, lo, hi, mesh=mesh)


def _objective(X, y, beta, lam, lo, hi, bf16):
    f, g, H = _pass(X, y, jnp.asarray(beta, jnp.float32), jnp.int32(lo),
                    jnp.int32(hi), bf16=bf16, mesh=X.sharding.mesh)
    beta = np.asarray(beta, np.float64)
    f = float(f) + 0.5 * lam * float(beta @ beta)
    g = np.asarray(g, np.float64) + lam * beta
    H = np.asarray(H, np.float64) + lam * np.eye(beta.size)
    return f, g, H


def solve(X, y, C: float, lo: int, hi: int, *, beta0=None, bf16: bool = False,
          max_steps: int = 40):
    """Minimise F over the rows outside ``[lo, hi)``, from ``beta0`` (or
    nought); returns beta (features + 1,) as float64 on the host."""
    lam = 1.0 / float(C)
    beta = (np.zeros(X.shape[1] + 1) if beta0 is None
            else np.asarray(beta0, np.float64))
    f, g, H = _objective(X, y, beta, lam, lo, hi, bf16)
    for _ in range(max_steps):
        step = np.linalg.solve(H, g)
        t = 1.0
        while True:
            cand = beta - t * step
            f_c, g_c, H_c = _objective(X, y, cand, lam, lo, hi, bf16)
            if f_c <= f + 1e-6 * abs(f) or t < 1e-3:
                break
            t *= 0.5
        moved = np.linalg.norm(cand - beta) / max(np.linalg.norm(cand), 1e-30)
        beta, f, g, H = cand, f_c, g_c, H_c
        if moved < 1e-7:
            break
    return beta


def fold_bounds(n: int, folds: int):
    """The rule, stated here and nowhere borrowed: thirds (or k-ths) of
    the rows by ``linspace`` cut to integers."""
    edges = np.linspace(0, n, folds + 1).astype(np.int64)
    return list(zip(edges[:-1].tolist(), edges[1:].tolist()))


def build(data, est_args: dict, precision: str = "float32"):
    """The reference's search on this table: every split's score, their
    means, the fold optima, and what ``compare`` needs for the refit."""
    bf16 = {"float32": False, "bfloat16": True}[precision]
    X, y = data["X"], data["y"]
    Cs = [float(c) for c in est_args["param_grid"]["C"]]
    bounds = fold_bounds(X.shape[0], int(est_args["cv"]))
    order = np.argsort(Cs)  # warm starts run from the smallest C up
    scores = np.zeros((len(Cs), len(bounds)))
    betas = np.zeros((len(bounds), len(Cs), X.shape[1] + 1))
    for fi, (lo, hi) in enumerate(bounds):
        beta = None
        for ci in order:
            beta = betas[fi, ci] = solve(X, y, Cs[ci], lo, hi, beta0=beta,
                                         bf16=bf16)
        hits = _hits(X, y, jnp.asarray(betas[fi], jnp.float32),
                     jnp.int32(lo), jnp.int32(hi), bf16=bf16,
                     mesh=X.sharding.mesh)
        scores[:, fi] = np.asarray(hits, np.float64) / (hi - lo)
    _, g0, _ = _objective(X, y, np.zeros(X.shape[1] + 1), 0.0, 0, 0, False)
    return {"Cs": Cs, "scores": scores, "mean": scores.mean(axis=1),
            "bounds": bounds, "fold_betas": betas, "refits": {},
            "bf16": bf16, "grad0_norm": float(np.linalg.norm(g0))}


def refit(ref, data, index: int):
    """The optimum over all rows at candidate ``index``'s ``C`` (kept:
    the fits of a window repeat one problem)."""
    if index not in ref["refits"]:
        ref["refits"][index] = solve(
            data["X"], data["y"], ref["Cs"][index], 0, 0,
            beta0=ref["fold_betas"][0, index], bf16=ref["bf16"])
    return ref["refits"][index]


def control_estimator(precision: str):
    """The reference in ``precision`` in the shape of the search, which
    ``control_search.py`` puts in the program's place under the timed
    path: ``fit`` takes the program's row-sharded table (``.data``,
    ``.n_samples``) and leaves the attributes the harness fetches."""

    class Control:
        def __init__(self, **est_args):
            self.est_args = est_args

        def fit(self, X, y):
            n = X.n_samples
            data = {"X": X.data if X.data.shape[0] == n else X.data[:n],
                    "y": y.data if y.data.shape[0] == n else y.data[:n]}
            ref = build(data, self.est_args, precision)
            self.split_test_scores_ = ref["scores"]
            self.coefs_paths_ = ref["fold_betas"].astype(np.float32)
            self.n_splits_ = ref["scores"].shape[1]
            self.best_index_ = int(np.argmax(ref["mean"]))
            beta = refit(ref, data, self.best_index_)
            self.coef_ = beta[:-1].astype(np.float32)
            self.intercept_ = np.float32(beta[-1])
            self.n_iter_ = 0
            return self

    return Control


def _gaps(ref, data, beta, best, C, lo, hi, grad0_norm):
    """How far ``beta`` stands from ``best``, the optimum of F over the
    rows outside ``[lo, hi)``, as the plain fits are judged
    (``logistic_newton.compare``): the Newton decrement at ``beta``
    against the optimum's length in the loss's own metric, and the
    gradient there against the gradient at nought."""
    _, g, H = _objective(data["X"], data["y"], beta, 1.0 / C, lo, hi, False)
    step = np.linalg.solve(H, g)  # the Newton step back to the optimum
    return (float(np.sqrt(max(g @ step, 0.0)) / np.sqrt(best @ H @ best)),
            float(np.linalg.norm(g) / grad0_norm))


def compare(ref, data, answer: dict, last: dict) -> dict:
    """Numbers compared for one fitted search (smaller is closer)."""
    bad = {name: float("inf") for name in (
        "score_gap", "choice_gap", "regret", "lane_newton_gap",
        "newton_gap", "grad_gap")}
    scores = np.asarray(answer["split_test_scores_"], np.float64)
    paths = np.asarray(answer["coefs_paths_"], np.float64)
    index = int(answer["best_index_"])
    beta = np.concatenate([np.asarray(answer["coef_"], np.float64).ravel(),
                           [float(answer["intercept_"])]])
    if (scores.shape != ref["scores"].shape or not np.isfinite(scores).all()
            or not 0 <= index < len(ref["Cs"])
            or paths.shape != ref["fold_betas"].shape
            or not np.isfinite(paths).all()
            or beta.shape != ref["fold_betas"].shape[2:]
            or not np.isfinite(beta).all()):
        return bad
    means = scores.mean(axis=1)  # the program's own, over ALL its folds
    lanes = [
        _gaps(ref, data, paths[fi, ci], ref["fold_betas"][fi, ci], C, lo, hi,
              ref["grad0_norm"])[0]
        for fi, (lo, hi) in enumerate(ref["bounds"])
        for ci, C in enumerate(ref["Cs"])]
    newton_gap, grad_gap = _gaps(
        ref, data, beta, refit(ref, data, index), ref["Cs"][index], 0, 0,
        ref["grad0_norm"])
    return {
        # the split scores, one by one: the same folds, the same fits on
        # them, scored on the rows each held out
        "score_gap": float(np.abs(scores - ref["scores"]).max()),
        # the choice, by the program's own scores: what the candidate it
        # returned stands under the best mean over all the folds (0 for
        # the search that chose as it states)
        "choice_gap": float(means.max() - means[index]),
        # what the program's choice costs by the reference's own means
        # (the scores plateau, so the index itself is not compared)
        "regret": float(ref["mean"].max() - ref["mean"][index]),
        # the lanes: every fold's fit at every C against the optimum on
        # that fold's train rows, the farthest of them
        "lane_newton_gap": max(lanes),
        # the refit, against the optimum over all rows at the chosen C
        "newton_gap": newton_gap,
        "grad_gap": grad_gap,
    }
