"""Solver algorithms — twin of ``dask_glm/algorithms.py`` (``admm``,
``lbfgs``, ``gradient_descent``, ``newton``, ``proximal_grad``).

Every solver consumes a row-sharded design matrix and returns the
coefficient vector.  The gradient of the masked total loss is computed by
autodiff under ``jit``; with sharded inputs XLA turns the loss reduction
into an ICI psum — the reference's per-iteration scatter/gather through the
scheduler disappears (SURVEY.md §3.1 "TPU mapping").

Two structural rules, learned the hard way on real TPU hardware:

* **Whole-solve fusion.**  Each solver's outer convergence loop runs
  device-side in ``lax.while_loop`` (including the stopping rule), so a fit
  costs ONE dispatch instead of ``max_iter`` dispatches each followed by a
  host ``float()`` sync.
* **Data as arguments, never closure constants.**  The jitted runners are
  module-level and take ``(x, y, mask)`` as arguments with ``(family,
  regularizer)`` as static args.  Capturing the design matrix in a closure
  would bake hundreds of MB into the HLO as a constant (breaking remote
  compilation outright) and force a recompile per ``fit`` — with arguments,
  one compilation serves every same-shape fit (Hyperband's many-models loop
  in particular).
"""

from __future__ import annotations

import logging
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.compat import shard_map_unchecked
from ..core.mesh import MeshHolder, get_mesh
from ..core.sharded import ShardedRows, shard_rows
from .families import Family, Logistic
from .lbfgs_core import (
    EXITS, LinearObjective, lbfgs_minimize, run_line_search, stall_threshold)
from .regularizers import L2, Regularizer, get_regularizer

logger = logging.getLogger(__name__)


def _prep(X, y):
    """Normalize inputs to (x, y, mask) padded device arrays."""
    # shard_rows dispatches on input type; device arrays stay on device
    # (forcing np.asarray here would round-trip them through the host).
    # Floating device dtypes pass through (bf16 designs are supported);
    # anything else promotes to f32.
    if isinstance(X, ShardedRows):
        Xs = X
    elif isinstance(X, jax.Array):
        Xs = shard_rows(
            X if jnp.issubdtype(X.dtype, jnp.floating)
            else X.astype(jnp.float32))
    else:
        Xs = shard_rows(np.asarray(X, dtype=np.float32))
    x, mask = Xs.data, Xs.mask
    if isinstance(y, ShardedRows):
        yv = y.data
    else:
        # a DEVICE-resident y must stay on device: `np.asarray(y)` on a
        # jax array is a device->host fetch, and the old unconditional
        # jnp.asarray(np.asarray(y)) round-tripped every device target
        # through the host — per SOLVER CALL, a PCIe bounce each way (it
        # made the sequential OvR arm read far slower than its compute).
        yv = y if isinstance(y, jax.Array) else jnp.asarray(np.asarray(y))
        if yv.shape[0] != x.shape[0]:
            yv = jnp.pad(yv, (0, x.shape[0] - yv.shape[0]))
    # mixed precision: X may stay half (bf16 halves its HBM traffic, the
    # dominant cost of every solver pass); parameters, targets, and every
    # reduction run in >= float32 — XLA fuses the widening into the matvec
    # so no f32 copy of X ever materializes
    return x, yv.astype(_param_dtype(x)), mask


def _param_dtype(x):
    """Accumulation/parameter dtype for a design matrix: at least f32."""
    return jnp.promote_types(x.dtype, jnp.float32)


def _pdim(x, family, intercept=False):
    """Parameter-vector length: features (one more with an intercept,
    which comes last) × the family's parameters per feature (1 for
    scalar-response families; K for multinomial softmax, whose flat beta
    reshapes to (features, K) inside the loss).  The length is how the
    intercept reaches the jitted runners: ``Family.split`` tells a vector
    one longer than the table is wide from one that is not."""
    return (x.shape[1] + bool(intercept)) * int(
        getattr(family, "params_per_feature", 1))


def _has_intercept(beta, x, family):
    """Whether a runner's parameter vector carries an intercept: a static
    fact of the trace, read from the vector's length as ``Family.split``
    reads it."""
    return beta.shape[0] != _pdim(x, family)


def _init_beta(beta0, x, family, intercept=False):
    """Resolve a solver's initial parameter vector: zeros (cold start)
    or a caller-supplied warm start (``LogisticRegression(warm_start=
    True)`` passes the previous fit's coefficients).  Shape-checked: a
    wrong-length init is a caller bug, not something to run with."""
    d = _pdim(x, family, intercept)
    dt = _param_dtype(x)
    if beta0 is None:
        return jnp.zeros(d, dtype=dt)
    b = jnp.asarray(beta0, dt).ravel()
    if b.shape[0] != d:
        raise ValueError(
            f"beta0 has {b.shape[0]} parameters; this solve needs {d}"
        )
    return b


#: Python-level solver dispatch counter (observability for the packed
#: OvR path: a K-class fit must cost O(1) dispatches, not K).
DISPATCH_COUNTS = {"solves": 0}


def reset_dispatch_counts():
    DISPATCH_COUNTS["solves"] = 0


#: what the counted runners (``_admm_run``, ``_lbfgs_run``) return in the
#: place of a scalar iteration count, as one small int32 vector so that
#: the host fetches it in the one transfer ``n_iter_`` already costs.
#: The first four places are every counted solver's: the solver's own
#: iterations (ADMM rounds; ``n_iter_``), the L-BFGS iterations inside
#: them, ``LBFGSState.n_evals`` (the local solves' operations that stream
#: the design matrix) and ``LBFGSState.n_trials`` (the line search's
#: trials on the cached linear predictor, which do not: each a reduction
#: over vectors of a row's length, for a value of ``phi``, its slope or,
#: once a history-less search, the curvature at the start of the line).
#: The next four say why the L-BFGS solves ended, one count for each of
#: ``lbfgs_core.EXITS`` (``LBFGSState.reason``): the gradient certified
#: (``max|g| <= tol``), the objective stalled (its relative decrease at
#: or under 10 eps), the search failed (no step passed Armijo), the
#: budget spent (``max_iter`` iterations and none of those).  ``lbfgs``
#: is one solve, so one of the four is 1; ADMM makes one local solve a
#: shard a round, so they sum to ``rounds x shards``.  A solve that
#: ends by ``failed`` or ``budget`` is one to distrust.
#: The rest are ADMM's alone (``_lbfgs_run``'s vector ends after eight):
#: summed over the rounds, the evaluations and the trials the slowest
#: shard's local solve made more than the fastest's (what the fastest
#: chip sat out at the round's all-reduce; 0 on one shard), the rounds
#: that moved ``rho``, and ``LBFGSState.n_guided``: those of ``trials``
#: that the searches with no history took, which start from the
#: curvature's guess and not from the unit step (a round's first, under
#: ``backtrack``; the largest over the shards; 0 under ``probe_grid`` and
#: for a black box).  Three trials a search (the curvature, the first
#: look, and the slope at the unit step or the look above that Armijo
#: refuses) is a guess that stood on the answer; more is the walk from
#: the guess to the answer
SOLVE_COUNTS = ("rounds", "inner_iters", "passes", "trials",
                *("exit_" + name for name in EXITS),
                "skew_passes", "skew_trials", "rho_moves", "guided_trials")
#: where ADMM's consensus stopped, behind its counts in the same vector
#: as float32 BIT PATTERNS (so they cost no second transfer): the last
#: round's residuals over their tolerances (under 1: that part of the
#: stopping rule was met) and the final ``rho`` over the initial one;
#: then where the last round's LOCAL solves stopped, the largest over
#: the shards: ``max|g|`` at the solve's last point over ``inner_tol``
#: (under 1: the gradient test was met; infinite where ``inner_tol`` is
#: 0) and the last iteration's relative decrease of the local objective
#: over ``lbfgs_core.stall_threshold`` (under 1: the float32 loss could
#: no longer tell two steps apart; infinite where a solve took no
#: iteration)
SOLVE_RATIOS = ("primal_ratio", "dual_ratio", "rho_ratio",
                "grad_ratio", "dec_ratio")
#: the solvers that take ``return_counts=True``
COUNTED_SOLVERS = ("admm", "lbfgs")


def unpack_counts(row):
    """``(counts, ratios)`` of one counted run's vector, once it is on
    the host: dicts by :data:`SOLVE_COUNTS` and :data:`SOLVE_RATIOS`,
    each as long as the solver filled it."""
    row = np.asarray(row, dtype=np.int32)
    n = len(SOLVE_COUNTS)
    return (dict(zip(SOLVE_COUNTS, row[:n].tolist())),
            dict(zip(SOLVE_RATIOS, row[n:].view(np.float32).tolist())))


def _iterations(n_it):
    """A runner's scalar iteration count: the counted runners return the
    :data:`SOLVE_COUNTS` vector in its place."""
    return n_it[0] if n_it.ndim else n_it


def _with_counts(beta, counts, return_n_iter, return_counts):
    """A counted solver's return value.  Counts stay on the device:
    converting here would block the async dispatch pipeline (callers
    convert after ALL solves)."""
    if return_counts:
        return beta, counts
    return (beta, counts[0]) if return_n_iter else beta


def _make_objective(family, reg, x, y, mask, lamduh):
    """Total objective as a traceable closure over THIS trace's arrays.

    ``lamduh`` is a traced scalar: zero simply zeroes the penalty term, so
    one compiled program covers every regularization strength.
    """
    return _lbfgs_objective("black_box", family, x, y, mask,
                            lambda b: reg.penalty(b, lamduh))


def _lbfgs_objective(objective, family, x, y, mask, smooth,
                     intercept=False):
    """The family's loss plus ``smooth`` (a function of the parameters
    alone), as a black-box closure or, for ``objective="linear"``, in the
    parts ``lbfgs_minimize`` can use to search on the cached linear
    predictor (with ``intercept``, :func:`_has_intercept` of the vector
    to be found, the intercept is the ``LinearObjective``'s ``offset``).
    ``objective`` is the counted runners' PRIVATE static
    argument, set by the entry points alone: ``admm()`` and ``lbfgs()``
    ask for ``"linear"``, a caller that puts the runner under ``vmap``
    for ``"black_box"``.  Asked for ``"linear"``, two kinds of family get
    the black box all the same: one with a matrix of parameters
    (``multinomial``), and one that defines only ``loss`` and so has no
    parts to hand over.  Both choices are chip readings: PERF.md section
    6, PR 29.
    """
    if objective not in ("black_box", "linear"):
        raise ValueError(f"unknown objective kind {objective!r}")
    parts = getattr(family, "pointwise_loss", Family.pointwise_loss)
    if (objective == "black_box" or parts is Family.pointwise_loss
            or getattr(family, "params_per_feature", 1) > 1):
        return lambda b: family.loss(b, x, y, mask) + smooth(b)
    return LinearObjective(
        predict=lambda *betas: family.products(
            x, *(family.split(b, x)[0] for b in betas)),
        pointwise=lambda eta: family.pointwise_loss(eta, y, mask),
        smooth=smooth,
        offset=(lambda b: family.split(b, x)[1]) if intercept else None)


def _converged(f_prev, f_new, tol):
    # isfinite guard: f_prev starts at inf, and inf <= inf would declare
    # convergence on the very first iteration
    return jnp.isfinite(f_prev) & (
        jnp.abs(f_prev - f_new) <= tol * jnp.maximum(jnp.abs(f_prev), 1.0)
    )


# ---------------------------------------------------------------- lbfgs --


@partial(jax.jit, static_argnames=(
    "family", "reg", "line_search", "objective"))
def _lbfgs_run(x, yv, mask, beta0, lamduh, max_iter, tol, *, family, reg,
               line_search="backtrack", objective="black_box"):
    obj = _lbfgs_objective(objective, family, x, yv, mask,
                           lambda b: reg.penalty(b, lamduh),
                           _has_intercept(beta0, x, family))
    beta, st = lbfgs_minimize(
        obj, beta0, max_iter=max_iter, tol=tol, line_search=line_search
    )
    return beta, jnp.concatenate([
        jnp.stack([st.k, st.k, st.n_evals, st.n_trials]),
        _exit_counts(st)]).astype(jnp.int32)


def lbfgs(X, y, *, family: type[Family] = Logistic, regularizer=L2,
          lamduh: float = 0.0, max_iter: int = 100, tol: float = 1e-5,
          beta0=None, return_n_iter: bool = False, line_search: str = "auto",
          return_counts: bool = False, intercept: bool = False):
    """Full-gradient L-BFGS on the total (smooth) objective.

    Reference: ``dask_glm/algorithms.py :: lbfgs`` (scipy driver with
    distributed gradient); here the whole optimizer is one XLA program.
    ``return_counts=True`` returns ``(beta, counts)``, the device vector
    :data:`SOLVE_COUNTS` lays out.

    ``line_search="auto"`` resolves per platform (probe_grid on TPU,
    backtrack on CPU — :func:`line_search_strategy`).

    ``intercept=True`` (every solver, ``packed_solve`` and
    ``lambda_sweep`` take it) fits a constant term beside the weights:
    the parameter vector is one longer than ``X`` is wide (per class for
    a matrix of parameters), the intercept LAST, and the linear predictor
    is ``X @ beta[:-1] + beta[-1]``.  It is the fit a column of ones
    appended to ``X`` gives, penalty and ADMM's consensus covering the
    intercept as they cover that column's weight, without the copy of
    the table that appending costs.  With ``intercept=False`` (the
    default) a caller who wants a constant term brings the column.
    """
    line_search = line_search_strategy(line_search)
    reg = get_regularizer(regularizer)
    if lamduh and not reg.smooth:
        raise ValueError(
            f"lbfgs requires a smooth penalty; got {reg.__name__}. "
            "Use proximal_grad or admm for l1/elastic_net."
        )
    x, yv, mask = _prep(X, y)
    DISPATCH_COUNTS["solves"] += 1
    beta0 = _init_beta(beta0, x, family, intercept)
    beta, counts = _lbfgs_run(
        x, yv, mask, beta0, jnp.asarray(lamduh, _param_dtype(x)),
        jnp.int32(max_iter), jnp.asarray(tol, _param_dtype(x)),
        family=family, reg=reg, line_search=line_search,
        objective="linear",
    )
    return _with_counts(beta, counts, return_n_iter, return_counts)


# ---------------------------------------------------- gradient descent --


@partial(jax.jit, static_argnames=("family", "reg", "line_search"))
def _gd_run(x, yv, mask, beta0, lamduh, max_it, tol, *, family, reg,
            line_search="backtrack"):
    obj = _make_objective(family, reg, x, yv, mask, lamduh)
    vg = jax.value_and_grad(obj)

    def cond(state):
        i, _, _, f_prev, converged = state
        return (i < max_it) & ~converged

    def body(state):
        i, beta, stepsize, f_prev, _ = state
        f, g = vg(beta)
        # c2=None: pure Armijo — the reference gradient_descent's
        # backtracking semantics, no curvature/expansion phase
        t, f_new, _gn, failed, _ = run_line_search(
            line_search, vg, beta, f, g, -stepsize * g, 1e-4, 30, c2=None)
        beta_new = beta - t * stepsize * g
        stepsize_new = jnp.where(t > 0, stepsize * t * 2.0, stepsize * 0.5)
        return i + 1, beta_new, stepsize_new, f_new, _converged(f_prev, f_new, tol)

    init = (
        jnp.int32(0),
        beta0,
        jnp.asarray(1.0, beta0.dtype),
        jnp.asarray(jnp.inf, beta0.dtype),
        jnp.asarray(False),
    )
    final = lax.while_loop(cond, body, init)
    return final[1], final[0]


def gradient_descent(X, y, *, family: type[Family] = Logistic,
                     regularizer=L2, lamduh: float = 0.0,
                     max_iter: int = 100, tol: float = 1e-7,
                     beta0=None, return_n_iter: bool = False,
                     line_search: str = "backtrack",
                     intercept: bool = False):
    """Armijo-backtracking gradient descent (reference ``gradient_descent``)."""
    line_search = line_search_strategy(line_search)
    reg = get_regularizer(regularizer)
    if lamduh and not reg.smooth:
        raise ValueError("gradient_descent requires a smooth penalty; use proximal_grad")
    x, yv, mask = _prep(X, y)
    DISPATCH_COUNTS["solves"] += 1
    beta0 = _init_beta(beta0, x, family, intercept)
    beta, n_it = _gd_run(
        x, yv, mask, beta0, jnp.asarray(lamduh, _param_dtype(x)),
        jnp.int32(max_iter), jnp.asarray(tol, _param_dtype(x)),
        family=family, reg=reg, line_search=line_search,
    )
    # n_it stays a device scalar: converting here would block the
    # async dispatch pipeline (callers convert after ALL solves)
    return (beta, n_it) if return_n_iter else beta


# ------------------------------------------------------ proximal grad --


@partial(jax.jit, static_argnames=("family", "reg"))
def _pg_run(x, yv, mask, beta0, lamduh, max_it, tol, *, family, reg):
    f_smooth = lambda b: family.loss(b, x, yv, mask)  # noqa: E731
    vg = jax.value_and_grad(f_smooth)

    def step(beta, t0):
        f, g = vg(beta)

        def cond(carry):
            t, j = carry
            z = reg.prox(beta - t * g, t * lamduh)
            diff = z - beta
            ub = f + jnp.dot(g, diff) + jnp.sum(diff ** 2) / (2 * t)
            # "not under the bound", so that a step whose loss is not a
            # number backtracks as one whose loss is infinite does: with
            # an intercept a pad row's predictor is the intercept, and
            # where its loss overflows the mask makes 0 * inf of it
            return ~(f_smooth(z) <= ub) & (j < 30)

        def body(carry):
            t, j = carry
            return 0.5 * t, j + 1

        t, _ = lax.while_loop(cond, body, (t0, jnp.int32(0)))
        z = reg.prox(beta - t * g, t * lamduh)
        return z, t, f

    def cond(state):
        i, _, _, _, converged = state
        return (i < max_it) & ~converged

    def body(state):
        i, beta, t, f_prev, _ = state
        beta_new, t_used, f = step(beta, t)
        return i + 1, beta_new, t_used * 2.0, f, _converged(f_prev, f, tol)

    init = (
        jnp.int32(0),
        beta0,
        jnp.asarray(1.0, beta0.dtype),
        jnp.asarray(jnp.inf, beta0.dtype),
        jnp.asarray(False),
    )
    final = lax.while_loop(cond, body, init)
    return final[1], final[0]


def proximal_grad(X, y, *, family: type[Family] = Logistic, regularizer=L2,
                  lamduh: float = 0.0, max_iter: int = 100, tol: float = 1e-7,
                  beta0=None, return_n_iter: bool = False,
                  intercept: bool = False):
    """Proximal gradient with backtracking on the smooth part (reference
    ``proximal_grad``): z = prox_{tλ}(β − t∇f(β))."""
    reg = get_regularizer(regularizer)
    x, yv, mask = _prep(X, y)
    DISPATCH_COUNTS["solves"] += 1
    beta0 = _init_beta(beta0, x, family, intercept)
    beta, n_it = _pg_run(
        x, yv, mask, beta0, jnp.asarray(lamduh, _param_dtype(x)),
        jnp.int32(max_iter), jnp.asarray(tol, _param_dtype(x)),
        family=family, reg=reg,
    )
    # n_it stays a device scalar: converting here would block the
    # async dispatch pipeline (callers convert after ALL solves)
    return (beta, n_it) if return_n_iter else beta


# ------------------------------------------------------------- newton --


@partial(jax.jit, static_argnames=("family", "reg", "line_search"))
def _newton_run(x, yv, mask, beta0, lamduh, max_it, tol, *, family, reg,
                line_search="backtrack"):
    obj = _make_objective(family, reg, x, yv, mask, lamduh)
    vg = jax.value_and_grad(obj)
    d = beta0.shape[0]

    def step(beta):
        f, g = vg(beta)
        w = family.hessian_weights(family.linear_predictor(beta, x)) * mask
        xw = x * w[:, None]
        H = xw.T @ x  # psum-reduced gemm
        if d != x.shape[1]:  # an intercept (newton: one number a feature)
            # the border a column of ones would have given: X'w, sum(w)
            xw1 = jnp.sum(xw, axis=0)
            H = jnp.block([[H, xw1[:, None]],
                           [xw1[None, :], jnp.sum(w)[None, None]]])
        if reg.smooth:
            H = H + lamduh * jnp.eye(d, dtype=_param_dtype(x))
        H = H + 1e-8 * jnp.eye(d, dtype=_param_dtype(x))
        p = -jnp.linalg.solve(H, g)
        # c2=None: pure Armijo (damped-Newton semantics)
        t, f_new, _gn, failed, _ = run_line_search(
            line_search, vg, beta, f, g, p, 1e-4, 30, c2=None)
        return beta + t * p, f, f_new

    def cond(state):
        i, _, _, converged = state
        return (i < max_it) & ~converged

    def body(state):
        i, beta, f_prev, _ = state
        beta_new, f, f_new = step(beta)
        return i + 1, beta_new, f_new, _converged(f_prev, f_new, tol)

    init = (
        jnp.int32(0),
        beta0,
        jnp.asarray(jnp.inf, beta0.dtype),
        jnp.asarray(False),
    )
    final = lax.while_loop(cond, body, init)
    return final[1], final[0]


def newton(X, y, *, family: type[Family] = Logistic, regularizer=L2,
           lamduh: float = 0.0, max_iter: int = 50, tol: float = 1e-8,
           beta0=None, return_n_iter: bool = False,
           line_search: str = "backtrack", intercept: bool = False):
    """Damped Newton: distributed Hessian XᵀWX (one psum-reduced gemm,
    bordered by ``X'w`` and ``sum(w)`` for an intercept), replicated
    (d×d) solve (reference ``newton``)."""
    line_search = line_search_strategy(line_search)
    reg = get_regularizer(regularizer)
    if lamduh and not reg.smooth:
        raise ValueError("newton requires a smooth penalty")
    if getattr(family, "params_per_feature", 1) > 1:
        raise ValueError(
            "newton needs scalar per-sample hessian weights; the "
            "multinomial family has a KxK block hessian — use lbfgs/"
            "gradient_descent/proximal_grad/admm"
        )
    x, yv, mask = _prep(X, y)
    DISPATCH_COUNTS["solves"] += 1
    beta0 = _init_beta(beta0, x, family, intercept)
    beta, n_it = _newton_run(
        x, yv, mask, beta0, jnp.asarray(lamduh, _param_dtype(x)),
        jnp.int32(max_iter), jnp.asarray(tol, _param_dtype(x)),
        family=family, reg=reg, line_search=line_search,
    )
    # n_it stays a device scalar: converting here would block the
    # async dispatch pipeline (callers convert after ALL solves)
    return (beta, n_it) if return_n_iter else beta


# --------------------------------------------------------------- admm --


@partial(jax.jit, static_argnames=(
    "family", "reg", "mesh_holder", "inner_iter", "line_search",
    "adaptive_rho", "objective"))
def _admm_run(x, yv, mask, lamduh, rho, abstol, reltol, inner_tol, max_it,
              z_init, *, family, reg, mesh_holder, inner_iter,
              line_search="backtrack", adaptive_rho=True,
              objective="black_box"):
    mesh = mesh_holder.mesh
    # rows shard over ('dcn', 'data') on a hierarchical multi-slice mesh
    # (core.distributed.global_mesh(hierarchical=True)) — the psums below
    # then span the slice boundary: XLA splits each into an ICI segment
    # and a DCN segment from the axis tuple
    from ..core.mesh import data_axes as _data_axes
    from ..core.mesh import data_axes_size as _data_axes_size

    row_ax = _data_axes(mesh)
    n_shards = _data_axes_size(mesh)
    d = z_init.shape[0]  # with the intercept, where the caller asked for one

    def one_shard(xb, yb, mb, z_rep, beta_b, u_b, rho_c):
        u0, b0 = u_b[0], beta_b[0]

        local_obj = _lbfgs_objective(
            objective, family, xb, yb, mb,
            lambda b: 0.5 * rho_c * jnp.sum((b - z_rep + u0) ** 2),
            _has_intercept(b0, xb, family))

        with jax.named_scope("admm.local_solve"):
            b_new, st = lbfgs_minimize(
                local_obj, b0, max_iter=inner_iter, tol=inner_tol,
                line_search=line_search,
            )
        with jax.named_scope("admm.consensus"):
            b_bar = lax.psum(b_new, row_ax) / n_shards
            u_bar = lax.psum(u0, row_ax) / n_shards
            z_new = reg.prox(b_bar + u_bar, lamduh / (rho_c * n_shards))
            u_new = u0 + b_new - z_new
            # residual pieces
            primal_sq = lax.psum(jnp.sum((b_new - z_new) ** 2), row_ax)
            beta_norm_sq = lax.psum(jnp.sum(b_new ** 2), row_ax)
            u_norm_sq = lax.psum(jnp.sum(u_new ** 2), row_ax)
            # the round lasts as long as its slowest shard's solve; the
            # negatives bring the fastest shard's counts in the same
            # all-reduce, and the difference is what that shard sat out
            # (and the guided searches' trials ride along, and how far
            # this round's solve stood from its two convergence tests:
            # a non-negative float32's bit pattern orders as its int32
            # does, and ``abs`` clears the sign a zero, a NaN or a
            # rounding of an accepted step's decrease may carry)
            stood = jnp.abs(jnp.stack([
                st.g_max / inner_tol,
                st.rel_dec / stall_threshold(st.rel_dec.dtype),
            ]).astype(jnp.float32))
            both = lax.pmax(jnp.concatenate([
                jnp.stack([st.k, st.n_evals, st.n_trials,
                           -st.n_evals, -st.n_trials, st.n_guided]),
                lax.bitcast_convert_type(stood, jnp.int32)]), row_ax)
            # why each shard's solve ended: the one all-reduce a round
            # this costs over the three there were (a sum, so it cannot
            # ride the pmax)
            exits = lax.psum(_exit_counts(st), row_ax)
            work = jnp.concatenate(
                [both[:3], exits, both[1:3] + both[3:5], both[5:6]])
        return (b_new[None], u_new[None], z_new, primal_sq, beta_norm_sq,
                u_norm_sq, work, both[6:])

    step = shard_map_unchecked(
        one_shard,
        mesh,
        in_specs=(
            P(row_ax, None),  # x
            P(row_ax),  # y
            P(row_ax),  # mask
            P(),  # z
            P(row_ax, None),  # beta per shard
            P(row_ax, None),  # u per shard
            P(),  # rho (replicated scalar; part of the carry when adaptive)
        ),
        out_specs=(
            P(row_ax, None),
            P(row_ax, None),
            P(),
            P(),
            P(),
            P(),
            P(),
            P(),
        ),
    )

    # Boyd residual stopping rule, also on device: the whole solve is one
    # XLA program regardless of iteration count.
    sqrt_d = jnp.sqrt(jnp.asarray(d, _param_dtype(x)))

    def cond(state):
        (i, _, _, _, _, primal, dual, eps_pri, eps_dual,
         rho_moved, _, _) = state
        return (i < max_it) & (
            (primal >= eps_pri) | (dual >= eps_dual) | rho_moved
        )

    def body(state):
        i, beta_l, u_l, z, rho_c, *_, work, _ = state
        z_old = z
        beta_l, u_l, z, primal_sq, beta_sq, u_sq, round_work, stood = step(
            x, yv, mask, z, beta_l, u_l, rho_c
        )
        primal = jnp.sqrt(primal_sq)
        dual = rho_c * jnp.sqrt(n_shards * jnp.sum((z - z_old) ** 2))
        eps_pri = sqrt_d * abstol + reltol * jnp.maximum(
            jnp.sqrt(beta_sq), jnp.sqrt(n_shards * 1.0) * jnp.linalg.norm(z)
        )
        eps_dual = sqrt_d * abstol + reltol * rho_c * jnp.sqrt(u_sq)
        rho_moved = jnp.asarray(False)
        if adaptive_rho:
            # Boyd §3.4.1 residual balancing: a lopsided rho makes one
            # residual stall (tiny rho → dual ≈ 0 while primal creeps;
            # huge rho → the reverse).  The scaled dual u must be
            # rescaled by rho/rho_new on every change.  While the
            # balancer is MOVING rho the convergence exit is suppressed:
            # Boyd's stopping thresholds assume a settled rho — eps_dual
            # scales WITH rho, so a huge initial rho would pass the dual
            # test trivially and stop rounds before balancing engages
            # (property-test find: rho=1e3 stopped 4 accuracy points
            # below the optimum).
            # no balancing once BOTH residuals pass their tolerances:
            # at an exact z fixed point dual == 0 makes `grow` true
            # forever, and an unconditional balancer would ride rho to
            # the clip cap (suppressing the exit for ~6 wasted rounds)
            # when the solve is already done
            done = (primal < eps_pri) & (dual < eps_dual)
            grow = ~done & (primal > 10.0 * dual)
            shrink = ~done & (dual > 10.0 * primal)
            # proportional step (He et al. / Boyd's τ-variant): √ of the
            # residual ratio, clipped to one decade per round — from a
            # rho 6 orders off, balance lands in ~3 rounds instead of
            # ~20 halvings, leaving the iteration budget for actual
            # convergence (property-test corner: rho=1e-3 + offset=1e3)
            factor = jnp.where(
                grow | shrink,
                jnp.clip(
                    jnp.sqrt(primal / jnp.maximum(dual, 1e-30)),
                    0.1, 10.0),
                1.0,
            )
            # clip to ±1e6 of the initial rho: a pathological run cannot
            # drive rho to inf/0 (wide enough that balancing from a
            # 6-orders-off initial rho is never clamped mid-walk)
            rho_new = jnp.clip(rho_c * factor, rho * 1e-6, rho * 1e6)
            rho_moved = rho_new != rho_c
            u_l = u_l * (rho_c / rho_new)
            rho_c = rho_new
        return (i + 1, beta_l, u_l, z, rho_c, primal, dual, eps_pri,
                eps_dual, rho_moved, work + jnp.concatenate(
                    # SOLVE_COUNTS' order: rho_moves stands before the
                    # guided searches' trials, the round's last count
                    [round_work[:-1], rho_moved[None].astype(jnp.int32),
                     round_work[-1:]]), stood)

    inf = jnp.asarray(jnp.inf, _param_dtype(x))
    zero = jnp.asarray(0.0, _param_dtype(x))
    # warm start: consensus z and every shard's beta begin at z_init
    # (zeros when cold); duals start at 0 either way — Boyd's warm-start
    # recipe for re-solves at nearby hyperparameters
    beta_l0 = jnp.broadcast_to(
        z_init, (n_shards, d)).astype(_param_dtype(x))
    u_l0 = jnp.zeros((n_shards, d), dtype=_param_dtype(x))
    z0 = z_init.astype(_param_dtype(x))
    init = (jnp.int32(0), beta_l0, u_l0, z0,
            jnp.asarray(rho, _param_dtype(x)), inf, inf, zero, zero,
            jnp.asarray(False), jnp.zeros(len(SOLVE_COUNTS) - 1, jnp.int32),
            # no round, no local solve: neither ratio is a number
            lax.bitcast_convert_type(
                jnp.full(2, jnp.inf, jnp.float32), jnp.int32))
    final = lax.while_loop(cond, body, init)
    (rounds, _, _, z, rho_c, primal, dual, eps_pri, eps_dual, _, work,
     stood) = final
    ratios = jnp.stack([primal / eps_pri, dual / eps_dual, rho_c / rho])
    return z, jnp.concatenate([
        rounds[None], work,
        lax.bitcast_convert_type(ratios.astype(jnp.float32), jnp.int32),
        stood])


def admm(X, y, *, family: type[Family] = Logistic, regularizer=L2,
         lamduh: float = 0.0, rho: float = 1.0, max_iter: int = 100,
         abstol: float = 1e-4, reltol: float = 1e-2,
         inner_iter: int = 50, inner_tol: float = 1e-6, mesh=None,
         return_n_iter: bool = False, line_search: str = "backtrack",
         adaptive_rho: bool = True, beta0=None,
         return_counts: bool = False, intercept: bool = False):
    """Consensus ADMM (Boyd et al. §8): per-shard local subproblems solved by
    the jit-safe L-BFGS inside ``shard_map``, consensus z through the
    regularizer's prox, scaled dual updates.

    Reference: ``dask_glm/algorithms.py :: admm`` — one scatter/gather round
    per iteration through the scheduler, scipy L-BFGS per chunk on workers
    (SURVEY.md §3.1).  Here the ENTIRE solve is one XLA program: P parallel
    local L-BFGS runs + psums for consensus and residuals per round, with
    the Boyd stopping rule evaluated on device.

    ``adaptive_rho`` (default on; the reference keeps rho fixed) applies
    Boyd §3.4.1 residual balancing on device — a property-test-found
    robustness gap: with a fixed rho 3 orders of magnitude off, the solve
    stalled below 85% train accuracy at max_iter=150 on separable data
    (tests/test_properties.py :: TestAdversarialSolvers).

    ``line_search`` defaults to ``backtrack`` (not ``auto``); pass
    ``auto``/``probe_grid`` explicitly to opt in.  Either way the local
    solves search on the cached linear predictor and read X twice an
    L-BFGS iteration (``lbfgs_core.LinearObjective``); what the two
    strategies cost on the chip is in PERF.md section 5 (the two
    ``admm-higgs`` cells run one each).

    ``return_counts=True`` returns ``(beta, counts)``, the device vector
    :data:`SOLVE_COUNTS` and :data:`SOLVE_RATIOS` lay out and
    :func:`unpack_counts` reads on the host (per round the slowest
    shard's inner iterations and evaluations, and what the fastest made
    fewer, summed over the rounds; how many shard-rounds' local solves
    ended by each of ``lbfgs_core.EXITS``; then where the consensus
    stopped, and where the last round's local solves did).
    """
    line_search = line_search_strategy(line_search)
    reg = get_regularizer(regularizer)
    mesh = mesh or get_mesh()
    x, yv, mask = _prep(X, y)
    DISPATCH_COUNTS["solves"] += 1
    dt = _param_dtype(x)
    beta, counts = _admm_run(
        x, yv, mask,
        jnp.asarray(lamduh, dt), jnp.asarray(rho, dt),
        jnp.asarray(abstol, dt), jnp.asarray(reltol, dt),
        jnp.asarray(inner_tol, dt), jnp.int32(max_iter),
        _init_beta(beta0, x, family, intercept),
        family=family, reg=reg, mesh_holder=MeshHolder(mesh),
        inner_iter=inner_iter, line_search=line_search,
        adaptive_rho=adaptive_rho, objective="linear",
    )
    return _with_counts(beta, counts, return_n_iter, return_counts)


# ------------------------------------------------------- packed (vmap) --


def pack_strategy(n_lanes: int | None = None) -> str:
    """How one-vs-rest multi-class solves execute,
    ``DASK_ML_TPU_PACK`` = ``packed`` | ``sequential`` | ``auto``:

    - ``packed``: all K solves as ONE vmapped XLA program.
    - ``sequential``: K whole-solve dispatches, one per class — each
      class stops at ITS OWN convergence instead of the pack's slowest
      lane.
    - ``auto`` (default): the measured per-platform winner — **packed
      on TPU at every measured K, sequential on CPU**.  Final clean
      chip numbers (fixed-work instrument, device-resident operands,
      all-outputs terminal dependency): **1.60× (K=4), 2.49× (K=8),
      4.02× (K=16), 7.55× (K=64)** — the packed gemm reads X once for
      all K lanes (the dominant HBM traffic, amortized K ways) and the
      MXU batches K ≤ 128 lanes at near-constant cost.  Three earlier
      contradictory adjudications were instrument errors, each worth
      knowing (docs/design.md "invalid-instrument postmortem"):
      coin-flip targets let the line-search-failure exit give the arms
      different WORK; iteration-count fetches inside the timed region
      gave the arms different SYNC; and a ``_prep``/``shard_rows``
      device→host→device round trip on device-resident operands — a
      real product bug found BY the instrument chase, since fixed —
      taxed the arms differently per input type.  On CPU the fixed-work
      pack loses (vmap serializes lanes; 0.84× at K=4) — sequential
      stays the CPU winner.  ``n_lanes`` is accepted for future
      K-dependent policies; the current winner does not depend on it.
    """
    from ..utils import env_choice

    v = env_choice("DASK_ML_TPU_PACK", ("auto", "packed", "sequential"))
    if v != "auto":
        return v
    return "packed" if jax.default_backend() == "tpu" else "sequential"


def line_search_strategy(requested: str = "auto") -> str:
    """Resolve a line-search choice, ``DASK_ML_TPU_LINE_SEARCH`` =
    ``auto`` | ``backtrack`` | ``probe_grid``.

    ``auto`` (the :func:`lbfgs` default) picks per platform:
    ``probe_grid`` on TPU, ``backtrack`` on CPU (the grid evaluates 34
    candidates where backtracking evaluates a few).  On the chip the two
    differ by the cost of their trials on vectors of a row's length, not
    by reads of the design matrix: PERF.md section 5 has the readings.
    An explicit ``requested`` value wins over the env knob; the env knob
    wins over ``auto``.  Resolution must happen OUTSIDE jit (same
    trace-time-staleness rule as ``ops.scatter.scatter_strategy``).
    """
    from ..utils import env_choice

    if requested != "auto":
        return requested
    v = env_choice("DASK_ML_TPU_LINE_SEARCH",
                   ("auto", "backtrack", "probe_grid"))
    if v != "auto":
        return v
    return "probe_grid" if jax.default_backend() == "tpu" else "backtrack"


def grid_pack_strategy() -> str:
    """Whether GRID-SEARCH C-sweeps pack (``solvers.lambda_sweep``) —
    ``DASK_ML_TPU_GRID_PACK`` = ``packed`` | ``sequential`` | ``auto``.
    A separate knob from ``DASK_ML_TPU_PACK``: the two optimizations
    have opposite signs on CPU (OvR packing loses 1.5×, the grid sweep
    WINS 2× at small n because it also removes per-candidate
    orchestration) and must not share one switch.  Auto follows the
    at-scale measurement: packed on TPU, sequential on CPU (at large n
    the CPU solve dominates and vmap serialization loses,
    ``grid_sweep_lbfgs`` CPU: 0.626×); small-n CPU users can force
    ``packed`` for the measured orchestration win."""
    from ..utils import env_choice

    v = env_choice("DASK_ML_TPU_GRID_PACK",
                   ("auto", "packed", "sequential"))
    if v != "auto":
        return v
    return "packed" if jax.default_backend() == "tpu" else "sequential"


def packed_solve(solver: str, X, Y, *, family: type[Family] = Logistic,
                 regularizer=L2, lamduh: float = 0.0, max_iter: int = 100,
                 tol: float = 1e-5, rho: float = 1.0, abstol: float = 1e-4,
                 reltol: float = 1e-2, inner_iter: int = 50,
                 inner_tol: float = 1e-6, mesh=None,
                 line_search: str | None = None, Beta0=None,
                 intercept: bool = False):
    """All K independent solves as ONE vmapped XLA program over the
    leading axis of ``Y`` — the one-vs-rest fit issues a single dispatch
    instead of K sequential ones (the solvers' whole-solve ``while_loop``
    design is vmap-safe by construction: converged lanes hold their carry
    while stragglers keep iterating).  Under ``pack_strategy() ==
    "sequential"`` (the measured CPU winner, or forced via
    ``DASK_ML_TPU_PACK``) the same K solves run as K dispatches instead;
    results are identical up to lane-vs-loop accumulation order.

    Reference: ``dask_ml/linear_model/glm.py :: LogisticRegression``
    dispatches per class; there is no packed equivalent to cite — this is
    the TPU-native improvement over the reference's task-per-class plan.

    Args:
      solver: one of ``admm | lbfgs | gradient_descent | proximal_grad |
        newton``.
      Y: (K, padded_rows) stacked targets aligned with ``X``'s padded
        rows (pad rows are dead via the mask).
    Returns:
      (betas (K, pdim), n_iters (K,)) — both device arrays; each lane
      carries its own executed-iteration count.
    """
    reg = get_regularizer(regularizer)
    strategy = pack_strategy(len(Y))
    if strategy == "packed":
        # a lax.cond grid under vmap executes BOTH branches in every
        # lane, so probe_grid would pay the full grid per lane per
        # iteration — lockstep backtracking is strictly better here.
        # (sequential solves have no lanes; they keep the request)
        if line_search not in (None, "backtrack", "auto"):
            logger.info(
                "packed_solve forces line_search='backtrack' "
                "(requested %r): vmapped lanes run grids in both cond "
                "branches", line_search,
            )
        line_search = "backtrack"
    elif line_search is None:
        # OUR default (sentinel, so a user's explicit value — including
        # 'auto' — is distinguishable): lbfgs follows the measured
        # per-platform policy; admm/gd/newton keep their own
        # measured-safe backtrack default rather than being silently
        # opted into the unadjudicated configuration
        line_search = (line_search_strategy("auto")
                       if solver == "lbfgs" else "backtrack")
    else:
        # an explicit request — 'auto' included — is the user's opt-in
        # and resolves through the policy for every solver, matching
        # the direct entry points' contract
        line_search = line_search_strategy(line_search)
    # the same rule for the line search's cached linear predictor: under
    # vmap it is K x rows floats twice over, so packed lanes keep the
    # black-box objective; one solve a dispatch caches, as admm() and
    # lbfgs() do
    objective = "black_box" if strategy == "packed" else "linear"
    x, _, mask = _prep(X, Y[0])
    dt = _param_dtype(x)
    Yd = jnp.asarray(Y).astype(dt)
    if Yd.ndim != 2 or Yd.shape[1] != x.shape[0]:
        raise ValueError(
            f"Y must be (K, padded_rows={x.shape[0]}); got {Yd.shape}"
        )
    K = Yd.shape[0]
    lam = jnp.asarray(lamduh, dt)
    # warm start: one initial parameter row per lane (previous fit's
    # betas_); zeros when cold.  Per-row resolution goes through
    # _init_beta so the batched path shares its validation exactly.
    if Beta0 is None:
        B0 = jnp.zeros((K, _pdim(x, family, intercept)), dtype=dt)
    else:
        if len(Beta0) != K:
            raise ValueError(
                f"Beta0 must have {K} rows (one per lane); got {len(Beta0)}"
            )
        B0 = jnp.stack(
            [_init_beta(b, x, family, intercept) for b in Beta0])

    def _sequential(one_fn, *extra_rows):
        # K whole-solve dispatches (the auto fallback where vmap packing
        # measured slower); each class converges independently
        DISPATCH_COUNTS["solves"] += K
        outs = [
            one_fn(Yd[c], *(e[c] for e in extra_rows)) for c in range(K)
        ]
        betas = jnp.stack([b for b, _ in outs])
        n_its = jnp.stack([n for _, n in outs])
        return betas, n_its

    if strategy == "packed":
        DISPATCH_COUNTS["solves"] += 1
    if solver == "admm":
        mesh = mesh or get_mesh()
        mh = MeshHolder(mesh)

        def one(yv, b0):
            beta, counts = _admm_run(
                x, yv, mask, lam, jnp.asarray(rho, dt),
                jnp.asarray(abstol, dt), jnp.asarray(reltol, dt),
                jnp.asarray(inner_tol, dt), jnp.int32(max_iter), b0,
                family=family, reg=reg, mesh_holder=mh,
                inner_iter=inner_iter, line_search=line_search,
                objective=objective,
            )
            return beta, counts[0]

        if strategy == "sequential":
            return _sequential(one, B0)
        return jax.vmap(one)(Yd, B0)
    runners = {
        "lbfgs": _lbfgs_run,
        "gradient_descent": _gd_run,
        "proximal_grad": _pg_run,
        "newton": _newton_run,
    }
    if solver not in runners:
        raise ValueError(f"Unknown solver {solver!r}")
    if solver in ("lbfgs", "gradient_descent", "newton") and lamduh \
            and not reg.smooth:
        raise ValueError(
            f"{solver} requires a smooth penalty; got {reg.__name__}"
        )
    if solver == "newton" and getattr(family, "params_per_feature", 1) > 1:
        raise ValueError("newton does not support matrix-parameter families")
    run = runners[solver]

    # proximal_grad has its own prox backtracking and takes no knob
    extra_kw = (
        {} if solver == "proximal_grad" else {"line_search": line_search}
    )
    if solver == "lbfgs":
        extra_kw["objective"] = objective

    def one(yv, b0):
        beta, n_it = run(
            x, yv, mask, b0, lam, jnp.int32(max_iter),
            jnp.asarray(tol, dt), family=family, reg=reg, **extra_kw,
        )
        return beta, _iterations(n_it)

    if strategy == "sequential":
        return _sequential(one, B0)
    return jax.vmap(one)(Yd, B0)


def lambda_sweep(solver: str, X, y, lams, *, family: type[Family] = Logistic,
                 regularizer=L2, max_iter: int = 100, tol: float = 1e-5,
                 rho: float = 1.0, abstol: float = 1e-4, reltol: float = 1e-2,
                 inner_iter: int = 50, inner_tol: float = 1e-6, mesh=None,
                 line_search: str = "backtrack", intercept: bool = False,
                 return_counts: bool = False):
    """All K solves of the SAME (X, y) at different regularization
    strengths as ONE vmapped program — the grid-search twin of
    ``packed_solve`` (there the lanes differ in y, here in ``lamduh``,
    which every runner takes as a TRACED scalar, so a hyperparameter
    sweep is one dispatch instead of K).  No sequential fallback here:
    the grid-search caller gates on ``grid_pack_strategy()`` (NOT
    ``pack_strategy()`` — the two knobs are deliberately separate, with
    opposite CPU signs) and keeps its per-candidate path where packing
    measured slower.

    Returns (betas (K, pdim), n_iters (K,)).  ``return_counts=True``
    (as ``lbfgs`` and ``admm`` take it) returns each lane's whole count
    in the iterations' place, (K, n): the counted runners' vector
    :data:`SOLVE_COUNTS` lays out, a single column of iterations for the
    others.  The lanes' objective is a black box, so a lane's ``trials``
    are 0 and its searches' trials are among its ``passes``.
    """
    reg = get_regularizer(regularizer)
    if line_search != "backtrack":
        line_search = "backtrack"  # same vmap-lane rule as packed_solve
    objective = "black_box"  # and the same rule for the cached predictor
    x, yd, mask = _prep(X, y)
    dt = _param_dtype(x)
    lam_v = jnp.asarray(np.asarray(lams), dt)
    if lam_v.ndim != 1:
        raise ValueError(f"lams must be 1-D, got shape {lam_v.shape}")
    K = lam_v.shape[0]
    if solver == "admm":
        DISPATCH_COUNTS["solves"] += 1  # after arg validation, like
        # every per-solver entry point — a rejected config must not
        # skew the dispatch instrumentation
        mesh = mesh or get_mesh()
        mh = MeshHolder(mesh)

        def one_a(lam):
            beta, counts = _admm_run(
                x, yd, mask, lam, jnp.asarray(rho, dt),
                jnp.asarray(abstol, dt), jnp.asarray(reltol, dt),
                jnp.asarray(inner_tol, dt), jnp.int32(max_iter),
                jnp.zeros(_pdim(x, family, intercept), dtype=dt),
                family=family, reg=reg, mesh_holder=mh,
                inner_iter=inner_iter, line_search=line_search,
                objective=objective,
            )
            return beta, counts if return_counts else counts[0]

        return jax.vmap(one_a)(lam_v)
    runners = {
        "lbfgs": _lbfgs_run,
        "gradient_descent": _gd_run,
        "proximal_grad": _pg_run,
        "newton": _newton_run,
    }
    if solver not in runners:
        raise ValueError(f"Unknown solver {solver!r}")
    if solver in ("lbfgs", "gradient_descent", "newton") \
            and not reg.smooth and bool(np.any(np.asarray(lams))):
        raise ValueError(
            f"{solver} requires a smooth penalty; got {reg.__name__}"
        )
    if solver == "newton" and getattr(family, "params_per_feature", 1) > 1:
        raise ValueError("newton does not support matrix-parameter families")
    DISPATCH_COUNTS["solves"] += 1
    run = runners[solver]
    B0 = jnp.zeros((K, _pdim(x, family, intercept)), dtype=dt)
    extra_kw = (
        {} if solver == "proximal_grad" else {"line_search": line_search}
    )
    if solver == "lbfgs":
        extra_kw["objective"] = objective

    return _sweep_lanes(
        x, yd, mask, lam_v, B0, jnp.int32(max_iter), jnp.asarray(tol, dt),
        run=run, family=family, reg=reg, extra_kw=tuple(extra_kw.items()),
        counts=return_counts)


@partial(jax.jit, static_argnames=(
    "run", "family", "reg", "extra_kw", "counts"))
def _sweep_lanes(x, yv, mask, lams, B0, max_iter, tol, *, run, family, reg,
                 extra_kw, counts=False):
    """``lambda_sweep``'s K lanes of one whole-solve runner, vmapped over
    ``lams`` and their starts: a program of its own name, so that a trace
    tells the lanes (``jit__sweep_lanes``) from the single solve the
    runner is elsewhere (``jit__lbfgs_run``: a search's refit)."""

    def one(lam, b0):
        beta, n_it = run(
            x, yv, mask, b0, lam, max_iter, tol, family=family, reg=reg,
            **dict(extra_kw),
        )
        return beta, jnp.atleast_1d(n_it) if counts else _iterations(n_it)

    return jax.vmap(one)(lams, B0)


def _exit_counts(st):
    """One finished L-BFGS solve as ``int32[4]`` counts by
    ``lbfgs_core.EXITS``: 1 at its ``LBFGSState.reason``."""
    return (jnp.arange(len(EXITS)) == st.reason).astype(jnp.int32)
