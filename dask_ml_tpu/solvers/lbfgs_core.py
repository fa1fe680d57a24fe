"""Jit-safe L-BFGS.

The reference's ADMM and lbfgs solvers call ``scipy.optimize.fmin_l_bfgs_b``
on the host / on workers (``dask_glm/algorithms.py :: admm, lbfgs``).  A
scipy callback cannot live inside an XLA program, so this is a from-scratch
L-BFGS built for tracing: fixed-size circular (s, y) history, two-loop
recursion as ``lax.fori_loop``, the whole optimizer one ``lax.while_loop``
— usable inside ``jit``, ``shard_map`` (ADMM's per-shard local solves), and
``vmap`` (many small models at once).

Two weak-Wolfe line-search strategies, selected STATICALLY per context
(:func:`run_line_search`):

* ``backtrack`` (the default, and REQUIRED under vmap — packed
  one-vs-rest, model cohorts): classic backtrack-then-expand while_loops.
  Under vmap lanes run in lockstep (masked) at the max lane's probe
  count; a ``lax.cond`` grid would execute both branches in every lane.
* ``probe_grid`` (opt-in for sequential solves): probe the unit step,
  else evaluate EVERY candidate step 2^k in one vmapped value_and_grad
  call — XLA batches the candidate matvecs into two S-column gemm
  passes, so the whole backtrack-and-expand cascade costs ~two
  design-matrix passes regardless of how many probes sequential search
  would have made.  Honest CPU measurement (100k x 16 logistic,
  controlled, interleaved): backtrack 0.29 s vs probe_grid 0.77 s for 4
  sequential solves — the grid pays all 34 candidates whenever the unit
  probe fails, which on small compute-bound problems outweighs the saved
  passes.  On big bandwidth-bound TPU solves the accounting reverses ON
  PAPER (2 X-passes vs 4+ per backtracking iteration); the default stays
  backtrack until bench.py's ``line_search`` extra measures the delta on
  a live chip ("measure before claiming" — the Pallas-Lloyd precedent).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class LBFGSState(NamedTuple):
    x: jax.Array
    f: jax.Array
    g: jax.Array
    S: jax.Array  # (m, d) s-history (circular)
    Y: jax.Array  # (m, d) y-history
    rho: jax.Array  # (m,)
    k: jax.Array  # iterations taken
    n_updates: jax.Array  # history entries written
    converged: jax.Array
    # calls of the objective or of value_and_grad at one point, a batched
    # grid of candidate steps counting once: each reads the data at least
    # once, so this is a lower bound on passes over the design matrix
    n_evals: jax.Array


def _two_loop(g, S, Y, rho, n_updates, m):
    """Two-loop recursion over the circular history → descent direction."""
    write_pos = n_updates % m
    # order newest → oldest: newest is at write_pos - 1
    order = (write_pos - 1 - jnp.arange(m)) % m
    valid = jnp.arange(m) < jnp.minimum(n_updates, m)

    def bwd(i, carry):
        q, alphas = carry
        j = order[i]
        a = jnp.where(valid[i], rho[j] * jnp.dot(S[j], q), 0.0)
        q = q - a * Y[j]
        return q, alphas.at[i].set(a)

    q, alphas = lax.fori_loop(0, m, bwd, (g, jnp.zeros(m, dtype=g.dtype)))

    newest = (write_pos - 1) % m
    sy = jnp.dot(S[newest], Y[newest])
    yy = jnp.dot(Y[newest], Y[newest])
    gamma = jnp.where(n_updates > 0, sy / jnp.maximum(yy, 1e-12), 1.0)
    r = gamma * q

    def fwd(i, r):
        ii = m - 1 - i  # oldest → newest
        j = order[ii]
        b = rho[j] * jnp.dot(Y[j], r)
        return r + jnp.where(valid[ii], (alphas[ii] - b), 0.0) * S[j]

    return lax.fori_loop(0, m, fwd, r)


def _backtrack_wolfe(value_and_grad, x, f0, g, p, c1, c2, max_backtracks):
    """Sequential weak-Wolfe search: Armijo backtracking, then step
    expansion while the curvature condition gᵀ(x+tp)·p ≥ c2·gᵀp fails but
    Armijo still holds at 2t.  Guarantees useful s·y on accepted steps so
    the L-BFGS history builds even in curved nonconvex valleys.

    The strategy for VMAPPED contexts (packed one-vs-rest, model
    cohorts): a ``lax.cond`` grid under vmap executes both branches in
    every lane, so probe_grid would pay the full grid per lane per
    iteration; these while_loops run lanes in lockstep (masked) at the
    max lane's probe count, which measures far cheaper for packed solves.
    """
    fun = lambda z: value_and_grad(z)[0]  # noqa: E731
    dg = jnp.dot(g, p)

    def bt_cond(carry):
        t, f_new, j = carry
        armijo = f_new <= f0 + c1 * t * dg
        return jnp.logical_not(armijo) & (j < max_backtracks)

    def bt_body(carry):
        t, _, j = carry
        t = 0.5 * t
        return t, fun(x + t * p), j + 1

    t0 = jnp.asarray(1.0, dtype=f0.dtype)
    t, f_new, j = lax.while_loop(bt_cond, bt_body, (t0, fun(x + p), 0))
    n_evals = 1 + j  # the unit step, then one objective call a backtrack
    failed = (j >= max_backtracks) & (f_new > f0 + c1 * t * dg)
    t = jnp.where(failed, 0.0, t)
    f_new = jnp.where(failed, f0, f_new)

    if c2 is not None:  # static: Armijo-only callers skip the expansion

        def ex_cond(carry):
            t, f_t, j = carry
            g_t = value_and_grad(x + t * p)[1]
            curv_ok = jnp.dot(g_t, p) >= c2 * dg
            t2 = 2.0 * t
            armijo2 = fun(x + t2 * p) <= f0 + c1 * t2 * dg
            return jnp.logical_not(curv_ok) & armijo2 & (j < 8) & (t > 0)

        def ex_body(carry):
            t, _, j = carry
            t = 2.0 * t
            return t, fun(x + t * p), j + 1

        t, f_new, j_ex = lax.while_loop(ex_cond, ex_body, (t, f_new, 0))
        # every test of the condition (one more than the expansions
        # taken) evaluates the gradient at t and the objective at 2t;
        # every expansion evaluates the objective once more
        n_evals = n_evals + 2 * (j_ex + 1) + j_ex
    return t, f_new, None, failed, n_evals


def run_line_search(strategy, value_and_grad, x, f0, g, p, c1,
                    max_backtracks, c2=0.9):
    """Dispatch on the STATIC strategy string.

    Returns ``(t, f_new, g_new_or_None, failed, n_evals)`` —
    ``probe_grid`` already evaluated the gradient at the accepted step
    and returns it (saving the caller's recompute pass); ``backtrack``
    returns None and the caller evaluates once at ``x + t p``.
    ``n_evals`` counts the search's own calls of the objective or of
    ``value_and_grad`` (see :class:`LBFGSState`).

    With the weak-Wolfe conditions (Armijo + curvature
    gᵀ(x+tp)·p ≥ c2·gᵀp); ``c2=None`` (STATIC) disables the curvature
    test entirely — pure Armijo, the gradient-descent/newton semantics.
    ``probe_grid`` (sequential contexts): unit-step probe, then one
    batched grid over every candidate step — fewest objective passes
    when the data is big.  ``backtrack`` (vmapped contexts): classic
    sequential backtrack-then-expand in lockstep across lanes.
    """
    if strategy == "backtrack":
        return _backtrack_wolfe(
            value_and_grad, x, f0, g, p, c1, c2, max_backtracks
        )
    if strategy == "probe_grid":
        return _grid_line_search(
            value_and_grad, x, f0, g, p, c1, c2, max_backtracks
        )
    raise ValueError(
        f"line_search must be 'probe_grid' or 'backtrack'; got {strategy!r}"
    )


def _grid_line_search(value_and_grad, x, f0, g, p, c1, c2, max_backtracks,
                      expansions=3):
    """Weak-Wolfe line search over a geometric step grid, batched evals.

    Candidates t_j = 2^(expansions-j), j = 0..expansions+max_backtracks
    (the same 2^-max_backtracks floor sequential backtracking reached,
    plus >1 expansion steps standing in for the sequential expansion
    phase).  All candidate values AND directional derivatives come from
    one ``vmap``'d value_and_grad call — for GLM losses XLA batches the
    candidate matvecs into two S-column gemm passes, so the whole
    backtrack-and-expand cascade costs ~two design-matrix passes.
    Selection prefers the LARGEST step satisfying Armijo + curvature
    (full weak Wolfe — keeps s·y useful so the L-BFGS history builds in
    curved valleys); if no candidate passes curvature, the largest
    Armijo-passing step; (0, f0, failed=True) when even Armijo never
    holds.  NaN/inf values fail the comparisons and are skipped.
    """
    dg = jnp.dot(g, p)
    # phase 1: probe the unit step alone — L-BFGS accepts t=1 in the
    # large majority of iterations once the history warms up, and a
    # single-candidate eval costs a fraction of the batched grid
    f1, g1 = value_and_grad(x + p)
    unit_ok = f1 <= f0 + c1 * dg
    if c2 is not None:
        unit_ok = unit_ok & (jnp.dot(g1, p) >= c2 * dg)

    def accept_unit(_):
        one = jnp.asarray(1.0, f0.dtype)
        return one, f1, g1, jnp.asarray(False), jnp.asarray(1)

    def grid(_):
        n_steps = expansions + 1 + max_backtracks
        ts = jnp.exp2(expansions - jnp.arange(n_steps)).astype(f0.dtype)
        fs, gs = jax.vmap(lambda t: value_and_grad(x + t * p))(ts)
        armijo = fs <= f0 + c1 * ts * dg
        any_a = jnp.any(armijo)
        # descending ts: argmax = first True = largest passing step
        if c2 is not None:
            wolfe = armijo & (gs @ p >= c2 * dg)
            idx = jnp.where(jnp.any(wolfe), jnp.argmax(wolfe),
                            jnp.argmax(armijo))
        else:
            idx = jnp.argmax(armijo)
        t = jnp.where(any_a, ts[idx], 0.0)
        f_new = jnp.where(any_a, fs[idx], f0)
        # failed: x_new == x, so the caller's current gradient is exact
        g_new = jnp.where(any_a, gs[idx], g)
        # the unit probe, and all candidates in one batched call
        return t, f_new, g_new, jnp.logical_not(any_a), jnp.asarray(2)

    return lax.cond(unit_ok, accept_unit, grid, None)


def lbfgs_minimize(
    fun: Callable,
    x0,
    *,
    max_iter: int = 100,
    tol: float = 1e-5,
    history: int = 10,
    c1: float = 1e-4,
    max_backtracks: int = 30,
    line_search: str = "backtrack",
):
    """Minimize a traceable scalar function; returns (x, LBFGSState).

    Convergence: ‖g‖_∞ ≤ tol (scipy's ``pgtol``), OR relative objective
    decrease ≤ 10·eps(dtype) (scipy's ``factr``-style stagnation exit,
    active only when ``tol > 0``): in fp32 a sum-scaled objective's
    gradient often cannot be certified below ~1e-4 even AT the optimum
    (rounding noise in the gradient evaluation exceeds it — scipy's own
    L-BFGS-B stops with a larger ‖g‖∞ on the same data), so a solve
    that has numerically converged must not burn max_iter failing the
    pgtol test.  ``tol = 0`` disables both CONVERGENCE tests (the
    line-search-failure exit still fires — a lane that cannot take any
    step has no further work worth timing), which is how the bench gets
    its fixed-iteration-count runs.
    ``line_search``: ``backtrack`` (default — the measured-safe choice on
    CPU; REQUIRED under ``vmap``) or ``probe_grid`` (batched grid — the
    bandwidth-optimal candidate for big-n TPU solves; flip per solve via
    ``solver_kwargs`` once the chip delta is measured — see
    :func:`run_line_search` and bench.py's ``line_search`` extra).
    """
    value_and_grad = jax.value_and_grad(fun)
    m = history
    d = x0.shape[0]
    f0, g0 = value_and_grad(x0)
    dtype = f0.dtype

    init = LBFGSState(
        x=x0,
        f=f0,
        g=g0,
        S=jnp.zeros((m, d), dtype=x0.dtype),
        Y=jnp.zeros((m, d), dtype=x0.dtype),
        rho=jnp.zeros((m,), dtype=dtype),
        k=jnp.asarray(0),
        n_updates=jnp.asarray(0),
        converged=jnp.max(jnp.abs(g0)) <= tol,
        n_evals=jnp.asarray(1),
    )

    def cond(st: LBFGSState):
        return (st.k < max_iter) & jnp.logical_not(st.converged)

    # the named scopes are the boundaries the device trace names after
    # any refactor (XProf's op names under ``diagnostics.trace()``)
    def body(st: LBFGSState):
        with jax.named_scope("lbfgs.direction"):
            p = -_two_loop(st.g, st.S, st.Y, st.rho, st.n_updates, m)
            # safeguard: if p is not a descent direction, use -g
            descent = jnp.dot(p, st.g) < 0
            p = jnp.where(descent, p, -st.g)
        with jax.named_scope("lbfgs.line_search"):
            t, f_ls, g_ls, failed, n_evals = run_line_search(
                line_search, value_and_grad, st.x, st.f, st.g, p, c1,
                max_backtracks,
            )
            x_new = st.x + t * p
            if g_ls is None:  # static per strategy: backtrack re-evaluates
                f_new, g_new = value_and_grad(x_new)
                n_evals = n_evals + 1
            else:  # probe_grid already evaluated (f, g) at the accepted step
                f_new, g_new = f_ls, g_ls
        with jax.named_scope("lbfgs.update"):
            s = x_new - st.x
            y = g_new - st.g
            sy = jnp.dot(s, y)
            # relative curvature condition: an absolute threshold rejects the
            # small-but-informative steps taken in narrow valleys
            good = sy > 1e-10 * jnp.linalg.norm(s) * jnp.linalg.norm(y)
            pos = st.n_updates % m
            S = jnp.where(good, st.S.at[pos].set(s), st.S)
            Y = jnp.where(good, st.Y.at[pos].set(y), st.Y)
            rho = jnp.where(
                good, st.rho.at[pos].set(1.0 / jnp.maximum(sy, 1e-12)),
                st.rho)
            n_updates = st.n_updates + jnp.where(good, 1, 0)
            rel_dec = (st.f - f_new) / jnp.maximum(
                jnp.maximum(jnp.abs(st.f), jnp.abs(f_new)), 1.0
            )
            stalled = (tol > 0) & (
                rel_dec <= 10.0 * jnp.finfo(dtype).eps
            )
            converged = (jnp.max(jnp.abs(g_new)) <= tol) | failed | stalled
            return LBFGSState(
                x=x_new, f=f_new, g=g_new, S=S, Y=Y, rho=rho,
                k=st.k + 1, n_updates=n_updates, converged=converged,
                n_evals=st.n_evals + n_evals,
            )

    final = lax.while_loop(cond, body, init)
    return final.x, final
