"""Jit-safe L-BFGS.

The reference's ADMM and lbfgs solvers call ``scipy.optimize.fmin_l_bfgs_b``
on the host / on workers (``dask_glm/algorithms.py :: admm, lbfgs``).  A
scipy callback cannot live inside an XLA program, so this is a from-scratch
L-BFGS built for tracing: fixed-size circular (s, y) history, two-loop
recursion as ``lax.fori_loop``, the whole optimizer one ``lax.while_loop``
— usable inside ``jit``, ``shard_map`` (ADMM's per-shard local solves), and
``vmap`` (many small models at once).

Two weak-Wolfe line-search strategies, selected STATICALLY per context
(:func:`run_line_search`), and two ways to evaluate a trial step, selected
by the KIND of objective (:func:`lbfgs_minimize`).  The strategies are one
piece of code over a scalar function ``phi(t)`` of the step:

* ``backtrack`` (the default, and REQUIRED under vmap — packed
  one-vs-rest, model cohorts): classic backtrack-then-expand while_loops.
  Under vmap lanes run in lockstep (masked) at the max lane's probe
  count; a ``lax.cond`` grid would execute both branches in every lane.
  It accepts the largest step ``2^-k <= 1`` that passes Armijo, doubled
  while the curvature test fails and Armijo holds above, and takes no
  trial whose outcome it already knows (:func:`_backtrack_wolfe`): no
  curvature test after a halving, the slope before the value at 2t,
  and, where an iteration has no history and the objective is a
  :class:`LinearObjective`, a first look at the power of two the
  curvature along the line points to (:func:`_start_exponent`) in the
  place of some twenty halvings from 1.
* ``probe_grid`` (opt-in for sequential solves): probe the unit step,
  else evaluate EVERY candidate step 2^k in one vmapped call of ``phi``.

How ``phi`` is made is what differs between the kinds of objective:

* a black-box callable: ``phi(t)`` evaluates the objective and its
  gradient at ``x + t p``, so every trial reads whatever the objective
  reads (for a GLM loss, the whole design matrix);
* a :class:`LinearObjective` (a pointwise function of a LINEAR map of
  the parameters, plus a smooth term in the parameters alone): one
  product gives the map of ``x`` and of ``p`` together, every trial is
  then a reduction over vectors of the map's length, and one transposed
  product gives the gradient at the accepted step.  An iteration reads
  the data twice whatever the search does.

What each costs on the chip is measured, not argued: PERF.md sections 5
and 6 (the ``admm-higgs`` cells run the two strategies).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

#: curvature constant of the weak-Wolfe test
_C2 = 0.9
#: why :func:`lbfgs_minimize` ended, as ``LBFGSState.reason`` holds it:
#: an index into :data:`EXITS`
EXIT_GTOL, EXIT_STALLED, EXIT_FAILED, EXIT_BUDGET = range(4)
EXITS = ("gtol", "stalled", "failed", "budget")


class LinearObjective(NamedTuple):
    """``f(b) = pointwise(image(b)) + smooth(b)``: an objective whose only
    read of the data is a LINEAR map of the parameters.

    ``predict`` takes a few parameter vectors and returns the tuple of
    their images in ONE read of the data (``Family.products`` of their
    weights); ``pointwise`` maps one image to a scalar
    (``Family.pointwise_loss`` with the targets and the mask closed
    over); ``smooth`` is the part that sees the parameters alone (a
    penalty; ADMM's proximity term).  ``offset``, where there is one, is
    the part of the map that reads no data: a linear function of the
    parameters whose value (a GLM's intercept: a scalar, or one number a
    class) is added to every row of the image, ``image(b) =
    predict(b)[0] + offset(b)``.  It is kept apart so that it can be
    added where an image is consumed; folded into ``predict`` it would
    cost each search two more passes over vectors of the image's length.  Calling the objective composes
    the parts, so it also serves wherever a black box is wanted.
    """

    predict: Callable
    pointwise: Callable
    smooth: Callable
    offset: Callable | None = None

    def image(self, b):
        eta, = self.predict(b)
        return eta if self.offset is None else eta + self.offset(b)

    def __call__(self, b):
        return self.pointwise(self.image(b)) + self.smooth(b)


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
    # operations that read the data, so a lower bound on passes over the
    # design matrix.  A black-box objective: its calls, or value_and_grad's,
    # at one point, a batched grid of candidate steps counting once.  A
    # LinearObjective: the first value_and_grad, then the product and the
    # transposed product of each iteration (1 + 2 k), never a trial
    n_evals: jax.Array
    # a LinearObjective's trials: reductions over the cached images that
    # the searches took (a value of phi, its slope, or the curvature at
    # the start of the line; a batched grid counting once); 0 for a black
    # box, whose trials are in n_evals
    n_trials: jax.Array
    # those of n_trials taken in searches that started from the
    # curvature's guess (iterations with no history, under backtrack):
    # the curvature's reduction, the first look, every halving or
    # doubling, the curvature test where the walk up reached 1.  How far
    # the guess stood from the answer
    n_guided: jax.Array
    # why the loop ended, an index into EXITS (lbfgs_minimize has the
    # rule), and how far the last point stood from the two convergence
    # tests: max|g| there (compare with ``tol``), and the last
    # iteration's relative decrease of the objective (compare with
    # :func:`stall_threshold`; inf where no iteration was taken)
    reason: jax.Array
    g_max: jax.Array
    rel_dec: jax.Array


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


def _black_box_phi(value_and_grad, x, p):
    """``phi(t) -> (f, slope, gradient)`` at ``x + t p`` by evaluating the
    objective there: every call reads what the objective reads."""

    def phi(t):
        f, g = value_and_grad(x + t * p)
        return f, jnp.dot(g, p), g

    return phi


def _cached_phi(obj: LinearObjective, x, p):
    """``phi(t) -> (f, slope, ())`` along ``x + t p`` for a
    :class:`LinearObjective`, from ONE product: the images ``eta`` of
    ``x`` and ``u`` of ``p`` (the image of ``x + t p`` is ``eta + t u``,
    plus the offset's ``c + t q`` where the objective has one: a scalar
    made inside each trial, never a pass over ``eta`` or ``u``).
    Also returns ``gradient_at(t)``, the objective's gradient at
    ``x + t p`` by one transposed product of the pointwise derivative at
    that image, and ``curvature()``, ``phi''(0)``: the derivative of the
    slope at the start of the line, one more reduction over the cached
    images (what a trial reads, less the targets where the pointwise
    loss is linear in them).  The images live for one search: none is
    carried between iterations, so none drifts from its product."""
    eta, u = obj.predict(x, p)
    if obj.offset is None:
        def offset_at(t):
            return None
    else:
        c, q = obj.offset(x), obj.offset(p)

        def offset_at(t):
            return c + t * q

    def image(t, s):
        return eta + t * u if s is None else eta + t * u + s

    def value(t, s=None):
        return obj.pointwise(image(t, s)) + obj.smooth(x + t * p)

    def phi(t):
        if obj.offset is None:
            f, slope = jax.jvp(value, (t,), (jnp.ones_like(t),))
        else:
            # the derivatives in the step and in the offset apart, by one
            # reverse pass.  As ONE tangent, ``u + q`` is a vector of the
            # image's length, which a batched search (probe_grid) writes
            # out and reads back (125 MB more of temporaries at 31M
            # rows); as two forward passes the batched grid carries a
            # third result and ran 15% longer on the chip (PERF.md
            # section 6, PR 33)
            f, (in_step, in_offset) = jax.value_and_grad(
                value, argnums=(0, 1))(t, offset_at(t))
            slope = in_step + jnp.sum(q * in_offset)
        return f, slope, ()

    def gradient_at(t):
        r = jax.grad(obj.pointwise)(image(t, offset_at(t)))
        (g,) = jax.linear_transpose(obj.image, x)(r)
        return g + jax.grad(obj.smooth)(x + t * p)

    def curvature():
        zero = jnp.zeros((), eta.dtype)
        return jax.jvp(lambda t: phi(t)[1], (zero,), (jnp.ones_like(zero),))[1]

    return phi, gradient_at, curvature


def _powers_of_two(n, dtype):
    """``[1, 1/2, ..., 2^-n]``, made on the host so that every entry is
    the power of two to the last bit (``exp2`` on the device is not:
    it read 3.8146970e-06 for 2^-18, and a step that is off in its last
    digits moves every later step of the solve)."""
    return jnp.asarray(np.ldexp(1.0, -np.arange(n + 1)), dtype)


def _start_exponent(curvature, dg, c1, max_backtracks):
    """``k`` of the step ``2^-k`` a history-less search starts from: the
    largest ``2^-k <= 1`` that passes Armijo on the parabola through
    ``phi(0)`` with slope ``dg`` and second derivative ``curvature``
    (``t <= 2 (1 - c1) (-dg) / curvature``), no smaller than the floor
    ``2^-max_backtracks``.  A curvature that is not a positive number
    gives 0, the unit step: the search as it is without a guess."""
    bound = 2.0 * (1.0 - c1) * (-dg) / curvature
    # how many of 1, 1/2, ... stand over the bound (none where it is no
    # number): the first that does not is the start
    k = jnp.sum(_powers_of_two(max_backtracks, bound.dtype) > bound)
    usable = jnp.isfinite(curvature) & (curvature > 0)
    return jnp.where(usable, jnp.minimum(k, max_backtracks), 0)


def _backtrack_wolfe(phi, f0, dg, c1, c2, max_backtracks, start=None):
    """Sequential weak-Wolfe search: the largest step ``2^-k <= 1`` that
    passes Armijo, then doublings while the curvature condition
    phi'(t) ≥ c2·phi'(0) fails but Armijo still holds at 2t.  Guarantees
    useful s·y on accepted steps so the L-BFGS history builds even in
    curved nonconvex valleys.

    It takes no trial whose outcome it knows.  The first look is at
    ``2^-start`` (``start``: a traced exponent from
    :func:`_start_exponent`; None, STATIC, is the unit step and leaves
    the walk up out of the program).  Where Armijo fails there the step
    halves until it holds, down to the floor ``2^-max_backtracks``;
    where it holds under 1 the step doubles while Armijo holds at 2t, up
    to 1.  Along a convex ``phi`` the steps that pass Armijo are an
    interval from 0, so either walk ends on the step a search from 1
    ends on.  After a halving, or a doubling that was refused, 2t has
    failed Armijo in this very search: no expansion can follow, so the
    curvature is not tested.  Where nothing is known above ``t`` (the
    unit step accepted at once, or a walk up that reached it) the slope
    at ``t`` comes first, the value at 2t only where the curvature test
    fails, and a step the expansion moves to keeps the value just taken.

    The strategy for VMAPPED contexts (packed one-vs-rest, model
    cohorts): a ``lax.cond`` grid under vmap executes both branches in
    every lane, so probe_grid would pay the full grid per lane per
    iteration; these while_loops run lanes in lockstep (masked) at the
    max lane's probe count, which measures far cheaper for packed solves
    (there the expansion's ``cond`` is a select and every test takes the
    value with the slope, as every test did before).
    """
    value = lambda t: phi(t)[0]  # noqa: E731
    slope = lambda t: phi(t)[1]  # noqa: E731
    armijo = lambda t, f: f <= f0 + c1 * t * dg  # noqa: E731

    first = 0 if start is None else start
    t0 = _powers_of_two(max_backtracks, f0.dtype)[first]
    halvings = max_backtracks - first  # what is left down to the floor

    def bt_cond(carry):
        t, f_new, j = carry
        return jnp.logical_not(armijo(t, f_new)) & (j < halvings)

    def bt_body(carry):
        t, _, j = carry
        t = 0.5 * t
        return t, value(t), j + 1

    f_first = value(t0)
    t, f_new, j = lax.while_loop(bt_cond, bt_body, (t0, f_first, 0))
    n_calls = 1 + j  # the first look, then one value a backtrack
    # (not "Armijo fails": a value that is no number at the floor is
    # handed back as it always was)
    failed = (j >= halvings) & (f_new > f0 + c1 * t * dg)
    t = jnp.where(failed, 0.0, t)
    f_new = jnp.where(failed, f0, f_new)
    fails_above = j > 0  # 2t is the step the last halving left

    if start is not None:

        climbs = armijo(t0, f_first)  # so no halving was taken

        def up_cond(carry):
            t, _, _, refused = carry
            return climbs & jnp.logical_not(refused) & (t < 1)

        def up_body(carry):
            t, f_t, j, _ = carry
            t2 = 2.0 * t
            f_t2 = value(t2)
            holds = armijo(t2, f_t2)
            return (jnp.where(holds, t2, t), jnp.where(holds, f_t2, f_t),
                    j + 1, jnp.logical_not(holds))

        t, f_new, j_up, refused = lax.while_loop(
            up_cond, up_body, (t, f_new, 0, jnp.asarray(False)))
        n_calls = n_calls + j_up  # one value a look above
        fails_above = fails_above | refused

    if c2 is not None:  # static: Armijo-only callers skip the expansion

        def ex_body(carry):
            t, f_t, j, n, _ = carry
            curv_ok = slope(t) >= c2 * dg
            t2 = 2.0 * t

            def look_above():
                f_t2 = value(t2)
                return f_t2, armijo(t2, f_t2)

            f_t2, armijo2 = lax.cond(
                curv_ok, lambda: (f_t, jnp.asarray(False)), look_above)
            j = j + armijo2
            # the slope at t, and the value at 2t where the curvature
            # test failed; the step moved to brings that value along
            return (jnp.where(armijo2, t2, t), jnp.where(armijo2, f_t2, f_t),
                    j, n + jnp.where(curv_ok, 1, 2), armijo2 & (j < 8))

        t, f_new, _, n_expand, _ = lax.while_loop(
            lambda carry: carry[-1], ex_body,
            (t, f_new, 0, 0, jnp.logical_not(fails_above) & (t > 0)))
        n_calls = n_calls + n_expand
    return t, f_new, None, failed, n_calls


def _search(strategy, phi, f0, dg, c1, c2, max_backtracks, start=None):
    """The search's decisions over ``phi(t) -> (value, slope, aux)``,
    whichever way ``phi`` is made; ``dg`` is the slope at 0, ``start``
    the exponent ``backtrack`` takes its first look at
    (:func:`_start_exponent`; ``probe_grid`` takes none).  Returns
    ``(t, f_new, aux_or_None, failed, n_calls)``: ``probe_grid`` hands
    back the ``aux`` of the accepted step (a black box's gradient there),
    ``backtrack`` None; ``n_calls`` counts the calls of ``phi``, a
    batched grid counting once."""
    if strategy == "backtrack":
        return _backtrack_wolfe(phi, f0, dg, c1, c2, max_backtracks, start)
    if strategy == "probe_grid":
        return _grid_line_search(phi, f0, dg, c1, c2, max_backtracks)
    raise ValueError(
        f"line_search must be 'probe_grid' or 'backtrack'; got {strategy!r}"
    )


def run_line_search(strategy, value_and_grad, x, f0, g, p, c1,
                    max_backtracks, c2=_C2):
    """Line search on a black-box ``value_and_grad``, dispatched on the
    STATIC strategy string.

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
    batched grid over every candidate step.  ``backtrack`` (vmapped
    contexts): classic sequential backtrack-then-expand in lockstep
    across lanes.
    """
    t, f_new, g_new, failed, n_evals = _search(
        strategy, _black_box_phi(value_and_grad, x, p), f0, jnp.dot(g, p),
        c1, c2, max_backtracks)
    if g_new is not None:
        # failed: x_new == x, so the caller's current gradient is exact
        g_new = jnp.where(failed, g, g_new)
    return t, f_new, g_new, failed, n_evals


def _grid_line_search(phi, f0, dg, c1, c2, max_backtracks, expansions=3):
    """Weak-Wolfe line search over a geometric step grid, batched evals.

    Candidates t_j = 2^(expansions-j), j = 0..expansions+max_backtracks
    (the same 2^-max_backtracks floor sequential backtracking reached,
    plus >1 expansion steps standing in for the sequential expansion
    phase).  All candidate values AND slopes come from one ``vmap``'d
    call of ``phi``.
    Selection prefers the LARGEST step satisfying Armijo + curvature
    (full weak Wolfe — keeps s·y useful so the L-BFGS history builds in
    curved valleys); if no candidate passes curvature, the largest
    Armijo-passing step; (0, f0, failed=True) when even Armijo never
    holds.  NaN/inf values fail the comparisons and are skipped.
    """
    # phase 1: probe the unit step alone — L-BFGS accepts t=1 in the
    # large majority of iterations once the history warms up, and a
    # single-candidate eval costs a fraction of the batched grid
    one = jnp.asarray(1.0, f0.dtype)
    f1, s1, aux1 = phi(one)
    unit_ok = f1 <= f0 + c1 * dg
    if c2 is not None:
        unit_ok = unit_ok & (s1 >= c2 * dg)

    def accept_unit(_):
        return one, f1, aux1, jnp.asarray(False), jnp.asarray(1)

    def grid(_):
        n_steps = expansions + 1 + max_backtracks
        ts = jnp.exp2(expansions - jnp.arange(n_steps)).astype(f0.dtype)
        fs, slopes, auxs = jax.vmap(phi)(ts)
        armijo = fs <= f0 + c1 * ts * dg
        any_a = jnp.any(armijo)
        # descending ts: argmax = first True = largest passing step
        if c2 is not None:
            wolfe = armijo & (slopes >= c2 * dg)
            idx = jnp.where(jnp.any(wolfe), jnp.argmax(wolfe),
                            jnp.argmax(armijo))
        else:
            idx = jnp.argmax(armijo)
        t = jnp.where(any_a, ts[idx], 0.0)
        f_new = jnp.where(any_a, fs[idx], f0)
        aux = jax.tree_util.tree_map(lambda a: a[idx], auxs)
        # the unit probe, and all candidates in one batched call
        return t, f_new, aux, jnp.logical_not(any_a), jnp.asarray(2)

    return lax.cond(unit_ok, accept_unit, grid, None)


def lbfgs_minimize(
    fun: Callable | LinearObjective,
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

    ``fun`` is a black-box callable, or a :class:`LinearObjective`: then
    an iteration reads the data twice (the images of ``x`` and ``p`` in
    one product before the search, one transposed product for the
    gradient at the accepted step) and every trial of the search works
    on the cached images.  Direction, history update, the Wolfe tests
    and the four exits are the same for both.  Under ``vmap`` the
    cached images are lanes x their length, and the products are the
    caller's to form (``algorithms._lbfgs_lane``).

    Convergence: ‖g‖_∞ ≤ tol (scipy's ``pgtol``), OR relative objective
    decrease ≤ 10·eps(dtype) (scipy's ``factr``-style stagnation exit,
    active only when ``tol > 0``): in fp32 a sum-scaled objective's
    gradient often cannot be certified below ~1e-4 even AT the optimum
    (rounding noise in the gradient evaluation exceeds it — scipy's own
    L-BFGS-B stops with a larger ‖g‖∞ on the same data), so a solve
    that has numerically converged must not burn max_iter failing the
    pgtol test.  ``tol = 0`` disables both CONVERGENCE tests (the
    line-search-failure exit still fires — a lane that cannot take any
    step has no further work worth timing), which gives a caller a
    fixed iteration count.

    Why the loop ended is ``LBFGSState.reason``, one of :data:`EXITS` by
    its index, beside the loop's flag ``converged``.  Where several
    tests hold at once the first of these names the exit: ``failed`` (no
    step passed Armijo, so ``t = 0``: the point did not move, and the
    objective's decrease, 0, would read as a stall too), then ``gtol``
    (‖g‖_∞ ≤ tol; also a start that already passes it, ``k`` 0), then
    ``stalled`` (``tol > 0`` and the relative decrease ≤
    :func:`stall_threshold`); ``budget`` is the loop that ended at
    ``max_iter`` with none of them.  ``g_max`` and ``rel_dec`` are the
    two quantities those tests read at the last point.  Under ``vmap``
    each lane has its own.
    ``line_search``: ``backtrack`` (default; REQUIRED under ``vmap``) or
    ``probe_grid`` (batched grid); what each costs is in PERF.md
    section 5.
    """
    linear = isinstance(fun, LinearObjective)
    value_and_grad = jax.value_and_grad(fun)
    m = history
    d = x0.shape[0]
    f0, g0 = value_and_grad(x0)
    dtype = f0.dtype
    g0_max = jnp.max(jnp.abs(g0))
    certified0 = g0_max <= tol

    init = LBFGSState(
        x=x0,
        f=f0,
        g=g0,
        S=jnp.zeros((m, d), dtype=x0.dtype),
        Y=jnp.zeros((m, d), dtype=x0.dtype),
        rho=jnp.zeros((m,), dtype=dtype),
        k=jnp.asarray(0),
        n_updates=jnp.asarray(0),
        converged=certified0,
        n_evals=jnp.asarray(1),
        n_trials=jnp.asarray(0),
        n_guided=jnp.asarray(0),
        reason=jnp.select([certified0], [EXIT_GTOL], EXIT_BUDGET),
        g_max=g0_max,
        rel_dec=jnp.asarray(jnp.inf, dtype),
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
            if linear:  # static per kind of objective
                phi, gradient_at, curvature = _cached_phi(fun, st.x, p)
                dg = jnp.dot(st.g, p)
                start, guided = None, jnp.asarray(False)
                if line_search == "backtrack":
                    # no history: p = -g of a loss SUMMED over the rows,
                    # and the step that fits is some 4 / rows, twenty
                    # halvings under 1.  Here the curvature along the
                    # line costs one trial and says where to look first;
                    # a black box's would cost two reads of the data
                    guided = st.n_updates == 0
                    start = lax.cond(
                        guided,
                        lambda: _start_exponent(
                            curvature(), dg, c1, max_backtracks),
                        lambda: jnp.asarray(0))
                t, f_new, _, failed, n_trials = _search(
                    line_search, phi, st.f, dg, c1, _C2, max_backtracks,
                    start)
                n_trials = n_trials + guided  # the curvature's reduction
                n_guided = jnp.where(guided, n_trials, 0)
                x_new = st.x + t * p
                g_new = gradient_at(t)
                n_evals = 2  # the product and the transposed product
            else:
                t, f_ls, g_ls, failed, n_evals = run_line_search(
                    line_search, value_and_grad, st.x, st.f, st.g, p, c1,
                    max_backtracks,
                )
                n_trials = n_guided = 0
                x_new = st.x + t * p
                if g_ls is None:  # static per strategy: backtrack re-evaluates
                    f_new, g_new = value_and_grad(x_new)
                    n_evals = n_evals + 1
                else:  # probe_grid evaluated (f, g) at the accepted step
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
            stalled = (tol > 0) & (rel_dec <= stall_threshold(dtype))
            g_max = jnp.max(jnp.abs(g_new))
            certified = g_max <= tol
            converged = certified | failed | stalled
            # the first that holds names the exit
            reason = jnp.select(
                [failed, certified, stalled],
                [EXIT_FAILED, EXIT_GTOL, EXIT_STALLED], EXIT_BUDGET)
            return LBFGSState(
                x=x_new, f=f_new, g=g_new, S=S, Y=Y, rho=rho,
                k=st.k + 1, n_updates=n_updates, converged=converged,
                n_evals=st.n_evals + n_evals,
                n_trials=st.n_trials + n_trials,
                n_guided=st.n_guided + n_guided,
                reason=reason, g_max=g_max, rel_dec=rel_dec,
            )

    final = lax.while_loop(cond, body, init)
    return final.x, final


def stall_threshold(dtype):
    """The relative decrease of the objective at or under which
    :func:`lbfgs_minimize` takes an iteration for no decrease (the
    ``stalled`` exit): 10 eps of the objective's dtype."""
    return 10.0 * jnp.finfo(dtype).eps
