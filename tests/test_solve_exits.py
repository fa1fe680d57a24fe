"""Why a solve stopped (ISSUE 36): ``lbfgs_minimize`` driven to each of
its four exits, black box and ``LinearObjective``, both line searches
and under ``vmap``; the precedence where several tests hold at once;
ADMM's four exit counts and the last round's two ratios out of the
whole-solve program in the vector ``n_iter_`` comes in, on 1, 2 and 8
shards with pad rows, against a host-side replay of the last round and
against what the PARENT commit (4f18305) fitted on the same table, bit
for bit; ``glm.solve`` and the registry carrying the new names."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import diagnostics, obs, solvers
from dask_ml_tpu.core import device_mesh, shard_rows, use_mesh
from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.solvers.algorithms import (
    SOLVE_COUNTS, SOLVE_RATIOS, _lbfgs_objective, unpack_counts)
from dask_ml_tpu.solvers.families import Logistic
from dask_ml_tpu.solvers.lbfgs_core import (
    EXIT_BUDGET, EXIT_FAILED, EXIT_GTOL, EXIT_STALLED, EXITS,
    LinearObjective, lbfgs_minimize, stall_threshold)

EXIT_NAMES = ["exit_" + name for name in EXITS]
LINE_SEARCHES = ["backtrack", "probe_grid"]
TEN_EPS = float(stall_threshold(jnp.float32))


# ---- lbfgs_minimize: the four exits ----------------------------------------

def _logistic_parts(scale=1.0, n=400, d=3):
    """A small logistic loss SUMMED over its rows plus a ridge, as the
    parts of a ``LinearObjective``; ``scale`` stretches the table (the
    unit step along ``-g`` then overshoots by ``scale ** 2``)."""
    rng = np.random.RandomState(0)
    X = jnp.asarray(scale * rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.rand(n) < 0.5, jnp.float32)
    return LinearObjective(
        predict=lambda *bs: tuple(X @ b for b in bs),
        pointwise=lambda eta: jnp.sum(jnp.logaddexp(0.0, eta) - y * eta),
        smooth=lambda b: 0.5 * jnp.sum(b * b))


#: exit -> the arguments that drive ``lbfgs_minimize`` to it on
#: ``_logistic_parts``: a loose ``tol``; a ``tol`` no float32 gradient of
#: this loss can meet; a search with one halving on a table where the
#: step that fits lies far under 1/2; one iteration
DRIVEN = {
    "gtol": dict(tol=1e-1),
    "stalled": dict(tol=1e-30),
    "failed": dict(tol=1e-30, max_backtracks=1, scale=100.0),
    "budget": dict(tol=1e-30, max_iter=1),
}


def _drive(exit_name, kind, line_search):
    kw = dict(DRIVEN[exit_name])
    parts = _logistic_parts(kw.pop("scale", 1.0))
    fun = parts if kind == "linear" else (lambda b: parts(b))
    solve = jax.jit(lambda x0: lbfgs_minimize(
        fun, x0, line_search=line_search, **kw))
    x0 = jnp.full(3, 0.5, jnp.float32)
    return x0, kw, solve(x0)


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
@pytest.mark.parametrize("kind", ["black_box", "linear"])
@pytest.mark.parametrize("exit_name", list(DRIVEN))
def test_each_exit_is_named(exit_name, kind, line_search):
    """The reason beside the flag: the same lines serve a black box and
    a ``LinearObjective``, ``backtrack`` and ``probe_grid``; ``g_max``
    and ``rel_dec`` are the two numbers the tests read at the last
    point."""
    x0, kw, (x, st) = _drive(exit_name, kind, line_search)
    tol = kw["tol"]
    assert EXITS[int(st.reason)] == exit_name
    assert st.reason.dtype == jnp.int32
    assert bool(st.converged) == (exit_name != "budget")
    assert float(st.g_max) == float(jnp.max(jnp.abs(st.g)))
    if exit_name == "gtol":
        assert float(st.g_max) <= tol and int(st.k) >= 1
    else:
        assert float(st.g_max) > tol
    if exit_name == "stalled":
        assert 0 <= float(st.rel_dec) <= TEN_EPS and int(st.k) > 1
    if exit_name == "failed":
        # no step passed Armijo: the point stands where it stood
        assert int(st.k) == 1 and np.array_equal(x, x0)
        assert float(st.rel_dec) == 0.0
    if exit_name == "budget":
        assert int(st.k) == kw["max_iter"]
        assert float(st.rel_dec) > TEN_EPS


def _shifted_square(x):
    """``1 + x'x / 2``: the unit step along ``-g`` lands on the optimum,
    and a start near it lowers the objective by less than 10 eps of 1."""
    return 1.0 + 0.5 * jnp.sum(x * x)


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
class TestPrecedence:
    def test_failed_is_named_before_stalled(self, line_search):
        """A search that failed left ``t = 0``: the objective did not
        fall, so the stall test holds too (``tol > 0``), and the exit is
        ``failed``."""
        _, kw, (_, st) = _drive("failed", "linear", line_search)
        assert kw["tol"] > 0 and float(st.rel_dec) <= TEN_EPS
        assert int(st.reason) == EXIT_FAILED

    def test_gtol_is_named_before_stalled(self, line_search):
        """One step from (5e-4) to the optimum: the gradient there is 0
        (``gtol``) and the objective fell from 1 + 1.25e-7 to 1, one
        float32 ulp (``stalled``): the exit is ``gtol``."""
        _, st = lbfgs_minimize(
            _shifted_square, jnp.asarray([5e-4], jnp.float32), tol=1e-4,
            line_search=line_search)
        assert int(st.k) == 1 and float(st.g_max) <= 1e-4
        assert 0 < float(st.rel_dec) <= TEN_EPS
        assert int(st.reason) == EXIT_GTOL

    def test_a_start_that_passes_the_gradient_test_is_gtol(
            self, line_search):
        _, st = lbfgs_minimize(
            _shifted_square, jnp.asarray([5e-4], jnp.float32), tol=1e-3,
            line_search=line_search)
        assert int(st.k) == 0 and int(st.reason) == EXIT_GTOL
        assert bool(st.converged) and st.g_max == np.float32(5e-4)
        assert float(st.rel_dec) == np.inf  # no iteration, no decrease

    def test_no_budget_and_no_test_met_is_budget(self, line_search):
        """``max_iter=0``: the loop never ran and nothing was certified."""
        _, st = lbfgs_minimize(
            _shifted_square, jnp.ones(2, jnp.float32), tol=1e-3,
            max_iter=0, line_search=line_search)
        assert int(st.k) == 0 and int(st.reason) == EXIT_BUDGET
        assert not bool(st.converged)

    def test_tol_zero_turns_both_convergence_tests_off(self, line_search):
        """``tol = 0``: a caller's fixed iteration count.  The solve
        stands at its float32 floor long before ``max_iter``, where the
        stall test would hold, and is never named ``stalled``: it runs
        on to its ``budget``, unless a search fails on the way, which
        still ends it as ``failed``."""
        parts = _logistic_parts()
        _, st = lbfgs_minimize(
            lambda b: parts(b), jnp.full(3, 0.5, jnp.float32), tol=0.0,
            max_iter=25, line_search=line_search)
        assert float(st.rel_dec) <= TEN_EPS and float(st.g_max) > 0
        assert int(st.reason) == (
            EXIT_BUDGET if int(st.k) == 25 else EXIT_FAILED)
        assert int(st.reason) != EXIT_STALLED
        parts = _logistic_parts(100.0)
        _, st = lbfgs_minimize(
            parts, jnp.full(3, 0.5, jnp.float32), tol=0.0, max_iter=6,
            max_backtracks=1, line_search=line_search)
        assert int(st.k) == 1 and int(st.reason) == EXIT_FAILED


# ---- under vmap: a lane's reason is its own --------------------------------

#: lane -> (curvatures, tol, max_iter): four lanes of ``a'x^2 / 2 + 1``
#: from (1, 1) that end four ways under three halvings
LANES = {
    "gtol": ((1.0, 2.0), 1e-3, 50),
    "stalled": ((1.0, 2.0), 1e-30, 50),
    "failed": ((1.0, 1000.0), 1e-3, 50),
    "budget": ((1.0, 10.0), 1e-30, 2),
}


def _lane(a, tol, max_iter, line_search):
    return lbfgs_minimize(
        lambda x: 1.0 + 0.5 * jnp.sum(a * x * x), jnp.ones(2, jnp.float32),
        tol=tol, max_iter=max_iter, max_backtracks=3,
        line_search=line_search)[1]


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
@pytest.mark.parametrize("lane", list(LANES))
def test_a_lane_under_vmap_ends_by_its_own_reason(lane, line_search):
    """Four lanes in one vmapped solve end four ways, each with the
    reason, the iteration count and the two numbers of the same solve
    run alone."""
    a, tol, max_iter = (jnp.asarray(v) for v in zip(*LANES.values()))
    st = jax.vmap(lambda a, t, m: _lane(a, t, m, line_search))(
        a.astype(jnp.float32), tol.astype(jnp.float32), max_iter)
    assert [EXITS[r] for r in st.reason.tolist()] == list(LANES)
    i = list(LANES).index(lane)
    alone = _lane(a[i].astype(jnp.float32), tol[i].astype(jnp.float32),
                  max_iter[i], line_search)
    assert EXITS[int(alone.reason)] == lane
    assert int(st.k[i]) == int(alone.k)
    np.testing.assert_allclose(st.g_max[i], alone.g_max, rtol=1e-6)
    np.testing.assert_allclose(st.rel_dec[i], alone.rel_dec, rtol=1e-5,
                               atol=1e-12)


# ---- ADMM: the counts and the ratios out of the whole-solve program --------

def _uneven(n=8003, d=6):
    """A table whose runs of rows pose local problems of unlike
    conditioning; 8,003 rows, so two and eight shards hold pad rows."""
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[n // 4: n // 2] *= 3.0
    X[3 * n // 4:] *= 0.3
    y = (X @ rng.normal(size=d) + rng.logistic(size=n) > 0)
    return X, y.astype(np.float32)


#: the names the parent's vector had, in its order
PARENTS_COUNTS = ("rounds", "inner_iters", "passes", "trials", "skew_passes",
                  "skew_trials", "rho_moves", "guided_trials")
PARENTS_RATIOS = ("primal_ratio", "dual_ratio", "rho_ratio")


def _bits(values):
    """float32 values as their bit patterns, in hex."""
    return " ".join(f"{b:08x}" for b in np.asarray(
        values, np.float32).ravel().view(np.uint32))


#: what the PARENT commit fitted on ``_uneven()`` with
#: ``LogisticRegression(solver="admm", C=10, max_iter=10,
#: solver_kwargs={"inner_iter": 30, "line_search": ...})`` and what
#: ``solvers.admm(lamduh=0.1, max_iter=10, inner_iter=30,
#: intercept=True, return_counts=True)`` counted (``counts`` in the order
#: of ``PARENTS_COUNTS``, ``ratios`` of ``PARENTS_RATIOS``; ``lbfgs`` as
#: the same estimator and solver call with ``solver="lbfgs"`` and no
#: ``max_iter`` / ``inner_iter``), float32 as bit patterns
#: (``/root/scratch/freeze.py`` of PR 36, run on the parent's tree).
#: ``canary`` is a float32 reduction this PR does not touch
#: (``Logistic.loss`` at a fixed point of the same table): where it
#: reads otherwise, the machine adds in another order than the one the
#: values were frozen on, and they say nothing
FROZEN = {
    "canary": "45993ac2",
    "admm-1-backtrack": dict(
        coef="bfa3349c bf28787d 3e977b69 bee14cb4 3f13dc06 3dffc89f",
        intercept="bb7cd204", n_iter=[4],
        counts=[4, 10, 24, 26, 0, 0, 1, 14],
        ratios="3d889840 3f1581fb 3ea1e880"),
    "admm-1-probe_grid": dict(
        coef="bfa3349c bf28787d 3e977b69 bee14cb4 3f13dc06 3dffc89f",
        intercept="bb7cd204", n_iter=[4],
        counts=[4, 10, 24, 16, 0, 0, 1, 0],
        ratios="3d889840 3f1581fb 3ea1e880"),
    "admm-2-backtrack": dict(
        coef="bfa4aa9e bf2996a7 3ea291ff bed00157 3f133198 3e0fdbd4",
        intercept="bc5144c7", n_iter=[10],
        counts=[10, 25, 60, 64, 16, 19, 4, 34],
        ratios="408f87f2 3f1908c3 41f2ca43"),
    "admm-2-probe_grid": dict(
        coef="bfa4aa9e bf2996a7 3ea291ff bed00157 3f133198 3e0fdbd4",
        intercept="bc5144c7", n_iter=[10],
        counts=[10, 25, 60, 39, 16, 11, 4, 0],
        ratios="408f87f2 3f1908c3 41f2ca43"),
    "admm-8-backtrack": dict(
        coef="bfa467c5 bf2bd36f 3e947e43 bede6b10 3f1406da 3e0f3403",
        intercept="bc35979e", n_iter=[10],
        counts=[10, 53, 116, 137, 50, 71, 1, 34],
        ratios="40e42d48 3e87e42d 41107d16"),
    "admm-8-probe_grid": dict(
        coef="bfa46bb6 bf2bdac9 3e94a5c7 bede8ff0 3f13fe44 3e0ef83d",
        intercept="bc2d3dbb", n_iter=[10],
        counts=[10, 53, 116, 71, 46, 31, 1, 0],
        ratios="40e4103f 3e9914a4 41107d16"),
    "lbfgs-backtrack": dict(
        coef="bfa34d0c bf288b5d 3e980a6e bee14d08 3f13f634 3dfcb9f9",
        intercept="bb8c3664", n_iter=[7],
        counts=[7, 7, 15, 17]),
    "lbfgs-probe_grid": dict(
        coef="bfa34d0c bf288b5d 3e980a6e bee14d08 3f13f634 3dfcb9f9",
        intercept="bb8c3664", n_iter=[7],
        counts=[7, 7, 15, 10]),
}


def _canary():
    X, y = _uneven()
    beta = jnp.asarray(np.linspace(-0.5, 0.5, 6), jnp.float32)
    return _bits(jax.jit(Logistic.loss)(
        beta, jnp.asarray(X), jnp.asarray(y), jnp.ones(len(y), jnp.float32)))


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
@pytest.mark.parametrize("shards", [1, 2, 8])
def test_admm_exits_sum_to_rounds_times_shards_and_the_fit_is_the_parents(
        shards, line_search):
    """One local solve a shard a round, each ended by one reason: the
    four counts sum to ``rounds x shards``.  And nothing of the path
    moved: ``coef_``, ``intercept_``, ``n_iter_``, every count the
    parent had and its three ratios are the parent's to the last bit."""
    X, y = _uneven()
    with use_mesh(device_mesh(shards)):
        sX, sy = shard_rows(X), shard_rows(y)
        assert (sX.data.shape[0] > len(y)) == (shards > 1)  # pad rows
        est = LogisticRegression(
            solver="admm", C=10.0, max_iter=10, solver_kwargs=dict(
                inner_iter=30, line_search=line_search)).fit(sX, sy)
        _, vector = solvers.admm(
            sX, y, lamduh=0.1, max_iter=10, inner_iter=30,
            line_search=line_search, intercept=True, return_counts=True)
    assert vector.dtype == jnp.int32
    assert vector.shape == (len(SOLVE_COUNTS) + len(SOLVE_RATIOS),)
    counts, ratios = unpack_counts(vector)
    assert sum(counts[n] for n in EXIT_NAMES) == counts["rounds"] * shards
    assert all(counts[n] >= 0 for n in EXIT_NAMES)
    assert ratios["grad_ratio"] >= 0 and ratios["dec_ratio"] >= 0
    if _canary() != FROZEN["canary"]:
        pytest.skip("another machine's float32 sums: the frozen values "
                    "were read on the one PR 36 was written on")
    want = FROZEN[f"admm-{shards}-{line_search}"]
    assert _bits(est.coef_) == want["coef"]
    assert _bits(est.intercept_) == want["intercept"]
    assert np.asarray(est.n_iter_).tolist() == want["n_iter"]
    assert [counts[n] for n in PARENTS_COUNTS] == want["counts"]
    assert _bits([ratios[n] for n in PARENTS_RATIOS]) == want["ratios"]


def _replay(X, y, shards, z, line_search, rho=1.0, inner_iter=30,
            inner_tol=1e-6):
    """One round's local solves made again outside the program, a shard
    at a time from the consensus ``z`` with duals 0 (the first round; or
    any round on ONE shard without a penalty, where ``z`` is the shard's
    own last answer and the dual stays 0 exactly): the largest over the
    shards of ``max|g| / inner_tol`` and ``rel_dec / (10 eps)``, and
    the reasons."""
    with use_mesh(device_mesh(shards)):
        sX = shard_rows(X)
    x, mask = np.asarray(sX.data), np.asarray(sX.mask)
    yv = np.pad(y, (0, len(mask) - len(y)))
    z = jnp.asarray(z, jnp.float32)
    rho = jnp.asarray(rho, jnp.float32)

    @jax.jit
    def solve(xb, yb, mb):
        obj = _lbfgs_objective(
            "linear", Logistic, xb, yb, mb,
            lambda b: 0.5 * rho * jnp.sum((b - z) ** 2), True)
        return lbfgs_minimize(obj, z, max_iter=inner_iter, tol=inner_tol,
                              line_search=line_search)[1]

    ends = [solve(*(jnp.asarray(part) for part in parts)) for parts in zip(
        np.split(x, shards), np.split(yv, shards), np.split(mask, shards))]
    return (max(float(st.g_max) / inner_tol for st in ends),
            max(abs(float(st.rel_dec)) / TEN_EPS for st in ends),
            [EXITS[int(st.reason)] for st in ends])


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
@pytest.mark.parametrize("shards", [1, 2, 8])
def test_ratios_are_the_worst_shards_of_the_round_replayed(
        shards, line_search):
    """One round from a cold start on 1, 2 and 8 shards (pad rows on the
    last of 2 and 8): ``grad_ratio`` / ``dec_ratio`` are the largest
    over the shards of what each shard's solve, made again on the host,
    read at its end, and the four counts are those solves' reasons."""
    X, y = _uneven()
    with use_mesh(device_mesh(shards)):
        _, vector = solvers.admm(
            shard_rows(X), y, lamduh=0.1, max_iter=1, inner_iter=30,
            line_search=line_search, intercept=True, return_counts=True)
    counts, ratios = unpack_counts(vector)
    grad, dec, reasons = _replay(X, y, shards, np.zeros(7), line_search)
    np.testing.assert_allclose(ratios["grad_ratio"], grad, rtol=1e-6)
    np.testing.assert_allclose(ratios["dec_ratio"], dec, rtol=1e-6)
    assert {n: counts[n] for n in EXIT_NAMES} == {
        "exit_" + name: reasons.count(name) for name in EXITS}


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
@pytest.mark.parametrize("rounds", [2, 3])
def test_ratios_are_the_last_rounds_not_the_largest_of_all(
        rounds, line_search):
    """The ratios are the LAST round's, as ``primal_ratio`` is.  On one
    shard without a penalty the consensus is the shard's own answer and
    the dual stays 0 exactly, so round ``r`` can be made again from the
    answer of a run of ``r - 1`` rounds; the counts add up over all of
    them."""
    X, y = _uneven()
    kw = dict(lamduh=0.0, inner_iter=30, line_search=line_search,
              intercept=True, adaptive_rho=False, abstol=0.0, reltol=0.0)
    with use_mesh(device_mesh(1)):
        sX = shard_rows(X)
        before = solvers.admm(sX, y, max_iter=rounds - 1, **kw)
        _, vector = solvers.admm(sX, y, max_iter=rounds, return_counts=True,
                                 **kw)
    counts, ratios = unpack_counts(vector)
    assert counts["rounds"] == rounds
    assert sum(counts[n] for n in EXIT_NAMES) == rounds
    grad, dec, _ = _replay(X, y, 1, np.asarray(before), line_search)
    np.testing.assert_allclose(ratios["grad_ratio"], grad, rtol=1e-6)
    np.testing.assert_allclose(ratios["dec_ratio"], dec, rtol=1e-6)
    first = _replay(X, y, 1, np.zeros(7), line_search)
    assert (grad, dec) != first[:2]


def test_fixed_iteration_count_reads_an_infinite_grad_ratio():
    """``inner_tol=0`` turns the local solves' convergence tests off:
    nothing is certified (``exit_gtol`` 0, ``exit_stalled`` 0) and
    ``max|g| / 0`` is no number."""
    X, y = _uneven()
    with use_mesh(device_mesh(2)):
        _, vector = solvers.admm(
            shard_rows(X), y, lamduh=0.1, max_iter=2, inner_iter=4,
            inner_tol=0.0, intercept=True, return_counts=True)
    counts, ratios = unpack_counts(vector)
    assert counts["exit_gtol"] == counts["exit_stalled"] == 0
    assert counts["exit_budget"] + counts["exit_failed"] == 4
    assert ratios["grad_ratio"] == np.inf and np.isfinite(ratios["dec_ratio"])


def test_no_round_reads_no_ratio():
    X, y = _uneven()
    _, vector = solvers.admm(X, y, max_iter=0, return_counts=True)
    counts, ratios = unpack_counts(vector)
    assert counts["rounds"] == sum(counts[n] for n in EXIT_NAMES) == 0
    assert ratios["grad_ratio"] == ratios["dec_ratio"] == np.inf


@pytest.mark.parametrize("line_search", LINE_SEARCHES)
def test_lbfgs_is_one_solve_and_fills_its_own_prefix(line_search):
    """The ``lbfgs`` solver is one solve: its four exit counts sum to 1,
    in a vector that ends after them and still unpacks by name; the
    parent's fit to the last bit."""
    X, y = _uneven()
    sX, sy = shard_rows(X), shard_rows(y)
    _, vector = solvers.lbfgs(sX, y, lamduh=0.1, line_search=line_search,
                              intercept=True, return_counts=True)
    assert vector.dtype == jnp.int32 and vector.shape == (8,)
    counts, ratios = unpack_counts(vector)
    assert tuple(counts) == SOLVE_COUNTS[:8] and ratios == {}
    assert SOLVE_COUNTS[4:8] == tuple(EXIT_NAMES)
    assert sum(counts[n] for n in EXIT_NAMES) == 1
    est = LogisticRegression(solver="lbfgs", C=10.0, solver_kwargs=dict(
        line_search=line_search)).fit(sX, sy)
    if _canary() != FROZEN["canary"]:
        pytest.skip("another machine's float32 sums")
    want = FROZEN[f"lbfgs-{line_search}"]
    assert _bits(est.coef_) == want["coef"]
    assert _bits(est.intercept_) == want["intercept"]
    assert np.asarray(est.n_iter_).tolist() == want["n_iter"]
    assert [counts[n] for n in PARENTS_COUNTS[:4]] == want["counts"]


# ---- the span and the registry ---------------------------------------------

def _solve_attrs():
    roots = [r for r in obs.span_records()
             if r.name == "glm.fit" and r.parent_id is None]
    tree = obs.span_tree(roots[-1])
    return next(c for c in tree["children"]
                if c["name"] == "glm.solve")["attrs"]


def _solve_counters():
    counters = diagnostics.run_report()["metrics"]["counters"]
    return {k: v for k, v in counters.items() if k.startswith("solve.")}


@pytest.mark.parametrize("shards", [1, 4])
def test_glm_solve_and_the_registry_carry_the_exits(shards):
    """Counts on the span and, summed over the fits, in the registry as
    ``solve.exit_*``; the two ratios on the span alone."""
    X, y = _uneven()
    before = _solve_counters()
    with use_mesh(device_mesh(shards)):
        LogisticRegression(solver="admm", C=10.0, max_iter=10).fit(
            shard_rows(X), shard_rows(y))
    solve = _solve_attrs()
    assert set(SOLVE_COUNTS) | set(SOLVE_RATIOS) <= set(solve)
    assert sum(solve[n] for n in EXIT_NAMES) == solve["rounds"] * shards
    assert solve["grad_ratio"] > 0 and solve["dec_ratio"] >= 0
    after = _solve_counters()
    for name in EXIT_NAMES:
        assert after["solve." + name] - before.get(
            "solve." + name, 0) == solve[name]
    assert not any("ratio" in name for name in after)


@pytest.mark.parametrize("max_iter, stopped", [(1, "budget"), (200, "boyd")])
def test_stopped_says_how_the_outer_loop_ended(max_iter, stopped):
    X, y = _uneven()
    est = LogisticRegression(solver="admm", C=10.0, max_iter=max_iter).fit(
        shard_rows(X), shard_rows(y))
    solve = _solve_attrs()
    assert solve["stopped"] == stopped
    assert (int(est.n_iter_[0]) == max_iter) == (stopped == "budget")


@pytest.mark.parametrize("max_iter, stopped", [(2, "budget"), (100, None)])
def test_lbfgs_span_names_its_exit(max_iter, stopped):
    """``lbfgs`` is its own outer loop: ``stopped`` is its exit's name,
    and the registry counts it."""
    X, y = _uneven()
    before = _solve_counters()
    LogisticRegression(solver="lbfgs", C=10.0, tol=1e-8,
                       max_iter=max_iter).fit(shard_rows(X), shard_rows(y))
    solve = _solve_attrs()
    assert sum(solve[n] for n in EXIT_NAMES) == 1
    assert solve["stopped"] in EXITS and solve["exit_" + solve["stopped"]]
    if stopped:
        assert solve["stopped"] == stopped
    else:
        assert solve["stopped"] != "budget"
    assert "grad_ratio" not in solve and "skew_passes" not in solve
    after = _solve_counters()
    name = "solve.exit_" + solve["stopped"]
    assert after[name] - before.get(name, 0) == 1


def test_a_ratio_that_is_no_number_is_left_off_the_span():
    """``inner_tol=0``: ``grad_ratio`` is infinite and the span leaves
    it off; the consensus's own ratios stay."""
    X, y = _uneven()
    LogisticRegression(
        solver="admm", C=10.0, max_iter=3, solver_kwargs=dict(
            inner_tol=0.0, inner_iter=4)).fit(shard_rows(X), shard_rows(y))
    solve = _solve_attrs()
    assert "grad_ratio" not in solve and "dec_ratio" in solve
    assert {"primal_ratio", "dual_ratio", "rho_ratio", "stopped"} <= set(
        solve)
    assert solve["exit_gtol"] == solve["exit_stalled"] == 0


def test_uncounted_solvers_say_nothing_of_exits():
    X, y = _uneven()
    LogisticRegression(solver="newton", C=10.0).fit(
        shard_rows(X), shard_rows(y))
    solve = _solve_attrs()
    assert "stopped" not in solve and not any(
        n in solve for n in EXIT_NAMES)
