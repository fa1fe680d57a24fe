import numpy as np
import pytest

import jax.numpy as jnp

import dask_ml_tpu.solvers as solvers
from dask_ml_tpu.core import shard_rows
from dask_ml_tpu.solvers import (
    L1,
    L2,
    ElasticNet,
    Logistic,
    Normal,
    Poisson,
    lambda_sweep,
    lbfgs_minimize,
    multinomial,
)


@pytest.fixture
def logistic_data(rng):
    n, d = 300, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    p = 1 / (1 + np.exp(-(X @ w)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return X, y, w


@pytest.fixture
def normal_data(rng):
    n, d = 300, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + 0.01 * rng.normal(size=n)).astype(np.float32)
    return X, y, w


class TestLBFGSCore:
    def test_quadratic_exact(self):
        A = jnp.asarray(np.diag([1.0, 10.0, 100.0]), dtype=jnp.float32)
        b = jnp.asarray([1.0, -2.0, 3.0])

        def f(x):
            return 0.5 * x @ A @ x - b @ x

        x, state = lbfgs_minimize(f, jnp.zeros(3), max_iter=100, tol=1e-6)
        np.testing.assert_allclose(np.asarray(x), np.linalg.solve(np.asarray(A), b), atol=1e-3)
        assert bool(state.converged)

    def test_rosenbrock(self):
        def f(z):
            return (1 - z[0]) ** 2 + 100 * (z[1] - z[0] ** 2) ** 2

        x, state = lbfgs_minimize(f, jnp.asarray([-1.2, 1.0]), max_iter=400, tol=1e-6)
        np.testing.assert_allclose(np.asarray(x), [1.0, 1.0], atol=1e-2)

    def test_inside_jit_and_vmap(self):
        import jax

        def f(x):
            return jnp.sum((x - 1.5) ** 2)

        solve = jax.jit(jax.vmap(lambda x0: lbfgs_minimize(f, x0, max_iter=50)[0]))
        out = solve(jnp.zeros((4, 3)))
        np.testing.assert_allclose(np.asarray(out), 1.5 * np.ones((4, 3)), atol=1e-4)


def _sklearn_logistic(X, y, C=1e5):
    from sklearn.linear_model import LogisticRegression as SkLR

    return SkLR(C=C, fit_intercept=False, tol=1e-8).fit(X, y).coef_[0]


class TestSolverParity:
    """All solvers minimize the same objective -> same optimum."""

    @pytest.mark.parametrize("name", ["lbfgs", "newton", "gradient_descent", "proximal_grad", "admm"])
    def test_logistic_unregularized(self, logistic_data, name):
        X, y, _ = logistic_data
        fn = getattr(solvers, name)
        kwargs = {"family": Logistic, "lamduh": 1e-5, "max_iter": 200}
        beta = fn(shard_rows(X), shard_rows(y), **kwargs)
        expected = _sklearn_logistic(X, y)
        np.testing.assert_allclose(np.asarray(beta), expected, atol=5e-2)

    @pytest.mark.parametrize("name", ["lbfgs", "newton", "admm"])
    def test_normal_family(self, normal_data, name):
        X, y, w = normal_data
        fn = getattr(solvers, name)
        beta = fn(shard_rows(X), shard_rows(y), family=Normal, lamduh=1e-6, max_iter=200)
        expected = np.linalg.lstsq(X, y, rcond=None)[0]
        np.testing.assert_allclose(np.asarray(beta), expected, atol=2e-2)

    def test_poisson_family(self, rng):
        n, d = 400, 4
        X = rng.normal(size=(n, d)).astype(np.float32) * 0.5
        w = rng.normal(size=d) * 0.5
        y = rng.poisson(np.exp(X @ w)).astype(np.float32)
        beta = solvers.lbfgs(shard_rows(X), shard_rows(y), family=Poisson, lamduh=1e-6, max_iter=300)
        from sklearn.linear_model import PoissonRegressor

        sk = PoissonRegressor(alpha=0, fit_intercept=False, tol=1e-8, max_iter=1000).fit(X, y)
        np.testing.assert_allclose(np.asarray(beta), sk.coef_, atol=5e-2)

    def test_l1_sparsity(self, normal_data):
        X, y, w = normal_data
        beta = solvers.admm(
            shard_rows(X), shard_rows(y), family=Normal, regularizer=L1,
            lamduh=300.0, max_iter=200,
        )
        # strong l1 must zero out some coordinates exactly
        assert np.sum(np.abs(np.asarray(beta)) < 1e-6) > 0

    def test_l1_proximal_grad_matches_admm(self, normal_data):
        X, y, _ = normal_data
        kw = dict(family=Normal, regularizer=L1, lamduh=50.0, max_iter=400)
        b1 = solvers.admm(shard_rows(X), shard_rows(y), **kw)
        b2 = solvers.proximal_grad(shard_rows(X), shard_rows(y), **kw)
        np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), atol=2e-2)

    def test_lbfgs_rejects_l1(self, normal_data):
        X, y, _ = normal_data
        with pytest.raises(ValueError, match="smooth"):
            solvers.lbfgs(shard_rows(X), shard_rows(y), regularizer=L1, lamduh=1.0)

    def test_l2_regularization_shrinks(self, normal_data):
        X, y, _ = normal_data
        b_weak = solvers.lbfgs(shard_rows(X), shard_rows(y), family=Normal, lamduh=1e-6)
        b_strong = solvers.lbfgs(shard_rows(X), shard_rows(y), family=Normal, regularizer=L2, lamduh=1e3)
        assert np.linalg.norm(np.asarray(b_strong)) < np.linalg.norm(np.asarray(b_weak))


class TestRegularizers:
    def test_l1_prox_soft_threshold(self):
        b = jnp.asarray([3.0, -0.5, 0.2])
        out = np.asarray(L1.prox(b, 1.0))
        np.testing.assert_allclose(out, [2.0, 0.0, 0.0])

    def test_l2_prox_shrinks(self):
        out = np.asarray(L2.prox(jnp.asarray([2.0]), 1.0))
        np.testing.assert_allclose(out, [1.0])

    def test_elastic_net_between(self):
        b = jnp.asarray([2.0])
        en = float(ElasticNet.prox(b, 1.0)[0])
        assert float(L1.prox(b, 1.0)[0]) >= 0 and en > 0

    def test_get_regularizer_names(self):
        assert solvers.get_regularizer("l1") is L1
        assert solvers.get_regularizer("elastic_net") is ElasticNet
        with pytest.raises(ValueError, match="Unknown regularizer"):
            solvers.get_regularizer("l7")


class TestLineSearchStrategies:
    """Both weak-Wolfe strategies must agree on convergence quality.
    The chip delta is now measured (probe_grid 1.24-1.38x on TPU,
    backtrack wins on CPU) and ``lbfgs`` defaults to ``auto`` — the
    per-platform winner via ``line_search_strategy`` / the
    ``DASK_ML_TPU_LINE_SEARCH`` knob."""

    def test_rosenbrock_probe_grid(self):
        import jax.numpy as jnp

        from dask_ml_tpu.solvers.lbfgs_core import lbfgs_minimize

        def f(z):
            return (1 - z[0]) ** 2 + 100 * (z[1] - z[0] ** 2) ** 2

        x, state = lbfgs_minimize(
            f, jnp.asarray([-1.2, 1.0]), max_iter=400, tol=1e-6,
            line_search="probe_grid",
        )
        np.testing.assert_allclose(np.asarray(x), [1.0, 1.0], atol=1e-2)

    def test_strategies_agree_on_logistic(self, rng):
        from dask_ml_tpu.solvers import Logistic, lbfgs

        X = rng.normal(size=(2000, 8)).astype(np.float32)
        w = rng.normal(size=8)
        y = (X @ w > 0).astype(np.float32)
        outs = {
            ls: np.asarray(lbfgs(
                X, y, family=Logistic, lamduh=1.0, max_iter=100, tol=1e-6,
                line_search=ls,
            ))
            for ls in ("backtrack", "probe_grid")
        }
        np.testing.assert_allclose(
            outs["backtrack"], outs["probe_grid"], rtol=0.05, atol=1e-3
        )

    def test_unknown_strategy_raises(self, rng):
        from dask_ml_tpu.solvers import Logistic, lbfgs

        X = rng.normal(size=(64, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        with pytest.raises(ValueError, match="line_search"):
            lbfgs(X, y, family=Logistic, line_search="bogus")


class TestLineSearchPolicy:
    """DASK_ML_TPU_LINE_SEARCH resolution rules (same contract shape as
    pack_strategy/scatter_strategy: explicit request > env knob > the
    measured per-platform auto)."""

    def test_auto_resolves_per_platform(self, monkeypatch):
        import jax

        from dask_ml_tpu.solvers.algorithms import line_search_strategy

        monkeypatch.delenv("DASK_ML_TPU_LINE_SEARCH", raising=False)
        expect = ("probe_grid" if jax.default_backend() == "tpu"
                  else "backtrack")
        assert line_search_strategy("auto") == expect

    def test_env_knob_overrides_auto(self, monkeypatch):
        from dask_ml_tpu.solvers.algorithms import line_search_strategy

        monkeypatch.setenv("DASK_ML_TPU_LINE_SEARCH", "probe_grid")
        assert line_search_strategy("auto") == "probe_grid"

    def test_explicit_request_beats_env(self, monkeypatch):
        from dask_ml_tpu.solvers.algorithms import line_search_strategy

        monkeypatch.setenv("DASK_ML_TPU_LINE_SEARCH", "probe_grid")
        assert line_search_strategy("backtrack") == "backtrack"

    def test_bad_env_rejected(self, monkeypatch):
        from dask_ml_tpu.solvers.algorithms import line_search_strategy

        monkeypatch.setenv("DASK_ML_TPU_LINE_SEARCH", "newton_exact")
        with pytest.raises(ValueError, match="DASK_ML_TPU_LINE_SEARCH"):
            line_search_strategy("auto")

    def test_packed_default_never_resolves_to_probe_grid(
            self, rng, monkeypatch, mesh):
        # packed_solve's own 'auto' default must NOT opt the sequential
        # fallback's admm/gd/newton dispatches into probe_grid (their
        # entry points keep backtrack as the measured-safe default);
        # an env knob forcing probe_grid with a non-lbfgs solver must
        # still converge to the same optimum — resolution correctness,
        # not performance, is what this pins
        from dask_ml_tpu.solvers import Logistic, packed_solve

        monkeypatch.setenv("DASK_ML_TPU_PACK", "sequential")
        X = rng.normal(size=(256, 5)).astype(np.float32)
        sX = shard_rows(X)
        w = rng.normal(size=5)
        Y = np.stack([
            (X @ w > 0).astype(np.float32),
            (X @ w > 0.5).astype(np.float32),
        ])
        Yp = np.zeros((2, sX.data.shape[0]), np.float32)
        Yp[:, :256] = Y
        B, _ = packed_solve("admm", sX, Yp, family=Logistic,
                            lamduh=0.1, max_iter=30)
        B2, _ = packed_solve("admm", sX, Yp, family=Logistic,
                            lamduh=0.1, max_iter=30,
                            line_search="backtrack")
        np.testing.assert_allclose(
            np.asarray(B), np.asarray(B2), rtol=1e-4, atol=1e-5)


class TestLambdaSweep:
    """solvers.lambda_sweep: K solves of the same (X, y) at different
    regularization strengths as one vmapped program — each lane must
    match the standalone solver at its lamduh."""

    def _data(self, rng):
        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
        return X, y

    @pytest.mark.parametrize("solver", ["lbfgs", "admm",
                                        "gradient_descent",
                                        "proximal_grad"])
    def test_lanes_match_standalone(self, rng, mesh, solver):
        X, y = self._data(rng)
        lams = [0.01, 0.1, 1.0]
        # tol=0: every lane and every standalone run executes exactly
        # max_iter rounds, so a convergence-criterion difference cannot
        # masquerade as a numeric one
        kwargs = dict(family=Logistic, max_iter=80, tol=0.0)
        if solver == "admm":
            kwargs["inner_iter"] = 20
            kwargs["abstol"] = kwargs.pop("tol")
            kwargs["reltol"] = 0.0  # Boyd rule fully disabled: every
            # lane and standalone run does exactly max_iter rounds
        betas, n_its = lambda_sweep(solver, X, y, lams, **kwargs)
        assert betas.shape[0] == len(lams)
        assert n_its.shape == (len(lams),)
        solo_fn = getattr(solvers, solver)
        for i, lam in enumerate(lams):
            solo = solo_fn(X, y, lamduh=lam, **kwargs)
            np.testing.assert_allclose(
                np.asarray(betas[i]), np.asarray(solo),
                rtol=5e-3, atol=2e-3,
                err_msg=f"{solver} lane {i} (lam={lam})")

    def test_newton_matrix_family_rejected(self, rng, mesh):
        X, y = self._data(rng)
        with pytest.raises(ValueError, match="matrix-parameter"):
            lambda_sweep("newton", X, y, [0.1], family=multinomial(3))

    def test_bad_lams_shape_rejected(self, rng, mesh):
        X, y = self._data(rng)
        with pytest.raises(ValueError, match="1-D"):
            lambda_sweep("lbfgs", X, y, [[0.1, 1.0]], family=Logistic)


class TestSolveCounts:
    """ISSUE 26 part C: ``LBFGSState.n_evals`` and the ``SOLVE_COUNTS``
    vector the counted runners carry out of the solve."""

    @pytest.mark.parametrize("diag", [[0.6, 0.8, 1.0], [0.5, 0.75, 1.0, 0.9]])
    @pytest.mark.parametrize("line_search,per_iter", [
        # the unit step's objective, the curvature test's gradient at t
        # and objective at 2t, then value_and_grad at the accepted point
        ("backtrack", 4),
        # the unit probe evaluates value and gradient at once
        ("probe_grid", 1),
    ])
    def test_n_evals_by_hand_when_every_unit_step_is_accepted(
            self, diag, line_search, per_iter):
        A = jnp.diag(jnp.asarray(diag, jnp.float32))
        x, st = lbfgs_minimize(lambda x: 0.5 * x @ A @ x,
                               jnp.ones(len(diag)), tol=1e-6,
                               line_search=line_search)
        k = int(st.k)
        assert k >= 4 and bool(st.converged)
        # one evaluation at the start, then per_iter an iteration
        assert int(st.n_evals) == 1 + per_iter * k

    def test_a_rejected_unit_step_costs_more_evaluations(self):
        # a steep valley: the first (gradient) step overshoots, so the
        # search backtracks (backtrack) or pays its one batched grid
        A = jnp.diag(jnp.asarray([1.0, 100.0], jnp.float32))
        f = lambda x: 0.5 * x @ A @ x  # noqa: E731
        _, bt = lbfgs_minimize(f, jnp.ones(2), tol=1e-6)
        assert int(bt.n_evals) > 1 + 4 * int(bt.k)
        _, grid = lbfgs_minimize(f, jnp.ones(2), tol=1e-6,
                                 line_search="probe_grid")
        k = int(grid.k)
        assert 1 + k < int(grid.n_evals) <= 1 + 2 * k

    @pytest.mark.parametrize("line_search", ["backtrack", "probe_grid"])
    def test_admm_counts_vector(self, logistic_data, line_search):
        X, y, _ = logistic_data
        kw = dict(family=Logistic, lamduh=1.0, line_search=line_search)
        beta, counts = solvers.admm(X, y, return_counts=True, **kw)
        rounds, inner, passes = (int(c) for c in counts)
        assert solvers.algorithms.SOLVE_COUNTS == (
            "rounds", "inner_iters", "passes")
        assert counts.dtype == jnp.int32 and counts.shape == (3,)
        # every round evaluates once at its start and at least once an
        # inner iteration
        assert rounds >= 1 and passes >= rounds + inner
        beta2, n_it = solvers.admm(X, y, return_n_iter=True, **kw)
        assert int(n_it) == rounds  # the scalar contract is unchanged
        np.testing.assert_array_equal(np.asarray(beta), np.asarray(beta2))
        # the same problem counts the same
        _, again = solvers.admm(X, y, return_counts=True, **kw)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(again))

    def test_lbfgs_counts_vector(self, logistic_data):
        X, y, _ = logistic_data
        _, counts = solvers.lbfgs(X, y, lamduh=1.0, return_counts=True,
                                  line_search="backtrack")
        rounds, inner, passes = (int(c) for c in counts)
        _, n_it = solvers.lbfgs(X, y, lamduh=1.0, return_n_iter=True,
                                line_search="backtrack")
        assert rounds == inner == int(n_it) and passes >= 1 + inner

    @pytest.mark.parametrize("solver", ["admm", "lbfgs"])
    @pytest.mark.parametrize("strategy", ["packed", "sequential"])
    def test_packed_solve_keeps_scalar_iterations(
            self, logistic_data, monkeypatch, solver, strategy):
        from dask_ml_tpu.solvers import packed_solve

        monkeypatch.setenv("DASK_ML_TPU_PACK", strategy)
        X, y, _ = logistic_data
        sX = shard_rows(X)
        Y = np.zeros((2, sX.data.shape[0]), np.float32)
        Y[0, :len(y)], Y[1, :len(y)] = y, 1 - y
        betas, n_its = packed_solve(solver, sX, jnp.asarray(Y),
                                    family=Logistic, lamduh=1.0,
                                    line_search="backtrack")
        assert betas.shape == (2, X.shape[1]) and n_its.shape == (2,)
        for lane in range(2):
            single, n_it = getattr(solvers, solver)(
                sX, Y[lane], family=Logistic, lamduh=1.0,
                return_n_iter=True, line_search="backtrack")
            assert int(n_its[lane]) == int(n_it)
            np.testing.assert_allclose(np.asarray(betas[lane]),
                                       np.asarray(single), atol=2e-4)

    @pytest.mark.parametrize("solver", ["admm", "lbfgs"])
    def test_lambda_sweep_keeps_scalar_iterations(
            self, logistic_data, solver):
        X, y, _ = logistic_data
        lams = [0.1, 1.0, 10.0]
        betas, n_its = lambda_sweep(solver, X, y, lams, family=Logistic)
        assert betas.shape == (3, X.shape[1]) and n_its.shape == (3,)
        for lane, lam in enumerate(lams):
            single, n_it = getattr(solvers, solver)(
                X, y, family=Logistic, lamduh=lam, return_n_iter=True,
                line_search="backtrack")
            assert int(n_its[lane]) == int(n_it)
            np.testing.assert_allclose(np.asarray(betas[lane]),
                                       np.asarray(single), atol=2e-4)
