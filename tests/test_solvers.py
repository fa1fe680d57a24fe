import contextlib

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
    """DASK_ML_TPU_LINE_SEARCH resolution rules: explicit request > env
    knob > the measured per-platform auto."""

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
            self, rng, monkeypatch, mesh, caplog):
        # packed_solve's lanes backtrack whatever they are asked (a grid
        # under vmap runs both cond branches in every lane): neither the
        # env knob nor an explicit probe_grid changes a bit of the
        # answer, and the explicit request is logged
        import logging

        from dask_ml_tpu.solvers import Logistic, packed_solve

        monkeypatch.setenv("DASK_ML_TPU_LINE_SEARCH", "probe_grid")
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
        with caplog.at_level(logging.INFO,
                             logger="dask_ml_tpu.solvers.algorithms"):
            B2, _ = packed_solve("admm", sX, Yp, family=Logistic,
                                lamduh=0.1, max_iter=30,
                                line_search="probe_grid")
        assert any("probe_grid" in r.getMessage() for r in caplog.records)
        B3, _ = packed_solve("admm", sX, Yp, family=Logistic,
                            lamduh=0.1, max_iter=30,
                            line_search="backtrack")
        np.testing.assert_array_equal(np.asarray(B), np.asarray(B3))
        np.testing.assert_array_equal(np.asarray(B2), np.asarray(B3))


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

    @staticmethod
    def _newton_optimum(X, y, lam, intercept):
        """The penalized logistic optimum in float64 on the host, the
        intercept last and penalized as the solvers penalize it."""
        A = np.hstack([X, np.ones((len(X), 1))]) if intercept else X
        A = A.astype(np.float64)
        w = np.zeros(A.shape[1])
        for _ in range(30):
            p = 1 / (1 + np.exp(-A @ w))
            H = (A * (p * (1 - p))[:, None]).T @ A + lam * np.eye(len(w))
            w -= np.linalg.solve(H, A.T @ (p - y) + lam * w)
        return w

    @pytest.mark.parametrize("intercept", [False, True])
    def test_lbfgs_lanes_search_on_the_cached_predictor(
            self, rng, mesh, intercept):
        """``lbfgs`` lanes search on the cached linear predictor.  They
        end where the black-box lanes and eight single solves end (the
        same iterations, or one apart: each solve stops once the
        float32 loss stalls, 1e-4 to 4e-4 from the float64 optimum here,
        and where exactly within that the rounding of its sums decides),
        read X once at the start and twice an iteration, and count their
        trials apart from those reads."""
        from dask_ml_tpu.solvers.algorithms import (
            SOLVE_COUNTS, _lbfgs_run, _prep, _sweep_lanes)

        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] + 0.5 * rng.logistic(size=300) > 0).astype(
            np.float32)
        lams = [0.01, 0.1, 1.0, 10.0, 100.0, 1000.0, 1e4, 1e5]
        kw = dict(family=Logistic, tol=1e-5, intercept=intercept)
        betas, counts = lambda_sweep("lbfgs", X, y, lams, return_counts=True,
                                     **kw)
        # the lanes as they were: the runner vmapped on the black box
        x, yv, mask = _prep(X, y)
        black_box, bb_counts = _sweep_lanes(
            x, yv, mask, jnp.asarray(lams, jnp.float32),
            jnp.zeros((len(lams), 5 + intercept), jnp.float32),
            jnp.int32(100), jnp.float32(1e-5), run=_lbfgs_run,
            family=Logistic, reg=L2, extra_kw=(
                ("line_search", "backtrack"), ("objective", "black_box")),
            counts=True)
        lane = dict(zip(SOLVE_COUNTS, np.asarray(counts).T))
        bb = dict(zip(SOLVE_COUNTS, np.asarray(bb_counts).T))
        # the lanes whose solves stop on a stalled float32 loss may stop an
        # iteration apart: where, the rounding of their sums decides
        assert (np.abs(lane["rounds"] - bb["rounds"]) <= 1).all(), (
            lane["rounds"], bb["rounds"])
        assert (lane["passes"] == 1 + 2 * lane["rounds"]).all()
        assert (lane["trials"] >= lane["rounds"]).all()
        assert not bb["trials"].any() and (
            bb["passes"] > 1 + 2 * bb["rounds"]).all()
        for i, lam in enumerate(lams):
            single = solvers.lbfgs(X, y, lamduh=lam, line_search="backtrack",
                                   **kw)
            for other in (black_box[i], single):
                np.testing.assert_allclose(
                    np.asarray(betas[i]), np.asarray(other), atol=5e-4,
                    err_msg=f"lane {i} (lam={lam})")
            np.testing.assert_allclose(
                np.asarray(betas[i]),
                self._newton_optimum(X, y, lam, intercept), atol=2e-3,
                err_msg=f"lane {i} (lam={lam})")

    @pytest.mark.parametrize("solver,family,objective,trials", [
        ("lbfgs", Logistic, "linear", True),
        ("lbfgs", Normal, "linear", True),
        # a matrix of parameters, and the runners with no cached
        # predictor: every trial a pass over X
        ("lbfgs", multinomial(3), "black_box", False),
        ("admm", Logistic, "black_box", False),
        ("gradient_descent", Logistic, "black_box", None),
    ])
    def test_which_lanes_search_on_the_cached_predictor(
            self, rng, mesh, solver, family, objective, trials):
        from dask_ml_tpu.solvers.algorithms import (
            SOLVE_COUNTS, sweep_objective)

        assert sweep_objective(solver, family) == objective
        X, y = self._data(rng)
        if family is multinomial(3):
            y = (X[:, 0] > 0).astype(np.float32) + (X[:, 1] > 0)
        _, counts = lambda_sweep(solver, X, y, [0.1, 1.0], family=family,
                                 max_iter=5, return_counts=True)
        lane = dict(zip(SOLVE_COUNTS, np.asarray(counts).T))
        # trials counted apart from the passes on the cached predictor
        # alone; a runner that counts no passes has no such column
        assert (bool(lane["trials"].all()) if "trials" in lane
                else None) == trials


def _force_objective(monkeypatch, kind):
    """Every dispatch of the counted runners asks for ``kind``, the
    runners' private static argument: what ``packed_solve`` and
    ``lambda_sweep`` ask for their vmapped lanes, here for the single
    solves ``admm()`` / ``lbfgs()`` they are compared with."""
    from dask_ml_tpu.solvers import algorithms

    for name in ("_admm_run", "_lbfgs_run"):
        run = getattr(algorithms, name)
        monkeypatch.setattr(
            algorithms, name,
            lambda *a, _run=run, **kw: _run(*a, **{**kw, "objective": kind}))


class TestSolveCounts:
    """ISSUE 26 part C: ``LBFGSState.n_evals`` and the ``SOLVE_COUNTS``
    vector the counted runners carry out of the solve."""

    @pytest.mark.parametrize("diag", [[0.6, 0.8, 1.0], [0.5, 0.75, 1.0, 0.9]])
    @pytest.mark.parametrize("line_search,per_iter", [
        # the unit step's objective, the curvature test's gradient at t
        # (it holds: no objective at 2t, ISSUE 35), then value_and_grad
        # at the accepted point
        ("backtrack", 3),
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
        assert int(bt.n_evals) > 1 + 3 * int(bt.k)
        _, grid = lbfgs_minimize(f, jnp.ones(2), tol=1e-6,
                                 line_search="probe_grid")
        k = int(grid.k)
        assert 1 + k < int(grid.n_evals) <= 1 + 2 * k

    @pytest.mark.parametrize("line_search", ["backtrack", "probe_grid"])
    def test_admm_counts_vector(self, logistic_data, line_search):
        X, y, _ = logistic_data
        kw = dict(family=Logistic, lamduh=1.0, line_search=line_search)
        beta, counts = solvers.admm(X, y, return_counts=True, **kw)
        rounds, inner, passes, trials = (int(c) for c in counts[:4])
        # the places every counted solver fills; ADMM's own come after
        # (tests/test_admm_consensus.py)
        assert solvers.algorithms.SOLVE_COUNTS[:4] == (
            "rounds", "inner_iters", "passes", "trials")
        assert counts.dtype == jnp.int32 and counts.shape == (17,)
        # every round reads X once at its start (a value_and_grad) and
        # twice an inner iteration (the product, the gradient); every
        # iteration tries at least its unit step
        assert rounds >= 1 and passes == rounds + 2 * inner
        assert trials >= inner
        # the searches with no history (a round's first) start from the
        # curvature's guess under backtrack: its reduction, a look, and
        # a look above or the slope; probe_grid guesses nothing
        guided = int(counts[solvers.algorithms.SOLVE_COUNTS.index(
            "guided_trials")])
        assert (3 * rounds <= guided <= trials
                if line_search == "backtrack" else guided == 0)
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
        rounds, inner, passes, trials = (int(c) for c in counts[:4])
        # then how the one solve ended (tests/test_solve_exits.py)
        assert counts.shape == (8,) and int(counts[4:].sum()) == 1
        _, n_it = solvers.lbfgs(X, y, lamduh=1.0, return_n_iter=True,
                                line_search="backtrack")
        assert rounds == inner == int(n_it) and passes == 1 + 2 * inner
        # backtrack: the first look's value, then a halving's value or
        # the slope where no halving was needed, at least; and once, with
        # no history yet, the curvature that said where to look first
        assert trials >= 2 * inner + 1

    @pytest.mark.parametrize("solver", ["admm", "lbfgs"])
    @pytest.mark.parametrize("strategy", ["packed", "sweep"])
    def test_packed_solve_keeps_scalar_iterations(
            self, logistic_data, monkeypatch, solver, strategy):
        """A lane's count is one scalar, the single solve's of the same
        kind: ``packed_solve``'s lanes over two targets, or (``sweep``)
        one ``lambda_sweep`` lane a target, on sharded rows."""
        from dask_ml_tpu.solvers import packed_solve

        if strategy == "packed" or solver == "admm":
            # these lanes run the black-box objective (lambda_sweep's
            # lbfgs lanes the cached predictor, as lbfgs() does)
            _force_objective(monkeypatch, "black_box")
        X, y, _ = logistic_data
        sX = shard_rows(X)
        Y = np.zeros((2, sX.data.shape[0]), np.float32)
        Y[0, :len(y)], Y[1, :len(y)] = y, 1 - y
        if strategy == "packed":
            betas, n_its = packed_solve(solver, sX, jnp.asarray(Y),
                                        family=Logistic, lamduh=1.0,
                                        line_search="backtrack")
        else:
            lanes = [lambda_sweep(solver, sX, Y[lane], [1.0],
                                  family=Logistic) for lane in range(2)]
            betas = jnp.concatenate([b for b, _ in lanes])
            n_its = jnp.concatenate([n for _, n in lanes])
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
            self, logistic_data, monkeypatch, solver):
        # the sweep's admm lanes run the black-box objective, as packed
        # lanes do; its lbfgs lanes the cached linear predictor, as the
        # single solve does
        if solver == "admm":
            _force_objective(monkeypatch, "black_box")
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

    @pytest.mark.parametrize("solver,lamduh,atol", [
        # one L-BFGS run to its own tolerance: the two kinds of objective
        # take the same iterations and end apart by float32 rounding
        # (1.2e-7 here)
        ("lbfgs", 0.1, 1e-5),
        ("lbfgs", 1.0, 1e-5),
        # ADMM stops by Boyd's rule (abstol 1e-4, reltol 1e-2) while still
        # 2e-2 from the optimum on these 38-row shards, and each round's
        # local solves start from the last round's rounding: the same
        # rounds, and answers 2.1e-5 apart at lamduh 1 ...
        ("admm", 1.0, 2e-4),
        # ... and 1.0e-3 apart under the weaker penalty, a twentieth of
        # the distance either stands from the optimum
        ("admm", 0.1, 2e-3),
    ])
    def test_black_box_and_cached_predictor_single_solves(
            self, logistic_data, monkeypatch, solver, lamduh, atol):
        """ISSUE 29 left the vmapped callers on the black-box objective
        and moved ``admm()`` / ``lbfgs()`` to the cached linear predictor:
        how far apart the two end on one problem is stated here, and not
        hidden in the lane-against-single comparisons above."""
        X, y, _ = logistic_data
        kw = dict(family=Logistic, lamduh=lamduh, return_n_iter=True,
                  line_search="backtrack")
        linear, n_linear = getattr(solvers, solver)(X, y, **kw)
        _force_objective(monkeypatch, "black_box")
        black_box, n_black_box = getattr(solvers, solver)(X, y, **kw)
        assert int(n_linear) == int(n_black_box)
        np.testing.assert_allclose(
            np.asarray(linear), np.asarray(black_box), atol=atol)


def _family_problem(name, rng, n=256, d=5):
    """A small table for each family: ``(family, x, y, mask, lam)`` with
    the last rows masked out, as ``shard_rows`` pads."""
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    if name == "logistic":
        family = Logistic
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))).astype(
            np.float32)
    elif name == "normal":
        family = Normal
        y = (X @ w + 0.1 * rng.normal(size=n)).astype(np.float32)
    elif name == "poisson":
        family = Poisson
        y = rng.poisson(np.exp(0.3 * (X @ w))).astype(np.float32)
    else:
        family = multinomial(3)
        y = np.argmax(X @ rng.normal(size=(d, 3))
                      + rng.gumbel(size=(n, 3)), axis=1).astype(np.float32)
    mask = (np.arange(n) < n - 8).astype(np.float32)
    return family, jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask), 0.5


FAMILIES = ["logistic", "normal", "poisson", "multinomial3"]


class TestLinearObjective:
    """ISSUE 29: the line search on the cached linear predictor.  A
    ``LinearObjective`` and the black box of the same loss take the same
    steps; what differs is what an iteration reads."""

    def _objectives(self, name, rng):
        """``(pdim, structured, black box)`` of one small problem.  The
        structured objective is put together here from the family's
        parts, for every family: what the entry points hand each family
        is ``test_which_families_search_on_the_cached_predictor``."""
        from dask_ml_tpu.solvers.algorithms import _pdim
        from dask_ml_tpu.solvers.lbfgs_core import LinearObjective

        family, x, y, mask, lam = _family_problem(name, rng)
        smooth = lambda b: L2.penalty(b, lam)  # noqa: E731
        linear = LinearObjective(
            predict=lambda *betas: family.linear_predictors(x, *betas),
            pointwise=lambda eta: family.pointwise_loss(eta, y, mask),
            smooth=smooth)
        return (_pdim(x, family), linear,
                lambda b: family.loss(b, x, y, mask) + smooth(b))

    @pytest.mark.parametrize("family,cached", [
        ("logistic", True), ("normal", True), ("poisson", True),
        # a matrix of parameters: the cached predictor is (rows, K) twice
        # and a solve on it read slower on the chip than the black box
        # (PERF.md section 6, PR 29), so the entry points keep the latter
        ("multinomial3", False),
    ])
    def test_which_families_search_on_the_cached_predictor(
            self, rng, family, cached):
        from dask_ml_tpu.solvers.algorithms import _lbfgs_objective
        from dask_ml_tpu.solvers.lbfgs_core import LinearObjective

        fam, x, y, mask, lam = _family_problem(family, rng)
        smooth = lambda b: L2.penalty(b, lam)  # noqa: E731
        asked = _lbfgs_objective("linear", fam, x, y, mask, smooth)
        assert isinstance(asked, LinearObjective) == cached
        assert not isinstance(
            _lbfgs_objective("black_box", fam, x, y, mask, smooth),
            LinearObjective)
        with pytest.raises(ValueError, match="unknown objective kind"):
            _lbfgs_objective("cached", fam, x, y, mask, smooth)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_loss_is_the_composition_of_its_parts(self, rng, family):
        fam, x, y, mask, _ = _family_problem(family, rng)
        d = x.shape[1] * fam.params_per_feature
        b, c = (jnp.asarray(rng.normal(size=d), jnp.float32)
                for _ in range(2))
        eta = fam.linear_predictor(b, x)
        assert eta.shape == ((256,) if fam.params_per_feature == 1
                             else (256, 3))
        np.testing.assert_allclose(
            float(fam.loss(b, x, y, mask)),
            float(fam.pointwise_loss(eta, y, mask)), rtol=1e-6)
        # several predictors in one reduction equal the products one by one
        one, = fam.linear_predictors(x, b)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(eta))
        pair = fam.linear_predictors(x, b, c)
        for got, beta in zip(pair, (b, c)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(fam.linear_predictor(beta, x)),
                rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_phi_from_cached_images_equals_the_objective_on_the_line(
            self, rng, family):
        import jax

        from dask_ml_tpu.solvers.lbfgs_core import (
            _black_box_phi, _cached_phi)

        d, linear, black_box = self._objectives(family, rng)
        x = jnp.asarray(0.3 * rng.normal(size=d), jnp.float32)
        p = jnp.asarray(0.3 * rng.normal(size=d), jnp.float32)
        vg = jax.value_and_grad(black_box)
        phi, gradient_at, curvature = _cached_phi(linear, x, p)
        reference = _black_box_phi(vg, x, p)
        for t in (0.0, 0.125, 1.0, 2.0):
            t = jnp.float32(t)
            f, slope, aux = phi(t)
            f_ref, g_ref = vg(x + t * p)
            assert aux == ()
            np.testing.assert_allclose(float(f), float(f_ref), rtol=2e-6)
            np.testing.assert_allclose(
                float(slope), float(jnp.dot(g_ref, p)), rtol=2e-4, atol=1e-4)
            np.testing.assert_allclose(
                float(slope), float(reference(t)[1]), rtol=2e-4, atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(gradient_at(t)), np.asarray(g_ref),
                rtol=2e-4, atol=2e-4)
        # the curvature at the start of the line: p' H p of the black box
        hvp = jax.jvp(jax.grad(black_box), (x,), (p,))[1]
        np.testing.assert_allclose(
            float(curvature()), float(jnp.dot(p, hvp)), rtol=2e-4)
        # called as a function, the structured objective is the black box
        np.testing.assert_allclose(
            float(linear(x)), float(black_box(x)), rtol=2e-6)

    @pytest.mark.parametrize("line_search", ["backtrack", "probe_grid"])
    @pytest.mark.parametrize("family", FAMILIES)
    def test_same_iterations_steps_and_answer_as_the_black_box(
            self, rng, family, line_search):
        d, linear, black_box = self._objectives(family, rng)
        x0 = jnp.zeros(d, jnp.float32)
        kw = dict(max_iter=60, tol=1e-4, line_search=line_search)
        x_lin, lin = lbfgs_minimize(linear, x0, **kw)
        x_bb, bb = lbfgs_minimize(black_box, x0, **kw)
        k = int(lin.k)
        assert k == int(bb.k) and k >= 3
        assert bool(lin.converged) and bool(bb.converged)
        np.testing.assert_allclose(
            np.asarray(x_lin), np.asarray(x_bb), rtol=1e-4, atol=1e-5)
        # the accepted steps s = t p still in the history (the last ten)
        np.testing.assert_allclose(
            np.asarray(lin.S), np.asarray(bb.S), rtol=1e-3, atol=1e-5)
        # reads of the data: the first value_and_grad, then the product
        # and the transposed product of each iteration; the black box's
        # count holds its trials, and has none apart
        assert int(lin.n_evals) == 1 + 2 * k
        assert int(bb.n_trials) == 0
        # the trials are the black box's evaluations inside the searches:
        # all but the first and, under backtrack, the one after each search
        extra = k if line_search == "backtrack" else 0
        unguided = guided = int(lin.n_guided)
        if line_search == "backtrack":
            # ... but for the one search with no history, the first: the
            # black box walks down from 1, the cached predictor starts
            # from the curvature's guess.  A search from 1 on the same
            # cached line takes what the black box took
            assert guided >= 3
            _, unguided = self._first_search(linear, x0, start=None)
        assert int(lin.n_trials) - guided + unguided == (
            int(bb.n_evals) - 1 - extra)

    @staticmethod
    def _first_search(linear, x0, start="guess"):
        """``(accepted step, trials)`` of the search a solve of
        ``linear`` from ``x0`` makes first, along ``-g``: from the
        exponent ``start``, from the curvature's guess, or (None) from
        the unit step as every search went before ISSUE 35."""
        import jax

        from dask_ml_tpu.solvers.lbfgs_core import (
            _C2, _cached_phi, _search, _start_exponent)

        f0, g = jax.value_and_grad(linear)(x0)
        phi, _, curvature = _cached_phi(linear, x0, -g)
        dg = -jnp.dot(g, g)
        if start == "guess":
            start = _start_exponent(curvature(), dg, 1e-4, 30)
        t, _, _, failed, n = _search(
            "backtrack", phi, f0, dg, 1e-4, _C2, 30,
            None if start is None else jnp.asarray(start))
        assert not bool(failed)
        return float(t), int(n)

    @staticmethod
    def _quadratic(diag):
        """0.5 x' diag x as a LinearObjective: predict scales by the
        root of the diagonal, pointwise is half the squared norm."""
        from dask_ml_tpu.solvers.lbfgs_core import LinearObjective

        root = jnp.sqrt(jnp.asarray(diag, jnp.float32))
        return LinearObjective(
            predict=lambda *bs: tuple(root * b for b in bs),
            pointwise=lambda eta: 0.5 * jnp.sum(eta ** 2),
            smooth=lambda b: jnp.float32(0.0))

    @pytest.mark.parametrize("line_search,per_iter,once", [
        # the unit step's value, then the curvature test's slope at t
        # (it holds: no value at 2t); once, with no history, the
        # curvature that said "start at 1"
        ("backtrack", 2, 1),
        # the unit probe gives value and slope at once
        ("probe_grid", 1, 0),
    ])
    def test_counts_by_hand_when_every_unit_step_is_accepted(
            self, line_search, per_iter, once):
        x, st = lbfgs_minimize(self._quadratic([0.6, 0.8, 1.0]),
                               jnp.ones(3), tol=1e-6,
                               line_search=line_search)
        k = int(st.k)
        assert k >= 4 and bool(st.converged)
        assert int(st.n_evals) == 1 + 2 * k
        assert int(st.n_trials) == per_iter * k + once
        # the guided search: curvature, the unit step's value, its slope
        assert int(st.n_guided) == 3 * once

    @pytest.mark.parametrize("line_search,trials", [
        # from (1, 1) along -g = -(1, 100): Armijo fails at t = 1, 1/2,
        # ..., 1/32 and holds at 1/64: seven values and a curvature test
        # that could move nothing before ISSUE 35 (9 trials).  The line
        # is a parabola, so its curvature puts the first look ON 1/64;
        # one look above, at 1/32, and the search is over
        ("backtrack", 3),
        # the unit probe fails, then the grid: one batched call
        ("probe_grid", 2),
    ])
    def test_counts_by_hand_on_a_step_that_backtracks(
            self, line_search, trials):
        _, st = lbfgs_minimize(self._quadratic([1.0, 100.0]), jnp.ones(2),
                               tol=1e-6, max_iter=1,
                               line_search=line_search)
        assert int(st.k) == 1
        assert int(st.n_evals) == 3 and int(st.n_trials) == trials
        assert np.asarray(st.x).tolist() == [1 - 1 / 64, 1 - 100 / 64]

    @pytest.mark.parametrize("solver", ["admm", "lbfgs"])
    def test_a_family_with_loss_alone_solves_on_the_black_box(
            self, logistic_data, monkeypatch, solver):
        """A ``Family`` subclass written before ISSUE 29 overrides
        ``loss`` and has no ``pointwise_loss``: ``admm()`` / ``lbfgs()``
        ask for the cached predictor and such a family gets the black
        box, the program it had."""
        from dask_ml_tpu.solvers.families import Family

        class LossAlone(Family):
            @staticmethod
            def loss(beta, X, y, mask):
                eta = X @ beta
                return jnp.sum(mask * (jnp.logaddexp(0.0, eta) - y * eta))

        X, y, _ = logistic_data
        kw = dict(lamduh=1.0, return_counts=True, line_search="backtrack")
        beta, counts = getattr(solvers, solver)(X, y, family=LossAlone, **kw)
        assert int(counts[3]) == 0  # no trial came from a cached predictor
        _force_objective(monkeypatch, "black_box")
        ref, ref_counts = getattr(solvers, solver)(
            X, y, family=Logistic, **kw)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref_counts))
        np.testing.assert_allclose(np.asarray(beta), np.asarray(ref),
                                   rtol=1e-6, atol=1e-7)

    def test_glm_solve_span_carries_trials(self, logistic_data):
        from dask_ml_tpu import obs
        from dask_ml_tpu.linear_model import LogisticRegression

        was_on = obs.enabled()
        obs.enable()
        try:
            X, y, _ = logistic_data
            LogisticRegression(solver="admm").fit(X, y)
            solve = next(c for c in obs.span_tree()["children"]
                         if c["name"] == "glm.solve")
        finally:
            if not was_on:
                obs.disable()
        a = solve["attrs"]
        assert set(solvers.algorithms.SOLVE_COUNTS) <= set(a)
        assert a["passes"] == a["rounds"] + 2 * a["inner_iters"]
        # a value and a slope or a halving a search; the curvature too
        # in the search that opens a round
        assert a["trials"] >= 2 * a["inner_iters"] + a["rounds"]
        assert 3 * a["rounds"] <= a["guided_trials"] <= a["trials"]

    @pytest.mark.parametrize("solver", ["admm", "lbfgs"])
    def test_vmapped_callers_keep_the_black_box_program(
            self, logistic_data, monkeypatch, mesh, solver):
        """``packed_solve`` and ``lambda_sweep``'s ``admm`` lanes put the
        runners under ``vmap`` and ask for the black-box objective: their
        programs hold the black box's products with X and no reduction
        with two results (the pair product).  10 products: one loop body
        takes the slope at t and the value at 2t once each (as
        ``make_jaxpr`` shows them).  ``lambda_sweep``'s ``lbfgs`` lanes
        search on the cached predictor: the pair is a reduction, and
        their three products with X (the first value and gradient's two,
        the gradient's one an iteration) are float32 ones, at
        ``HIGHEST``."""
        import jax

        from dask_ml_tpu.solvers import packed_solve

        def eqns(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for v in eqn.params.values():
                    for j in (v if isinstance(v, (tuple, list)) else (v,)):
                        j = getattr(j, "jaxpr", j)
                        if hasattr(j, "eqns"):
                            yield from eqns(j)

        def census(fn, shape, precisions=None):
            # X whole, or one shard's rows of it (admm's shard_map)
            shapes = (shape, (shape[0] // mesh.devices.size, shape[1]))
            found = list(eqns(jax.make_jaxpr(fn)().jaxpr))
            dots = [e for e in found if e.primitive.name == "dot_general"
                    and any(tuple(v.aval.shape) in shapes for v in e.invars)]
            if precisions is not None:
                precisions.extend(e.params["precision"] for e in dots)
            pairs = sum(e.primitive.name == "reduce" and len(e.outvars) > 1
                        for e in found)
            return len(dots), pairs

        X, y, _ = logistic_data
        sX = shard_rows(X)
        shape = tuple(sX.data.shape)
        Y = np.zeros((2, shape[0]), np.float32)
        Y[0, :len(y)], Y[1, :len(y)] = y, 1 - y
        kw = dict(family=Logistic, lamduh=1.0)
        assert census(lambda: packed_solve(
            solver, sX, jnp.asarray(Y), **kw), shape) == (10, 0)
        precisions = []
        assert census(lambda: lambda_sweep(
            solver, sX, Y[0], [0.1, 1.0], family=Logistic), shape,
            precisions) == ((3, 1) if solver == "lbfgs" else (10, 0))
        if solver == "lbfgs":
            highest = jax.lax.Precision.HIGHEST
            assert precisions == [(highest, highest)] * 3, precisions
        # one solve a dispatch: the first value_and_grad (two products),
        # then the pair and one transposed product an iteration
        dots, pairs = census(lambda: getattr(solvers, solver)(
            sX, Y[0], **kw), shape)
        assert (dots, pairs) == (3, 1)


def _parabola(c, log):
    """``phi(t) = -t + c t^2 / 2`` (value 0 and slope -1 at the start,
    Armijo up to ``2 (1 - c1) / c``) whose value and slope are two host
    callbacks that write themselves into ``log``: under ``jit`` what a
    search does not use is never called, so the log is the search's
    trials, in order."""
    import jax

    def recorded(kind, fn):
        def call(t):
            log.append((kind, float(t)))
            return np.float32(fn(float(t)))

        return lambda t: jax.pure_callback(
            call, jax.ShapeDtypeStruct((), jnp.float32), t)

    value = recorded("value", lambda t: -t + 0.5 * c * t * t)
    slope = recorded("slope", lambda t: -1.0 + c * t)
    return lambda t: (value(t), slope(t), ())


def _recorded_search(c, start, c2="wolfe"):
    """``(t, f_new, failed, n_calls, log)`` of one jitted ``backtrack``
    search of :func:`_parabola` from the exponent ``start`` (None: the
    unit step, with no walk up in the program)."""
    import jax

    from dask_ml_tpu.solvers.lbfgs_core import _C2, _search

    log = []

    def search(start):
        return _search("backtrack", _parabola(c, log), jnp.float32(0.0),
                       jnp.float32(-1.0), 1e-4,
                       _C2 if c2 == "wolfe" else c2, 30, start)

    t, f_new, _, failed, n = jax.jit(search)(
        None if start is None else jnp.asarray(start))
    return float(t), float(f_new), bool(failed), int(n), log


def _values(*exponents):
    return [("value", 2.0 ** e) for e in exponents]


class TestGuidedSearch:
    """ISSUE 35: the backtracking search takes no trial whose outcome it
    knows.  A search with no history starts from the curvature along the
    line and not from 1; after a halving, or a doubling that Armijo
    refused, nothing is asked about 2t; where nothing is known above t
    the slope comes first and the value at 2t only if the curvature
    test fails.  The accepted step is the one a search from 1 accepts."""

    @pytest.mark.parametrize("family", FAMILIES)
    def test_the_start_is_an_exact_power_of_two_under_the_parabolas_bound(
            self, rng, family):
        import jax

        from dask_ml_tpu.solvers.lbfgs_core import (
            _cached_phi, _powers_of_two, _start_exponent)

        d, linear, _ = TestLinearObjective()._objectives(family, rng)
        x0 = jnp.zeros(d, jnp.float32)
        g = jax.grad(linear)(x0)
        _, _, curvature = _cached_phi(linear, x0, -g)
        c, dg = float(curvature()), -float(jnp.dot(g, g))
        assert c > 0
        k = int(jax.jit(_start_exponent, static_argnums=(2, 3))(
            jnp.float32(c), jnp.float32(dg), 1e-4, 30))
        t0 = np.float32(_powers_of_two(30, jnp.float32)[k])
        # the power of two to the last bit: a mantissa of one half
        assert np.frexp(t0) == (0.5, 1 - k)
        bound = min(1.0, 2 * (1 - 1e-4) * -dg / c)
        assert t0 <= bound and (2 * t0 > bound or t0 == 1 or k == 30)

    @pytest.mark.parametrize("curvature,dg,k", [
        # no positive number: the unit step, the search as it was
        (np.nan, -1.0, 0), (np.inf, -1.0, 0), (-np.inf, -1.0, 0),
        (-1.0, -1.0, 0), (0.0, -1.0, 0), (1.0, np.nan, 0),
        # a bound of 2 or of 1.9998 / 1.9998: nothing over 1
        (1.0, -1.0, 0), (1.9998, -1.0, 0),
        # just under a power of two goes to the next one down
        (3.0, -1.0, 1), (4.0, -1.0, 2), (100.0, -1.0, 6),
        # never under the floor 2^-max_backtracks
        (2.0 ** 31, -1.0, 30), (1e30, -1.0, 30), (1.0, -1e-30, 30),
    ])
    def test_start_exponent_by_hand(self, curvature, dg, k):
        from dask_ml_tpu.solvers.lbfgs_core import _start_exponent

        assert int(_start_exponent(
            jnp.float32(curvature), jnp.float32(dg), 1e-4, 30)) == k

    @pytest.mark.parametrize("start,trials", [
        # a search from 1: six halvings, and no curvature test after them
        (None, 7), (0, 7),
        # a start above the answer walks down ...
        (3, 4),
        # ... the parabola's own guess stands on it: one look above ...
        ("guess", 2), (6, 2),
        # ... and a start below walks up to it, then is refused at 1/32
        (9, 5), (30, 26),
    ])
    def test_every_start_ends_on_the_step_a_search_from_one_accepts(
            self, start, trials):
        # _quadratic([1, 100]) from (1, 1) along -g: Armijo holds up to
        # 0.019998, so 1/64 is the largest power of two that passes
        t, n = TestLinearObjective._first_search(
            TestLinearObjective._quadratic([1.0, 100.0]), jnp.ones(2),
            start=start)
        assert t == 1 / 64 and n == trials

    @pytest.mark.parametrize("start", ["guess", "above", "below"])
    @pytest.mark.parametrize("family", FAMILIES)
    def test_guided_and_unguided_searches_accept_equal_steps(
            self, rng, family, start):
        d, linear, _ = TestLinearObjective()._objectives(family, rng)
        x0 = jnp.zeros(d, jnp.float32)
        first = TestLinearObjective._first_search
        from_one, _ = first(linear, x0, start=None)
        k = int(round(-np.log2(from_one)))
        assert from_one == 2.0 ** -k  # equal, not close
        if start != "guess":
            start = min(k + 3, 30) if start == "below" else max(k - 3, 0)
        assert first(linear, x0, start=start)[0] == from_one

    @pytest.mark.parametrize("start", [None, 0, 12, 30])
    def test_the_floor_and_failed_are_as_before(self, start):
        # Armijo holds nowhere (a line that rises under a slope said to
        # fall: c = 1e12 puts the bound under the floor 2^-30)
        t, f_new, failed, n, log = _recorded_search(1e12, start)
        first = start or 0
        assert failed and t == 0.0 and f_new == 0.0
        assert n == 31 - first
        assert log == _values(*range(-first, -31, -1))

    @pytest.mark.parametrize("c,start,c2,trials,accepted", [
        # after a halving the search takes no slope and no value(2t):
        # 2t is the step whose Armijo test just failed
        (100.0, None, "wolfe", _values(0, -1, -2, -3, -4, -5, -6), 1 / 64),
        (100.0, 3, "wolfe", _values(-3, -4, -5, -6), 1 / 64),
        # nor after a walk up that Armijo stopped
        (100.0, 6, "wolfe", _values(-6, -5), 1 / 64),
        (100.0, 9, "wolfe", _values(-9, -8, -7, -6, -5), 1 / 64),
        # the unit step accepted at the first look: the slope, which
        # holds, and no value at 2
        (0.5, None, "wolfe", _values(0) + [("slope", 1.0)], 1.0),
        # a walk up that reached 1 knows nothing above it either
        (0.5, 3, "wolfe", _values(-3, -2, -1, 0) + [("slope", 1.0)], 1.0),
        # an expansion keeps the value it moved to: each of 2, 4, 8, 16
        # is taken once, then its slope (which holds at 16)
        (0.01, None, "wolfe",
         [(kind, 2.0 ** e) for e in range(5) for kind in ("value", "slope")],
         16.0),
        (0.01, 2, "wolfe",
         _values(-2, -1) + [(kind, 2.0 ** e) for e in range(5)
                            for kind in ("value", "slope")], 16.0),
        # eight expansions at most, and no test after the eighth
        (1e-6, None, "wolfe",
         [(kind, 2.0 ** e) for e in range(8) for kind in ("value", "slope")]
         + _values(8), 256.0),
        # Armijo alone (gradient_descent, newton): never a slope
        (0.5, None, None, _values(0), 1.0),
        (100.0, None, None, _values(0, -1, -2, -3, -4, -5, -6), 1 / 64),
    ])
    def test_the_trials_a_search_takes_in_order(
            self, c, start, c2, trials, accepted):
        t, f_new, failed, n, log = _recorded_search(c, start, c2)
        assert not failed and t == accepted
        assert log == trials and n == len(trials)
        # the value handed back is the one taken at the accepted step
        assert f_new == np.float32(-t + 0.5 * c * t * t)

    def test_only_the_search_with_no_history_is_guided(self):
        # on the quadratic the first search guesses (curvature, 1/64,
        # 1/32) and every later one starts at 1 with no curvature taken
        _, st = lbfgs_minimize(
            TestLinearObjective._quadratic([1.0, 100.0]), jnp.ones(2),
            tol=1e-6, max_iter=6)
        assert int(st.k) >= 3 and int(st.n_updates) == int(st.k)
        assert int(st.n_guided) == 3 < int(st.n_trials)
        _, grid = lbfgs_minimize(
            TestLinearObjective._quadratic([1.0, 100.0]), jnp.ones(2),
            tol=1e-6, max_iter=6, line_search="probe_grid")
        assert int(grid.n_guided) == 0


@pytest.fixture(scope="module")
def v5e_devices():
    """The four chips of a DESCRIBED v5e host (the TPU's compiler runs
    here without the chip).  Described inside the fixture, never at
    import: one process at a time may load the TPU's library."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_chip(v5e_devices):
    """One chip of the described host: a mesh to give
    ``jax.ShapeDtypeStruct``s their sharding."""
    from jax.sharding import Mesh

    return Mesh(np.array(v5e_devices[:1]).reshape(1, 1), ("data", "model"))


@contextlib.contextmanager
def _compile_cache_off():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


class TestCompiledForTheChip:
    """ISSUEs 29 and 33, at the benchmark's size (31,250,000 rows on one
    v5e): what the chip's compiler makes of the whole-solve program, in
    the two forms a caller can ask for: ``"scalar"``, the 28-column
    table with the intercept beside 28 weights (what every GLM fit runs
    since ISSUE 33), and ``"column"``, a 29-column table that brings its
    own column of ones (``intercept=False``: the program as it was).
    Compiled, never run: no time or result comes from here."""

    ROWS, PARAMS = 31_250_000, 29
    FORMS = {"scalar": 28, "column": 29}  # the table's width

    @pytest.fixture()
    def lower(self, v5e_chip):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dask_ml_tpu.core.mesh import MeshHolder
        from dask_ml_tpu.solvers.algorithms import _admm_run

        def S(shape, spec, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(v5e_chip, spec))

        def lower(form, **kw):
            scalars = [S((), P())] * 5
            args = (S((self.ROWS, self.FORMS[form]), P("data", None)),
                    S((self.ROWS,), P("data")), S((self.ROWS,), P("data")),
                    *scalars, S((), P(), jnp.int32),
                    S((self.PARAMS,), P()))
            return _admm_run.lower(
                *args, family=Logistic, reg=L2,
                mesh_holder=MeshHolder(v5e_chip), inner_iter=30,
                **kw).compile()

        with _compile_cache_off():
            yield lower

    def _row_vector_passes(self, hlo):
        """The fusions of ``hlo`` that read ONE array of a row's length
        and nothing larger, and write one: an elementwise pass over a
        row vector (adding a scalar to ``eta`` would be one)."""
        import re

        row = r"f32\[%d\]" % self.ROWS
        bodies = dict(re.findall(
            r"^(?:ENTRY )?(%[\w.-]+) [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S))
        fused = set(re.findall(r"calls=(%[\w.-]+)", hlo))
        found = []
        for name, body in bodies.items():
            if name in fused:
                continue
            shape = dict(re.findall(
                r"^\s*(?:ROOT )?(%[\w.-]+) = (\(.*?\)|\S+) ", body, re.M))
            for out, operands in re.findall(
                    r"^\s*(?:ROOT )?%%[\w.-]+ = (%s\S*) fusion\((.*?)\), kind="
                    % row, body, re.M):
                read = [shape.get(o.strip(), "") for o in re.sub(
                    r"/\*.*?\*/", "", operands).split(",")]
                if sum(str(self.ROWS) in r for r in read) == 1 and any(
                        re.match(row, r) for r in read):
                    found.append((name, out, operands))
        return found

    @pytest.mark.parametrize("form", list(FORMS))
    @pytest.mark.parametrize("line_search", ["backtrack", "probe_grid"])
    def test_cached_predictor_holds_four_row_vectors_and_one_pair_product(
            self, lower, line_search, form):
        import re

        compiled = lower(form, line_search=line_search, objective="linear")
        memory = compiled.memory_analysis()
        vector = self.ROWS * 4
        # the table (28 and 29 features both lie on 32 sublanes: 4.0 GB),
        # the targets and the mask: what the caller holds, and all a fit
        # needs beside the temporaries below (4,250,013,184 B, both forms)
        assert memory.argument_size_in_bytes < 4.26e9
        # a CEILING, and over the one ISSUE 29 set (the black box's two
        # vectors and 80 MB): eta, u, the residual and the hoisted
        # -y * mask, which the black box holds too.  The residual does
        # not take eta's place, as it does in the black box, because eta
        # and u are the two results of ONE fusion (PERF.md section 7).
        # Under probe_grid the 34 candidates are one fused reduction over
        # the four, and a rows x 34 array (4.25 GB) is never made.  The
        # intercept adds no fifth: 502,286,848 / 502,320,128 B against
        # the column form's 501,996,544 / 502,255,616
        assert memory.temp_size_in_bytes < 4.02 * vector
        # the pair X @ [x | p]: ONE fusion with two results of a row's
        # length, fed by the table
        hlo = compiled.as_text()
        pair = re.findall(
            r"= \(f32\[%d\]\S*, f32\[%d\]\S*\) fusion\(" % (
                self.ROWS, self.ROWS), hlo)
        assert len(pair) == 1
        # the intercept is added inside the fusions that consume eta and
        # u (the trials, the residual): no pass over a row vector of its
        # own.  (Both forms have the first value_and_grad's product,
        # which reads the TABLE and writes eta, and -y * mask, which
        # reads two vectors.)
        assert self._row_vector_passes(hlo) == []
        if form == "scalar":
            # and no table but the caller's: nothing 29 wide, nothing
            # concatenated or padded at the table's size
            assert "f32[%d,29]" % self.ROWS not in hlo
            assert not re.search(
                r"f32\[%d,\d+\]\S* (concatenate|pad)\(" % self.ROWS, hlo)

    @pytest.mark.parametrize("form", list(FORMS))
    def test_black_box_program_is_the_parents(self, lower, form):
        # what packed_solve's and lambda_sweep's lanes run, here without
        # the vmap: two row vectors of temporaries, as before ISSUE 29
        compiled = lower(form, line_search="backtrack", objective="black_box")
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert 2 * self.ROWS * 4 < temp < 2.1 * self.ROWS * 4  # 252,606,464


class TestConsensusCompiledForFourChips:
    """ISSUE 34, at the benchmark's size (250,000,000 x 28 over the four
    chips of a described v5e host, 62,500,000 rows a chip: the cell
    ``admm-higgs-250m.fit-4chip``): what the chip's compiler makes of the
    whole-solve program once the consensus has four members.  The cell's
    memory claim rests on temporaries ``peak_hbm_gib`` cannot see, and
    its guarantee on nothing of a row's size crossing chips.  Compiled,
    never run.  (In this file because one process at a time may load the
    TPU's library: ``v5e_devices``.)"""

    CHIPS, ROWS, D = 4, 250_000_000, 28

    @pytest.mark.parametrize("line_search", ["backtrack", "probe_grid"])
    def test_a_chips_share_fits_and_only_small_all_reduces_cross(
            self, v5e_devices, line_search):
        import re

        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from dask_ml_tpu.core.mesh import MeshHolder
        from dask_ml_tpu.solvers.algorithms import SOLVE_COUNTS, _admm_run

        mesh = Mesh(np.array(v5e_devices[:self.CHIPS]).reshape(
            self.CHIPS, 1), ("data", "model"))

        def S(shape, spec, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))

        row = S((self.ROWS,), P("data"))
        args = (S((self.ROWS, self.D), P("data", None)), row, row,
                *[S((), P())] * 5, S((), P(), jnp.int32),
                S((self.D + 1,), P()))
        with _compile_cache_off():
            compiled = _admm_run.lower(
                *args, family=Logistic, reg=L2,
                mesh_holder=MeshHolder(mesh), inner_iter=30,
                line_search=line_search, objective="linear").compile()
        memory = compiled.memory_analysis()
        share = self.ROWS // self.CHIPS
        # a chip's arguments: its rows of the table on 32 sublanes
        # (8.0 GB), targets and mask (0.25 GB each): 8,500,013,184 B
        assert 8.0e9 < memory.argument_size_in_bytes < 8.52e9
        # and its temporaries: eta, u, the residual and -y * mask, four
        # vectors of ITS rows as on one chip, and half a vector more
        # (1,127,375,872 / 1,127,473,152 B): partitioned over four chips
        # the program carries a bf16[62500000] ``-y`` through the outer
        # loop that no instruction reads (PERF.md section 6, PR 34).
        # Table and temporaries leave 6.7 GB of the chip free
        assert 3.9 * share * 4 < memory.temp_size_in_bytes < 4.55 * share * 4
        hlo = compiled.as_text()
        assert not re.search(r"all-gather|all-to-all|collective-permute",
                             hlo)
        # every collective is an all-reduce, and the largest operand of
        # any is the 29 parameters or the six counts of the round's
        # work: nothing of a row's size ever crosses chips
        crossing = re.findall(
            r"= (\(?[a-z0-9]+\[[0-9,]*\][^=\n]*?) all-reduce(?:-start)?\(",
            hlo)
        assert crossing
        for shapes in crossing:
            for shape in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", shapes):
                dims = [int(n) for n in shape.split(",") if n]
                assert int(np.prod(dims or [1])) <= self.D + 1, shapes
        # the slowest and the fastest shard's counts ride ONE all-reduce
        # (a max over s32[8]: the guided searches' trials came to it in
        # ISSUE 35, the last round's two ratios as bit patterns in ISSUE
        # 36), where the slowest's alone rode before; with the
        # parameters' sum and the residuals' three scalars that was three
        # all-reduces a round.  ISSUE 36 adds the fourth and says so: a
        # sum over s32[4], how many shards' solves ended by each exit
        assert len(re.findall(r"s32\[8\]\S* all-reduce(?:-start)?\(", hlo)) == 1
        assert len(re.findall(r"s32\[4\]\S* all-reduce(?:-start)?\(", hlo)) == 1
        assert len(crossing) == 4
        assert len(SOLVE_COUNTS) == 12


class TestKMeansInitCompiledForTheChip:
    """ISSUE 31, at the benchmark's size (25,000,000 x 50 on one v5e):
    what the chip's compiler makes of ``kmeans.init_scalable`` with the
    fold at the width of the round's draw.  Compiled, never run.  (In
    this file because one process at a time may load the TPU's library:
    ``v5e_chip``.)"""

    ROWS, D, ELL, CAP = 25_000_000, 50, 16.0, 64

    def test_every_branch_is_one_fusion_fed_by_the_table(self, v5e_chip):
        import re

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dask_ml_tpu.cluster import k_means as km
        from dask_ml_tpu.core.mesh import MeshHolder

        def S(shape, spec, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(v5e_chip, spec))

        row = S((self.ROWS,), P("data"))
        args = (S((self.ROWS, self.D), P("data", None)), row, row,
                S((self.D,), P()), row, S((2,), P(), jnp.uint32),
                S((), P(), jnp.int32))
        with _compile_cache_off():
            compiled = km._init_rounds.lower(
                *args, ell=self.ELL, cap=self.CAP, max_rounds=32,
                mesh_holder=MeshHolder(v5e_chip), scatter="onehot2").compile()
        widths = km._fold_widths(self.ELL, self.CAP)
        assert widths == (16, 24, 32, 64)
        # six vectors of a row's length (601,733,632 B), and no branch
        # adds a rows x width array (1.6 to 6.4 GB) of its own.  The
        # parent's 500,955,136 B were five, the carried distances among
        # them in fast memory (95 MiB, "color 1" of the compiler's buffer
        # assignment); so they are with up to three branches, and with
        # four all six lie in HBM (PERF.md section 6, PR 31)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.61e9
        hlo = compiled.as_text()
        bodies = dict(re.findall(
            r"^(?:ENTRY )?(%[\w.-]+) [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S))
        fused = set(re.findall(r"calls=(%[\w.-]+)", hlo))
        # rows x width exists inside a fusion only: no instruction of a
        # computation that is not fused (so none with a buffer) has it
        for name, body in bodies.items():
            if name not in fused:
                assert not re.search(
                    r"= f32\[%d,(%s)\]" % (
                        self.ROWS, "|".join(map(str, widths))), body), name
        (branches,) = re.findall(r"branch_computations=\{([^}]*)\}", hlo)
        branches = branches.split(", ")
        assert len(branches) == len(widths)
        table = r"f32\[%d,%d\]" % (self.ROWS, self.D)
        for branch, width in zip(branches, widths):
            # one fusion reads the table, and its product is width wide
            (param,) = re.findall(
                r"(%%[\w.-]+) = %s\S* get-tuple-element" % table,
                bodies[branch])
            (fusion,) = re.findall(
                r"fusion\([^)]*%s[,)].*?calls=(%%[\w.-]+)" % re.escape(param),
                bodies[branch])
            assert re.search(
                r"= f32\[%d,%d\]\S* convolution\(.*operand_precision="
                r"\{highest,highest\}" % (self.ROWS, width), bodies[fusion])


class TestKMeansTailCompiledForTheChip:
    """ISSUE 37, at the benchmark's size (25,000,000 x 50 on one v5e): the
    two programs of a k-means fit that are none of its solver's, the
    stopping threshold and the last assignment, read the table once each.
    Compiled, never run.  (In this file because one process at a time may
    load the TPU's library: ``v5e_chip``.)"""

    ROWS, D, K = 25_000_000, 50, 8

    def _compiled(self, mesh, program, *operands, rows=ROWS, **static):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        specs = {"table": ((rows, self.D), P("data", None)),
                 "row": ((rows,), P("data")),
                 "centers": ((self.K, self.D), P())}
        args = [jax.ShapeDtypeStruct(
            specs[o][0], jnp.float32,
            sharding=NamedSharding(mesh, specs[o][1]))
            if isinstance(o, str) else o for o in operands]
        with _compile_cache_off():
            return program.lower(*args, **static).compile()

    def _fed_by_the_table(self, compiled):
        """The entry computation's fusions that take the table, each as
        ``(result shapes, what it calls, its line)``."""
        import re

        hlo = compiled.as_text()
        (entry,) = re.findall(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S)
        (table,) = re.findall(
            r"(%%[\w.-]+) = f32\[%d,%d\]\S* parameter\(" % (self.ROWS, self.D),
            entry)
        return hlo, entry, [
            line for line in entry.splitlines()
            if re.search(r" fusion\([^)]*%s[,)]" % re.escape(table), line)]

    def test_the_tolerance_reads_the_table_once(self, v5e_chip):
        import re

        from dask_ml_tpu.cluster import k_means as km
        from dask_ml_tpu.core.mesh import MeshHolder

        compiled = self._compiled(v5e_chip, km._tol, "table", "row", 1e-4,
                                  mesh_holder=MeshHolder(v5e_chip))
        # 129,024 B when written: the sampled rows and their sums
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6
        hlo, entry, fed = self._fed_by_the_table(compiled)
        # behind a ``lax.cond`` a pass over the table asks for a copy of
        # it in row-major tiles, 11.92 GB (PERF.md section 6, PR 37)
        assert "conditional" not in hlo
        sample = [line for line in fed if "gather" in line]
        passes = [line for line in fed if "gather" not in line]
        # the sample: about 1,024 rows at a fixed stride, not a read
        (sample,) = sample
        (rows,) = re.findall(r"= f32\[(\d+),%d\]" % self.D, sample)
        assert 1024 <= int(rows) <= 1026
        # ONE pass, with both column sums as its results
        assert len(passes) == 1
        assert re.search(
            r"= \(f32\[%d\]\S*, f32\[%d\]\S*\) fusion\(" % (self.D, self.D),
            passes[0])
        # and nothing of the table's size is made anywhere
        assert not re.search(r"= f32\[%d,%d\]\S* (?!parameter)" % (
            self.ROWS, self.D), entry)

    def test_four_chips_exchange_the_tolerances_sums_alone(self, v5e_devices):
        """Each shard samples and sums its own rows: what crosses chips
        is the sample's ``d + 1`` numbers and the pass's sums, all-reduced,
        and nothing is gathered."""
        import re

        from jax.sharding import Mesh

        from dask_ml_tpu.cluster import k_means as km
        from dask_ml_tpu.core.mesh import MeshHolder

        mesh = Mesh(np.array(v5e_devices[:4]).reshape(4, 1),
                    ("data", "model"))
        hlo = self._compiled(mesh, km._tol, "table", "row", 1e-4,
                             rows=4 * self.ROWS,
                             mesh_holder=MeshHolder(mesh)).as_text()
        assert not re.search(r"all-gather|all-to-all|collective-permute",
                             hlo)
        reduced = re.findall(r"= ([^=\n]*?) all-reduce(?:-start)?\(", hlo)
        assert len(reduced) == 2  # the sample's sums, then the pass's
        for results in reduced:
            for dims in re.findall(r"f32\[([0-9,]*)\]", results):
                assert int(np.prod([int(n) for n in dims.split(",") if n]
                                   or [1])) <= self.D + 1, results

    def test_the_assignment_on_carried_norms_reads_the_table_once(
            self, v5e_chip):
        from dask_ml_tpu.cluster import k_means as km

        given = self._compiled(
            v5e_chip, km._assign, "table", "row", "centers", "row")
        assert given.memory_analysis().temp_size_in_bytes < 1e6  # 0
        (fusion,) = self._fed_by_the_table(given)[2]
        assert "highest" in given.as_text()
        assert "s32[%d]" % self.ROWS in fusion  # the distances' argmin

    def test_the_three_operand_assignment_is_the_parents_program(
            self, v5e_chip):
        """What ``predict``, ``score`` and ``MiniBatchKMeans`` run: two
        reads of the table and the norms as a temporary, as before."""
        from dask_ml_tpu.cluster import k_means as km

        plain = self._compiled(v5e_chip, km._assign, "table", "row",
                               "centers")
        # 100,108,800 B: |x|^2, written out between the two
        assert plain.memory_analysis().temp_size_in_bytes >= 4 * self.ROWS
        norms, distances = self._fed_by_the_table(plain)[2]
        assert "= f32[%d]" % self.ROWS in norms
        assert "s32[%d]" % self.ROWS in distances


class TestTsqrRCompiledForTheChip:
    """ISSUE 32, at the benchmark's size (25,000,000 x 64 on one v5e; 100M
    rows over four): what the chip's compiler makes of ``tsqr.r``, the
    factorization ``PCA.fit`` keeps R of and never Q.  Compiled, never
    run.  (In this file because one process at a time may load the TPU's
    library: ``v5e_chip``.)"""

    ROWS, D = 25_000_000, 64

    def _compiled(self, mesh, rows, strategy="cholqr2"):
        import importlib

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        tsqr = importlib.import_module("dask_ml_tpu.linalg.tsqr")

        def S(shape, spec):
            return jax.ShapeDtypeStruct(
                shape, jnp.float32, sharding=NamedSharding(mesh, spec))

        with _compile_cache_off():
            return tsqr._tsqr_r_impl.lower(
                S((rows, self.D), P("data", None)), S((rows,), P("data")),
                S((self.D,), P()), mesh_holder=tsqr._MeshHolder(mesh),
                find_mean=True, strategy=strategy).compile()

    def test_no_array_of_the_tables_size_is_written(self, v5e_chip):
        import re

        compiled = self._compiled(v5e_chip, self.ROWS)
        table = self.ROWS * self.D * 4
        # ISSUE 32 asked for temporaries under a tenth of the table; the
        # passes keep a step's slab and (32, 64, 64) sums: under a
        # hundredth (451,584 B when written)
        assert compiled.memory_analysis().temp_size_in_bytes < table / 100
        hlo = compiled.as_text()
        # one loop a pass, each under its named scope, and on one chip no
        # collective at all
        for scope in ("pca.mean", "pca.gram", "pca.repair"):
            assert re.search(r"while\(.*op_name=\"jit\(_tsqr_r_fn\)/%s/while"
                             % re.escape(scope), hlo), scope
        assert not re.search(r"all-reduce|all-gather|all-to-all", hlo)

    def test_four_chips_exchange_only_d_by_d(self, v5e_devices):
        import re

        from jax.sharding import Mesh

        mesh = Mesh(np.array(v5e_devices[:4]).reshape(4, 1),
                    ("data", "model"))
        hlo = self._compiled(mesh, 4 * self.ROWS).as_text()
        shapes = re.findall(
            r"= \(?([a-z0-9]+\[[0-9,]*\])[^=\n]*? all-(?:reduce|gather)"
            r"(?:-start)?\(", hlo)
        assert shapes  # the sums do cross chips
        for shape in shapes:
            dims = [int(n) for n in re.findall(r"\d+", shape.split("[")[1])]
            assert int(np.prod(dims or [1])) <= self.D * self.D, shape


class TestSearchCompiledForTheChip:
    """ISSUE 39, at the benchmark's size (31,250,000 x 28 on one v5e,
    ``cv=3``, eight values of ``C``): what the chip's compiler makes of a
    search's programs: a fold cut as slabs, the fold's eight lanes, the
    gemm that scores them.  Compiled, never run.  (In this file because
    one process at a time may load the TPU's library: ``v5e_chip``.)"""

    ROWS, D, LANES = 31_250_000, 28, 8
    EDGES = (0, 10_416_666, 20_833_333, 31_250_000)
    TABLE = 31_250_000 * 32 * 4  # 28 columns lie on 32 sublanes

    @staticmethod
    def _shapes(mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def S(shape, dtype=jnp.float32):
            spec = P("data", *[None] * (len(shape) - 1)) if shape and (
                shape[0] > 1000) else P()
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))

        return S

    @pytest.mark.parametrize("fold", [0, 1, 2])
    def test_a_fold_is_slices_and_at_most_one_slab_of_temporaries(
            self, v5e_chip, fold):
        import re

        from dask_ml_tpu.core.mesh import MeshHolder
        from dask_ml_tpu.model_selection import KFold
        from dask_ml_tpu.model_selection._split import _fold_slabs_fn

        assert tuple(KFold(3).bounds(self.ROWS)) == self.EDGES
        S = self._shapes(v5e_chip)
        lo, hi = self.EDGES[fold:fold + 2]
        with _compile_cache_off():
            compiled = _fold_slabs_fn.lower(
                (S((self.ROWS, self.D)), S((self.ROWS,))), lo=lo, hi=hi,
                n=self.ROWS, mesh_holder=MeshHolder(v5e_chip)).compile()
        memory = compiled.memory_analysis()
        # what the caller holds: the table and its labels (no index, no
        # mask goes in), and what comes out: both sides of both, and a
        # mask a side: a second table and three row vectors
        assert memory.argument_size_in_bytes < self.TABLE + 1.01 * 4 * self.ROWS
        assert memory.output_size_in_bytes < self.TABLE + 3.01 * 4 * self.ROWS
        # an end fold's train side is ONE slice: nothing beside the
        # output.  The middle fold's is two pieces, which the compiler
        # cuts out before it joins them: the train slab once more
        # (2,666,821,632 B when written), never the table
        slab = (self.ROWS - (hi - lo)) * 32 * 4
        assert memory.temp_size_in_bytes <= (1.001 * slab if fold == 1 else 0)
        hlo = compiled.as_text()
        assert not re.search(r" gather\(| sort\(", hlo)  # nothing by index

    def _lanes(self, mesh, rows):
        """``_sweep_lanes`` compiled for eight ``lbfgs`` lanes of
        ``LogisticRegression`` over a fold's train slab of ``rows``, as
        the search calls it (``lambda_sweep``)."""
        from dask_ml_tpu.solvers.algorithms import (
            _lbfgs_lane, _sweep_lanes, sweep_objective)

        assert sweep_objective("lbfgs", Logistic) == "linear"
        S = self._shapes(mesh)
        with _compile_cache_off():
            return _sweep_lanes.lower(
                S((rows, self.D)), S((rows,)), S((rows,)), S((self.LANES,)),
                S((self.LANES, self.D + 1)), S((), jnp.int32), S(()),
                run=_lbfgs_lane, family=Logistic, reg=L2,
                extra_kw=(("line_search", "backtrack"),),
                counts=True).compile()

    def test_eight_lanes_hold_a_fold_and_their_temporaries(self, v5e_chip):
        rows = self.ROWS - self.EDGES[1]  # the first fold's train rows
        compiled = self._lanes(v5e_chip, rows)
        memory = compiled.memory_analysis()
        vector = rows * 4
        assert memory.argument_size_in_bytes < rows * 32 * 4 + 2.01 * vector
        # each lane's images of x and p at a row's length, the residual
        # made inside the gradient's product: 17 row vectors when written
        # (1,421,454,336 B; 1,420,318,720 on the black box), 3.4 GB under
        # the chip's 15.75 with the table and the fold resident (8.5 GB)
        assert memory.temp_size_in_bytes < 17.5 * vector
        assert "jit__sweep_lanes" in compiled.as_text()[:200]

    @pytest.mark.parametrize("fold", [0, 1])
    def test_the_lanes_read_the_table_by_float32_reductions(
            self, v5e_chip, fold):
        """Eight lanes under ``vmap`` make no product with the table at
        the default precision, which would feed it a bfloat16 copy of
        the table: they read it by the pair of images, a float32
        reduction, and by the gradient, a product at ``HIGHEST``.  Both
        train slabs' lengths a search compiles (the first fold's, and
        the others')."""
        import re

        rows = self.ROWS - (self.EDGES[fold + 1] - self.EDGES[fold])
        hlo = self._lanes(v5e_chip, rows).as_text()
        table = r"f32\[%d,%d\]" % (rows, self.D)
        assert not re.search(r"bf16\[[^\]]*\] convert\(", hlo)
        assert "bf16[%d,%d]" % (rows, self.D) not in hlo
        products = re.findall(r" (?:dot|convolution)\(.*", hlo)
        assert products and all(
            "operand_precision={highest,highest}" in p for p in products)
        # the fusions the table feeds: the pair X @ [x | p] for all lanes
        # (two f32[8, rows] results), the gradient X' r (f32[8, 28]) and
        # the first image (f32[8, rows]), each in float32
        # (the table reaches the products by bitcasts: never a copy)
        fed = re.findall(r"^%%([\w.-]+) \([^)]*%s[^)]*\) -> (.+) \{$"
                         % table, hlo, re.M)
        assert all(name.startswith("bitcast_fusion")
                   for name, out in fed if out == "f32[%d,%d]" % (
                       rows, self.D)), fed
        fed = [out for _, out in fed if out != "f32[%d,%d]" % (rows, self.D)]
        lanes = r"f32\[%d,%d\]" % (self.LANES, rows)
        assert any(re.fullmatch(r"\(%s, %s\)" % (lanes, lanes), out)
                   for out in fed), fed
        assert "f32[%d,%d]" % (self.LANES, self.D) in fed, fed
        assert set(fed) <= {"(f32[%d,%d], f32[%d,%d])" % (
            self.LANES, rows, self.LANES, rows),
            "f32[%d,%d]" % (self.LANES, rows),
            "(f32[%d], f32[%d], f32[%d,%d])" % (
                self.LANES, self.LANES, self.LANES, rows),
            "f32[%d,%d]" % (self.LANES, self.D)}, fed

    def test_admm_and_refit_lower_alike_after_the_lanes(self, v5e_devices):
        """The lanes' own way of forming their products leaves the single
        solves alone: ``_admm_run`` in both line searches, on one shard
        and on four, and ``_lbfgs_run`` as a search's refit runs it lower
        to the same text (locations stripped) whether or not the lanes'
        program was traced before them in the process."""
        import jax
        from jax.sharding import Mesh

        from dask_ml_tpu.core.mesh import MeshHolder
        from dask_ml_tpu.solvers.algorithms import _admm_run, _lbfgs_run

        def texts():
            jax.clear_caches()  # every lowering below traces anew
            out = []
            for shards, ls in ((1, "backtrack"), (1, "probe_grid"),
                               (4, "backtrack")):
                mesh = Mesh(np.array(v5e_devices[:shards]).reshape(
                    shards, 1), ("data", "model"))
                S, rows = self._shapes(mesh), self.ROWS * shards
                out.append(_admm_run.lower(
                    S((rows, self.D)), S((rows,)), S((rows,)),
                    *[S(())] * 5, S((), jnp.int32), S((self.D + 1,)),
                    family=Logistic, reg=L2, mesh_holder=MeshHolder(mesh),
                    inner_iter=30, line_search=ls,
                    objective="linear").as_text())
            S = self._shapes(Mesh(np.array(v5e_devices[:1]).reshape(1, 1),
                                  ("data", "model")))
            out.append(_lbfgs_run.lower(
                S((self.ROWS, self.D)), S((self.ROWS,)), S((self.ROWS,)),
                S((self.D + 1,)), S(()), S((), jnp.int32), S(()),
                family=Logistic, reg=L2, line_search="probe_grid",
                objective="linear").as_text())
            assert "loc(" not in out[0]
            return out

        before = texts()
        self._lanes(Mesh(np.array(v5e_devices[:1]).reshape(1, 1),
                         ("data", "model")), self.ROWS - self.EDGES[1])
        try:
            assert texts() == before
        finally:
            jax.clear_caches()

    def test_the_scoring_gemm_makes_no_rows_by_lanes_array(self, v5e_chip):
        from dask_ml_tpu.model_selection._search import _sweep_kernels

        S = self._shapes(v5e_chip)
        rows = self.EDGES[1]  # a held-out slab
        acc, _ = _sweep_kernels()
        with _compile_cache_off():
            compiled = acc.lower(
                S((rows, self.D)), S((rows,)), S((rows,)),
                S((self.LANES, self.D + 1)), fit_intercept=True).compile()
        # eta for eight lanes would be rows x 8 (1.3 GB on eight
        # sublanes): the compare, the mask and the sum fuse into the
        # product's consumer
        assert compiled.memory_analysis().temp_size_in_bytes < rows * 4


class TestSgdPassCompiledForTheChip:
    """At the benchmark's size (62,500,000 x 28 on one v5e, 60
    blocks of 1,048,576 rows): one ``partial_fit`` pass over the resident
    table, ``linear_model/_sgd_pass.py``, reads its blocks where they lie.
    On the chip its steps take their sums from the one-read kernel
    (``_sgd_kernel.py``), which reads the table's transpose: a bitcast,
    since the table lies with its rows minor.  The XLA step asks for
    float32 products, which at the TPU's default precision a one-vs-all
    step would feed a bfloat16 copy of the table.  Compiled, never run.
    (In this file because one process at a time may load the TPU's
    library: ``v5e_chip``.)"""

    ROWS, D, BLOCK = 62_500_000, 28, 1_048_576

    def _compiled(self, mesh, classes, monkeypatch, precision=None,
                  kernel=True):
        import contextlib

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dask_ml_tpu.core.mesh import MeshHolder
        from dask_ml_tpu.linear_model import _sgd_pass

        if precision is not None:  # the default: what the pass refuses
            monkeypatch.setattr(jax, "default_matmul_precision",
                                lambda _: contextlib.nullcontext())
            jax.clear_caches()  # no trace of the pass as it asks is reused

        def S(shape, spec, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=NamedSharding(mesh, spec))

        k = 1 if classes == 2 else classes
        state = {"coef": S((self.D, k), P()), "intercept": S((k,), P()),
                 "t": S((), P())}
        hyper = {name: S((), P()) for name in (
            "alpha", "eta0", "power_t", "t0", "l1_ratio", "epsilon",
            "eta_scale")}
        blocks = -(-self.ROWS // self.BLOCK)
        args = (state, S((self.ROWS, self.D), P("data", None)),
                S((self.ROWS,), P("data")), S((self.ROWS,), P("data")),
                S((blocks,), P(), jnp.int32), hyper, S((classes,), P()))
        with _compile_cache_off():
            return _sgd_pass._pass_program.lower(
                *args, block=self.BLOCK, targets="ova", loss="log_loss",
                penalty="l2", schedule="optimal", fit_intercept=True,
                mesh_holder=MeshHolder(mesh), axes=("data",),
                kernel=kernel).compile()

    @pytest.mark.parametrize("classes", [2, 3])
    def test_the_pass_copies_nothing_and_stays_float32(
            self, v5e_chip, classes, monkeypatch):
        """The path the chip takes: the kernel in ``jit__sgd_pass``, the
        table handed to it as its transpose, a bitcast of the parameter
        (``{0,1}``: rows minor), never a copy, a transpose or a slice."""
        import re

        compiled = self._compiled(v5e_chip, classes, monkeypatch)
        hlo = compiled.as_text()
        # 258,560 B (two classes) and 226,304 B (three) when written
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6
        assert "bf16[" not in hlo
        assert hlo.count('custom_call_target="tpu_custom_call"') == 1
        assert "f32[62500000,28]{0,1:T(8,128)} parameter(" in hlo
        table = [line for line in hlo.splitlines()
                 if re.search(r"= f32\[(62500000,28|28,62500000)\]", line)]
        ops = {re.search(r"\} ([\w-]+)\(", line).group(1) for line in table}
        assert ops <= {"parameter", "get-tuple-element", "bitcast"}, ops
        assert "f32[1048576,28]" not in hlo

    @pytest.mark.parametrize("classes", [2, 3])
    def test_the_xla_step_copies_nothing_and_stays_float32(
            self, v5e_chip, classes, monkeypatch):
        """The step off the kernel's path: two products a block."""
        compiled = self._compiled(v5e_chip, classes, monkeypatch,
                                  kernel=False)
        # 290,816 B (two classes) and 355,328 B (three) when written:
        # a block is read where it lies, never copied out of the table
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6
        assert "bf16[" not in compiled.as_text()
        assert "custom-call" not in compiled.as_text()

    def test_the_default_precision_would_copy_the_table(
            self, v5e_chip, monkeypatch):
        """Why the pass asks: three classes at the default precision make
        a bfloat16 copy of the table (4,000,328,704 B of temporaries when
        written), two classes are reductions either way."""
        import jax

        try:
            compiled = self._compiled(v5e_chip, 3, monkeypatch, "default",
                                      kernel=False)
        finally:
            jax.clear_caches()  # nor is this trace, by a later test
        assert compiled.memory_analysis().temp_size_in_bytes > 3.5e9
        assert "bf16[" in compiled.as_text()
