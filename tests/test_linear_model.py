import numpy as np
import pytest
import sklearn.linear_model as sl

import dask_ml_tpu.linear_model as dlm
from dask_ml_tpu.core import shard_rows


@pytest.fixture
def clf_data(rng):
    n, d = 400, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    p = 1 / (1 + np.exp(-(X @ w + 0.3)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return X, y


@pytest.fixture
def reg_data(rng):
    n, d = 300, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + 1.7 + 0.05 * rng.normal(size=n)).astype(np.float32)
    return X, y


class TestLogisticRegression:
    @pytest.mark.parametrize("solver", ["admm", "lbfgs", "newton", "proximal_grad"])
    def test_parity_with_sklearn(self, clf_data, solver):
        X, y = clf_data
        ours = dlm.LogisticRegression(solver=solver, C=1e4, max_iter=200).fit(
            shard_rows(X), shard_rows(y)
        )
        theirs = sl.LogisticRegression(C=1e4, tol=1e-8).fit(X, y)
        np.testing.assert_allclose(
            np.asarray(ours.coef_), theirs.coef_[0], atol=0.1
        )
        assert ours.intercept_ == pytest.approx(theirs.intercept_[0], abs=0.1)

    def test_predict_and_score(self, clf_data):
        X, y = clf_data
        lr = dlm.LogisticRegression(solver="lbfgs", C=10.0).fit(X, y)
        acc = lr.score(X, y)
        # sklearn scores exactly 0.815 on this fixture; match it
        assert acc > 0.80

    def test_predict_proba_shape_and_range(self, clf_data):
        X, y = clf_data
        lr = dlm.LogisticRegression(solver="lbfgs").fit(X, y)
        proba = np.asarray(lr.predict_proba(X))
        assert proba.shape == (400, 2)
        np.testing.assert_allclose(proba.sum(1), np.ones(400), atol=1e-5)

    def test_decision_function(self, clf_data):
        X, y = clf_data
        lr = dlm.LogisticRegression(solver="lbfgs").fit(X, y)
        eta = np.asarray(lr.decision_function(X))
        assert eta.shape == (400,)
        np.testing.assert_array_equal(
            eta > 0, np.asarray(lr.predict(X)).astype(bool)
        )

    def test_l1_penalty_sparsifies(self, clf_data):
        X, y = clf_data
        Xw = np.hstack([X, np.zeros((X.shape[0], 3), dtype=np.float32)])
        lr = dlm.LogisticRegression(penalty="l1", C=0.01, solver="admm").fit(Xw, y)
        coef = np.asarray(lr.coef_)
        assert np.sum(np.abs(coef[-3:]) < 1e-4) == 3

    def test_no_intercept(self, clf_data):
        X, y = clf_data
        lr = dlm.LogisticRegression(fit_intercept=False, solver="lbfgs").fit(X, y)
        assert lr.intercept_ == 0.0

    def test_bad_solver(self, clf_data):
        X, y = clf_data
        with pytest.raises(ValueError, match="solver"):
            dlm.LogisticRegression(solver="saga").fit(X, y)


class TestLinearRegression:
    def test_parity_with_sklearn(self, reg_data):
        X, y = reg_data
        ours = dlm.LinearRegression(solver="lbfgs", C=1e6, max_iter=300).fit(X, y)
        theirs = sl.LinearRegression().fit(X, y)
        np.testing.assert_allclose(np.asarray(ours.coef_), theirs.coef_, atol=2e-2)
        assert ours.intercept_ == pytest.approx(theirs.intercept_, abs=2e-2)

    def test_admm_solver(self, reg_data):
        X, y = reg_data
        ours = dlm.LinearRegression(solver="admm", C=1e6, max_iter=200).fit(
            shard_rows(X), shard_rows(y)
        )
        theirs = sl.LinearRegression().fit(X, y)
        np.testing.assert_allclose(np.asarray(ours.coef_), theirs.coef_, atol=5e-2)

    def test_r2_score(self, reg_data):
        X, y = reg_data
        lr = dlm.LinearRegression(solver="lbfgs", C=1e6).fit(X, y)
        assert lr.score(X, y) > 0.98


class TestPoissonRegression:
    def test_recovers_coefficients(self, rng):
        n, d = 500, 4
        X = (rng.normal(size=(n, d)) * 0.4).astype(np.float32)
        w = (rng.normal(size=d) * 0.5).astype(np.float32)
        y = rng.poisson(np.exp(X @ w + 0.2)).astype(np.float32)
        ours = dlm.PoissonRegression(solver="lbfgs", C=1e6, max_iter=300).fit(X, y)
        sk = sl.PoissonRegressor(alpha=0.0, tol=1e-8, max_iter=1000).fit(X, y)
        np.testing.assert_allclose(np.asarray(ours.coef_), sk.coef_, atol=5e-2)
        assert ours.intercept_ == pytest.approx(sk.intercept_, abs=5e-2)

    def test_predict_positive(self, rng):
        X = rng.normal(size=(100, 3)).astype(np.float32)
        y = rng.poisson(1.0, size=100).astype(np.float32)
        pr = dlm.PoissonRegression(solver="lbfgs").fit(X, y)
        assert (np.asarray(pr.predict(X)) > 0).all()

    def test_deviance_decreases_with_fit(self, rng):
        X = (rng.normal(size=(200, 3)) * 0.4).astype(np.float32)
        w = np.array([0.5, -0.3, 0.2], dtype=np.float32)
        y = rng.poisson(np.exp(X @ w)).astype(np.float32)
        fitted = dlm.PoissonRegression(solver="lbfgs", C=1e6).fit(X, y)
        unfitted = dlm.PoissonRegression(solver="lbfgs", max_iter=0 or 1, C=1e6)
        unfitted.coef_ = np.zeros(3, dtype=np.float32)
        unfitted.intercept_ = 0.0
        assert fitted.get_deviance(X, y) < unfitted.get_deviance(X, y)


class TestReviewRegressions:
    def test_score_with_sharded_y(self, clf_data):
        X, y = clf_data
        sX, sy = shard_rows(X), shard_rows(y)
        lr = dlm.LogisticRegression(solver="lbfgs", C=10.0).fit(sX, sy)
        assert lr.score(sX, sy) > 0.5

    def test_linear_score_with_sharded_y(self, reg_data):
        X, y = reg_data
        sX, sy = shard_rows(X), shard_rows(y)
        lr = dlm.LinearRegression(solver="lbfgs", C=1e6).fit(sX, sy)
        assert lr.score(sX, sy) > 0.9


class TestMixedPrecision:
    """bf16 design matrix + f32 parameters/accumulation: X's HBM traffic
    halves (the dominant solver cost on TPU) while every reduction and the
    fitted coefficients stay float32 (solvers.algorithms._param_dtype)."""

    @pytest.mark.parametrize("solver", ["admm", "lbfgs", "gradient_descent"])
    def test_bf16_design_matrix_converges(self, clf_data, solver):
        import jax.numpy as jnp

        X, y = clf_data
        f32 = dlm.LogisticRegression(solver=solver, C=10.0).fit(
            shard_rows(X), y
        )
        bf16 = dlm.LogisticRegression(solver=solver, C=10.0).fit(
            shard_rows(X, dtype=jnp.bfloat16), y
        )
        assert np.asarray(bf16.coef_).dtype == np.float32
        acc_f32 = f32.score(shard_rows(X), y)
        acc_bf16 = bf16.score(shard_rows(X, dtype=jnp.bfloat16), y)
        assert acc_bf16 >= acc_f32 - 0.02

    def test_bf16_regression(self, reg_data):
        import jax.numpy as jnp

        X, y = reg_data
        lr = dlm.LinearRegression(solver="lbfgs", C=1e6).fit(
            shard_rows(X, dtype=jnp.bfloat16), shard_rows(y)
        )
        assert np.asarray(lr.coef_).dtype == np.float32
        assert lr.score(shard_rows(X), y) > 0.85


class TestNIter:
    @pytest.mark.parametrize("solver", ["admm", "lbfgs", "newton",
                                        "gradient_descent", "proximal_grad"])
    def test_n_iter_recorded(self, clf_data, solver):
        X, y = clf_data
        lr = dlm.LogisticRegression(solver=solver).fit(shard_rows(X), y)
        assert lr.n_iter_.shape == (1,) and 1 <= lr.n_iter_[0] <= lr.max_iter

    def test_multiclass_n_iter_per_class(self, rng):
        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = rng.randint(0, 3, size=300)
        lr = dlm.LogisticRegression(solver="lbfgs").fit(shard_rows(X), y)
        assert lr.n_iter_.shape == (3,)

    def test_linear_regression_n_iter(self, reg_data):
        X, y = reg_data
        lr = dlm.LinearRegression(solver="lbfgs").fit(shard_rows(X), y)
        assert lr.n_iter_.shape == (1,)


@pytest.fixture
def multiclass_data(rng):
    n, d, K = 1200, 6, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    W = rng.normal(size=(d, K))
    y = (X @ W + rng.normal(scale=0.5, size=(n, K))).argmax(1)
    return X, y


class TestPackedOvR:
    """The K one-vs-rest solves run as ONE vmapped
    program (O(1) dispatches), with parity against sklearn OvR."""

    @pytest.mark.parametrize(
        "solver", ["lbfgs", "admm", "gradient_descent", "proximal_grad"]
    )
    def test_single_dispatch_and_accuracy(self, multiclass_data, mesh,
                                          solver):
        from dask_ml_tpu import solvers

        X, y = multiclass_data
        solvers.reset_dispatch_counts()
        lr = dlm.LogisticRegression(
            solver=solver, C=1.0, max_iter=150
        ).fit(X, y)
        assert solvers.DISPATCH_COUNTS["solves"] == 1
        assert lr.betas_.shape[0] == 4
        assert lr.n_iter_.shape == (4,)
        acc = float((lr.predict(X) == y).mean())
        sk = sl.LogisticRegression(C=1.0, max_iter=300).fit(X, y)
        assert acc >= sk.score(X, y) - 0.03

    def test_sharded_multiclass_single_dispatch(self, multiclass_data, mesh):
        from dask_ml_tpu import solvers

        X, y = multiclass_data
        sX, sy = shard_rows(X), shard_rows(y.astype(np.float32))
        solvers.reset_dispatch_counts()
        lr = dlm.LogisticRegression(solver="lbfgs", C=1.0, max_iter=150).fit(
            sX, sy
        )
        assert solvers.DISPATCH_COUNTS["solves"] == 1
        assert float((lr.predict(sX)[: len(y)] == y).mean()) > 0.8

    def test_packed_matches_sequential_loop(self, multiclass_data, mesh):
        # the packed program must agree with K independent solves
        from dask_ml_tpu.solvers import Logistic, lbfgs, packed_solve
        from dask_ml_tpu.core import shard_rows as _sr

        X, y = multiclass_data
        sX = _sr(X)
        n_pad = sX.data.shape[0]
        classes = np.unique(y)
        Y = np.zeros((len(classes), n_pad), np.float32)
        for i, c in enumerate(classes):
            Y[i, : len(y)] = (y == c)
        betas, n_its = packed_solve(
            "lbfgs", sX, Y, family=Logistic, lamduh=1.0, max_iter=150,
        )
        for i, c in enumerate(classes):
            b, n_it = lbfgs(
                sX, Y[i], family=Logistic, lamduh=1.0, max_iter=150,
                return_n_iter=True,
            )
            # loose rtol: the batched (vmapped) gemm accumulates in a
            # different order than K independent gemms, and converged
            # lanes hold their carry while stragglers iterate
            np.testing.assert_allclose(
                np.asarray(betas[i]), np.asarray(b), rtol=5e-3, atol=1e-3
            )

    @pytest.mark.parametrize("solver", ["admm", "lbfgs",
                                        "gradient_descent",
                                        "proximal_grad", "newton"])
    def test_lanes_match_one_binary_solve_per_class(
            self, multiclass_data, mesh, solver):
        """Every ``packed_solve`` solver: each one-vs-rest lane against
        the solver's own single solve of ``y == c``.  Tolerance as above:
        the lanes' vmapped gemms (and, for ``admm`` / ``lbfgs``, the
        black-box objective against the single solve's cached
        predictor) accumulate in another order."""
        from dask_ml_tpu import solvers
        from dask_ml_tpu.core import shard_rows as _sr

        X, y = multiclass_data
        sX = _sr(X)
        classes = np.unique(y)
        Y = np.zeros((len(classes), sX.data.shape[0]), np.float32)
        for i, c in enumerate(classes):
            Y[i, : len(y)] = (y == c)
        betas, n_its = solvers.packed_solve(
            solver, sX, Y, lamduh=1.0, max_iter=100)
        assert betas.shape == (len(classes), X.shape[1])
        assert n_its.shape == (len(classes),)
        # the single solve at the lanes' stopping tolerance (the entry
        # points' own defaults differ by solver; admm stops by Boyd's)
        kw = {} if solver == "admm" else {"tol": 1e-5}
        for i in range(len(classes)):
            b = getattr(solvers, solver)(sX, Y[i], lamduh=1.0, max_iter=100,
                                         **kw)
            np.testing.assert_allclose(
                np.asarray(betas[i]), np.asarray(b), rtol=5e-3, atol=1e-3)


class TestMultinomial:
    def test_parity_with_sklearn(self, multiclass_data, mesh):
        X, y = multiclass_data
        ours = dlm.LogisticRegression(
            solver="lbfgs", C=1.0, max_iter=300, multi_class="multinomial"
        ).fit(X, y)
        sk = sl.LogisticRegression(C=1.0, max_iter=300).fit(X, y)
        p_ours = np.asarray(ours.predict_proba(X))
        p_sk = sk.predict_proba(X)
        assert np.abs(p_ours - p_sk).max() < 0.02
        # coefs agree in the sum-to-zero gauge (softmax is shift-invariant
        # per feature; sklearn's multinomial is centered the same way)
        np.testing.assert_allclose(
            np.asarray(ours.coef_) - np.asarray(ours.coef_).mean(0),
            sk.coef_ - sk.coef_.mean(0), atol=5e-2,
        )
        assert ours.n_iter_.shape == (1,)

    def test_binary_multinomial_uses_sigmoid_path(self, clf_data, mesh):
        X, y = clf_data
        lr = dlm.LogisticRegression(
            solver="lbfgs", multi_class="multinomial", max_iter=100
        ).fit(X, y)
        assert lr.coef_.ndim == 1  # binary contract unchanged
        assert float((lr.predict(X) == y).mean()) > 0.8

    def test_invalid_multi_class_raises(self, clf_data, mesh):
        X, y = clf_data
        with pytest.raises(ValueError, match="multi_class"):
            dlm.LogisticRegression(multi_class="bogus").fit(X, y)

    def test_multinomial_newton_rejected(self, multiclass_data, mesh):
        X, y = multiclass_data
        with pytest.raises(ValueError, match="newton"):
            dlm.LogisticRegression(
                solver="newton", multi_class="multinomial"
            ).fit(X, y)


class TestSampleClassWeights:
    """Weights thread through the masked reductions."""

    def _imbalanced(self, rng, n=600, d=5, noisy=False):
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d)
        if noisy:
            p = 1 / (1 + np.exp(-(X @ w + 1.2)))
            return X, (rng.uniform(size=n) < p).astype(np.float32)
        return X, (X @ w + 1.2 > 0).astype(np.float32)  # skewed positive

    def test_logreg_balanced_parity_with_sklearn(self, rng, mesh):
        # noisy labels: a separable set makes the optimum ill-conditioned
        # and amplifies solver-tolerance differences
        X, y = self._imbalanced(rng, noisy=True)
        ours = dlm.LogisticRegression(
            solver="lbfgs", C=1.0, max_iter=500, tol=1e-8,
            class_weight="balanced",
        ).fit(X, y)
        sk = sl.LogisticRegression(
            C=1.0, max_iter=500, tol=1e-8, class_weight="balanced"
        ).fit(X, y)
        np.testing.assert_allclose(
            np.asarray(ours.coef_), sk.coef_[0], rtol=5e-2, atol=2e-2
        )
        np.testing.assert_allclose(
            float(ours.intercept_), sk.intercept_[0], rtol=5e-2, atol=2e-2
        )

    def test_logreg_integer_weights_equal_duplication(self, rng, mesh):
        X, y = self._imbalanced(rng, n=200)
        sw = rng.randint(1, 4, size=200)
        Xd = np.repeat(X, sw, axis=0)
        yd = np.repeat(y, sw)
        a = dlm.LogisticRegression(solver="lbfgs", C=1.0, max_iter=300).fit(
            X, y, sample_weight=sw
        )
        b = dlm.LogisticRegression(solver="lbfgs", C=1.0, max_iter=300).fit(
            Xd, yd
        )
        np.testing.assert_allclose(
            np.asarray(a.coef_), np.asarray(b.coef_), rtol=1e-3, atol=1e-4
        )

    def test_logreg_class_weight_dict_shifts_boundary(self, rng, mesh):
        X, y = self._imbalanced(rng)
        plain = dlm.LogisticRegression(solver="lbfgs", max_iter=200).fit(X, y)
        up = dlm.LogisticRegression(
            solver="lbfgs", max_iter=200, class_weight={0.0: 10.0, 1.0: 1.0}
        ).fit(X, y)
        # upweighting the minority class must increase its recall
        minority_recall = lambda m: float(  # noqa: E731
            ((np.asarray(m.predict(X)) == 0) & (y == 0)).sum()
        ) / max((y == 0).sum(), 1)
        assert minority_recall(up) >= minority_recall(plain)

    def test_linear_regression_sample_weight(self, rng, mesh):
        n, d = 200, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X @ rng.normal(size=d)).astype(np.float32)
        sw = rng.randint(1, 4, size=n)
        a = dlm.LinearRegression(solver="lbfgs", max_iter=300).fit(
            X, y, sample_weight=sw
        )
        b = dlm.LinearRegression(solver="lbfgs", max_iter=300).fit(
            np.repeat(X, sw, axis=0), np.repeat(y, sw)
        )
        np.testing.assert_allclose(
            np.asarray(a.coef_), np.asarray(b.coef_), rtol=1e-3, atol=1e-3
        )

    def test_string_labels_with_sample_weight(self, rng, mesh):
        # host string labels must survive the weighted path (no device cast)
        n, d = 200, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = np.where(X[:, 0] > 0, "dog", "cat")
        sw = rng.rand(n).astype(np.float32) + 0.5
        lr = dlm.LogisticRegression(
            solver="lbfgs", max_iter=100, class_weight="balanced"
        ).fit(X, y, sample_weight=sw)
        assert set(np.asarray(lr.predict(X)).tolist()) <= {"cat", "dog"}

    def test_sgd_regressor_rejects_short_sample_weight(self, rng, mesh):
        from dask_ml_tpu.linear_model import SGDRegressor

        X = rng.normal(size=(100, 4)).astype(np.float32)
        y = X[:, 0].astype(np.float32)
        with pytest.raises(ValueError, match="sample_weight"):
            SGDRegressor(max_iter=5).fit(X, y, sample_weight=np.ones(50))


class TestBinaryMultinomialPenalty:
    def test_binary_multinomial_equals_sigmoid_at_double_C(self, clf_data, mesh):
        # 2-class softmax == sigmoid at half the penalty (w0 = -w1 splits
        # the norm): the multinomial path must solve at lamduh/2
        X, y = clf_data
        mn = dlm.LogisticRegression(
            solver="lbfgs", C=1.0, max_iter=300, tol=1e-8,
            multi_class="multinomial",
        ).fit(X, y)
        sig2c = dlm.LogisticRegression(
            solver="lbfgs", C=2.0, max_iter=300, tol=1e-8,
        ).fit(X, y)
        np.testing.assert_allclose(
            np.asarray(mn.coef_), np.asarray(sig2c.coef_),
            rtol=1e-3, atol=1e-4,
        )


    def test_binary_multinomial_l1_matches_full_penalty_sigmoid(self, clf_data, mesh):
        # L1: the split-pair penalty minimizes to |w1-w0| in the optimal
        # gauge, so the true binary softmax L1 fit equals the sigmoid fit
        # at FULL lamduh (NOT half, which is the L2-only scaling)
        X, y = clf_data
        mn = dlm.LogisticRegression(
            multi_class="multinomial", penalty="l1",
            solver="proximal_grad", C=0.05, max_iter=500, tol=1e-9,
        ).fit(X, y)
        sig = dlm.LogisticRegression(
            penalty="l1", solver="proximal_grad", C=0.05, max_iter=500,
            tol=1e-9,
        ).fit(X, y)
        assert np.asarray(mn.coef_).shape == np.asarray(sig.coef_).shape
        np.testing.assert_allclose(
            np.asarray(mn.coef_), np.asarray(sig.coef_), atol=3e-2
        )


class TestClassWeightPackingRules:
    def test_class_weight_packing_rules(self, mesh):
        from dask_ml_tpu.linear_model import SGDClassifier as TpuSGD
        from dask_ml_tpu.model_selection._packing import pack_key

        assert pack_key(TpuSGD()) is not None
        # dict class weights pack (per-model stacked masks carry them);
        # 'balanced' stays unpackable — it needs the full label
        # distribution, which the block-streaming plane cannot give
        assert pack_key(TpuSGD(class_weight={0.0: 2.0})) is not None
        assert pack_key(TpuSGD(class_weight="balanced")) is None


class TestDeviceScore:
    def test_glm_device_score_matches_host(self, rng, mesh):
        n, d = 501, 5
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        lr = dlm.LogisticRegression(solver="lbfgs", max_iter=100).fit(X, y)
        dev = lr.score(shard_rows(X), shard_rows(y))
        host = lr.score(X, y)
        assert dev == pytest.approx(host, abs=1e-6)

    def test_glm_device_score_multiclass(self, rng, mesh):
        X = rng.normal(size=(600, 5)).astype(np.float32)
        W = rng.normal(size=(5, 3))
        y = (X @ W).argmax(1).astype(np.float32)
        lr = dlm.LogisticRegression(solver="lbfgs", max_iter=100).fit(X, y)
        assert lr.score(shard_rows(X), shard_rows(y)) == pytest.approx(
            lr.score(X, y), abs=1e-6
        )


class TestClassWeightValidation:
    def test_unknown_dict_key_raises(self, clf_data, mesh):
        X, y = clf_data
        with pytest.raises(ValueError, match="class_weight keys"):
            dlm.LogisticRegression(
                solver="lbfgs", max_iter=10, class_weight={7.0: 2.0}
            ).fit(X, y)

    def test_sgd_unknown_dict_key_raises(self, clf_data, mesh):
        from dask_ml_tpu.linear_model import SGDClassifier

        X, y = clf_data
        with pytest.raises(ValueError, match="class_weight keys"):
            SGDClassifier(max_iter=5, class_weight={"dog": 2.0}).fit(X, y)


class TestDeviceIngest:
    """Raw jax.Array inputs stay on device end to end (the r5 ingest
    round-trip fix): wrapping is a device-side reshard and label
    discovery fetches only the K unique values."""

    def test_raw_device_labels_full_estimator(self, mesh, rng):
        # raw jnp X AND y through the estimator: classes discovered on
        # device (only K scalars cross), OvR and multinomial both solve
        import jax.numpy as _jnp

        from dask_ml_tpu.linear_model import LogisticRegression

        X = _jnp.asarray(rng.normal(size=(300, 8)).astype(np.float32))
        w = rng.normal(size=8)
        y = _jnp.asarray(
            np.digitize(np.asarray(X) @ w, [-0.5, 0.5]).astype(np.float32))
        for mc in ("ovr", "multinomial"):
            lr = LogisticRegression(solver="lbfgs", C=10.0, max_iter=60,
                                    multi_class=mc).fit(X, y)
            assert set(np.asarray(lr.classes_)) == {0.0, 1.0, 2.0}
            acc = (np.asarray(lr.predict(X)) == np.asarray(y)).mean()
            assert acc > 0.8, (mc, acc)

    def test_device_input_stays_on_device(self, monkeypatch, mesh, rng):
        # the r5 round-trip bug: shard_rows/_prep must never fetch a
        # device-resident input back to host (np.asarray on a jax.Array
        # is a device->host transfer, paid PER SOLVER CALL)
        import jax as _jax
        import jax.numpy as _jnp

        import dask_ml_tpu.core.sharded as sharded_mod
        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.solvers import Logistic, lbfgs

        Xd = _jnp.asarray(rng.normal(size=(64, 5)).astype(np.float32))
        yd = (Xd[:, 0] > 0).astype(_jnp.float32)

        real_asarray = np.asarray

        def guarded(a, *args, **kw):
            assert not isinstance(a, _jax.Array), (
                "np.asarray called on a device array inside the ingest "
                "path — device->host round trip")
            return real_asarray(a, *args, **kw)

        monkeypatch.setattr(sharded_mod.np, "asarray", guarded)
        sX = shard_rows(Xd)
        assert sX.n_samples == 64
        monkeypatch.undo()
        # end-to-end: device X and device y through the solver wrapper
        b = lbfgs(Xd, yd, family=Logistic, lamduh=0.1, max_iter=20)
        assert np.isfinite(np.asarray(b)).all()

    def test_sequential_matches_packed(self, multiclass_data, mesh):
        # the one-vs-rest lanes (one dispatch) against K binary fits on
        # y == c, one solve each.  Tolerance = the stagnation-exit noise
        # floor: both stop when the fp32 objective can no longer certify
        # progress (lbfgs_core round-5 exit), and lanes (black-box
        # objective, vmapped gemms) and single fits (cached predictor)
        # accumulate in different orders inside that certified band
        from dask_ml_tpu import solvers

        X, y = multiclass_data
        kw = dict(solver="lbfgs", C=1.0, max_iter=150)
        solvers.reset_dispatch_counts()
        lr = dlm.LogisticRegression(**kw).fit(X, y)
        assert solvers.DISPATCH_COUNTS["solves"] == 1
        classes = np.unique(y)
        binary = [dlm.LogisticRegression(**kw).fit(
            X, (y == c).astype(np.float32)) for c in classes]
        assert solvers.DISPATCH_COUNTS["solves"] == 1 + len(classes)
        np.testing.assert_allclose(
            np.asarray(lr.betas_),
            np.stack([np.asarray(b.betas_[0]) for b in binary]),
            rtol=5e-3, atol=5e-3)
