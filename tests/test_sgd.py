"""Device-native SGD estimator tests.

Pattern per SURVEY.md §4: convergence parity vs sklearn at the accuracy
level (loose tolerance for iterative solvers), plus the contracts the
adaptive searches rely on (partial_fit block streaming, classes on first
call, warm restart, device residency).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu.core import shard_rows, unshard
from dask_ml_tpu.linear_model import SGDClassifier, SGDRegressor


def _binary_data(rng, n=600, d=8):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + 0.1 * rng.normal(size=n) > 0).astype(np.int64)
    return X, y


def clf_targets(clf, y, classes):
    """Encode y the way partial_fit would (for shape inspection in tests)."""
    if not hasattr(clf, "classes_"):
        clf.classes_ = np.sort(np.asarray(classes))
    return clf._encode_targets(np.asarray(y))


def _multiclass_data(rng, n=900, d=6, k=4):
    from sklearn.datasets import make_blobs

    X, y = make_blobs(n_samples=n, n_features=d, centers=k,
                      cluster_std=1.0, random_state=7)
    return X.astype(np.float32), y


class TestSGDClassifier:
    def test_binary_parity_with_sklearn(self, rng):
        from sklearn.linear_model import SGDClassifier as SkSGD

        X, y = _binary_data(rng)
        ours = SGDClassifier(alpha=1e-4, max_iter=300, tol=None).fit(X, y)
        theirs = SkSGD(alpha=1e-4, max_iter=50, tol=None, random_state=0).fit(X, y)
        acc_ours = (ours.predict(X) == y).mean()
        acc_theirs = (theirs.predict(X) == y).mean()
        assert acc_ours > 0.9
        assert acc_ours >= acc_theirs - 0.05

    def test_multiclass_labels_and_proba(self, rng):
        X, y = _multiclass_data(rng)
        clf = SGDClassifier(max_iter=300, tol=None).fit(X, y)
        assert list(clf.classes_) == [0, 1, 2, 3]
        pred = clf.predict(X)
        assert pred.dtype == y.dtype  # real labels, not booleans
        assert (pred == y).mean() > 0.9
        proba = np.asarray(clf.predict_proba(X))
        assert proba.shape == (len(y), 4)
        np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-5)
        assert clf.coef_.shape == (4, X.shape[1])

    def test_string_labels(self, rng):
        X, y = _binary_data(rng)
        labels = np.array(["neg", "pos"])[y]
        clf = SGDClassifier(max_iter=200, tol=None).fit(X, labels)
        assert set(clf.predict(X[:10])) <= {"neg", "pos"}
        assert clf.coef_.shape == (1, X.shape[1])

    def test_partial_fit_stream_requires_classes(self, rng):
        X, y = _binary_data(rng)
        clf = SGDClassifier()
        with pytest.raises(ValueError, match="classes"):
            clf.partial_fit(X[:100], y[:100])

    def test_partial_fit_stream_converges(self, rng):
        X, y = _binary_data(rng, n=2000)
        clf = SGDClassifier(learning_rate="constant", eta0=0.5)
        classes = np.unique(y)
        for epoch in range(30):
            for lo in range(0, len(X), 256):
                clf.partial_fit(X[lo:lo + 256], y[lo:lo + 256], classes=classes)
        assert (clf.predict(X) == y).mean() > 0.9
        assert clf.t_ == 30 * len(range(0, len(X), 256))

    def test_ragged_blocks_bounded_compiles(self, rng):
        # Streaming ragged chunk sizes must hit the bucket padding, not
        # recompile per shape: every chunk <=256 pads to the SAME 256-row
        # program shape.
        from dask_ml_tpu.linear_model._sgd import _bucket_rows

        sizes = (100, 101, 117, 250, 255, 256, 90)
        assert {_bucket_rows(s) for s in sizes} == {256}
        assert _bucket_rows(257) == 1024
        assert _bucket_rows(70000) == 65536 * 2  # beyond top bucket: rounded up

        X, y = _binary_data(rng, n=700)
        clf = SGDClassifier(learning_rate="constant", eta0=0.1)
        classes = np.unique(y)
        shapes = set()
        for size in sizes:
            xb, yb, mask = clf._prep_block(
                X[:size], clf_targets(clf, y[:size], classes)
            )
            shapes.add(xb.shape)
            clf.partial_fit(X[:size], y[:size], classes=classes)
        assert shapes == {(256, X.shape[1])}  # one compiled shape for all

    def test_sharded_rows_input(self, rng, mesh):
        X, y = _binary_data(rng, n=333)  # not divisible by 8: pad+mask path
        Xs, ys = shard_rows(X), shard_rows(y.astype(np.float32))
        clf = SGDClassifier(max_iter=300, tol=None).fit(Xs, ys)
        assert (clf.predict(Xs) == y).mean() > 0.9
        dense = SGDClassifier(max_iter=300, tol=None).fit(X, y)
        np.testing.assert_allclose(
            clf.coef_, dense.coef_, rtol=1e-3, atol=1e-4
        )

    def test_device_resident_state(self, rng):
        X, y = _binary_data(rng)
        clf = SGDClassifier(max_iter=20, tol=None).fit(X, y)
        assert isinstance(clf._state["coef"], jax.Array)

    def test_hinge_and_penalties(self, rng):
        X, y = _binary_data(rng)
        for loss in ("hinge", "squared_hinge", "modified_huber", "log_loss"):
            for penalty in ("l2", "l1", "elasticnet"):
                clf = SGDClassifier(loss=loss, penalty=penalty, max_iter=150,
                                    tol=None).fit(X, y)
                assert (clf.predict(X) == y).mean() > 0.85, (loss, penalty)

    def test_proba_unavailable_for_hinge(self, rng):
        X, y = _binary_data(rng)
        clf = SGDClassifier(loss="hinge", max_iter=20).fit(X, y)
        with pytest.raises(AttributeError):
            clf.predict_proba(X)

    def test_clone_contract(self):
        from sklearn.base import clone

        clf = SGDClassifier(alpha=0.5, loss="hinge")
        c = clone(clf)
        assert c.get_params()["alpha"] == 0.5
        assert c.get_params()["loss"] == "hinge"


class TestSGDRegressor:
    def test_parity_with_sklearn(self, rng):
        from sklearn.linear_model import SGDRegressor as SkSGD

        n, d = 800, 6
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d)
        y = (X @ w + 0.05 * rng.normal(size=n)).astype(np.float32)
        ours = SGDRegressor(max_iter=500, tol=None,
                            learning_rate="constant", eta0=0.1).fit(X, y)
        assert ours.score(X, y) > 0.98
        theirs = SkSGD(max_iter=100, tol=None, random_state=0).fit(X, y)
        assert ours.score(X, y) >= theirs.score(X, y) - 0.02
        np.testing.assert_allclose(ours.coef_, w, rtol=0.1, atol=0.05)

    def test_huber_loss(self, rng):
        n, d = 600, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d)
        y = X @ w
        y[::50] += 50.0  # outliers
        hub = SGDRegressor(loss="huber", epsilon=0.5, max_iter=800, tol=None,
                           learning_rate="constant", eta0=0.05).fit(X, y)
        clean = ~(np.arange(n) % 50 == 0)
        pred = np.asarray(hub.predict(X))
        assert np.corrcoef(pred[clean], y[clean])[0, 1] > 0.95

    def test_partial_fit_stream(self, rng):
        n, d = 2000, 5
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d)
        y = X @ w
        reg = SGDRegressor(learning_rate="constant", eta0=0.1)
        for _ in range(40):
            for lo in range(0, n, 500):
                reg.partial_fit(X[lo:lo + 500], y[lo:lo + 500])
        assert reg.score(X, y) > 0.98

    def test_sharded_input(self, rng, mesh):
        n, d = 331, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = X @ rng.normal(size=d).astype(np.float32)
        reg = SGDRegressor(max_iter=400, tol=None, learning_rate="constant",
                           eta0=0.1).fit(shard_rows(X), shard_rows(y))
        assert reg.score(X, y) > 0.97


class TestDeviceNativeAdaptivePlane:
    """VERDICT round-1 item 2: the adaptive-search plane trains ON DEVICE
    when given our SGD estimators — partial_fit is an XLA program, not a
    host sklearn call."""

    def test_incremental_wrapper_device_native(self, rng):
        from dask_ml_tpu.wrappers import Incremental

        X, y = _binary_data(rng, n=1500)
        inc = Incremental(
            SGDClassifier(learning_rate="constant", eta0=0.5),
            chunk_size=256,
        )
        for _ in range(20):
            inc.partial_fit(X, y, classes=np.unique(y))
        est = inc.estimator_
        assert isinstance(est._state["coef"], jax.Array)
        assert (np.asarray(inc.predict(X)) == y).mean() > 0.9

    def test_incremental_search_device_native(self, rng):
        from dask_ml_tpu.model_selection import IncrementalSearchCV

        X, y = _binary_data(rng, n=1200)
        search = IncrementalSearchCV(
            SGDClassifier(learning_rate="constant"),
            {"eta0": [0.01, 0.1, 0.5], "alpha": [1e-4, 1e-2]},
            n_initial_parameters=6,
            max_iter=15,
            random_state=0,
        )
        search.fit(X, y, classes=np.unique(y))
        assert hasattr(search, "best_estimator_")
        assert isinstance(search.best_estimator_._state["coef"], jax.Array)
        assert search.best_score_ > 0.85

    def test_hyperband_device_native(self, rng):
        from dask_ml_tpu.model_selection import HyperbandSearchCV

        X, y = _binary_data(rng, n=1200)
        search = HyperbandSearchCV(
            SGDClassifier(learning_rate="constant"),
            {"eta0": [0.01, 0.1, 0.5, 1.0], "alpha": [1e-4, 1e-3, 1e-2]},
            max_iter=9,
            random_state=0,
        )
        search.fit(X, y, classes=np.unique(y))
        assert isinstance(search.best_estimator_._state["coef"], jax.Array)
        # the search actually exercised partial_fit as XLA programs
        assert search.best_estimator_.t_ > 0


class TestReviewRegressions:
    def test_optimal_schedule_rejects_alpha_zero(self, rng):
        X, y = _binary_data(rng, n=100)
        with pytest.raises(ValueError, match="alpha"):
            SGDClassifier(alpha=0.0, learning_rate="optimal").fit(X, y)

    def test_one_bad_epoch_does_not_stop_fit(self, rng):
        # A single non-improving epoch (oscillation at constant LR) must not
        # halt training; only n_iter_no_change consecutive ones may.
        n, d = 400, 5
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = X @ rng.normal(size=d).astype(np.float32)
        reg = SGDRegressor(learning_rate="constant", eta0=0.9, max_iter=300,
                           tol=1e-4).fit(X, y)
        assert reg.score(X, y) > 0.9

    def test_warm_start_rejects_new_labels(self, rng):
        X, y = _multiclass_data(rng)
        clf = SGDClassifier(max_iter=30, warm_start=True).fit(X, y)
        y2 = y.copy()
        y2[:] = 7  # label outside fitted classes_
        with pytest.raises(ValueError, match="warm_start"):
            clf.fit(X, y2)
        # subset of fitted classes is fine
        keep = y < 2
        clf.fit(X[keep], y[keep])
        assert clf.coef_.shape[0] == 4  # state keeps the full class set

    def test_modified_huber_proba_matches_sklearn_formula(self, rng):
        X, y = _binary_data(rng)
        clf = SGDClassifier(loss="modified_huber", max_iter=100,
                            tol=None).fit(X, y)
        m = np.asarray(clf.decision_function(X))
        expect_p1 = (np.clip(m, -1, 1) + 1) / 2
        got = np.asarray(clf.predict_proba(X))
        np.testing.assert_allclose(got[:, 1], expect_p1, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got.sum(axis=1), 1.0, rtol=1e-5)

    def test_set_param_fingerprint_stable(self):
        from dask_ml_tpu.checkpoint import _param_repr

        assert _param_repr({"hinge", "log_loss"}) == _param_repr(
            {"log_loss", "hinge"}
        )


class TestReviewRegressions2:
    def test_single_class_fit_rejected(self, rng):
        X, _ = _binary_data(rng, n=50)
        with pytest.raises(ValueError, match="2 classes"):
            SGDClassifier(max_iter=5).fit(X, np.zeros(50))

    def test_single_class_partial_fit_rejected(self, rng):
        X, _ = _binary_data(rng, n=50)
        with pytest.raises(ValueError, match="2 classes"):
            SGDClassifier().partial_fit(X, np.zeros(50), classes=[0])

    def test_packed_plane_validates_like_unpacked(self, rng):
        from dask_ml_tpu.model_selection._packing import Cohort

        bad = SGDClassifier(alpha=0.0, learning_rate="optimal")
        ok = SGDClassifier(alpha=1e-4, learning_rate="optimal")
        with pytest.raises(ValueError, match="alpha"):
            Cohort([bad, ok], classes=[0, 1])


class TestMixedPrecisionSGD:
    def test_bf16_blocks_train_f32_params(self, rng):
        import jax.numpy as jnp

        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.linear_model import SGDClassifier

        X = rng.normal(size=(512, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        sX = shard_rows(X, dtype=jnp.bfloat16)
        sy = shard_rows(y)
        clf = SGDClassifier(learning_rate="constant", eta0=0.3, max_iter=80)
        clf.fit(sX, sy)
        assert clf._state["coef"].dtype == jnp.float32
        acc = (np.asarray(clf.predict(sX)) == y).mean()
        assert acc > 0.9


class TestSGDWeights:
    def test_sample_weight_equals_duplication(self, rng, mesh):
        from dask_ml_tpu.linear_model import SGDClassifier

        n, d = 150, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        sw = rng.randint(1, 3, size=n)
        a = SGDClassifier(max_iter=40, random_state=0, tol=None).fit(
            X, y, sample_weight=sw
        )
        # duplication changes the padded batch size/bucket, so exact
        # trajectory parity is not expected — compare the weighted loss
        # direction instead: the weighted fit must classify high-weight
        # rows better than an unweighted fit of the same budget
        b = SGDClassifier(max_iter=40, random_state=0, tol=None).fit(X, y)
        heavy = sw >= 2
        acc_a = (np.asarray(a.predict(X[heavy])) == y[heavy]).mean()
        acc_b = (np.asarray(b.predict(X[heavy])) == y[heavy]).mean()
        assert acc_a >= acc_b - 0.05

    def test_class_weight_dict_changes_balance(self, rng, mesh):
        from dask_ml_tpu.linear_model import SGDClassifier

        n, d = 400, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] + 1.0 > 0).astype(np.float32)  # imbalanced
        plain = SGDClassifier(max_iter=60, random_state=0, tol=None).fit(X, y)
        up = SGDClassifier(
            max_iter=60, random_state=0, tol=None,
            class_weight={0.0: 8.0, 1.0: 1.0},
        ).fit(X, y)
        rec0 = lambda m: float(  # noqa: E731
            ((np.asarray(m.predict(X)) == 0) & (y == 0)).sum()
        ) / max((y == 0).sum(), 1)
        assert rec0(up) >= rec0(plain)

    def test_balanced_class_weight_in_fit_works(self, rng, mesh):
        from dask_ml_tpu.linear_model import SGDClassifier

        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] + 1.0 > 0).astype(np.float32)
        m = SGDClassifier(
            max_iter=30, random_state=0, tol=None, class_weight="balanced"
        ).fit(X, y)
        assert hasattr(m, "classes_")

    def test_balanced_rejected_in_partial_fit(self, rng, mesh):
        from dask_ml_tpu.linear_model import SGDClassifier

        X = rng.normal(size=(64, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        with pytest.raises(ValueError, match="partial_fit"):
            SGDClassifier(class_weight="balanced").partial_fit(
                X, y, classes=[0.0, 1.0]
            )

    def test_regressor_sample_weight(self, rng, mesh):
        from dask_ml_tpu.linear_model import SGDRegressor

        X = rng.normal(size=(150, 4)).astype(np.float32)
        y = (X @ rng.normal(size=4)).astype(np.float32)
        m = SGDRegressor(max_iter=30, random_state=0, tol=None).fit(
            X, y, sample_weight=np.ones(150)
        )
        assert hasattr(m, "_state")

    def test_sample_and_class_weight_combine_linearly(self, rng, mesh):
        # combining sample_weight with class_weight must apply each ONCE:
        # integer sw + dict cw == duplication + dict cw (review regression:
        # two chained effective_mask calls squared the sample weights)
        from dask_ml_tpu.linear_model import SGDClassifier

        n, d = 120, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        sw = rng.randint(1, 3, size=n)
        cw = {0.0: 3.0, 1.0: 1.0}
        a = SGDClassifier(max_iter=1, random_state=0, tol=None,
                          learning_rate="constant", eta0=0.1,
                          class_weight=cw).fit(X, y, sample_weight=sw)
        b = SGDClassifier(max_iter=1, random_state=0, tol=None,
                          learning_rate="constant", eta0=0.1,
                          class_weight=cw).fit(
            np.repeat(X, sw, axis=0), np.repeat(y, sw))
        # ONE gradient step on the weighted mean loss: duplication and
        # integer weights give the same weighted mean -> same step
        np.testing.assert_allclose(
            a.coef_, b.coef_, rtol=1e-5, atol=1e-6
        )


class TestConvergenceCanary:
    def test_fixed_problem_budget(self, rng, mesh):
        # the loose accuracy-level parity tests would
        # not catch a 2x convergence regression — pin a budget on a fixed
        # problem: the fit must reach both the accuracy AND the epoch
        # count below the bound (historically n_iter_ ~ 30-60 here)
        from dask_ml_tpu.linear_model import SGDClassifier

        X = rng.normal(size=(512, 8)).astype(np.float32)
        w = rng.normal(size=8)
        y = (X @ w > 0).astype(np.float32)
        # FIXED budget: a convergence regression shows up as an accuracy
        # drop at constant epochs (currently ~0.99 at 60 epochs)
        m = SGDClassifier(max_iter=60, tol=None, random_state=0).fit(X, y)
        assert m.score(X, y) > 0.97


class TestMinibatchEpochs:
    """fit(batch_size=B): epoch = one scanned program of n_pad/B minibatch
    steps over stride interleaves (closer to sklearn's per-sample SGD than
    the default full-batch epoch)."""

    def test_minibatch_fit_matches_fullbatch_accuracy(self, rng):
        X, y = _binary_data(rng, n=600)
        full = SGDClassifier(max_iter=60, tol=None).fit(X, y)
        mb = SGDClassifier(max_iter=60, tol=None, batch_size=128).fit(X, y)
        acc_full = (full.predict(X) == y).mean()
        acc_mb = (mb.predict(X) == y).mean()
        assert acc_mb > 0.9
        assert acc_mb >= acc_full - 0.03

    def test_minibatch_advances_t_per_step(self, rng):
        X, y = _binary_data(rng, n=512)
        mb = SGDClassifier(max_iter=1, tol=None, batch_size=128).fit(X, y)
        # 512 rows pad to a 1024 bucket -> nearest divisor split of 1024/128
        assert mb.t_ > 1.0  # several steps in the single epoch
        full = SGDClassifier(max_iter=1, tol=None).fit(X, y)
        assert full.t_ == 1.0

    def test_minibatch_sharded_parity(self, rng, mesh):
        X, y = _binary_data(rng, n=640)
        sX, sy = shard_rows(X), shard_rows(y)
        host = SGDClassifier(max_iter=40, tol=None, batch_size=80).fit(X, y)
        dev = SGDClassifier(max_iter=40, tol=None, batch_size=80).fit(sX, sy)
        acc_dev = (dev.predict(X) == y).mean()
        assert acc_dev > 0.9
        assert abs(acc_dev - (host.predict(X) == y).mean()) < 0.05

    def test_minibatch_regressor(self, rng):
        X = rng.normal(size=(500, 6)).astype(np.float32)
        w = rng.normal(size=6).astype(np.float32)
        y = X @ w + 0.01 * rng.normal(size=500).astype(np.float32)
        mb = SGDRegressor(
            max_iter=200, tol=None, batch_size=64, learning_rate="constant",
            eta0=0.05, penalty=None,
        ).fit(X, y)
        from sklearn.metrics import r2_score

        assert r2_score(y, np.asarray(mb.predict(X))) > 0.95

    def test_batch_size_larger_than_n_is_fullbatch(self, rng):
        X, y = _binary_data(rng, n=300)
        mb = SGDClassifier(max_iter=3, tol=None, batch_size=10_000).fit(X, y)
        assert mb.t_ == 3.0  # one step per epoch: the full-batch path

    def test_batch_size_validated(self, rng):
        X, y = _binary_data(rng, n=100)
        with pytest.raises(ValueError, match="batch_size"):
            SGDClassifier(batch_size=0.5).fit(X, y)
        with pytest.raises(ValueError, match="batch_size"):
            SGDClassifier(batch_size=-128).fit(X, y)

    def test_tiny_batch_size_capped_at_n_real(self, rng):
        # n=300 bucket-pads to 1024; batch_size=2 would ask for 512
        # minibatches, but n_mb caps at n_real (then the divisor clamp)
        # so no minibatch is padding-only
        X, y = _binary_data(rng, n=300)
        mb = SGDClassifier(max_iter=2, tol=None, batch_size=2).fit(X, y)
        n_mb = mb.t_ / 2  # steps per epoch
        assert n_mb <= 300
        assert (mb.predict(X) == y).mean() > 0.85

    def test_batch_size_over_n_real_is_fullbatch_despite_padding(self, rng):
        # n=300 pads to a 1024 bucket: batch_size=400 exceeds n_samples so
        # the documented full-batch path must win over the padded count
        X, y = _binary_data(rng, n=300)
        mb = SGDClassifier(max_iter=3, tol=None, batch_size=400).fit(X, y)
        assert mb.t_ == 3.0


class TestEarlyStoppingAndAdaptive:
    def test_early_stopping_halts_before_max_iter(self, rng):
        X, y = _binary_data(rng, n=800)
        es = SGDClassifier(
            max_iter=500, tol=1e-3, early_stopping=True,
            validation_fraction=0.2, random_state=0,
            learning_rate="constant", eta0=0.1,
        ).fit(X, y)
        assert es.n_iter_ < 500
        assert (es.predict(X) == y).mean() > 0.9

    def test_early_stopping_requires_tol(self, rng):
        X, y = _binary_data(rng, n=100)
        with pytest.raises(ValueError, match="early_stopping requires"):
            SGDClassifier(tol=None, early_stopping=True).fit(X, y)
        with pytest.raises(ValueError, match="validation_fraction"):
            SGDClassifier(
                early_stopping=True, validation_fraction=1.5
            ).fit(X, y)

    def test_early_stopping_sharded(self, rng, mesh):
        X, y = _binary_data(rng, n=640)
        es = SGDClassifier(
            max_iter=300, tol=1e-4, early_stopping=True, random_state=0,
        ).fit(shard_rows(X), shard_rows(y))
        assert es.n_iter_ <= 300
        assert (es.predict(X) == y).mean() > 0.9

    def test_adaptive_learning_rate_decays_and_stops(self, rng):
        X, y = _binary_data(rng, n=400)
        ad = SGDClassifier(
            learning_rate="adaptive", eta0=0.5, max_iter=2000, tol=1e-3,
            n_iter_no_change=3, random_state=0,
        ).fit(X, y)
        # plateau -> eta/5 cascades until 1e-6 floor: stops well short
        assert ad.n_iter_ < 2000
        assert (ad.predict(X) == y).mean() > 0.9

    def test_adaptive_beats_fixed_tiny_eta_on_budget(self, rng):
        # adaptive starts big and decays on plateau (tol active so the
        # eta/5 branch actually runs); a fixed tiny eta crawls
        X, y = _binary_data(rng, n=400)
        ad = SGDClassifier(
            learning_rate="adaptive", eta0=0.5, max_iter=200, tol=1e-3,
            n_iter_no_change=3, random_state=0,
        ).fit(X, y)
        slow = SGDClassifier(
            learning_rate="constant", eta0=1e-4, max_iter=200, tol=None,
            random_state=0,
        ).fit(X, y)
        assert ad.n_iter_ < 200  # the decay cascade terminated the fit
        assert (ad.predict(X) == y).mean() >= (slow.predict(X) == y).mean()

    def test_regressor_early_stopping(self, rng):
        X = rng.normal(size=(600, 6)).astype(np.float32)
        w = rng.normal(size=6).astype(np.float32)
        y = X @ w + 0.01 * rng.normal(size=600).astype(np.float32)
        es = SGDRegressor(
            max_iter=500, tol=1e-5, early_stopping=True, random_state=0,
            learning_rate="constant", eta0=0.05, penalty=None,
        ).fit(X, y)
        assert es.n_iter_ < 500
        from sklearn.metrics import r2_score

        assert r2_score(y, np.asarray(es.predict(X))) > 0.9

    def test_ensemble_routes_adaptive_to_member_fit(self, rng):
        from dask_ml_tpu.ensemble import BlockwiseVotingClassifier

        X, y = _binary_data(rng, n=400)
        ens = BlockwiseVotingClassifier(
            SGDClassifier(learning_rate="adaptive", eta0=0.5, tol=1e-3,
                          random_state=0),
            n_blocks=4,
        ).fit(X, y)
        # fell back to per-member fit (each ran its own adaptive decay)
        assert len(ens.estimators_) == 4
        assert all(m.n_iter_ >= 1 for m in ens.estimators_)
        assert (np.asarray(ens.predict(X)) == y).mean() > 0.85
