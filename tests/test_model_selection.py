import numpy as np
import pytest
from sklearn.linear_model import SGDClassifier

import dask_ml_tpu.linear_model as dlm
import dask_ml_tpu.model_selection as dms
from dask_ml_tpu.core import shard_rows, unshard
from dask_ml_tpu.core.sharded import ShardedRows
from dask_ml_tpu.model_selection.utils_test import ConstantFunction, LinearFunction


@pytest.fixture
def clf_data(rng):
    n, d = 300, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w > 0).astype(np.int64)
    return X, y


class TestSplit:
    def test_train_test_split_sizes(self, clf_data):
        X, y = clf_data
        Xtr, Xte, ytr, yte = dms.train_test_split(X, y, test_size=0.2, random_state=0)
        assert Xtr.shape == (240, 5) and Xte.shape == (60, 5)
        assert ytr.shape == (240,) and yte.shape == (60,)

    def test_split_no_overlap_covers_all(self, clf_data):
        X, _ = clf_data
        Xi = np.arange(300)
        tr, te = dms.train_test_split(Xi, test_size=0.25, random_state=1)
        assert len(set(tr) & set(te)) == 0
        assert len(set(tr) | set(te)) == 300

    def test_sharded_in_sharded_out(self, clf_data):
        X, y = clf_data
        s = shard_rows(X)
        Xtr, Xte = dms.train_test_split(s, test_size=0.2, random_state=0)
        assert isinstance(Xtr, ShardedRows) and isinstance(Xte, ShardedRows)
        assert Xtr.n_samples == 240 and Xte.n_samples == 60

    def test_no_shuffle_contiguous(self):
        X = np.arange(100).reshape(100, 1)
        Xtr, Xte = dms.train_test_split(X, test_size=0.2, shuffle=False)
        np.testing.assert_array_equal(Xtr[:, 0], np.arange(80))
        np.testing.assert_array_equal(Xte[:, 0], np.arange(80, 100))

    def test_mismatched_lengths_raise(self):
        with pytest.raises(ValueError, match="same length"):
            dms.train_test_split(np.ones(10), np.ones(11))

    def test_kfold_contiguous_slabs(self):
        X = np.zeros((100, 2))
        folds = list(dms.KFold(n_splits=5).split(X))
        assert len(folds) == 5
        np.testing.assert_array_equal(folds[0][1], np.arange(20))
        for train, test in folds:
            assert len(train) == 80 and len(test) == 20
            assert len(set(train) & set(test)) == 0

    def test_kfold_validates(self):
        with pytest.raises(ValueError, match="n_splits"):
            list(dms.KFold(n_splits=1).split(np.zeros((10, 1))))

    def test_shuffle_split_deterministic(self):
        X = np.zeros((50, 2))
        a = list(dms.ShuffleSplit(n_splits=3, random_state=7).split(X))
        b = list(dms.ShuffleSplit(n_splits=3, random_state=7).split(X))
        for (tr1, te1), (tr2, te2) in zip(a, b):
            np.testing.assert_array_equal(tr1, tr2)
            np.testing.assert_array_equal(te1, te2)


class TestGridSearchCV:
    def test_parity_with_sklearn(self, clf_data):
        import sklearn.model_selection as sms

        X, y = clf_data
        param_grid = {"alpha": [1e-4, 1e-2, 1.0]}
        est = SGDClassifier(tol=1e-3, random_state=0)
        ours = dms.GridSearchCV(est, param_grid, cv=3).fit(X, y)
        theirs = sms.GridSearchCV(est, param_grid, cv=3).fit(X, y)
        assert ours.best_params_ == theirs.best_params_
        assert set(ours.cv_results_["param_alpha"]) == set(
            theirs.cv_results_["param_alpha"]
        )

    def test_best_estimator_refit(self, clf_data):
        X, y = clf_data
        gs = dms.GridSearchCV(
            SGDClassifier(tol=1e-3, random_state=0), {"alpha": [1e-4, 1.0]}, cv=3
        ).fit(X, y)
        assert hasattr(gs, "best_estimator_")
        assert gs.predict(X).shape == (300,)
        assert gs.score(X, y) > 0.5

    def test_refit_false_blocks_predict(self, clf_data):
        X, y = clf_data
        gs = dms.GridSearchCV(
            SGDClassifier(tol=1e-3), {"alpha": [1e-4]}, cv=3, refit=False
        ).fit(X, y)
        with pytest.raises(AttributeError, match="refit"):
            gs.predict(X)

    def test_pipeline_prefix_cache(self, clf_data):
        from sklearn.pipeline import Pipeline
        from sklearn.preprocessing import StandardScaler

        X, y = clf_data
        calls = {"n": 0}

        class CountingScaler(StandardScaler):
            def fit_transform(self, X, y=None, **kw):
                calls["n"] += 1
                return super().fit_transform(X, y, **kw)

        pipe = Pipeline([("sc", CountingScaler()), ("clf", SGDClassifier(tol=1e-3, random_state=0))])
        gs = dms.GridSearchCV(
            pipe, {"clf__alpha": [1e-4, 1e-3, 1e-2]}, cv=3, refit=False
        ).fit(X, y)
        # shared scaler prefix must be fit once per fold, not per candidate
        # (3 folds x 3 candidates would be 9 without the cache)
        assert calls["n"] == 3
        assert gs.best_score_ > 0.5

    def test_sharded_input(self, clf_data):
        X, y = clf_data
        gs = dms.GridSearchCV(
            SGDClassifier(tol=1e-3, random_state=0), {"alpha": [1e-4, 1.0]}, cv=3
        ).fit(shard_rows(X), shard_rows(y))
        assert gs.best_score_ > 0.5

    def test_randomized_search(self, clf_data):
        X, y = clf_data
        rs = dms.RandomizedSearchCV(
            SGDClassifier(tol=1e-3, random_state=0),
            {"alpha": np.logspace(-5, 0, 20)}, n_iter=4, random_state=0, cv=3,
        ).fit(X, y)
        assert len(rs.cv_results_["params"]) == 4


class TestIncrementalSearchCV:
    def test_trains_to_max_iter_without_patience(self, clf_data):
        X, y = clf_data
        search = dms.IncrementalSearchCV(
            ConstantFunction(), {"value": [0.1, 0.5, 0.9]},
            n_initial_parameters="grid", max_iter=5, chunk_size=50,
        )
        search.fit(X, y)
        assert search.best_score_ == 0.9
        # every model trained exactly max_iter calls
        assert all(
            recs[-1]["partial_fit_calls"] == 5
            for recs in search.model_history_.values()
        )

    def test_patience_stops_plateaued_models(self, clf_data):
        X, y = clf_data
        search = dms.IncrementalSearchCV(
            ConstantFunction(), {"value": [0.2, 0.8]},
            n_initial_parameters="grid", max_iter=50, patience=3, tol=1e-3,
            chunk_size=50,
        )
        search.fit(X, y)
        # constant scores plateau immediately -> far fewer than max_iter calls
        assert all(
            recs[-1]["partial_fit_calls"] < 50
            for recs in search.model_history_.values()
        )

    def test_history_records_structure(self, clf_data):
        X, y = clf_data
        search = dms.IncrementalSearchCV(
            LinearFunction(), {"slope": [1.0, 2.0]},
            n_initial_parameters="grid", max_iter=3, chunk_size=50,
        ).fit(X, y)
        rec = search.history_[0]
        for key in ("model_id", "params", "partial_fit_calls", "score",
                    "elapsed_wall_time"):
            assert key in rec
        assert search.cv_results_["rank_test_score"][search.best_index_] == 1

    def test_real_sgd_improves(self, clf_data):
        X, y = clf_data
        search = dms.IncrementalSearchCV(
            SGDClassifier(tol=None, random_state=0),
            {"alpha": [1e-4, 1e-3]}, n_initial_parameters="grid",
            max_iter=10, chunk_size=50,
        )
        search.fit(X, y, classes=[0, 1])
        assert search.best_score_ > 0.7

    def test_inverse_decay(self, clf_data):
        X, y = clf_data
        search = dms.InverseDecaySearchCV(
            LinearFunction(), {"slope": [1.0, 2.0, 3.0, 4.0]},
            n_initial_parameters="grid", max_iter=8, chunk_size=50,
        ).fit(X, y)
        # the best (steepest) model survives to the end
        assert search.best_params_["slope"] == 4.0
        calls = [r[-1]["partial_fit_calls"] for r in search.model_history_.values()]
        assert max(calls) > min(calls)  # losers stopped early


class TestSuccessiveHalving:
    def test_exact_schedule_with_fake_models(self, clf_data):
        X, y = clf_data
        # 9 models, eta=3: rounds keep 9 -> 3 -> 1; budgets 1 -> 3 -> 9
        values = {i: i / 10 for i in range(9)}
        search = dms.SuccessiveHalvingSearchCV(
            ConstantFunction(), {"value": [values[i] for i in range(9)]},
            n_initial_parameters="grid", n_initial_iter=1, aggressiveness=3,
            max_iter=9, chunk_size=50,
        ).fit(X, y)
        hist = search.model_history_
        final_calls = sorted(
            recs[-1]["partial_fit_calls"] for recs in hist.values()
        )
        # 6 losers stop at 1 call, 2 mid at 3 calls, the winner gets 9
        assert final_calls == [1, 1, 1, 1, 1, 1, 3, 3, 9]
        assert search.best_score_ == 0.8

    def test_requires_n_initial_iter(self, clf_data):
        X, y = clf_data
        with pytest.raises(ValueError, match="n_initial_iter"):
            dms.SuccessiveHalvingSearchCV(
                ConstantFunction(), {"value": [0.1]},
            ).fit(X, y)

    def test_patience_stops_plateaued_bracket(self, clf_data):
        # patience is a BASE-loop post-filter, so SHA brackets honor it
        # too: constant scores plateau immediately and the winner stops
        # long before its granted r_i budget
        X, y = clf_data
        kw = dict(
            n_initial_parameters="grid", n_initial_iter=1, aggressiveness=3,
            max_iter=81, chunk_size=50,
        )
        grid = {"value": [i / 10 for i in range(9)]}
        full = dms.SuccessiveHalvingSearchCV(
            ConstantFunction(), grid, **kw).fit(X, y)
        stopped = dms.SuccessiveHalvingSearchCV(
            ConstantFunction(), grid, patience=3, tol=1e-3, **kw).fit(X, y)
        calls = lambda s: sum(  # noqa: E731
            recs[-1]["partial_fit_calls"]
            for recs in s.model_history_.values()
        )
        assert stopped.best_score_ == full.best_score_ == 0.8
        assert calls(stopped) < calls(full)


class TestHyperband:
    def test_bracket_params_r81(self):
        from dask_ml_tpu.model_selection._hyperband import _get_hyperband_params

        # canonical Li et al. example: R=81, eta=3
        out = _get_hyperband_params(81, 3)
        assert [(n, r) for _, n, r in out] == [
            (81, 1), (34, 3), (15, 9), (8, 27), (5, 81)
        ]

    def test_metadata_counts(self):
        search = dms.HyperbandSearchCV(
            ConstantFunction(), {"value": [0.1]}, max_iter=9, aggressiveness=3
        )
        meta = search.metadata
        # R=9, eta=3: brackets (n=9,r=1), (n=5,r=3), (n=3,r=9)
        assert [b["n_models"] for b in meta["brackets"]] == [9, 5, 3]
        assert meta["n_models"] == 17
        assert meta["partial_fit_calls"] == sum(
            b["partial_fit_calls"] for b in meta["brackets"]
        )

    def test_fit_finds_best_and_metadata_matches(self, clf_data, rng):
        X, y = clf_data
        search = dms.HyperbandSearchCV(
            LinearFunction(),
            {"slope": list(rng.uniform(0.1, 2.0, size=30)),
             "intercept": list(rng.uniform(0, 0.1, size=10))},
            max_iter=9, aggressiveness=3, random_state=0, chunk_size=50,
        ).fit(X, y)
        assert search.metadata_["n_models"] == search.metadata["n_models"]
        assert search.best_score_ > 0
        assert hasattr(search, "cv_results_")
        assert "bracket" in search.history_[0]
        # model ids globally unique across brackets
        ids = list(search.model_history_)
        assert len(ids) == len(set(ids)) == search.metadata_["n_models"]

    def test_real_sgd_hyperband(self, clf_data):
        X, y = clf_data
        search = dms.HyperbandSearchCV(
            SGDClassifier(tol=None, random_state=0),
            {"alpha": np.logspace(-5, 1, 30)},
            max_iter=9, random_state=0, chunk_size=50,
        )
        search.fit(X, y, classes=[0, 1])
        assert search.best_score_ > 0.7
        assert search.predict(X).shape == (300,)


class TestReviewRegressions:
    def test_sha_refit_same_instance(self, clf_data):
        X, y = clf_data
        search = dms.SuccessiveHalvingSearchCV(
            ConstantFunction(), {"value": [i / 10 for i in range(9)]},
            n_initial_parameters="grid", n_initial_iter=1, aggressiveness=3,
            max_iter=9, chunk_size=50,
        )
        search.fit(X, y)
        first = sorted(r[-1]["partial_fit_calls"] for r in search.model_history_.values())
        search.fit(X, y)
        second = sorted(r[-1]["partial_fit_calls"] for r in search.model_history_.values())
        assert first == second == [1, 1, 1, 1, 1, 1, 3, 3, 9]

    def test_patience_with_improving_model_keeps_training(self, clf_data):
        X, y = clf_data
        search = dms.IncrementalSearchCV(
            LinearFunction(), {"slope": [1.0]}, n_initial_parameters="grid",
            max_iter=10, patience=2, tol=1e-3, chunk_size=50,
        ).fit(X, y)
        # monotonically improving model must NOT stop after the first score
        calls = list(search.model_history_.values())[0][-1]["partial_fit_calls"]
        assert calls == 10

    def test_split_integer_sizes_are_counts(self):
        X = np.arange(100).reshape(100, 1)
        Xtr, Xte = dms.train_test_split(X, test_size=1, random_state=0)
        assert Xte.shape == (1, 1) and Xtr.shape == (99, 1)

    def test_incremental_requires_y(self, clf_data):
        X, _ = clf_data
        with pytest.raises(ValueError, match="y is required"):
            dms.IncrementalSearchCV(
                ConstantFunction(), {"value": [0.1]}, n_initial_parameters="grid"
            ).fit(X)

    def test_grid_fit_params_unsupervised(self, rng):
        from dask_ml_tpu.cluster import KMeans

        X = rng.normal(size=(60, 3)).astype(np.float32)
        gs = dms.GridSearchCV(KMeans(init="random", random_state=0), {"n_clusters": [2, 3]}, cv=2)
        gs.fit(X)  # y=None path
        assert gs.best_params_["n_clusters"] in (2, 3)


class TestDeviceSideSplit:
    def test_take_no_host_materialization(self, rng, mesh):
        # the sharded path must not call np.asarray on X-sized data
        import unittest.mock as um

        import numpy as np

        from dask_ml_tpu.core import shard_rows, unshard
        from dask_ml_tpu.model_selection import _split

        X = rng.normal(size=(200, 4)).astype(np.float32)
        Xs = shard_rows(X)
        idx = rng.permutation(150)
        real_asarray = np.asarray
        big_pulls = []

        def spy(a, *args, **kw):
            out = real_asarray(a, *args, **kw)
            import jax

            if isinstance(a, jax.Array) and out.size >= 100 * 4:
                big_pulls.append(out.shape)
            return out

        with um.patch.object(_split.np, "asarray", side_effect=spy):
            taken = _split._take(Xs, idx)
        assert big_pulls == []  # gather stayed on device
        np.testing.assert_allclose(unshard(taken), X[idx])

    def test_take_result_row_sharded(self, rng, mesh):
        import numpy as np

        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.core.mesh import DATA_AXIS
        from dask_ml_tpu.model_selection._split import _take

        X = rng.normal(size=(100, 3)).astype(np.float32)
        taken = _take(shard_rows(X), np.arange(37))
        assert taken.n_samples == 37
        assert taken.data.sharding.spec[0] == DATA_AXIS


class TestKMeansParInitDeviceSide:
    def test_no_length_n_host_pull_per_round(self, rng, mesh):
        import unittest.mock as um

        import numpy as np

        from dask_ml_tpu.cluster import k_means as km
        from dask_ml_tpu.core import shard_rows

        n = 4096
        X = np.concatenate([
            rng.normal(i * 5, 0.5, size=(n // 4, 8)) for i in range(4)
        ]).astype(np.float32)
        Xs = shard_rows(X)
        real_asarray = np.asarray
        big_pulls = []

        def spy(a, *args, **kw):
            out = real_asarray(a, *args, **kw)
            import jax

            # guard against O(n)-sized pulls (the old per-round boolean
            # vector); the legitimate end-of-init candidate pull is
            # O(k log n * d), far below n*4 at this shape
            if isinstance(a, jax.Array) and out.size >= n * 4:
                big_pulls.append(out.shape)
            return out

        import jax

        with um.patch.object(km.np, "asarray", side_effect=spy):
            centers = km.init_scalable(
                Xs, 4, jax.random.PRNGKey(0), oversampling_factor=2
            )
        assert big_pulls == [], big_pulls
        # init still finds the 4 well-separated blobs
        got = np.sort(np.asarray(centers)[:, 0])
        expect = np.array([0.0, 5.0, 10.0, 15.0])
        np.testing.assert_allclose(got, expect, atol=1.5)


class TestMultimetricScoring:
    """sklearn's multimetric contract on GridSearchCV (reference surface:
    dask-ml forwards sklearn's scoring semantics): list/dict scoring,
    per-metric cv_results_ columns, refit-by-name, refit=False."""

    def _data(self, rng):
        X = rng.normal(size=(120, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        return X, y

    def test_list_scoring_refit_by_name(self, rng):
        from sklearn.tree import DecisionTreeClassifier

        X, y = self._data(rng)
        gs = dms.GridSearchCV(
            DecisionTreeClassifier(random_state=0), {"max_depth": [1, 3]},
            scoring=["accuracy", "neg_log_loss"], refit="accuracy", cv=3,
        ).fit(X, y)
        assert gs.multimetric_
        for m in ("accuracy", "neg_log_loss"):
            assert f"mean_test_{m}" in gs.cv_results_
            assert f"rank_test_{m}" in gs.cv_results_
            assert f"split0_test_{m}" in gs.cv_results_
        best = int(np.argmax(gs.cv_results_["mean_test_accuracy"]))
        assert gs.best_index_ == best
        assert gs.score(X, y) == pytest.approx(
            gs.best_estimator_.score(X, y))

    def test_dict_scoring_with_callable(self, rng):
        from sklearn.tree import DecisionTreeClassifier

        from dask_ml_tpu.metrics import accuracy_score

        X, y = self._data(rng)

        def my_scorer(est, Xv, yv):
            return float(accuracy_score(yv, est.predict(Xv)))

        gs = dms.GridSearchCV(
            DecisionTreeClassifier(random_state=0), {"max_depth": [1, 3]},
            scoring={"acc": "accuracy", "mine": my_scorer}, refit="mine",
            cv=3,
        ).fit(X, y)
        np.testing.assert_allclose(
            gs.cv_results_["mean_test_acc"], gs.cv_results_["mean_test_mine"]
        )

    def test_refit_false_builds_columns_without_best(self, rng):
        from sklearn.tree import DecisionTreeClassifier

        X, y = self._data(rng)
        gs = dms.GridSearchCV(
            DecisionTreeClassifier(random_state=0), {"max_depth": [1, 3]},
            scoring=["accuracy", "r2"], refit=False, cv=3,
        ).fit(X, y)
        assert "mean_test_accuracy" in gs.cv_results_
        assert not hasattr(gs, "best_index_")

    def test_bad_refit_name_raises(self, rng):
        from sklearn.tree import DecisionTreeClassifier

        X, y = self._data(rng)
        with pytest.raises(ValueError, match="refit must be False"):
            dms.GridSearchCV(
                DecisionTreeClassifier(), {"max_depth": [1]},
                scoring=["accuracy"], refit=True, cv=3,
            ).fit(X, y)

    def test_single_metric_keys_unchanged(self, rng):
        from sklearn.tree import DecisionTreeClassifier

        X, y = self._data(rng)
        gs = dms.GridSearchCV(
            DecisionTreeClassifier(random_state=0), {"max_depth": [1, 3]},
            cv=3,
        ).fit(X, y)
        assert not gs.multimetric_
        assert "mean_test_score" in gs.cv_results_
        assert "rank_test_score" in gs.cv_results_

    def test_stratified_cv_for_library_classifiers(self, rng):
        """Our own GLM classifiers must stratify under cv=int like sklearn
        estimators do (is_classifier sees the ClassifierMixin)."""
        from sklearn.base import is_classifier

        from dask_ml_tpu.linear_model import LogisticRegression

        assert is_classifier(LogisticRegression())
        # class-sorted labels: unstratified contiguous folds would give a
        # single-class train split and error
        X = rng.normal(size=(90, 3)).astype(np.float32)
        y = np.repeat([0, 1, 2], 30)
        X[y == 1] += 3.0
        X[y == 2] -= 3.0
        gs = dms.GridSearchCV(
            LogisticRegression(solver="lbfgs", max_iter=30),
            {"C": [1.0]}, cv=3,
        ).fit(X, y)
        assert gs.best_score_ > 0.5

    def test_multimetric_prediction_caching(self, rng):
        from sklearn.base import BaseEstimator

        calls = {"n": 0}

        class Counting(BaseEstimator):
            def __init__(self, c=1.0):
                self.c = c
            def fit(self, X, y):
                self.classes_ = np.unique(y)
                return self
            def predict(self, X):
                calls["n"] += 1
                return np.zeros(len(X), dtype=np.int64)
            def predict_proba(self, X):
                p = np.full((len(X), 2), 0.5)
                return p

        X = rng.normal(size=(60, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)
        dms.GridSearchCV(
            Counting(), {"c": [1.0]},
            scoring={"a": "accuracy", "b": "accuracy"}, refit="a",
            cv=2, n_jobs=1,
        ).fit(X, y)
        # 2 folds x 1 candidate: one predict per fold despite 2 metrics
        assert calls["n"] == 2


class TestDataFrameSplit:
    def test_train_test_split_preserves_pandas(self, rng):
        import pandas as pd

        df = pd.DataFrame({"a": range(20), "b": np.arange(20.0)})
        y = pd.Series(np.arange(20) % 2, name="t")
        Xtr, Xte, ytr, yte = dms.train_test_split(
            df, y, test_size=0.25, random_state=0
        )
        assert isinstance(Xtr, pd.DataFrame) and isinstance(yte, pd.Series)
        assert len(Xtr) == 15 and len(Xte) == 5
        # row alignment preserved between X and y
        assert (Xtr["a"].to_numpy() % 2 == ytr.to_numpy()).all()

    def test_pandas_X_in_grid_search(self, rng):
        import pandas as pd
        from sklearn.tree import DecisionTreeClassifier

        df = pd.DataFrame({
            "a": rng.normal(size=100), "b": rng.normal(size=100),
        })
        y = (df["a"] > 0).astype(int)
        gs = dms.GridSearchCV(
            DecisionTreeClassifier(random_state=0), {"max_depth": [1, 2]},
            cv=3,
        ).fit(df, y)
        assert gs.best_score_ > 0.9

    def test_callable_refit_selects_index(self, rng):
        from sklearn.tree import DecisionTreeClassifier

        X = rng.normal(size=(120, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)

        def pick_simplest_within_1pct(cv_results):
            scores = np.asarray(cv_results["mean_test_score"])
            ok = scores >= scores.max() - 0.01
            return int(np.flatnonzero(ok)[0])  # candidates ordered simple->complex

        gs = dms.GridSearchCV(
            DecisionTreeClassifier(random_state=0),
            {"max_depth": [1, 2, 4, 8]}, cv=3,
            refit=pick_simplest_within_1pct,
        ).fit(X, y)
        assert gs.best_params_["max_depth"] in (1, 2)
        assert hasattr(gs, "best_estimator_")
        assert not hasattr(gs, "best_score_")

    def test_multimetric_roc_auc_proba_only_estimator(self, rng):
        """The prediction-caching proxy must not invent decision_function:
        a probability-only classifier goes through predict_proba."""
        from dask_ml_tpu.naive_bayes import GaussianNB

        X = rng.normal(size=(150, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        gs = dms.GridSearchCV(
            GaussianNB(), {"var_smoothing": [1e-9, 1e-7]},
            scoring=["accuracy", "roc_auc"], refit="roc_auc", cv=3,
        ).fit(X, y)
        assert gs.cv_results_["mean_test_roc_auc"][gs.best_index_] > 0.8


class TestDeviceResidentSearch:
    """Sharded data stays on device through the CV
    searches — fold slicing by device gather, scoring by scalar fetch."""

    def _tpu_est(self, **kw):
        from dask_ml_tpu.linear_model import SGDClassifier as TpuSGD

        kw.setdefault("max_iter", 30)
        kw.setdefault("random_state", 0)
        kw.setdefault("tol", None)
        return TpuSGD(**kw)

    def test_grid_no_host_materialization(self, clf_data, monkeypatch, mesh):
        # transfer guard: any unshard inside the search layer is a bug on
        # the device path (fold gathers run on device, scores are scalars)
        import dask_ml_tpu.model_selection._search as search_mod

        def _boom(a):
            raise AssertionError("O(n) unshard on the device search path")

        monkeypatch.setattr(search_mod, "unshard", _boom)
        X, y = clf_data
        sX, sy = shard_rows(X), shard_rows(y.astype(np.float32))
        gs = dms.GridSearchCV(
            self._tpu_est(), {"alpha": [1e-4, 1e-2]}, cv=3
        ).fit(sX, sy)
        assert gs.best_score_ > 0.5
        # post-fit inference keeps sharded input on device too
        gs.predict(sX)
        assert gs.score(sX, sy) > 0.5

    def test_device_path_matches_host_path(self, clf_data, mesh):
        from sklearn.model_selection import KFold

        X, y = clf_data
        yf = y.astype(np.float32)
        host = dms.GridSearchCV(
            self._tpu_est(), {"alpha": [1e-4, 1e-2]}, cv=KFold(3),
            refit=False,
        ).fit(X, yf)
        dev = dms.GridSearchCV(
            self._tpu_est(), {"alpha": [1e-4, 1e-2]}, cv=KFold(3),
            refit=False,
        ).fit(shard_rows(X), shard_rows(yf))
        np.testing.assert_allclose(
            host.cv_results_["mean_test_score"],
            dev.cv_results_["mean_test_score"], rtol=1e-4,
        )

    def test_incremental_keeps_test_split_sharded(self, clf_data, monkeypatch, mesh):
        import dask_ml_tpu.model_selection._incremental as inc_mod

        def _boom(a):
            raise AssertionError("O(n) unshard in incremental search")

        monkeypatch.setattr(inc_mod, "unshard", _boom)
        X, y = clf_data
        sX, sy = shard_rows(X), shard_rows(y.astype(np.float32))
        search = dms.IncrementalSearchCV(
            self._tpu_est(tol=1e-3), {"alpha": [1e-4, 1e-2]},
            n_initial_parameters=2, max_iter=3, random_state=0,
        ).fit(sX, sy, classes=[0.0, 1.0])
        assert search.best_score_ > 0.0


class TestPrefixCacheEviction:
    def test_refcount_evicts_all_entries(self, clf_data, monkeypatch):
        import dask_ml_tpu.model_selection._search as search_mod
        from sklearn.pipeline import Pipeline
        from sklearn.preprocessing import StandardScaler

        created = []
        orig = search_mod._OnceCache

        class Spy(orig):
            def __init__(self):
                super().__init__()
                created.append(self)

        monkeypatch.setattr(search_mod, "_OnceCache", Spy)
        X, y = clf_data
        pipe = Pipeline([
            ("sc", StandardScaler()),
            ("clf", SGDClassifier(tol=1e-3, random_state=0)),
        ])
        gs = dms.GridSearchCV(
            pipe, {"clf__alpha": [1e-4, 1e-3, 1e-2]}, cv=3, refit=False
        ).fit(X, y)
        assert gs.best_score_ > 0.5
        # every (prefix, fold) entry was released by its last consumer:
        # transformed fold data must not be pinned for the fit's lifetime
        assert created and len(created[0]) == 0


class TestSequentialBrackets:
    def test_sequential_matches_concurrent(self, clf_data, mesh):
        # same brackets, same per-bracket seeds -> identical results; only
        # the scheduling differs (sequential is the multi-controller form)
        from dask_ml_tpu.linear_model import SGDClassifier as TpuSGD

        X, y = clf_data
        yf = y.astype(np.float32)
        kw = dict(
            parameters={"alpha": [1e-5, 1e-4, 1e-3, 1e-2]},
            max_iter=4, aggressiveness=2, random_state=0,
        )
        conc = dms.HyperbandSearchCV(
            TpuSGD(random_state=0, tol=None), **kw
        ).fit(X, yf, classes=[0.0, 1.0])
        seq = dms.HyperbandSearchCV(
            TpuSGD(random_state=0, tol=None), sequential_brackets=True, **kw
        ).fit(X, yf, classes=[0.0, 1.0])
        assert seq.best_score_ == pytest.approx(conc.best_score_, abs=1e-6)
        assert seq.metadata_["n_models"] == conc.metadata_["n_models"]
        assert (
            seq.cv_results_["test_score"] == conc.cv_results_["test_score"]
        )

    def test_patience_forwarded_to_brackets(self, mesh):
        hb = dms.HyperbandSearchCV(
            SGDClassifier(tol=None), {"alpha": [1e-4, 1e-3]},
            max_iter=9, patience=2, tol=1e-3,
        )
        for _s, sha in hb._make_brackets():
            assert sha.patience == 2 and sha.tol == 1e-3

    def test_patience_reduces_hyperband_budget(self, clf_data):
        # behavioral, not just forwarding: plateaued models stop early in
        # every bracket, so the observed budget drops below metadata's
        X, y = clf_data
        grid = {"value": [i / 10 for i in range(10)]}
        full = dms.HyperbandSearchCV(
            ConstantFunction(), grid, max_iter=27, random_state=0,
            chunk_size=50,
        ).fit(X, y)
        stopped = dms.HyperbandSearchCV(
            ConstantFunction(), grid, max_iter=27, random_state=0,
            patience=2, tol=1e-3, chunk_size=50,
        ).fit(X, y)
        assert (
            stopped.metadata_["partial_fit_calls"]
            < full.metadata_["partial_fit_calls"]
        )
        assert stopped.best_score_ == full.best_score_

    def test_patience_true_auto_sizes(self):
        search = dms.IncrementalSearchCV(
            ConstantFunction(), {"value": [0.1]}, max_iter=30, patience=True,
        )
        assert search._patience_calls() == 10

    def test_completed_fit_cleans_bracket_checkpoints(self, clf_data, mesh,
                                                      tmp_path):
        import os

        from dask_ml_tpu.linear_model import SGDClassifier as TpuSGD

        X, y = clf_data
        ckdir = tmp_path / "hb"
        ckdir.mkdir()
        hb = dms.HyperbandSearchCV(
            TpuSGD(random_state=0, tol=None), {"alpha": [1e-5, 1e-4]},
            max_iter=4, aggressiveness=2, random_state=0,
            sequential_brackets=True, checkpoint=str(ckdir),
        ).fit(X, y.astype(np.float32), classes=[0.0, 1.0])
        assert hb.best_score_ > 0.5
        # bracket snapshots are kept while the fit runs (crash recovery)
        # and removed once the WHOLE fit completes
        assert not [f for f in os.listdir(ckdir) if f.endswith(".pkl")]


class TestVerboseLogging:
    def test_verbose_emits_round_decisions(self, clf_data, caplog):
        import logging

        X, y = clf_data
        with caplog.at_level(
            logging.INFO, logger="dask_ml_tpu.model_selection._incremental"
        ):
            dms.IncrementalSearchCV(
                ConstantFunction(), {"value": [0.2, 0.8]},
                n_initial_parameters="grid", max_iter=3, chunk_size=50,
                verbose=True,
            ).fit(X, y)
        rounds = [r for r in caplog.records if "models continue" in r.message]
        assert len(rounds) >= 2
        assert "best score" in rounds[0].message

    def test_silent_by_default(self, clf_data, caplog):
        import logging

        X, y = clf_data
        with caplog.at_level(
            logging.INFO, logger="dask_ml_tpu.model_selection._incremental"
        ):
            dms.IncrementalSearchCV(
                ConstantFunction(), {"value": [0.5]},
                n_initial_parameters="grid", max_iter=2, chunk_size=50,
            ).fit(X, y)
        assert not [r for r in caplog.records if "models continue" in r.message]

    def test_hyperband_forwards_verbose(self):
        hb = dms.HyperbandSearchCV(
            SGDClassifier(tol=None), {"alpha": [1e-4]}, max_iter=9,
            verbose=True,
        )
        assert all(sha.verbose for _s, sha in hb._make_brackets())


class TestStratifiedSplit:
    def test_stratify_preserves_proportions(self, rng):
        X = rng.normal(size=(300, 3)).astype(np.float32)
        y = np.r_[np.zeros(270), np.ones(30)]  # 10% minority
        Xtr, Xte, ytr, yte = dms.train_test_split(
            X, y, stratify=y, test_size=0.2, random_state=0
        )
        assert yte.mean() == pytest.approx(0.1, abs=0.02)
        assert ytr.mean() == pytest.approx(0.1, abs=0.02)
        # sharded X with host stratify labels also works
        sXtr, sXte, ytr2, yte2 = dms.train_test_split(
            shard_rows(X), y, stratify=y, test_size=0.2, random_state=0
        )
        assert isinstance(sXtr, ShardedRows)
        assert yte2.mean() == pytest.approx(0.1, abs=0.02)

    def test_stratify_rejects_sharded_labels(self, rng):
        X = rng.normal(size=(80, 2)).astype(np.float32)
        y = (rng.rand(80) > 0.5).astype(np.float32)
        with pytest.raises(ValueError, match="host labels"):
            dms.train_test_split(X, y, stratify=shard_rows(y))
        with pytest.raises(ValueError, match="shuffle"):
            dms.train_test_split(X, y, stratify=y, shuffle=False)


class TestNBCheckpointRoundtrip:
    def test_mid_stream_checkpoint_exact(self, rng, tmp_path):
        from dask_ml_tpu.checkpoint import load_estimator, save_estimator
        from dask_ml_tpu.naive_bayes import GaussianNB

        X = rng.normal(size=(200, 3)).astype(np.float32)
        y = rng.randint(0, 2, 200)
        nb = GaussianNB().partial_fit(X[:100], y[:100], classes=[0, 1])
        p = str(tmp_path / "nb.ckpt")
        save_estimator(nb, p)
        nb2 = load_estimator(p)
        nb2.partial_fit(X[100:], y[100:])
        full = GaussianNB().fit(X, y)
        np.testing.assert_allclose(
            np.asarray(nb2.theta_), np.asarray(full.theta_), rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(nb2.var_), np.asarray(full.var_), rtol=1e-4
        )


class TestPackedGlmGridSweep:
    """GridSearchCV fast path: a binary LogisticRegression grid over only
    C runs as ONE vmapped solve per fold (solvers.lambda_sweep) + one
    scoring gemm — r4's packed-search feature.  Results must be
    indistinguishable from the per-candidate path."""

    def _data(self, rng):
        X = rng.normal(size=(600, 8)).astype(np.float32)
        y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float32)
        return X, {"C": np.logspace(-2, 2, 7).tolist()}, y

    def test_matches_sequential_and_skips_dispatches(self, rng, mesh):
        # the packed search against the per-candidate one, which an
        # explicit scoring routes to ("accuracy" scores what the
        # default scoring does)
        from dask_ml_tpu import solvers

        X, grid, y = self._data(rng)
        results = {}
        for strat, scoring in (("packed", None), ("sequential", "accuracy")):
            solvers.reset_dispatch_counts()
            gs = dms.GridSearchCV(
                dlm.LogisticRegression(solver="lbfgs", max_iter=60),
                grid, cv=3, refit=False, return_train_score=True,
                scoring=scoring)
            gs.fit(X, y)
            results[strat] = (gs, solvers.DISPATCH_COUNTS["solves"])
        gp, dp = results["packed"]
        gq, dq = results["sequential"]
        np.testing.assert_allclose(
            gp.cv_results_["mean_test_score"],
            gq.cv_results_["mean_test_score"], atol=1e-6)
        np.testing.assert_allclose(
            gp.cv_results_["mean_train_score"],
            gq.cv_results_["mean_train_score"], atol=1e-6)
        assert gp.best_index_ == gq.best_index_
        assert dp == 3          # one sweep per fold
        assert dq == 7 * 3      # one solve per (candidate, fold)

    def test_sharded_inputs_take_fast_path(self, rng, mesh):
        import warnings

        from dask_ml_tpu import solvers
        from dask_ml_tpu.core import shard_rows

        X, grid, y = self._data(rng)
        solvers.reset_dispatch_counts()
        gs = dms.GridSearchCV(
            dlm.LogisticRegression(solver="lbfgs", max_iter=60),
            grid, cv=3, refit=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # unshuffled-KFold notice
            gs.fit(shard_rows(X), shard_rows(y))
        assert solvers.DISPATCH_COUNTS["solves"] == 3
        assert 0.9 < gs.best_score_ <= 1.0

    def test_ineligible_grids_fall_back(self, rng, mesh):
        from dask_ml_tpu import solvers

        X, grid, y = self._data(rng)
        # a second swept param: not a pure-C grid -> per-candidate path
        solvers.reset_dispatch_counts()
        gs = dms.GridSearchCV(
            dlm.LogisticRegression(solver="lbfgs", max_iter=60),
            {"C": [0.1, 1.0], "fit_intercept": [True, False]},
            cv=2, refit=False)
        gs.fit(X, y)
        assert solvers.DISPATCH_COUNTS["solves"] == 2 * 2 * 2
        # multiclass labels: fall back (sweep is binary-only)
        y3 = rng.randint(0, 3, size=len(y)).astype(np.float32)
        solvers.reset_dispatch_counts()
        gs3 = dms.GridSearchCV(
            dlm.LogisticRegression(solver="lbfgs", max_iter=60),
            {"C": [0.1, 1.0]}, cv=2, refit=False)
        gs3.fit(X, y3)
        assert hasattr(gs3, "cv_results_")

    def test_randomized_search_takes_fast_path(self, rng, mesh):
        from scipy.stats import loguniform

        from dask_ml_tpu import solvers

        X, _, y = self._data(rng)
        solvers.reset_dispatch_counts()
        rs = dms.RandomizedSearchCV(
            dlm.LogisticRegression(solver="lbfgs", max_iter=60),
            {"C": loguniform(1e-2, 1e2)}, n_iter=6, cv=2,
            random_state=0, refit=False)
        rs.fit(X, y)
        assert solvers.DISPATCH_COUNTS["solves"] == 2  # one sweep/fold
        best = float(np.max(np.asarray(rs.cv_results_["mean_test_score"])))
        assert 0.9 < best <= 1.0

    def test_linear_regression_sweep_matches_sequential(self, rng, mesh):
        # the packed search against the per-candidate one an explicit
        # scoring routes to ("r2" is the default score's)
        from dask_ml_tpu import solvers

        X = rng.normal(size=(500, 6)).astype(np.float32)
        w = rng.normal(size=6).astype(np.float32)
        y = (X @ w + 0.3 + 0.05 * rng.normal(size=500)).astype(np.float32)
        grid = {"C": np.logspace(0, 6, 5).tolist()}
        results = {}
        for strat, scoring in (("packed", None), ("sequential", "r2")):
            solvers.reset_dispatch_counts()
            gs = dms.GridSearchCV(
                dlm.LinearRegression(solver="lbfgs", max_iter=80),
                grid, cv=3, refit=False, scoring=scoring)
            gs.fit(X, y)
            results[strat] = (gs, solvers.DISPATCH_COUNTS["solves"])
        gp, dp = results["packed"]
        gq, dq = results["sequential"]
        np.testing.assert_allclose(
            np.asarray(gp.cv_results_["mean_test_score"]),
            np.asarray(gq.cv_results_["mean_test_score"]), atol=1e-5)
        assert gp.best_index_ == gq.best_index_
        assert dp == 3 and dq == 5 * 3

    def test_inplace_mutating_pipeline_is_safe(self, rng):
        # host fold slices must be FRESH per candidate: a Pipeline step
        # with copy=False mutates its input in place, and a shared
        # cached slice would poison every later candidate of the fold
        # (r4 review finding — device slices stay shared: jax arrays
        # are immutable)
        from sklearn.pipeline import Pipeline
        from sklearn.preprocessing import StandardScaler

        X = (rng.normal(size=(200, 4)) * 5 + 3).astype(np.float64)
        y = (X[:, 0] > 3).astype(int)
        pipe = Pipeline([
            ("sc", StandardScaler(copy=False)),
            ("clf", SGDClassifier(tol=1e-3, random_state=0)),
        ])
        # the same candidate twice: identical params MUST score
        # identically; under the shared-slice bug the second run fits
        # on already-scaled data
        gs = dms.GridSearchCV(
            pipe, {"clf__alpha": [1e-4, 1e-4]}, cv=2, refit=False,
            cache_cv=False)
        gs.fit(X, y)
        s = np.asarray(gs.cv_results_["mean_test_score"], dtype=float)
        np.testing.assert_allclose(s[0], s[1])
