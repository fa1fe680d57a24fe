"""True parallelism in the search planes (VERDICT round-1 item 6): the
thread-pool fan-out must produce real wall-clock overlap (>1.5x with 4
workers), identical results to serial, and a compute-once prefix cache."""

import threading
import time

import numpy as np
import pytest

import jax

from conftest import PeakInside
from sklearn.base import BaseEstimator

from dask_ml_tpu.model_selection import GridSearchCV, IncrementalSearchCV


class SleepyClassifier(BaseEstimator):
    """GIL-releasing slow fit (time.sleep releases the GIL like sklearn's C
    kernels do), deterministic score."""

    #: the fits asleep at once, over every instance (a test that reads
    #: it puts a new gauge here first)
    asleep = PeakInside()

    def __init__(self, delay=0.05, quality=0.5):
        self.delay = delay
        self.quality = quality

    def fit(self, X, y=None, **kwargs):
        with SleepyClassifier.asleep:
            time.sleep(self.delay)
        self.fitted_ = True
        return self

    partial_fit = fit

    def score(self, X, y=None):
        return self.quality

    def predict(self, X):
        return np.zeros(len(X))


class TestGridSearchParallel:
    def _grid(self, n_jobs):
        return GridSearchCV(
            SleepyClassifier(delay=0.05),
            {"quality": [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]},
            cv=2,
            n_jobs=n_jobs,
            refit=False,
        )

    def test_four_workers_speedup(self, rng):
        X = rng.normal(size=(40, 3))
        y = (X[:, 0] > 0).astype(int)
        t0 = time.perf_counter()
        self._grid(1).fit(X, y)
        serial = time.perf_counter() - t0
        t0 = time.perf_counter()
        self._grid(4).fit(X, y)
        par = time.perf_counter() - t0
        assert serial / par > 1.5, (serial, par)

    def test_parallel_results_match_serial(self, rng):
        X = rng.normal(size=(40, 3))
        y = (X[:, 0] > 0).astype(int)
        a = self._grid(1).fit(X, y)
        b = self._grid(4).fit(X, y)
        assert a.best_params_ == b.best_params_
        np.testing.assert_allclose(
            a.cv_results_["mean_test_score"], b.cv_results_["mean_test_score"]
        )
        assert a.cv_results_["rank_test_score"] == b.cv_results_["rank_test_score"]

    def test_error_score_raise_propagates(self, rng):
        class Exploder(BaseEstimator):
            def __init__(self, boom=True):
                self.boom = boom

            def fit(self, X, y=None):
                raise RuntimeError("boom")

            def score(self, X, y=None):  # pragma: no cover
                return 0.0

        X = rng.normal(size=(20, 2))
        search = GridSearchCV(Exploder(), {"boom": [True, False]}, cv=2,
                              n_jobs=4, refit=False)
        with pytest.raises(RuntimeError, match="boom"):
            search.fit(X, np.zeros(20))

    def test_prefix_cache_compute_once_under_threads(self, rng):
        from sklearn.pipeline import Pipeline
        from sklearn.preprocessing import StandardScaler

        fit_counts = {"n": 0}
        lock = threading.Lock()

        class CountingScaler(StandardScaler):
            def fit(self, X, y=None, sample_weight=None):
                with lock:
                    fit_counts["n"] += 1
                time.sleep(0.02)  # widen the race window
                return super().fit(X, y)

        X = rng.normal(size=(60, 3))
        y = (X[:, 0] > 0).astype(int)
        pipe = Pipeline([
            ("sc", CountingScaler()),
            ("clf", SleepyClassifier(delay=0.01)),
        ])
        search = GridSearchCV(
            pipe,
            {"clf__quality": [0.1, 0.3, 0.5, 0.7]},
            cv=3, n_jobs=4, refit=False,
        )
        search.fit(X, y)
        # one scaler fit per FOLD (3), never per candidate x fold (12)
        assert fit_counts["n"] == 3, fit_counts


class TestIncrementalParallel:
    def test_models_overlap_in_wall_clock(self, rng):
        X = rng.normal(size=(60, 3))
        y = (X[:, 0] > 0).astype(int)
        n_models = 6
        search = IncrementalSearchCV(
            SleepyClassifier(delay=0.08),
            {"quality": np.linspace(0.1, 0.9, n_models)},
            n_initial_parameters=n_models,
            max_iter=2,
            random_state=0,
        )
        SleepyClassifier.asleep = PeakInside()
        search.fit(X, y)
        # models train at the same time, not one after another
        assert SleepyClassifier.asleep.peak >= 2
        assert search.best_score_ == pytest.approx(0.9)


class TestMeshPropagation:
    def test_caller_mesh_reaches_worker_threads(self, rng):
        # thread-local mesh overrides must survive the executor hop
        from dask_ml_tpu.core.mesh import device_mesh, get_mesh, use_mesh

        seen = []

        class MeshSpy(BaseEstimator):
            def fit(self, X, y=None):
                seen.append(get_mesh().shape)
                self.fitted_ = True
                return self

            def partial_fit(self, X, y=None, **kw):
                seen.append(get_mesh().shape)
                self.fitted_ = True
                return self

            def score(self, X, y=None):
                return 0.5

        X = rng.normal(size=(40, 3))
        y = (X[:, 0] > 0).astype(int)
        from conftest import require_devices_divisible

        mesh = device_mesh(require_devices_divisible(4), model_axis=4)
        with use_mesh(mesh):
            GridSearchCV(MeshSpy(), {}, cv=2, n_jobs=4, refit=False).fit(X, y)
            IncrementalSearchCV(
                MeshSpy(), {}, n_initial_parameters="grid", max_iter=1,
            ).fit(X, y)
        assert seen, "no fits ran"
        for shape in seen:
            assert dict(shape) == {"data": len(jax.devices()) // 4,
                                   "model": 4}, shape


class MutatingScaler(BaseEstimator):
    """A transformer that scales its input IN PLACE (the sklearn
    ``copy=False`` hazard class): under a shared fold cache, one
    candidate's fit would poison every later candidate's view of the
    same fold slice."""

    def fit(self, X, y=None):
        return self

    def transform(self, X):
        X *= 2.0  # in-place: mutates whatever array object it was given
        return X

    def fit_transform(self, X, y=None):
        return self.fit(X).transform(X)


class TestFoldCacheMutationSafety:
    """The refcounted fold cache under concurrent
    n_jobs mutation.  Host numpy fold slices must be fresh per task
    (mutable), so an in-place pipeline step cannot corrupt siblings;
    results must be identical serial vs 4-way concurrent."""

    def _grid(self, n_jobs):
        from sklearn.pipeline import Pipeline
        from sklearn.linear_model import LogisticRegression as SkLR

        return GridSearchCV(
            Pipeline([("mut", MutatingScaler()),
                      ("clf", SkLR(max_iter=50))]),
            {"clf__C": [0.01, 0.1, 1.0, 10.0, 100.0]},
            cv=3, n_jobs=n_jobs, refit=False,
            cache_cv=False,  # the mutating step must not be prefix-cached
        )

    def test_inplace_step_concurrent_matches_serial(self, rng):
        X = rng.normal(size=(90, 4)).astype(np.float64)
        y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(int)
        Xa, Xb = X.copy(), X.copy()
        a = self._grid(1).fit(Xa, y)
        b = self._grid(4).fit(Xb, y)
        np.testing.assert_allclose(
            a.cv_results_["mean_test_score"],
            b.cv_results_["mean_test_score"],
        )
        # the ORIGINAL arrays must also be untouched: fold slices are
        # copies, never views into the caller's X
        np.testing.assert_array_equal(Xa, X)
        np.testing.assert_array_equal(Xb, X)

    def test_inplace_step_with_prefix_cache_is_safe(self, rng):
        """cache_cv=True shares fitted-prefix OUTPUTS across candidates;
        a later in-place final step mutating the cached transformed
        array would poison siblings.  Concurrent scores must still match
        serial."""
        from sklearn.pipeline import Pipeline
        from sklearn.linear_model import LogisticRegression as SkLR

        class MutatingLR(SkLR):
            def fit(self, X, y, **kw):
                X *= 1.0 + float(self.C)  # in-place, C-dependent
                return super().fit(X, y, **kw)

        def grid(n_jobs):
            return GridSearchCV(
                Pipeline([("mut", MutatingScaler()),
                          ("clf", MutatingLR(max_iter=50))]),
                {"clf__C": [0.01, 1.0, 100.0]},
                cv=2, n_jobs=n_jobs, refit=False, cache_cv=True,
            )

        X = rng.normal(size=(60, 4)).astype(np.float64)
        y = (X[:, 0] > 0).astype(int)
        a = grid(1).fit(X.copy(), y)
        b = grid(4).fit(X.copy(), y)
        np.testing.assert_allclose(
            a.cv_results_["mean_test_score"],
            b.cv_results_["mean_test_score"],
        )
