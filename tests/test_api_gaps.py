"""Round-1 API gaps (VERDICT item 8): OneHotEncoder(drop), multiclass
LogisticRegression, make_classification_df, device LabelEncoder."""

import numpy as np
import pytest

import jax

from dask_ml_tpu.core import ShardedRows, shard_rows, unshard


class TestOneHotDrop:
    def _data(self):
        return np.array(
            [[0, 10], [1, 20], [2, 10], [0, 20], [1, 10]], dtype=np.float64
        )

    @pytest.mark.parametrize("drop", [None, "first", "if_binary"])
    def test_parity_with_sklearn(self, drop):
        from sklearn.preprocessing import OneHotEncoder as SkOHE

        from dask_ml_tpu.preprocessing import OneHotEncoder

        X = self._data()
        ours = OneHotEncoder(drop=drop).fit(X)
        theirs = SkOHE(drop=drop, sparse_output=False).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.transform(X)), theirs.transform(X)
        )
        assert list(ours.get_feature_names_out()) == list(
            theirs.get_feature_names_out()
        )

    def test_drop_array(self):
        from sklearn.preprocessing import OneHotEncoder as SkOHE

        from dask_ml_tpu.preprocessing import OneHotEncoder

        X = self._data()
        drop = [1.0, 20.0]
        ours = OneHotEncoder(drop=drop).fit(X)
        theirs = SkOHE(drop=np.asarray(drop), sparse_output=False).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.transform(X)), theirs.transform(X)
        )

    def test_drop_bad_value_raises(self):
        from dask_ml_tpu.preprocessing import OneHotEncoder

        with pytest.raises(ValueError, match="not a category"):
            OneHotEncoder(drop=[99.0, 20.0]).fit(self._data())

    def test_drop_sharded_roundtrip(self, mesh):
        from dask_ml_tpu.preprocessing import OneHotEncoder

        X = self._data()
        enc = OneHotEncoder(drop="first").fit(X)
        out = enc.transform(shard_rows(X))
        assert isinstance(out, ShardedRows)
        back = enc.inverse_transform(out)
        np.testing.assert_allclose(back.astype(np.float64), X)

    def test_drop_frame(self):
        import pandas as pd

        from dask_ml_tpu.preprocessing import OneHotEncoder

        df = pd.DataFrame({"a": ["x", "y", "x"], "b": [1, 2, 1]})
        out = OneHotEncoder(drop="first").fit_transform(df)
        assert list(out.columns) == ["a_y", "b_2"]


class TestMulticlassLogistic:
    def test_three_classes_labels_and_proba(self, rng, mesh):
        from sklearn.datasets import make_blobs

        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = make_blobs(n_samples=600, n_features=5, centers=3,
                          cluster_std=1.0, random_state=0)
        X = X.astype(np.float32)
        lr = LogisticRegression(solver="lbfgs", max_iter=100).fit(
            shard_rows(X), y
        )
        assert list(lr.classes_) == [0, 1, 2]
        pred = lr.predict(shard_rows(X))
        assert pred.dtype == y.dtype
        assert (pred == y).mean() > 0.95
        proba = np.asarray(lr.predict_proba(shard_rows(X)))
        assert proba.shape == (600, 3)
        np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-5)
        assert lr.coef_.shape == (3, 5)
        assert np.asarray(lr.intercept_).shape == (3,)

    def test_string_labels(self, rng):
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(300, 4)).astype(np.float32)
        y = np.where(X[:, 0] > 0, "pos", "neg")
        lr = LogisticRegression(solver="lbfgs", max_iter=100).fit(X, y)
        assert set(lr.classes_) == {"neg", "pos"}
        assert set(lr.predict(X[:20])) <= {"neg", "pos"}
        assert lr.score(X, y) > 0.9

    def test_binary_backward_compatible_shapes(self, rng):
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(300, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        lr = LogisticRegression(solver="admm", max_iter=50).fit(
            shard_rows(X), shard_rows(y)
        )
        assert np.asarray(lr.coef_).shape == (6,)
        assert isinstance(lr.intercept_, float)
        assert lr.score(X, y) > 0.9

    def test_parity_with_sklearn_multiclass(self, rng):
        from sklearn.datasets import make_blobs
        from sklearn.linear_model import LogisticRegression as SkLR

        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = make_blobs(n_samples=450, n_features=4, centers=3,
                          cluster_std=1.5, random_state=1)
        X = X.astype(np.float32)
        ours = LogisticRegression(solver="lbfgs", max_iter=200).fit(X, y)
        theirs = SkLR(max_iter=200).fit(X, y)
        ours_acc = (ours.predict(X) == y).mean()
        theirs_acc = theirs.score(X, y)
        assert ours_acc >= theirs_acc - 0.03

    def test_no_inert_param_warnings(self, rng):
        # class_weight is REAL since round 3; warm_start is REAL since
        # round 5 (seeds the solver with the previous coefficients) —
        # nothing left to warn about
        import warnings

        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(60, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(int)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            LogisticRegression(warm_start=True, max_iter=5).fit(X, y)
            LogisticRegression(class_weight="balanced", max_iter=5).fit(X, y)

    def test_warm_start_seeds_previous_solution(self, rng):
        """A warm refit on the SAME data starts at the previous optimum:
        the solver converges in (far) fewer iterations and reproduces
        the cold solution.  Covers binary, packed OvR, and multinomial."""
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(200, 6)).astype(np.float32)
        w = rng.normal(size=6)
        yb = (X @ w > 0).astype(np.float32)
        y3 = np.digitize(X @ w, [-0.5, 0.5]).astype(np.float32)

        for y, kw in [
            (yb, {}),
            (y3, {}),  # OvR (the packed lanes)
            (y3, {"multi_class": "multinomial"}),
        ]:
            cold = LogisticRegression(
                solver="lbfgs", max_iter=200, warm_start=True, **kw
            ).fit(X, y)
            first_iters = int(np.max(cold.n_iter_))
            coef_first = np.asarray(cold.coef_).copy()
            cold.fit(X, y)  # warm refit, same data
            assert int(np.max(cold.n_iter_)) <= max(first_iters // 2, 2), (
                kw, cold.n_iter_, first_iters)
            np.testing.assert_allclose(
                np.asarray(cold.coef_), coef_first, atol=1e-3)

    def test_warm_start_packed_lanes(self, rng):
        """The vmapped packed-OvR path consumes the per-lane Beta0
        stack."""
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(200, 6)).astype(np.float32)
        w = rng.normal(size=6)
        y3 = np.digitize(X @ w, [-0.5, 0.5]).astype(np.float32)
        clf = LogisticRegression(
            solver="lbfgs", max_iter=200, warm_start=True).fit(X, y3)
        first = int(np.max(clf.n_iter_))
        coef_first = np.asarray(clf.coef_).copy()
        clf.fit(X, y3)
        assert int(np.max(clf.n_iter_)) <= max(first // 2, 2), (
            clf.n_iter_, first)
        np.testing.assert_allclose(
            np.asarray(clf.coef_), coef_first, atol=1e-3)

    def test_warm_start_cold_starts_on_changed_geometry(self, rng):
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(100, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        clf = LogisticRegression(
            solver="lbfgs", max_iter=50, warm_start=True).fit(X, y)
        # different feature count: silently cold-starts, must not crash
        X2 = rng.normal(size=(100, 6)).astype(np.float32)
        y2 = (X2[:, 0] > 0).astype(np.float32)
        clf.fit(X2, y2)
        assert clf.coef_.shape == (6,)

    def test_single_class_raises(self, rng):
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(40, 3)).astype(np.float32)
        with pytest.raises(ValueError, match="2 classes"):
            LogisticRegression().fit(X, np.zeros(40))


class TestMakeClassificationDf:
    def test_shapes_and_names(self):
        import pandas as pd

        from dask_ml_tpu.datasets import make_classification_df

        df, y = make_classification_df(
            n_samples=120, n_features=7, chunks=40, random_state=0
        )
        assert isinstance(df, pd.DataFrame) and isinstance(y, pd.Series)
        assert df.shape == (120, 7)
        assert list(df.columns) == [f"feature_{i}" for i in range(7)]
        assert y.name == "target"
        assert set(y.unique()) == {0, 1}

    def test_dates_column(self):
        from dask_ml_tpu.datasets import make_classification_df

        df, _ = make_classification_df(
            n_samples=50, n_features=5, random_state=0,
            dates=("2024-01-01", "2024-02-01"),
        )
        assert df.columns[0] == "date"
        assert df["date"].between("2024-01-01", "2024-02-01").all()

    def test_deterministic(self):
        from dask_ml_tpu.datasets import make_classification_df

        a, ya = make_classification_df(n_samples=60, n_features=4, random_state=7)
        b, yb = make_classification_df(n_samples=60, n_features=4, random_state=7)
        np.testing.assert_allclose(a.to_numpy(), b.to_numpy())
        assert (ya == yb).all()


class TestLabelEncoderDevice:
    def test_sharded_numeric_stays_sharded(self, rng, mesh):
        from dask_ml_tpu.preprocessing import LabelEncoder

        y = rng.choice([3.0, 7.0, 11.0], size=101).astype(np.float32)
        ys = shard_rows(y)
        le = LabelEncoder().fit(ys)
        out = le.transform(ys)
        assert isinstance(out, ShardedRows)
        np.testing.assert_array_equal(
            unshard(out), np.searchsorted(le.classes_, y)
        )
        back = le.inverse_transform(out)
        np.testing.assert_allclose(back, y)

    def test_sharded_unseen_raises(self, rng, mesh):
        from dask_ml_tpu.preprocessing import LabelEncoder

        le = LabelEncoder().fit(np.array([1.0, 2.0]))
        with pytest.raises(ValueError, match="unseen"):
            le.transform(shard_rows(np.array([1.0, 3.0], dtype=np.float32)))

    def test_parity_with_sklearn(self, rng):
        from sklearn.preprocessing import LabelEncoder as SkLE

        from dask_ml_tpu.preprocessing import LabelEncoder

        y = rng.choice(["a", "b", "c"], size=50)
        ours = LabelEncoder().fit(y)
        theirs = SkLE().fit(y)
        np.testing.assert_array_equal(ours.classes_, theirs.classes_)
        np.testing.assert_array_equal(
            np.asarray(ours.transform(y)), theirs.transform(y)
        )


class TestReviewRegressions:
    def test_precomputed_rejects_nonsquare(self, rng, mesh):
        from dask_ml_tpu.cluster import SpectralClustering

        X = rng.normal(size=(40, 5)).astype(np.float32)
        for nc in (10, None):
            with pytest.raises(ValueError, match="n_samples, n_samples"):
                SpectralClustering(
                    affinity="precomputed", n_components=nc
                ).fit(shard_rows(X))

    def test_callable_metric_eager_numpy_ok(self, rng, mesh):
        # numpy-based callables must keep working on sharded x sharded
        # input (they run eagerly on the global operands, not in the ring)
        from dask_ml_tpu.metrics import pairwise_distances

        def np_metric(a, b):
            a = np.asarray(a)
            b = np.asarray(b)
            return np.abs(a[:, None, 0] - b[None, :, 0])

        X = rng.normal(size=(17, 3)).astype(np.float32)
        Y = rng.normal(size=(9, 3)).astype(np.float32)
        out = pairwise_distances(shard_rows(X), shard_rows(Y), metric=np_metric)
        np.testing.assert_allclose(
            np.asarray(out), np.abs(X[:, None, 0] - Y[None, :, 0]), rtol=1e-5
        )


class TestContractGapsRound3:
    """score(sample_weight=), predict_log_proba, scaler partial_fit —
    sklearn-contract surface a switching user expects (round-3 sweep)."""

    def _clf_data(self, rng):
        X = rng.normal(size=(200, 5)).astype(np.float32)
        w = rng.normal(size=5)
        y = (X @ w > 0).astype(np.int64)
        return X, y

    def test_logreg_weighted_score_and_log_proba(self, rng):
        from sklearn.metrics import accuracy_score as sk_acc

        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = self._clf_data(rng)
        sw = rng.rand(200)
        m = LogisticRegression(max_iter=60).fit(X, y)
        assert m.score(X, y, sample_weight=sw) == pytest.approx(
            sk_acc(y, np.asarray(m.predict(X)), sample_weight=sw), abs=1e-6
        )
        lp = np.asarray(m.predict_log_proba(X))
        np.testing.assert_allclose(
            np.exp(lp), np.asarray(m.predict_proba(X)), atol=1e-6
        )

    def test_sgd_weighted_score_and_log_proba(self, rng):
        from dask_ml_tpu.linear_model import SGDClassifier, SGDRegressor

        X, y = self._clf_data(rng)
        sw = rng.rand(200)
        m = SGDClassifier(max_iter=60, tol=None).fit(X, y)
        s_w = m.score(X, y, sample_weight=sw)
        assert 0.0 <= s_w <= 1.0
        lp = np.asarray(m.predict_log_proba(X))
        np.testing.assert_allclose(
            np.exp(lp), np.asarray(m.predict_proba(X)), atol=1e-6
        )
        yr = (X[:, 0] * 2).astype(np.float32)
        r = SGDRegressor(max_iter=100, tol=None).fit(X, yr)
        assert r.score(X, yr, sample_weight=sw) <= 1.0

    def test_kmeans_weighted_score(self, rng):
        from dask_ml_tpu.cluster import KMeans, MiniBatchKMeans

        X = rng.normal(size=(120, 3)).astype(np.float32)
        w = np.zeros(120); w[:60] = 1.0
        for cls in (KMeans, MiniBatchKMeans):
            m = cls(n_clusters=3, random_state=0).fit(X)
            # zero-weighted rows contribute nothing: score == score on X[:60]
            assert m.score(X, sample_weight=w) == pytest.approx(
                m.score(X[:60]), rel=1e-4
            )

    def test_standard_scaler_partial_fit_matches_fit(self, rng):
        from dask_ml_tpu.preprocessing import StandardScaler

        X = rng.normal(size=(300, 4)).astype(np.float32) * 3 + 1
        full = StandardScaler().fit(X)
        stream = StandardScaler()
        for lo in range(0, 300, 100):
            stream.partial_fit(X[lo:lo + 100])
        np.testing.assert_allclose(
            np.asarray(stream.mean_), np.asarray(full.mean_), rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(stream.var_), np.asarray(full.var_), rtol=1e-4
        )
        assert stream.n_samples_seen_ == 300
        # refit resets the stream state
        refit = stream.fit(X[:100])
        assert refit.n_samples_seen_ == 100

    def test_minmax_maxabs_partial_fit(self, rng):
        from dask_ml_tpu.preprocessing import MaxAbsScaler, MinMaxScaler

        X = rng.normal(size=(200, 3)).astype(np.float32) * 5
        for cls, attrs in ((MinMaxScaler, ("data_min_", "data_max_")),
                           (MaxAbsScaler, ("max_abs_",))):
            full = cls().fit(X)
            stream = cls()
            for lo in range(0, 200, 64):
                stream.partial_fit(X[lo:lo + 64])
            for a in attrs:
                np.testing.assert_allclose(
                    np.asarray(getattr(stream, a)),
                    np.asarray(getattr(full, a)), rtol=1e-6,
                )
            assert stream.n_samples_seen_ == 200

    def test_scaler_partial_fit_streams_through_incremental(self, rng):
        from dask_ml_tpu.wrappers import Incremental
        from dask_ml_tpu.preprocessing import StandardScaler

        X = rng.normal(size=(256, 4)).astype(np.float32)
        inc = Incremental(StandardScaler(), chunk_size=64).fit(X)
        np.testing.assert_allclose(
            np.asarray(inc.estimator_.mean_),
            X.mean(axis=0), rtol=1e-4, atol=1e-5,
        )

    def test_weighted_score_string_labels(self, rng):
        from dask_ml_tpu.linear_model import SGDClassifier

        X = rng.normal(size=(150, 4)).astype(np.float32)
        y = np.where(X[:, 0] > 0, "pos", "neg")
        sw = rng.rand(150)
        m = SGDClassifier(max_iter=50, tol=None).fit(X, y)
        s = m.score(X, y, sample_weight=sw)
        hits = np.asarray(m.predict(X)) == y
        assert s == pytest.approx(np.average(hits, weights=sw))

    def test_standard_scaler_stream_checkpoint_roundtrip(self, rng, tmp_path):
        from dask_ml_tpu.checkpoint import load_estimator, save_estimator
        from dask_ml_tpu.preprocessing import StandardScaler

        X = rng.normal(size=(300, 4)).astype(np.float32) * 2 + 3
        a = StandardScaler().partial_fit(X[:100]).partial_fit(X[100:200])
        p = str(tmp_path / "scaler.ckpt")
        save_estimator(a, p)
        b = load_estimator(p)
        b.partial_fit(X[200:])
        full = StandardScaler().fit(X)
        np.testing.assert_allclose(
            np.asarray(b.mean_), np.asarray(full.mean_), rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(b.var_), np.asarray(full.var_), rtol=1e-4
        )
        assert b.n_samples_seen_ == 300

    def test_balanced_class_weight_sub_unit_mask_mass(self, rng):
        # regression: the balanced branch clamped per-class weight mass
        # to 1, shrinking balanced weights whenever mass < 1
        import jax.numpy as jnp

        from dask_ml_tpu.utils import effective_mask

        y_idx = jnp.asarray(np.r_[np.zeros(30), np.ones(10)], jnp.float32)
        tiny = jnp.full((40,), 1e-3, jnp.float32)  # mask IS the weight
        m = effective_mask(
            tiny, y_idx, class_weight="balanced", classes=[0, 1],
            n_samples=40,
        )
        m = np.asarray(m)
        # balanced: minority rows upweighted by exactly count ratio 3x
        assert m[39] / m[0] == pytest.approx(3.0, rel=1e-4)


class TestFeatureNamesAndPcaScore:
    """Round-5 API slivers: get_feature_names_out across the transformer
    surface (sklearn OneToOne / ClassNamePrefix mixin contracts) and the
    probabilistic-PCA log-likelihood (``PCA.score[_samples]``)."""

    def test_one_to_one_names(self, rng):
        from dask_ml_tpu.impute import SimpleImputer
        from dask_ml_tpu.preprocessing import (
            MaxAbsScaler,
            MinMaxScaler,
            Normalizer,
            QuantileTransformer,
            RobustScaler,
            StandardScaler,
        )

        X = rng.normal(size=(30, 3)).astype(np.float64)
        for est in (StandardScaler(), MinMaxScaler(), MaxAbsScaler(),
                    RobustScaler(), QuantileTransformer(n_quantiles=5),
                    Normalizer(), SimpleImputer()):
            est.fit(X)
            assert list(est.get_feature_names_out()) == ["x0", "x1", "x2"]
            assert list(est.get_feature_names_out(["a", "b", "c"])) == [
                "a", "b", "c"]

    def test_imputer_indicator_names_match_width(self, rng):
        from dask_ml_tpu.impute import SimpleImputer

        X = rng.normal(size=(30, 3)).astype(np.float64)
        X[2, 1] = np.nan
        im = SimpleImputer(add_indicator=True).fit(X)
        names = im.get_feature_names_out()
        assert list(names) == ["x0", "x1", "x2", "missingindicator_x1"]
        assert np.asarray(im.transform(X)).shape[1] == len(names)

    def test_decomposition_names(self, rng):
        from dask_ml_tpu.decomposition import (
            PCA,
            IncrementalPCA,
            TruncatedSVD,
        )

        X = rng.normal(size=(40, 4)).astype(np.float64)
        assert list(
            PCA(n_components=2).fit(X).get_feature_names_out()
        ) == ["pca0", "pca1"]
        assert list(
            TruncatedSVD(n_components=2).fit(X).get_feature_names_out()
        ) == ["truncatedsvd0", "truncatedsvd1"]
        assert list(
            IncrementalPCA(n_components=2).fit(X).get_feature_names_out()
        ) == ["incrementalpca0", "incrementalpca1"]

    @pytest.mark.parametrize("whiten", [False, True])
    def test_pca_score_samples_matches_sklearn(self, rng, whiten):
        from sklearn.decomposition import PCA as SkPCA

        from dask_ml_tpu.decomposition import PCA

        X = rng.normal(size=(60, 5)).astype(np.float64)
        ours = PCA(n_components=3, whiten=whiten).fit(X)
        ref = SkPCA(n_components=3, whiten=whiten).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.get_covariance()), ref.get_covariance(),
            atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(ours.score_samples(X)), ref.score_samples(X),
            atol=1e-4,
        )
        assert ours.score(X) == pytest.approx(ref.score(X), abs=1e-4)
        # sharded input path slices to real rows
        s = shard_rows(X.astype(np.float32))
        assert np.asarray(ours.score_samples(s)).shape == (60,)


class TestRound5Slivers:
    """Continuation-session sliver sweep: methods a migrating sklearn
    user would reach for that the surface audit found missing."""

    @pytest.mark.parametrize("whiten", [False, True])
    @pytest.mark.parametrize("k", [3, 5])
    def test_pca_get_precision_parity(self, rng, whiten, k):
        from sklearn.decomposition import PCA as SkPCA

        from dask_ml_tpu.decomposition import PCA

        X = (rng.normal(size=(80, 5)) * np.linspace(2, 0.3, 5)).astype(
            np.float64
        )
        ours = PCA(n_components=k, whiten=whiten).fit(X)
        ref = SkPCA(n_components=k, whiten=whiten, svd_solver="full").fit(X)
        scale = np.abs(ref.get_precision()).max()
        np.testing.assert_allclose(
            np.asarray(ours.get_precision()) / scale,
            ref.get_precision() / scale, atol=2e-5,
        )

    def test_incremental_pca_covariance_tracks_full_pca(self, rng):
        # deliberate deviation from sklearn's IPCA (docstring): our
        # noise_variance_ is the PCA-consistent residual estimator, so
        # the model covariance/precision must track FULL PCA on the
        # same data — sklearn's IPCA tail-spectrum estimate does not
        from sklearn.decomposition import PCA as SkPCA

        from dask_ml_tpu.decomposition import IncrementalPCA

        X = (rng.normal(size=(300, 8)) * np.linspace(3, 0.1, 8)).astype(
            np.float32
        )
        io = IncrementalPCA(n_components=4, batch_size=60).fit(X)
        ref = SkPCA(n_components=4, svd_solver="full").fit(
            X.astype(np.float64)
        )
        # streamed fit: loose sanity on the covariance (incremental
        # components/noise carry estimation error of their own; the
        # precision INVERSE amplifies it, so exactness is asserted via
        # the transplanted-attributes check below instead)
        got = np.asarray(io.get_covariance())
        want = ref.get_covariance()
        scale = np.abs(want).max()
        np.testing.assert_allclose(got / scale, want / scale, atol=5e-2)
        # formula exactness: with identical fitted attributes the two
        # classes must produce identical covariance/precision
        io.components_ = np.asarray(ref.components_, np.float64)
        io.explained_variance_ = np.asarray(
            ref.explained_variance_, np.float64
        )
        io.noise_variance_ = float(ref.noise_variance_)
        io.n_components_ = 4
        for m in ("get_covariance", "get_precision"):
            got, want = np.asarray(getattr(io, m)()), getattr(ref, m)()
            scale = np.abs(want).max()
            # f32 device math: formula-identical up to roundoff
            np.testing.assert_allclose(
                got / scale, want / scale, atol=1e-5
            )

    def test_kmeans_get_feature_names_out(self, rng):
        from dask_ml_tpu.cluster import KMeans

        X = rng.normal(size=(100, 4)).astype(np.float32)
        km = KMeans(n_clusters=3, random_state=0).fit(shard_rows(X))
        assert list(km.get_feature_names_out()) == [
            "kmeans0", "kmeans1", "kmeans2",
        ]
        # names describe transform's output width
        assert np.asarray(km.transform(shard_rows(X))).shape[1] == 3

    def test_ordinal_encoder_get_feature_names_out(self):
        import pandas as pd

        from dask_ml_tpu.preprocessing import OrdinalEncoder

        Xc = np.array([["a", "x"], ["b", "y"], ["a", "y"]], dtype=object)
        oe = OrdinalEncoder().fit(Xc)
        assert list(oe.get_feature_names_out()) == ["x0", "x1"]
        assert list(oe.get_feature_names_out(["u", "v"])) == ["u", "v"]
        df = pd.DataFrame({"c1": ["a", "b"], "c2": [1.0, 2.0]})
        oe2 = OrdinalEncoder().fit(df)
        assert list(oe2.get_feature_names_out()) == ["c1", "c2"]

    def test_simple_imputer_inverse_transform(self, rng):
        from sklearn.impute import SimpleImputer as SkImputer

        from dask_ml_tpu.impute import SimpleImputer

        X = rng.normal(size=(50, 4)).astype(np.float64)
        X[rng.rand(*X.shape) < 0.25] = np.nan
        ours = SimpleImputer(strategy="mean", add_indicator=True).fit(X)
        ref = SkImputer(strategy="mean", add_indicator=True).fit(X)
        t = np.asarray(ours.transform(X))
        inv, inv_ref = (
            np.asarray(ours.inverse_transform(t)),
            ref.inverse_transform(ref.transform(X)),
        )
        np.testing.assert_array_equal(np.isnan(inv), np.isnan(inv_ref))
        np.testing.assert_allclose(
            np.nan_to_num(inv), np.nan_to_num(inv_ref), atol=1e-6
        )
        # sharded roundtrip preserves the container
        s = shard_rows(X.astype(np.float32))
        ts = ours.transform(s)
        invs = ours.inverse_transform(ts)
        assert isinstance(invs, ShardedRows)
        assert invs.n_samples == 50
        with pytest.raises(ValueError, match="add_indicator"):
            SimpleImputer().fit(X).inverse_transform(t[:, :4])


class TestRound5AdviceFixes:
    """ISSUE 1 satellites: validation/regularization fixes flagged by the
    round-5 advice review."""

    def test_imputer_inverse_transform_rejects_wrong_width(self, rng):
        from dask_ml_tpu.impute import SimpleImputer

        X = rng.normal(size=(30, 4)).astype(np.float64)
        X[rng.rand(*X.shape) < 0.3] = np.nan
        imp = SimpleImputer(strategy="mean", add_indicator=True).fit(X)
        t = np.asarray(imp.transform(X))
        # truncated input used to be SILENTLY split at d columns; now the
        # width must be exactly d + len(indicator_features_)
        with pytest.raises(ValueError, match="columns"):
            imp.inverse_transform(t[:, :-1])
        with pytest.raises(ValueError, match="columns"):
            imp.inverse_transform(np.hstack([t, t[:, :1]]))
        # the exact width still round-trips
        assert np.asarray(imp.inverse_transform(t)).shape == X.shape

    def test_ordinal_encoder_feature_names_validates_input(self):
        import pandas as pd

        from dask_ml_tpu.preprocessing import OrdinalEncoder

        Xc = np.array([["a", "x"], ["b", "y"], ["a", "y"]], dtype=object)
        oe = OrdinalEncoder().fit(Xc)
        with pytest.raises(ValueError, match="2 features"):
            oe.get_feature_names_out(["only_one"])
        df = pd.DataFrame({"c1": ["a", "b"], "c2": [1.0, 2.0]})
        oe2 = OrdinalEncoder().fit(df)
        # frame fit: the fitted column names verbatim, or an error
        assert list(oe2.get_feature_names_out(["c1", "c2"])) == ["c1", "c2"]
        with pytest.raises(ValueError, match="columns seen at fit"):
            oe2.get_feature_names_out(["c2", "c1"])

    def test_pca_get_precision_unjittered_when_well_posed(self, rng):
        """The full-rank branch must report the PLAIN inverse when it is
        finite — the 1e-12·trace jitter only rescues a singular
        covariance (it used to be applied unconditionally)."""
        from sklearn.decomposition import PCA as SkPCA

        from dask_ml_tpu.decomposition import PCA

        X = (rng.normal(size=(60, 4)) * np.linspace(2, 0.5, 4)).astype(
            np.float64
        )
        ours = PCA(n_components=4).fit(X)  # k == d: full-rank branch
        ref = SkPCA(n_components=4, svd_solver="full").fit(X)
        scale = np.abs(ref.get_precision()).max()
        np.testing.assert_allclose(
            np.asarray(ours.get_precision()) / scale,
            ref.get_precision() / scale, atol=1e-6,
        )
