import numpy as np
import pytest
import sklearn.metrics as sm
import sklearn.metrics.pairwise as smp

import dask_ml_tpu.metrics as dmm
from dask_ml_tpu.core import shard_rows


@pytest.fixture
def XY(rng):
    X = rng.normal(size=(33, 6)).astype(np.float32)
    Y = rng.normal(size=(7, 6)).astype(np.float32)
    return X, Y


class TestPairwise:
    def test_euclidean_parity(self, XY):
        X, Y = XY
        got = np.asarray(dmm.euclidean_distances(X, Y))
        np.testing.assert_allclose(got, smp.euclidean_distances(X, Y), atol=1e-4)

    def test_euclidean_sharded_rows(self, XY):
        X, Y = XY
        s = shard_rows(X)
        got = np.asarray(dmm.euclidean_distances(s, Y))[: s.n_samples]
        np.testing.assert_allclose(got, smp.euclidean_distances(X, Y), atol=1e-4)

    def test_argmin_min(self, XY):
        X, Y = XY
        idx, dist = dmm.pairwise_distances_argmin_min(X, Y)
        eidx, edist = smp.pairwise_distances_argmin_min(X, Y)
        np.testing.assert_array_equal(np.asarray(idx), eidx)
        np.testing.assert_allclose(np.asarray(dist), edist, atol=1e-4)

    @pytest.mark.parametrize("name", ["linear", "polynomial", "rbf", "sigmoid"])
    def test_kernels_parity(self, XY, name):
        X, Y = XY
        ours = dmm.PAIRWISE_KERNEL_FUNCTIONS[name]
        theirs = {
            "linear": smp.linear_kernel,
            "polynomial": smp.polynomial_kernel,
            "rbf": smp.rbf_kernel,
            "sigmoid": smp.sigmoid_kernel,
        }[name]
        np.testing.assert_allclose(
            np.asarray(ours(X, Y)), theirs(X, Y), atol=1e-4, rtol=1e-4
        )

    def test_cosine_metric(self, XY):
        X, Y = XY
        got = np.asarray(dmm.pairwise_distances(X, Y, metric="cosine"))
        np.testing.assert_allclose(got, smp.cosine_distances(X, Y), atol=1e-4)

    def test_bad_metric_raises(self, XY):
        with pytest.raises(ValueError, match="Unsupported metric"):
            dmm.pairwise_distances(*XY, metric="mahalanobis")


class TestClassification:
    def test_accuracy_parity(self, rng):
        y = rng.randint(0, 2, size=51)
        p = rng.randint(0, 2, size=51)
        assert dmm.accuracy_score(y, p) == pytest.approx(sm.accuracy_score(y, p))

    def test_accuracy_unnormalized(self, rng):
        y = rng.randint(0, 2, size=51)
        p = rng.randint(0, 2, size=51)
        assert dmm.accuracy_score(y, p, normalize=False) == pytest.approx(
            sm.accuracy_score(y, p, normalize=False)
        )

    def test_accuracy_sharded_mask_excludes_padding(self, rng):
        y = rng.randint(0, 2, size=51)
        p = y.copy()
        s_y, s_p = shard_rows(y), shard_rows(p)
        assert dmm.accuracy_score(s_y, s_p) == pytest.approx(1.0)

    def test_accuracy_sample_weight(self, rng):
        y = rng.randint(0, 2, size=40)
        p = rng.randint(0, 2, size=40)
        w = rng.uniform(size=40)
        assert dmm.accuracy_score(y, p, sample_weight=w) == pytest.approx(
            sm.accuracy_score(y, p, sample_weight=w), abs=1e-6
        )

    def test_log_loss_binary_proba_matrix(self, rng):
        y = rng.randint(0, 2, size=60)
        proba = rng.uniform(0.01, 0.99, size=(60, 2)).astype(np.float64)
        proba /= proba.sum(1, keepdims=True)
        assert dmm.log_loss(y, proba) == pytest.approx(sm.log_loss(y, proba), rel=1e-5)

    def test_log_loss_multiclass(self, rng):
        y = rng.randint(0, 3, size=60)
        proba = rng.uniform(0.01, 0.99, size=(60, 3)).astype(np.float64)
        proba /= proba.sum(1, keepdims=True)
        assert dmm.log_loss(y, proba) == pytest.approx(sm.log_loss(y, proba), rel=1e-5)

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="different lengths"):
            dmm.accuracy_score(np.ones(5), np.ones(6))


class TestRegression:
    @pytest.mark.parametrize(
        "ours,theirs",
        [
            (dmm.mean_squared_error, sm.mean_squared_error),
            (dmm.mean_absolute_error, sm.mean_absolute_error),
            (dmm.r2_score, sm.r2_score),
        ],
    )
    def test_parity(self, rng, ours, theirs):
        y = rng.normal(size=45).astype(np.float64)
        p = y + 0.3 * rng.normal(size=45)
        assert ours(y, p) == pytest.approx(theirs(y, p), rel=1e-4)

    def test_msle_parity(self, rng):
        y = rng.uniform(0.1, 5.0, size=45)
        p = rng.uniform(0.1, 5.0, size=45)
        assert dmm.mean_squared_log_error(y, p) == pytest.approx(
            sm.mean_squared_log_error(y, p), rel=1e-4
        )

    def test_rmse(self, rng):
        y = rng.normal(size=45)
        p = y + 0.3 * rng.normal(size=45)
        assert dmm.mean_squared_error(y, p, squared=False) == pytest.approx(
            np.sqrt(sm.mean_squared_error(y, p)), rel=1e-4
        )

    def test_sample_weight(self, rng):
        y = rng.normal(size=45)
        p = y + 0.3 * rng.normal(size=45)
        w = rng.uniform(size=45)
        assert dmm.mean_squared_error(y, p, sample_weight=w) == pytest.approx(
            sm.mean_squared_error(y, p, sample_weight=w), rel=1e-4
        )


class TestScorer:
    def test_get_scorer_known(self):
        assert callable(dmm.get_scorer("accuracy"))

    def test_get_scorer_unknown(self):
        with pytest.raises(ValueError, match="not a valid scoring"):
            dmm.get_scorer("nope")

    def test_scorer_applies_sign(self, rng):
        class Dummy:
            def predict(self, X):
                return np.zeros(len(X))

        y = np.ones(10)
        score = dmm.SCORERS["neg_mean_squared_error"](Dummy(), np.zeros((10, 2)), y)
        assert score == pytest.approx(-1.0)


class TestReviewRegressions:
    """Cases from code review: mixed sharded/plain inputs, constant y, labels=."""

    def test_mixed_sharded_plain_accuracy(self, rng):
        y = rng.randint(0, 2, size=33)
        s = shard_rows(y)
        assert dmm.accuracy_score(y, s) == pytest.approx(1.0)
        assert dmm.accuracy_score(s, y) == pytest.approx(1.0)

    def test_sharded_weights_plain_y(self, rng):
        y = rng.randint(0, 2, size=33)
        p = rng.randint(0, 2, size=33)
        w = rng.uniform(size=33)
        import sklearn.metrics as sm
        assert dmm.accuracy_score(y, p, sample_weight=shard_rows(w)) == pytest.approx(
            sm.accuracy_score(y, p, sample_weight=w), abs=1e-6
        )

    def test_r2_constant_y(self):
        assert dmm.r2_score(np.ones(10), np.zeros(10)) == 0.0
        assert dmm.r2_score(np.ones(10), np.ones(10)) == 1.0

    def test_log_loss_unseen_label_raises(self, rng):
        proba = np.full((3, 2), 0.5)
        with pytest.raises(ValueError, match="not in `labels`"):
            dmm.log_loss(np.array([0, 1, 5]), proba, labels=[0, 1])

    def test_pairwise_sharded_output_unpadded(self, rng):
        X = rng.normal(size=(33, 4)).astype(np.float32)
        s = shard_rows(X)
        D = dmm.euclidean_distances(s)
        assert D.shape == (33, 33)
        K = dmm.rbf_kernel(s)
        assert K.shape == (33, 33)
        idx, dist = dmm.pairwise_distances_argmin_min(s, X[:5])
        assert idx.shape == (33,)


class TestRingPairwise:
    """Sharded x sharded pairwise via the ppermute ring (VERDICT round-1
    item 7; SURVEY.md §5: structurally ring attention's outer loop)."""

    def _xy(self, rng, n=101, m=53, d=5):
        X = rng.normal(size=(n, d)).astype(np.float32)
        Y = rng.normal(size=(m, d)).astype(np.float32)
        return X, Y

    def test_euclidean_ring_matches_replicated(self, rng, mesh):
        from dask_ml_tpu.metrics.pairwise import euclidean_distances

        X, Y = self._xy(rng)
        ring = np.asarray(euclidean_distances(shard_rows(X), shard_rows(Y)))
        rep = np.asarray(euclidean_distances(shard_rows(X), Y))
        assert ring.shape == (101, 53)
        np.testing.assert_allclose(ring, rep, rtol=1e-4, atol=1e-4)

    def test_sq_and_cosine_and_kernels(self, rng, mesh):
        from dask_ml_tpu.metrics.pairwise import (
            euclidean_distances,
            linear_kernel,
            pairwise_distances,
            polynomial_kernel,
            rbf_kernel,
        )

        X, Y = self._xy(rng, n=64, m=40)
        Xs, Ys = shard_rows(X), shard_rows(Y)
        for ring, rep in [
            (euclidean_distances(Xs, Ys, squared=True),
             euclidean_distances(Xs, Y, squared=True)),
            (pairwise_distances(Xs, Ys, metric="cosine"),
             pairwise_distances(Xs, Y, metric="cosine")),
            (rbf_kernel(Xs, Ys, gamma=0.7), rbf_kernel(Xs, Y, gamma=0.7)),
            (linear_kernel(Xs, Ys), linear_kernel(Xs, Y)),
            (polynomial_kernel(Xs, Ys, degree=2), polynomial_kernel(Xs, Y, degree=2)),
        ]:
            np.testing.assert_allclose(
                np.asarray(ring), np.asarray(rep), rtol=1e-4, atol=1e-4
            )

    def test_near_duplicate_rows_no_cancellation(self, rng, mesh):
        # Regression: the ‖x‖²+‖y‖²−2x·y expansion loses ~all precision
        # when rows are near-duplicates (true distance 1e-6 came out
        # 7e-4, r3 verdict weak #1).  The safe path must recompute those
        # entries with the exact (x−y)² form.
        from sklearn.metrics.pairwise import euclidean_distances as sk_euc
        from sklearn.metrics.pairwise import rbf_kernel as sk_rbf

        from dask_ml_tpu.metrics.pairwise import (
            euclidean_distances,
            rbf_kernel,
        )

        base = rng.normal(size=(37, 6)).astype(np.float32)
        X = base
        # Y rows are X rows nudged by ~1e-6 — deep in cancellation land
        Y = (base[:29] + 1e-6 * rng.normal(size=(29, 6))).astype(np.float32)
        ours = np.asarray(euclidean_distances(shard_rows(X), shard_rows(Y)))
        ref = sk_euc(X, Y)
        np.testing.assert_allclose(ours, ref, rtol=1e-3, atol=1e-5)
        # rbf with a sharp gamma: affinity between near-duplicates must
        # be ~1, not exp(-gamma * (cancellation noise))
        g = 1e6
        ours_k = np.asarray(rbf_kernel(shard_rows(X), shard_rows(Y), gamma=g))
        ref_k = sk_rbf(X.astype(np.float64), Y.astype(np.float64), gamma=g)
        np.testing.assert_allclose(ours_k, ref_k, atol=1e-3)
        # replicated (non-ring) paths too
        ours2 = np.asarray(euclidean_distances(shard_rows(X), Y))
        np.testing.assert_allclose(ours2, ref, rtol=1e-3, atol=1e-5)
        ours_k2 = np.asarray(rbf_kernel(shard_rows(X), Y, gamma=g))
        np.testing.assert_allclose(ours_k2, ref_k, atol=1e-3)
        # Y=None self path: diagonal exactly 0, off-diagonal still safe
        ours_self = np.asarray(euclidean_distances(shard_rows(X)))
        np.testing.assert_allclose(np.diag(ours_self), 0.0)
        np.testing.assert_allclose(ours_self, sk_euc(X, X),
                                   rtol=1e-3, atol=1e-5)
        # zero-row operand must trace and return an empty result
        empty = np.zeros((0, 6), dtype=np.float32)
        assert euclidean_distances(shard_rows(X), empty).shape == (37, 0)
        # X-vs-X self RING (same ShardedRows object twice): global
        # diagonal exactly 0 even though blocks meet off-device
        Xs = shard_rows(X)
        ours_ring = np.asarray(euclidean_distances(Xs, Xs))
        np.testing.assert_allclose(np.diag(ours_ring), 0.0)
        np.testing.assert_allclose(ours_ring, sk_euc(X, X),
                                   rtol=1e-3, atol=1e-5)
        k_ring = np.asarray(rbf_kernel(Xs, Xs, gamma=g))
        np.testing.assert_allclose(np.diag(k_ring), 1.0)
        np.testing.assert_allclose(
            k_ring, sk_rbf(X.astype(np.float64), X.astype(np.float64),
                           gamma=g), atol=1e-3)

    def test_ring_result_row_sharded(self, rng, mesh):
        from dask_ml_tpu.core.mesh import DATA_AXIS
        from dask_ml_tpu.metrics.pairwise import _ring_impl, _sq_euclidean
        from dask_ml_tpu.core.mesh import MeshHolder, get_mesh

        X, Y = self._xy(rng, n=64, m=32)
        Xs, Ys = shard_rows(X), shard_rows(Y)
        out = _ring_impl(
            Xs.data, Ys.data, mesh_holder=MeshHolder(get_mesh()),
            fn=_sq_euclidean,
        )
        # never replicated
        assert out.sharding.spec[0] == DATA_AXIS

    def test_uneven_rows(self, rng, mesh):
        # both operands need pad+mask handling (neither divisible by 8)
        from dask_ml_tpu.metrics.pairwise import euclidean_distances

        X, Y = self._xy(rng, n=13, m=11)
        ring = np.asarray(euclidean_distances(shard_rows(X), shard_rows(Y)))
        from sklearn.metrics.pairwise import euclidean_distances as sk_euc

        np.testing.assert_allclose(ring, sk_euc(X, Y), rtol=1e-4, atol=1e-4)


class TestManhattan:
    def test_matches_sklearn(self, rng, mesh):
        from sklearn.metrics import pairwise_distances as sk_pd

        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.metrics import pairwise_distances

        X = rng.normal(size=(101, 7)).astype(np.float32)
        Y = rng.normal(size=(23, 7)).astype(np.float32)
        for name in ("manhattan", "cityblock", "l1"):
            D = np.asarray(pairwise_distances(shard_rows(X), Y, metric=name))
            np.testing.assert_allclose(
                D, sk_pd(X, Y, metric="manhattan"), rtol=1e-4, atol=1e-4
            )

    def test_sharded_x_sharded_rides_ring(self, rng, mesh):
        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.metrics import pairwise_distances

        X = rng.normal(size=(64, 5)).astype(np.float32)
        Y = rng.normal(size=(40, 5)).astype(np.float32)
        D = np.asarray(pairwise_distances(shard_rows(X), shard_rows(Y),
                                          metric="manhattan"))
        ref = np.abs(X[:, None, :] - Y[None, :, :]).sum(-1)
        np.testing.assert_allclose(D, ref, rtol=1e-4, atol=1e-4)


class TestPrecisionRecallF1:
    def _data(self, rng, k=2):
        t = rng.randint(0, k, size=403)
        p = t.copy()
        flip = rng.rand(403) < 0.3
        p[flip] = rng.randint(0, k, size=flip.sum())
        return t, p

    @pytest.mark.parametrize("average", ["binary", "macro", "micro", "weighted"])
    def test_binary_parity(self, rng, mesh, average):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm
        from dask_ml_tpu.core import shard_rows

        t, p = self._data(rng, 2)
        for fn, name in ((dm.precision_score, "precision_score"),
                         (dm.recall_score, "recall_score"),
                         (dm.f1_score, "f1_score")):
            ours = fn(shard_rows(t.astype(np.float32)),
                      shard_rows(p.astype(np.float32)), average=average)
            theirs = getattr(skm, name)(t, p, average=average)
            assert ours == pytest.approx(theirs, abs=1e-6), (name, average)

    @pytest.mark.parametrize("average", ["macro", "micro", "weighted"])
    def test_multiclass_parity(self, rng, mesh, average):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t, p = self._data(rng, 4)
        assert dm.f1_score(t, p, average=average) == pytest.approx(
            skm.f1_score(t, p, average=average), abs=1e-6)
        assert dm.precision_score(t, p, average=average) == pytest.approx(
            skm.precision_score(t, p, average=average), abs=1e-6)

    def test_per_class_and_weights(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t, p = self._data(rng, 3)
        w = rng.rand(403)
        np.testing.assert_allclose(
            dm.recall_score(t, p, average=None, sample_weight=w),
            skm.recall_score(t, p, average=None, sample_weight=w),
            atol=1e-6,
        )

    def test_scorer_registry(self, rng, mesh):
        from dask_ml_tpu.metrics import get_scorer

        for name in ("f1", "f1_macro", "precision", "recall_macro"):
            assert callable(get_scorer(name))

    def test_binary_average_rejects_multiclass(self, rng, mesh):
        from dask_ml_tpu import metrics as dm

        t, p = self._data(rng, 3)
        with pytest.raises(ValueError, match="multiclass"):
            dm.f1_score(t, p)  # default average='binary'

    def test_absent_pos_label_scores_zero_with_warning(self, mesh):
        from sklearn.exceptions import UndefinedMetricWarning

        from dask_ml_tpu import metrics as dm

        with pytest.warns(UndefinedMetricWarning):
            assert dm.precision_score([0, 0, 0], [0, 0, 0]) == 0.0

    def test_labels_order_preserved(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t, p = self._data(rng, 3)
        order = [2, 0, 1]
        np.testing.assert_allclose(
            dm.recall_score(t, p, average=None, labels=order),
            skm.recall_score(t, p, average=None, labels=order),
            atol=1e-6,
        )


class TestRocAuc:
    def test_parity_with_sklearn(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm
        from dask_ml_tpu.core import shard_rows

        t = rng.randint(0, 2, size=501)
        s = rng.normal(size=501).astype(np.float32) + t  # informative
        ours = dm.roc_auc_score(shard_rows(t.astype(np.float32)),
                                shard_rows(s))
        assert ours == pytest.approx(skm.roc_auc_score(t, s), abs=1e-6)

    def test_ties_and_weights(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = rng.randint(0, 2, size=400)
        s = np.round(rng.normal(size=400) + t, 1)  # heavy ties
        w = rng.rand(400)
        assert dm.roc_auc_score(t, s, sample_weight=w) == pytest.approx(
            skm.roc_auc_score(t, s, sample_weight=w), abs=1e-6)

    def test_single_class_raises(self, mesh):
        from dask_ml_tpu import metrics as dm

        with pytest.raises(ValueError, match="2 classes"):
            dm.roc_auc_score([1, 1, 1], [0.1, 0.2, 0.3])

    def test_scorer_uses_decision_function(self, rng, mesh):
        from sklearn.linear_model import LogisticRegression as SKLR

        from dask_ml_tpu.metrics import get_scorer

        X = rng.normal(size=(200, 4)); y = (X[:, 0] > 0).astype(int)
        est = SKLR().fit(X, y)
        auc = get_scorer("roc_auc")(est, X, y)
        assert 0.9 < auc <= 1.0


class TestConfusionMatrix:
    def test_parity_with_sklearn(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm
        from dask_ml_tpu.core import shard_rows

        t = rng.randint(0, 4, size=333)
        p = rng.randint(0, 4, size=333)
        ours = dm.confusion_matrix(shard_rows(t.astype(np.float32)),
                                   shard_rows(p.astype(np.float32)))
        np.testing.assert_array_equal(ours, skm.confusion_matrix(t, p))
        assert ours.dtype == np.int64

    @pytest.mark.parametrize("normalize", ["true", "pred", "all"])
    def test_normalized(self, rng, mesh, normalize):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = rng.randint(0, 3, size=200)
        p = rng.randint(0, 3, size=200)
        np.testing.assert_allclose(
            dm.confusion_matrix(t, p, normalize=normalize),
            skm.confusion_matrix(t, p, normalize=normalize), atol=1e-6)

    def test_weighted_and_labels(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = rng.randint(0, 3, size=150)
        p = rng.randint(0, 3, size=150)
        w = rng.rand(150)
        np.testing.assert_allclose(
            dm.confusion_matrix(t, p, labels=[2, 1, 0], sample_weight=w),
            skm.confusion_matrix(t, p, labels=[2, 1, 0], sample_weight=w),
            atol=1e-5)

    def test_balanced_accuracy(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = rng.randint(0, 3, size=300)
        p = rng.randint(0, 3, size=300)
        assert dm.balanced_accuracy_score(t, p) == pytest.approx(
            skm.balanced_accuracy_score(t, p), abs=1e-6)

    def test_balanced_accuracy_predicted_only_class(self, mesh):
        """A class appearing only in y_pred must not drag the average
        (sklearn drops true-absent classes)."""
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = [0, 0, 1]
        p = [0, 0, 2]
        assert dm.balanced_accuracy_score(t, p) == pytest.approx(
            skm.balanced_accuracy_score(t, p))

    def test_balanced_accuracy_adjusted(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = rng.randint(0, 3, size=200)
        p = rng.randint(0, 3, size=200)
        assert dm.balanced_accuracy_score(t, p, adjusted=True) == pytest.approx(
            skm.balanced_accuracy_score(t, p, adjusted=True), abs=1e-6)

    def test_normalized_absent_class_zero_filled(self, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        ours = dm.confusion_matrix([0, 1], [0, 1], labels=[0, 1, 2],
                                   normalize="true")
        theirs = skm.confusion_matrix([0, 1], [0, 1], labels=[0, 1, 2],
                                      normalize="true")
        # sklearn zero-fills the absent class rows (nan_to_num)
        np.testing.assert_allclose(ours, theirs)


class TestExtraRegressionMetrics:
    def test_parity_with_sklearn(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm
        from dask_ml_tpu.core import shard_rows

        t = rng.normal(size=501).astype(np.float32) + 3.0
        p = t + 0.3 * rng.normal(size=501).astype(np.float32)
        w = rng.rand(501)
        st, sp = shard_rows(t), shard_rows(p)
        assert dm.mean_absolute_percentage_error(st, sp, sample_weight=w) == \
            pytest.approx(skm.mean_absolute_percentage_error(t, p, sample_weight=w), rel=1e-5)
        assert dm.median_absolute_error(st, sp) == pytest.approx(
            skm.median_absolute_error(t, p), rel=1e-5)
        assert dm.explained_variance_score(st, sp, sample_weight=w) == \
            pytest.approx(skm.explained_variance_score(t, p, sample_weight=w), rel=1e-4)

    def test_median_even_and_odd(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        for n in (10, 11):
            t = rng.normal(size=n).astype(np.float32)
            p = rng.normal(size=n).astype(np.float32)
            assert dm.median_absolute_error(t, p) == pytest.approx(
                skm.median_absolute_error(t, p), rel=1e-5)

    def test_constant_target_explained_variance(self, mesh):
        from dask_ml_tpu import metrics as dm

        assert dm.explained_variance_score([2.0, 2.0], [2.0, 2.0]) == 1.0
        assert dm.explained_variance_score([2.0, 2.0], [1.0, 3.0]) == 0.0

    def test_mape_zero_target_matches_sklearn(self, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = np.array([0.0, 1.0], np.float32)
        p = np.array([0.5, 1.0], np.float32)
        ours = dm.mean_absolute_percentage_error(t, p)
        theirs = skm.mean_absolute_percentage_error(t, p)
        assert ours == pytest.approx(theirs, rel=1e-4)

    def test_multioutput_uniform_average(self, rng, mesh):
        import sklearn.metrics as skm

        from dask_ml_tpu import metrics as dm

        t = rng.normal(size=(60, 3)).astype(np.float32) + 4.0
        p = t + 0.2 * rng.normal(size=(60, 3)).astype(np.float32)
        for name in ("mean_absolute_percentage_error",
                     "median_absolute_error", "explained_variance_score"):
            assert getattr(dm, name)(t, p) == pytest.approx(
                getattr(skm, name)(t, p), rel=1e-4), name


class TestAdvisorRound2Fixes:
    """Pins for the round-2 advisor findings (ADVICE.md)."""

    def test_roc_auc_multiblock_prefix_matches_sklearn(self, rng, mesh, monkeypatch):
        # shrink the two-level prefix-sum block so a small input spans
        # many blocks — exercises the f64 block-base assembly end to end
        import sklearn.metrics as skm

        from dask_ml_tpu.metrics import classification as cl

        monkeypatch.setattr(cl, "_AUC_BLOCK", 64)
        t = rng.randint(0, 2, size=1000)
        s = np.round(rng.normal(size=1000) + t, 1)  # heavy ties
        w = rng.rand(1000)
        got = cl.roc_auc_score(t, s, sample_weight=w)
        assert got == pytest.approx(
            skm.roc_auc_score(t, s, sample_weight=w), abs=1e-6)
        # unweighted too
        assert cl.roc_auc_score(t, s) == pytest.approx(
            skm.roc_auc_score(t, s), abs=1e-6)

    def test_explicit_labels_with_absent_pos_label_raises(self, mesh):
        from dask_ml_tpu import metrics as dm

        with pytest.raises(ValueError, match="not a valid label"):
            dm.precision_score([0, 0, 1], [0, 1, 1], labels=[0, 1],
                               pos_label=2)
