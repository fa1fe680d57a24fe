import numpy as np
import pytest
import sklearn.decomposition as sd

import dask_ml_tpu.decomposition as dd
from dask_ml_tpu.core import shard_rows, unshard
from dask_ml_tpu.core.sharded import ShardedRows
from dask_ml_tpu.linalg import randomized_svd, tsqr, tsqr_svd


@pytest.fixture
def X(rng):
    # tall-skinny with decaying spectrum
    base = rng.normal(size=(200, 10)).astype(np.float64)
    scale = np.linspace(3.0, 0.1, 10)
    return (base * scale).astype(np.float64)


class TestTSQR:
    def test_qr_reconstruction(self, X):
        s = shard_rows(X)
        q, r = tsqr(s)
        np.testing.assert_allclose(np.asarray(q @ r), unshard(s.data), atol=1e-3)

    def test_q_orthonormal(self, X):
        q, r = tsqr(shard_rows(X))
        qtq = np.asarray(q.T @ q)
        np.testing.assert_allclose(qtq, np.eye(X.shape[1]), atol=1e-3)

    def test_r_upper_triangular(self, X):
        _, r = tsqr(shard_rows(X))
        r = np.asarray(r)
        np.testing.assert_allclose(r, np.triu(r), atol=1e-5)

    def test_svd_singular_values_parity(self, X):
        _, s, _ = tsqr_svd(shard_rows(X))
        expected = np.linalg.svd(X, compute_uv=False)
        np.testing.assert_allclose(np.asarray(s), expected, rtol=1e-3)

    def test_too_wide_raises(self):
        with pytest.raises(ValueError, match="tall-skinny"):
            tsqr(shard_rows(np.ones((8, 10), dtype=np.float32)))

    def test_wide_rejected_despite_padding(self):
        # 9x10 pads to 16 rows on an 8-device mesh; the TRUE shape (9 < 10)
        # must still be rejected — padding must not mask rank deficiency
        with pytest.raises(ValueError, match="tall-skinny"):
            tsqr(shard_rows(np.ones((9, 10), dtype=np.float32)))

    def test_short_shards_ok(self, rng):
        # 16x10 over 8 shards: each shard is short (2 rows < 10 cols) but the
        # stacked R (16 rows) recovers full rank — must factor correctly.
        X = rng.normal(size=(16, 10)).astype(np.float64)
        q, r = tsqr(shard_rows(X))
        # slice padding: 16 divides an 8-device mesh but not e.g. 5
        qh = np.asarray(q)[:16]
        np.testing.assert_allclose(qh @ np.asarray(r), X, atol=1e-5)
        sv = np.linalg.svd(np.asarray(r), compute_uv=False)
        np.testing.assert_allclose(sv, np.linalg.svd(X, compute_uv=False), rtol=1e-5)

    def test_padding_zero_rows_safe(self, rng):
        # 37 rows over 8 shards -> 3 zero pad rows; R must match unpadded
        X = rng.normal(size=(370, 4)).astype(np.float64)
        s = shard_rows(X)
        _, r = tsqr(s)
        sv_padded = np.linalg.svd(np.asarray(r), compute_uv=False)
        sv_true = np.linalg.svd(X, compute_uv=False)
        np.testing.assert_allclose(sv_padded, sv_true, rtol=1e-4)


class TestCholQR2:
    """The CholeskyQR2 fast path (``strategy='cholqr2'``) and its guarded
    Householder fallback — linalg/tsqr.py docstring."""

    def test_parity_with_householder(self, X):
        Xf = X.astype(np.float32)
        q1, r1 = tsqr(shard_rows(Xf), strategy="cholqr2")
        q1 = np.asarray(q1)[: Xf.shape[0]].astype(np.float64)
        r1 = np.asarray(r1).astype(np.float64)
        np.testing.assert_allclose(q1.T @ q1, np.eye(10), atol=1e-4)
        np.testing.assert_allclose(q1 @ r1, Xf, atol=1e-4)
        np.testing.assert_allclose(r1, np.triu(r1), atol=1e-5)
        # Cholesky R has a positive diagonal by construction
        assert (np.diag(r1) > 0).all()
        # same factorization as Householder up to column signs
        _, r2 = tsqr(shard_rows(Xf), strategy="householder")
        r2 = np.asarray(r2).astype(np.float64)
        np.testing.assert_allclose(
            np.abs(r1), np.abs(r2), rtol=1e-3, atol=1e-4
        )

    def test_rank_deficient_falls_back(self, rng):
        # duplicate columns: the Gram Cholesky degenerates, the guard must
        # route to the Householder body and still return an orthonormal Q
        A = rng.normal(size=(400, 6)).astype(np.float32)
        Xd = np.concatenate([A, A[:, :3]], axis=1)
        q, r = tsqr(shard_rows(Xd), strategy="cholqr2")
        qh = np.asarray(q)[:400].astype(np.float64)
        np.testing.assert_allclose(qh.T @ qh, np.eye(9), atol=5e-4)
        np.testing.assert_allclose(
            qh @ np.asarray(r).astype(np.float64), Xd, atol=1e-4
        )

    def test_moderate_conditioning_holds_fast_path(self, rng):
        # cond ~ 3e2 in f32: inside CholeskyQR2's provable regime — the
        # result must be machine-orthonormal (if the fallback fired this
        # would also pass, so the A/B bench is what pins the perf claim;
        # this pins correctness at the regime boundary)
        U, _ = np.linalg.qr(rng.normal(size=(600, 12)))
        V, _ = np.linalg.qr(rng.normal(size=(12, 12)))
        s = np.logspace(0, -2.5, 12)
        Xc = ((U * s) @ V.T).astype(np.float32)
        q, _ = tsqr(shard_rows(Xc), strategy="cholqr2")
        qh = np.asarray(q)[:600].astype(np.float64)
        np.testing.assert_allclose(qh.T @ qh, np.eye(12), atol=5e-4)

    def test_env_knob(self, X, monkeypatch):
        from dask_ml_tpu.linalg.tsqr import tsqr_strategy

        monkeypatch.setenv("DASK_ML_TPU_TSQR", "cholqr2")
        assert tsqr_strategy() == "cholqr2"
        q, r = tsqr(shard_rows(X.astype(np.float32)))
        r = np.asarray(r)
        assert (np.diag(r) > 0).all()  # the cholqr2 signature
        monkeypatch.setenv("DASK_ML_TPU_TSQR", "bogus")
        with pytest.raises(ValueError, match="DASK_ML_TPU_TSQR"):
            tsqr_strategy()

    def test_pca_parity_under_cholqr2(self, rng, monkeypatch):
        monkeypatch.setenv("DASK_ML_TPU_TSQR", "cholqr2")
        X = rng.normal(size=(300, 8)).astype(np.float32) * np.linspace(
            2.0, 0.2, 8
        ).astype(np.float32)
        ours = dd.PCA(n_components=4, svd_solver="tsqr").fit(shard_rows(X))
        sk = sd.PCA(n_components=4, svd_solver="full").fit(X)
        np.testing.assert_allclose(
            ours.explained_variance_, sk.explained_variance_, rtol=1e-3
        )
        np.testing.assert_allclose(
            np.abs(np.asarray(ours.components_)),
            np.abs(sk.components_), atol=1e-3
        )


class TestRandomizedSVD:
    def test_topk_parity(self, X):
        u, s, vt = randomized_svd(shard_rows(X), 3, random_state=0)
        expected = np.linalg.svd(X, compute_uv=False)[:3]
        np.testing.assert_allclose(np.asarray(s), expected, rtol=1e-2)

    def test_low_rank_reconstruction(self, rng):
        # exactly rank-3 matrix is recovered to numerical precision
        A = rng.normal(size=(100, 3)) @ rng.normal(size=(3, 8))
        A = A.astype(np.float64)
        u, s, vt = randomized_svd(shard_rows(A), 3, random_state=0)
        approx = np.asarray(u * s @ vt)[:100]
        np.testing.assert_allclose(approx, A, atol=1e-3)


class TestPCA:
    def test_parity_full(self, X):
        ours = dd.PCA(n_components=4, svd_solver="full").fit(shard_rows(X))
        theirs = sd.PCA(n_components=4, svd_solver="full").fit(X)
        np.testing.assert_allclose(np.asarray(ours.mean_), theirs.mean_, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(ours.singular_values_), theirs.singular_values_, rtol=1e-3
        )
        np.testing.assert_allclose(
            np.abs(np.asarray(ours.components_)), np.abs(theirs.components_), atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(ours.explained_variance_ratio_),
            theirs.explained_variance_ratio_,
            rtol=1e-3,
        )

    def test_signs_deterministic_match_sklearn(self, X):
        ours = dd.PCA(n_components=3).fit(X)
        theirs = sd.PCA(n_components=3).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.components_), theirs.components_, atol=1e-3
        )

    def test_transform_parity(self, X):
        ours = dd.PCA(n_components=3).fit(X)
        theirs = sd.PCA(n_components=3).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.transform(X)), theirs.transform(X), atol=1e-3
        )

    def test_fit_transform_equals_transform(self, X):
        p = dd.PCA(n_components=3)
        ft = np.asarray(p.fit_transform(X))
        t = np.asarray(p.transform(X))
        np.testing.assert_allclose(ft, t, atol=1e-3)

    def test_randomized_solver(self, X):
        ours = dd.PCA(n_components=3, svd_solver="randomized", random_state=0).fit(X)
        theirs = sd.PCA(n_components=3).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.singular_values_), theirs.singular_values_, rtol=1e-2
        )

    def test_fraction_n_components(self, X):
        ours = dd.PCA(n_components=0.9, svd_solver="full").fit(X)
        theirs = sd.PCA(n_components=0.9, svd_solver="full").fit(X)
        assert ours.n_components_ == theirs.n_components_

    def test_inverse_transform_roundtrip(self, X):
        p = dd.PCA(n_components=10).fit(X)  # full rank
        np.testing.assert_allclose(
            np.asarray(p.inverse_transform(p.transform(X))), X, atol=1e-3
        )

    def test_wide_raises(self):
        with pytest.raises(ValueError, match="tall-skinny|n_samples"):
            dd.PCA(n_components=2).fit(np.ones((5, 50), dtype=np.float32))

    def test_whiten(self, X):
        ours = dd.PCA(n_components=3, whiten=True).fit(X)
        out = np.asarray(ours.transform(X))
        np.testing.assert_allclose(out.std(axis=0, ddof=1), np.ones(3), rtol=1e-2)


class TestTruncatedSVD:
    def test_parity_attrs(self, X):
        ours = dd.TruncatedSVD(n_components=3).fit(shard_rows(X))
        theirs = sd.TruncatedSVD(n_components=3, algorithm="arpack").fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.singular_values_), theirs.singular_values_, rtol=1e-3
        )
        np.testing.assert_allclose(
            np.abs(np.asarray(ours.components_)), np.abs(theirs.components_), atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(ours.explained_variance_), theirs.explained_variance_, rtol=1e-2
        )

    def test_fit_transform_sharded(self, X):
        s = shard_rows(X)
        out = dd.TruncatedSVD(n_components=3).fit_transform(s)
        assert isinstance(out, ShardedRows)
        assert unshard(out).shape == (200, 3)

    def test_transform_then_inverse(self, X):
        t = dd.TruncatedSVD(n_components=9).fit(X)
        recon = np.asarray(t.inverse_transform(t.transform(X)))
        assert np.linalg.norm(recon - X) / np.linalg.norm(X) < 0.1

    def test_bad_n_components(self, X):
        with pytest.raises(ValueError, match="n_components"):
            dd.TruncatedSVD(n_components=10).fit(X)  # == n_features

    def test_randomized(self, X):
        ours = dd.TruncatedSVD(n_components=3, algorithm="randomized", random_state=0).fit(X)
        theirs = sd.TruncatedSVD(n_components=3, algorithm="arpack").fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.singular_values_), theirs.singular_values_, rtol=1e-2
        )


class TestIncrementalPCA:
    def test_parity_with_sklearn(self, X):
        ours = dd.IncrementalPCA(n_components=3, batch_size=50).fit(X)
        theirs = sd.IncrementalPCA(n_components=3, batch_size=50).fit(X)
        np.testing.assert_allclose(
            np.asarray(ours.singular_values_), theirs.singular_values_, rtol=1e-2
        )
        np.testing.assert_allclose(np.asarray(ours.mean_), theirs.mean_, atol=1e-4)
        np.testing.assert_allclose(
            np.abs(np.asarray(ours.components_)), np.abs(theirs.components_), atol=5e-2
        )

    def test_partial_fit_accumulates(self, X):
        ipca = dd.IncrementalPCA(n_components=3)
        ipca.partial_fit(X[:100])
        ipca.partial_fit(X[100:])
        assert ipca.n_samples_seen_ == 200

    def test_small_batch_raises(self, X):
        ipca = dd.IncrementalPCA(n_components=5)
        with pytest.raises(ValueError, match="n_components"):
            ipca.partial_fit(X[:3])

    def test_transform_shape(self, X):
        ipca = dd.IncrementalPCA(n_components=3, batch_size=50).fit(X)
        assert np.asarray(ipca.transform(X)).shape == (200, 3)


class TestReviewRegressions:
    def test_tsvd_nonzero_padded_rows(self, rng):
        # sharded input whose pad rows are nonzero (e.g. from a scaler)
        import dask_ml_tpu.preprocessing as dp
        X = rng.normal(loc=5.0, size=(83, 6)).astype(np.float64)  # pads to 88
        s = shard_rows(X)
        scaled = dp.StandardScaler().fit(s).transform(s)  # pad rows = -mean/scale != 0
        ours = dd.TruncatedSVD(n_components=3).fit(scaled)
        X_scaled = (X - X.mean(0)) / X.std(0)
        expected = np.linalg.svd(X_scaled, compute_uv=False)[:3]
        np.testing.assert_allclose(
            np.asarray(ours.singular_values_), expected, rtol=1e-2
        )

    def test_tsvd_fit_transform_plain_in_plain_out(self):
        out = dd.TruncatedSVD(n_components=2).fit_transform(np.random.RandomState(0).normal(size=(37, 5)))
        assert not isinstance(out, ShardedRows)
        assert np.asarray(out).shape == (37, 2)

    def test_ipca_default_components_small_tail(self, rng):
        X = rng.normal(size=(105, 10)).astype(np.float32)
        ipca = dd.IncrementalPCA(batch_size=50).fit(X)  # tail of 5 rows must be dropped
        assert ipca.n_samples_seen_ == 100

    def test_ipca_noise_variance_finite(self, rng):
        X = rng.normal(size=(5, 10)).astype(np.float32)
        ipca = dd.IncrementalPCA().partial_fit(X)
        assert np.isfinite(float(ipca.noise_variance_))

    def test_pca_fraction_one(self, rng):
        X = rng.normal(size=(50, 6)).astype(np.float64)
        p = dd.PCA(n_components=1.0, svd_solver="full").fit(X)
        assert p.n_components_ == p.components_.shape[0] <= 6


class TestStreamedTruncatedSVD:
    """Sparse stream -> SVD without densifying the
    corpus; peak dense memory is O(n_features * sketch)."""

    def _sparse_blocks(self, rng, n=1200, d=300, block=100, density=0.05):
        import scipy.sparse

        rows = []
        for lo in range(0, n, block):
            b = min(block, n - lo)
            rows.append(scipy.sparse.random(
                b, d, density=density, random_state=lo + 1, dtype=np.float32,
                format="csr",
            ))
        return rows

    def test_parity_with_dense_fit(self, rng, mesh):
        # low-rank + noise: a separated spectrum is what sketching can
        # recover accurately (a flat random spectrum is adversarial for
        # ANY randomized method, dense or streamed)
        import scipy.sparse

        from dask_ml_tpu.decomposition import TruncatedSVD

        n, d, r = 1200, 300, 8
        latent = rng.normal(size=(n, r)) * np.linspace(10, 2, r)
        dense_np = (
            latent @ rng.normal(size=(r, d)) + 0.01 * rng.normal(size=(n, d))
        ).astype(np.float32)
        blocks = [
            scipy.sparse.csr_matrix(dense_np[lo: lo + 100])
            for lo in range(0, n, 100)
        ]
        dense = dense_np
        streamed = TruncatedSVD(
            n_components=5, n_iter=7, random_state=0
        ).fit_streamed(lambda: iter(blocks))
        ref = TruncatedSVD(
            n_components=5, algorithm="tsqr"
        ).fit(dense)
        np.testing.assert_allclose(
            np.asarray(streamed.singular_values_),
            np.asarray(ref.singular_values_), rtol=1e-2,
        )
        # subspace parity (signs already canonicalized on both paths)
        np.testing.assert_allclose(
            np.abs(np.asarray(streamed.components_)),
            np.abs(np.asarray(ref.components_)), atol=5e-2,
        )
        np.testing.assert_allclose(
            np.asarray(streamed.explained_variance_),
            np.asarray(ref.explained_variance_), rtol=5e-2,
        )

    def test_bounded_peak_memory(self, rng, mesh):
        import tracemalloc

        from dask_ml_tpu.decomposition import TruncatedSVD

        n, d = 4000, 2000
        blocks = self._sparse_blocks(rng, n=n, d=d, block=200, density=0.01)
        dense_bytes = n * d * 4
        tracemalloc.start()
        TruncatedSVD(n_components=8, n_iter=4, random_state=0).fit_streamed(
            lambda: iter(blocks)
        )
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # the whole fit must stay well under one dense corpus copy
        assert peak < dense_bytes / 2, (peak, dense_bytes)

    def test_text_pipeline_end_to_end(self, mesh):
        from dask_ml_tpu.decomposition import TruncatedSVD
        from dask_ml_tpu.feature_extraction.text import HashingVectorizer

        docs = [f"word{i % 7} token{i % 13} common text" for i in range(500)]
        vec = HashingVectorizer(n_features=4096)
        svd = TruncatedSVD(n_components=4, n_iter=4, random_state=0)
        svd.fit_streamed(
            lambda: vec.stream_transform(docs), n_features=4096
        )
        assert np.asarray(svd.components_).shape == (4, 4096)
        emb = svd.transform(vec.transform(docs[:50]))
        assert np.asarray(emb).shape == (50, 4)

    def test_empty_stream_raises(self, mesh):
        from dask_ml_tpu.decomposition import TruncatedSVD

        with pytest.raises(ValueError, match="empty"):
            TruncatedSVD(n_components=2).fit_streamed(lambda: iter([]))


class TestIPCADonation:
    """ISSUE-12 aliasing regression: the rank-update's five-tensor state
    chain is donated (in-place in HBM), the batch buffer is not."""

    def test_update_donates_state_chain_not_batch(self):
        import jax.numpy as jnp

        from dask_ml_tpu.decomposition.incremental_pca import _update

        rng = np.random.RandomState(2)
        k, d, n = 3, 8, 64
        comp = jnp.zeros((k, d), jnp.float32)
        sv = jnp.zeros((k,), jnp.float32)
        mean = jnp.zeros((d,), jnp.float32)
        var = jnp.zeros((d,), jnp.float32)
        n_seen = jnp.asarray(0, jnp.int32)
        batch = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        out = _update(comp, sv, mean, var, n_seen, batch, k=k)
        for name, arr in (("components", comp), ("singular_values", sv),
                          ("mean", mean), ("var", var),
                          ("n_seen", n_seen)):
            assert arr.is_deleted(), f"{name} must be consumed in place"
        assert not batch.is_deleted(), "batch is deliberately NOT donated"
        assert out[0].shape == (k, d)

    def test_partial_fit_chain_consistent_under_donation(self):
        rng = np.random.RandomState(4)
        X1 = rng.normal(size=(50, 8)).astype(np.float32)
        X2 = rng.normal(size=(50, 8)).astype(np.float32)
        a = dd.IncrementalPCA(n_components=3)
        a.partial_fit(X1)
        comp_after_1 = np.asarray(a.components_)
        a.partial_fit(X2)  # donation must not corrupt the chain
        b = dd.IncrementalPCA(n_components=3)
        b.partial_fit(X1)
        np.testing.assert_allclose(np.asarray(b.components_),
                                   comp_after_1, rtol=1e-5)
