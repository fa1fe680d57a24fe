"""Device-op policy tests: k-means precision modes and the scatter
strategy (one-hot gemm vs segment_sum).

The Pallas Lloyd kernel these tests originally covered was deleted after
its win-or-delete chip adjudication (XLA won every variant — see
docs/design.md "Pallas negative result" and cluster/k_means.py).
"""

import numpy as np
import pytest
import jax.numpy as jnp


class TestKMeansPrecision:
    def test_kmeans_fast_env_matches_highest(self, rng, monkeypatch, mesh):
        # end-to-end: DASK_ML_TPU_KMEANS_PRECISION=fast must converge to
        # the same clustering as highest on well-separated blobs
        from dask_ml_tpu.cluster import KMeans
        from dask_ml_tpu.core import shard_rows

        centers_true = np.array(
            [[0, 0, 0], [8, 8, 8], [-8, 8, -8]], dtype=np.float32)
        X = np.concatenate([
            c + rng.normal(scale=0.5, size=(120, 3)).astype(np.float32)
            for c in centers_true
        ])
        sX = shard_rows(X)
        km_hi = KMeans(n_clusters=3, init="random", random_state=0,
                       max_iter=30).fit(sX)
        monkeypatch.setenv("DASK_ML_TPU_KMEANS_PRECISION", "fast")
        km_fast = KMeans(n_clusters=3, init="random", random_state=0,
                         max_iter=30).fit(sX)
        np.testing.assert_allclose(
            np.sort(np.asarray(km_fast.cluster_centers_), axis=0),
            np.sort(np.asarray(km_hi.cluster_centers_), axis=0),
            rtol=1e-3, atol=1e-3)
        assert km_fast.inertia_ == pytest.approx(km_hi.inertia_, rel=1e-3)


class TestScatterPolicy:
    """ops.scatter: one policy for segment_sum vs one-hot gemm, shared by
    the quantile sketch and the k-means reduce (r3 verdict #5b)."""

    def _agree(self, values, ids, k):
        from dask_ml_tpu.ops import bucket_sum

        outs = {strat: np.asarray(bucket_sum(
            jnp.asarray(values), jnp.asarray(ids), k, strategy=strat))
            for strat in ("segsum", "onehot")}
        np.testing.assert_allclose(outs["segsum"], outs["onehot"],
                                   rtol=1e-5, atol=1e-5)
        return outs["segsum"]

    def test_strategies_agree_1d(self, rng):
        ids = rng.randint(0, 17, size=400).astype(np.int32)
        vals = rng.normal(size=400).astype(np.float32)
        got = self._agree(vals, ids, 17)
        want = np.zeros(17, np.float32)
        np.add.at(want, ids, vals)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_strategies_agree_2d_weighted(self, rng):
        ids = rng.randint(0, 9, size=300).astype(np.int32)
        w = rng.uniform(0.1, 2.0, size=300).astype(np.float32)
        x = rng.normal(size=(300, 4)).astype(np.float32)
        got = self._agree(x * w[:, None], ids, 9)
        want = np.zeros((9, 4), np.float32)
        np.add.at(want, ids, x * w[:, None])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_large_segment_count_forces_segsum(self):
        from dask_ml_tpu.ops import scatter_strategy

        assert scatter_strategy(4096) == "segsum"  # one-hot would be
        # memory-quadratic at sketch bin counts
        assert scatter_strategy(1025) == "segsum"
        assert scatter_strategy(1024) == "onehot"
        assert scatter_strategy(64) == "onehot"
        # a histogram past the guard takes the two-level one-hot
        assert scatter_strategy(4096, histogram=True) == "onehot2"
        assert scatter_strategy(64, histogram=True) == "onehot"

    def test_sharding_mismatch_raises(self, rng):
        # the error path: a row-sharded/PADDED values zipped with an
        # unpadded ids (the shard_rows pad divergence) must fail loudly at
        # trace time, not misalign rows to buckets
        from dask_ml_tpu.ops import bucket_sum

        vals = jnp.asarray(rng.normal(size=(48, 3)).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, 4, size=40).astype(np.int32))
        with pytest.raises(ValueError, match="padded/sharded"):
            bucket_sum(vals, ids, 4)

    def test_sharded_padded_inputs_align(self, rng, mesh):
        # positive twin of the mismatch case: when values AND ids ride the
        # same padded row sharding, the scatter sums match the host oracle
        # (pad rows neutralized by zero pre-weighting, as consumers do)
        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.ops import bucket_sum

        n, k = 37, 5  # deliberately not divisible by the 8-device mesh
        x = rng.normal(size=(n, 3)).astype(np.float32)
        ids = rng.randint(0, k, size=n).astype(np.int32)
        sx = shard_rows(x)
        sids = shard_rows(ids)
        w = np.asarray(shard_rows(np.ones(n, np.float32)).mask)[
            : sx.data.shape[0]]
        got = np.asarray(bucket_sum(
            sx.data * jnp.asarray(w)[:, None], sids.data, k))
        want = np.zeros((k, 3), np.float32)
        np.add.at(want, ids, x)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_bad_rank_rejected(self, rng):
        from dask_ml_tpu.ops import bucket_sum

        ids = jnp.asarray(rng.randint(0, 4, size=8).astype(np.int32))
        with pytest.raises(ValueError, match="1-d or 2-d"):
            bucket_sum(jnp.zeros((8, 2, 2)), ids, 4)
        with pytest.raises(ValueError, match="ids must be 1-d"):
            bucket_sum(jnp.zeros((8,)), jnp.zeros((8, 2), jnp.int32), 4)

    def test_bad_explicit_strategy_rejected(self, rng):
        from dask_ml_tpu.ops import bucket_sum

        ids = jnp.asarray(rng.randint(0, 4, size=8).astype(np.int32))
        with pytest.raises(ValueError, match="strategy"):
            bucket_sum(jnp.zeros((8,)), ids, 4, strategy="matmulish")
        # ...and the typo must surface even when the large-segment OOM
        # guard would have overridden the strategy anyway
        big_ids = jnp.asarray(rng.randint(0, 2000, size=8).astype(np.int32))
        with pytest.raises(ValueError, match="strategy"):
            bucket_sum(jnp.zeros((8,)), big_ids, 2000, strategy="matmulish")

    def test_explicit_strategy_pass_through(self, rng):
        # callers inside jit resolve the strategy OUTSIDE the trace and
        # pass it through; both explicit forms must agree with the oracle
        from dask_ml_tpu.ops import bucket_sum

        vals = rng.normal(size=16).astype(np.float32)
        ids = rng.randint(0, 4, size=16).astype(np.int32)
        want = np.zeros(4, np.float32)
        np.add.at(want, ids, vals)
        for strat in ("segsum", "onehot"):
            got = np.asarray(bucket_sum(
                jnp.asarray(vals), jnp.asarray(ids), 4, strategy=strat))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_explicit_onehot_overridden_above_segment_cap(self, rng):
        # the OOM guard binds even for an explicit strategy argument:
        # 4096 one-hot columns is memory-quadratic everywhere
        from dask_ml_tpu.ops import bucket_sum

        vals = rng.normal(size=32).astype(np.float32)
        ids = rng.randint(0, 4096, size=32).astype(np.int32)
        want = np.zeros(4096, np.float32)
        np.add.at(want, ids, vals)
        got = np.asarray(bucket_sum(
            jnp.asarray(vals), jnp.asarray(ids), 4096, strategy="onehot"))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_kmeans_equal_under_both_strategies(self, rng, mesh):
        # the weighted fit on one-hot sums against the plain Lloyd
        # reference (benchmarks/references/kmeans_lloyd.py's arithmetic,
        # here in numpy and float64): both from the blobs' own centres,
        # each row to its nearest centre, each centre to its rows'
        # weighted mean, until no centre moves
        from dask_ml_tpu.cluster import KMeans
        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.ops import scatter_strategy

        truth = np.array([[0, 0, 0], [6, 6, 6], [-6, 6, -6]], np.float64)
        X = np.concatenate([
            c + rng.normal(scale=0.4, size=(100, 3)).astype(np.float32)
            for c in truth
        ]).astype(np.float32)
        w = rng.uniform(0.5, 1.5, size=300).astype(np.float32)
        assert scatter_strategy(3) == "onehot"
        km = KMeans(n_clusters=3, init=truth.astype(np.float32),
                    max_iter=20).fit(shard_rows(X), sample_weight=w)
        centres = truth
        for _ in range(100):
            near = ((X[:, None, :] - centres[None]) ** 2).sum(-1).argmin(1)
            moved = np.stack([np.average(X[near == j], axis=0,
                                         weights=w[near == j])
                              for j in range(3)])
            if np.array_equal(moved.astype(np.float32),
                              centres.astype(np.float32)):
                break
            centres = moved
        np.testing.assert_allclose(
            np.sort(np.asarray(km.cluster_centers_), axis=0),
            np.sort(centres, axis=0), rtol=1e-4, atol=1e-4)
