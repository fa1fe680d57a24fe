"""Out-of-core text + streaming ingestion (VERDICT round-1 item 10): lazy
corpus chunking (no list(seq)), streaming vectorizer blocks, and the
end-to-end file -> vectorizer -> device-native SGD pipeline."""

import numpy as np
import pytest

import jax

from dask_ml_tpu import io as dio
from dask_ml_tpu.feature_extraction.text import (
    CountVectorizer,
    HashingVectorizer,
    densify_to_device,
)
from dask_ml_tpu.linear_model import SGDClassifier


class CountingIter:
    """A one-shot document iterator that records peak simultaneous
    materialization (would be len(corpus) if anything list()'d it)."""

    def __init__(self, docs):
        self._docs = list(docs)
        self.yielded = 0

    def __iter__(self):
        for d in self._docs:
            self.yielded += 1
            yield d


class TestLazyChunking:
    def test_chunks_is_lazy(self):
        from dask_ml_tpu.feature_extraction.text import _chunks

        def gen():
            for i in range(100):
                yield f"doc {i}"

        it = _chunks(gen(), 10)
        first = next(it)
        assert len(first) == 10  # only one chunk pulled so far

    def test_hashing_transform_accepts_generator(self):
        docs = [f"word{i % 7} common text" for i in range(500)]
        hv = HashingVectorizer(n_features=64)
        out_gen = hv.transform(iter(docs))
        out_list = hv.transform(docs)
        assert (out_gen != out_list).nnz == 0

    def test_count_fit_accepts_generator(self):
        docs = ["apple banana", "banana cherry", "apple apple"] * 50
        cv_gen = CountVectorizer().fit(iter(docs))
        cv_list = CountVectorizer().fit(docs)
        assert cv_gen.vocabulary_ == cv_list.vocabulary_

    def test_count_min_df_fraction_with_generator(self):
        # n_docs must be counted during the streaming pass
        docs = ["rare word"] + ["common text"] * 99
        cv = CountVectorizer(min_df=0.5).fit(iter(docs))
        assert set(cv.vocabulary_) == {"common", "text"}

    def test_stream_transform_blocks(self):
        docs = [f"tok{i % 5} filler" for i in range(250)]
        hv = HashingVectorizer(n_features=32)
        hv.chunk_size = 100
        blocks = list(hv.stream_transform(iter(docs)))
        assert [b.shape[0] for b in blocks] == [100, 100, 50]
        import scipy.sparse

        np.testing.assert_allclose(
            scipy.sparse.vstack(blocks).toarray(), hv.transform(docs).toarray()
        )

    def test_count_stream_transform(self):
        docs = ["apple banana", "banana cherry"] * 60
        cv = CountVectorizer().fit(docs)
        cv.chunk_size = 50
        blocks = list(cv.stream_transform(iter(docs)))
        import scipy.sparse

        np.testing.assert_allclose(
            scipy.sparse.vstack(blocks).toarray(), cv.transform(docs).toarray()
        )


class TestEndToEndStreaming:
    def test_text_file_to_device_sgd(self, tmp_path, rng, mesh):
        # file -> stream_text_lines -> HashingVectorizer.stream_transform
        # -> densify -> device-native SGD partial_fit: the full out-of-core
        # text pipeline, with labels derived per line
        n = 2000
        lines, labels = [], []
        for i in range(n):
            if rng.rand() > 0.5:
                lines.append("good great excellent fine product")
                labels.append(1)
            else:
                lines.append("bad awful poor terrible product")
                labels.append(0)
        p = tmp_path / "docs.txt"
        p.write_text("\n".join(lines) + "\n")
        labels = np.asarray(labels)

        hv = HashingVectorizer(n_features=128)
        clf = SGDClassifier(learning_rate="constant", eta0=0.5)
        offset = 0
        for _ in range(3):  # epochs over the stream
            offset = 0
            for block_lines in dio.stream_text_lines(str(p), block_lines=256):
                Xb = np.asarray(hv.transform(block_lines).todense(), np.float32)
                yb = labels[offset: offset + len(block_lines)]
                offset += len(block_lines)
                clf.partial_fit(Xb, yb, classes=[0, 1])
        assert offset == n
        X_all = np.asarray(hv.transform(lines).todense(), np.float32)
        assert (clf.predict(X_all) == labels).mean() > 0.99
        assert isinstance(clf._state["coef"], jax.Array)

    def test_csv_stream_to_sgd_regressor(self, tmp_path, rng, mesh):
        # numeric side: stream_csv_blocks -> device SGD partial_fit
        from dask_ml_tpu.linear_model import SGDRegressor

        n, d = 3000, 6
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32)
        y = X @ w
        p = tmp_path / "data.csv"
        np.savetxt(p, np.column_stack([X, y]), delimiter=",", fmt="%.6f")

        reg = SGDRegressor(learning_rate="constant", eta0=0.1)
        for _ in range(15):
            for block in dio.stream_csv_blocks(str(p), block_rows=512):
                reg.partial_fit(block[:, :d], block[:, d])
        assert reg.score(X, y) > 0.98

    def test_densify_to_device_sharded(self, rng, mesh):
        import scipy.sparse

        from dask_ml_tpu.core import ShardedRows

        S = scipy.sparse.random(37, 8, density=0.3, random_state=0, format="csr")
        out = densify_to_device(S)
        assert isinstance(out, ShardedRows)
        np.testing.assert_allclose(
            np.asarray(out.unpad()), S.toarray(), rtol=1e-6
        )


class TestReviewRegressions:
    def test_stream_transform_fixed_vocab_unfitted(self):
        cv = CountVectorizer(vocabulary={"apple": 0, "banana": 1})
        blocks = list(cv.stream_transform(["apple banana", "banana"]))
        assert blocks[0].shape == (2, 2)

    def test_fit_transform_fixed_vocab_streams(self):
        # one-shot generator + fixed vocabulary: single pass, no list()
        cv = CountVectorizer(vocabulary={"apple": 0, "banana": 1})
        out = cv.fit_transform(d for d in ["apple", "banana banana"])
        np.testing.assert_allclose(out.toarray(), [[1, 0], [0, 2]])

    def test_multinomial_is_implemented(self, rng):
        # round 2 warned-and-fell-back to OvR; round 3 implements the true
        # softmax family, so the fit must succeed with NO warning
        import warnings

        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(90, 3)).astype(np.float32)
        y = rng.randint(0, 3, size=90)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            lr = LogisticRegression(
                solver="lbfgs", max_iter=5, multi_class="multinomial"
            ).fit(X, y)
        assert lr.betas_.shape[0] == 3

    def test_dates_seed_does_not_alias_chunk_seed(self):
        from dask_ml_tpu.datasets import make_classification_df

        a, _ = make_classification_df(
            n_samples=60, n_features=5, chunks=30, random_state=3
        )
        b, _ = make_classification_df(
            n_samples=60, n_features=5, chunks=30, random_state=3,
            dates=("2024-01-01", "2024-02-01"),
        )
        # feature data identical whether or not dates are requested
        np.testing.assert_allclose(
            a.to_numpy(), b.drop(columns="date").to_numpy()
        )


class TestStreamedClustering:
    def test_file_to_device_minibatch_kmeans(self, tmp_path, rng):
        """Out-of-core clustering: CSV -> native prefetched blocks ->
        device-resident MiniBatchKMeans partial_fit (the reference's
        Incremental(sklearn.MiniBatchKMeans) streaming pattern, with the
        model on device instead of hopping hosts)."""
        from sklearn.datasets import make_blobs
        from sklearn.metrics import adjusted_rand_score

        from dask_ml_tpu.cluster import MiniBatchKMeans

        X, y = make_blobs(n_samples=3000, centers=4, n_features=6,
                          cluster_std=0.5, random_state=2)
        p = tmp_path / "blobs.csv"
        np.savetxt(p, X.astype(np.float32), delimiter=",", fmt="%.6f")

        mbk = MiniBatchKMeans(n_clusters=4, random_state=0)
        for block in dio.stream_csv_blocks(str(p), 512, prefetch=2):
            mbk.partial_fit(block)
        pred = np.asarray(mbk.predict(X.astype(np.float32)))
        assert adjusted_rand_score(y, pred) > 0.95


class TestStreamedBlocksFit:
    """SURVEY §7 hard-part (b): a stream larger than device memory fits
    through partial_fit with only one live block."""

    def test_stream_fit_accuracy_and_laziness(self, mesh):
        from dask_ml_tpu.datasets import stream_classification_blocks
        from dask_ml_tpu.linear_model import SGDClassifier

        gen = stream_classification_blocks(6, 4096, 8, seed=0)
        import types

        assert isinstance(gen, types.GeneratorType)  # lazy, block-at-a-time
        clf = SGDClassifier(random_state=0)
        total_rows = 0
        for Xb, yb in gen:
            clf.partial_fit(Xb, yb, classes=[0.0, 1.0])
            total_rows += Xb.n_samples
        assert total_rows == 6 * 4096
        # held-out generalization: block index 6 shares the stream's true
        # coefficient (same seed) but was never trained on (fold_in(key,6))
        Xt, yt = list(stream_classification_blocks(7, 4096, 8, seed=0))[-1]
        import numpy as np

        acc = (np.asarray(clf.predict(Xt))[:4096]
               == np.asarray(yt.data)).mean()
        assert acc > 0.8

    def test_blocks_differ_across_stream(self, mesh):
        from dask_ml_tpu.datasets import stream_classification_blocks
        import numpy as np

        b = list(stream_classification_blocks(2, 256, 4, seed=1))
        assert not np.allclose(
            np.asarray(b[0][0].data), np.asarray(b[1][0].data)
        )


class TestKitchenSinkPipeline:
    """The realistic dask-ml user journey end to end: pandas DataFrame →
    Categorizer → DummyEncoder → StandardScaler → LogisticRegression,
    searched with GridSearchCV — every stage a dask_ml_tpu component."""

    def test_dataframe_to_glm_grid_search(self, rng):
        import pandas as pd
        from sklearn.pipeline import Pipeline

        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.model_selection import GridSearchCV
        from dask_ml_tpu.preprocessing import (
            Categorizer,
            DummyEncoder,
            StandardScaler,
        )

        n = 400
        city = rng.choice(["nyc", "sf", "tok"], size=n)
        xnum = rng.normal(size=n).astype(np.float32)
        # signal: city=sf shifts the decision strongly
        logits = 2.0 * xnum + 3.0 * (city == "sf") - 1.0
        y = (logits + 0.3 * rng.normal(size=n) > 0).astype(int)
        df = pd.DataFrame({"city": city, "xnum": xnum})

        class ToFloat32:
            """pandas → float32 array at the device boundary."""

            def fit(self, X, y=None):
                return self

            def transform(self, X):
                return np.asarray(X, dtype=np.float32)

            def fit_transform(self, X, y=None):
                return self.transform(X)

            def get_params(self, deep=True):
                return {}

            def set_params(self, **kw):
                return self

        pipe = Pipeline([
            ("cat", Categorizer()),
            ("dum", DummyEncoder()),
            ("asf", ToFloat32()),
            ("sc", StandardScaler()),
            ("clf", LogisticRegression(max_iter=60)),
        ])
        gs = GridSearchCV(pipe, {"clf__C": [0.1, 1.0, 10.0]}, cv=3).fit(df, y)
        assert gs.best_score_ > 0.85
        pred = np.asarray(gs.predict(df))
        assert (pred == y).mean() > 0.85
