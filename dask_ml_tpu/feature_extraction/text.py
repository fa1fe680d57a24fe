"""Text feature extraction over chunked host data.

Reference parity: ``dask_ml/feature_extraction/text.py ::
{HashingVectorizer, FeatureHasher, CountVectorizer}`` (unverified — mount
empty; SURVEY.md §2 #14).  The reference maps sklearn vectorizers over
``dask.bag``/``dask.dataframe`` partitions; stateless hashing is a single
``map_partitions``, and ``CountVectorizer`` does a two-pass distributed
vocabulary build then transform.

TPU-first design: tokenization and hashing are irreducibly host-side string
work — there is nothing for the MXU here, and sparse term matrices are
TPU-hostile (SURVEY.md §7 hard part (e)).  So this module keeps the compute
on host, parallelized over document chunks with a thread pool (sklearn's
vectorizers release the GIL in their C tokenization paths often enough for
this to scale), returns ``scipy.sparse`` for host pipelines, and provides
``densify_to_device`` to cross the host→HBM boundary as a dense, row-sharded
``ShardedRows`` ready for jitted estimators (TruncatedSVD, GLMs, KMeans).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse

import sklearn.feature_extraction.text
from sklearn.feature_extraction import FeatureHasher as _SkFeatureHasher

__all__ = [
    "HashingVectorizer",
    "FeatureHasher",
    "CountVectorizer",
    "densify_to_device",
]

# Documents per host-parallel chunk.  Small enough to load-balance across
# threads, large enough that sklearn's per-call setup cost is amortized.
_DEFAULT_CHUNK_SIZE = 10_000


def _check_docs(raw):
    """Reject a bare string (sklearn contract: iterable of documents)."""
    if isinstance(raw, str):
        raise ValueError(
            "Iterable over raw text documents expected, string object received."
        )
    return raw


def _chunks(seq, size):
    """Lazily batch an iterable of documents into lists of ``size``.

    The corpus is NEVER materialized whole: a
    generator of documents streams through with at most one chunk buffered
    here — the out-of-core path the reference gets from dask.bag.
    """
    import itertools

    it = iter(_check_docs(seq))
    while True:
        block = list(itertools.islice(it, size))
        if not block:
            return
        yield block


def _map_chunks(fn, chunked, n_threads=None, max_in_flight=None):
    """Apply ``fn`` to each chunk in parallel; returns results in order.

    Chunks are consumed lazily with a bounded in-flight window, so memory
    holds O(window) chunks of input (plus all outputs), not the corpus.
    """
    from collections import deque

    # graftlint: disable=thread-dispatch -- host-only work: fn is tokenize/hash over python strings (GIL-releasing C), no jax program is dispatched from these threads
    with ThreadPoolExecutor(max_workers=n_threads) as pool:
        window = max_in_flight or (pool._max_workers or 4) * 2
        out = []
        pending = deque()
        for chunk in chunked:
            pending.append(pool.submit(fn, chunk))
            if len(pending) >= window:
                out.append(pending.popleft().result())
        while pending:
            out.append(pending.popleft().result())
    return out


def densify_to_device(X, mesh=None, dtype=np.float32):
    """Densify a (sparse) term matrix and ingest it as ``ShardedRows``.

    The explicit host→device boundary for text pipelines: downstream jitted
    estimators want dense, row-sharded input.
    """
    from ..core.sharded import shard_rows

    if scipy.sparse.issparse(X):
        X = X.toarray()
    return shard_rows(np.asarray(X, dtype=dtype), mesh)


class _ChunkedStatelessMixin:
    """transform = embarrassingly parallel map over document chunks.

    Twin of the reference's single ``map_partitions`` call for stateless
    vectorizers (no fit state beyond constructor params).
    """

    chunk_size = _DEFAULT_CHUNK_SIZE

    def transform(self, raw_X):
        base = self._sk_transform
        parts = _map_chunks(base, _chunks(raw_X, self.chunk_size))
        if not parts:
            return scipy.sparse.csr_matrix((0, self.n_features), dtype=self.dtype)
        return scipy.sparse.vstack(parts).tocsr()

    def stream_transform(self, raw_X):
        """Yield one sparse block per document chunk, out-of-core: neither
        the corpus nor the full term matrix is ever materialized.  Feed
        each block (densified) to a device estimator's ``partial_fit`` —
        the streaming text→TPU pipeline (reference: dask.bag streaming)."""
        for chunk in _chunks(raw_X, self.chunk_size):
            yield self._sk_transform(chunk)

    def fit_transform(self, raw_X, y=None):
        self.fit(raw_X, y)
        return self.transform(raw_X)


class HashingVectorizer(_ChunkedStatelessMixin, sklearn.feature_extraction.text.HashingVectorizer):
    """Stateless hashing vectorizer over chunked documents.

    Same params and hash function as sklearn's, so outputs are bit-identical
    to sklearn on the same documents; only the execution is chunk-parallel.
    """

    def _sk_transform(self, docs):
        return sklearn.feature_extraction.text.HashingVectorizer.transform(self, docs)


class FeatureHasher(_ChunkedStatelessMixin, _SkFeatureHasher):
    """Stateless feature hasher over chunked dict/pair-iterable samples."""

    def _sk_transform(self, samples):
        return _SkFeatureHasher.transform(self, samples)


class CountVectorizer(sklearn.feature_extraction.text.CountVectorizer):
    """Two-pass distributed-vocabulary CountVectorizer.

    Pass 1 (fit): count per-chunk document/term frequencies in parallel and
    merge them into GLOBAL df/tf counters, then apply ``min_df`` /
    ``max_df`` / ``max_features`` to the merged counts — matching sklearn's
    corpus-global semantics (applying them per chunk would silently diverge:
    a term appearing once in each of two chunks has global df=2).  This is
    the reference's distributed vocabulary build over ``dask.bag``.
    Pass 2 (transform): with the vocabulary fixed, transforming chunks is
    stateless and parallel.
    """

    chunk_size = _DEFAULT_CHUNK_SIZE

    def fit(self, raw_documents, y=None):
        """Streams: a generator of documents is consumed in ONE pass
        (per-chunk counting + global merge) without materializing the
        corpus.  ``fit_transform`` needs two passes, so IT materializes
        one-shot iterators."""
        if self.vocabulary is not None:
            _check_docs(raw_documents)
            self.vocabulary_ = self._as_vocab_dict(self.vocabulary)
            self.fixed_vocabulary_ = True
            return self
        self._build_vocabulary(_check_docs(raw_documents))
        return self

    def fit_transform(self, raw_documents, y=None):
        docs = _check_docs(raw_documents)
        if self.vocabulary is not None:
            # fixed vocabulary: fit consumes nothing, ONE streaming pass
            self.fit(())
            return self.transform(docs)
        if not hasattr(docs, "__len__"):
            docs = list(docs)  # two passes needed; generators are one-shot
        self.fit(docs)
        return self.transform(docs)

    def _build_vocabulary(self, docs):
        # Per-chunk counting must NOT apply df limits — those are corpus-
        # global.  Strip them from the local vectorizer params.
        local_params = {
            **self._sk_params(),
            "min_df": 1,
            "max_df": 1.0,
            "max_features": None,
        }
        n_seen = {"docs": 0}

        def counted_chunks():
            for chunk in _chunks(docs, self.chunk_size):
                n_seen["docs"] += len(chunk)
                yield chunk

        def local_counts(chunk):
            vec = sklearn.feature_extraction.text.CountVectorizer(**local_params)
            try:
                counts = vec.fit_transform(chunk)
            except ValueError as e:
                # a chunk of only stop words / empty docs has no local
                # vocabulary and simply contributes nothing — but genuine
                # parameter errors must propagate
                if "empty vocabulary" in str(e):
                    return {}, {}
                raise
            terms = vec.get_feature_names_out()
            df = np.asarray((counts > 0).sum(axis=0)).ravel()
            tf = np.asarray(counts.sum(axis=0)).ravel()
            return dict(zip(terms, df)), dict(zip(terms, tf))

        results = _map_chunks(local_counts, counted_chunks())
        df_total: dict = {}
        tf_total: dict = {}
        for df_c, tf_c in results:
            for t, c in df_c.items():
                df_total[t] = df_total.get(t, 0) + int(c)
            for t, c in tf_c.items():
                tf_total[t] = tf_total.get(t, 0) + int(c)

        import numbers

        n_docs = n_seen["docs"]
        min_df = (
            self.min_df
            if isinstance(self.min_df, numbers.Integral)
            else self.min_df * n_docs
        )
        max_df = (
            self.max_df
            if isinstance(self.max_df, numbers.Integral)
            else self.max_df * n_docs
        )
        if max_df < min_df:
            raise ValueError("max_df corresponds to < documents than min_df")
        kept = sorted(t for t, c in df_total.items() if min_df <= c <= max_df)
        if self.max_features is not None and len(kept) > self.max_features:
            # Mirror sklearn's _limit_features exactly, including its
            # tie-breaking: argsort (unstable) over -tf in alphabetical
            # vocabulary order picks the same winners on tf ties.  kept is
            # already alphabetical; sorted(top) restores that order after
            # the top-k selection.
            tfs = np.array([tf_total[t] for t in kept])
            top = (-tfs).argsort()[: self.max_features]
            kept = [kept[i] for i in sorted(top)]
        if not kept:
            raise ValueError(
                "empty vocabulary; perhaps the documents only contain stop words"
            )
        self.vocabulary_ = {term: i for i, term in enumerate(kept)}
        self.fixed_vocabulary_ = False

    @staticmethod
    def _as_vocab_dict(vocabulary):
        if isinstance(vocabulary, dict):
            return dict(vocabulary)
        return {term: i for i, term in enumerate(vocabulary)}

    def _ensure_vocabulary(self):
        if not hasattr(self, "vocabulary_"):
            if self.vocabulary is not None:
                self.vocabulary_ = self._as_vocab_dict(self.vocabulary)
                self.fixed_vocabulary_ = True
            else:
                raise ValueError("CountVectorizer not fitted")

    def transform(self, raw_documents):
        self._ensure_vocabulary()
        params = {**self._sk_params(), "vocabulary": self.vocabulary_}

        def local_transform(chunk):
            vec = sklearn.feature_extraction.text.CountVectorizer(**params)
            return vec.transform(chunk)

        parts = _map_chunks(local_transform, _chunks(raw_documents, self.chunk_size))
        if not parts:
            return scipy.sparse.csr_matrix((0, len(self.vocabulary_)), dtype=self.dtype)
        return scipy.sparse.vstack(parts).tocsr()

    def stream_transform(self, raw_documents):
        """Yield one sparse block per document chunk (vocabulary fixed),
        out-of-core — see ``_ChunkedStatelessMixin.stream_transform``."""
        self._ensure_vocabulary()
        params = {**self._sk_params(), "vocabulary": self.vocabulary_}
        for chunk in _chunks(raw_documents, self.chunk_size):
            vec = sklearn.feature_extraction.text.CountVectorizer(**params)
            yield vec.transform(chunk)

    def _sk_params(self):
        """Constructor params understood by sklearn's CountVectorizer."""
        params = self.get_params(deep=False)
        valid = set(
            sklearn.feature_extraction.text.CountVectorizer()
            .get_params(deep=False)
            .keys()
        )
        return {k: v for k, v in params.items() if k in valid}
