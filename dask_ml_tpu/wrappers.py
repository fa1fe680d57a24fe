"""Meta-estimators — twin of ``dask_ml/wrappers.py`` (``ParallelPostFit``,
``Incremental``; SURVEY.md §2 #26).

``ParallelPostFit``: fit an arbitrary estimator once (often on a sample),
then run inference over large data in row chunks.  With a device-native
(our) estimator the chunking is bypassed — inference is already one sharded
XLA program.  ``Incremental``: stream blocks through ``partial_fit``
(``_partial.fit`` chain in the reference).
"""

from __future__ import annotations

import numpy as np

from . import _partial
from .base import TPUEstimator, clone
from .core.sharded import ShardedRows, unshard
from .utils import copy_learned_attributes

_FIT_KWARG_ERR = "postfit_estimator has not been fit; call fit first"


class ParallelPostFit(TPUEstimator):
    def __init__(self, estimator=None, scoring=None, predict_meta=None,
                 predict_proba_meta=None, transform_meta=None):
        self.estimator = estimator
        self.scoring = scoring
        self.predict_meta = predict_meta
        self.predict_proba_meta = predict_proba_meta
        self.transform_meta = transform_meta

    # -- fitting ------------------------------------------------------
    def fit(self, X, y=None, **kwargs):
        est = clone(self.estimator)
        Xh = unshard(X) if isinstance(X, ShardedRows) else X
        yh = unshard(y) if isinstance(y, ShardedRows) else y
        est.fit(Xh, yh, **kwargs) if yh is not None else est.fit(Xh, **kwargs)
        self.estimator_ = est
        copy_learned_attributes(est, self)
        return self

    @property
    def _postfit_estimator(self):
        if hasattr(self, "estimator_"):
            return self.estimator_
        # pre-fitted estimator passed in (reference allows this)
        from sklearn.utils.validation import check_is_fitted

        check_is_fitted(self.estimator)
        return self.estimator

    # -- chunked inference --------------------------------------------
    def _apply(self, method, X, chunk_size=100_000):
        est = self._postfit_estimator
        fn = getattr(est, method)
        if isinstance(est, TPUEstimator) and isinstance(X, ShardedRows):
            # device-native estimator + sharded input: inference is already
            # one sharded XLA program — no host round-trip, no chunking
            return fn(X)
        if isinstance(X, ShardedRows):
            X = unshard(X)
        X = np.asarray(X)
        outs = [
            np.asarray(fn(X[lo:hi]))
            for lo, hi in _partial._row_chunks(X.shape[0], chunk_size)
        ]
        return np.concatenate(outs)

    # -- streaming inference --------------------------------------------
    def predict_blocks(self, X, method="predict", chunk_size=100_000):
        """Yield per-chunk inference results instead of concatenating
        them in host memory — the "inference over huge X" form of
        ParallelPostFit.  ``X`` may be an array, a ShardedRows, a
        sharded dataset (:mod:`dask_ml_tpu.data` — its parallel readers
        feed inference; target columns are dropped), or an ITERABLE of
        row blocks (e.g. ``io.stream_csv_blocks`` or a vectorizer's
        ``stream_transform``); each yielded block's result is
        the caller's to write out/reduce, so peak host memory is one
        chunk's worth regardless of the total row count.

        Reference: ``dask_ml/wrappers.py :: ParallelPostFit`` markets lazy
        blockwise inference via dask's ``map_blocks``; this is the
        generator twin for data that never exists as one array.
        """
        import scipy.sparse

        est = self._postfit_estimator
        fn = getattr(est, method)

        def _as_block(out):
            # sparse estimator outputs (e.g. a transformer) stay sparse:
            # np.asarray(csr) is a useless 0-d object array
            return out if scipy.sparse.issparse(out) else np.asarray(out)
        if hasattr(X, "iter_blocks"):  # sharded dataset: X columns only
            for xb in _partial._x_only(X.iter_blocks()):
                yield _as_block(fn(xb))
            return
        if isinstance(X, ShardedRows):
            if isinstance(est, TPUEstimator):
                # device-native: chunk the INPUT as device views so each
                # chunk's inference (and its host fetch, e.g. predict's
                # label gather) is chunk-sized — calling fn on the whole
                # X would materialize the full O(n) result before the
                # loop, the exact large-fetch hazard this method avoids
                for lo, hi in _partial._row_chunks(X.n_samples, chunk_size):
                    xb = ShardedRows(
                        data=X.data[lo:hi], mask=X.mask[lo:hi],
                        n_samples=hi - lo,
                    )
                    yield _as_block(fn(xb))
                return
            # host estimator: fetch INPUT rows chunkwise — never the
            # whole array at once (a one-piece unshard would break the
            # bounded-memory contract)
            for lo, hi in _partial._row_chunks(X.n_samples, chunk_size):
                yield _as_block(fn(np.asarray(X.data[lo:hi])))
            return
        if scipy.sparse.issparse(X):
            # sparse row slices stay sparse all the way into the
            # estimator (densifying a wide chunk defeats the purpose)
            for lo, hi in _partial._row_chunks(X.shape[0], chunk_size):
                yield _as_block(fn(X[lo:hi]))
            return
        if hasattr(X, "shape"):
            X = np.asarray(X)
            for lo, hi in _partial._row_chunks(X.shape[0], chunk_size):
                yield _as_block(fn(X[lo:hi]))
            return
        for block in X:  # iterable of row blocks, passed through AS-IS
            # (sparse blocks reach a sparse-capable estimator unchanged;
            # densify upstream for estimators that require dense)
            yield _as_block(fn(block))

    def predict(self, X):
        return self._apply("predict", X)

    def predict_proba(self, X):
        return self._apply("predict_proba", X)

    def predict_log_proba(self, X):
        return self._apply("predict_log_proba", X)

    def transform(self, X):
        return self._apply("transform", X)

    def score(self, X, y, compute=True):
        from .metrics.scorer import check_scoring

        scorer = check_scoring(self._postfit_estimator, self.scoring)
        if self.scoring:
            return scorer(self, X, y)
        Xh = unshard(X) if isinstance(X, ShardedRows) else X
        yh = unshard(y) if isinstance(y, ShardedRows) else y
        return self._postfit_estimator.score(Xh, yh)


class Incremental(ParallelPostFit):
    """Fit via sequential ``partial_fit`` over row chunks.

    Reference: ``wrappers.py :: Incremental`` (``shuffle_blocks``,
    ``random_state``, ``assume_equal_chunks``); the chain of
    ``dask_ml/_partial.py :: fit`` becomes a host stream into a resident
    model (SURVEY.md §3.5).
    """

    def __init__(self, estimator=None, scoring=None, shuffle_blocks=True,
                 random_state=None, assume_equal_chunks=True,
                 predict_meta=None, predict_proba_meta=None,
                 transform_meta=None, chunk_size=None, prefetch_depth=None):
        # chunk_size=None resolves (in _partial.fit, at use time — the
        # sklearn init contract forbids transforming params here) to the
        # shared device bucket size ``_sgd.DEFAULT_STREAM_CHUNK``: an
        # off-bucket chunk pads every block up to the bucket anyway —
        # wasted compute per partial_fit on the streaming path.
        # prefetch_depth=None likewise resolves at use time to the
        # DASK_ML_TPU_PREFETCH_DEPTH knob (pipeline.resolve_depth): the
        # next block's parse + H2D staging overlaps the current block's
        # device step; 0 keeps the strictly serial stream
        self.shuffle_blocks = shuffle_blocks
        self.random_state = random_state
        self.assume_equal_chunks = assume_equal_chunks
        self.chunk_size = chunk_size
        self.prefetch_depth = prefetch_depth
        super().__init__(
            estimator=estimator, scoring=scoring, predict_meta=predict_meta,
            predict_proba_meta=predict_proba_meta, transform_meta=transform_meta,
        )

    def _fit_for_estimator(self, estimator, X, y, **fit_kwargs):
        _partial.fit(
            estimator, X, y,
            chunk_size=self.chunk_size,
            shuffle_blocks=self.shuffle_blocks,
            random_state=self.random_state,
            prefetch_depth=self.prefetch_depth,
            **fit_kwargs,
        )
        self.estimator_ = estimator
        copy_learned_attributes(estimator, self)
        return self

    def fit(self, X, y=None, **fit_kwargs):
        return self._fit_for_estimator(clone(self.estimator), X, y, **fit_kwargs)

    def partial_fit(self, X, y=None, **fit_kwargs):
        """One more pass over (X, y) without re-initializing the model."""
        est = getattr(self, "estimator_", None) or clone(self.estimator)
        return self._fit_for_estimator(est, X, y, **fit_kwargs)
