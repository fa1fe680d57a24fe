"""TruncatedSVD — PCA without mean-centering.

Reference: ``dask_ml/decomposition/truncated_svd.py :: TruncatedSVD``
(``algorithm='tsqr'`` exact / ``'randomized'``; fitted attrs
``components_``, ``explained_variance_(ratio_)``, ``singular_values_``).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..base import ComponentsOutMixin, TPUEstimator, TransformerMixin
from ..core.sharded import ShardedRows, masked_var
from ..linalg import randomized_svd, tsqr_r
from ..preprocessing.data import _ingest_float, _like_input, _masked_or_plain
from .pca import _project
from ..utils import svd_flip


class TruncatedSVD(ComponentsOutMixin, TransformerMixin, TPUEstimator):
    def __init__(self, n_components=2, algorithm="tsqr", n_iter=5,
                 random_state=None, tol=0.0, compute=True):
        self.n_components = n_components
        self.algorithm = algorithm
        self.n_iter = n_iter
        self.random_state = random_state
        self.tol = tol
        self.compute = compute

    def fit(self, X, y=None):
        self.fit_transform(X)
        return self

    def fit_transform(self, X, y=None):
        X_in = X
        X = _ingest_float(self, X)
        d = X.data.shape[1]
        k = self.n_components
        if not 0 < k < d:
            raise ValueError(
                f"n_components must be in (0, n_features={d}); got {k}"
            )
        if self.algorithm in ("tsqr", "full"):
            # R alone (no Q, no zeroed copy of the table: the mask is
            # applied inside the passes), then one product for the scores
            r, _, _ = tsqr_r(X)
            _, s, vt = jnp.linalg.svd(r, full_matrices=False)
            _, vt = svd_flip(None, vt, u_based_decision=False)
            s, vt = s[:k], vt[:k]
            transformed = _project(
                X.data, X.mask, jnp.zeros((d,), X.data.dtype), vt,
                jnp.ones((), X.data.dtype), k=k)
        elif self.algorithm == "randomized":
            # Zero the padded rows: there is no centering step to do it,
            # and sharded inputs from upstream transforms (e.g. a scaler)
            # carry nonzero pad rows.
            u, s, vt = randomized_svd(
                X.data * X.mask[:, None], k, n_iter=self.n_iter,
                random_state=self.random_state,
            )
            u, vt = svd_flip(u, vt, u_based_decision=False)
            transformed = u * s
        else:
            raise ValueError(f"Unknown algorithm: {self.algorithm!r}")

        n = X.n_samples
        self.components_ = vt
        exp_var = masked_var(transformed, X.mask)
        full_var = jnp.sum(masked_var(X.data, X.mask))
        self.explained_variance_ = exp_var
        self.explained_variance_ratio_ = exp_var / full_var
        self.singular_values_ = s
        self.n_features_in_ = d
        if isinstance(X_in, ShardedRows):
            return ShardedRows(data=transformed, mask=X.mask, n_samples=n)
        return transformed[:n]

    def transform(self, X):
        import scipy.sparse

        if scipy.sparse.issparse(X):
            # sparse projection on host: n×d stays sparse, only the n×k
            # result densifies (the reference consumes sparse natively in
            # ``dask_ml/decomposition/truncated_svd.py``)
            import numpy as np

            return np.asarray(X @ np.asarray(self.components_).T)
        x, _ = _masked_or_plain(X)
        return _like_input(X, x @ self.components_.T)

    def inverse_transform(self, X):
        x, _ = _masked_or_plain(X)
        return _like_input(X, x @ self.components_)

    def fit_streamed(self, blocks, n_features=None):
        """Fit from a RE-ITERABLE stream of sparse/dense row blocks without
        ever materializing the dense corpus.

        ``blocks`` is a zero-argument callable returning a fresh iterator
        of row blocks (scipy.sparse or ndarray, each ``(b, n_features)``)
        — e.g. ``lambda: vectorizer.stream_transform(corpus)``.  The
        randomized range finder runs ``n_iter`` passes of ``A^T A`` over
        the stream (each block contributes ``B^T (B Q)``; blocks stay
        sparse, so peak dense memory is ``O(n_features x sketch)``, never
        ``O(n_rows x n_features)``), then one final pass accumulates the
        small ``(AQ)^T AQ`` Gram whose eigendecomposition yields the
        components, singular values, and explained variance — no pass
        stores anything n_rows-sized.

        Reference: ``dask_ml/decomposition/truncated_svd.py`` fits lazy
        sparse dask arrays; this is the streaming twin for corpora that
        never exist as one array.
        """
        import numpy as np
        import scipy.sparse

        k = self.n_components
        oversample = 10
        first_iter = None
        if n_features is None:
            # peek one block for the width; the partially-consumed
            # iterator (first block re-chained) serves as pass 0's source
            # so the peeked block's work is not thrown away
            import itertools

            it = iter(blocks())
            first = next(it, None)
            if first is None:
                raise ValueError("empty block stream")
            n_features = first.shape[1]
            first_iter = itertools.chain([first], it)
        d = int(n_features)
        if not 0 < k < d:
            raise ValueError(
                f"n_components must be in (0, n_features={d}); got {k}"
            )
        ell = min(k + oversample, d)
        from ..utils import check_random_state

        rng = check_random_state(self.random_state)
        Q = rng.normal(size=(d, ell)).astype(np.float32)

        def _mm(B, C):
            out = B @ C  # scipy sparse @ dense -> dense; ndarray works too
            return np.asarray(out, dtype=np.float64)

        def _dense64(a):
            """Densify one HOST accumulator term to float64.

            This whole range-finder pass is a host-only path: ``B``
            blocks are numpy/scipy matrices from the caller's iterator
            and the densifications here never touch a device value —
            formerly four per-call host-sync-loop suppressions, now a
            named host tail the rule can see past, with the hostness
            runtime-verified by the sanitizer (tests/test_sanitize.py
            streams this fit under an armed transfer guard: zero
            device crossings, zero device dispatches)."""
            return np.asarray(a, dtype=np.float64)

        n_rows = 0
        col_sum = np.zeros(d, np.float64)
        col_sumsq = np.zeros(d, np.float64)
        passes = max(int(self.n_iter), 1)
        for p in range(passes):
            H = np.zeros((d, ell), np.float64)
            src = first_iter if (p == 0 and first_iter is not None) \
                else blocks()
            first_iter = None
            for B in src:
                Y = _mm(B, Q)
                H += _dense64(B.T @ Y)
                if p == 0:
                    n_rows += B.shape[0]
                    if scipy.sparse.issparse(B):
                        col_sum += _dense64(B.sum(axis=0)).ravel()
                        col_sumsq += _dense64(
                            B.multiply(B).sum(axis=0)
                        ).ravel()
                    else:
                        Bd = _dense64(B)
                        col_sum += Bd.sum(axis=0)
                        col_sumsq += (Bd * Bd).sum(axis=0)
            # re-orthonormalize between passes (the stability trick behind
            # power_iteration_normalizer='QR')
            Q, _ = np.linalg.qr(H)
            Q = Q.astype(np.float32)
        if n_rows < 1:
            raise ValueError("empty block stream")

        # final pass: the l x l Gram of AQ plus its column means
        M = np.zeros((ell, ell), np.float64)
        w_sum = np.zeros(ell, np.float64)
        for B in blocks():
            W = _mm(B, Q)
            M += W.T @ W
            w_sum += W.sum(axis=0)
        evals, G = np.linalg.eigh(M)  # ascending
        order = np.argsort(evals)[::-1][:k]
        s = np.sqrt(np.maximum(evals[order], 0.0))
        V = (Q @ G[:, order]).T  # (k, d) right singular vectors
        # deterministic signs, same convention as the dense path
        # (svd_flip u_based_decision=False: sign of each row's max-|.|)
        max_abs = np.argmax(np.abs(V), axis=1)
        signs = np.sign(V[np.arange(V.shape[0]), max_abs])
        signs[signs == 0] = 1.0
        V = V * signs[:, None]

        mean_t = (G[:, order].T @ (w_sum / n_rows)) * signs
        exp_var = np.maximum(s**2 / n_rows - mean_t**2, 0.0)
        full_var = float(
            np.sum(col_sumsq / n_rows - (col_sum / n_rows) ** 2)
        )
        self.components_ = jnp.asarray(V.astype(np.float32))
        self.singular_values_ = jnp.asarray(s.astype(np.float32))
        self.explained_variance_ = jnp.asarray(exp_var.astype(np.float32))
        self.explained_variance_ratio_ = jnp.asarray(
            (exp_var / max(full_var, 1e-30)).astype(np.float32)
        )
        self.n_features_in_ = d
        return self
