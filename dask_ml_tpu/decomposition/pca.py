"""PCA for tall-skinny row-sharded matrices.

Reference: ``dask_ml/decomposition/pca.py :: PCA`` — requires a single
column block (tall-skinny), ``svd_solver ∈ {auto, full, tsqr, randomized}``,
fitted attrs ``components_``, ``explained_variance_(ratio_)``,
``singular_values_``, ``mean_``, ``noise_variance_`` (SURVEY.md §3.4).

TPU design: the exact solvers (``full``, ``tsqr``) fit from the (d, d)
``R`` of the centred table alone (``linalg/tsqr.py :: tsqr_r``: the mean,
the Gram and the CholeskyQR2 repair as three reads of the table that keep
(d, d) state; no ``Q``, no centred copy), then one small program for the
SVD of ``R``, the sign flip and every fitted statistic.  A fit is two
programs and one wait.  ``randomized`` centres a copy and runs Halko.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs as _obs
from .. import programs as _programs
from ..base import ComponentsOutMixin, TPUEstimator, TransformerMixin
from ..core.sharded import ShardedRows, masked_mean
from ..linalg import randomized_svd
from ..linalg.tsqr import factor_r, householder_r
from ..preprocessing.data import _ingest_float, _like_input, _masked_or_plain
from ..utils import svd_flip


def _spectrum_fn(r, n, *, k):
    """From the (d, d) ``R`` of the centred table to the fitted arrays of
    an exact fit keeping ``k`` components: ``(components, explained
    variance, its ratio, singular values, noise variance)``."""
    with jax.named_scope("pca.svd"):
        _, s, vt = jnp.linalg.svd(r, full_matrices=False)
    # sklearn >= 1.5 flips on V (deterministic whatever the row order or
    # the padding); match it so components_ agree elementwise
    _, vt = svd_flip(None, vt, u_based_decision=False)
    explained = (s ** 2) / (n - 1)
    total = jnp.sum(explained)
    rank = min(r.shape)
    noise = ((total - jnp.sum(explained[:k])) / (rank - k) if k < rank
             else jnp.zeros((), s.dtype))
    return vt[:k], explained[:k], explained[:k] / total, s[:k], noise


# graftlint: disable=donation-miss -- (d, d) in, (k, d) and vectors out; R is the caller's
_spectrum = _programs.cached_program(
    _spectrum_fn, name="pca.spectrum", static_argnames=("k",))


@partial(jax.jit, static_argnames=("k",))
def _project(x, mask, mean, components, scale, *, k):
    """``(x - mean) @ components[:k].T * scale``, the pad rows zero: one
    program, so the centred rows are an operand of the product and never
    a table in memory."""
    out = jnp.matmul(x - mean, components[:k].T,
                     precision=jax.lax.Precision.HIGHEST)
    return out * scale * mask[:, None].astype(out.dtype)


class PCA(ComponentsOutMixin, TransformerMixin, TPUEstimator):
    def __init__(self, n_components=None, copy=True, whiten=False,
                 svd_solver="auto", tol=0.0, iterated_power=4, random_state=None):
        self.n_components = n_components
        self.copy = copy
        self.whiten = whiten
        self.svd_solver = svd_solver
        self.tol = tol
        self.iterated_power = iterated_power
        self.random_state = random_state

    # -- solver selection (mirrors reference `_fit` policy) ------------
    def _resolve(self, n_samples, n_features):
        n_components = self.n_components
        if n_components is None:
            n_components = min(n_samples, n_features)
        solver = self.svd_solver
        if solver == "auto":
            if isinstance(n_components, float):
                solver = "full"
            elif n_components < 0.8 * min(n_samples, n_features) and n_features > 50:
                solver = "randomized"
            else:
                solver = "full"
        if solver == "tsqr":
            solver = "full"
        return n_components, solver

    def _center(self, X: ShardedRows):
        mean = masked_mean(X.data, X.mask)
        centered = (X.data - mean) * X.mask[:, None]
        return centered, mean

    def fit(self, X, y=None):
        self._fit(X)
        return self

    def _fit(self, X):
        # the fit's spans (live under ``obs.enable()`` or a profiler
        # session): ``pca.fit`` is the root, ``pca.factor`` and
        # ``pca.spectrum`` its children
        with _obs.span("pca.fit", solver=self.svd_solver) as root:
            return self._fit_spanned(X, root)

    def _fit_spanned(self, X, root):
        X = _ingest_float(self, X)
        n, d = X.n_samples, X.data.shape[1]
        if n < d:
            raise ValueError(
                f"n_samples ({n}) must be >= n_features ({d}) for tall-skinny PCA"
            )
        n_components, solver = self._resolve(n, d)
        if isinstance(n_components, float):
            if not 0 < n_components <= 1.0:
                raise ValueError(f"Invalid n_components: {n_components}")
            k_request = d
        else:
            if n_components > d:
                raise ValueError(
                    f"n_components={n_components} must be <= n_features={d}"
                )
            k_request = n_components

        root.set(rows=n, features=d, chips=len(X.data.sharding.device_set))
        if solver == "randomized":
            return self._fit_randomized(X, n_components, k_request)

        k = d if isinstance(n_components, float) else n_components
        n_f = np.asarray(n, X.data.dtype)  # goes with the dispatch
        with _obs.span("pca.factor") as span:
            r, mean, info = factor_r(X, center="mean")
            # queued behind the factorization before anything is waited
            # for: the chip goes on while the host wakes up
            fitted = _spectrum(r, n_f, k=k)
            passes, held = (int(v) for v in np.asarray(info))  # the wait
            if not held:  # the guard's verdict: the backward-stable arm
                r, fitted = householder_r(X, mean), None
                passes += 1
            span.set(passes=passes, fallback=int(not held))
        with _obs.span("pca.spectrum"):
            if fitted is None:
                fitted = _spectrum(r, n_f, k=k)
            if isinstance(n_components, float):
                # the least k whose ratios reach the fraction: a host
                # number, so one fetch and a second, smaller, spectrum
                cum = np.cumsum(np.asarray(fitted[2]))
                k = min(int(np.searchsorted(cum, n_components, side="left"))
                        + 1, d)
                fitted = _spectrum(r, n_f, k=k)
            # every caller reads the fitted arrays on the host: their
            # copies start now, and the fit ends when they are there
            for value in fitted + (mean,):
                value.copy_to_host_async()
            jax.block_until_ready(fitted)
        reg = _obs.registry()
        reg.counter("pca.count").inc()
        reg.counter("pca.passes").inc(passes)
        reg.counter("pca.fallbacks").inc(int(not held))

        self.n_components_ = k
        (self.components_, self.explained_variance_,
         self.explained_variance_ratio_, self.singular_values_,
         self.noise_variance_) = fitted
        self.mean_ = mean
        self.n_samples_ = n
        self.n_features_in_ = d
        self.n_passes_ = passes
        return X

    def _fit_randomized(self, X, n_components, k_request):
        """Halko's range finder on a centred copy of the table (the copy
        is the solver's: it multiplies the table ``2 * iterated_power + 2``
        times)."""
        n, d = X.n_samples, X.data.shape[1]
        centered, mean = self._center(X)
        _, s, vt = randomized_svd(
            centered, k_request, n_iter=self.iterated_power,
            random_state=self.random_state,
        )
        _, vt = svd_flip(None, vt, u_based_decision=False)
        # s has k_request entries; the total variance needs all d, so it
        # is the masked total variance
        from ..core.sharded import masked_var

        explained = (s ** 2) / (n - 1)
        total_var = jnp.sum(masked_var(X.data, X.mask, ddof=1))
        if isinstance(n_components, float):
            cum = jnp.cumsum(explained / total_var)
            k = min(int(jnp.searchsorted(cum, n_components, side="left"))
                    + 1, len(s))
        else:
            k = n_components
        self.n_components_ = k
        self.components_ = vt[:k]
        self.explained_variance_ = explained[:k]
        self.explained_variance_ratio_ = explained[:k] / total_var
        self.singular_values_ = s[:k]
        self.mean_ = mean
        self.n_samples_ = n
        self.n_features_in_ = d
        if k < min(n, d):
            self.noise_variance_ = (total_var - jnp.sum(explained[:k])) / (
                min(n, d) - k
            )
        else:
            self.noise_variance_ = jnp.asarray(0.0, dtype=s.dtype)
        return X

    def transform(self, X):
        x, _ = _masked_or_plain(X)
        out = (x - self.mean_) @ self.components_.T
        if self.whiten:
            out = out / jnp.sqrt(self.explained_variance_)
        return _like_input(X, out)

    def fit_transform(self, X, y=None):
        """The fit, then one product ``(X - mean_) @ components_.T`` (no
        ``Q`` of the table anywhere)."""
        rows = self._fit(X)
        scale = (1.0 / jnp.sqrt(self.explained_variance_) if self.whiten
                 else jnp.ones((), rows.data.dtype))
        out = _project(rows.data, rows.mask, self.mean_, self.components_,
                       scale, k=self.n_components_)
        if isinstance(X, ShardedRows):
            return ShardedRows(data=out, mask=X.mask, n_samples=X.n_samples)
        return out[: self.n_samples_]

    def inverse_transform(self, X):
        x, _ = _masked_or_plain(X)
        if self.whiten:
            x = x * jnp.sqrt(self.explained_variance_)
        return _like_input(X, x @ self.components_ + self.mean_)

    def get_covariance(self):
        """Model covariance (probabilistic-PCA form) — one small (d, d)
        device gemm, replicating sklearn's formula EXACTLY, including
        its whiten=True behavior (components rescaled by √λ before the
        (λ−σ²) weighting — sklearn's own convention, matched so scores
        agree elementwise in both modes)."""
        c = self.components_
        ev = self.explained_variance_
        if self.whiten:
            c = c * jnp.sqrt(ev)[:, None]
        diff = jnp.maximum(ev - self.noise_variance_, 0.0)
        cov = (c.T * diff) @ c
        d = c.shape[1]
        return cov + self.noise_variance_ * jnp.eye(d, dtype=cov.dtype)

    def get_precision(self):
        """Inverse of :meth:`get_covariance` via the matrix-inversion
        lemma (sklearn ``PCA.get_precision``): O(d·k²) instead of a
        d×d inverse when k < d, exact fallback otherwise."""
        d = self.components_.shape[1]
        ev = self.explained_variance_
        nv = self.noise_variance_
        if float(nv) == 0.0 or self.n_components_ >= d:
            cov = self.get_covariance()
            prec = jnp.linalg.inv(cov)
            if bool(jnp.all(jnp.isfinite(prec))):
                return prec  # plain inverse is well-posed: report it exactly
            # singular / near-singular covariance only: regularize with a
            # trace-scaled jitter so callers get a finite precision instead
            # of inf/nan (sklearn raises LinAlgError here; a loud-but-
            # finite answer serves score_samples better)
            jitter = 1e-12 * jnp.trace(cov) / d
            return jnp.linalg.inv(cov + jitter * jnp.eye(d, dtype=cov.dtype))
        c = self.components_
        if self.whiten:
            c = c * jnp.sqrt(ev)[:, None]
        diff = jnp.maximum(ev - nv, 0.0)
        # a component whose variance is entirely noise (diff == 0) adds
        # nothing to the model covariance, so it must add nothing to the
        # precision: zero its row (exact) instead of letting 1/diff blow
        # up — the masked diagonal lane then decouples in the inverse
        c = c * (diff > 0)[:, None]
        inner = jnp.diag(1.0 / jnp.where(diff > 0, diff, 1.0)) + (c @ c.T) / nv
        middle = jnp.linalg.inv(inner)
        eye = jnp.eye(d, dtype=c.dtype)
        return (eye - (c.T @ middle @ c) / nv) / nv

    def score_samples(self, X):
        """Per-sample average log-likelihood under the probabilistic PCA
        model (sklearn ``PCA.score_samples``; Tipping & Bishop 1999).
        Computed on device: one centering, one (d, d) solve."""
        x, _ = _masked_or_plain(X)
        xc = x - self.mean_
        cov = self.get_covariance()
        d = cov.shape[0]
        # clamp for invertibility when noise_variance_ == 0 (k == d):
        # the model covariance is then exactly the sample covariance and
        # a tiny jitter keeps the Cholesky well-posed
        jitter = 1e-12 * jnp.trace(cov) / d
        cov = cov + jitter * jnp.eye(d, dtype=cov.dtype)
        chol = jnp.linalg.cholesky(cov)
        sol = jax.scipy.linalg.cho_solve((chol, True), xc.T)  # (d, n)
        mahal = jnp.sum(xc.T * sol, axis=0)
        logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
        ll = -0.5 * (d * jnp.log(2.0 * jnp.pi) + logdet + mahal)
        if isinstance(X, ShardedRows):
            return ll[: X.n_samples]
        return ll

    def score(self, X, y=None):
        """Mean of ``score_samples`` over the real rows (score_samples
        already slices sharded inputs to their true row count)."""
        return float(jnp.mean(self.score_samples(X)))
