"""Scalers (reference: ``dask_ml/preprocessing/data.py`` — ``StandardScaler``,
``MinMaxScaler``, ``RobustScaler``, ``QuantileTransformer``).

Where the reference builds lazy dask reductions (`X.mean()`, `da.percentile`),
each fit here is one jitted masked reduction over the sharded sample axis.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..base import OneToOneFeatureMixin, TPUEstimator, TransformerMixin
from ..core.sharded import ShardedRows, masked_mean, masked_var
from ..utils import check_array, handle_zeros_in_scale


def _as_float(x):
    return x.astype(jnp.float32) if not jnp.issubdtype(x.dtype, jnp.inexact) else x


def _masked_or_plain(X):
    """(data, mask) for either a ShardedRows or a plain array."""
    if isinstance(X, ShardedRows):
        return _as_float(X.data), X.mask
    x = _as_float(jnp.asarray(X))
    return x, jnp.ones(x.shape[0], dtype=jnp.float32)


def _ingest_float(est, X):
    """check_array + shard, casting integer input to float (sklearn scalers
    accept integer arrays)."""
    X = check_array(X)
    if not isinstance(X, ShardedRows):
        X = est._ingest(X)
    if not jnp.issubdtype(X.data.dtype, jnp.inexact):
        X = ShardedRows(data=X.data.astype(jnp.float32), mask=X.mask, n_samples=X.n_samples)
    return X


def _like_input(X, out):
    """Wrap transform output like the input (sharded in → sharded out)."""
    if isinstance(X, ShardedRows):
        return ShardedRows(data=out, mask=X.mask, n_samples=X.n_samples)
    return out


# Above this many (padded) rows the exact sort-based quantile becomes an
# all-gather-shaped cost on a sharded column (SURVEY.md §7 hard-part (d));
# switch to the one-pass histogram sketch.  Env-overridable for tests.
def _approx_rows_threshold() -> int:
    import os

    return int(os.environ.get("DASK_ML_TPU_EXACT_QUANTILE_MAX_ROWS", 4_000_000))


@partial(jax.jit, static_argnames=("bins", "refinements", "scatter"))
def _hist_quantiles(x, mask, probs, *, bins=4096, refinements=3,
                    scatter="segsum"):
    """Merge-based approximate per-feature quantiles, one fused program.

    The ``da.percentile`` twin: per-shard histograms merge by ADDITION
    (XLA inserts the psum over the sharded row axis), then quantiles are
    linearly interpolated inside the bracketing bin.  A fixed uniform grid
    collapses on outlier-heavy features (one 1e9 outlier makes the bin
    width swamp a [0,1] bulk), so the histogram is RE-FOCUSED
    ``refinements`` times onto the bins bracketing the requested
    quantiles.  When the interior quantiles land in one bin the window
    shrinks ~``bins/3``× per pass (window = bracketing bins ±1), so the
    defaults resolve a 1e9-range outlier column to ~1e-4 absolute in
    1 + refinements full data scans — still far cheaper than a
    distributed sort at the billion-row scale this path targets.
    """
    n, d = x.shape
    mvalid = mask[:, None] > 0
    lo = jnp.min(jnp.where(mvalid, x, jnp.inf), axis=0)  # (d,)
    hi = jnp.max(jnp.where(mvalid, x, -jnp.inf), axis=0)

    probs = jnp.asarray(probs, x.dtype)
    total = jnp.sum(mask)
    targets = probs[:, None] * jnp.broadcast_to(total, (d,))[None, :]  # (p, d)
    # p=0 / p=1 are EXACTLY the masked min/max (already in hand) and must
    # not steer the refinement window: with an extreme outlier the max's
    # bin keeps the window at full range forever and the promised
    # per-pass tightening never happens for everything else
    interior = (probs > 0.0) & (probs < 1.0)  # (p,)

    weights_all = jnp.broadcast_to(mask[:, None], x.shape)
    feat_off = jnp.arange(d, dtype=jnp.int32)[None, :] * bins

    def hist_pass(lo_f, hi_f):
        """One histogram over [lo_f, hi_f] per feature; returns per-prob
        interpolated values and the next (tighter) bracketing ranges."""
        width = jnp.maximum(hi_f - lo_f, 1e-30)
        pos = (x - lo_f[None, :]) / width[None, :] * bins
        idx = jnp.clip(pos.astype(jnp.int32), 0, bins - 1)
        inside = weights_all * (x >= lo_f[None, :]) * (x <= hi_f[None, :])
        below = jnp.sum(weights_all * (x < lo_f[None, :]), axis=0)  # (d,)
        # routed through the shared scatter policy (ops.scatter): with
        # d*bins segments the one-hot form is memory-quadratic, so the
        # policy resolves to segment_sum — but the decision lives in ONE
        # place with the k-means reduce
        from ..ops.scatter import bucket_sum

        counts = bucket_sum(
            (inside).ravel(), (feat_off + idx).ravel(),
            num_segments=d * bins, strategy=scatter,
        ).reshape(d, bins)
        cdf = jnp.cumsum(counts, axis=1)

        def one_feature(cdf_f, lo_1, width_1, below_1, tgt_f):
            t = tgt_f - below_1  # ranks relative to this window
            b = jnp.clip(jnp.searchsorted(cdf_f, t), 0, bins - 1)
            prev = jnp.where(b > 0, cdf_f[jnp.maximum(b - 1, 0)], 0.0)
            cnt = jnp.maximum(cdf_f[b] - prev, 1e-30)
            frac = jnp.clip((t - prev) / cnt, 0.0, 1.0)
            binw = width_1 / bins
            val = lo_1 + (b.astype(x.dtype) + frac) * binw
            # next window: the bins bracketing the INTERIOR quantiles,
            # widened one bin each side — fp32 edge arithmetic at large
            # scales (lo ~ 1e9, ulp 64) can otherwise round the window
            # past the true quantile region and exclude the bulk.  With
            # no interior probs the sentinel fillers would INVERT the
            # window (bmin=bins-1 > bmax=0), so fall back to the genuine
            # full span [lo_1, lo_1 + width_1] — the window is unused for
            # the final values then (endpoints are exact) but must stay a
            # valid range for the next pass's histogram.
            has_interior = jnp.any(interior)
            bmin = jnp.min(jnp.where(interior, b, bins - 1))
            bmax = jnp.max(jnp.where(interior, b, 0))
            nlo = jnp.where(
                has_interior,
                lo_1 + (bmin.astype(x.dtype) - 1.0) * binw, lo_1,
            )
            nhi = jnp.where(
                has_interior,
                lo_1 + (bmax.astype(x.dtype) + 2.0) * binw, lo_1 + width_1,
            )
            return val, nlo, nhi

        vals, nlo, nhi = jax.vmap(
            one_feature, in_axes=(0, 0, 0, 0, 1), out_axes=(1, 0, 0)
        )(cdf, lo_f, width, below, targets)
        return vals, nlo, nhi

    vals, lo_r, hi_r = hist_pass(lo, hi)
    for _ in range(refinements):
        vals, lo_r, hi_r = hist_pass(lo_r, hi_r)
    # interior values must stay inside the DATA range: the refinement
    # window is widened one bin past the bracketing bins, so the final
    # interpolation can land just below min/above max for tie-heavy
    # columns (caught by an r4 property test: p=0.1 of a column whose
    # minimum is -7.0 came back -7.0023, inverting order vs p=0)
    vals = jnp.clip(vals, lo[None, :], hi[None, :])
    # exact endpoints: the sketch's interpolation cannot beat the masked
    # min/max it already computed
    vals = jnp.where(interior[:, None], vals, jnp.where(
        (probs <= 0.0)[:, None], lo[None, :], hi[None, :]))
    return vals  # (p, d)


def _masked_quantiles(x, mask, probs, method: str = "auto"):
    """Per-feature quantiles ignoring padded rows.

    ``exact``: ``jnp.nanquantile`` (one device sort per feature) — strictly
    more accurate than the reference's approximate ``da.percentile``.
    ``auto`` switches to the histogram sketch past the row threshold,
    where a distributed sort would all-gather the column.
    """
    if method == "exact" or (
        method == "auto" and x.shape[0] <= _approx_rows_threshold()
    ):
        xm = jnp.where(mask[:, None] > 0, x, jnp.nan)
        return jnp.nanquantile(xm, jnp.asarray(probs), axis=0)
    from ..ops.scatter import scatter_strategy

    # resolved OUTSIDE the jit: the strategy is a static argument
    return _hist_quantiles(x, mask, jnp.asarray(probs),
                           scatter=scatter_strategy(x.shape[1] * 4096))


class StandardScaler(OneToOneFeatureMixin, TransformerMixin, TPUEstimator):
    """Standardize features to zero mean, unit variance."""

    def __init__(self, copy=True, with_mean=True, with_std=True):
        self.copy = copy
        self.with_mean = with_mean
        self.with_std = with_std

    # stream moments are device state a mid-stream checkpoint must carry
    # (same opt-in MiniBatchKMeans/SGD use); the exact row count is the
    # trailing-underscore n_samples_seen_, persisted automatically
    _checkpoint_private_attrs = ("_pf_mean", "_pf_m2")

    def fit(self, X, y=None):
        for a in ("_pf_mean", "_pf_m2", "n_samples_seen_"):
            if hasattr(self, a):
                delattr(self, a)
        return self.partial_fit(X, y)

    def partial_fit(self, X, y=None):
        """Incremental fit over a stream of row blocks (sklearn contract,
        absent from the reference's lazy-reduction scaler): Chan et al.
        parallel merge of per-feature (mean, M2) device moments, so
        ``fit`` on one array and a ``partial_fit`` stream over its blocks
        produce identical statistics.  The merge weights come from the
        EXACT Python-int ``n_samples_seen_`` — an f32 running count would
        freeze at 2^24 rows and silently mis-weight every later block.
        """
        X = _ingest_float(self, X)
        data, mask = X.data, X.mask
        nb = int(X.n_samples)
        mb = masked_mean(data, mask)
        vb = masked_var(data, mask)
        if not hasattr(self, "_pf_mean"):
            self._pf_mean, self._pf_m2 = mb, vb * nb
            self.n_samples_seen_ = nb
        else:
            from ..utils import chan_merge

            _n, self._pf_mean, self._pf_m2 = chan_merge(
                float(self.n_samples_seen_), self._pf_mean, self._pf_m2,
                float(nb), mb, vb,
            )
            self.n_samples_seen_ += nb
        self.mean_ = self._pf_mean if self.with_mean else None
        if self.with_std:
            var = self._pf_m2 / max(self.n_samples_seen_, 1)
            self.var_ = var
            self.scale_ = handle_zeros_in_scale(jnp.sqrt(var))
        else:
            self.var_ = None
            self.scale_ = None
        self.n_features_in_ = data.shape[1]
        return self

    def transform(self, X, y=None, copy=None):
        x, _ = _masked_or_plain(X)
        if self.with_mean:
            x = x - self.mean_
        if self.with_std:
            x = x / self.scale_
        return _like_input(X, x)

    def inverse_transform(self, X, copy=None):
        x, _ = _masked_or_plain(X)
        if self.with_std:
            x = x * self.scale_
        if self.with_mean:
            x = x + self.mean_
        return _like_input(X, x)


class MinMaxScaler(OneToOneFeatureMixin, TransformerMixin, TPUEstimator):
    """Scale features to a given range (default [0, 1])."""

    def __init__(self, feature_range=(0, 1), copy=True):
        self.feature_range = feature_range
        self.copy = copy

    def fit(self, X, y=None):
        for a in ("data_min_", "data_max_", "n_samples_seen_"):
            if hasattr(self, a):
                delattr(self, a)
        return self.partial_fit(X, y)

    def partial_fit(self, X, y=None):
        """Incremental fit: running per-feature min/max over row blocks."""
        X = _ingest_float(self, X)
        data, mask = X.data, X.mask
        big = jnp.asarray(jnp.finfo(data.dtype).max, dtype=data.dtype)
        data_min = jnp.min(jnp.where(mask[:, None] > 0, data, big), axis=0)
        data_max = jnp.max(jnp.where(mask[:, None] > 0, data, -big), axis=0)
        if hasattr(self, "data_min_"):
            data_min = jnp.minimum(self.data_min_, data_min)
            data_max = jnp.maximum(self.data_max_, data_max)
            self.n_samples_seen_ += int(X.n_samples)
        else:
            self.n_samples_seen_ = int(X.n_samples)
        lo, hi = self.feature_range
        self.data_min_ = data_min
        self.data_max_ = data_max
        self.data_range_ = data_max - data_min
        self.scale_ = (hi - lo) / handle_zeros_in_scale(self.data_range_)
        self.min_ = lo - data_min * self.scale_
        self.n_features_in_ = data.shape[1]
        return self

    def transform(self, X, y=None, copy=None):
        x, _ = _masked_or_plain(X)
        return _like_input(X, x * self.scale_ + self.min_)

    def inverse_transform(self, X, copy=None):
        x, _ = _masked_or_plain(X)
        return _like_input(X, (x - self.min_) / self.scale_)


class RobustScaler(OneToOneFeatureMixin, TransformerMixin, TPUEstimator):
    """Scale by median and IQR (outlier-robust)."""

    def __init__(self, with_centering=True, with_scaling=True, quantile_range=(25.0, 75.0), copy=True):
        self.with_centering = with_centering
        self.with_scaling = with_scaling
        self.quantile_range = quantile_range
        self.copy = copy

    def fit(self, X, y=None):
        X = _ingest_float(self, X)
        data, mask = X.data, X.mask
        q_min, q_max = self.quantile_range
        if not 0 <= q_min <= q_max <= 100:
            raise ValueError(f"Invalid quantile_range: {self.quantile_range}")
        qs = _masked_quantiles(data, mask, [q_min / 100.0, 0.5, q_max / 100.0])
        self.center_ = qs[1] if self.with_centering else None
        if self.with_scaling:
            self.scale_ = handle_zeros_in_scale(qs[2] - qs[0])
        else:
            self.scale_ = None
        self.n_features_in_ = data.shape[1]
        return self

    def transform(self, X, y=None):
        x, _ = _masked_or_plain(X)
        if self.with_centering:
            x = x - self.center_
        if self.with_scaling:
            x = x / self.scale_
        return _like_input(X, x)

    def inverse_transform(self, X):
        x, _ = _masked_or_plain(X)
        if self.with_scaling:
            x = x * self.scale_
        if self.with_centering:
            x = x + self.center_
        return _like_input(X, x)


class QuantileTransformer(OneToOneFeatureMixin, TransformerMixin, TPUEstimator):
    """Map features to a uniform or normal distribution via quantiles.

    The reference approximates with ``da.percentile`` per chunk; here the
    reference quantile grid is exact and the transform is a vmapped
    ``jnp.interp`` per feature — one fused XLA program.

    ``subsample``/``random_state``/``ignore_implicit_zeros`` are accepted for
    API compatibility but inert: quantiles are computed on device for the
    FULL data — exactly (one sort per feature) up to the
    ``DASK_ML_TPU_EXACT_QUANTILE_MAX_ROWS`` threshold, then via the
    refining histogram sketch (``_hist_quantiles``: endpoint probs are the
    exact masked min/max; interior probs tighten by ~bins× per refinement
    pass) — so subsampling is unnecessary, and sparse input is densified
    at ingest.
    """

    def __init__(self, n_quantiles=1000, output_distribution="uniform",
                 ignore_implicit_zeros=False, subsample=int(1e5),
                 random_state=None, copy=True):
        self.n_quantiles = n_quantiles
        self.output_distribution = output_distribution
        self.ignore_implicit_zeros = ignore_implicit_zeros
        self.subsample = subsample
        self.random_state = random_state
        self.copy = copy

    def fit(self, X, y=None):
        if self.output_distribution not in ("uniform", "normal"):
            raise ValueError(f"Invalid output_distribution: {self.output_distribution!r}")
        X = _ingest_float(self, X)
        n_q = min(self.n_quantiles, X.n_samples)
        self.n_quantiles_ = n_q
        refs = jnp.linspace(0.0, 1.0, n_q)
        self.references_ = refs
        self.quantiles_ = _masked_quantiles(X.data, X.mask, refs).astype(X.data.dtype)
        self.n_features_in_ = X.data.shape[1]
        return self

    def _map(self, x, forward: bool):
        quantiles = self.quantiles_  # (n_q, d)
        refs = self.references_

        def per_feature(col, q):
            if forward:
                return jnp.interp(col, q, refs)
            return jnp.interp(col, refs, q)

        out = jax.vmap(per_feature, in_axes=(1, 1), out_axes=1)(x, quantiles)
        return out

    def transform(self, X):
        x, _ = _masked_or_plain(X)
        out = self._map(x, forward=True)
        if self.output_distribution == "normal":
            from jax.scipy.stats import norm

            clipped = jnp.clip(out, 1e-7, 1 - 1e-7)
            out = norm.ppf(clipped)
        return _like_input(X, out)

    def inverse_transform(self, X):
        x, _ = _masked_or_plain(X)
        if self.output_distribution == "normal":
            from jax.scipy.stats import norm

            x = norm.cdf(x)
        return _like_input(X, self._map(x, forward=False))


class PolynomialFeatures(TransformerMixin, TPUEstimator):
    """Polynomial feature expansion (reference: ``dask_ml/preprocessing/data.py``
    :: ``PolynomialFeatures``).

    The combination structure is static (it depends only on ``n_features``
    and ``degree``), so the expansion compiles to one XLA program: a stack of
    column products in sklearn's output order.  ``preserve_dataframe`` is
    honoured for pandas input like the reference.
    """

    def __init__(self, degree=2, interaction_only=False, include_bias=True,
                 preserve_dataframe=False):
        self.degree = degree
        self.interaction_only = interaction_only
        self.include_bias = include_bias
        self.preserve_dataframe = preserve_dataframe

    @staticmethod
    def _combinations(n_features, degree, interaction_only, include_bias):
        from itertools import chain, combinations, combinations_with_replacement

        comb = combinations if interaction_only else combinations_with_replacement
        start = 0 if include_bias else 1
        return list(chain.from_iterable(
            comb(range(n_features), d) for d in range(start, degree + 1)
        ))

    def fit(self, X, y=None):
        import pandas as pd

        if isinstance(X, pd.DataFrame):
            n = X.shape[1]
            self.feature_names_in_ = np.asarray(X.columns, dtype=object)
        else:
            x, _ = _masked_or_plain(check_array(X))
            n = x.shape[1]
        self.n_features_in_ = n
        self.combinations_ = self._combinations(
            n, self.degree, self.interaction_only, self.include_bias
        )
        self.n_output_features_ = len(self.combinations_)
        powers = np.zeros((self.n_output_features_, n), dtype=np.int64)
        for i, combo in enumerate(self.combinations_):
            for j in combo:
                powers[i, j] += 1
        self.powers_ = powers
        return self

    def get_feature_names_out(self, input_features=None):
        if input_features is None:
            input_features = getattr(
                self, "feature_names_in_",
                [f"x{j}" for j in range(self.n_features_in_)],
            )
        names = []
        for row in self.powers_:
            terms = [
                (f"{input_features[j]}" if p == 1 else f"{input_features[j]}^{p}")
                for j, p in enumerate(row) if p > 0
            ]
            names.append(" ".join(terms) if terms else "1")
        return np.asarray(names, dtype=object)

    def transform(self, X, y=None):
        import pandas as pd

        frame_in = isinstance(X, pd.DataFrame)
        if frame_in:
            x, _ = _masked_or_plain(X.to_numpy(dtype=np.float64))
        else:
            x, _ = _masked_or_plain(X)
        if x.shape[1] != self.n_features_in_:
            raise ValueError(
                f"X has {x.shape[1]} features; expected {self.n_features_in_}"
            )
        cols = [
            (jnp.ones(x.shape[0], x.dtype) if not combo
             else jnp.prod(x[:, jnp.asarray(combo)], axis=1))
            for combo in self.combinations_
        ]
        out = jnp.stack(cols, axis=1)
        if frame_in and self.preserve_dataframe:
            return pd.DataFrame(np.asarray(out), index=X.index,
                                columns=self.get_feature_names_out())
        return _like_input(X, out)


class MaxAbsScaler(OneToOneFeatureMixin, TransformerMixin, TPUEstimator):
    """Scale each feature by its maximum absolute value (sparse-friendly
    sklearn semantics: no centering, zeros stay zero).  One masked
    reduction over the sharded sample axis."""

    def __init__(self, copy=True):
        self.copy = copy

    def fit(self, X, y=None):
        for a in ("max_abs_", "n_samples_seen_"):
            if hasattr(self, a):
                delattr(self, a)
        return self.partial_fit(X, y)

    def partial_fit(self, X, y=None):
        """Incremental fit: running per-feature max |x| over row blocks."""
        X = _ingest_float(self, X)
        data, mask = X.data, X.mask
        mabs = jnp.max(
            jnp.where(mask[:, None] > 0, jnp.abs(data), 0.0), axis=0
        )
        if hasattr(self, "max_abs_"):
            mabs = jnp.maximum(self.max_abs_, mabs)
            self.n_samples_seen_ += int(X.n_samples)
        else:
            self.n_samples_seen_ = int(X.n_samples)
        self.max_abs_ = mabs
        self.scale_ = handle_zeros_in_scale(mabs)
        self.n_features_in_ = data.shape[1]
        return self

    def transform(self, X, y=None, copy=None):
        x, _ = _masked_or_plain(X)
        return _like_input(X, x / self.scale_)

    def inverse_transform(self, X, copy=None):
        x, _ = _masked_or_plain(X)
        return _like_input(X, x * self.scale_)


class Normalizer(OneToOneFeatureMixin, TransformerMixin, TPUEstimator):
    """Scale each ROW to unit norm (l1/l2/max) — stateless, one fused
    elementwise pass; rows of all zeros stay zero (sklearn semantics)."""

    def __init__(self, norm="l2", copy=True):
        self.norm = norm
        self.copy = copy

    def fit(self, X, y=None):
        if self.norm not in ("l1", "l2", "max"):
            raise ValueError(f"Invalid norm: {self.norm!r}")
        # stateless: fit only records the width — no device transfer
        check_array(X)
        self.n_features_in_ = (
            X.data.shape[1] if isinstance(X, ShardedRows)
            else np.asarray(X).shape[1]
        )
        return self

    def transform(self, X, y=None, copy=None):
        if self.norm not in ("l1", "l2", "max"):
            raise ValueError(f"Invalid norm: {self.norm!r}")
        d, _ = _masked_or_plain(X)
        if self.norm == "l1":
            n = jnp.sum(jnp.abs(d), axis=1, keepdims=True)
        elif self.norm == "l2":
            n = jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True))
        else:
            n = jnp.max(jnp.abs(d), axis=1, keepdims=True)
        return _like_input(X, d / jnp.where(n > 0, n, 1.0))
