"""Shared utilities — twin of ``dask_ml/utils.py`` (reference symbols:
``check_array``, ``handle_zeros_in_scale``, ``svd_flip``, ``draw_seed``,
``_timer``, ``assert_estimator_equal``), re-done for jax arrays.
"""

from __future__ import annotations

import contextlib
import logging
import numbers
import time

import numpy as np

import jax
import jax.numpy as jnp

from .core.sharded import ShardedRows, unshard

logger = logging.getLogger(__name__)


def check_array(
    array,
    *,
    accept_sharded: bool = True,
    ensure_2d: bool = True,
    allow_nd: bool = False,
    dtype="numeric",
    copy: bool = False,
):
    """Validate input like the reference's dask-aware ``check_array``.

    Accepts numpy arrays, jax arrays, and :class:`ShardedRows`.  Returns the
    input unchanged structurally (no premature host transfer), after shape /
    dtype validation.
    """
    if isinstance(array, ShardedRows):
        inner = array.data
        if ensure_2d and inner.ndim != 2:
            raise ValueError(f"Expected 2D input, got ndim={inner.ndim}")
        if array.n_samples == 0:
            raise ValueError("Found array with 0 samples")
        return array
    if hasattr(array, "to_numpy"):  # pandas
        array = array.to_numpy()
    arr = jnp.asarray(array) if isinstance(array, jax.Array) else np.asarray(array)
    if dtype == "numeric" and not np.issubdtype(arr.dtype, np.number):
        raise ValueError(f"Expected numeric dtype, got {arr.dtype}")
    if arr.ndim == 0:
        raise ValueError("Expected an array, got a scalar")
    if ensure_2d and arr.ndim != 2:
        if arr.ndim == 1 or not allow_nd:
            raise ValueError(
                f"Expected 2D array, got ndim={arr.ndim}. "
                "Reshape your data with .reshape(-1, 1) for a single feature."
            )
    if not allow_nd and arr.ndim > 2:
        raise ValueError(f"Expected <=2 dims, got ndim={arr.ndim}")
    if arr.shape[0] == 0:
        raise ValueError("Found array with 0 samples")
    if copy and isinstance(arr, np.ndarray):
        arr = arr.copy()
    return arr


def check_consistent_length(*arrays):
    lengths = set()
    for a in arrays:
        if a is None:
            continue
        if isinstance(a, ShardedRows):
            n = a.n_samples
        else:
            shape = getattr(a, "shape", None)
            n = shape[0] if shape else len(a)
        lengths.add(int(n))
    if len(lengths) > 1:
        raise ValueError(f"Inconsistent sample counts: {sorted(lengths)}")


def check_chunks(n_samples, n_features=None, chunks=None):
    """Normalize a row-block size the way the reference normalizes dask
    chunks (reference: ``dask_ml/utils.py :: check_chunks``).

    The TPU collection model has no column chunking (features live whole on
    each shard — SURVEY §2.2 data parallelism), so ``chunks`` here is the
    ROW-block granularity; ``_partial.fit`` normalizes its ``chunk_size``
    through this.  Accepts ``None`` (auto: ≤ 16 blocks), an int (rows per
    block), or a (rows, features) tuple whose feature entry must cover all
    columns.  Returns rows-per-block as an int.
    """
    n_samples = int(n_samples)
    if n_samples <= 0:
        raise ValueError("n_samples must be positive")
    if chunks is None:
        return max(1, -(-n_samples // 16))
    if isinstance(chunks, numbers.Integral):
        chunks = int(chunks)
        if chunks <= 0:
            raise ValueError(f"chunks must be positive; got {chunks}")
        return chunks
    if isinstance(chunks, (tuple, list)) and len(chunks) == 2:
        rows, cols = chunks
        if n_features is not None and int(cols) != int(n_features):
            raise ValueError(
                f"column chunking is not supported on the TPU layout; the "
                f"feature chunk must span all {n_features} columns, got {cols}"
            )
        return check_chunks(n_samples, n_features, int(rows))
    raise ValueError(f"Unrecognized chunks: {chunks!r}")


def check_matching_blocks(*arrays):
    """Raise unless all sharded inputs share one row layout (reference:
    ``dask_ml/utils.py :: check_matching_blocks`` — same-chunk check).

    For :class:`ShardedRows`, "matching blocks" means identical logical
    length, identical padded length, and identical device sharding — the
    preconditions for zipping two collections through one shard_map.
    Non-sharded array-likes only need matching logical length.
    """
    check_consistent_length(*arrays)
    sharded = [a for a in arrays if isinstance(a, ShardedRows)]
    if len(sharded) < 2:
        return
    first = sharded[0]
    for other in sharded[1:]:
        if other.data.shape[0] != first.data.shape[0]:
            raise ValueError(
                f"Mismatched padded lengths: {first.data.shape[0]} vs "
                f"{other.data.shape[0]} — reshard with shard_rows so the "
                f"pad+mask layouts agree"
            )
        if other.data.sharding != first.data.sharding:
            raise ValueError(
                "Mismatched device shardings: "
                f"{first.data.sharding} vs {other.data.sharding}"
            )


def slice_columns(X, columns):
    """Select columns from an array, dataframe or ShardedRows (reference:
    ``dask_ml/utils.py :: slice_columns``).  ``None`` returns X unchanged;
    dataframes slice by label, arrays by position."""
    if columns is None:
        return X
    if isinstance(X, ShardedRows):
        cols = np.asarray(columns)
        if cols.dtype == bool:  # mask → positions (parity with X[:, mask])
            cols = np.flatnonzero(cols)
        idx = jnp.asarray(cols.astype(np.int32))
        return ShardedRows(
            data=X.data[:, idx], mask=X.mask, n_samples=X.n_samples
        )
    if hasattr(X, "iloc"):  # pandas
        return X[list(columns)]
    return X[:, np.asarray(columns)]


def env_choice(name: str, allowed: tuple, default: str = "auto") -> str:
    """Read a strategy knob from the environment with validation — the
    shared shape behind ``DASK_ML_TPU_SCATTER`` / ``DASK_ML_TPU_PACK``
    (each policy keeps its own platform-auto logic, but the read/validate
    step lives once)."""
    import os

    v = os.environ.get(name, default).strip().lower()
    if v not in allowed:
        raise ValueError(
            f"{name} must be {'|'.join(allowed)}, got {v!r}"
        )
    return v


def safe_denominator(x):
    """0-safe divisor that PRESERVES fractional weight masses.

    ``maximum(x, 1)`` silently shrinks any mean whose total mass is in
    (0, 1) — the mask doubles as the per-row weight throughout this
    framework, so sub-unit masses are legitimate (caught by the NB
    weighted-stream and sub-unit-KMeans property tests).  The kept branch
    is never 0, so the division is always finite.
    """
    return jnp.where(x > 0, x, 1.0)


def chan_merge(na, ma, m2a, nb, mb, vb):
    """Merge two (count, mean, M2) moment summaries (Chan et al. 1979) —
    the numerically safe parallel-variance update shared by
    ``StandardScaler.partial_fit`` (scalar count, (d,) moments) and
    ``GaussianNB.partial_fit`` ((k,1) per-class counts, (k,d) moments).
    Counts must broadcast against the moment arrays; zero-count sides are
    handled (the 1-clamped denominator only engages when n == 0, where
    every product above it is 0 too).  Returns ``(n, mean, m2)``.
    """
    n = na + nb
    nsafe = safe_denominator(n)
    delta = mb - ma
    mean = ma + delta * (nb / nsafe)
    m2 = m2a + vb * nb + delta * delta * (na * nb / nsafe)
    return n, mean, m2


def handle_zeros_in_scale(scale):
    """Avoid division by ~0 when scaling (constant features scale by 1).

    Reference: ``dask_ml/utils.py :: handle_zeros_in_scale``.
    """
    scale = jnp.asarray(scale)
    if scale.ndim == 0:
        return jnp.where(scale == 0.0, 1.0, scale)
    eps = 10 * jnp.finfo(scale.dtype).eps
    return jnp.where(jnp.abs(scale) < eps, 1.0, scale)


def svd_flip(u, v, u_based_decision: bool = True):
    """Deterministic SVD sign convention (reference: ``utils.py :: svd_flip``).
    ``u`` may be None where only ``v`` is kept (a V-based decision)."""
    if u_based_decision:
        max_abs = jnp.argmax(jnp.abs(u), axis=0)
        signs = jnp.sign(u[max_abs, jnp.arange(u.shape[1])])
    else:
        max_abs = jnp.argmax(jnp.abs(v), axis=1)
        signs = jnp.sign(v[jnp.arange(v.shape[0]), max_abs])
    if u is not None:
        u = u * signs[jnp.newaxis, :]
    v = v * signs[:, jnp.newaxis]
    return u, v


def _check_class_weight_keys(class_weight, classes):
    """A dict key naming no fitted class is a typo, not a preference —
    raise like sklearn's compute_class_weight instead of silently
    training unweighted."""
    known = set(np.asarray(classes).tolist())
    unknown = [k for k in class_weight if k not in known]
    if unknown:
        raise ValueError(
            f"class_weight keys {unknown!r} are not in the fitted classes "
            f"{sorted(known)!r}"
        )


def effective_mask(mask, y_padded=None, *, sample_weight=None,
                   class_weight=None, classes=None, n_samples=None):
    """Fold per-row weights into a validity mask.

    The pad+mask discipline makes every masked reduction a weighted
    reduction for free: the mask IS a multiplicative per-row weight, so
    ``sample_weight`` and ``class_weight`` simply scale it (pad rows stay
    at 0).  sklearn semantics throughout: ``'balanced'`` uses
    ``n / (K * count_k)`` with UNWEIGHTED counts; a class-weight dict
    defaults absent classes to 1.0.

    Args:
      mask: (padded_n,) validity/weight vector (device).
      y_padded: (padded_n,) raw label values (device) — required for
        ``class_weight``.
      sample_weight: host (n_samples,) per-row weights, or None.
      class_weight: dict {label: weight} or ``'balanced'`` or None.
      classes: label inventory (required for ``class_weight``).
      n_samples: true row count (defaults to ``len(sample_weight)``).
    Returns the weighted mask (device, same shape as ``mask``).
    """
    w = mask
    if sample_weight is not None:
        sw = np.asarray(sample_weight, np.float32).ravel()
        n = int(n_samples) if n_samples is not None else sw.shape[0]
        if sw.shape[0] != n:
            raise ValueError(
                f"sample_weight has {sw.shape[0]} entries for {n} samples"
            )
        pad = int(mask.shape[0]) - sw.shape[0]
        if pad < 0:
            raise ValueError(
                f"sample_weight longer ({sw.shape[0]}) than padded rows "
                f"({mask.shape[0]})"
            )
        if pad:
            sw = np.pad(sw, (0, pad))
        w = w * jnp.asarray(sw)
    if class_weight is not None:
        if y_padded is None or classes is None:
            raise ValueError("class_weight requires labels and classes")
        cls_np = np.asarray(classes)
        cls = jnp.asarray(cls_np, y_padded.dtype)
        ind = (
            (y_padded[None, :] == cls[:, None]).astype(jnp.float32)
            * mask[None, :]
        )
        if isinstance(class_weight, str):
            if class_weight != "balanced":
                raise ValueError(
                    f"class_weight must be a dict or 'balanced'; got "
                    f"{class_weight!r}"
                )
            counts = jnp.sum(ind, axis=1)
            total = jnp.sum(mask)
            cw = total / (len(cls_np) * safe_denominator(counts))
        else:
            _check_class_weight_keys(class_weight, cls_np)
            cw = jnp.asarray(
                [float(class_weight.get(c, 1.0)) for c in cls_np.tolist()],
                jnp.float32,
            )
        w = w * jnp.sum(cw[:, None] * ind, axis=0)
    return w


def classes_f32_exact(classes) -> bool:
    """True when every class label survives a float32 round-trip — the
    precondition for device-side label comparison (int labels past 2^24
    would collide after the cast and silently score wrong)."""
    classes = np.asarray(classes)
    return bool(
        np.issubdtype(classes.dtype, np.number)
        and np.array_equal(
            classes.astype(np.float32).astype(classes.dtype), classes
        )
    )


def masked_device_accuracy(pred_idx, y_data, mask, classes) -> float:
    """Masked accuracy as ONE replicated scalar fetch.

    ``pred_idx``: (padded_n,) predicted class indices (device);
    ``y_data``: (padded_n,) raw label values (device).  Comparison is on
    VALUES — a label outside ``classes`` counts as a miss, matching the
    host accuracy path.  The single scalar fetch is the only legal form
    for multi-host global arrays (and avoids the O(n) transfer anywhere).
    Callers must gate on :func:`classes_f32_exact`.
    """
    cls = jnp.asarray(np.asarray(classes).astype(np.float32))
    hit = (
        (cls[pred_idx] == y_data.astype(jnp.float32)).astype(jnp.float32)
        * mask
    )
    return float(jnp.sum(hit) / safe_denominator(jnp.sum(mask)))


def reweight_rows(X, *, sample_weight=None, class_weight=None,
                  classes=None, y_padded=None):
    """Return ``X`` (ShardedRows) with per-row weights folded into its
    mask via :func:`effective_mask` — the one place estimators rebuild a
    weighted ShardedRows, so the weighting contract cannot drift between
    them.  No-op (same object) when no weights are given."""
    if sample_weight is None and class_weight is None:
        return X
    return ShardedRows(
        data=X.data,
        mask=effective_mask(
            X.mask, y_padded, sample_weight=sample_weight,
            class_weight=class_weight, classes=classes,
            n_samples=X.n_samples,
        ),
        n_samples=X.n_samples,
    )


def host_class_weight_rows(class_weight, classes, yv):
    """Per-row class weights resolved ON HOST — the twin of
    :func:`effective_mask`'s device class-weight branch for label arrays
    that cannot cross to device (strings, big ints).  Same sklearn
    semantics: ``'balanced'`` is ``n / (K * count_k)`` with unweighted
    counts; dict keys default to 1.0.  Keep the two branches in sync."""
    classes = np.asarray(classes)
    yv = np.asarray(yv)
    if isinstance(class_weight, str):
        if class_weight != "balanced":
            raise ValueError(
                f"class_weight must be a dict or 'balanced'; got "
                f"{class_weight!r}"
            )
        # align counts to the FULL class inventory: a class absent from
        # this yv must not shift (or overrun) the weight table
        uniq, counts_u = np.unique(yv, return_counts=True)
        counts = np.zeros(len(classes))
        counts[np.searchsorted(classes, uniq)] = counts_u
        cw = yv.shape[0] / (len(classes) * np.maximum(counts, 1.0))
    else:
        _check_class_weight_keys(class_weight, classes)
        cw = np.asarray(
            [float(class_weight.get(c, 1.0)) for c in classes.tolist()]
        )
    return cw[np.searchsorted(classes, yv)].astype(np.float32)


def check_max_iter(max_iter):
    """Reject non-positive epoch budgets up front: every epoch-loop
    estimator reads the loop variable after the loop, so ``max_iter=0``
    would otherwise surface as an unbound-variable crash mid-fit."""
    if max_iter < 1:
        raise ValueError(f"max_iter must be >= 1, got {max_iter}")


def draw_seed(random_state, low=0, high=2**31 - 1, size=None):
    """Draw integer seed(s) from a numpy RandomState-compatible source.

    Reference: ``dask_ml/utils.py :: draw_seed``.
    """
    rng = check_random_state(random_state)
    return rng.randint(low, high, size=size)


def check_random_state(random_state) -> np.random.RandomState:
    if random_state is None or isinstance(random_state, numbers.Integral):
        return np.random.RandomState(random_state)
    if isinstance(random_state, np.random.RandomState):
        return random_state
    raise ValueError(f"Cannot make RandomState from {random_state!r}")


@contextlib.contextmanager
def _timer(name: str, _logger=None, level=logging.INFO):
    """Log phase durations (reference: ``utils.py :: _timer``)."""
    _logger = _logger or logger
    start = time.perf_counter()
    _logger.log(level, "Starting %s", name)
    try:
        yield
    finally:
        _logger.log(level, "Finished %s in %.4fs", name, time.perf_counter() - start)


def copy_learned_attributes(from_estimator, to_estimator):
    """Copy fitted (trailing-underscore) attributes between estimators.

    Reference: ``dask_ml/_utils.py :: copy_learned_attributes``.
    """
    for name, value in vars(from_estimator).items():
        if name.endswith("_") and not name.startswith("_"):
            setattr(to_estimator, name, value)
    return to_estimator


def assert_estimator_equal(left, right, exclude=(), **kwargs):
    """Assert two fitted estimators carry (approximately) equal fitted attrs.

    Reference: ``dask_ml/utils.py :: assert_estimator_equal``.
    """
    left_attrs = {k for k in vars(left) if k.endswith("_") and not k.startswith("_")}
    right_attrs = {k for k in vars(right) if k.endswith("_") and not k.startswith("_")}
    if isinstance(exclude, str):
        exclude = {exclude}
    attrs = (left_attrs & right_attrs) - set(exclude)
    assert attrs, "no common fitted attributes"
    for attr in attrs:
        l, r = getattr(left, attr), getattr(right, attr)
        _assert_eq(l, r, name=attr, **kwargs)


def _assert_eq(l, r, name="", **kwargs):
    if isinstance(l, (ShardedRows, jax.Array)):
        l = unshard(l)
    if isinstance(r, (ShardedRows, jax.Array)):
        r = unshard(r)
    if isinstance(l, np.ndarray) or isinstance(r, np.ndarray):
        np.testing.assert_allclose(np.asarray(l), np.asarray(r), err_msg=name, **kwargs)
    elif isinstance(l, numbers.Number):
        np.testing.assert_allclose(l, r, err_msg=name, **kwargs)
    else:
        assert l == r, f"{name}: {l!r} != {r!r}"
