"""Scatter-add strategy: ``segment_sum`` vs one-hot gemm, one policy.

Two lowerings exist for "accumulate rows into labeled buckets" — the
shape under the histogram quantile sketch
(``preprocessing/data.py :: _hist_quantiles``), the k-means per-cluster
reduce (``cluster/k_means.py :: _lloyd_step``), and GaussianNB's
per-class moments:

- ``jax.ops.segment_sum`` — an XLA scatter-add.  On CPU this wins big
  (r3 measurement: 160× over the one-hot gemm).  On TPU scatters
  historically lower poorly (serialized updates).
- one-hot matmul — builds the (n, k) indicator and rides the MXU.  The
  k-means header's historical choice on TPU.

No chip reading of the two lowerings against each other exists (ROADMAP
D6: ``SCATTER`` waits for a cell on either side).  The policy here is
the single place both consumers consult:

``DASK_ML_TPU_SCATTER`` = ``segsum`` | ``onehot`` | ``auto`` (default).
``auto`` picks ``onehot`` on TPU and ``segsum`` elsewhere, EXCEPT when
``num_segments`` is large (> 1024): a one-hot with that many columns is
memory-quadratic and loses everywhere (the 4096-bin sketch would build
an (n·d, 4096·d) indicator).  The strategy is read at TRACE time.

A HISTOGRAM (1-D values) over many buckets has a third lowering,
``onehot2``: split every id into ``(id // w, id % w)`` and take ONE
outer-product gemm of the two narrow indicators, ``(n, S/w)ᵀ @ (n, w)``
— the same ``2·n·S`` flops as the wide one-hot, from operands of
``S/w + w`` columns instead of ``S``.  Measured on a v5e at 25M rows
into 2049 buckets (PERF.md, PR 28): ``segment_sum`` 171 ms, ``onehot2``
6.7 ms, bit-equal on 0/1 weights.  ``scatter_strategy(n, histogram=True)``
returns it where the policy says one-hot and ``n`` is over the guard.

Reference analogue: dask's graph has no such choice — blockwise numpy
``np.add.at``/``bincount`` is the only lowering (SURVEY.md §2.1 #13).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_ONEHOT_MAX_SEGMENTS = 1024


def scatter_strategy(num_segments: int | None = None, *,
                     histogram: bool = False) -> str:
    """The platform policy, overridable via ``DASK_ML_TPU_SCATTER``.
    ``histogram``: the values are 1-D, so many buckets may take the
    two-level one-hot (``onehot2``) where one-hot is the policy."""
    from ..utils import env_choice

    v = env_choice("DASK_ML_TPU_SCATTER", ("auto", "segsum", "onehot"))
    # the large-segment guard binds even under the env override: forcing
    # onehot to A/B the k-means reduce must not make the 4096-bin sketch
    # build an (n·d, d·4096) indicator — that is an OOM, not a strategy
    large = num_segments is not None and num_segments > _ONEHOT_MAX_SEGMENTS
    if large and not histogram:
        return "segsum"
    if v == "auto":
        v = "onehot" if jax.default_backend() == "tpu" else "segsum"
    return "onehot2" if large and v == "onehot" else v


def _onehot2_sum(values, ids, num_segments: int, precision):
    """(n,) values into ``num_segments`` buckets by one outer-product gemm
    of two narrow indicators (module docstring)."""
    width = 1 << max((int(num_segments) - 1).bit_length() + 1 >> 1, 0)
    high = -(-num_segments // width)
    rows = jax.nn.one_hot(ids // width, high, dtype=values.dtype)
    cols = jax.nn.one_hot(ids % width, width, dtype=values.dtype)
    return jnp.dot((rows * values[:, None]).T, cols, precision=precision,
                   preferred_element_type=values.dtype
                   ).reshape(-1)[:num_segments]


def bucket_sum(values, ids, num_segments: int, *, precision=None,
               strategy: str | None = None):
    """Sum ``values`` ((n,) or (n, d)) into buckets given by ``ids``.

    Pre-weight ``values`` for weighted accumulation.  ``precision``
    applies to the one-hot gemm path only (segment_sum accumulates in
    full f32 natively, which is strictly at least as precise).

    ``strategy``: callers inside jitted code MUST resolve
    ``scatter_strategy`` OUTSIDE the jit and pass it through as a static
    argument — resolving here at trace time would bake the env value
    into the jit cache, so flipping ``DASK_ML_TPU_SCATTER`` in-process
    (the documented A/B use case) would silently keep the stale
    strategy.  ``None`` (eager callers) resolves at call time.  The
    large-segment OOM guard binds either way.
    """
    if getattr(values, "ndim", None) not in (1, 2):
        raise ValueError(
            f"values must be 1-d or 2-d, got ndim={getattr(values, 'ndim', None)}"
        )
    if getattr(ids, "ndim", None) != 1:
        raise ValueError(
            f"ids must be 1-d, got ndim={getattr(ids, 'ndim', None)}"
        )
    if values.shape[0] != ids.shape[0]:
        # the sharding-mismatch class: a row-sharded/padded `values` zipped
        # with an unpadded `ids` (or vice versa) silently misaligns rows to
        # buckets — surface it as shapes, at trace time, not as wrong sums
        raise ValueError(
            f"values and ids disagree on the row count: values has "
            f"{values.shape[0]} rows, ids has {ids.shape[0]} — were they "
            f"padded/sharded differently before the scatter?"
        )
    if strategy is None:
        strategy = scatter_strategy(num_segments)
    elif strategy not in ("segsum", "onehot", "onehot2"):
        # validate BEFORE the large-segment override: a typo from a
        # large-segment caller must surface, not silently coerce
        raise ValueError(
            f"strategy must be 'segsum', 'onehot' or 'onehot2', "
            f"got {strategy!r}"
        )
    elif num_segments > _ONEHOT_MAX_SEGMENTS and strategy == "onehot":
        strategy = "segsum"
    if strategy == "onehot2" and values.ndim == 1:
        return _onehot2_sum(values, ids, num_segments, precision)
    if strategy != "onehot":
        return jax.ops.segment_sum(values, ids, num_segments=num_segments)
    oh = jax.nn.one_hot(ids, num_segments, dtype=values.dtype)  # (n, k)
    if values.ndim == 1:
        return jnp.dot(oh.T, values[:, None], precision=precision,
                       preferred_element_type=values.dtype)[:, 0]
    return jnp.dot(oh.T, values, precision=precision,
                   preferred_element_type=values.dtype)
