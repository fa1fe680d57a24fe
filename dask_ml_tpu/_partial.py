"""Sequential partial_fit engine — twin of ``dask_ml/_partial.py``.

The reference builds a linear task chain (model₀ →partial_fit(block₀)→
model₁ → …) so a stateful estimator streams over blocks *inside the dask
graph*, with the model hopping worker→worker.  On TPU the inversion is the
design (SURVEY.md §3.5): the model state stays put (device arrays for our
estimators, host object for wrapped sklearn estimators) and the data
streams through in row chunks.

The stream rides :mod:`dask_ml_tpu.pipeline`: block *k+1*'s slice/parse
and host→device staging run on a prefetch thread while block *k*'s
device step executes (``DASK_ML_TPU_PREFETCH_DEPTH``; 0 restores the
strictly serial seed behavior).  ``x`` may also be an ITERATOR of blocks
(``io.stream_csv_blocks``, ``io.stream_binary_blocks``, or any generator
yielding ``X`` or ``(X, y)``) for out-of-core streams that never exist
as one array.

A table held on the device takes no stream: where ``x`` and ``y`` are
both ``ShardedRows`` and the model is this package's ``SGDClassifier``
or ``SGDRegressor`` without per-row weights, :func:`fit` makes the pass
ONE program over the resident blocks (``linear_model/_sgd_pass.py``).
What that pass guarantees: every real row trains the model once, in its
block; the blocks come in the order the stream would visit them (the
same ``shuffle_blocks`` / ``random_state`` rule); one SGD step a block,
the state carried across calls and never reinitialised by one; nothing
of the table's size crosses to the host (the host sends the blocks'
order); the answer is the host stream's over the same blocks to float32
rounding.
"""

from __future__ import annotations

import logging

import numpy as np

from . import obs
from .core.sharded import ShardedRows, unshard
from .utils import check_chunks, check_random_state

logger = logging.getLogger(__name__)


def _row_chunks(n: int, chunk_size: int):
    for start in range(0, n, chunk_size):
        yield start, min(start + chunk_size, n)


def _iter_block_pairs(x):
    """Normalize an iterator source's items to ``(X, y_or_None)``."""
    for item in x:
        if isinstance(item, tuple):
            if len(item) != 2:
                raise ValueError(
                    f"block tuples must be (X, y); got length {len(item)}"
                )
            yield item
        else:
            yield item, None


def fit(model, x, y=None, *, chunk_size: int | None = None, shuffle_blocks=False,
        random_state=None, prefetch_depth: int | None = None, **kwargs):
    """Stream row chunks of (x, y) through ``model.partial_fit`` in order.

    Reference: ``dask_ml/_partial.py :: fit``.  ``shuffle_blocks`` permutes
    the chunk visit order (the reference shuffles dask blocks the same way).
    ``chunk_size`` defaults to the shared device bucket size so
    default-chunk streams pad zero extra rows per ``partial_fit``.

    ``x`` may be an iterator/generator of blocks (each ``X`` or
    ``(X, y)``); then ``y`` must be None (targets ride the stream) and
    ``shuffle_blocks`` is IGNORED — a one-shot stream has no random
    access to permute, and ``Incremental``'s default (True) must not
    make direct reader feeds error; blocks train in stream order.

    ``x`` may also be a sharded dataset (:mod:`dask_ml_tpu.data` —
    anything with the ``iter_blocks`` protocol): targets ride the
    dataset's columns, its N parallel readers feed the prefetch worker
    through the merge queue, and ``shuffle_blocks`` is likewise ignored
    — the dataset owns the GLOBAL key-derived per-epoch shuffle (every
    epoch a deterministic permutation; no shuffle buffer in host RAM).
    ``prefetch_depth`` (default: the ``DASK_ML_TPU_PREFETCH_DEPTH``
    knob) overlaps the next block's parse + H2D staging with the
    current block's device step; results are bit-identical at every
    depth.
    """
    from .pipeline import stream_partial_fit

    if hasattr(x, "iter_blocks"):  # sharded dataset (dask_ml_tpu.data)
        if y is not None:
            raise ValueError(
                "with a sharded dataset, y must ride the dataset's "
                "columns, not be passed separately"
            )
        if shuffle_blocks:
            logger.debug(
                "shuffle_blocks ignored for a dataset source: the "
                "dataset owns its key-derived global shuffle"
            )
        with obs.span("fit", estimator=type(model).__name__,
                      source="dataset"):
            return stream_partial_fit(
                model, x, depth=prefetch_depth, fit_kwargs=kwargs,
            )

    if hasattr(x, "__next__"):
        if y is not None:
            raise ValueError(
                "with an iterator of blocks, y must ride the stream as "
                "(X, y) tuples, not be passed separately"
            )
        if shuffle_blocks:
            logger.debug(
                "shuffle_blocks ignored for an iterator source: a "
                "one-shot stream has no random access to permute"
            )
        with obs.span("fit", estimator=type(model).__name__,
                      source="iterator"):
            return stream_partial_fit(
                model, _iter_block_pairs(x), depth=prefetch_depth,
                fit_kwargs=kwargs,
            )

    resident = False
    if isinstance(x, ShardedRows):
        from .linear_model import _sgd_pass

        resident = _sgd_pass.takes(model, x, y, kwargs)

    def host(a):
        return unshard(a) if isinstance(a, ShardedRows) else np.asarray(a)

    xv = x if resident else host(x)
    n = xv.shape[0]
    if chunk_size is None:
        from .linear_model._sgd import DEFAULT_STREAM_CHUNK

        chunk_size = DEFAULT_STREAM_CHUNK
    else:
        # accept dask-style (rows, cols) specs too; validates positivity
        chunk_size = check_chunks(
            n, xv.shape[1] if len(xv.shape) > 1 else None, chunk_size
        )
    yv = None
    if y is not None:
        yv = y if resident else host(y)
        if yv.shape[0] != n:
            raise ValueError(
                f"x and y have different lengths: {n} vs {yv.shape[0]}"
            )
    spans = list(_row_chunks(n, chunk_size))
    if shuffle_blocks:
        rng = check_random_state(random_state)
        rng.shuffle(spans)
    if resident:
        return _sgd_pass.run(model, xv, yv, [lo for lo, _ in spans],
                             chunk_size, kwargs)

    def _blocks():
        for i, (lo, hi) in enumerate(spans):
            logger.debug("partial_fit chunk %d/%d", i + 1, len(spans))
            yield xv[lo:hi], (None if yv is None else yv[lo:hi])

    with obs.span("fit", estimator=type(model).__name__,
                  blocks=len(spans)):
        return stream_partial_fit(
            model, _blocks(), depth=prefetch_depth, fit_kwargs=kwargs,
        )


def _x_only(stream):
    """Feature blocks of a dataset stream (targets dropped — inference
    has no use for them); closes the stream's readers on exit."""
    try:
        for blk in stream:
            yield blk[0] if isinstance(blk, tuple) else blk
    finally:
        close = getattr(stream, "close", None)
        if close is not None:
            close()


def stage_predict_block(xb, policy):
    """Host-side bucket pad of ONE predict block: returns ``(block,
    n_real)`` where ``n_real`` is the real row count to slice back from
    the padded predictions, or ``(block, None)`` for blocks the pad must
    not touch (device-resident input, non-2-D hosts, no-op pads).

    The ONE predict-staging entry the offline plane
    (:func:`predict`'s prefetch stage) and the online serve plane
    (``serve/batcher.py``) share, so the bucket discipline — and the
    slice-back contract — cannot drift between them.  Row-wise
    inference makes the pad exact: padding rows never influence real
    rows' outputs.  Safe on a host worker thread: numpy + the
    ``bucket.*`` counters only."""
    import jax.numpy as jnp

    from . import programs

    if isinstance(xb, (ShardedRows, jnp.ndarray)):
        return xb, None
    xa = np.asarray(xb)
    if xa.ndim != 2:
        return xb, None
    padded, _, _ = programs.pad_block(xa, policy=policy)
    return padded, (None if padded is xa else xa.shape[0])


def predict(model, x, *, chunk_size: int = 100_000,
            prefetch_depth: int | None = None):
    """Chunked predict (reference ``_partial.predict``: blockwise).

    ``x`` may be an iterator of blocks (out-of-core inference); array
    input is sliced as before.  The prefetch thread pulls/parses block
    k+1 while the model predicts block k.

    Device-native models get the shape-bucketing policy on the way in
    (``DASK_ML_TPU_BUCKET``, design.md §12): ragged tail blocks pad up
    to a bucket on the prefetch worker and the padded predictions are
    sliced back, so a variable-chunk inference stream resolves to the
    same few compiled shapes a training stream does.  Row-wise
    inference makes the pad exact — padding rows never influence real
    rows' outputs.
    """
    from . import programs
    from .base import TPUEstimator
    from .pipeline import prefetch_blocks

    if hasattr(x, "iter_blocks"):  # sharded dataset: predict over X
        blocks = _x_only(x.iter_blocks())
    elif hasattr(x, "__next__"):
        blocks = x
    else:
        xv = unshard(x) if isinstance(x, ShardedRows) else np.asarray(x)
        blocks = (xv[lo:hi] for lo, hi in _row_chunks(xv.shape[0], chunk_size))

    policy = programs.resolve_policy()
    bucketed = policy.kind != "off" and isinstance(model, TPUEstimator)

    def _stage(xb):
        """Host-side bucket pad (prefetch worker) — the shared
        :func:`stage_predict_block` discipline, gated on the model
        being device-native (host estimators see raw blocks)."""
        if not bucketed:
            return xb, None
        return stage_predict_block(xb, policy)

    with obs.span("predict", estimator=type(model).__name__):
        outs = []
        for xb, n in prefetch_blocks(blocks, depth=prefetch_depth,
                                     stage=_stage, label="partial_predict"):
            p = np.asarray(model.predict(xb))
            outs.append(p if n is None else p[:n])
    return np.concatenate(outs)
