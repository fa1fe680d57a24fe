"""Synthetic datasets — twin of ``dask_ml/datasets.py`` (SURVEY.md §2 #19:
``make_classification``, ``make_regression``, ``make_blobs``,
``make_counts``, ``make_classification_df``).

The reference calls sklearn's generators once per dask block with per-block
seeds; here each chunk is generated the same way on the host and the result
is ingested as one row-sharded device array (``chunks`` keeps the reference
signature and controls generation batch size / seeding granularity).
"""

from __future__ import annotations

import numpy as np
import sklearn.datasets as skd

from .core.mesh import get_mesh
from .core.sharded import shard_rows
from .utils import draw_seed


def _chunk_sizes(n_samples, chunks):
    if chunks is None:
        return [n_samples]
    if isinstance(chunks, (int, np.integer)):
        sizes = [int(chunks)] * (n_samples // int(chunks))
        if n_samples % int(chunks):
            sizes.append(n_samples % int(chunks))
        return sizes
    return list(chunks)


def _seeds(random_state, n_chunks):
    """n_chunks chunk seeds + one extra seed for global structure (centers /
    coefficients), all from one stream so nothing aliases."""
    all_seeds = draw_seed(random_state, size=n_chunks + 1)
    return all_seeds[:-1], int(all_seeds[-1])


def _generate(gen, n_samples, chunks, random_state, seeds=None, **kwargs):
    sizes = _chunk_sizes(n_samples, chunks)
    if seeds is None:
        seeds, _ = _seeds(random_state, len(sizes))
    Xs, ys = [], []
    for size, seed in zip(sizes, seeds):
        X, y = gen(n_samples=int(size), random_state=int(seed), **kwargs)
        Xs.append(X)
        ys.append(y)
    X = np.concatenate(Xs).astype(np.float32)
    y = np.concatenate(ys)
    mesh = get_mesh()
    return shard_rows(X, mesh), shard_rows(y, mesh)


def make_classification(n_samples=100, n_features=20, n_informative=2,
                        n_classes=2, chunks=None, random_state=None, **kwargs):
    return _generate(
        skd.make_classification, n_samples, chunks, random_state,
        n_features=n_features, n_informative=n_informative,
        n_classes=n_classes, **kwargs,
    )


def make_regression(n_samples=100, n_features=100, n_informative=10,
                    chunks=None, random_state=None, **kwargs):
    return _generate(
        skd.make_regression, n_samples, chunks, random_state,
        n_features=n_features, n_informative=n_informative, **kwargs,
    )


def make_blobs(n_samples=100, n_features=2, centers=None, cluster_std=1.0,
               chunks=None, random_state=None, **kwargs):
    if centers is None:
        centers = 3
    chunk_seeds, center_seed = _seeds(random_state, len(_chunk_sizes(n_samples, chunks)))
    if isinstance(centers, (int, np.integer)):
        # fix the centers across chunks (reference does the same: sample
        # centers once, then generate per block) — the centers seed comes
        # from the same stream as chunk seeds so nothing aliases
        rng = np.random.RandomState(center_seed)
        centers = rng.uniform(-10, 10, size=(int(centers), n_features))
    return _generate(
        skd.make_blobs, n_samples, chunks, random_state, seeds=chunk_seeds,
        n_features=n_features, centers=centers, cluster_std=cluster_std,
        **kwargs,
    )


def make_counts(n_samples=100, n_features=20, n_informative=10, scale=1.0,
                chunks=None, random_state=None):
    """Poisson-count regression targets (reference ``make_counts``).

    The coefficient vector is drawn once; X and the Poisson draws are
    generated per chunk with per-chunk seeds like the other generators.
    """
    n_informative = min(n_informative, n_features)
    sizes = _chunk_sizes(n_samples, chunks)
    seeds, coef_seed = _seeds(random_state, len(sizes))
    coef_rng = np.random.RandomState(coef_seed)
    coef = np.zeros(n_features)
    coef[:n_informative] = coef_rng.normal(0, 1, size=n_informative)
    Xs, ys = [], []
    for size, seed in zip(sizes, seeds):
        rng = np.random.RandomState(int(seed))
        Xc = rng.normal(0, 1, size=(int(size), n_features)).astype(np.float32)
        rate = np.exp(np.clip(Xc @ coef * scale, -20, 20))
        Xs.append(Xc)
        ys.append(rng.poisson(rate))
    X = np.concatenate(Xs)
    y = np.concatenate(ys)
    mesh = get_mesh()
    return shard_rows(X, mesh), shard_rows(y.astype(np.float32), mesh)


def make_classification_df(n_samples=100, n_features=20, chunks=None,
                           random_state=None, dates=None,
                           feature_prefix="feature_", target_name="target",
                           **kwargs):
    """Classification data as a (DataFrame, Series) pair — twin of
    ``dask_ml/datasets.py :: make_classification_df`` (named feature
    columns; optional ``dates=(start, end)`` adds a random ``date`` column,
    the reference's time-series-flavored knob).  Chunk seeding matches
    :func:`make_classification` exactly."""
    import pandas as pd

    Xs, ys = make_classification(
        n_samples=n_samples, n_features=n_features, chunks=chunks,
        random_state=random_state, **kwargs,
    )
    from .core.sharded import unshard

    X = unshard(Xs)
    y = unshard(ys).astype(np.int64)
    columns = [f"{feature_prefix}{i}" for i in range(n_features)]
    df = pd.DataFrame(X, columns=columns)
    if dates is not None:
        start, end = dates
        # the dates seed must not alias any chunk/global seed consumed by
        # make_classification's _seeds(random_state, n_chunks + 1): draw
        # one PAST that range from the same stream
        n_chunks = len(_chunk_sizes(n_samples, chunks))
        rng = np.random.RandomState(
            int(draw_seed(random_state, size=n_chunks + 2)[-1])
        )
        stamps = pd.to_datetime(start) + pd.to_timedelta(
            rng.uniform(
                0, (pd.to_datetime(end) - pd.to_datetime(start)).total_seconds(),
                size=n_samples,
            ),
            unit="s",
        )
        df.insert(0, "date", stamps)
    return df, pd.Series(y, name=target_name)


def stream_classification_blocks(n_blocks, block_rows, n_features, *,
                                 seed=0, coef=None):
    """Yield device-resident synthetic classification blocks, one at a
    time — the ingest-free stream behind the >device-memory fit story
    (SURVEY.md §7 hard-part (b)).

    Each block is generated ON DEVICE by one jitted program (per-block
    PRNG fold-in, ``jax.random``) and is dropped as soon as the consumer
    releases it, so a stream of ``n_blocks * block_rows`` rows can far
    exceed HBM while only one block is ever live.  ``block_rows`` should
    be one of the SGD bucket sizes (``linear_model._sgd._BUCKETS``) so
    the consuming ``partial_fit`` compiles exactly one program.

    Reference: ``dask_ml/datasets.py`` generates chunked synthetic data
    lazily per block with per-block seeds; here the blocks are born on
    the accelerator instead of being uploaded from the host.

    Yields ``(X, y)`` as :class:`~dask_ml_tpu.core.sharded.ShardedRows`
    with full masks.
    """
    import jax
    import jax.numpy as jnp

    from .core.sharded import ShardedRows

    key = jax.random.PRNGKey(seed)
    kw, key = jax.random.split(key)
    w = (jax.random.normal(kw, (n_features,), jnp.float32)
         if coef is None else jnp.asarray(coef, jnp.float32))

    @jax.jit
    def gen(k):
        kx, ku = jax.random.split(k)
        X = jax.random.normal(kx, (block_rows, n_features), jnp.float32)
        p = jax.nn.sigmoid(X @ w)
        y = (p > jax.random.uniform(ku, (block_rows,))).astype(jnp.float32)
        return X, y

    mask = jnp.ones((block_rows,), jnp.float32)
    for i in range(n_blocks):
        Xb, yb = gen(jax.random.fold_in(key, i))
        yield (
            ShardedRows(data=Xb, mask=mask, n_samples=block_rows),
            ShardedRows(data=yb, mask=mask, n_samples=block_rows),
        )
