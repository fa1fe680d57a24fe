"""Device-born table for a logistic model: standard-normal float32
features, labels drawn through ``sigmoid(X @ w + b)``.

Same idea as ``dask_ml_tpu.datasets.stream_classification_blocks``
(rows from ``jax.random``, labels through a logistic model), written
here so that the data depends on the keys alone.

The table's content comes from ``params["table_seed"]``, block by block
(``block_rows`` rows each, every block from its own key), in a fixed
order.  The run's key draws one sign for each feature column and the
column is multiplied by it: every seed poses the same problem mirrored in
some of its 28 axes.  A sign flip is exact in floating point, and every
product, sum and norm of the fit mirrors with it, so all seeds drive the
solver through the same steps bit for bit (the fitted coefficients come
out with those signs) and ``fit_s`` does not move with the seed.
Measured in PR 25: with the whole table drawn from the run seed fit_s
read 2.72 s on one seed and 3.57 to 3.67 s on five others; with the run
seed drawing the order of the blocks, the order changed how float32 sums
round, which picked one of a few solver paths 0.4% apart in fit_s, and
the driver's check refused the 1% bound over that spread.

One jitted call makes the table with the row sharding it is handed, so
no device ever holds more than its own rows and nothing of O(rows)
touches the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, rows: int, params: dict, sharding_for):
    """Return ``{"X", "y", "truth"}``: X (rows, features) float32 and y
    (rows,) float32 in {0, 1}, both row-sharded; ``truth`` holds the
    generating weights (host-sized)."""
    d = int(params["features"])
    block = min(int(params["block_rows"]), rows)
    if rows % block:
        raise ValueError(f"{rows} rows are not whole blocks of {block}")
    blocks = rows // block
    k_w, k_b, k_x, k_u = jax.random.split(
        jax.random.key(int(params["table_seed"])), 4)
    w = jax.random.normal(k_w, (d,), jnp.float32) * params["weight_scale"]
    b = jax.random.normal(k_b, (), jnp.float32) * params["intercept_scale"]

    def table(key, k_x, k_u, w, b):
        signs = jax.random.rademacher(key, (d,), jnp.float32)

        def one(block_id):
            X = jax.random.normal(
                jax.random.fold_in(k_x, block_id), (block, d), jnp.float32)
            # an elementwise product and a row sum: float32 on every
            # backend (a matmul would run in bfloat16 passes on the TPU)
            eta = jnp.sum(X * w[None, :], axis=1) + b
            u = jax.random.uniform(
                jax.random.fold_in(k_u, block_id), (block,), jnp.float32)
            y = (u < jax.nn.sigmoid(eta)).astype(jnp.float32)
            return X * signs[None, :], y

        X, y = jax.vmap(one)(jnp.arange(blocks))
        return X.reshape(rows, d), y.reshape(rows), signs

    X, y, signs = jax.jit(
        table, out_shardings=(sharding_for(2), sharding_for(1), None))(
        key, k_x, k_u, w, b)
    return {"X": X, "y": y, "truth": {"w": w * signs, "b": b}}
