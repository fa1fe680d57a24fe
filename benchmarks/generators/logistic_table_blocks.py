"""``logistic_table``'s table, made one block at a time on every chip.

The same model, keys and parameters as ``logistic_table.py`` (read its
docstring: rows from ``params["table_seed"]`` block by block in a fixed
order, labels through ``sigmoid(X @ w + b)``, the run's key drawing one
sign for each feature column), for a chip's share too large to be drawn
in one piece.  There all blocks are one ``vmap``; the TPU's compiler then
holds the random bits of ALL of a chip's rows twice beside the table (at
62,500,000 x 28 a chip 20.66 GB of the 15.75 it has: refused at compile,
PERF.md section 6, PR 34).  Here every chip walks its own blocks in a
loop and writes each into its place, so one block's temporaries are all
that is ever made beside the table.

``X`` is ``logistic_table``'s bit for bit (block ``i`` comes from the
same key); a label can differ where ``u`` and ``sigmoid(eta)`` meet in
the last bit, since the row sums may be added up in another order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def make(key, rows: int, params: dict, sharding_for):
    """Return ``{"X", "y", "truth"}``: X (rows, features) float32 and y
    (rows,) float32 in {0, 1}, both row-sharded; ``truth`` holds the
    generating weights (host-sized)."""
    d = int(params["features"])
    mesh = sharding_for(2).mesh
    axis = mesh.axis_names[0]
    block = min(int(params["block_rows"]), rows // mesh.size)
    if rows % (block * mesh.size):
        raise ValueError(f"{rows} rows are not whole blocks of {block} on "
                         f"each of {mesh.size} chips")
    per_chip = rows // block // mesh.size
    k_w, k_b, k_x, k_u = jax.random.split(
        jax.random.key(int(params["table_seed"])), 4)
    w = jax.random.normal(k_w, (d,), jnp.float32) * params["weight_scale"]
    b = jax.random.normal(k_b, (), jnp.float32) * params["intercept_scale"]

    def local(signs, k_x, k_u, w, b):
        """This chip's rows: its run of blocks, one after the other."""
        first = jax.lax.axis_index(axis) * per_chip

        def one(i, table):
            X, y = table
            Xb = jax.random.normal(
                jax.random.fold_in(k_x, first + i), (block, d), jnp.float32)
            # an elementwise product and a row sum: float32 on every
            # backend (a matmul would run in bfloat16 passes on the TPU)
            eta = jnp.sum(Xb * w[None, :], axis=1) + b
            u = jax.random.uniform(
                jax.random.fold_in(k_u, first + i), (block,), jnp.float32)
            yb = (u < jax.nn.sigmoid(eta)).astype(jnp.float32)
            return (jax.lax.dynamic_update_slice_in_dim(
                        X, Xb * signs[None, :], i * block, 0),
                    jax.lax.dynamic_update_slice_in_dim(y, yb, i * block, 0))

        return jax.lax.fori_loop(
            0, per_chip, one,
            (jnp.zeros((per_chip * block, d), jnp.float32),
             jnp.zeros((per_chip * block,), jnp.float32)))

    def table(key, k_x, k_u, w, b):
        signs = jax.random.rademacher(key, (d,), jnp.float32)
        X, y = jax.shard_map(
            local, mesh=mesh, in_specs=PartitionSpec(),
            out_specs=(PartitionSpec(axis, None), PartitionSpec(axis)),
            check_vma=False)(signs, k_x, k_u, w, b)
        return X, y, signs

    X, y, signs = jax.jit(
        table, out_shardings=(sharding_for(2), sharding_for(1), None))(
        key, k_x, k_u, w, b)
    return {"X": X, "y": y, "truth": {"w": w * signs, "b": b}}
