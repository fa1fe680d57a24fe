"""Device-born table for k-means: isotropic Gaussian blobs, float32.

``sklearn.datasets.make_blobs`` / ``dask_ml.datasets.make_blobs`` at their
defaults, written here so that the data depends on the keys alone:
``centers`` generating centres uniform in ``center_box`` ^ features, every
row its centre plus ``cluster_std`` x a standard normal, the blobs of
equal size (row ``i`` of a block belongs to blob ``i % centers``, so every
chip's share holds every blob).

The table's content comes from ``params["table_seed"]``, block by block
(``block_rows`` rows each, every block from its own key), in a fixed
order.  The run's key draws one sign for each feature column and the
column is multiplied by it: every seed poses the same problem mirrored in
some of its 50 axes.  A sign flip is exact in floating point and squared
distances do not see it, so every seed drives k-means|| through the same
draws and rounds and Lloyd through the same iterations, and the centres
come out mirrored (why ``fit_s`` must not move with the seed:
``logistic_table.py``).

One jitted call makes the table.  Every device fills its own rows, one
block after the other in a loop that writes into the table in place, so a
device holds its share of the table and one block's temporaries and never
more (a ``vmap`` over the blocks would hold the normal draws of all of
them beside the table), and nothing of O(rows) touches the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, rows: int, params: dict, sharding_for):
    """Return ``{"X", "y", "truth"}``: X (rows, features) float32,
    row-sharded; ``y`` is None (nothing supervises a clustering);
    ``truth`` holds the generating centres and ``cluster_std``
    (host-sized)."""
    d, k = int(params["features"]), int(params["centers"])
    lo, hi = params["center_box"]
    std = float(params["cluster_std"])
    sharding = sharding_for(2)
    mesh, axis = sharding.mesh, sharding.mesh.axis_names[0]
    shards = mesh.shape[axis]
    block = min(int(params["block_rows"]), rows // shards)
    if rows % (block * shards) or block % k:
        raise ValueError(f"{rows} rows over {shards} devices are not whole "
                         f"blocks of {block}, or a block not whole rounds of "
                         f"{k} blobs")
    per_shard = rows // shards // block
    k_c, k_x = jax.random.split(jax.random.key(int(params["table_seed"])))
    centres = jax.random.uniform(k_c, (k, d), jnp.float32, lo, hi)

    def local(k_x, centres, signs):
        first = jax.lax.axis_index(axis) * per_shard  # this device's blocks
        of_row = centres[jnp.arange(block) % k]  # (block, d)

        def fill(i, table):
            noise = jax.random.normal(
                jax.random.fold_in(k_x, first + i), (block, d), jnp.float32)
            return jax.lax.dynamic_update_slice(
                table, (of_row + std * noise) * signs[None, :], (i * block, 0))

        return jax.lax.fori_loop(
            0, per_shard, fill, jnp.zeros((per_shard * block, d), jnp.float32))

    def table(key, k_x, centres):
        signs = jax.random.rademacher(key, (d,), jnp.float32)
        spec = jax.sharding.PartitionSpec
        X = jax.shard_map(
            local, mesh=mesh, in_specs=(spec(), spec(), spec()),
            out_specs=spec(axis), check_vma=False)(k_x, centres, signs)
        return X, signs

    X, signs = jax.jit(table, out_shardings=(sharding, None))(
        key, k_x, centres)
    return {"X": X, "y": None,
            "truth": {"centers": centres * signs[None, :], "cluster_std": std}}
