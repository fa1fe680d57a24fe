"""Device-born table for a PCA: a Gaussian with a known spectrum, off the
origin, float32.

``X = Z diag(s) V' + mu``: ``Z`` standard normal (rows, features), ``V`` a
fixed orthogonal matrix, ``s`` a geometric sequence from ``s_max`` down to
``s_min`` (the standard deviations along the principal axes), ``mu``
uniform in ``center_box`` ^ features (``make_blobs``' own centre box).
Every principal component is well defined (neighbouring variances a fixed
ratio apart) and the mean is many of the small standard deviations, so an
answer feels both a centring taken too lightly and a table or a product
rounded to bfloat16 in its small eigenvalues.

The table's content comes from ``params["table_seed"]``, block by block
(``block_rows`` rows each, every block from its own key), in a fixed
order.  The run's key draws one sign for each feature column and the
column is multiplied by it: every seed poses the same problem mirrored in
some of its axes.  A sign flip is exact in floating point, and the sums,
the Gram matrices and the factorizations of the fit mirror with it, so
every seed drives the fit through the same arithmetic and the components
come out mirrored (why ``fit_s`` must not move with the seed:
``logistic_table.py``).

One jitted call makes the table.  Every device fills its own rows, one
block after the other in a loop that writes into the table in place, so a
device holds its share of the table and one block's temporaries and never
more, and nothing of O(rows) touches the host (``blobs_table.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, rows: int, params: dict, sharding_for):
    """Return ``{"X", "y", "truth"}``: X (rows, features) float32,
    row-sharded; ``y`` is None (nothing supervises a PCA); ``truth`` holds
    the generating mean, axes and standard deviations (host-sized; the
    sample's own differ from them by its sampling error, so no answer is
    compared with them)."""
    d = int(params["features"])
    lo, hi = params["center_box"]
    sharding = sharding_for(2)
    mesh, axis = sharding.mesh, sharding.mesh.axis_names[0]
    shards = mesh.shape[axis]
    block = min(int(params["block_rows"]), rows // shards)
    if rows % (block * shards):
        raise ValueError(f"{rows} rows over {shards} devices are not whole "
                         f"blocks of {block}")
    per_shard = rows // shards // block
    k_v, k_m, k_x = jax.random.split(
        jax.random.key(int(params["table_seed"])), 3)
    s = jnp.geomspace(float(params["s_max"]), float(params["s_min"]), d,
                      dtype=jnp.float32)
    mu = jax.random.uniform(k_m, (d,), jnp.float32, lo, hi)

    def local(k_x, axes, mu, signs):
        first = jax.lax.axis_index(axis) * per_shard  # this device's blocks

        def fill(i, table):
            z = jax.random.normal(
                jax.random.fold_in(k_x, first + i), (block, d), jnp.float32)
            rows_ = jnp.matmul(z, axes, precision=jax.lax.Precision.HIGHEST)
            return jax.lax.dynamic_update_slice(
                table, (rows_ + mu) * signs[None, :], (i * block, 0))

        return jax.lax.fori_loop(
            0, per_shard, fill, jnp.zeros((per_shard * block, d), jnp.float32))

    def table(key, k_v, k_x, mu):
        v, _ = jnp.linalg.qr(jax.random.normal(k_v, (d, d), jnp.float32))
        signs = jax.random.rademacher(key, (d,), jnp.float32)
        spec = jax.sharding.PartitionSpec
        X = jax.shard_map(
            local, mesh=mesh, in_specs=(spec(),) * 4,
            out_specs=spec(axis), check_vma=False)(
                k_x, s[:, None] * v.T, mu, signs)  # diag(s) V'
        return X, v, signs

    X, v, signs = jax.jit(table, out_shardings=(sharding, None, None))(
        key, k_v, k_x, mu)
    return {"X": X, "y": None,
            "truth": {"mean": mu * signs, "std": s,
                      "components": v.T * signs[None, :]}}
