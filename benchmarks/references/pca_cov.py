"""Plain reference for PCA: the eigenvectors of the centred covariance.

What ``PCA`` states it returns are the eigenvectors and eigenvalues of the
sample covariance of the rows (``explained_variance_`` with ``n - 1`` in
the denominator), largest first.  Here: the mean as sums over blocks of
rows, added up in float64 on the host; the covariance as ``(x_b - mu)'
(x_b - mu)``, a block of ``BLOCK_ROWS`` rows at a time in float32 under
``jax.default_matmul_precision("highest")``, the blocks' results added up
in float64 on the host; ``numpy.linalg.eigh`` in float64.  No QR, no
Cholesky, no repair: nothing of the program's route.  Every chip walks
its own rows and nothing of the table's size is made beside the table.
Imports nothing of ``dask_ml_tpu`` and takes nothing that it made.

A block is short on purpose: a float32 product that contracts over many
rows loses, along the MXU's K dimension, what lies under its accumulator's
last bit (PERF.md section 6, PRs 28 and 32), and the small eigenvalues
here are 256 times smaller than the large ones.

``precision="float32"`` is the reference.  ``precision="bfloat16"`` is the
control: the same sums with the table rounded to bfloat16 and every
product's operands (the centred rows) rounded to bfloat16, accumulated in
float32: the one-pass arithmetic a matmul at the TPU's default precision
does.  The rounding is ``lax.reduce_precision``, which no compiler may
drop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1024  # per product


def _bf16(x):
    """Round to bfloat16's 8 exponent and 7 mantissa bits, kept float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _local_pass(X, mu, *, bf16: bool, block: int, moment: int):
    """Over this device's rows, one block after the other: per block the
    sum of its rows (``moment`` 1) or ``(x - mu)'(x - mu)`` (``moment``
    2), stacked."""
    nb, tail = divmod(X.shape[0], block)

    def one(start, size):
        xb = jax.lax.dynamic_slice_in_dim(X, start, size, 0)
        if bf16:
            xb = _bf16(xb)
        if moment == 1:
            return jnp.sum(xb, axis=0)
        z = xb - mu
        if bf16:
            z = _bf16(z)
        with jax.default_matmul_precision("highest"):
            return z.T @ z

    parts = []
    if nb:
        parts.append(jax.lax.map(lambda i: one(i * block, block),
                                 jnp.arange(nb)))
    if tail:
        parts.append(one(nb * block, tail)[None])
    return jnp.concatenate(parts)


@partial(jax.jit, static_argnames=("bf16", "mesh", "block", "moment"))
def _pass(X, mu, *, bf16: bool, mesh, moment: int, block: int = BLOCK_ROWS):
    """``_local_pass`` on every chip's own rows (the table is row-sharded
    over ``mesh``'s one axis); the blocks of all chips come back stacked."""
    axis = mesh.axis_names[0]
    rows, whole = jax.sharding.PartitionSpec(axis), jax.sharding.PartitionSpec()
    return jax.shard_map(
        partial(_local_pass, bf16=bf16, block=block, moment=moment),
        mesh=mesh, in_specs=(rows, whole), out_specs=rows,
        check_vma=False)(X, mu)


def solve(X, *, bf16: bool = False):
    """The PCA of the row-sharded table ``X``, float64 on the host:
    ``mean`` (d,), ``variances`` (d,) largest first, ``components``
    (d, d), one a row, and ``rms_std``, the root of the mean variance."""
    n, d = X.shape
    mesh = X.sharding.mesh
    sums = np.asarray(_pass(X, jnp.zeros((d,), jnp.float32), bf16=bf16,
                            mesh=mesh, moment=1), np.float64).sum(axis=0)
    mean = sums / n
    scatter = np.asarray(_pass(X, jnp.asarray(mean, jnp.float32), bf16=bf16,
                               mesh=mesh, moment=2), np.float64).sum(axis=0)
    # centred by the float32 rounding of the mean: put back what the
    # rounding left, n (mean32 - mean)(mean32 - mean)'
    off = np.asarray(jnp.asarray(mean, jnp.float32), np.float64) - mean
    scatter -= n * np.outer(off, off)
    values, vectors = np.linalg.eigh(scatter / (n - 1))
    return {"mean": mean, "variances": values[::-1].copy(),
            "components": vectors[:, ::-1].T.copy(), "n": n,
            "rms_std": float(np.sqrt(values.mean()))}


def build(data, est_args: dict, precision: str = "float32"):
    """The reference's answer for this table, and what ``compare`` needs."""
    bf16 = {"float32": False, "bfloat16": True}[precision]
    return solve(data["X"], bf16=bf16)


def compare(ref, data, answer: dict, last: dict) -> dict:
    """Numbers compared for one fitted answer (smaller is closer)."""
    d = ref["mean"].shape[0]
    comps = np.asarray(answer["components_"], np.float64)
    variances = np.asarray(answer["explained_variance_"], np.float64)
    singular = np.asarray(answer["singular_values_"], np.float64)
    mean = np.asarray(answer["mean_"], np.float64)
    bad = {"variance_gap": float("inf"), "component_gap": float("inf"),
           "mean_gap": float("inf")}
    if (comps.shape != (d, d) or variances.shape != (d,)
            or singular.shape != (d,) or mean.shape != (d,)
            or not all(np.isfinite(v).all()
                       for v in (comps, variances, singular, mean))):
        return bad
    along = np.sum(comps * ref["components"], axis=1)  # v_i . ref_i
    sign = np.where(along < 0, -1.0, 1.0)
    return {
        # the largest relative error of an eigenvalue, as explained_variance_
        # gives it and as singular_values_ ** 2 / (n - 1) does
        "variance_gap": float(max(
            np.abs(v / ref["variances"] - 1.0).max()
            for v in (variances, singular ** 2 / (ref["n"] - 1)))),
        # the farthest a fitted component lies from the reference's, free
        # of sign: the sine of the angle between two unit vectors, and a
        # component that is not of unit length counts by what it lacks
        "component_gap": float(np.sqrt(np.sum(
            (comps * sign[:, None] - ref["components"]) ** 2, axis=1)).max()),
        # the largest error of a mean, in the table's RMS standard deviation
        "mean_gap": float(np.abs(mean - ref["mean"]).max() / ref["rms_std"]),
    }


def control_estimator(precision: str):
    """The reference in ``precision`` in the shape of an estimator, which a
    control reading puts in the program's place under the timed path:
    ``fit`` takes the program's row-sharded table (``.data``,
    ``.n_samples``: the rows as ``shard_rows`` laid them out) and leaves
    the attributes a fit leaves."""
    bf16 = {"float32": False, "bfloat16": True}[precision]

    class Control:
        def __init__(self, **est_args):
            self.est_args = est_args

        def fit(self, X, y=None):
            n = X.n_samples
            rows = X.data if X.data.shape[0] == n else X.data[:n]
            got = solve(jax.device_put(rows, _row_sharding(rows)), bf16=bf16)
            self.mean_ = got["mean"].astype(np.float32)
            self.components_ = got["components"].astype(np.float32)
            self.explained_variance_ = got["variances"].astype(np.float32)
            self.singular_values_ = np.sqrt(
                got["variances"] * (n - 1)).astype(np.float32)
            self.noise_variance_ = np.float32(0.0)
            self.n_passes_ = 2
            return self

    return Control


def _row_sharding(rows):
    """The rows' own devices under a mesh of one axis, as ``_pass`` wants
    it (the program's mesh has two)."""
    devices = np.array(sorted(rows.sharding.device_set, key=lambda d: d.id))
    return jax.sharding.NamedSharding(jax.sharding.Mesh(devices, ("rows",)),
                                      jax.sharding.PartitionSpec("rows"))
