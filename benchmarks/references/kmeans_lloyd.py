"""Plain reference for k-means: Lloyd's iteration to a fixed point.

What ``KMeans`` states it minimises is the inertia, the sum over the rows
of the squared distance to the nearest centre.  Lloyd's iteration from the
generator's own centres (``data["truth"]``) reaches, on blobs that stand
apart, the optimum every sound k-means run must find: assign each row to
its nearest centre, move each centre to the mean of its rows, until no
centre moves.  Distances are the plain ``sum((x - c) ** 2)``; the rows are
walked in blocks so that nothing of the table's size is made beside the
table, every chip walks its own rows, and the blocks' sums are added up on
the host in float64.  Imports nothing of ``dask_ml_tpu`` and takes nothing
that it made.

``precision="float32"`` is the reference.  ``precision="bfloat16"`` is the
control: the same iteration with the table and the centres rounded to
bfloat16 and the distances taken as ``|x|^2 + |c|^2 - 2 x.c`` from those
(float32 accumulation), the one-pass arithmetic a matmul at the TPU's
default precision does and a later PR would be tempted by.  The rounding
is ``lax.reduce_precision``, which no compiler may drop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 250_000  # per chip
MAX_STEPS = 100
_HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    """Round to bfloat16's 8 exponent and 7 mantissa bits, kept float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _d2(xb, c, bf16: bool):
    """(rows, k) squared distances of a block's rows to the centres."""
    if not bf16:
        return jnp.sum((xb[:, None, :] - c[None, :, :]) ** 2, axis=-1)
    xb, c = _bf16(xb), _bf16(c)
    return jnp.maximum(
        jnp.sum(xb * xb, axis=1)[:, None] + jnp.sum(c * c, axis=1)[None, :]
        - 2.0 * jnp.dot(xb, c.T, precision=_HI), 0.0)


def _local_pass(X, theirs, centres, at, perm, *, bf16: bool, block: int):
    """Over this device's rows, one block after the other: per block the
    sums and counts of the rows nearest to each of ``centres``, the sum of
    every row's least squared distance to ``at`` (another set of centres),
    and how many rows ``theirs`` (another assignment, renamed by
    ``perm``) puts elsewhere than ``centres`` does."""
    k = centres.shape[0]
    nb, tail = divmod(X.shape[0], block)

    def one(start, size):
        xb = jax.lax.dynamic_slice_in_dim(X, start, size, 0)
        tb = jax.lax.dynamic_slice_in_dim(theirs, start, size, 0)
        mine = jnp.argmin(_d2(xb, centres, bf16), axis=1)
        hot = jax.nn.one_hot(mine, k, dtype=xb.dtype)
        rows = _bf16(xb) if bf16 else xb
        return (jnp.dot(hot.T, rows, precision=_HI), jnp.sum(hot, axis=0),
                jnp.sum(jnp.min(_d2(xb, at, bf16), axis=1)),
                jnp.sum(perm[tb] != mine))

    parts = []
    if nb:
        parts.append(jax.lax.map(lambda i: one(i * block, block),
                                 jnp.arange(nb)))
    if tail:
        parts.append(jax.tree.map(lambda v: v[None], one(nb * block, tail)))
    return jax.tree.map(lambda *v: jnp.concatenate(v), *parts)


@partial(jax.jit, static_argnames=("bf16", "mesh", "block"))
def _pass(X, theirs, centres, at, perm, *, bf16: bool, mesh,
          block: int = BLOCK_ROWS):
    """``_local_pass`` on every chip's own rows (the table is row-sharded
    over ``mesh``'s one axis); the blocks of all chips come back stacked."""
    axis = mesh.axis_names[0]
    rows, whole = jax.sharding.PartitionSpec(axis), jax.sharding.PartitionSpec()
    return jax.shard_map(
        partial(_local_pass, bf16=bf16, block=block), mesh=mesh,
        in_specs=(rows, rows, whole, whole, whole), out_specs=rows,
        check_vma=False)(X, theirs, centres, at, perm)


def sweep(X, centres, *, at=None, theirs=None, perm=None, bf16=False):
    """One walk over the table, summed on the host in float64: the sums
    (k, d) and counts (k,) of the rows nearest to each of ``centres``, the
    inertia at ``at`` (default ``centres``), and the rows that ``theirs``
    (renamed by ``perm``) assigns otherwise."""
    k = centres.shape[0]
    mesh = X.sharding.mesh
    if theirs is None:
        theirs = jnp.zeros((X.shape[0],), jnp.int32, device=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names[0])))
    c = jnp.asarray(centres, jnp.float32)
    out = _pass(X, theirs, c, c if at is None else jnp.asarray(at, jnp.float32),
                jnp.asarray(np.arange(k) if perm is None else perm, jnp.int32),
                bf16=bf16, mesh=mesh)
    return tuple(np.asarray(v, np.float64).sum(axis=0) for v in out)


def lloyd(X, start, *, bf16: bool = False):
    """Lloyd from ``start`` until no centre moves: ``(centres, steps)``,
    the centres float64 on the host."""
    centres = np.asarray(start, np.float64)
    for step in range(1, MAX_STEPS + 1):
        sums, counts, _, _ = sweep(X, centres, bf16=bf16)
        moved = np.where(counts[:, None] > 0,
                         sums / np.maximum(counts, 1.0)[:, None], centres)
        if np.array_equal(moved.astype(np.float32),
                          centres.astype(np.float32)):
            break
        centres = moved
    return centres, step


def build(data, est_args: dict, precision: str = "float32"):
    """The reference's answer for this table, and what ``compare`` needs."""
    bf16 = {"float32": False, "bfloat16": True}[precision]
    X = data["X"]
    centres, steps = lloyd(X, data["truth"]["centers"], bf16=bf16)
    _, counts, inertia, _ = sweep(X, centres, bf16=bf16)
    return {"centers": centres, "inertia": inertia, "steps": steps,
            "counts": counts,
            # the RMS distance of a row from its centre: a blob's spread
            "spread": float(np.sqrt(inertia / X.shape[0]))}


def far_start(X, k: int, head: int = 100_000):
    """``k`` of the table's first ``head`` rows, each the farthest from
    those before it: one row of every blob where blobs stand apart."""
    rows = np.asarray(X[:head], np.float64)
    chosen, d2 = [0], ((rows - rows[0]) ** 2).sum(axis=1)
    for _ in range(k - 1):
        chosen.append(int(np.argmax(d2)))
        d2 = np.minimum(d2, ((rows - rows[chosen[-1]]) ** 2).sum(axis=1))
    return rows[chosen]


def control_estimator(precision: str):
    """The reference in ``precision`` in the shape of an estimator, which a
    control reading puts in the program's place under the timed path:
    ``fit`` takes the program's row-sharded table (``.data``,
    ``.n_samples``: the rows as ``shard_rows`` laid them out) and leaves
    the attributes a fit leaves.  It knows no generating centres, so it
    starts from ``far_start``."""
    bf16 = {"float32": False, "bfloat16": True}[precision]

    class Control:
        def __init__(self, **est_args):
            self.est_args = est_args

        def fit(self, X, y=None):
            n = X.n_samples
            rows = X.data if X.data.shape[0] == n else X.data[:n]
            rows = jax.device_put(rows, _row_sharding(rows))
            k = int(self.est_args.get("n_clusters", 8))
            centres, self.n_iter_ = lloyd(rows, far_start(rows, k), bf16=bf16)
            self.cluster_centers_ = centres.astype(np.float32)
            self.inertia_ = float(sweep(rows, centres, bf16=bf16)[2])
            self.labels_ = _labels(rows, jnp.asarray(centres, jnp.float32),
                                   bf16=bf16, mesh=rows.sharding.mesh)
            return self

    return Control


def _row_sharding(rows):
    """The rows' own devices under a mesh of one axis, as ``_pass`` wants
    it (the program's mesh has two)."""
    devices = np.array(sorted(rows.sharding.device_set, key=lambda d: d.id))
    return jax.sharding.NamedSharding(jax.sharding.Mesh(devices, ("rows",)),
                             jax.sharding.PartitionSpec("rows"))


@partial(jax.jit, static_argnames=("bf16", "mesh", "block"))
def _labels(X, centres, *, bf16: bool, mesh, block: int = BLOCK_ROWS):
    """Every row's nearest centre (the control's ``labels_``), every chip
    its own rows, block by block."""
    axis = mesh.axis_names[0]

    def local(X, centres):
        nb, tail = divmod(X.shape[0], block)

        def one(start, size):
            xb = jax.lax.dynamic_slice_in_dim(X, start, size, 0)
            return jnp.argmin(_d2(xb, centres, bf16), axis=1).astype(jnp.int32)

        parts = []
        if nb:
            parts.append(jax.lax.map(lambda i: one(i * block, block),
                                     jnp.arange(nb)).reshape(-1))
        if tail:
            parts.append(one(nb * block, tail))
        return jnp.concatenate(parts)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(
            jax.sharding.PartitionSpec(axis), jax.sharding.PartitionSpec()),
        out_specs=jax.sharding.PartitionSpec(axis), check_vma=False)(
        X, centres)


def match(centres, ref_centres):
    """For each fitted centre the reference centre nearest to it, or None
    where two fitted centres claim one (a blob split, another merged)."""
    d2 = ((centres[:, None, :] - ref_centres[None, :, :]) ** 2).sum(axis=-1)
    perm = d2.argmin(axis=1)
    return perm if len(set(perm.tolist())) == len(ref_centres) else None


def compare(ref, data, answer: dict, last: dict) -> dict:
    """Numbers compared for one fitted answer (smaller is closer)."""
    centres = np.asarray(answer["cluster_centers_"], np.float64)
    inertia = float(answer["inertia_"])
    bad = {"centre_gap": float("inf"), "inertia_gap": float("inf"),
           "label_mismatch": float("inf")}
    if (centres.shape != ref["centers"].shape
            or not np.isfinite(centres).all() or not np.isfinite(inertia)):
        return bad
    perm = match(centres, ref["centers"])
    if perm is None:
        return bad
    X, bad_labels = data["X"], False
    theirs = last.get("labels_")
    if theirs is not None and theirs.shape == (X.shape[0],):
        theirs = jax.device_put(
            jnp.asarray(theirs, jnp.int32), jax.sharding.NamedSharding(
                X.sharding.mesh, jax.sharding.PartitionSpec(
                    X.sharding.mesh.axis_names[0])))
    elif theirs is not None:  # labels of another table's length
        theirs, bad_labels = None, True
    _, _, at_theirs, differ = sweep(X, ref["centers"], at=centres,
                                    theirs=theirs, perm=perm)
    out = {
        # the farthest a fitted centre lies from the reference's, in
        # blob spreads
        "centre_gap": float(np.sqrt(
            ((centres - ref["centers"][perm]) ** 2).sum(axis=1)).max()
            / ref["spread"]),
        # the program's own inertia_ against the reference's evaluation of
        # the inertia AT the program's centres
        "inertia_gap": abs(inertia - at_theirs) / at_theirs,
    }
    if theirs is not None or bad_labels:
        # the share of the last fit's labels_ (kept on the device) that
        # differ from the reference's assignment, centres matched
        out["label_mismatch"] = (float("inf") if bad_labels
                                 else float(differ) / X.shape[0])
    return out
