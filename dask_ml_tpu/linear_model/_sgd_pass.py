"""One ``partial_fit`` pass of an SGD estimator over a table resident on
the device, as ONE program.

``_partial.fit`` (so ``Incremental.fit`` / ``partial_fit``) cuts the rows
of a ``ShardedRows`` table into blocks of ``chunk_size`` and puts them in
their visit order (``shuffle_blocks`` / ``random_state``); where both
``X`` and ``y`` are resident and the estimator is this package's
``SGDClassifier`` or ``SGDRegressor`` it hands the blocks' first rows
here.  The program :data:`_pass_program` (``sgd.pass``, XLA module
``jit__sgd_pass``) ``lax.scan``s :func:`~._sgd.sgd_step` over those
starts: each step takes its block out of the resident table with
``lax.dynamic_slice_in_dim`` (no copy of the table), encodes the block's
labels on the device, and updates the donated state.  The host sends the
order, ``int32[blocks]``, and nothing else of the pass's size; it waits
for nothing inside a pass or between passes.

A block is rows ``[start, start + chunk)`` of the table.  Every slice the
program takes is ``chunk`` rows long (on a row-sharded mesh, at most a
shard), so the slice of the last, short block starts earlier than the
block: the rows it shares with the block before it are masked out by row
index, as are pad rows, and train nothing.  The two products of a step,
``xb @ coef`` and ``xb.T @ dmarg``, are asked for at ``highest`` matmul
precision: float32, where the TPU's default would feed a matrix product
bfloat16 inputs.

On a mesh of several row shards each shard steps on its own rows of the
block, and the shards' new states are averaged, weighted by their rows
of the block.  A step is affine in the block's mean gradient, so that
average IS the step on the whole block (a shard without rows of it
weighs 0); a block inside one shard takes that shard's step as it is.
The answer is the host stream's over the same blocks to float32
rounding, and no table byte moves between shards or to the host.
"""

from __future__ import annotations

import math
import weakref

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from .. import programs as _programs
from ..core.compat import shard_map_unchecked
from ..core.mesh import MeshHolder
from ..core.sharded import ShardedRows
from ._sgd import (SGDClassifier, SGDRegressor, labels_outside, ova_targets,
                   sgd_step)

__all__ = ["takes", "run"]


def _row_axes(x: ShardedRows):
    """The mesh axes ``x``'s rows are split over, or None where its
    layout is not rows-over-axes, every other axis whole."""
    sharding = x.data.sharding
    if not isinstance(sharding, NamedSharding):
        return None
    spec = tuple(sharding.spec) + (None,) * (x.data.ndim - len(sharding.spec))
    rows, rest = spec[0], spec[1:]
    if rows is None or any(a is not None for a in rest):
        return None
    return tuple(rows) if isinstance(rows, (tuple, list)) else (rows,)


def takes(model, x, y, fit_kwargs) -> bool:
    """Whether a pass of ``model.partial_fit`` over ``(x, y)`` runs here:
    this package's SGD estimators, with rows and labels resident and
    aligned, a floating table, and no per-row weights (``sample_weight``,
    ``class_weight``: those keep the host stream)."""
    if not isinstance(model, (SGDClassifier, SGDRegressor)):
        return False
    if set(fit_kwargs) - {"classes"}:
        return False
    if getattr(model, "class_weight", None) is not None:
        return False
    if not (isinstance(x, ShardedRows) and isinstance(y, ShardedRows)):
        return False
    if x.data.ndim != 2 or y.data.ndim != 1:
        return False
    if not jnp.issubdtype(x.data.dtype, jnp.floating):
        return False
    rows = x.data.shape[0]
    if (y.data.shape[0], x.mask.shape[0], y.n_samples) != (
            rows, rows, x.n_samples):
        return False
    axes = _row_axes(x)
    return axes is not None and y.data.sharding.is_equivalent_to(
        NamedSharding(x.data.sharding.mesh, P(axes)), 1)


def _sgd_pass(state, x, y, mask, starts, hyper, classes, *, block, targets,
              loss, penalty, schedule, fit_intercept, mesh_holder, axes):
    """``lax.scan`` of :func:`sgd_step` over the blocks that start at
    ``starts``, in that order (see the module docstring)."""
    mesh = mesh_holder.mesh
    shards = math.prod(mesh.shape[a] for a in axes)
    local = x.shape[0] // shards
    size = min(block, local)
    step = dict(loss=loss, penalty=penalty, schedule=schedule,
                fit_intercept=fit_intercept)

    def encode(yb, classes):
        if targets == "ova":
            return ova_targets(yb, classes)
        return yb.astype(jnp.float32)[:, None]

    def shard_pass(state, x, y, mask, starts, hyper, classes):
        first = lax.axis_index(axes) * local if shards > 1 else 0
        offsets = jnp.arange(size, dtype=jnp.int32)

        def body(st, start):
            at = jnp.clip(start - first, 0, local - size)
            rows = first + at + offsets
            own = (rows >= start) & (rows < start + block)
            mb = jnp.where(own, lax.dynamic_slice_in_dim(mask, at, size), 0)
            xb = lax.dynamic_slice_in_dim(x, at, size)
            yb = encode(lax.dynamic_slice_in_dim(y, at, size), classes)
            # the step's mean loss is left unused, so it is never
            # computed: a pass returns the state alone
            new, _ = sgd_step(st, xb, yb, mb, hyper, **step)
            if shards > 1:
                n = jnp.sum(mb)
                share = n / lax.psum(n, axes)
                new = {k: st[k] + lax.psum(share * (new[k] - st[k]), axes)
                       for k in ("coef", "intercept")} | {"t": new["t"]}
            return new, None

        with jax.default_matmul_precision("highest"):
            return lax.scan(body, state, starts)[0]

    rows_spec = P(axes)
    return shard_map_unchecked(
        shard_pass, mesh,
        in_specs=(P(), P(axes, None), rows_spec, rows_spec, P(), P(), P()),
        out_specs=P(),
    )(state, x, y, mask, starts, hyper, classes)


_pass_program = _programs.cached_program(
    _sgd_pass, name="sgd.pass",
    static_argnames=("block", "targets", "loss", "penalty", "schedule",
                     "fit_intercept", "mesh_holder", "axes"),
    donate_argnames=("state",),
)


# graftlint: disable=donation-miss -- the output is one scalar; the labels are the caller's, read by every later pass
_labels_outside = _programs.cached_program(
    labels_outside, name="sgd.labels_check")

#: label arrays already checked against a class set: ``id(labels) ->
#: (weak reference, classes)``.  A jax array never changes, so a table's
#: labels are read once for their check, not once a pass.
_CHECKED: dict = {}


def _check_labels(model, y: ShardedRows, classes) -> int:
    """Raise where a real row's label is not in ``classes_``; return the
    waits on the device this took (1 the first time these labels meet
    this class set, else 0)."""
    key = np.asarray(model.classes_).tobytes()
    seen = _CHECKED.get(id(y.data))
    if seen is not None and seen[0]() is y.data and seen[1] == key:
        return 0
    if float(_labels_outside(y.data, y.mask, classes)) > 0:
        raise ValueError("y contains labels not in `classes`")
    for k in [k for k, (ref, _) in _CHECKED.items() if ref() is None]:
        del _CHECKED[k]
    _CHECKED[id(y.data)] = (weakref.ref(y.data), key)
    return 1


def run(model, x: ShardedRows, y: ShardedRows, starts, block: int,
        fit_kwargs):
    """One pass of ``model.partial_fit`` over the blocks of ``(x, y)``
    that start at ``starts``, in that order, as one dispatch of
    ``sgd.pass``; the state stays on the device.  Span
    ``incremental.pass``: ``blocks``, ``table_host_mb`` (bytes of table
    or labels brought to the host, MB) and ``host_waits`` (waits of the
    host on the device inside the pass)."""
    model._validate()
    classifier = isinstance(model, SGDClassifier)
    if classifier and not hasattr(model, "classes_"):
        if fit_kwargs.get("classes") is None:
            raise ValueError(
                "classes must be passed on the first partial_fit call")
        model._set_classes(fit_kwargs["classes"])
    mesh = x.data.sharding.mesh
    with obs.span("incremental.pass", estimator=type(model).__name__,
                  blocks=len(starts), block_rows=int(block)) as span:
        classes, waits = np.zeros(2, y.data.dtype), 0
        if classifier:
            classes = np.asarray(model.classes_, y.data.dtype)
            waits += _check_labels(model, y, jnp.asarray(classes))
        model._ensure_state(x.data.shape[1])
        # every operand but the table whole on each device of the table's
        # mesh, as the program's outputs come: a fresh state and a carried
        # one are then one signature, and the pass one compile
        state, hyper, classes, starts = jax.device_put(
            (model._state, model._hyper(), classes,
             np.asarray(starts, np.int32)), NamedSharding(mesh, P()))
        model._state = _pass_program(
            state, x.data, y.data, x.mask, starts, hyper, classes,
            block=int(block), targets="ova" if classifier else "value",
            loss=model.loss, penalty=model.penalty,
            schedule=model.learning_rate, fit_intercept=model.fit_intercept,
            mesh_holder=MeshHolder(mesh), axes=_row_axes(x))
        span.set(table_host_mb=0.0, host_waits=waits)
    return model
