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
bfloat16 inputs.  Each product is a read of the block.

Where :func:`one_read` holds (a float32 table on TPUs with its rows
minor, shapes :mod:`._sgd_kernel` covers), a step instead takes its sums
from :func:`._sgd_kernel.block_sums`, a Pallas kernel inside the same
program that reads each of the block's rows once and gets the margins,
their loss derivatives and the gradient from that one read; the update
(:func:`~._sgd.sgd_update`) and the losses are :func:`sgd_step`'s own.
The kernel walks the block's own rows, so the short last block takes no
earlier rows.  The span says which step ran (``step_kernel``), and so
does the registry (``sgd.pass_kernel``, tagged ``pallas`` / ``xla``).

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
from ..utils import safe_denominator
from . import _sgd_kernel
from ._sgd import (SGDClassifier, SGDRegressor, labels_outside, ova_targets,
                   sgd_step, sgd_update)

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


def one_read(x: ShardedRows, y: ShardedRows, outputs: int) -> bool:
    """Whether a pass's steps take their sums from one read of each
    block (:mod:`._sgd_kernel`): a float32 table and mask and float32 or
    int32 labels on TPUs, the table with its rows minor, so that its
    transpose is a bitcast, and shapes the kernel covers."""
    data = x.data
    if any(d.platform != "tpu" for d in data.sharding.device_set):
        return False
    if (data.dtype, x.mask.dtype) != (jnp.float32, jnp.float32) or (
            y.data.dtype not in (jnp.float32, jnp.int32)):
        return False
    layout = data.format.layout
    if layout is None or tuple(layout.major_to_minor) != (1, 0):
        return False
    shards = math.prod(data.sharding.mesh.shape[a] for a in _row_axes(x))
    return _sgd_kernel.covers(data.shape[1], data.shape[0] // shards,
                              outputs)


def _sgd_pass(state, x, y, mask, starts, hyper, classes, *, block, targets,
              loss, penalty, schedule, fit_intercept, mesh_holder, axes,
              kernel):
    """``lax.scan`` of an SGD step over the blocks that start at
    ``starts``, in that order (see the module docstring): the step's sums
    from :func:`._sgd_kernel.block_sums` where ``kernel``, else
    :func:`sgd_step` on the block's slice."""
    mesh = mesh_holder.mesh
    shards = math.prod(mesh.shape[a] for a in axes)
    local = x.shape[0] // shards
    size = min(block, local)
    update = dict(penalty=penalty, schedule=schedule,
                  fit_intercept=fit_intercept)

    def encode(yb, classes):
        if targets == "ova":
            return ova_targets(yb, classes)
        return yb.astype(jnp.float32)[:, None]

    def shard_pass(state, x, y, mask, starts, hyper, classes):
        first = lax.axis_index(axes) * local if shards > 1 else 0
        offsets = jnp.arange(size, dtype=jnp.int32)
        positive = None
        if targets == "ova":  # the class each column's target is +1 for
            positive = classes[1:] if classes.shape[0] == 2 else classes

        def kernel_step(st, start):
            """The step from the kernel's sums over this shard's rows of
            the block, each row read once."""
            lo = jnp.clip(start - first, 0, local)
            hi = jnp.clip(start + block - first, 0, local)
            gx, gs, n = _sgd_kernel.block_sums(
                x, y, mask, lo, hi, st["coef"], st["intercept"], positive,
                hyper["epsilon"], loss=loss, size=size)
            count = safe_denominator(n)
            return sgd_update(st, gx / count, gs / count, hyper,
                              **update), n

        def xla_step(st, start):
            """:func:`sgd_step` on the block's slice: the margins and the
            gradient are two products, each a read of the block."""
            at = jnp.clip(start - first, 0, local - size)
            rows = first + at + offsets
            own = (rows >= start) & (rows < start + block)
            mb = jnp.where(own, lax.dynamic_slice_in_dim(mask, at, size), 0)
            xb = lax.dynamic_slice_in_dim(x, at, size)
            yb = encode(lax.dynamic_slice_in_dim(y, at, size), classes)
            # the step's mean loss is left unused, so it is never
            # computed: a pass returns the state alone
            new, _ = sgd_step(st, xb, yb, mb, hyper, loss=loss, **update)
            return new, jnp.sum(mb)

        def body(st, start):
            new, n = (kernel_step if kernel else xla_step)(st, start)
            if shards > 1:
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
                     "fit_intercept", "mesh_holder", "axes", "kernel"),
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
    or labels brought to the host, MB), ``host_waits`` (waits of the host
    on the device inside the pass) and ``step_kernel`` (``"pallas"`` where
    the steps' sums came from one read of each block, :func:`one_read`,
    else ``"xla"``; also the registry count ``sgd.pass_kernel``, tagged
    with it)."""
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
        kernel = one_read(x, y, model._state["coef"].shape[1])
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
            mesh_holder=MeshHolder(mesh), axes=_row_axes(x), kernel=kernel)
        path = "pallas" if kernel else "xla"
        obs.registry().counter("sgd.pass_kernel", path).inc()
        span.set(table_host_mb=0.0, host_waits=waits, step_kernel=path)
    return model
