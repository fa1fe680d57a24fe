"""The sums of one SGD step from ONE read of its block: a Pallas kernel.

A step of :func:`~._sgd.sgd_step` over a block needs, for each output
column ``k``, the sums over the block's rows ``sum_i g_ik x_i`` and
``sum_i g_ik``, and the rows' weight ``sum_i w_i``: ``w_i`` is row
``i``'s mask and ``g_ik = w_i dz_k(m_ik)`` its loss derivative at its own
margin ``m_ik = x_i . coef_k + intercept_k``.  Row ``i``'s share needs no
other row, so one read of a row serves its margins, their derivatives and
the gradient.  XLA reads the block twice, once for ``x @ coef`` and once
for ``x.T @ dmarg``: it puts no row reduction and column reduction that
depends on it over one read.

:func:`block_sums` reads the table's transpose, ``x.T``: on the TPU a
float32 ``(rows, features)`` table with few features lies with its rows
minor (rows on lanes, features on sublanes), so ``x.T`` is a bitcast of
the same bytes and the kernel reads the table where it lies (the caller
checks the layout; :func:`covers` the shapes).  It walks the tiles of
:func:`tile_rows` rows that hold the block's rows ``[lo, hi)``, the first
and last tile's index prefetched as scalars: a block that starts inside a
tile takes one tile more, and a tile past the last is not read again.
Rows of a tile outside ``[lo, hi)`` or past the table's end are selected
away with ``jnp.where``, never multiplied by 0: a tile that overhangs the
table holds undefined values.  Inside a tile it works on a few hundred
rows at a time, in float32 on the VPU, and keeps its partial sums that
many lanes wide; the caller adds the lanes up.  The losses are
``_sgd.py``'s own functions.

Pallas is imported where the kernel is built, in :func:`block_sums`,
when a pass first takes the kernel (:func:`_pallas`, without the GPU
interpreter that comes with it): ``import dask_ml_tpu`` does not import
it, nor does a pass that keeps XLA's step.  Off the TPU the kernel runs
under ``jax.experimental.pallas.tpu``'s interpret mode, which is how the
tests drive it.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
from jax import lax

from ._sgd import _CLS_LOSSES, _margin_losses, _regression_losses

__all__ = ["block_sums", "covers", "tile_rows"]

#: rows of the labels and the mask loaded at a time: the TPU tiles a
#: float32 row vector by 1,024, so a load starts on such a bound
LOAD = 1024
#: most rows a grid step reads, and most bytes of the table a tile may
#: hold in fast memory (twice, for the double buffer): 32,768 rows of 28
#: float32 features on 32 sublanes are 4 MiB
TILE, TILE_BYTES = 32_768, 4 << 20
#: most output columns: each adds two products a feature a row to the
#: VPU's work on a tile, which the read of the tile hides only while the
#: columns are few; wider one-vs-all steps keep XLA's matrix products
MAX_OUTPUTS = 4
#: the module of Pallas's GPU interpreter, which ``pallas_call``'s module
#: imports with it (in jax 0.9 with Mosaic GPU's modules: two thirds of
#: the import's time on a CPU host); a TPU kernel never runs it
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


def _pallas():
    """``(jax.experimental.pallas, jax.experimental.pallas.tpu)``, imported
    where the kernel is built.  Where Pallas is not yet imported, its GPU
    interpreter is held out of the import (``pallas_call`` then keeps its
    own stand-in, as on an installation without it): the first pass that
    takes the kernel pays for the TPU's part of Pallas alone."""
    held = ("jax._src.pallas.pallas_call" not in sys.modules
            and _GPU_INTERPRETER not in sys.modules)
    if held:
        sys.modules[_GPU_INTERPRETER] = None  # its import raises ImportError
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    finally:
        if held:
            del sys.modules[_GPU_INTERPRETER]
    return pl, pltpu


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sub(outputs: int) -> int:
    """Rows worked on at a time inside a tile, so lanes of the partial
    sums: the partial gradient of every output column is kept in vector
    registers, ``outputs`` x features x ``sub`` floats in all."""
    return max(128, 512 // outputs // 128 * 128)


def tile_rows(features: int, rows: int, size: int) -> int:
    """Rows a grid step reads, from the shapes alone: the most whole
    loads under :data:`TILE` whose features fit :data:`TILE_BYTES`, no
    more than a block of ``size`` rows needs, nor than the ``rows`` of
    the table hold."""
    tile = TILE
    while tile > LOAD and _round_up(features, 8) * 4 * tile > TILE_BYTES:
        tile //= 2
    return min(tile, _round_up(size, LOAD), rows // LOAD * LOAD)


def covers(features: int, rows: int, outputs: int) -> bool:
    """Whether the kernel takes a table of these shapes: a whole load of
    rows, a tile's features in fast memory, at most :data:`MAX_OUTPUTS`
    output columns."""
    return (rows >= LOAD and outputs <= MAX_OUTPUTS
            and _round_up(features, 8) * 4 * LOAD <= TILE_BYTES)


def _kernel(ints, floats, classes, coef_ref, x_ref, y_ref, w_ref,
            gx_ref, g_ref, n_ref, *, tile, sub, loss):
    """One grid step: tile ``ints[0] + i`` of the table while it is not
    past tile ``ints[1]``, its rows in ``[ints[2], ints[3])`` counted."""
    pl, _ = _pallas()
    i = pl.program_id(0)
    t = ints[0] + i
    outputs = g_ref.shape[0]

    @pl.when(i == 0)
    def _():
        gx_ref[...] = jnp.zeros_like(gx_ref)
        g_ref[...] = jnp.zeros_like(g_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    @pl.when(t <= ints[1])
    def _():
        lo, hi, epsilon = ints[2], ints[3], floats[outputs]
        lanes = lax.broadcasted_iota(jnp.int32, (1, sub), 1)

        def part(at, ys, ws, sums):
            """The sums over the ``sub`` rows at ``at`` of the tile."""
            gx, gs, ns = sums
            rows = t * tile + at + lanes
            own = (rows >= lo) & (rows < hi)
            xs = jnp.where(own, x_ref[:, pl.ds(at, sub)], 0.0)
            ws = jnp.where(own, ws, 0.0)
            gx, gs = list(gx), list(gs)
            for k in range(outputs):
                margin = (jnp.sum(xs * coef_ref[k], axis=0, keepdims=True)
                          + floats[k])
                if loss in _CLS_LOSSES:
                    signs = jnp.where(ys == classes[k], 1.0, -1.0)
                    dz = _margin_losses(loss, margin, signs)[1]
                else:
                    dz = _regression_losses(
                        loss, margin, ys.astype(jnp.float32), epsilon)[1]
                g = jnp.where(own, ws * dz, 0.0)
                gx[k] = gx[k] + xs * g
                gs[k] = gs[k] + g
            return tuple(gx), tuple(gs), ns + ws

        def chunk(c, sums):
            # the labels and the mask a load at a time, the table ``sub``
            # rows at a time
            at = pl.multiple_of(c * LOAD, LOAD)
            ys = y_ref[pl.ds(at, LOAD)].reshape(1, LOAD)
            ws = w_ref[pl.ds(at, LOAD)].reshape(1, LOAD)
            for s in range(0, LOAD, sub):
                sums = part(at + s, ys[:, s:s + sub], ws[:, s:s + sub], sums)
            return sums

        zeros = jnp.zeros((1, sub), jnp.float32)
        gx, gs, ns = lax.fori_loop(
            0, tile // LOAD, chunk,
            ((jnp.zeros(gx_ref.shape[1:], jnp.float32),) * outputs,
             (zeros,) * outputs, zeros))
        for k in range(outputs):
            gx_ref[k] += gx[k]
            g_ref[k] += gs[k]
        n_ref[...] += ns


def block_sums(x, y, mask, lo, hi, coef, intercept, classes, epsilon, *,
               loss, size):
    """``(sum_i g_i x_i [d, K], sum_i g_i [K], sum_i w_i)`` over the rows
    ``[lo, hi)`` of the table ``x`` ``(rows, d)`` float32, its labels
    ``y`` and its mask ``(rows,)``, with ``hi - lo <= size``: ``w_i`` the
    mask and ``g_ik = w_i dz_k(x_i . coef_k + intercept_k)`` for
    ``loss``, where a classifier's target in column ``k`` is +1 where
    ``y_i == classes[k]`` and -1 elsewhere, and a regressor's is
    ``y_i``.  One read of each row, float32 throughout."""
    pl, pltpu = _pallas()
    rows, d = x.shape
    outputs = coef.shape[1]
    tile, sub = tile_rows(d, rows, size), _sub(outputs)
    steps = -(-size // tile) + 1
    first = jnp.minimum(lo, rows - 1) // tile
    last = jnp.maximum((hi - 1) // tile, first)
    ints = jnp.stack([first, last, lo, hi]).astype(jnp.int32)
    floats = jnp.concatenate([intercept.astype(jnp.float32),
                              jnp.reshape(epsilon, (1,)).astype(jnp.float32)])
    if classes is None:
        classes = jnp.zeros((outputs,), y.dtype)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def tile_at(i, ints):
        return jnp.minimum(ints[0] + i, ints[1])

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, ints: (0,) * len(shape))

    row_tile = pl.BlockSpec((tile,), lambda i, ints: (tile_at(i, ints),))
    gx, gs, ns = pl.pallas_call(
        lambda ints, *refs: _kernel(ints, *refs, tile=tile, sub=sub,
                                    loss=loss),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[smem, smem, whole(outputs, d, 1),
                      pl.BlockSpec((d, tile),
                                   lambda i, ints: (0, tile_at(i, ints))),
                      row_tile, row_tile],
            out_specs=[whole(outputs, d, sub), whole(outputs, 1, sub),
                       whole(1, sub)]),
        out_shape=[jax.ShapeDtypeStruct((outputs, d, sub), jnp.float32),
                   jax.ShapeDtypeStruct((outputs, 1, sub), jnp.float32),
                   jax.ShapeDtypeStruct((1, sub), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * 4 * tile * (_round_up(d, 8) + 2) + (4 << 20)),
        name="sgd_block_sums",
    )(ints, floats, classes.astype(y.dtype),
      coef.T.astype(jnp.float32)[:, :, None], x.T, y, mask)
    return jnp.sum(gx, axis=2).T, jnp.sum(gs, axis=(1, 2)), jnp.sum(ns)
