"""The serve plane's device programs, behind the central program cache.

Four programs cover every resident linear model:

* :data:`margins` — one model, one coalesced batch: ``xb @ coef +
  intercept``.  The label decision (argmax / sign) happens on the HOST
  over the fetched ``(b, k)`` margins: micro-batches are small by
  definition, and keeping the device program class-count-agnostic means
  one compiled shape per (bucket, d, k) instead of one per decode rule.
* :data:`lane_margins` — the vmap of the same gemm over a stacked model
  axis: requests for DIFFERENT homogeneous models that land in the same
  micro-batch window dispatch as ONE program over the residency
  registry's lane-packed state (the lane-packing of the solvers'
  ``packed_solve``) instead of M separate launches.
* :data:`proba` — the probability transform of a margins buffer, with
  the **margins donated**: the output has the margins' exact shape
  (sigmoid / clip per class column, normalized along the class axis),
  so XLA aliases the donated buffer and the transform is in-place in
  HBM — the probabilities overwrite the margins instead of doubling the
  batch's live footprint.  Shape-agnostic over leading axes, so the
  same program body serves ``(b, k)`` single-model and ``(M, b, k)``
  lane-packed margins; the donation follows every per-signature AOT
  executable the cache mints, including the fresh one when a coalesced
  batch crosses a bucket rung (regression-pinned in
  tests/test_serve.py).
* :data:`lane_refresh` — hot-swap of ONE lane of a pack's resident
  stack (a model re-loaded under an existing name — the online plane's
  deploy primitive), with the **stacks donated**: ``dynamic_update_
  slice`` writes the new coefficients into the resident ``[M, d, k]``
  buffer in place rather than re-uploading and re-stacking M models.
  The lane index is a traced scalar, so every lane shares one program.

The batch buffers (``xb`` / ``xs``) are deliberately NOT donated: the
gemm's output is ``(…, k)`` — smaller than the ``(…, d)`` input — so
there is no same-shaped output to alias into and the donation would be
a no-op (the same reasoning design.md §8 records for training block
buffers).  Donation lives where it aliases.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import programs as _programs

__all__ = ["margins", "lane_margins", "proba", "lane_refresh"]


def _margins_fn(coef, intercept, xb):
    """``(d,k),(k,),(b,d) -> (b,k)`` — the whole single-model serve
    predict: one gemm on the MXU, bias add fused."""
    return xb @ coef + intercept


# graftlint: disable=donation-miss -- gemm-output-smaller: the (b,k) margins cannot alias (b,d)/(d,k) inputs, and coef/intercept are the resident model state (module docstring)
margins = _programs.cached_program(_margins_fn, name="serve.margins")


def _lane_margins_fn(coefs, intercepts, xs):
    """``(M,d,k),(M,k),(M,b,d) -> (M,b,k)`` — per-lane batches against
    per-lane models, one program for the whole pack."""
    return jax.vmap(_margins_fn)(coefs, intercepts, xs)


# graftlint: disable=donation-miss -- gemm-output-smaller, and the stacked coefs/intercepts are the residency registry's LIVE lane state (donating them would delete the pack)
lane_margins = _programs.cached_program(
    _lane_margins_fn, name="serve.lane_margins")


def _proba_fn(m, *, loss):
    """Margins → per-class probabilities, same shape (``k`` is the last
    axis; ``k == 1`` yields the positive-class column, the host decode
    assembles the binary pair).  Mirrors ``SGDClassifier.
    predict_proba``'s formulas on device."""
    if loss == "modified_huber":
        p = (jnp.clip(m, -1.0, 1.0) + 1.0) / 2.0
    elif loss == "log_loss":
        p = jax.nn.sigmoid(m)
    else:
        raise ValueError(
            f"probability estimates are not available for loss={loss!r}")
    if m.shape[-1] == 1:
        return p
    if loss == "modified_huber":
        z = jnp.sum(p, axis=-1, keepdims=True)
        return jnp.where(z > 0, p / z, 1.0 / m.shape[-1])
    return p / jnp.sum(p, axis=-1, keepdims=True)


proba = _programs.cached_program(
    _proba_fn, name="serve.proba", static_argnames=("loss",),
    donate_argnames=("m",),
)


def _lane_refresh_fn(coefs, intercepts, coef, intercept, lane):
    """Write one model's fresh state into lane ``lane`` of the resident
    stacks, in place (both stacks donated; ``lane`` traced)."""
    zero = jnp.int32(0)
    return (
        jax.lax.dynamic_update_slice(coefs, coef[None], (lane, zero, zero)),
        jax.lax.dynamic_update_slice(intercepts, intercept[None],
                                     (lane, zero)),
    )


lane_refresh = _programs.cached_program(
    _lane_refresh_fn, name="serve.lane_refresh",
    donate_argnames=("coefs", "intercepts"),
)
