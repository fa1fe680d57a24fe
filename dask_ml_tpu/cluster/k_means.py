"""Scalable KMeans: k-means‖ initialization + Lloyd iterations.

Reference: ``dask_ml/cluster/k_means.py :: KMeans`` — k-means‖ init
(Bahmani et al. 2012, ``init_scalable``) and blockwise Lloyd rounds with
tree-reduced center updates (``_kmeans_single_lloyd``); SURVEY.md §3.2.

TPU design: one jitted SPMD step per Lloyd round — the pairwise-distance
gemm rides the MXU, per-cluster sums are a one-hot matmul (another gemm),
and the k×d/k reductions are psums over ICI inserted by XLA.  The k-means‖
rounds are one per-shard program that carries every row's least distance
and nearest candidate, with a per-shard PRNG for candidate sampling; only
the (tiny) candidate set ever reaches the host, where the final weighted
k-means++ runs exactly as the reference does it.
"""

from __future__ import annotations

import logging
import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from .. import obs as _obs
from ..base import TPUEstimator, TransformerMixin
from ..core.compat import shard_map_unchecked as _shard_map
from ..core.mesh import MeshHolder, data_axes, data_axes_size, get_mesh
from ..core.prng import as_key, fold_in_shard
from ..core.sharded import ShardedRows, unshard
from ..preprocessing.data import _ingest_float as _ingest_float_any
from ..utils import _timer, safe_denominator
from .. import sanitize as _san

logger = logging.getLogger(__name__)

#: runtime-verified twin of the segment-boundary host-sync-loop
#: suppression in fit's checkpointed Lloyd loop (two findings on the one
#: convergence line: float(shift) and float(tol)) — see sanitize/sites.py
_SEG_SYNC = _san.AllowSite(
    "kmeans-segment-sync", rule="host-sync-loop",
    cites=("648c6eac595ea7e4", "dfd1ac1a1b0ae4ba"),
    note="one shift/tol scalar pair per fused 32-iteration Lloyd "
         "segment, not per iteration",
)


def _ingest_float(est, X):
    """KMeans ingests half-precision input as float32: the Lloyd/init
    kernels accumulate distances and counts, and float16 accumulators both
    overflow early and break the fused loop's mixed-dtype carry (sklearn
    likewise computes k-means in wider precision than half)."""
    X = _ingest_float_any(est, X)
    if X.data.dtype in (jnp.float16, jnp.bfloat16):
        X = ShardedRows(data=X.data.astype(jnp.float32), mask=X.mask,
                        n_samples=X.n_samples)
    return X


# the one squared-distance kernel, shared with metrics.pairwise
from ..metrics.pairwise import _sq_euclidean  # noqa: E402
from ..metrics.pairwise import _sq_euclidean_hi as _sq_dists  # noqa: E402


def _kmeans_mode() -> str:
    """Precision mode for the Lloyd round, ``DASK_ML_TPU_KMEANS_PRECISION``:

    - ``highest`` (default): HIGHEST-precision gemms — assignment and
      sums bit-comparable to the fp32 reference.
    - ``fast``: cross term at ``Precision.HIGH`` (3 bf16 passes, error
      ~2⁻²² vs fp32's 2⁻²⁴) and the per-cluster reduce as a 3-pass
      bf16-split gemm (both operands split: the one-hot side carries the
      sample-weight mask).  6 MXU passes per round instead of 12; on
      MXU-bound shapes (k ≥ ~32) this can halve round time at
      k-means-irrelevant precision cost.  No reading of it stands in
      ``PERF.md`` (the benchmark's k-means cell runs ``highest``); the
      default stays ``highest`` as a deliberate precision-contract
      exception.
    """
    import os

    v = os.environ.get("DASK_ML_TPU_KMEANS_PRECISION", "highest").lower()
    if v not in ("highest", "fast"):
        raise ValueError(
            f"DASK_ML_TPU_KMEANS_PRECISION must be 'highest' or 'fast', "
            f"got {v!r}"
        )
    return v


#: up to this many clusters the Lloyd reduce sums offsets from per-cluster
#: anchors, picked for every row by a chain of selects that fuses into the
#: reduce's operand; a gather of more (or its gemm form) would be made in
#: memory beside the table, and their sums are shorter by 1/k anyway
_ANCHOR_MAX_CLUSTERS = 16


def _row_anchors(anchors, labels):
    """``anchors[labels]``, (n, d), as a chain of ``k - 1`` selects:
    elementwise, so it fuses into whatever consumes it and is never made
    in memory."""
    own = jnp.broadcast_to(anchors[0], (labels.shape[0], anchors.shape[1]))
    for j in range(1, anchors.shape[0]):
        own = jnp.where((labels == j)[:, None], anchors[j], own)
    return own


def _lloyd_step_fn(x, mask, centers, *, mode="highest", scatter="segsum"):
    """One Lloyd round: assign, reduce per-cluster sums/counts, update.

    Returns (new_centers, inertia, shift).  Everything is gemm-shaped; with
    sharded x the per-cluster reductions become ICI psums.  ``mode`` is
    static (see ``_kmeans_mode``).
    """
    with jax.named_scope("lloyd.assign"):
        if mode == "fast":
            d2 = _sq_euclidean(x, centers, precision=jax.lax.Precision.HIGH)
        else:
            d2 = _sq_dists(x, centers)
        labels = jnp.argmin(d2, axis=1)
        # jnp.min selects the SAME element as d2[argmin] but lowers to a
        # fused reduce; a take_along_axis gather here costs ~14 ms/round on
        # a v5e (11x the whole rest of the step) because XLA:TPU lowers
        # dynamic row-gathers serially
        min_d2 = jnp.min(d2, axis=1)
        inertia = jnp.sum(min_d2 * mask)
    # per-cluster reduce through the shared scatter policy (ops.scatter):
    # one-hot gemm on the MXU or segment_sum, whichever the platform
    # measurement favors.  Precision on the gemm path: HIGH in fast mode
    # (3-pass bf16 split — Mosaic's kernel writes the same split by
    # hand), HIGHEST otherwise (centers feed the next round's argmin).
    # The weight mask pre-multiplies x so both strategies accumulate the
    # same weighted rows; counts use HIGHEST so fractional sample
    # weights are never bf16-quantized in the denominator.
    from ..ops.scatter import bucket_sum

    k_ = centers.shape[0]
    prec = (jax.lax.Precision.HIGH if mode == "fast"
            else jax.lax.Precision.HIGHEST)
    with jax.named_scope("lloyd.reduce"):
        counts = bucket_sum(mask, labels, k_,
                            precision=jax.lax.Precision.HIGHEST,
                            strategy=scatter)  # (k,)
        # mean = a + mean(x - a): what is summed is every row's offset
        # from an ANCHOR near its current centre, not the row.  A
        # cluster's sum of rows grows to rows x |centre| and float32
        # accumulation on the MXU loses what lies under its last bit
        # (measured on a v5e at 25M x 50: centres off by 2e-3 relative,
        # PERF.md PR 28); the offsets' accumulator stays 2^-8 of that.
        # The anchor is the centre rounded to bfloat16, so it stands
        # still once the centre has settled: the same labels then give
        # the same sums bit for bit, and Lloyd reaches its exact fixed
        # point (shift == 0) as it does on sums of rows.
        if k_ <= _ANCHOR_MAX_CLUSTERS:
            anchor = jax.lax.reduce_precision(centers, exponent_bits=8,
                                              mantissa_bits=7)
            offsets = x - _row_anchors(anchor, labels)
        else:
            anchor, offsets = 0.0, x
        moved = bucket_sum(offsets * mask[:, None], labels, k_,
                           precision=prec, strategy=scatter)
    safe = safe_denominator(counts)[:, None]
    new_centers = jnp.where(counts[:, None] > 0, anchor + moved / safe,
                            centers)
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, inertia, shift


# The Lloyd hot programs route through the central program cache
# (design.md §12): compile books + compile-ahead for the step, and —
# now that the cache captures XLA cost_analysis per signature — the
# per-program roofline attribution of device_report().  ``centers`` is
# donated in both: the (k, d) output centers alias the dead input
# buffer in HBM.  ``x``/``mask`` are deliberately
# NOT donated — fit reuses them across segments (and _assign reads x
# after the loop), so that donation would delete live buffers.
from .. import programs as _programs  # noqa: E402

_lloyd_step = _programs.cached_program(
    _lloyd_step_fn, name="kmeans.lloyd_step",
    static_argnames=("mode", "scatter"), donate_argnames=("centers",),
)


# A fused Pallas Lloyd kernel (ops/lloyd.py) lived here and was deleted
# after it lost to the XLA lowering of ``_lloyd_step`` on a v5e: XLA's
# fusion already keeps the round at about two HBM passes, so the kernel
# had no traffic to remove.  Its records went with PR 21; what the chip
# reads of the Lloyd round today is in PERF.md section 5.


def _lloyd_loop_fn(x, mask, centers, tol, max_iter, *,
                   mode="highest", scatter="segsum"):
    """The ENTIRE Lloyd iteration as one XLA program.

    The reference re-enters the scheduler every round (SURVEY.md §3.2); a
    per-round jitted step would likewise pay one dispatch + one host sync
    (the ``shift <= tol`` check) per round.  Fusing the loop into
    ``lax.while_loop`` keeps convergence control on device: one dispatch
    per fit, no host round-trips.  ``tol``/``max_iter`` are device scalars
    so different settings don't recompile.

    Returns ``(centers, inertia, n_iter, shift)`` — the final center
    shift rides along so a SEGMENTED run (``FitCheckpoint`` chunking)
    can detect convergence that lands exactly on a segment boundary.
    """

    def step(x_, m_, c_):
        # tracer operands: the cached step bypasses to its jitted twin,
        # which inlines here (its donation is ignored under the outer
        # trace — the loop program's own centers donation is the one
        # that aliases)
        return _lloyd_step(x_, m_, c_, mode=mode, scatter=scatter)

    def cond(state):
        i, _, _, shift = state
        return (i < max_iter) & (shift > tol)

    def body(state):
        i, centers, _, _ = state
        new_centers, inertia, shift = step(x, mask, centers)
        return i + 1, new_centers, inertia, shift

    init = (
        jnp.int32(0),
        centers,
        jnp.asarray(jnp.inf, x.dtype),
        jnp.asarray(jnp.inf, x.dtype),
    )
    i, centers, inertia, shift = jax.lax.while_loop(cond, body, init)
    return centers, inertia, i, shift


# Roofline honesty note (design.md §16): cost_analysis counts this
# fused while program's body ONCE — the trip count is data-dependent —
# so the loop's attributed flops/bytes (hence roofline_frac) are a
# floor over the whole dispatch, not a per-round measurement.  The
# per-round number is the benchmark's ``lloyd.hbm_roof_pct``, which
# multiplies by the rounds the fit reports (PERF.md section 3).
_lloyd_loop = _programs.cached_program(
    _lloyd_loop_fn, name="kmeans.lloyd_loop",
    static_argnames=("mode", "scatter"), donate_argnames=("centers",),
)


def _assign_fn(x, mask, centers, x_norm=None):
    # given the rows' |x|^2 (fit holds k-means||'s) the table is read once
    d2 = (_sq_dists(x, centers) if x_norm is None else _new_d2(
        x, x_norm, centers, jnp.ones((centers.shape[0],), bool)))
    labels = jnp.argmin(d2, axis=1)
    min_d2 = jnp.min(d2, axis=1)  # same element as d2[argmin], fused lowering
    return labels, jnp.sum(min_d2 * mask)


# no donation (the gemm-output-smaller class of design.md §8):
# graftlint: disable=donation-miss -- outputs (labels + scalar) smaller than every input; x/centers stay live in fit/predict
_assign = _programs.cached_program(_assign_fn, name="kmeans.assign")


# ---------------------------------------------------------------------------
# k-means|| (Bahmani et al. 2012) in memory bounded by the table
# ---------------------------------------------------------------------------
#
# What is carried across rounds is, per row, the least squared distance to
# any candidate so far and the slot of that candidate:
# d2(x, C u C') = min(d2(x, C), d2(x, C')), so a round computes distances to
# its own at most ``cap`` new slots and folds them in.  Candidates live in
# one buffer of ``1 + max_rounds * cap`` slots with a validity vector, so one
# compiled program serves every round, and the candidates' weights are a
# histogram of the carried slots: no array of rows x slots exists anywhere.
# Every step is written per shard (``shard_map``): a shard draws, compacts
# and gathers among its own rows, and what crosses chips is a round's
# ``shards x cap`` surviving candidate rows, phi and the weights.

#: rows of one block of the first-selected search (``_first_selected``)
_SELECT_BLOCK = 1024
#: the largest float32 under 2**32: a probability on the scale of 32 bits
_BELOW_2_32 = 4294967040.0


def _drawn(bits, p):
    """Bernoulli(min(p, 1)) of 32 random bits.  A float32 uniform steps by
    2^-23 and a k-means|| round's p is about ell / rows, so ``u < p``
    would draw half as many rows again from a 100M-row table as the law
    says (read on four chips, PERF.md PR 28)."""
    steps = jnp.round(jnp.minimum(p, 1.0) * _BELOW_2_32).astype(bits.dtype)
    return (bits < steps) | (p >= 1.0)


def _unit_interval(bits):
    """32 random bits as float32 in (0, 1]: exact steps of 2^-32 near 0."""
    return (bits.astype(jnp.float32) + 0.5) * 2.0 ** -32


def _new_d2(x, x_norm, rows, valid):
    """Squared distances of ``x`` to one round's candidate ``rows``, with
    INVALID slots pushed out of every min/argmin.  The sentinel is +inf
    selected via ``where`` -- never added or multiplied (0 * inf = NaN
    would poison every distance; a finite dtype-max sentinel can be
    beaten by legitimate large distances)."""
    r_norm = jnp.sum(rows * rows, axis=1)
    d2 = x_norm[:, None] + r_norm[None, :] - 2.0 * jnp.dot(
        x, rows.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.where(valid[None, :], jnp.maximum(d2, 0.0),
                     jnp.asarray(jnp.inf, x.dtype))


def _fold_widths(ell: float, cap: int) -> tuple:
    """The static widths a round's fold is compiled at: ``ell``, one and
    a half and twice ``ell`` rounded up to whole sublanes of 8, and
    ``cap``.  A round draws ``ell`` rows in expectation (variance at most
    ``ell``), so the first two serve nearly every round and the last next
    to none; equal widths merge, and a ``cap`` of 8 leaves one.  (On a
    v5e at 25M x 50 the fold reads X in 7.6 ms and costs 9.0 / 9.6 / 11.4
    / 19.8 ms at 16 / 24 / 32 / 64 columns: PERF.md section 5.)"""
    return tuple(sorted({min(-(-int(np.ceil(m * ell)) // 8) * 8, cap)
                         for m in (1, 1.5, 2)} | {cap}))


def _fold_candidates(x, x_norm, rows, valid, d2, nearest, base, widths):
    """Fold one round's candidate ``rows`` (the ``valid`` ones packed
    first, as ``_gather_candidates`` leaves them) into every row's least
    squared distance ``d2`` and ``nearest`` slot (``base`` + the column):
    ``(d2, nearest, width)``.  The distances are computed at the narrowest
    of the static ``widths`` that holds the valid count, chosen in the
    program.  Slots past the count read +inf at any width, so the width
    changes no min and no argmin; no branch holds a collective, and the
    count is the same on every shard."""
    def fold_at(width):
        def fold(d2, nearest):
            new = _new_d2(x, x_norm, rows[:width], valid[:width])
            least = jnp.min(new, axis=1)
            closer = least < d2
            return (jnp.where(closer, least, d2), jnp.where(
                closer, base + jnp.argmin(new, axis=1).astype(jnp.int32),
                nearest))
        return fold

    table = jnp.asarray(widths, jnp.int32)
    # how many widths the count passes: the index of the first that holds it
    branch = jnp.searchsorted(table[:-1], jnp.sum(valid, dtype=jnp.int32))
    d2, nearest = jax.lax.switch(
        branch, [fold_at(w) for w in widths], d2, nearest)
    return d2, nearest, table[branch]


def _first_selected(sel, k: int):
    """Positions of the first ``k`` true entries of ``sel`` (n,), and how
    many of the ``k`` there are -- a compaction without a sort.

    Two levels: the count of each block of ``_SELECT_BLOCK`` entries (one
    reduction over ``sel``), a running sum over the blocks, and for each
    wanted rank a binary search for its block and a running sum inside
    that one block.  A ``top_k`` over the scores reads the same slots but
    sorts n of them."""
    n = sel.shape[0]
    block = min(_SELECT_BLOCK, n)
    n_blocks = -(-n // block)
    s = jnp.pad(sel, (0, n_blocks * block - n)).reshape(n_blocks, block)
    upto = jnp.cumsum(jnp.sum(s, axis=1, dtype=jnp.int32))  # inclusive
    want = jnp.arange(1, k + 1, dtype=jnp.int32)  # the j-th selected row
    blk = jnp.minimum(jnp.searchsorted(upto, want, side="left"),
                      n_blocks - 1).astype(jnp.int32)
    before = jnp.where(blk > 0, upto[jnp.maximum(blk - 1, 0)], 0)
    inside = s[blk]  # (k, block)
    rank = jnp.cumsum(inside.astype(jnp.int32), axis=1)
    pos = jnp.argmax(inside & (rank == (want - before)[:, None]), axis=1)
    found = want <= upto[-1]
    return jnp.where(found, blk * block + pos.astype(jnp.int32), 0), found


def _gather_candidates(x, sel, cap: int, row_ax):
    """One round's candidates: each shard takes the first ``cap`` of its
    own selected rows, and of the ``shards x cap`` survivors (all that
    crosses chips) every shard keeps the same first ``cap``."""
    idx, found = _first_selected(sel, cap)
    rows = jnp.where(found[:, None], jnp.take(x, idx, axis=0), 0.0)
    rows = jax.lax.all_gather(rows, row_ax, tiled=True)
    found = jax.lax.all_gather(found, row_ax, tiled=True)
    keep, valid = _first_selected(found, cap)
    return jnp.where(valid[:, None], jnp.take(rows, keep, axis=0), 0.0), valid


def _init_first_fn(x, mask, key, *, mesh_holder):
    """The first candidate, one real row drawn with probability
    proportional to its weight (each shard's winner of an exponential
    race, then the winner of those), every row's squared distance to it,
    and phi."""
    mesh = mesh_holder.mesh
    row_ax = data_axes(mesh)

    def local(x_l, m_l, key):
        # an exponential race, time / weight, the least wins: the winners
        # are the draws nearest 0, where float32 is fine-grained
        wait = -jnp.log1p(-_unit_interval(jax.random.bits(
            jax.random.fold_in(fold_in_shard(key, row_ax), 0), m_l.shape)))
        wait = jnp.where(m_l > 0, wait / m_l, jnp.inf)
        best = jnp.argmin(wait)
        waits = jax.lax.all_gather(wait[best], row_ax)
        rows = jax.lax.all_gather(x_l[best], row_ax)
        first = rows[jnp.argmin(waits)]
        with jax.named_scope("kmeansll.distances"):
            d2 = _new_d2(x_l, jnp.sum(x_l * x_l, axis=1), first[None, :],
                         jnp.ones((1,), bool))[:, 0]
            phi = jax.lax.psum(jnp.sum(d2 * m_l), row_ax)
        return first, d2, phi

    return _shard_map(
        local, mesh, in_specs=(P(row_ax, None), P(row_ax), P()),
        out_specs=(P(), P(row_ax), P()))(x, mask, key)


# no donation: the one output of a row's shape (the distances) has the
# mask's, and the mask stays live in the caller
# graftlint: disable=donation-miss -- the only same-shape input (mask) stays live in fit; x and key are larger/smaller than every output
_init_first = _programs.cached_program(
    _init_first_fn, name="kmeans.init_first",
    static_argnames=("mesh_holder",))


def _row_norms_fn(x):
    return jnp.sum(x * x, axis=1)


# Every round's distances want |x|^2.  A program of its own, queued
# behind ``kmeans.init_first`` BEFORE the host waits for phi: the chip
# reads X for it while the host wakes up, sizes the buffer and dispatches
# the rounds (2.7 ms of idle a fit on a v5e's host, PERF.md PR 28).
# graftlint: disable=donation-miss -- the one input (the table) stays live; the output is a column
_row_norms = _programs.cached_program(_row_norms_fn, name="kmeans.row_norms")


def _init_rounds_fn(x, mask, x_norm, first, min_d2, key, n_rounds, *, ell,
                    cap, max_rounds, mesh_holder, scatter):
    """Every k-means|| round after the first candidate, and the
    candidates' weights, as ONE program: ``n_rounds`` is a device scalar
    (at most ``max_rounds``, which sizes the buffer), so the rounds are a
    ``while_loop`` as the Lloyd iterations are.

    A round draws each row with ``p = min(ell * w * d2 / phi, 1)``, keeps
    at most ``cap`` of the drawn rows in the round's own slots, computes
    distances to the slots it filled (``_fold_candidates``: as many
    columns as the draw needs, not ``cap``) and folds them into the
    carried least distance and nearest slot.  Returns ``(candidates,
    valid, weights, counts)``: the whole buffer, which slots hold a row,
    for each slot the summed weight of the rows nearest to it, and in one
    ``int32[2]`` (one transfer) the rounds run and the distance columns
    they computed in all."""
    from ..ops.scatter import bucket_sum

    mesh = mesh_holder.mesh
    row_ax = data_axes(mesh)
    slots = 1 + max_rounds * cap
    widths = _fold_widths(ell, cap)

    def local(x_l, m_l, x_norm, d2_l, first, key, n_rounds):
        key = fold_in_shard(key, row_ax)

        def total(d2):
            return jax.lax.psum(jnp.sum(d2 * m_l), row_ax)

        def cond(state):
            r, phi = state[0], state[1]
            return (r < n_rounds) & (phi > 0)

        def body(state):
            r, phi, d2, nearest, cand, cvalid, folded = state
            with jax.named_scope("kmeansll.sample"):
                # stream 0 of this shard's key drew the first candidate
                bits = jax.random.bits(jax.random.fold_in(key, r + 1),
                                       m_l.shape)
                sel = _drawn(bits, ell * d2 * m_l / phi) & (m_l > 0)
                rows, valid = _gather_candidates(x_l, sel, cap, row_ax)
            base = 1 + r * cap
            with jax.named_scope("kmeansll.distances"):
                d2, nearest, width = _fold_candidates(
                    x_l, x_norm, rows, valid, d2, nearest, base, widths)
                phi = total(d2)
            cand = jax.lax.dynamic_update_slice(cand, rows, (base, 0))
            cvalid = jax.lax.dynamic_update_slice(cvalid, valid, (base,))
            return r + 1, phi, d2, nearest, cand, cvalid, folded + width

        cand = jnp.zeros((slots, x_l.shape[1]), x_l.dtype).at[0].set(first)
        cvalid = jnp.zeros((slots,), bool).at[0].set(True)
        state = (jnp.int32(0), total(d2_l), d2_l,
                 jnp.zeros(m_l.shape, jnp.int32), cand, cvalid, jnp.int32(0))
        rounds, _, _, nearest, cand, cvalid, folded = jax.lax.while_loop(
            cond, body, state)
        with jax.named_scope("kmeansll.weigh"):
            weights = jax.lax.psum(
                bucket_sum(m_l, nearest, slots, strategy=scatter,
                           precision=jax.lax.Precision.HIGHEST), row_ax)
        return cand, cvalid, weights, jnp.stack([rounds, folded])

    return _shard_map(
        local, mesh,
        in_specs=(P(row_ax, None), P(row_ax), P(row_ax), P(row_ax), P(), P(),
                  P()),
        out_specs=(P(), P(), P(), P()),
    )(x, mask, x_norm, min_d2, first, key, n_rounds)


# no donation: no output has a row's shape, so there is nothing for
# ``min_d2``'s buffer to alias
# graftlint: disable=donation-miss -- outputs (candidate buffer, weights) are smaller than every row-sized input
_init_rounds = _programs.cached_program(
    _init_rounds_fn, name="kmeans.init_scalable",
    static_argnames=("ell", "cap", "max_rounds", "mesh_holder", "scatter"),
)

#: the candidate buffer holds this many rounds or a multiple of it, so
#: that tables whose ``ceil(ln phi)`` differ by a little share one program
_ROUNDS_QUANTUM = 8


def init_scalable(X: ShardedRows, n_clusters: int, key, oversampling_factor=2,
                  init_max_iter=None):
    """k-means|| (Bahmani et al. 2012) -- reference ``k_means.py :: init_scalable``.

    Device side, two programs and two host syncs: ``kmeans.init_first``
    draws the first candidate and returns phi, from which the host takes
    ``n_rounds = ceil(ln phi)``; ``kmeans.init_scalable`` runs every
    round (the Bernoulli draw, a sort-free compaction of at most ``cap``
    drawn rows a round, distances to those rows alone -- the product is
    as wide as the round's draw, one of ``_fold_widths``, not ``cap`` --
    the fold into the carried least distance and nearest slot) and weighs
    the candidates by a histogram of the nearest slots.  Its largest
    intermediate is rows x ``cap``; nothing of the table's size leaves a
    chip or reaches the host.  Host side: one pull of the candidate
    buffer and its weights, then the weighted k-means++ and 10 Lloyd
    steps on the O(k log n) valid candidates, exactly the reference's
    division of labour.  The per-round capacity is 4 * ell: a round draws
    at most ell rows in expectation, so an overflow (rows drawn and
    dropped) is vanishingly rare and harmless to the sampling guarantee.
    """
    return _init_scalable(X, n_clusters, key, oversampling_factor,
                          init_max_iter)[0]


def _sample_candidates(X, n_clusters, key, oversampling_factor,
                       init_max_iter, behind=None):
    """The device part of k-means|| and its one pull: the candidate
    buffer ``(slots, d)``, which slots hold a row, every slot's weight,
    the rounds run, the distance columns they computed and ``cap``, all
    on the host, and the rows' ``|x|^2``, left on the device for the
    caller to carry (every init-only row vector dies with this frame).
    ``behind()`` is called once the rounds are dispatched and before the
    pull: what it queues runs on the device while the host works on the
    candidates."""
    from ..ops.scatter import scatter_strategy

    x, mask = X.data, X.mask
    ell = oversampling_factor * n_clusters
    mh = MeshHolder(get_mesh())
    cap = int(min(max(4 * ell, 8), x.shape[0] // data_axes_size(mh.mesh)))

    # 1. one real point drawn by weight, and phi: the first host sync
    # (both programs derive their streams from ``key`` inside: an eager
    # split is a dispatch the chip would wait for)
    with _obs.span("kmeans.init.first"):
        first, min_d2, phi = _init_first(x, mask, key, mesh_holder=mh)
        x_norm = _row_norms(x)  # the chip's work while the host waits
        phi = float(phi)
    n_rounds = int(np.ceil(np.log(max(phi, 2.0))))
    if init_max_iter is not None:
        n_rounds = min(n_rounds, int(init_max_iter))
    n_rounds = max(n_rounds, 1)
    max_rounds = -(-n_rounds // _ROUNDS_QUANTUM) * _ROUNDS_QUANTUM

    # 2. the rounds and the weights: one program, then ONE host pull of
    # the O(k log n) candidate buffer at the very end
    with _obs.span("kmeans.init.rounds", max_rounds=max_rounds):
        out = _init_rounds(
            x, mask, x_norm, first, min_d2, key, jnp.int32(n_rounds),
            ell=float(ell),
            cap=cap, max_rounds=max_rounds, mesh_holder=mh,
            scatter=scatter_strategy(1 + max_rounds * cap, histogram=True))
        if behind is not None:
            behind()
        cand, keep, weights, (rounds, slots) = jax.device_get(out)
        return cand, keep, weights, rounds, slots, cap, x_norm


def _init_scalable(X, n_clusters, key, oversampling_factor, init_max_iter,
                   behind=None):
    """``init_scalable``, its counts (``rounds`` run, valid
    ``candidates``, ``cap``, and ``slots``: the distance columns the
    rounds computed, ``rounds * cap`` if every fold were ``cap`` wide),
    which ``KMeans.fit`` puts on its span, and the rows' ``|x|^2`` the
    rounds ran on; ``behind``: see ``_sample_candidates``."""
    cand, keep, weights, rounds, slots, cap, x_norm = _sample_candidates(
        X, n_clusters, key, oversampling_factor, init_max_iter, behind)
    x, n = X.data, X.n_samples
    cand = np.asarray(cand, dtype=np.float64)[keep]
    weights = np.asarray(weights, dtype=np.float64)[keep]
    counts = {"rounds": int(rounds), "candidates": len(cand), "cap": cap,
              "slots": int(slots)}
    logger.debug("k-means||: %s", counts)

    if cand.shape[0] <= n_clusters:
        # degenerate: fewer candidates than clusters — pad with random real
        # rows gathered device-side (a stream no shard's index reaches)
        sub = jax.random.fold_in(key, 2**31 - 1)
        n_extra = n_clusters - cand.shape[0] + 1
        extra_idx = jax.random.choice(sub, n, (n_extra,), replace=n_extra > n)
        extra = np.asarray(jnp.take(x, extra_idx, axis=0), dtype=np.float64)
        cand = np.vstack([cand, extra])
        weights = np.concatenate([weights, np.ones(n_extra)])

    # final: weighted k-means++ + a few Lloyd steps on the candidate set
    # (host-local, candidate set is ~k·oversampling·rounds points)
    from sklearn.cluster import KMeans as SKKMeans

    local = SKKMeans(n_clusters=n_clusters, init="k-means++", n_init=1,
                     max_iter=10, random_state=0)
    with _one_host_thread():
        local.fit(cand, sample_weight=np.maximum(weights, 1e-12))
    return jnp.asarray(local.cluster_centers_, dtype=x.dtype), counts, x_norm


_HOST_POOLS = None


def _one_host_thread():
    """The host's BLAS and OpenMP pools held to one thread while sklearn
    clusters the few hundred candidates: fanned out over the cores that
    fit waits on its threads' wake-ups (measured on the v5e's host, 13
    cores, PERF.md PR 28: 9 ms of a 0.67 s fit and most of its run-to-run
    spread; here a fit of 460 x 50 read 2.3 ms with stalls to 140 ms
    against 2.0 ms and none).  The controller is kept: finding the
    loaded pools anew costs more than the fit."""
    global _HOST_POOLS
    if _HOST_POOLS is None:
        import threadpoolctl

        _HOST_POOLS = threadpoolctl.ThreadpoolController()
    return _HOST_POOLS.limit(limits=1)


#: rows whose mean anchors the one pass of ``_tol_fn``
_TOL_SAMPLE = 1024


def _sample_stride(rows: int, want: int) -> int:
    """The stride that takes about ``want`` of ``rows`` rows: the largest
    prime at most ``rows // want``, so that a table laid out with a period
    is sampled across it (the benchmark's blobs repeat every 8 rows, and
    ``25M // 1024`` = 24,414 would meet four of the eight); 1 where there
    are not twice ``want`` rows."""
    stride = max(rows // want, 1)
    while any(stride % p == 0 for p in range(2, int(stride ** 0.5) + 1)):
        stride -= 1
    return stride


def _tol_fn(x, mask, tol, *, mesh_holder):
    """The Lloyd loop's stopping threshold, sklearn's ``tol * mean_j
    var_j`` over the real rows, from ONE read of the table:
    ``(threshold, anchor_share)``.

    ``var = (S2 - S1^2 / n) / n`` of ``S1 = sum((x - a) m)`` and ``S2 =
    sum((x - a)^2 m)`` is off by about ``eps * (1 + (mean - a)^2 / var)``,
    so the anchor ``a`` is no row but the masked mean of about
    ``_TOL_SAMPLE`` rows taken at a fixed stride (``_sample_stride``)
    over the whole table (each shard over its own rows, one ``psum`` of
    ``d + 1`` numbers): within sigma / 32 of the mean whatever the table's
    order, period or offset.
    ``anchor_share`` = ``max_j (S1_j^2 / n) / S2_j`` is the share of the
    anchored second moment that is the anchor's own offset: near 0 for a
    good anchor, and what says when the one pass would lose digits (a
    column with no spread may read 1 and loses nothing).  No centred second
    pass stands behind a ``lax.cond``: the ``conditional`` wants the table
    copied to row-major tiles, 11.92 GB at 25M x 50 (PERF.md PR 37).
    ``core.sharded.masked_var`` keeps its three passes for those who
    publish a variance; this is a scale for a threshold."""
    mesh = mesh_holder.mesh
    row_ax = data_axes(mesh)
    per_shard = max(_TOL_SAMPLE // data_axes_size(mesh), 1)

    def local(x_l, m_l):
        m = m_l.astype(x_l.dtype)
        stride = _sample_stride(x_l.shape[0], per_shard)
        rows, w = x_l[::stride], m[::stride]
        sample = jax.lax.psum(jnp.concatenate(
            [jnp.sum(rows * w[:, None], axis=0), jnp.sum(w)[None]]), row_ax)
        anchor = sample[:-1] / safe_denominator(sample[-1])
        xs = x_l - anchor
        xm = xs * m[:, None]
        return jax.lax.psum(
            (jnp.sum(xm, axis=0), jnp.sum(xs * xm, axis=0), jnp.sum(m)),
            row_ax)

    s1, s2, n = _shard_map(
        local, mesh, in_specs=(P(row_ax, None), P(row_ax)),
        out_specs=(P(), P(), P()))(x, mask)
    offset = s1 * s1 / n
    share = jnp.max(offset / safe_denominator(s2))  # s2 == 0: s1 == 0 too
    return (tol * jnp.mean((s2 - offset) / n)).astype(x.dtype), share


# graftlint: disable=donation-miss -- outputs are two scalars; the table and its mask stay live in fit
_tol = _programs.cached_program(
    _tol_fn, name="kmeans.tol", static_argnames=("mesh_holder",))


class KMeans(TransformerMixin, TPUEstimator):
    """Parameters mirror the reference (``n_clusters``, ``init='k-means||'``,
    ``oversampling_factor``, ``max_iter``, ``tol``, ``init_max_iter``,
    ``random_state``, ``n_jobs`` accepted-inert).

    ``fit_checkpoint`` (a :class:`~dask_ml_tpu.resilience.FitCheckpoint`)
    makes the fit preemption-safe: the fused Lloyd ``while_loop`` runs as
    SEGMENTS of ``every_n_iters`` iterations (same compiled step program,
    one extra dispatch + scalar sync per boundary), snapshotting the
    centers atomically at each boundary so a killed fit resumes from the
    last snapshot with the identical trajectory.  Preemption (SIGTERM via
    :class:`~dask_ml_tpu.resilience.PreemptionWatcher`) is honored at the
    same boundaries.
    """

    def __init__(self, n_clusters=8, init="k-means||", oversampling_factor=2,
                 max_iter=300, tol=1e-4, precompute_distances="auto",
                 random_state=None, copy_x=True, n_jobs=1, algorithm="full",
                 init_max_iter=None, fit_checkpoint=None):
        self.n_clusters = n_clusters
        self.init = init
        self.oversampling_factor = oversampling_factor
        self.max_iter = max_iter
        self.tol = tol
        self.precompute_distances = precompute_distances
        self.random_state = random_state
        self.copy_x = copy_x
        self.n_jobs = n_jobs
        self.algorithm = algorithm
        self.init_max_iter = init_max_iter
        self.fit_checkpoint = fit_checkpoint

    def _init_centers(self, X: ShardedRows, key, span=None, behind=None,
                      norms=None):
        """The starting centres; k-means||'s counts go on ``span`` (the
        fit's ``kmeans.init``) and into the always-on registry, it calls
        ``behind()`` where device work can hide host work, and appends
        the rows' ``|x|^2`` to the list ``norms`` where it computed them
        (k-means|| alone), for the fit's last assignment."""
        init = self.init
        if isinstance(init, (np.ndarray, jnp.ndarray)):
            # a COPY, never a view of the user's array: the Lloyd loop
            # donates its centers operand, and jnp.asarray of an
            # already-right-dtype device array would alias the user's
            # buffer into the donation
            centers = jnp.array(init, dtype=X.data.dtype)
            if centers.shape != (self.n_clusters, X.data.shape[1]):
                raise ValueError(
                    f"init array must be ({self.n_clusters}, {X.data.shape[1]}), "
                    f"got {centers.shape}"
                )
            return centers
        if init == "k-means||":
            with _timer("k-means|| initialization", logger, logging.DEBUG):
                centers, counts, x_norm = _init_scalable(
                    X, self.n_clusters, key, self.oversampling_factor,
                    self.init_max_iter, behind,
                )
            if norms is not None:
                norms.append(x_norm)
            if span is not None:
                span.set(**counts)
            reg = _obs.registry()
            reg.counter("kmeans.init_rounds").inc(counts["rounds"])
            reg.counter("kmeans.candidates").inc(counts["candidates"])
            reg.counter("kmeans.init_slots").inc(counts["slots"])
            return centers
        if init == "random":
            p = X.mask / jnp.sum(X.mask)
            idx = jax.random.choice(
                key, X.data.shape[0], (self.n_clusters,), replace=False, p=p
            )
            return X.data[idx]
        if init == "k-means++":
            # host-side k-means++ on a small device-gathered sample, like the
            # reference's fallback path
            from sklearn.cluster import kmeans_plusplus

            from ..utils import draw_seed

            n_sample = min(X.n_samples, max(1000, 50 * self.n_clusters))
            key, sub = jax.random.split(key)
            # VALIDITY-uniform subsample + the true weights inside
            # sklearn's k-means++.  Subsampling proportionally to the
            # weights would weight twice (seed probability ~ w^2 d^2 vs
            # sklearn's w d^2); a 0/1 validity draw keeps zero-weight
            # rows out while kmeans_plusplus applies w exactly once.
            p = (X.mask[: X.n_samples] > 0).astype(jnp.float32)
            p = p / jnp.sum(p)
            # replace=False always: n_sample = min(n_samples, ...), so a
            # no-replacement draw is always valid; zero-probability rows
            # that must fill the draw are neutralized by w_sample=0 in
            # kmeans_plusplus
            idx = jax.random.choice(
                sub, X.n_samples, (n_sample,), replace=False, p=p,
            )
            sample = np.asarray(jnp.take(X.data, idx, axis=0), dtype=np.float64)
            w_sample = np.asarray(
                jnp.take(X.mask[: X.n_samples], idx), dtype=np.float64
            )
            seed = int(draw_seed(int(jax.random.randint(key, (), 0, 2**31 - 1))))
            centers, _ = kmeans_plusplus(
                sample, self.n_clusters, sample_weight=w_sample,
                random_state=seed,
            )
            return jnp.asarray(centers, dtype=X.data.dtype)
        raise ValueError(f"Unknown init: {init!r}")

    def fit(self, X, y=None, sample_weight=None):
        # the fit's spans (live under ``obs.enable()`` or a profiler
        # session): ``kmeans.fit`` is the root, ``kmeans.init``,
        # ``kmeans.lloyd`` and ``kmeans.assign`` its children
        with _obs.span("kmeans.fit", n_clusters=self.n_clusters,
                       init=(self.init if isinstance(self.init, str)
                             else "array")) as root:
            return self._fit(X, sample_weight, root)

    def _fit(self, X, sample_weight, root):
        if self.n_clusters <= 0:
            raise ValueError("n_clusters must be positive")
        X = _ingest_float(self, X)
        if X.n_samples < self.n_clusters:
            raise ValueError(
                f"n_samples={X.n_samples} < n_clusters={self.n_clusters}"
            )
        root.set(rows=X.n_samples, features=X.data.shape[1],
                 chips=len(X.data.sharding.device_set))
        valid_mask = X.mask  # pre-weighting validity, for the tol scale
        if sample_weight is not None:
            # the mask is the per-row weight everywhere downstream: the
            # k-means|| sampling probabilities, the Lloyd center sums and
            # counts, and the inertia all become their weighted (sklearn)
            # forms by scaling it
            from ..utils import reweight_rows

            X = reweight_rows(X, sample_weight=sample_weight)
        key = as_key(self.random_state)
        ckpt = self.fit_checkpoint
        it0 = 0
        snap = ckpt.load_if_matches(self) if ckpt is not None else None
        if snap is not None:
            # resume mid-fit: the snapshot's centers REPLACE the (seed-
            # deterministic) init, and the Lloyd budget continues from the
            # recorded iteration count
            it0, state = snap
            # copy: the loop donates centers; the snapshot's array must
            # stay valid for a retried resume
            centers = jnp.array(state["centers"], dtype=X.data.dtype)
        x, mask = X.data, X.mask
        # the stopping threshold and its anchor's share, device scalars,
        # queued once
        tol = []

        def queue_tol():
            # sklearn-style tol scaling: mean of per-feature variances,
            # masked so pad rows don't inflate the threshold, and from
            # UNWEIGHTED variances: sklearn's _tolerance ignores
            # sample_weight, so weighting must not move it
            tol.extend(_tol(x, valid_mask, float(self.tol),
                            mesh_holder=MeshHolder(get_mesh())))

        norms = []  # the rows' |x|^2, where the init computed them
        if snap is None:
            with _obs.span("kmeans.init") as span:
                # the threshold needs no centres: queued behind k-means||'s
                # rounds, its read of X runs while the host clusters the
                # candidates
                centers = self._init_centers(X, key, span, behind=queue_tol,
                                             norms=norms)
        with _obs.span("kmeans.lloyd") as span:
            if not tol:
                queue_tol()
            centers, n_iter = self._lloyd(x, mask, tol[0], centers, ckpt, it0)
            # the assignment is queued behind the loop before anything is
            # waited for: the chip goes on while the host wakes up
            labels, inertia = _assign(x, mask, centers, *norms)
            # every caller reads the centres on the host: their copy
            # starts now, not at the first ``np.asarray``
            centers.copy_to_host_async()
            # the wait for the loop, and the one fetch of both
            n_iter, share = jax.device_get((n_iter, tol[1]))
            n_iter = int(n_iter)
            span.set(iters=n_iter, tol_anchor_share=float(share))
        reg = _obs.registry()
        reg.counter("kmeans.count").inc()
        reg.counter("kmeans.lloyd_iters").inc(n_iter)
        with _obs.span("kmeans.assign"):
            self.inertia_ = float(inertia)  # the wait for the assignment

        self.cluster_centers_ = centers
        self.labels_ = (labels if labels.shape[0] == X.n_samples
                        else labels[: X.n_samples])
        self.n_iter_ = n_iter
        self.n_features_in_ = x.shape[1]
        return self

    def _lloyd(self, x, mask, tol, centers, ckpt, it0):
        """The Lloyd iterations from ``centers`` down to a shift of
        ``tol`` (a device scalar): ``(centers, n_iter)``; the fused loop
        leaves ``n_iter`` on the device, for the caller to wait on."""
        from ..resilience.preemption import active_watcher, check_preemption

        with _timer("Lloyd loop", logger, logging.DEBUG), \
                _san.region("kmeans.fit.lloyd"):
            from ..ops.scatter import scatter_strategy

            # policy knobs resolve OUTSIDE the jit so they participate in
            # the jit cache key (static args); resolving inside would bake
            # the first call's env values in for the process lifetime
            mode = _kmeans_mode()
            scatter = scatter_strategy(self.n_clusters)
            if ckpt is None and active_watcher() is None:
                # the uninstrumented fast path: ONE fused dispatch
                centers, _, n_iter_dev, _ = _lloyd_loop(
                    x, mask, centers, tol, jnp.int32(self.max_iter),
                    mode=mode, scatter=scatter,
                )
                return centers, n_iter_dev
            # segmented: the SAME compiled step program in chunks of
            # the checkpoint cadence, one host boundary per chunk
            # (snapshot + preemption check + fault-injection point)
            from ..resilience.testing import maybe_fault

            chunk = (ckpt.chunk_iters(32) if ckpt is not None
                     else min(32, int(self.max_iter)))
            n_iter = it0
            while n_iter < self.max_iter:
                maybe_fault("step")
                seg = min(chunk, self.max_iter - n_iter)
                centers, _, seg_n_dev, shift = _lloyd_loop(
                    x, mask, centers, tol, jnp.int32(seg), mode=mode,
                    scatter=scatter,
                )
                seg_n = int(seg_n_dev)
                n_iter += seg_n
                if ckpt is not None and ckpt.due(n_iter):
                    ckpt.save(self, {"centers": centers}, n_iter)
                check_preemption(ckpt, self, {"centers": centers}, n_iter)
                # converged: the segment stopped early, or the final
                # shift cleared tol exactly at the boundary (the fused
                # loop's cond — boundaries must not add iterations)
                with _SEG_SYNC.allow():
                    # graftlint: disable=host-sync-loop -- segment-boundary sync: one scalar fetch per fused 32-iteration segment, not per Lloyd iteration
                    if seg_n < seg or float(shift) <= float(tol):
                        break
            if ckpt is not None:
                ckpt.complete()
        return centers, n_iter

    def predict(self, X):
        X = _ingest_float(self, X)
        labels, _ = _assign(X.data, X.mask, self.cluster_centers_)
        return labels[: X.n_samples]

    def fit_predict(self, X, y=None, sample_weight=None):
        return self.fit(X, sample_weight=sample_weight).labels_

    def transform(self, X):
        """Distances to each center (reference semantic)."""
        X = _ingest_float(self, X)
        d = jnp.sqrt(_sq_dists(X.data, self.cluster_centers_))
        return d[: X.n_samples]

    def score(self, X, y=None, sample_weight=None):
        X = _ingest_float(self, X)
        if sample_weight is not None:
            from ..utils import reweight_rows

            X = reweight_rows(X, sample_weight=sample_weight)
        _, inertia = _assign(X.data, X.mask, self.cluster_centers_)
        return -float(inertia)

    def get_feature_names_out(self, input_features=None):
        """sklearn contract for cluster-transformers: ``transform``
        outputs one distance column per center, named
        ``<classname_lower><i>``."""
        import numpy as np

        k = self.cluster_centers_.shape[0]
        prefix = type(self).__name__.lower()
        return np.asarray([f"{prefix}{i}" for i in range(k)], dtype=object)
