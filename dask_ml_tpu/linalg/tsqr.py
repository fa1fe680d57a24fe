"""Tall-skinny QR (TSQR) and SVD on row-sharded matrices.

Reference path: ``da.linalg.tsqr`` — blockwise QR per chunk, stack the R
factors, recurse (SURVEY.md §3.4).  TPU-native version: one ``shard_map``
program, with two interchangeable local factorizations behind one policy:

- ``householder`` — local ``jnp.linalg.qr`` per shard, ``all_gather`` of
  the small (d×d) R factors over ICI, replicated second-stage QR, local Q
  correction.  Backward stable at any conditioning, but Householder panel
  factorization pipelines poorly onto the MXU (it is a sequence of
  rank-1/skinny updates, not large gemms).
- ``cholqr2`` — CholeskyQR2 (Yamamoto et al. 2015): G = psum(XᵀX), tiny
  replicated Cholesky, Q₁ = X·R₁⁻¹, then one repair pass (re-Gram +
  Cholesky) that restores orthogonality to O(eps) whenever
  cond(X)²·eps ≲ 1.  Every heavy op is an (n×d)·(d×d) gemm — pure MXU —
  and the only collective is a d×d psum (cheaper than the all_gather of
  P R-factors).  A replicated validity guard (finite Cholesky + repair
  deviation ‖G₂−I‖_F < 1/8) routes ill-conditioned inputs to the
  Householder body via ``lax.cond`` — the literature's Cholesky *shift*
  exists to avoid failure when there is no alternative factorization;
  with a fallback in the same program, failure detection is enough.

Zero host round-trips either way; the whole factorization (including the
guarded fallback) is a single XLA program.  Strategy is resolved OUTSIDE
jit and threaded through as a static argument (the scatter-knob staleness
lesson — ADVICE r4): ``DASK_ML_TPU_TSQR`` = ``householder`` | ``cholqr2``
| ``auto`` (default: ``cholqr2``).

``tsqr_r`` is the factorization for a caller that wants ``R`` alone
(``PCA.fit``, ``TruncatedSVD``): the same CholeskyQR2, with the centring
and the mask applied inside the passes and the repair computed block by
block, so that neither ``Q`` nor a centred copy of the table is ever
made.  At 25M x 64 float32 on one v5e (``pca-tsqr.fit-1chip``; PERF.md
section 5) it is three reads of the table; the Householder arm needs a
centred copy and a workspace of the table's size and does not fit there.

Padding note: zero rows contribute nothing to R (or to the Gram) and
produce zero rows of Q, so the pad+mask ingest discipline composes
transparently (provided padded rows are zeroed — masked centering does
this; ``tsqr_r`` takes the mask and does it itself).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.compat import shard_map_unchecked as _shard_map
from ..core.mesh import data_axes, get_mesh
from ..core.sharded import ShardedRows

# CholeskyQR2 acceptance: with ‖G₂−I‖ below this, one repair pass provably
# restores orthogonality to O(eps) (Yamamoto et al. 2015 need
# 8·cond²·(mn+n(n+1))·eps ≤ 1; the computed repair deviation is the
# runtime-observable proxy for that condition).
_CHOLQR2_DEV_MAX = 0.125


def tsqr_strategy() -> str:
    """Local-factorization policy, overridable via ``DASK_ML_TPU_TSQR``.

    ``auto`` is ``cholqr2`` on every platform: every heavy op is an MXU
    gemm and its one collective a d×d psum, and the guarded Householder
    fallback inside the same program covers the ill-conditioned regime,
    so the default costs no correctness.  On the chip
    ``pca-tsqr.fit-1chip`` runs ``tsqr_r`` under ``cholqr2``; what the
    Householder arm read there is in PERF.md section 6 (PR 32).
    """
    from ..utils import env_choice

    v = env_choice("DASK_ML_TPU_TSQR", ("auto", "householder", "cholqr2"))
    return "cholqr2" if v == "auto" else v


@partial(jax.jit, static_argnames=("mesh_holder", "strategy"))
def _tsqr_impl(x, *, mesh_holder, strategy="householder"):
    mesh = mesh_holder.mesh
    d = x.shape[1]
    # all data-carrying axes (('dcn','data') on a hierarchical mesh):
    # the R all_gather / Gram psum then spans the slice boundary over DCN
    row_ax = data_axes(mesh)
    hi = jax.lax.Precision.HIGHEST

    def local_hh(xs):
        # Short shards (m < d) are fine: reduced QR then yields q1 (m, k),
        # r1 (k, d) with k = min(m, d); only the STACKED R must be tall.
        q1, r1 = jnp.linalg.qr(xs, mode="reduced")  # (m, k), (k, d)
        k = r1.shape[0]
        r_all = jax.lax.all_gather(r1, row_ax)  # (P, k, d)
        q2, r = jnp.linalg.qr(r_all.reshape(-1, d), mode="reduced")  # (P·k, d), (d, d)
        i = jax.lax.axis_index(row_ax)
        q2_i = jax.lax.dynamic_slice_in_dim(q2, i * k, k)
        return q1 @ q2_i, r

    def local_cq(xs):
        from jax.scipy.linalg import solve_triangular

        eye = jnp.eye(d, dtype=xs.dtype)
        # Gram + Cholesky + whiten.  HIGHEST precision everywhere: the
        # Gram squares the condition number, so bf16 gemm passes would
        # throw away exactly the bits the repair pass needs.
        g = jax.lax.psum(jnp.matmul(xs.T, xs, precision=hi), row_ax)
        l1 = jnp.linalg.cholesky(g)  # lower; NaNs if not numerically PD
        q1 = jnp.matmul(
            xs, solve_triangular(l1.T, eye, lower=False), precision=hi
        )
        # repair pass: re-Gram measures how far Q₁ is from orthonormal
        g2 = jax.lax.psum(jnp.matmul(q1.T, q1, precision=hi), row_ax)
        l2 = jnp.linalg.cholesky(g2)
        dev = jnp.linalg.norm(g2 - eye)
        # replicated predicate (every input is a psum result), so all
        # shards take the same branch and the fallback's all_gather
        # cannot desynchronize
        ok = (
            jnp.isfinite(l1).all()
            & jnp.isfinite(l2).all()
            & (dev < _CHOLQR2_DEV_MAX)
        )

        def accept(_):
            q = jnp.matmul(
                q1, solve_triangular(l2.T, eye, lower=False), precision=hi
            )
            r = jnp.matmul(l2.T, l1.T, precision=hi)  # R = R₂·R₁, (d, d)
            return q, r

        def fallback(_):
            return local_hh(xs)

        return jax.lax.cond(ok, accept, fallback, None)

    local = local_cq if strategy == "cholqr2" else local_hh
    return _shard_map(
        local, mesh, in_specs=P(row_ax, None),
        out_specs=(P(row_ax, None), P()),
    )(x)


class _MeshHolder:
    """Hashable wrapper so the mesh can be a static jit argument."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __hash__(self):
        return hash(self.mesh)

    def __eq__(self, other):
        return isinstance(other, _MeshHolder) and self.mesh == other.mesh


def tsqr(x, mesh=None, strategy=None):
    """Reduced QR of a row-sharded tall-skinny matrix: X = Q R.

    Q comes back row-sharded like X; R is (d, d) replicated.  ``strategy``
    (``householder``/``cholqr2``) defaults to the ``tsqr_strategy()``
    policy, resolved here — at call time, outside jit.
    """
    # Validate on the TRUE shape: ShardedRows pads rows, and a wide matrix
    # padded past its column count must still be rejected.
    true_shape = x.shape
    if isinstance(x, ShardedRows):
        x = x.data
    mesh = mesh or get_mesh()
    if true_shape[0] < true_shape[1]:
        # Individual shards may be short (stage 2 recovers rank from the
        # stacked R factors), but the overall matrix must be tall-skinny.
        raise ValueError(
            f"tsqr requires a tall-skinny matrix: got shape {true_shape} "
            "(rows < cols); use randomized_svd / svd_compressed instead"
        )
    if strategy in (None, "auto"):
        strategy = tsqr_strategy()
    elif strategy not in ("householder", "cholqr2"):
        # _tsqr_impl dispatches with a plain equality check; an
        # unrecognized string would silently run Householder
        raise ValueError(
            f"strategy must be householder|cholqr2|auto, got {strategy!r}"
        )
    return _tsqr_impl(
        x, mesh_holder=_MeshHolder(mesh), strategy=strategy,
    )


def tsqr_svd(x, mesh=None):
    """SVD of a row-sharded tall-skinny matrix via TSQR.

    X = Q R; R = U_r S Vt (small, replicated)  ⇒  U = Q U_r (sharded).
    Twin of ``da.linalg.svd`` (SURVEY.md §3.4).
    """
    q, r = tsqr(x, mesh)
    u_r, s, vt = jnp.linalg.svd(r, full_matrices=False)
    return q @ u_r, s, vt


# ---------------------------------------------------------------------
# R alone: CholeskyQR2 that never holds Q (``PCA.fit``, ``TruncatedSVD``)

#: Rows of one MXU accumulation.  A float32 product that contracts over
#: rows loses, along the MXU's K dimension, what lies under the
#: accumulator's last bit, always downwards, so a sum of squares comes
#: out short: on one v5e at 25M x 64 (PERF.md section 6, PR 32) a Gram
#: matrix taken as one contraction had its diagonal 3.2e-3 short; 65,536
#: rows at a time left every eigenvalue 1.1e-5 short, 32,768 1.6e-6,
#: 16,384 and fewer 5e-8 to 1e-7, under float32's own 6e-8 to 1.2e-7,
#: while shorter blocks only add loop steps (2,048 rows: 2.3 times the
#: time).  So the passes below contract ``_R_BLOCK_ROWS`` rows at a time,
#: the largest block that loses nothing, and add the blocks' results
#: with compensation.
_R_BLOCK_ROWS = 16384
#: blocks of one step of the passes' ``while`` loops (the loop's own turn
#: costs about as much as a quarter of a block: 79.5 -> 72.4 ms a
#: factorization from 1 to 4, same PR)
_R_UNROLL = 4


def _two_sum(hi, lo, term):
    """``(hi, lo) + term`` with the rounding error of the addition kept
    in ``lo`` (Knuth's branch-free TwoSum; XLA does not reassociate
    floating-point adds, so the error term survives compilation)."""
    s = hi + term
    t = s - hi
    return s, lo + ((hi - (s - t)) + (term - t))


def _blocked_pass(xs, ms, per_block, out_shape):
    """One read of this shard's rows: ``sum_b per_block(x_b, m_b)`` over
    blocks of ``_R_BLOCK_ROWS`` rows, one block a loop step, the sum
    carried with compensation.  ``per_block`` maps ``(rows, d), (rows,)``
    to ``out_shape``."""
    m = xs.shape[0]
    block = min(_R_BLOCK_ROWS, m)
    steps, tail = divmod(m, block)

    def at(start, size):
        return per_block(jax.lax.dynamic_slice_in_dim(xs, start, size, 0),
                         jax.lax.dynamic_slice_in_dim(ms, start, size, 0))

    zero = jnp.zeros(out_shape, xs.dtype)
    hi, lo = jax.lax.fori_loop(
        0, steps, lambda i, carry: _two_sum(*carry, at(i * block, block)),
        (zero, zero), unroll=_R_UNROLL)
    if tail:
        hi, lo = _two_sum(hi, lo, at(steps * block, tail))
    return hi + lo


def _tsqr_r_fn(x, mask, mu, *, mesh_holder, find_mean, strategy):
    """``(r, mean, info)``: see ``tsqr_r``.  What is subtracted from the
    rows is ``mu``, or with ``find_mean`` their masked mean, found by a
    pass of this program."""
    from jax.scipy.linalg import solve_triangular

    mesh = mesh_holder.mesh
    d = x.shape[1]
    row_ax = data_axes(mesh)
    hi = jax.lax.Precision.HIGHEST

    def local(xs, ms, mu):
        ms = ms.astype(xs.dtype)
        passes = 0
        if find_mean:
            with jax.named_scope("pca.mean"):
                # mean = a + mean(x - a), a one real row: the sums stay at
                # the table's spread whatever its offset (masked_mean's
                # anchor, at one read of the table and not two)
                first = jax.lax.axis_index(row_ax) == 0
                a = jax.lax.psum(jnp.where(first, xs[0], 0.0), row_ax)
                sums = _blocked_pass(
                    xs, ms, lambda xb, mb: jnp.concatenate(
                        [jnp.sum((xb - a) * mb[:, None], axis=0),
                         jnp.sum(mb, keepdims=True)]),
                    (d + 1,))
                sums = jax.lax.psum(sums, row_ax)
                mu = a + sums[:d] / sums[d]
            passes += 1

        def gram(w=None):
            """``sum z'z`` over the real rows, ``z = (x - mu) [@ w]``:
            centring before any product, every product at HIGHEST."""
            def per_block(xb, mb):
                z = (xb - mu) * mb[:, None]
                if w is not None:
                    z = jnp.matmul(z, w, precision=hi)
                return jnp.matmul(z.T, z, precision=hi)

            return jax.lax.psum(
                _blocked_pass(xs, ms, per_block, (d, d)), row_ax)

        if strategy == "householder":
            with jax.named_scope("pca.householder"):
                z = (xs - mu) * ms[:, None]
                r1 = jnp.linalg.qr(z, mode="r")  # (k, d), k = min(m, d)
                r_all = jax.lax.all_gather(r1, row_ax)
                r = jnp.linalg.qr(r_all.reshape(-1, d), mode="r")
            return r, mu, jnp.array([passes + 1, 1], jnp.int32)

        eye = jnp.eye(d, dtype=xs.dtype)
        with jax.named_scope("pca.gram"):
            l1 = jnp.linalg.cholesky(gram())  # NaNs if not numerically PD
            r1_inv = solve_triangular(l1.T, eye, lower=False)
        with jax.named_scope("pca.repair"):
            # Q1 = (X - mu) R1^-1 a block at a time, its Gram added up:
            # how far Q1 is from orthonormal, without Q1
            g2 = gram(r1_inv)
            l2 = jnp.linalg.cholesky(g2)
            dev = jnp.linalg.norm(g2 - eye)
            ok = (jnp.isfinite(l1).all() & jnp.isfinite(l2).all()
                  & (dev < _CHOLQR2_DEV_MAX))
            r = jnp.matmul(l2.T, l1.T, precision=hi)  # R = R2 R1
        return r, mu, jnp.stack(
            [jnp.int32(passes + 2), ok.astype(jnp.int32)])

    return _shard_map(
        local, mesh, in_specs=(P(row_ax, None), P(row_ax), P()),
        out_specs=(P(), P(), P()),
    )(x, mask, mu)


# through the central program cache like the other fits' hot programs:
# its misses are what the benchmark's ``window.compiles`` counts.  No
# donation: every output is (d, d) or smaller, the table stays the caller's
from .. import programs as _programs  # noqa: E402

# graftlint: disable=donation-miss -- outputs are (d, d) and smaller; the table and its mask stay live in the caller
_tsqr_r_impl = _programs.cached_program(
    _tsqr_r_fn, name="tsqr.r",
    static_argnames=("mesh_holder", "find_mean", "strategy"),
)


def factor_r(x: ShardedRows, center=None, mesh=None, strategy=None):
    """``tsqr_r`` without its wait: ``(r, mean, info)`` as the program
    left them on the device.  A caller that has more to queue behind the
    factorization (``PCA._fit``) reads ``info`` when it must and calls
    ``householder_r`` itself where the guard did not hold."""
    d = x.data.shape[1]
    if x.data.shape[0] < d:
        # fewer real rows than columns are fine (R'R is still X'X; the
        # guard sends a rank-deficient Gram to Householder), but the
        # stacked rows must reach a (d, d) R
        raise ValueError(
            f"tsqr requires a tall-skinny matrix: got shape {x.shape} "
            "(rows < cols); use randomized_svd / svd_compressed instead"
        )
    if strategy in (None, "auto"):
        strategy = tsqr_strategy()
    elif strategy not in ("householder", "cholqr2"):
        raise ValueError(
            f"strategy must be householder|cholqr2|auto, got {strategy!r}"
        )
    if isinstance(center, str) and center != "mean":
        raise ValueError(f"center must be None, 'mean' or an array, "
                         f"got {center!r}")
    if center is None or isinstance(center, str):
        # a host array: it goes with the dispatch, where a device zeros
        # would be one more program in front of it
        mu = np.zeros((d,), x.data.dtype)
    else:
        mu = jnp.asarray(center, x.data.dtype).reshape(d)
    r, mean, info = _tsqr_r_impl(
        x.data, x.mask, mu, mesh_holder=_MeshHolder(mesh or get_mesh()),
        find_mean=isinstance(center, str), strategy=strategy)
    return r, (None if center is None else mean), info


def householder_r(x: ShardedRows, center=None, mesh=None):
    """``R`` by the backward-stable arm, for a table the CholeskyQR2 guard
    rejected.  It makes the centred copy and the QR's workspace: about
    three tables of memory, which only the table that needs it pays."""
    return factor_r(x, center, mesh, strategy="householder")[0]


def tsqr_r(x: ShardedRows, center=None, mesh=None, strategy=None):
    """The ``R`` of a row-sharded tall-skinny table, without ``Q``.

    ``R`` is (d, d), replicated, upper triangular, with ``R'R = (X -
    mu)'(X - mu)`` over the real rows (``x.mask``); ``center`` is None
    (``mu = 0``), an array of ``d`` numbers, or ``"mean"`` (the masked
    mean of the rows, computed by one more pass of the same program).
    Returns ``(r, mean, info)``: ``mean`` is what was subtracted (None
    without centring), ``info`` a host ``int32[2]``: the reads of the
    table the factorization made, and whether the CholeskyQR2 guard held
    (1) or the Householder arm was dispatched in its place (0).

    Under ``cholqr2`` every read of the table is a pass that keeps
    (d, d) state: the centred, masked Gram; its Cholesky ``R1``; the
    repair as blocks of rows ``(x_b - mu) R1^-1`` whose Grams are added;
    ``R = R2 R1``.  Nothing of the table's size is written.  Across
    chips the only collectives are ``psum``s of (d, d).
    """
    r, mean, info = factor_r(x, center, mesh, strategy)
    info = np.array(info)  # the wait for the factorization
    if not info[1]:
        r = householder_r(x, mean, mesh)
        info[0] += 1
    return r, mean, info
