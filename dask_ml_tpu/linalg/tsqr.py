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
| ``auto`` (default: ``cholqr2``; no chip reading exists, PERF.md
section 7 row 6).

Padding note: zero rows contribute nothing to R (or to the Gram) and
produce zero rows of Q, so the pad+mask ingest discipline composes
transparently (provided padded rows are zeroed — masked centering does
this).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
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
    so the default costs no correctness.  No chip reading of the two
    strategies exists (PERF.md section 7 row 6 is the cell that would
    give one).
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
