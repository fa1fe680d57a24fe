"""Row-sharded arrays: the ``dask.array`` replacement.

The reference chunks the sample axis into blocks and builds per-block tasks
(``da.blockwise`` / ``map_blocks`` — SURVEY.md §1 L2).  Here the sample axis
is sharded over the mesh's ``data`` axis.  Because XLA wants static,
divisible shapes, rows are **padded** up to a multiple of the data-axis size
and a float mask marks real rows; every reduction in the framework is
mask-weighted, and outputs are sliced back to the true row count at the API
boundary.  This pad+mask discipline is what lets every fit step compile to a
single fused XLA program with no dynamic shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import data_axes, data_axes_size, get_mesh


def pad_rows(x: np.ndarray, multiple: int):
    """Pad axis 0 of ``x`` up to a multiple; returns (padded, n_real)."""
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(np.asarray(x), pad_width), n


def row_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """NamedSharding that splits axis 0 over every data-carrying axis
    (``('dcn', 'data')`` on a hierarchical mesh), replicates the rest."""
    spec = P(data_axes(mesh), *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicate(x, mesh: Mesh | None = None):
    """Place ``x`` replicated across the mesh."""
    mesh = mesh or get_mesh()
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))


@dataclass(frozen=True)
class ShardedRows:
    """A 1- or 2-D array sharded by rows over the mesh data axis.

    Attributes:
      data: padded jax.Array, axis 0 divisible by the data-axis size.
      mask: float (padded_n,) — 1.0 for real rows, 0.0 for padding.
      n_samples: true row count.
    """

    data: jax.Array
    mask: jax.Array
    n_samples: int

    @property
    def shape(self):
        return (self.n_samples,) + self.data.shape[1:]

    @property
    def padded(self) -> int:
        return self.data.shape[0]

    @property
    def dtype(self):
        return self.data.dtype

    def unpad(self, x=None):
        """Slice a padded-rows result back to the true row count."""
        x = self.data if x is None else x
        return x[: self.n_samples]


def shard_rows(
    x,
    mesh: Mesh | None = None,
    *,
    dtype=None,
) -> ShardedRows:
    """Ingest a host array as a row-sharded, padded ``ShardedRows``.

    Already-sharded inputs pass through; the mask is rebuilt only if absent.
    """
    if isinstance(x, ShardedRows):
        return x
    # collective-layer fault-injection point (resilience.testing): the
    # in-process stand-in for an ICI/DCN transport fault at the sharding
    # boundary; a no-op unless a FaultPlan is active
    from ..resilience.testing import maybe_fault

    maybe_fault("collective")
    mesh = mesh or get_mesh()
    n_shards = data_axes_size(mesh)
    if isinstance(x, jax.Array):
        # DEVICE-resident input stays on device: np.asarray(x) here
        # would be a device->host fetch and the re-ingest a host->device
        # upload — a full round trip per call.  Padding/mask build on
        # device; device_put onto the row sharding is a device-side
        # reshard.
        if dtype is not None:
            x = x.astype(dtype)
        n = x.shape[0]
        pad = (-n) % n_shards
        if pad:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        data = jax.device_put(x, row_sharding(mesh, x.ndim))
        mask = _row_mask(
            n, padded=n + pad, sharding=row_sharding(mesh, 1))
        return ShardedRows(data=data, mask=mask, n_samples=n)
    x = np.asarray(x)
    if dtype is not None:
        x = x.astype(dtype)
    padded, n = pad_rows(x, n_shards)
    mask_np = np.zeros(padded.shape[0], dtype=np.float32)
    mask_np[:n] = 1.0
    # the HOST array goes to device_put with the sharding, so each device
    # receives only its own rows and none ever holds the whole array
    data = jax.device_put(padded, row_sharding(mesh, padded.ndim))
    mask = jax.device_put(mask_np, row_sharding(mesh, 1))
    return ShardedRows(data=data, mask=mask, n_samples=n)


def as_sharded(x):
    """Wrap a RAW device array (1-D targets or 2-D designs alike) into
    :class:`ShardedRows` (device-side pad+mask, no host round trip);
    everything else — ShardedRows, numpy, pandas, lists, None — passes
    through unchanged.  Entry points that dispatch on ShardedRows
    (estimator ``fit``/``score``, the CV search) apply this so raw
    ``jax.Array`` inputs ride the no-fetch device paths (class
    discovery, device scoring, device fold slicing) instead of falling
    back to an O(n) ``np.asarray`` fetch; paths that already route
    through :func:`shard_rows`/solver ``_prep`` get the same treatment
    from those functions' own device branches."""
    if isinstance(x, jax.Array):
        return shard_rows(x)
    return x


def unshard(x) -> np.ndarray:
    """Bring a (possibly sharded) array back to host memory."""
    from ..resilience.testing import maybe_fault
    # instrumented AT THE DEFINITION, not by patching the module attr:
    # most call sites bound `unshard` by name at import time, so a patch
    # would miss them — and the bulk device_get below rides numpy's
    # buffer protocol, invisible to the sanitizer's ArrayImpl hook
    from ..sanitize.core import record_d2h

    maybe_fault("collective")
    record_d2h()
    if isinstance(x, ShardedRows):
        x = x.unpad()
    return np.asarray(jax.device_get(x))


# The masked reductions reduce over the (padded, sharded) row axis only —
# that is the axis the mask lives on.


@jax.jit
def masked_sum(x, mask):
    """Sum over rows counting only real (mask==1) rows."""
    m = mask.reshape(mask.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
    return jnp.sum(x * m, axis=0)


def _masked_anchor(x, m):
    """A valid data value per feature to shift by: moments computed on
    (x − anchor) work at the data's SPREAD scale instead of its offset
    scale.  At offset 1e6 in f32 a raw-scale mean carries ~0.1 absolute
    error which enters the variance as its square (2.3% var error, found
    by an r4 adversarial property test); after shifting, the subtraction
    x − anchor is exact for values within 2× of the anchor (Sterbenz)
    and the residual moments are accurate to ~eps·spread."""
    anchor = jnp.min(jnp.where(m > 0, x, jnp.inf), axis=0)
    return jnp.where(jnp.isfinite(anchor), anchor, 0.0)


@jax.jit
def masked_mean(x, mask):
    m = mask.reshape(mask.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
    anchor = _masked_anchor(x, m)
    shifted = jnp.sum((x - anchor) * m, axis=0) / jnp.sum(m, axis=0)
    return anchor + shifted


@partial(jax.jit, static_argnames=("ddof",))
def masked_var(x, mask, ddof=0):
    m = mask.reshape(mask.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
    count = jnp.sum(m, axis=0)
    anchor = _masked_anchor(x, m)
    xs = x - anchor
    mean_s = jnp.sum(xs * m, axis=0) / count
    sq = jnp.sum((xs - mean_s) ** 2 * m, axis=0)
    return sq / (count - ddof)


#: Distinct values the class-discovery scan collects before it hands the
#: vector to the sort.  On one v5e a step over 31.25M float32 labels
#: takes 0.17 ms and the sort 494-784 ms, so every label vector of up to
#: ``UNIQUE_CAP`` classes is found in at most 45 ms, and a fall-back
#: after ``UNIQUE_CAP`` wasted steps adds those 45 ms, 6-9%, to the sort
#: (PERF.md section 6, PR 27).
UNIQUE_CAP = 256


@jax.jit
def _unique_scan(data, mask):
    """Ascending scan for the distinct values of the real rows:
    ``(values[UNIQUE_CAP], k, overflow)``, of which ``values[:k]`` are
    found.  ``overflow`` says the answer is not in ``values``: more than
    ``UNIQUE_CAP`` values exist, or a real float row is NaN (no order to
    scan in).  One pass finds the least and the largest real value, then
    every step the least value above the last, until the largest is
    reached: per shard a ``min`` and an all-reduce of one scalar.  The
    loop ends on a value it has read, never on a sentinel, so the dtype's
    largest value is a value like any other."""
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.uint8)
    floating = jnp.issubdtype(data.dtype, jnp.floating)
    if floating:
        bottom, top = -jnp.inf, jnp.inf
    else:
        bottom, top = jnp.iinfo(data.dtype).min, jnp.iinfo(data.dtype).max
    real = mask > 0

    def least(sel):
        return jnp.min(jnp.where(sel, data, jnp.array(top, data.dtype)))

    def step(carry):
        values, k, v = carry
        v = least(real & (data > v))
        return values.at[k].set(v), k + 1, v

    first = least(real)
    last = jnp.max(jnp.where(real, data, jnp.array(bottom, data.dtype)))
    k = jnp.any(real).astype(jnp.int32)  # no real row: nothing found
    values, k, v = jax.lax.while_loop(
        lambda c: (c[2] < last) & (c[1] < UNIQUE_CAP), step,
        (jnp.zeros(UNIQUE_CAP, data.dtype).at[0].set(first), k, first))
    overflow = v < last
    if floating:
        overflow = overflow | jnp.any(real & jnp.isnan(data))
    return values, k, overflow


def masked_unique(data, mask, span=None) -> np.ndarray:
    """Sorted distinct values of the real (mask > 0) rows of a device
    vector, on the host in the input dtype: ``np.unique`` of the real
    rows, with neither the rows nor a sort crossing anything.  One
    program scans for them in ascending order (:func:`_unique_scan`) and
    one fetch brings ``(values, k, overflow)`` back; a vector of more
    than ``UNIQUE_CAP`` values, or with a NaN, is sorted by
    ``jnp.unique`` as before.  ``span``, an open ``obs`` span, is told
    which ``path`` ran and its ``scan_steps``; the registry counts
    ``classes.scan`` / ``classes.sort``."""
    from .. import obs

    values, k, overflow = jax.device_get(_unique_scan(data, mask))
    found = values[:k].astype(data.dtype)
    if span is not None:
        span.set(path="sort" if overflow else "scan", scan_steps=int(k))
    if not overflow:
        obs.registry().counter("classes.scan").inc()
        return found
    obs.registry().counter("classes.sort").inc()
    # pad rows take a real value (the scan's first), so that they add
    # no value of their own to the sort
    return np.asarray(jnp.unique(jnp.where(mask > 0, data, found[0])))


@partial(jax.jit, static_argnames=("padded", "sharding"))
def _row_mask(n, *, padded, sharding):
    """The mask of ``n`` real rows among ``padded``, born with the row
    sharding: every device makes its own rows' part.  Made eagerly it
    was an ``int32`` counter, a ``bool`` and a ``float32`` vector of ALL
    the rows on the default device before the scatter: 2.25 GB beside
    that chip's share of a 250M-row table (PERF.md section 6, PR 34)."""
    return jax.lax.with_sharding_constraint(
        (jnp.arange(padded) < n).astype(jnp.float32), sharding)
