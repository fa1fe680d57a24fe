"""Splitters — twin of ``dask_ml/model_selection/_split.py``
(``train_test_split``, ``ShuffleSplit``, ``KFold``; SURVEY.md §2 #25).

The reference splits blockwise (per-chunk shuffles, contiguous slabs).
Here splits are index-based on the host (indices are O(n) ints) and the
selected rows are gathered device-side, so a split of a sharded array
yields sharded arrays without materializing X on the host.  The one
splitter whose folds ARE contiguous slabs, this module's unshuffled
``KFold``, needs no indices at all on sharded input: a fold is its bounds
``(lo, hi)`` and ``_fold_slabs`` cuts it on the device.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..core.mesh import MeshHolder, data_axes_size, get_mesh
from ..core.sharded import ShardedRows, row_sharding
from ..utils import check_random_state


def _n_samples(a):
    return a.n_samples if isinstance(a, ShardedRows) else np.asarray(a).shape[0]


@partial(jax.jit, static_argnames=("mesh_holder",))
def _gather_rows(x, idx, *, mesh_holder):
    """Device-side row gather with the output re-sharded over the data
    axis — XLA emits the collective permute; no bytes touch the host."""
    out = jnp.take(x, idx, axis=0)
    return jax.lax.with_sharding_constraint(
        out, row_sharding(mesh_holder.mesh, x.ndim)
    )


def _take(a, idx):
    """Row-subset of an array-like; sharded in → sharded out.

    The gather runs entirely on device (the old
    path did device→host→device per split); the index set is padded to the
    shard multiple and masked, same discipline as ingest.
    """
    if isinstance(a, ShardedRows):
        from ..core.sharded import pad_rows

        mesh = get_mesh()
        n_shards = data_axes_size(mesh)
        idx, k = pad_rows(np.asarray(idx, dtype=np.int32), n_shards)
        mask_np = np.zeros(idx.shape[0], dtype=np.float32)
        mask_np[:k] = 1.0
        data = _gather_rows(
            a.data, jnp.asarray(idx), mesh_holder=MeshHolder(mesh)
        )
        mask = jax.device_put(jnp.asarray(mask_np), row_sharding(mesh, 1))
        return ShardedRows(data=data, mask=mask, n_samples=k)
    if hasattr(a, "iloc"):  # pandas DataFrame/Series stay pandas
        # (reference semantics: dask-ml splits dataframes partition-wise
        # and returns dataframes)
        return a.iloc[idx]
    return np.asarray(a)[idx]


def _take_host_bytes(a, idx) -> int:
    """Bytes of the index and mask arrays ``_take(a, idx)`` makes on the
    host and sends to the device (``int32`` + ``float32`` a padded row);
    0 for anything but sharded rows."""
    if not isinstance(a, ShardedRows):
        return 0
    return 8 * (len(idx) + (-len(idx)) % data_axes_size(get_mesh()))


@partial(jax.jit, static_argnames=("lo", "hi", "n", "mesh_holder"))
def _fold_slabs_fn(arrays, *, lo, hi, n, mesh_holder):
    """One fold of an unshuffled ``KFold`` as slabs: for each array of
    ``arrays`` (rows ``[0, n)`` real) its train rows ``[0, lo) + [hi, n)``
    and its held-out rows ``[lo, hi)``, and one mask for either side.
    What ``_gather_rows`` returns for the sorted indices, bit for bit (pad
    rows repeat row 0, as a gather by a zero-padded index does), with no
    index: slices and a concatenation, row-sharded over the mesh."""
    mesh = mesh_holder.mesh
    shards = data_axes_size(mesh)

    def side(x, pieces, k):
        pad = (-k) % shards
        if pad:
            pieces = pieces + [jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])]
        out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
        return jax.lax.with_sharding_constraint(
            out, row_sharding(mesh, x.ndim))

    def mask(k):
        rows = k + (-k) % shards
        return jax.lax.with_sharding_constraint(
            (jnp.arange(rows) < k).astype(jnp.float32),
            row_sharding(mesh, 1))

    k_test = hi - lo
    train = [side(x, [x[:lo], x[hi:n]], n - k_test) for x in arrays]
    test = [side(x, [x[lo:hi]], k_test) for x in arrays]
    return train, mask(n - k_test), test, mask(k_test)


def _fold_slabs(X, y, lo: int, hi: int):
    """``(Xtr, ytr, Xte, yte)``: the fold of ``X`` (``ShardedRows``) and
    ``y`` (``ShardedRows`` of its length, or None) whose held-out rows are
    ``[lo, hi)``, cut on the device by ONE program.  Nothing of the
    table's length is made on the host or crosses to the device; the
    arrays of one side share one mask."""
    n, k_test = X.n_samples, int(hi) - int(lo)
    train, m_train, test, m_test = _fold_slabs_fn(
        tuple(a.data for a in (X, y) if a is not None), lo=int(lo),
        hi=int(hi), n=n, mesh_holder=MeshHolder(get_mesh()))
    tr = [ShardedRows(data=d, mask=m_train, n_samples=n - k_test)
          for d in train] + [None]
    te = [ShardedRows(data=d, mask=m_test, n_samples=k_test)
          for d in test] + [None]
    return tr[0], tr[1], te[0], te[1]


def _as_count(v, n):
    """Float in (0, 1] → fraction of n; int → absolute count (sklearn rule)."""
    if isinstance(v, float) and v <= 1.0:
        return int(round(v * n))
    return int(v)


def _resolve_sizes(n, train_size, test_size):
    if train_size is None and test_size is None:
        test_size = 0.25
    if test_size is None:
        n_test = n - _as_count(train_size, n)
    else:
        n_test = _as_count(test_size, n)
    if train_size is None:
        n_train = n - n_test
    else:
        n_train = _as_count(train_size, n)
    if n_train + n_test > n:
        raise ValueError(
            f"train_size + test_size = {n_train + n_test} > n_samples = {n}"
        )
    if n_train <= 0 or n_test <= 0:
        raise ValueError(f"Degenerate split: n_train={n_train}, n_test={n_test}")
    return n_train, n_test


class ShuffleSplit:
    """Random permutation splits (reference: per-block shuffle)."""

    def __init__(self, n_splits=10, test_size=None, train_size=None,
                 blockwise=True, random_state=None):
        self.n_splits = n_splits
        self.test_size = test_size
        self.train_size = train_size
        self.blockwise = blockwise
        self.random_state = random_state

    def split(self, X, y=None, groups=None):
        n = _n_samples(X)
        n_train, n_test = _resolve_sizes(n, self.train_size, self.test_size)
        rng = check_random_state(self.random_state)
        for _ in range(self.n_splits):
            perm = rng.permutation(n)
            yield np.sort(perm[:n_train]), np.sort(perm[n_train:n_train + n_test])

    def get_n_splits(self, X=None, y=None, groups=None):
        return self.n_splits


class KFold:
    """Contiguous-slab K folds (reference semantics)."""

    def __init__(self, n_splits=5, shuffle=False, random_state=None):
        self.n_splits = n_splits
        self.shuffle = shuffle
        self.random_state = random_state

    def bounds(self, n: int):
        """The folds' edges in ``[0, n]``, ``n_splits + 1`` of them: fold
        ``i`` holds out positions ``[bounds[i], bounds[i + 1])`` (of the
        rows themselves when unshuffled)."""
        if self.n_splits < 2:
            raise ValueError("n_splits must be >= 2")
        if self.n_splits > n:
            raise ValueError(f"n_splits={self.n_splits} > n_samples={n}")
        return np.linspace(0, n, self.n_splits + 1, dtype=int)

    def split(self, X, y=None, groups=None):
        n = _n_samples(X)
        bounds = self.bounds(n)
        idx = np.arange(n)
        if self.shuffle:
            check_random_state(self.random_state).shuffle(idx)
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            test = idx[lo:hi]
            train = np.concatenate([idx[:lo], idx[hi:]])
            yield np.sort(train), np.sort(test)

    def get_n_splits(self, X=None, y=None, groups=None):
        return self.n_splits


def train_test_split(*arrays, test_size=None, train_size=None, random_state=None,
                     shuffle=True, blockwise=True, stratify=None, **options):
    """Split each array into train/test (reference ``train_test_split``).

    ``stratify`` takes a HOST label array (sklearn semantics: class
    proportions preserved in both splits).  Sharded label arrays are
    rejected with guidance — stratified selection needs the full label
    vector on host, an O(n) pull the sharded path refuses implicitly.
    """
    if not arrays:
        raise ValueError("At least one array required")
    if options:
        raise TypeError(f"Unexpected kwargs: {sorted(options)}")
    n = _n_samples(arrays[0])
    for a in arrays[1:]:
        if _n_samples(a) != n:
            raise ValueError("All arrays must have the same length")
    n_train, n_test = _resolve_sizes(n, train_size, test_size)
    if stratify is not None:
        if isinstance(stratify, ShardedRows):
            raise ValueError(
                "stratify requires host labels (an O(n) pull for sharded "
                "arrays): pass the original host label array, or use "
                "sklearn's StratifiedKFold via the CV searches"
            )
        if not shuffle:
            raise ValueError("stratify requires shuffle=True")
        from sklearn.model_selection import StratifiedShuffleSplit

        sss = StratifiedShuffleSplit(
            n_splits=1, train_size=n_train, test_size=n_test,
            random_state=random_state,
        )
        train_idx, test_idx = next(
            sss.split(np.zeros((n, 1)), np.asarray(stratify))
        )
        train_idx, test_idx = np.sort(train_idx), np.sort(test_idx)
    elif shuffle:
        rng = check_random_state(random_state)
        perm = rng.permutation(n)
        train_idx = np.sort(perm[:n_train])
        test_idx = np.sort(perm[n_train:n_train + n_test])
    else:
        train_idx = np.arange(n_train)
        test_idx = np.arange(n_train, n_train + n_test)
    out = []
    for a in arrays:
        out.append(_take(a, train_idx))
        out.append(_take(a, test_idx))
    return out
