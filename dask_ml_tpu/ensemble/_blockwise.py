"""Blockwise voting ensembles.

Reference: ``dask_ml/ensemble/_blockwise.py`` — fit one clone of the
sub-estimator per dask block (embarrassingly parallel), predict by
hard/soft vote (classifier) or mean (regressor).  Here "block" = an equal
row slice, and the embarrassing parallelism is REAL (SURVEY.md §2.2
"ensemble parallelism"):

* packable device-native sub-estimators (our SGD family) train as ONE
  vmapped XLA program — every member advances on its own block in a
  single dispatch per epoch (the shard_map-with-no-collectives layout,
  realized as a stacked model axis with stacked data);
* arbitrary sklearn sub-estimators fan out over a thread pool (their C
  kernels release the GIL), the thread-pool analogue of one-task-per-block.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..base import ClassifierMixin, RegressorMixin, TPUEstimator, clone
from ..core.sharded import ShardedRows, masked_unique, unshard
from ..utils import check_max_iter
from .. import sanitize as _san

#: runtime-verified twin of the epoch-boundary host-sync-loop
#: suppression in the packed ensemble epoch loop (see sanitize/sites.py)
_ENSEMBLE_SYNC = _san.AllowSite(
    "ensemble-epoch-sync", rule="host-sync-loop",
    cites="de76260843a0de2f",
    note="one mean-loss scalar per packed epoch, only when tol is set",
)


def _to_host_pair(X, y):
    Xh = unshard(X) if isinstance(X, ShardedRows) else np.asarray(X)
    yh = unshard(y) if isinstance(y, ShardedRows) else (np.asarray(y) if y is not None else None)
    return Xh, yh


# One compiled program per (loss, penalty, schedule, fit_intercept, shapes)
# for the WHOLE ensemble's epoch — module-level so repeated fits (grid
# search candidates, pipeline refits) reuse the executable instead of
# paying a fresh XLA compile per fit.
@partial(
    jax.jit,
    static_argnames=("loss", "penalty", "schedule", "fit_intercept"),
    donate_argnames=("states",),
)
def _ensemble_epoch(states, xb, yb, mask, hypers, *, loss, penalty,
                    schedule, fit_intercept):
    from ..linear_model._sgd import sgd_step

    step = partial(
        sgd_step, loss=loss, penalty=penalty, schedule=schedule,
        fit_intercept=fit_intercept,
    )
    # vmap over (state, OWN block, OWN mask, hyper): one dispatch per epoch
    return jax.vmap(step)(states, xb, yb, mask, hypers)


class _BlockwiseBase(TPUEstimator):
    def __init__(self, estimator, n_blocks=8):
        self.estimator = estimator
        self.n_blocks = n_blocks

    def _fit_blocks(self, X, y, **kwargs):
        if self.n_blocks < 1:
            raise ValueError("n_blocks must be >= 1")
        # the packed device path slices blocks straight from the (possibly
        # device-resident) arrays — NO host round-trip; only the thread
        # fallback for arbitrary sklearn estimators materializes X on host
        if self._try_fit_packed(X, y, kwargs):
            return self

        Xh, yh = _to_host_pair(X, y)
        n = Xh.shape[0]
        bounds = np.linspace(0, n, self.n_blocks + 1, dtype=int)
        spans = [(lo, hi) for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]
        members = [clone(self.estimator) for _ in spans]

        # mesh scoping is thread-local: re-enter the caller's mesh in
        # each worker so device-native members keep the active mesh
        from ..core.mesh import get_mesh, use_mesh

        mesh = get_mesh()

        def fit_one(pair):
            est, (lo, hi) = pair
            with use_mesh(mesh):
                if yh is not None:
                    est.fit(Xh[lo:hi], yh[lo:hi], **kwargs)
                else:
                    est.fit(Xh[lo:hi], **kwargs)
            return est

        from ..model_selection._search import _uses_device_estimator

        if _uses_device_estimator(self.estimator):
            # collective-safety (the PR-1 deadlock class): non-packable
            # DEVICE configs land here too (class_weight / adaptive lr /
            # early_stopping route past _try_fit_packed), and threads
            # interleaving their multi-device dispatch on the shared mesh
            # can deadlock the runtime.  A device fit occupies every
            # device, so threads buy no overlap for them: serialize.
            members = [fit_one(pair) for pair in zip(members, spans)]
        else:
            with ThreadPoolExecutor(
                max_workers=min(8, max(4, len(members)))
            ) as pool:
                members = list(pool.map(fit_one, zip(members, spans)))
        self.estimators_ = members
        self.n_features_in_ = Xh.shape[1]
        return self

    def _try_fit_packed(self, X, y, kwargs) -> bool:
        """Device-native path: same-config SGD members train as ONE stacked
        program — member i's batch is block i, so each epoch is a single
        vmapped dispatch for the whole ensemble.  Blocks are sliced from
        the input WHERE IT LIVES: a ShardedRows never round-trips to host
        (an O(n) device→host fetch and re-upload of data that is already
        where the program runs).  Returns False when the
        sub-estimator isn't packable (caller falls back to threads)."""
        from ..linear_model._sgd import SGDClassifier, sgd_init
        from ..model_selection._packing import pack_key

        probe = clone(self.estimator)
        if y is None or pack_key(probe) is None or self.n_blocks < 2:
            return False
        if getattr(probe, "class_weight", None) is not None:
            # the ensemble's packed epoch applies the plain validity mask
            # only; the threaded fallback's est.fit DOES apply weights —
            # route weighted members there instead of dropping weights
            return False
        if (getattr(probe, "learning_rate", None) == "adaptive"
                or getattr(probe, "early_stopping", False)):
            # the packed epoch has no per-member eta_scale decay or
            # validation split; each member's OWN fit() implements both,
            # so route these configs to the threaded fallback rather
            # than silently training at fixed eta / without a holdout
            return False

        if isinstance(X, ShardedRows):
            data = X.data.astype(jnp.float32)
            mask_full = X.mask
            ydata = y.data if isinstance(y, ShardedRows) else jnp.asarray(
                np.asarray(y))
        else:
            Xh = np.asarray(X, dtype=np.float32)
            data = jnp.asarray(Xh)
            mask_full = jnp.ones((data.shape[0],), jnp.float32)
            ydata = jnp.asarray(
                unshard(y) if isinstance(y, ShardedRows) else np.asarray(y)
            )
        n = data.shape[0]
        if ydata.shape[0] < n:  # host y vs padded device X: align lengths
            ydata = jnp.pad(ydata, (0, n - ydata.shape[0]))
        bounds = np.linspace(0, n, self.n_blocks + 1, dtype=int)
        spans = [(lo, hi) for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]
        members = [clone(self.estimator) for _ in spans]
        # equal block shapes are required to stack: pad every block to the
        # LONGEST span and mask the filler ("no silent caps" — the old
        # min-span trim dropped up to n_blocks-1 real rows).  Each slice
        # window is pulled left so it stays in bounds; `valid` marks where
        # the block's own rows sit inside its window.
        size = max(hi - lo for lo, hi in spans)
        sts = [min(lo, n - size) for lo, _hi in spans]
        valid = np.zeros((len(spans), size), np.float32)
        for b, ((lo, hi), st) in enumerate(zip(spans, sts)):
            valid[b, lo - st: hi - st] = 1.0
        xb = jnp.stack([jax.lax.dynamic_slice_in_dim(data, st, size) for st in sts])
        mask = jnp.stack([
            jax.lax.dynamic_slice_in_dim(mask_full, st, size) for st in sts
        ]).astype(jnp.float32) * jnp.asarray(valid)

        is_clf = isinstance(members[0], SGDClassifier)
        if is_clf:
            if "classes" in kwargs:
                classes = np.sort(np.asarray(kwargs["classes"]))
            elif isinstance(y, ShardedRows):
                classes = masked_unique(y.data, y.mask)
            else:
                classes = np.unique(np.asarray(ydata))
            for m in members:
                m._set_classes(classes)
            # ±1 one-vs-all targets built on device (device labels never
            # round-trip); shared encoder with the SGD streaming path
            enc = members[0]._encode_targets_device(ydata, mask_full)
        else:
            enc = ydata.astype(jnp.float32).reshape(-1, 1)
        yb = jnp.stack([jax.lax.dynamic_slice_in_dim(enc, st, size) for st in sts])

        from ..linear_model._sgd import EpochStopper

        m0 = members[0]
        k_out = yb.shape[2]
        for m in members:
            m._validate()
            m._state = sgd_init(xb.shape[2], k_out)
            m.n_features_in_ = int(xb.shape[2])
        states = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[m._state for m in members]
        )
        hypers = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[m._hyper() for m in members]
        )

        check_max_iter(m0.max_iter)
        stop = EpochStopper(m0.tol, getattr(m0, "n_iter_no_change", 5))
        for epoch in range(m0.max_iter):
            states, losses = _ensemble_epoch(
                states, xb, yb, mask, hypers, loss=m0.loss,
                penalty=m0.penalty, schedule=m0.learning_rate,
                fit_intercept=m0.fit_intercept,
            )
            # the host sync happens only when a tol check is active —
            # tol=None epochs pipeline without a device round-trip
            with _ENSEMBLE_SYNC.allow():
                # graftlint: disable=host-sync-loop -- epoch-boundary tol check, and only when tol is set; tol=None epochs pipeline freely
                if stop.active and stop.update(float(jnp.mean(losses))):
                    break
        for i, m in enumerate(members):
            m._state = jax.tree.map(lambda v: v[i], states)
            m.n_iter_ = epoch + 1
        self.estimators_ = members
        self.n_features_in_ = int(data.shape[1])
        return True


class BlockwiseVotingClassifier(ClassifierMixin, _BlockwiseBase):
    def __init__(self, estimator, voting="hard", classes=None, n_blocks=8):
        self.voting = voting
        self.classes = classes
        super().__init__(estimator, n_blocks=n_blocks)

    def fit(self, X, y, **kwargs):
        if self.voting not in ("hard", "soft"):
            raise ValueError(f"voting must be 'hard' or 'soft', got {self.voting!r}")
        self._fit_blocks(X, y, **kwargs)
        # keep classes_ sorted: vote counting indexes by searchsorted;
        # device labels are inventoried on device (no O(n) fetch)
        if self.classes is not None:
            self.classes_ = np.unique(np.asarray(self.classes))
        elif isinstance(y, ShardedRows):
            self.classes_ = masked_unique(y.data, y.mask)
        else:
            self.classes_ = np.unique(np.asarray(y))
        return self

    def predict(self, X):
        Xh, _ = _to_host_pair(X, None)
        if self.voting == "soft":
            return self.classes_[np.argmax(self.predict_proba(X), axis=1)]
        votes = np.stack([est.predict(Xh) for est in self.estimators_])  # (m, n)
        # majority vote via class-indexed bincount
        idx = np.searchsorted(self.classes_, votes)
        counts = np.apply_along_axis(
            lambda col: np.bincount(col, minlength=len(self.classes_)), 0, idx
        )
        return self.classes_[np.argmax(counts, axis=0)]

    def predict_proba(self, X):
        if self.voting != "soft":
            raise AttributeError("predict_proba requires voting='soft'")
        Xh, _ = _to_host_pair(X, None)
        # align each block's proba columns (its own classes_ subset) into
        # the global class inventory before averaging
        n = Xh.shape[0]
        k = len(self.classes_)
        acc = np.zeros((n, k))
        for est in self.estimators_:
            cols = np.searchsorted(self.classes_, est.classes_)
            if (cols >= k).any() or (self.classes_[cols] != est.classes_).any():
                raise ValueError(
                    f"block estimator saw classes {est.classes_} outside {self.classes_}"
                )
            acc[:, cols] += np.asarray(est.predict_proba(Xh))
        return acc / len(self.estimators_)

    def score(self, X, y):
        from ..metrics import accuracy_score

        _, yh = _to_host_pair(X, y)
        return accuracy_score(yh, self.predict(X).astype(yh.dtype))


class BlockwiseVotingRegressor(RegressorMixin, _BlockwiseBase):
    def fit(self, X, y, **kwargs):
        return self._fit_blocks(X, y, **kwargs)

    def predict(self, X):
        Xh, _ = _to_host_pair(X, None)
        return np.stack([est.predict(Xh) for est in self.estimators_]).mean(axis=0)

    def score(self, X, y):
        from ..metrics import r2_score

        _, yh = _to_host_pair(X, y)
        return r2_score(yh, self.predict(X))
