"""Multi-model packing: train a cohort of models in ONE XLA program.

The reference's "model-parallel search" is task parallelism — one dask
future per candidate model (``dask_ml/model_selection/_incremental.py ::
_fit`` submits per-model ``_partial_fit`` futures; SURVEY.md §2.2 row 2).
On TPU, dispatching one tiny program per model leaves the chip idle between
dispatches; the idiomatic inversion (SURVEY.md §7 hard-part (c)) is to
**vmap the SGD update over a stacked model axis**: configurations that share
the compiled branches (loss / penalty / schedule — the *static* part of a
config) are bucketed together, their state pytrees stacked to ``[M, d, K]``
and their hyperparameters to ``[M]`` traced scalars, and one fused program
advances all M models on the same data block.

When the active mesh has a nontrivial ``model`` axis, the stacked state is
sharded over MODEL_AXIS and the batch over DATA_AXIS — each device group
trains its slice of the cohort on its slice of the rows, with XLA inserting
the data-axis psum for the gradients: 2-D (model × data) parallelism from
annotations alone, the scaling-book recipe.

``BaseIncrementalSearchCV`` uses this automatically: each adaptive round
groups the instructed models by (pack key, budget, step counter) and trains
every lockstep group through one :class:`Cohort` — so a Hyperband bracket
of 30 homogeneous configs costs ~1 dispatch per block instead of 30.
``DISPATCH_STATS`` records the packing wins so tests (and users) can verify
N models trained with ≪N dispatches.
"""

from __future__ import annotations

import logging
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import programs as _programs
from ..core.mesh import DATA_AXIS, MODEL_AXIS, get_mesh
from ..linear_model._sgd import _HYPER_KEYS, SGDClassifier, SGDRegressor, \
    sgd_step

__all__ = ["pack_key", "Cohort", "DISPATCH_STATS", "reset_dispatch_stats"]

logger = logging.getLogger(__name__)

# Observability: how many fused dispatches ran vs how many model-steps they
# covered.  A packed round of M models advances models_stepped by M while
# dispatches grows by 1.
DISPATCH_STATS = {"dispatches": 0, "models_stepped": 0, "cohorts": 0,
                  "score_dispatches": 0}


def reset_dispatch_stats():
    for k in DISPATCH_STATS:
        DISPATCH_STATS[k] = 0


def pack_key(model):
    """Hashable static-config key, or None if the model can't be packed.

    Models sharing a key compile to the SAME branches of the SGD step, so
    only their (traced) hyperparameter scalars differ — the precondition
    for stacking them under vmap with zero recompilation.
    """
    if isinstance(model, (SGDClassifier, SGDRegressor)):
        if getattr(model, "class_weight", None) == "balanced":
            # 'balanced' needs the full label distribution — invalid for
            # the block-streaming plane (partial_fit raises the same way)
            return None
        return (
            type(model).__name__,
            model.loss,
            model.penalty,
            model.learning_rate,
            model.fit_intercept,
        )
    return None


def _packed_accuracy_impl(states, xb, yb, mask):
    """vmap of masked accuracy over the stacked model axis.

    ``yb`` is the shared ±1 one-vs-all target matrix; the true class
    index is recovered from it (binary: sign of the single column),
    so no separate label array is threaded through."""
    if yb.shape[1] == 1:
        y_idx = (yb[:, 0] > 0).astype(jnp.int32)
    else:
        y_idx = jnp.argmax(yb, axis=1).astype(jnp.int32)

    def one(state):
        m = xb @ state["coef"] + state["intercept"]
        if m.shape[1] == 1:
            pred = (m[:, 0] > 0).astype(jnp.int32)
        else:
            pred = jnp.argmax(m, axis=1).astype(jnp.int32)
        hit = (pred == y_idx).astype(jnp.float32) * mask
        from ..utils import safe_denominator

        return jnp.sum(hit) / safe_denominator(jnp.sum(mask))

    return jax.vmap(one)(states)


@lru_cache(maxsize=8)
def _packed_accuracy_jit(rep_sharding):
    """One jit wrapper per output sharding (i.e. per mesh) — a fresh
    jax.jit every call would re-trace each scoring round.  Bounded: the
    key holds a Mesh reference, and an unbounded cache would pin every
    mesh a long-lived process (or the test suite's per-fixture meshes)
    ever built, executables included."""
    return jax.jit(_packed_accuracy_impl, out_shardings=rep_sharding)


def _packed_step_impl(states, xb, yb, mask, hypers, *, loss, penalty,
                      schedule, fit_intercept):
    """vmap of the single-model fused step over the stacked model axis.
    Data (xb/yb/mask) is broadcast; states and hyperparameters carry the
    model axis.  One XLA program, M models."""
    step = partial(
        sgd_step, loss=loss, penalty=penalty, schedule=schedule,
        fit_intercept=fit_intercept,
    )
    # mask carries the model axis: per-model class weights fold into each
    # lane's mask (a weightless cohort passes M broadcast copies)
    return jax.vmap(step, in_axes=(0, None, None, 0, 0))(
        states, xb, yb, mask, hypers
    )


# One compiled program per (statics, M, shapes); the stacked state is
# donated so the whole cohort advances in place in HBM.  Routed through
# the central program cache (design.md §12) so the concurrent search
# orchestrator can WARM the next round's re-packed signature on the
# blessed compile-ahead thread (``Cohort.warm``) and graftscope
# attributes the packed program's device time + roofline cost under its
# own name.
_packed_step = _programs.cached_program(
    _packed_step_impl, name="search.packed_step",
    static_argnames=("loss", "penalty", "schedule", "fit_intercept"),
    donate_argnames=("states",),
)


def _model_sharding(mesh, ndim):
    """Shard the leading (model) axis over MODEL_AXIS, replicate the rest."""
    return NamedSharding(mesh, P(MODEL_AXIS, *([None] * (ndim - 1))))


class Cohort:
    """A lockstep group of same-pack-key SGD models trained as one stack.

    Stacks the per-model state pytrees once, advances them with
    :func:`_packed_step` for any number of blocks, then ``finalize()``
    writes each model's slice (and final loss) back — models behave exactly
    as if ``partial_fit`` had been called on each individually.
    """

    def __init__(self, models, classes=None):
        if not models:
            raise ValueError("empty cohort")
        keys = {pack_key(m) for m in models}
        if len(keys) != 1 or None in keys:
            raise ValueError(f"models are not packable together: {keys}")
        for m in models:
            # same hyperparameter validation the unpacked plane applies in
            # partial_fit — packed and unpacked rounds must reject the same
            # configs (e.g. alpha=0 with learning_rate='optimal')
            m._validate()
        self.models = list(models)
        self._m0 = models[0]
        self._classes = classes
        self._stacked = None
        self._losses = None
        # captured HERE (the dispatch thread, under the caller's mesh
        # scope): warm() runs on the prefetch worker, whose thread-local
        # mesh would read as the default — the model-axis width decides
        # whether _stack() will shard (and so whether a shape-struct
        # warm can ever match the real signature)
        self._model_ax = get_mesh().shape.get(MODEL_AXIS, 1)

    # -- target prep (shared across the cohort: same y, same classes) ----
    def _prep(self, X, y, with_weights=True):
        from ..core.sharded import ShardedRows

        m0 = self._m0
        if isinstance(m0, SGDClassifier):
            for m in self.models:
                if not hasattr(m, "classes_"):
                    if self._classes is None:
                        raise ValueError(
                            "classes must be provided to pack unfitted "
                            "classifiers (pass classes= to fit)"
                        )
                    m._set_classes(self._classes)
            if isinstance(y, ShardedRows) and isinstance(X, ShardedRows):
                # device blocks (see _incremental._to_blocks): encode on
                # device, zero host I/O on the packed training path
                targets = m0._encode_targets_device(y.data, y.mask)
            else:
                targets = m0._encode_targets(np.asarray(y))
        else:
            targets = m0._targets(y, X)
        xb, yb, mask = m0._prep_block(X, targets)
        for m in self.models:
            m._ensure_state(xb.shape[1])
        # per-model weighted masks: each lane's class_weight (dict) scales
        # its own copy of the block mask, so weighted models pack too
        n_real = (
            X.n_samples if isinstance(X, ShardedRows)
            else int(np.asarray(X).shape[0])
        )
        if with_weights and any(
            getattr(m, "class_weight", None) is not None for m in self.models
        ):
            masks = jnp.stack([
                m._apply_weights(yb, mask, None, n_real,
                                 allow_balanced=False)
                if getattr(m, "class_weight", None) is not None else mask
                for m in self.models
            ])
        else:
            masks = jnp.broadcast_to(mask, (len(self.models),) + mask.shape)
        return xb, yb, masks, mask

    def _stack(self):
        states = [m._state for m in self.models]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        hypers = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[m._hyper() for m in self.models]
        )
        mesh = get_mesh()
        M = len(self.models)
        model_ax = mesh.shape.get(MODEL_AXIS, 1)
        if model_ax > 1:
            if M % model_ax == 0:
                stacked = jax.tree.map(
                    lambda x: jax.device_put(x, _model_sharding(mesh, x.ndim)),
                    stacked,
                )
                hypers = jax.tree.map(
                    lambda x: jax.device_put(x, _model_sharding(mesh, x.ndim)),
                    hypers,
                )
            else:
                # no silent caps: a user who built a 2-D mesh loses
                # model-parallelism here — say so instead of quietly
                # training replicated
                logger.warning(
                    "cohort of %d models does not divide the mesh model "
                    "axis (%d); training replicated without MODEL_AXIS "
                    "sharding — pad the cohort to a multiple of %d to "
                    "shard it",
                    M, model_ax, model_ax,
                )
        return stacked, hypers

    def _advance(self, xb, yb, masks):
        """The device half every training entry funnels through: stack
        lazily, dispatch ONE packed step, book the stats."""
        if self._stacked is None:
            self._stacked, self._hypers = self._stack()
        m0 = self._m0
        self._stacked, self._losses = _packed_step(
            self._stacked, xb, yb, masks, self._hypers,
            loss=m0.loss, penalty=m0.penalty, schedule=m0.learning_rate,
            fit_intercept=m0.fit_intercept,
        )
        DISPATCH_STATS["dispatches"] += 1
        DISPATCH_STATS["models_stepped"] += len(self.models)
        return self

    def step(self, X, y):
        """Advance every model in the cohort by one block: ONE dispatch."""
        xb, yb, masks, _base = self._prep(X, y)
        return self._advance(xb, yb, masks)

    def partial_fit(self, X, y=None, **kwargs):
        """Duck-type the estimator surface for the shared pipeline
        discipline: a cohort consumes ``(X, y)`` blocks exactly like a
        single model (``classes`` already rode in at construction —
        extra fit kwargs are the single-model plane's concern and were
        validated before the cohort was packed)."""
        return self.step(X, y)

    # -- staged streaming protocol (pipeline.UnitStream) -----------------
    def _pf_stage(self, X, y, classes=None, sample_weight=None, **kwargs):
        """Host parse → target encode → bucket-pad → device upload for
        ONE cohort block; returns the staged ``(xb, yb, mask)`` payload
        for :meth:`_pf_consume`, or None to decline THAT block (the
        pipeline then routes it through :meth:`partial_fit` on the
        dispatch thread).  Declines device-resident blocks (staging them
        would dispatch programs off-thread — the PR-1 deadlock class),
        per-call weighting, and weighted members (their per-lane masks
        are a device program).  Safe on the prefetch worker thread:
        pure host work plus H2D puts."""
        from ..core.sharded import ShardedRows

        if (kwargs or sample_weight is not None or y is None
                or isinstance(X, (ShardedRows, jnp.ndarray))
                or isinstance(y, (ShardedRows, jnp.ndarray))
                or any(getattr(m, "class_weight", None) is not None
                       for m in self.models)):
            return None
        m0 = self._m0
        if isinstance(m0, SGDClassifier):
            if not hasattr(m0, "classes_"):
                cls = classes if classes is not None else self._classes
                if cls is None:
                    return None  # first consume derives classes serially
                for m in self.models:
                    if not hasattr(m, "classes_"):
                        m._set_classes(cls)
            targets = m0._encode_targets(np.asarray(y))
        else:
            targets = m0._targets_host(y)
        staged = m0._prep_block_host(X, targets)
        # compile-ahead: the re-packed round's stacked program builds on
        # the blessed compile thread while the previous block computes
        self.warm(staged[0].shape, staged[1].shape[1])
        return staged

    def _pf_consume(self, staged):
        """Device step on a block pre-staged by :meth:`_pf_stage` — the
        shared ``mask`` broadcasts over the model axis here (weighted
        cohorts declined at stage time).  Dispatch-thread only."""
        xb, yb, mask = staged
        for m in self.models:
            m._ensure_state(xb.shape[1])
        masks = jnp.broadcast_to(mask, (len(self.models),) + mask.shape)
        return self._advance(xb, yb, masks)

    # -- compile-ahead (programs.ahead; design.md §12/§17) ---------------
    def warm(self, xshape, k) -> bool:
        """Enqueue an ahead-of-time compile of the packed step for a
        staged block of shape ``xshape`` (already bucketed) and ``k``
        output columns — the re-pack twin of ``_BaseSGD._warm_step``,
        keyed by the cohort size too (every halving round's survivor
        re-pack is a NEW stacked signature).  Pure host work (shape
        structs + a queue put): safe from the prefetch worker."""
        if not _programs.compile_ahead_enabled():
            return False
        m0 = self._m0
        M = len(self.models)
        if self._model_ax > 1 and M % self._model_ax == 0:
            # _stack() will device_put the stacked state with a
            # MODEL_AXIS NamedSharding — a signature these plain shape
            # structs cannot predict (cache._leaf_key keys sharding),
            # so the warm would compile a program no dispatch ever hits
            return False
        b, d = int(xshape[0]), int(xshape[1])
        k = int(k)
        key = (M, b, d, k, m0.loss, m0.penalty, m0.learning_rate,
               m0.fit_intercept)
        if getattr(self, "_warm_memo", None) == key:
            return False
        self._warm_memo = key
        f32 = jnp.float32
        sds = jax.ShapeDtypeStruct
        states = {"coef": sds((M, d, k), f32),
                  "intercept": sds((M, k), f32), "t": sds((M,), f32)}
        hypers = {name: sds((M,), f32) for name in _HYPER_KEYS}
        return _packed_step.warm(
            (states, sds((b, d), f32), sds((b, k), f32),
             sds((M, b), f32), hypers),
            loss=m0.loss, penalty=m0.penalty, schedule=m0.learning_rate,
            fit_intercept=m0.fit_intercept,
        )

    def packed_accuracy(self, X, y):
        """All M models' held-out accuracies as ONE vmapped program and
        one (M,)-scalar fetch — the scoring twin of :meth:`step` (M
        separate ``model.score`` calls cost M dispatches and M fetches).
        The output is forced
        replicated so the fetch stays legal when the stacked model axis
        spans processes.  Classifier cohorts only."""
        m0 = self._m0
        if not isinstance(m0, SGDClassifier):
            raise TypeError("packed_accuracy requires a classifier cohort")
        if type(m0).score is not SGDClassifier.score:
            # a subclass with a custom score() means plain accuracy is
            # NOT its metric — refuse so the caller falls back to
            # per-model score() calls
            raise TypeError(
                "cohort models override score(); packed accuracy would "
                "silently replace their metric"
            )
        # scoring is unweighted: skip building the per-lane weighted masks
        xb, yb, _masks, base_mask = self._prep(X, y, with_weights=False)
        if self._stacked is None:
            self._stacked, self._hypers = self._stack()
        # accuracy is unweighted by definition: score with the plain
        # validity mask, not any lane's class-weighted one
        accs = _packed_accuracy_jit(NamedSharding(get_mesh(), P()))(
            self._stacked, xb, yb, base_mask
        )
        DISPATCH_STATS["score_dispatches"] += 1
        return np.asarray(accs)

    def finalize(self):
        """Write stacked state back into the individual models."""
        if self._stacked is None:
            return self.models
        for i, m in enumerate(self.models):
            m._state = jax.tree.map(lambda x: x[i], self._stacked)
            if self._losses is not None:
                m._loss_ = self._losses[i]
        self._stacked = None
        DISPATCH_STATS["cohorts"] += 1
        return self.models
