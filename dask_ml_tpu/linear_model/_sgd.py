"""Device-native incremental estimators: ``SGDClassifier`` / ``SGDRegressor``.

The reference has no in-repo SGD — ``Incremental`` and the adaptive searches
(``dask_ml/_partial.py :: fit``, ``model_selection/_incremental.py ::
_partial_fit``) wrap *sklearn's* Cython ``SGDClassifier`` and train it on the
host, one data block per call.  On TPU that design leaves the accelerator
idle during the framework's flagship adaptive-search story, so these
estimators are the TPU-native workhorse instead:

* model state (``coef``, ``intercept``, step counter) lives on device as a
  pytree; ``partial_fit`` is ONE fused XLA program over the whole block —
  a gemm for the margins (MXU), a masked-mean gradient, and the parameter
  update, with the state buffers **donated** so the update is in-place in
  HBM;
* the update step is a *pure function* of (state, batch, hyperparams) with
  hyperparameters as traced scalars — so ``jax.vmap`` over a stacked model
  axis trains many configurations in one program (multi-model packing,
  SURVEY.md §2.2 "model-parallel search") with zero recompilation across
  configs;
* blocks are padded to a small set of bucket sizes so streaming variable-
  length chunks does not recompile per shape;
* multiclass is one-vs-all in a single ``[d, n_classes]`` coefficient
  matrix — one gemm instead of n_classes separate binary problems (the
  sklearn semantics, the MXU layout).

Unlike sklearn's per-sample updates, each ``partial_fit`` applies ONE
minibatch gradient step per block (the natural unit on a vector machine),
and ``fit``'s default is one FULL-batch step per epoch — i.e. gradient
descent with the SGD learning-rate schedule.  ``n_iter_`` counts epochs and
``tol`` compares whole-data epoch losses, so both diverge from sklearn's
per-sample accounting by design; pass ``batch_size=B`` to ``fit`` via the
constructor for scanned minibatch epochs (``n_pad/B`` device-side steps per
epoch) that track sklearn's trajectory more closely.  Convergence parity
with sklearn is asserted at the accuracy level in tests, matching the
reference's loose-rtol pattern for iterative solvers.
"""

from __future__ import annotations

import numbers
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..base import ClassifierMixin, RegressorMixin, TPUEstimator
from ..core.sharded import ShardedRows
from ..utils import safe_denominator
from .. import programs as _programs
from .. import sanitize as _san

__all__ = ["SGDClassifier", "SGDRegressor"]

# Streamed blocks are padded up to one of these row counts (then to the next
# multiple of the largest) so a stream of ragged chunk sizes compiles at most
# len(_BUCKETS)+ programs per (d, k) shape.  The policy now lives in
# dask_ml_tpu/programs/bucket.py behind the DASK_ML_TPU_BUCKET knob
# (off / pow2 / explicit ladders); these names stay as the historical
# entry points every caller and test binds.
_BUCKETS = _programs.DEFAULT_BUCKETS
_bucket_rows = _programs.bucket_rows
_bucket_pad = _programs.pad_block

#: Default streaming block size: a bucket entry, so default-chunk streams
#: pad zero extra rows per partial_fit (wrappers.Incremental, _partial.fit)
DEFAULT_STREAM_CHUNK = _BUCKETS[3]

_CLS_LOSSES = ("log_loss", "hinge", "squared_hinge", "modified_huber")
_REG_LOSSES = ("squared_error", "huber")
_PENALTIES = ("l2", "l1", "elasticnet", None)
_SCHEDULES = ("constant", "optimal", "invscaling", "adaptive")

#: the traced-scalar hyperparameter keys every step signature carries
#: (order-free: dicts key the program-cache signature sorted)
_HYPER_KEYS = ("alpha", "eta0", "power_t", "t0", "l1_ratio", "epsilon",
               "eta_scale")


def _margin_losses(loss: str, margins, ysigned):
    """Per-row, per-class loss and dLoss/dMargin for ±1 targets.

    ``margins``/``ysigned``: [B, K].  Returns (loss [B,K], grad [B,K]).
    """
    z = ysigned * margins
    if loss == "log_loss":
        ell = jnp.logaddexp(0.0, -z)
        dz = -jax.nn.sigmoid(-z)
    elif loss == "hinge":
        ell = jnp.maximum(0.0, 1.0 - z)
        dz = jnp.where(z < 1.0, -1.0, 0.0)
    elif loss == "squared_hinge":
        h = jnp.maximum(0.0, 1.0 - z)
        ell = h * h
        dz = -2.0 * h
    elif loss == "modified_huber":
        h = jnp.maximum(0.0, 1.0 - z)
        ell = jnp.where(z >= -1.0, h * h, -4.0 * z)
        dz = jnp.where(z >= -1.0, -2.0 * h, -4.0)
    else:  # pragma: no cover
        raise ValueError(f"unknown classifier loss {loss!r}")
    return ell, dz * ysigned


def ova_targets(ydata, classes):
    """Device labels -> ±1 one-vs-all targets ``[n, K]`` (``K = 1`` for
    two classes), the device twin of ``SGDClassifier._encode_targets``.
    Pure and jit-safe, one elementwise pass: a label outside ``classes``
    is -1 in every column, so callers check :func:`labels_outside`."""
    hit = ydata[:, None] == classes[None, :]
    if classes.shape[0] == 2:
        hit = hit[:, 1:]
    return jnp.where(hit, 1.0, -1.0).astype(jnp.float32)


def labels_outside(ydata, mask, classes):
    """How many real rows (``mask > 0``) hold a label not in ``classes``."""
    known = jnp.any(ydata[:, None] == classes[None, :], axis=1)
    return jnp.sum(jnp.where(known, 0.0, 1.0) * (mask > 0))


def _regression_losses(loss: str, pred, y, epsilon):
    r = pred - y
    if loss == "squared_error":
        return 0.5 * r * r, r
    if loss == "huber":
        a = jnp.abs(r)
        ell = jnp.where(a <= epsilon, 0.5 * r * r, epsilon * (a - 0.5 * epsilon))
        grad = jnp.where(a <= epsilon, r, epsilon * jnp.sign(r))
        return ell, grad
    raise ValueError(f"unknown regressor loss {loss!r}")  # pragma: no cover


def _learning_rate(schedule: str, t, hyper):
    if schedule == "constant":
        return hyper["eta0"]
    if schedule == "adaptive":
        # sklearn semantics: eta stays at eta0 until the epoch loop sees
        # a plateau, then divides by 5 — the division arrives as a traced
        # eta_scale in hyper, so no recompile per adjustment
        return hyper["eta0"] * hyper["eta_scale"]
    if schedule == "optimal":
        # sklearn's heuristic: eta = 1 / (alpha * (t0 + t)) with
        # t0 = 1 / (alpha * eta0-like init); we fold t0 into hyper.
        return 1.0 / (hyper["alpha"] * (hyper["t0"] + t))
    if schedule == "invscaling":
        return hyper["eta0"] / jnp.power(t + 1.0, hyper["power_t"])
    raise ValueError(f"unknown learning_rate {schedule!r}")  # pragma: no cover


def sgd_init(n_features: int, n_outputs: int, dtype=jnp.float32):
    """Fresh device state pytree.  ``n_outputs``: n_classes for OvA
    classification (1 for binary would lose the ±class symmetry — binary
    uses K=1 column with ±1 targets), or 1 for regression."""
    return {
        "coef": jnp.zeros((n_features, n_outputs), dtype=dtype),
        "intercept": jnp.zeros((n_outputs,), dtype=dtype),
        "t": jnp.zeros((), dtype=dtype),
    }


def sgd_step(state, xb, yb, mask, hyper, *, loss, penalty, schedule,
             fit_intercept=True):
    """One minibatch SGD step; pure, jit/vmap-safe.

    Args:
      state: pytree from :func:`sgd_init`.
      xb: [B, d] batch rows (padding rows allowed).
      yb: classifier: [B, K] ±1 one-vs-all targets; regressor: [B, 1].
      mask: [B] 1.0 for real rows.
      hyper: dict of traced scalars — alpha, eta0, power_t, t0, l1_ratio,
        epsilon.
      loss/penalty/schedule: static strings selecting the compiled branches.
    Returns (new_state, mean_loss).
    """
    coef, intercept, t = state["coef"], state["intercept"], state["t"]
    margins = xb @ coef + intercept  # [B, K]
    if loss in _CLS_LOSSES:
        ell, dmarg = _margin_losses(loss, margins, yb)
    else:
        ell, dmarg = _regression_losses(loss, margins, yb, hyper["epsilon"])
    m = mask[:, None].astype(margins.dtype)
    count = safe_denominator(jnp.sum(mask))
    mean_loss = jnp.sum(ell * m) / count
    dmarg = dmarg * m / count
    gcoef = xb.T @ dmarg  # [d, K] — the other MXU gemm
    gint = jnp.sum(dmarg, axis=0)

    alpha = hyper["alpha"]
    if penalty == "l2":
        gcoef = gcoef + alpha * coef
    elif penalty == "l1":
        gcoef = gcoef + alpha * jnp.sign(coef)
    elif penalty == "elasticnet":
        l1r = hyper["l1_ratio"]
        gcoef = gcoef + alpha * (l1r * jnp.sign(coef) + (1.0 - l1r) * coef)

    eta = _learning_rate(schedule, t, hyper)
    new = {
        "coef": coef - eta * gcoef,
        "intercept": intercept - eta * gint if fit_intercept else intercept,
        "t": t + 1.0,
    }
    return new, mean_loss


# One compiled program per (loss, penalty, schedule, fit_intercept, shapes);
# state donated so the update happens in place in HBM.  Routed through the
# central program cache (design.md §12): shape-bucketed streams resolve to
# already-compiled executables and the compile-ahead worker can pre-build
# the next bucket's program while the current block computes.
_jitted_step = _programs.cached_program(
    sgd_step, name="sgd.step",
    static_argnames=("loss", "penalty", "schedule", "fit_intercept"),
    donate_argnames=("state",),
)


def sgd_epoch(state, xs, ys, ms, hyper, *, loss, penalty, schedule,
              fit_intercept=True):
    """One epoch = ``lax.scan`` of :func:`sgd_step` over the minibatch axis.

    ``xs``/``ys``/``ms`` carry shape ``(B, n_mb, ...)``: minibatch ``i`` is
    the stride-``n_mb`` row interleave ``rows[i::n_mb]`` (see
    :func:`_minibatch_views`), indexed out with ``dynamic_index_in_dim`` on
    the UNSHARDED axis 1 so a row-sharded stack needs no data movement and
    each step's gradient still spans every shard (GSPMD inserts the psum
    exactly as in the full-batch step).  Returns (state, mean epoch loss).
    """

    def body(st, i):
        xb = jax.lax.dynamic_index_in_dim(xs, i, axis=1, keepdims=False)
        yb = jax.lax.dynamic_index_in_dim(ys, i, axis=1, keepdims=False)
        mb = jax.lax.dynamic_index_in_dim(ms, i, axis=1, keepdims=False)
        st, step_loss = sgd_step(
            st, xb, yb, mb, hyper, loss=loss, penalty=penalty,
            schedule=schedule, fit_intercept=fit_intercept,
        )
        return st, (step_loss, jnp.sum(mb))

    n_mb = xs.shape[1]
    state, (losses, counts) = jax.lax.scan(body, state, jnp.arange(n_mb))
    # row-count-weighted mean: bucket padding makes minibatches carry
    # unequal numbers of real rows, and an unweighted mean would deflate
    # the epoch loss the tol stopper compares
    total = safe_denominator(jnp.sum(counts))
    return state, jnp.sum(losses * counts) / total


_jitted_epoch = _programs.cached_program(
    sgd_epoch, name="sgd.epoch",
    static_argnames=("loss", "penalty", "schedule", "fit_intercept"),
    donate_argnames=("state",),
)


def _eval_loss_fn(state, xb, yb, mask, hyper, *, loss):
    """Masked mean loss of the CURRENT state over ``mask`` rows — the
    per-epoch validation metric for ``early_stopping``.  This is an EXTRA
    forward pass over all rows each epoch (~+50% epoch FLOPs on the
    full-batch path); accepted so ``sgd_step``'s signature stays shared
    with the packing/ensemble planes, and only paid when early_stopping
    is on."""
    margins = xb @ state["coef"] + state["intercept"]
    if loss in _CLS_LOSSES:
        ell, _ = _margin_losses(loss, margins, yb)
    else:
        ell, _ = _regression_losses(loss, margins, yb, hyper["epsilon"])
    m = mask[:, None].astype(margins.dtype)
    return jnp.sum(ell * m) / safe_denominator(jnp.sum(mask))


# graftlint: disable=donation-miss -- output is one scalar; state/xb/yb stay live in the caller (the epoch step reads state right after)
_eval_loss = _programs.cached_program(
    _eval_loss_fn, name="sgd.eval_loss", static_argnames=("loss",),
)


def _row_shard_count(arr) -> int:
    """Device count along the row axis of ``arr``'s sharding (1 when the
    array is unsharded / on one device)."""
    try:
        spec = arr.sharding.spec  # NamedSharding
        axis = spec[0] if len(spec) else None
        if axis is None:
            return 1
        mesh = arr.sharding.mesh
        if isinstance(axis, (tuple, list)):
            out = 1
            for a in axis:
                out *= mesh.shape[a]
            return out
        return mesh.shape[axis]
    except AttributeError:
        return 1


def _minibatch_views(est, xb, yb, mask, n_real=None):
    """(xs, ys, ms) minibatch stacks for ``fit``, or None for the
    full-batch path.

    ``batch_size`` padded rows per step (global, across shards; the real
    rows per step average ``n_real/n_mb`` since the bucket-pad tail is
    interleaved too).  The padded row count splits as (B, n_mb) — a FREE
    row-major reshape, no copy — so minibatch ``i`` is the
    stride-``n_mb`` interleave ``rows[i::n_mb]``: every shard contributes
    ``local/n_mb`` rows to every minibatch, which keeps the row sharding
    intact (``n_mb`` is clamped to a divisor of the per-shard row count)
    and doubles as a deterministic mixing of the input order.  Pad rows
    carry mask 0 and spread across the minibatches; ``n_mb`` is further
    capped at ``n_real`` so every minibatch holds at least one real row
    (row ``i < n_real`` is always in minibatch ``i``) — no pure
    weight-decay steps on padding-only batches.
    """
    bs = getattr(est, "batch_size", None)
    n_pad = int(xb.shape[0])
    if bs is None:
        return None
    bs = int(bs)
    # full-batch cutoff on the REAL row count: a batch_size >= n_samples
    # means one step per epoch regardless of how far the bucket padding
    # stretched n_pad
    if bs >= (int(n_real) if n_real is not None else n_pad):
        return None
    local = n_pad // max(_row_shard_count(xb), 1)
    n_mb = max(n_pad // bs, 1)
    if n_real is not None:
        n_mb = min(n_mb, int(n_real))
    while n_mb > 1 and local % n_mb:
        n_mb -= 1
    if n_mb <= 1:
        return None
    B = n_pad // n_mb
    xs = xb.reshape(B, n_mb, *xb.shape[1:])
    ys = yb.reshape(B, n_mb, *yb.shape[1:])
    ms = mask.reshape(B, n_mb)
    return xs, ys, ms


class EpochStopper:
    """sklearn's stopping rule, shared by every epoch loop (fit,
    blockwise-ensemble packed fits): stop only after ``n_iter_no_change``
    CONSECUTIVE epochs fail to improve the best loss by ``tol`` — a single
    oscillating epoch (constant LR, large eta0) must not halt training.
    ``update`` returns True when training should stop; with ``tol=None``
    it never syncs the loss (callers should skip the host pull)."""

    def __init__(self, tol, patience: int = 5):
        self.tol = tol
        self.patience = patience
        self.best = np.inf
        self.bad = 0

    @property
    def active(self) -> bool:
        return self.tol is not None

    def update(self, cur: float) -> bool:
        if not self.active:
            return False
        if cur > self.best - self.tol:
            self.bad += 1
            if self.bad >= self.patience:
                return True
        else:
            self.bad = 0
        self.best = min(self.best, cur)
        return False

    def reset_patience(self) -> None:
        """Clear the no-improvement counter but KEEP the best loss —
        sklearn's adaptive-eta rule: after an eta/5 reduction the new
        regime must still beat the pre-reduction best, or the next
        plateau fires against it."""
        self.bad = 0


def _run_epochs(est, xb, yb, mask, n_real=None) -> int:
    """Epoch loop for ``fit``.

    Default (``batch_size=None``): one fused FULL-batch gradient step per
    epoch — i.e. plain gradient descent with the SGD learning-rate
    schedule, NOT sklearn's per-sample updates; ``n_iter_``/``tol`` count
    these whole-data epochs.  With ``batch_size=B`` each epoch is one
    scanned XLA program of ``n_pad/B`` minibatch steps over stride
    interleaves of the (shard-resident) rows — closer to sklearn's
    semantics and usually faster to converge per epoch on large n.  The
    scalar epoch loss syncs to host only when a tol check is active.

    ``early_stopping=True`` carves ``validation_fraction`` of the rows
    out by MASK (a per-row Bernoulli split on device — zero data
    movement, sharded-input safe): training runs on the remaining rows
    and the stopping rule watches the held-out masked mean LOSS (not
    sklearn's score — a documented divergence that serves both tasks
    with one fused forward pass).  ``learning_rate='adaptive'`` follows
    sklearn: on each plateau eta divides by 5 (a traced eta_scale — no
    recompile) until it falls below 1e-6.
    """
    from ..utils import check_max_iter

    check_max_iter(est.max_iter)
    hyper = est._hyper()
    adaptive = est.learning_rate == "adaptive"
    early = bool(getattr(est, "early_stopping", False))
    train_mask, val_mask = mask, None
    if early:
        from ..core.prng import as_key

        frac = float(getattr(est, "validation_fraction", 0.1))
        sel = (
            jax.random.uniform(
                as_key(getattr(est, "random_state", None)), (xb.shape[0],)
            )
            < frac
        ).astype(mask.dtype)
        val_mask = mask * sel
        train_mask = mask * (1.0 - sel)
        if float(jnp.sum(val_mask)) == 0.0:  # degenerate tiny input
            early, train_mask, val_mask = False, mask, None
    stop = EpochStopper(est.tol, getattr(est, "n_iter_no_change", 5))

    from ..resilience.preemption import active_watcher, check_preemption
    from ..resilience.testing import maybe_fault

    ckpt = getattr(est, "fit_checkpoint", None)
    epoch0 = 0
    snap = ckpt.load_if_matches(est) if ckpt is not None else None
    if snap is not None:
        # resume mid-fit: the snapshot replaces the fresh state installed
        # by _ensure_state, and the stopping rule + adaptive-eta scale
        # continue exactly where the killed fit left off (the validation
        # mask above is a pure function of random_state, so the resumed
        # trajectory is identical to the uninterrupted one)
        epoch0, st = snap
        est._state = jax.tree.map(jnp.asarray, st["state"])
        stop.best, stop.bad = float(st["best"]), int(st["bad"])
        hyper = {**hyper, "eta_scale": jnp.float32(st["eta_scale"])}

    def _snapshot_state():
        return {"state": est._state, "best": stop.best, "bad": stop.bad,
                "eta_scale": float(hyper["eta_scale"])}

    views = _minibatch_views(est, xb, yb, train_mask, n_real)
    n_iter = est.max_iter
    for epoch in range(epoch0, est.max_iter):
        maybe_fault("step")
        with _san.region("sgd.fit.epochs"), _san.step_guard():
            if views is not None:
                xs, ys, ms = views
                est._state, loss = _jitted_epoch(
                    est._state, xs, ys, ms, hyper, loss=est.loss,
                    penalty=est.penalty, schedule=est.learning_rate,
                    fit_intercept=est.fit_intercept,
                )
            else:
                loss = est._step_block(xb, yb, train_mask, hyper)
        done = False
        if stop.active:
            monitor = (
                _eval_loss(est._state, xb, yb, val_mask, hyper,
                           loss=est.loss)
                if early else loss
            )
            if stop.update(float(monitor)):
                if not adaptive:
                    n_iter, done = epoch + 1, True
                else:
                    # sklearn's adaptive rule: divide eta by 5 and keep
                    # going; stop once eta underflows 1e-6.  The stopper's
                    # best loss persists across reductions — only the
                    # patience counter resets
                    new_scale = hyper["eta_scale"] / 5.0
                    if float(new_scale) * float(hyper["eta0"]) < 1e-6:
                        n_iter, done = epoch + 1, True
                    else:
                        hyper = {**hyper, "eta_scale": new_scale}
                        stop.reset_patience()
        # boundary instrumentation only when someone is listening: the
        # snapshot dict costs a device->host sync (float(eta_scale)), and
        # the uninstrumented fit keeps its one-dispatch-per-epoch shape
        if ckpt is not None or active_watcher() is not None:
            if ckpt is not None and not done and ckpt.due(epoch + 1):
                ckpt.save(est, _snapshot_state(), epoch + 1)
            check_preemption(ckpt, est, _snapshot_state(), epoch + 1)
        if done:
            break
    if ckpt is not None:
        ckpt.complete()
    return n_iter


class _BaseSGD(TPUEstimator):
    """Shared plumbing: ingest/pad blocks, drive the jitted step."""

    def __init__(self):  # pragma: no cover - abstract
        raise NotImplementedError

    # -- hyperparams as traced scalars (vmap/packing-compatible) ----------
    def _hyper(self):
        eta0 = float(self.eta0)
        alpha = float(self.alpha)
        if self.learning_rate == "optimal" and eta0 <= 0:
            # sklearn's init: typw = sqrt(1/sqrt(alpha)); eta0 such that the
            # first step size is reasonable.  We just need a stable t0.
            eta0 = 1.0
        t0 = 1.0 / (alpha * eta0) if alpha > 0 and eta0 > 0 else 1.0
        values = (
            alpha, float(self.eta0), float(getattr(self, "power_t", 0.25)),
            t0, float(getattr(self, "l1_ratio", 0.15)),
            float(getattr(self, "epsilon", 0.1)),
        )
        # cache the DEVICE scalars keyed on the host values: streamed
        # partial_fit calls _hyper once per block, and re-materializing
        # seven scalar uploads per step is both wasted puts and an
        # implicit-transfer finding under graftsan's steady-phase
        # transfer guard (a set_params between calls changes the key and
        # rebuilds; nothing donates hyper, so sharing across steps is
        # safe)
        cached = getattr(self, "_hyper_cache", None)
        if cached is not None and cached[0] == values:
            return cached[1]
        hyper = {
            "alpha": jnp.float32(values[0]),
            "eta0": jnp.float32(values[1]),
            "power_t": jnp.float32(values[2]),
            "t0": jnp.float32(values[3]),
            "l1_ratio": jnp.float32(values[4]),
            "epsilon": jnp.float32(values[5]),
            "eta_scale": jnp.float32(1.0),
        }
        self._hyper_cache = (values, hyper)
        return hyper

    def _validate(self):
        bs = getattr(self, "batch_size", None)
        if bs is not None and (
            not isinstance(bs, numbers.Integral) or int(bs) < 1
        ):
            raise ValueError(
                f"batch_size must be a positive int or None; got {bs!r}"
            )
        if getattr(self, "early_stopping", False):
            vf = float(getattr(self, "validation_fraction", 0.1))
            if not 0.0 < vf < 1.0:
                raise ValueError(
                    f"validation_fraction must be in (0, 1); got {vf}"
                )
            if self.tol is None:
                raise ValueError(
                    "early_stopping requires a tol (the stopping rule "
                    "compares held-out losses against it)"
                )
        if self.penalty not in _PENALTIES:
            raise ValueError(f"penalty must be one of {_PENALTIES}")
        if self.learning_rate not in _SCHEDULES:
            raise ValueError(f"learning_rate must be one of {_SCHEDULES}")
        if self.learning_rate == "optimal" and not float(self.alpha) > 0:
            raise ValueError(
                "alpha must be > 0 with learning_rate='optimal' "
                "(the schedule is eta = 1/(alpha*(t0+t)))"
            )

    def _prep_block(self, X, targets):
        """Block → (xb, yb, mask) on device.

        ShardedRows X: rows stay sharded with their own mask; the host
        ``targets`` matrix is sharded the same way (zero-padded rows are
        masked out), and XLA inserts the gradient psum from the
        NamedSharding.  Host array X: padded up to a bucket size so ragged
        streamed chunks don't recompile per shape.
        """
        if isinstance(X, ShardedRows):
            # keep floating X as-is: bf16 rows halve HBM traffic and the
            # step's gemms promote to f32 internally; an eager astype here
            # would materialize an f32 copy on device EVERY call
            xd = X.data
            if not jnp.issubdtype(xd.dtype, jnp.floating):
                xd = xd.astype(jnp.float32)
            if isinstance(targets, jnp.ndarray):
                # device-encoded targets (see _encode_targets_device):
                # already row-aligned with X.data, nothing crosses to host
                return xd, targets, X.mask
            # host-encoded targets must match xd's row count EXACTLY —
            # X may be a relaxed _to_blocks slice whose length is NOT a
            # data-axis multiple, so re-sharding targets (which pads to
            # that multiple) would diverge from xd on multi-device meshes
            t = np.asarray(targets, np.float32)
            if t.shape[0] != xd.shape[0]:
                t = np.concatenate([
                    t,
                    np.zeros((xd.shape[0] - t.shape[0], t.shape[1]),
                             np.float32),
                ])
            return xd, jnp.asarray(t), X.mask
        return self._prep_block_host(X, targets)

    def _prep_block_host(self, X, targets):
        """Host-block tail of :meth:`_prep_block`: bucket-pad + H2D puts
        only.  The ONLY prep entry the staged protocol may use — the
        prefetch worker thread runs it, so it must never compile,
        dispatch, or fetch (graftlint's ``stage-purity`` rule holds the
        whole reachable set to that)."""
        X, targets, mask = _bucket_pad(
            np.asarray(X, dtype=np.float32),
            np.asarray(targets, dtype=np.float32),
        )
        return jnp.asarray(X), jnp.asarray(targets), jnp.asarray(mask)

    def _step_block(self, xb, yb, mask, hyper=None):
        self._state, loss = _jitted_step(
            self._state, xb, yb, mask,
            self._hyper() if hyper is None else hyper,
            loss=self.loss, penalty=self.penalty,
            schedule=self.learning_rate, fit_intercept=self.fit_intercept,
        )
        return loss

    # -- staged streaming protocol (pipeline.stream_partial_fit) ----------
    def _pf_consume(self, staged):
        """Device step on a block pre-staged by :meth:`_pf_stage` —
        ``partial_fit`` minus the host encode/pad/upload, which the
        pipeline's worker thread already ran for this block while the
        previous one computed.  Runs on the consumer thread (program
        dispatch stays single-threaded, design.md §7)."""
        from ..resilience.testing import maybe_fault

        maybe_fault("step")
        xb, yb, mask = staged
        self._ensure_state(xb.shape[1])
        # graftsan: the steady-state streamed step is all-device operands
        # (state donated, hyper cached) — the transfer guard holds it to
        # zero implicit host crossings per block
        with _san.region("sgd.partial_fit"), _san.step_guard():
            self._loss_ = self._step_block(xb, yb, mask)
        return self

    def _pf_stage_ok(self, X, y, sample_weight, kwargs) -> bool:
        """Eligibility gate shared by the staged-protocol probes: host
        blocks only — staging a device-resident block (ShardedRows OR a
        bare jax.Array) would fetch/cast/dispatch on the worker thread,
        the thread-dispatch hazard — and no per-block weighting
        (``effective_mask`` is itself a device program; those calls
        keep the serial path)."""
        return not (
            kwargs
            or sample_weight is not None
            or y is None
            or isinstance(X, (ShardedRows, jnp.ndarray))
            or isinstance(y, (ShardedRows, jnp.ndarray))
        )

    # -- compile-ahead (programs.ahead; design.md §12) --------------------
    def _warm_step(self, xshape, k) -> bool:
        """Enqueue an ahead-of-time compile of the streamed step program
        for a staged block of shape ``xshape`` (already bucketed) and
        ``k`` output columns, on the blessed compile-ahead thread.  Pure
        host work (shape structs + a queue put) — safe from the prefetch
        worker, where ``_pf_stage`` calls it per block (a known
        signature short-circuits in microseconds)."""
        if not _programs.compile_ahead_enabled():
            return False
        b, d = int(xshape[0]), int(xshape[1])
        k = int(k)
        # steady streams hit the same (b, d, k, statics) every block:
        # one tuple compare instead of rebuilding the shape structs and
        # re-walking the cache's signature table per staged block
        key = (b, d, k, self.loss, self.penalty, self.learning_rate,
               self.fit_intercept)
        if getattr(self, "_warm_memo", None) == key:
            return False
        self._warm_memo = key
        f32 = jnp.float32
        sds = jax.ShapeDtypeStruct
        state = {"coef": sds((d, k), f32), "intercept": sds((k,), f32),
                 "t": sds((), f32)}
        hyper = {name: sds((), f32) for name in _HYPER_KEYS}
        return _jitted_step.warm(
            (state, sds((b, d), f32), sds((b, k), f32), sds((b,), f32),
             hyper),
            loss=self.loss, penalty=self.penalty,
            schedule=self.learning_rate, fit_intercept=self.fit_intercept,
        )

    def _pf_warm(self, shape, classes=None) -> bool:
        """Shape-based twin of the ``_pf_stage`` warm hook for callers
        that know an upcoming block's (n, d) before staging it (the
        adaptive search warms each unit's program before its burst).
        Returns False when the output width cannot be derived yet."""
        if len(shape) != 2:
            return False
        k = self._warm_k(classes)
        if k is None:
            return False
        return self._warm_step(
            (_bucket_rows(int(shape[0])), int(shape[1])), k)

    # device state lives in a non-underscore-suffixed private attr; tell
    # checkpoint.save_estimator to persist it with the fitted attrs
    _checkpoint_private_attrs = ("_state",)

    # -- sklearn surface ---------------------------------------------------
    @property
    def t_(self):
        return float(self._state["t"]) if hasattr(self, "_state") else 0.0


class SGDClassifier(ClassifierMixin, _BaseSGD):
    """Linear classifier trained by minibatch SGD, state resident on device.

    One-vs-all over ``classes_`` in a single coefficient matrix; binary
    keeps one column (±1 targets).  Reference counterpart: sklearn's
    ``SGDClassifier`` as driven by ``dask_ml/_partial.py :: fit`` — here
    ``partial_fit`` IS the XLA program, so ``Incremental`` and the adaptive
    searches train on the TPU.
    """

    def __init__(self, loss="log_loss", penalty="l2", alpha=1e-4,
                 l1_ratio=0.15, fit_intercept=True, max_iter=1000, tol=1e-3,
                 learning_rate="optimal", eta0=0.01, power_t=0.25,
                 n_iter_no_change=5, random_state=None, warm_start=False,
                 class_weight=None, batch_size=None, early_stopping=False,
                 validation_fraction=0.1, fit_checkpoint=None):
        self.class_weight = class_weight
        self.batch_size = batch_size
        self.early_stopping = early_stopping
        self.validation_fraction = validation_fraction
        self.fit_checkpoint = fit_checkpoint
        self.loss = loss
        self.penalty = penalty
        self.alpha = alpha
        self.l1_ratio = l1_ratio
        self.fit_intercept = fit_intercept
        self.max_iter = max_iter
        self.tol = tol
        self.learning_rate = learning_rate
        self.eta0 = eta0
        self.power_t = power_t
        self.n_iter_no_change = n_iter_no_change
        self.random_state = random_state
        self.warm_start = warm_start

    def _validate(self):
        super()._validate()
        if self.loss not in _CLS_LOSSES:
            raise ValueError(f"loss must be one of {_CLS_LOSSES}")

    def _set_classes(self, classes):
        """Validate + assign the class set (shared by fit / partial_fit /
        the packed Cohort plane, so all three reject the same configs)."""
        classes = np.sort(np.asarray(classes))
        if len(classes) < 2:
            raise ValueError(
                "classifier needs samples of at least 2 classes; got "
                f"{classes.tolist()}"
            )
        self.classes_ = classes

    def _encode_targets(self, y):
        """y labels → ±1 one-vs-all float matrix [n, K] (K=1 binary)."""
        y = np.asarray(y).ravel()
        idx = np.searchsorted(self.classes_, y)
        if (idx >= len(self.classes_)).any() or (
            self.classes_[idx] != y
        ).any():
            raise ValueError("y contains labels not in `classes`")
        if len(self.classes_) == 2:
            return np.where(idx == 1, 1.0, -1.0).astype(np.float32)[:, None]
        out = -np.ones((y.shape[0], len(self.classes_)), dtype=np.float32)
        out[np.arange(y.shape[0]), idx] = 1.0
        return out

    def _encode_targets_device(self, ydata, mask):
        """Device twin of :meth:`_encode_targets`: labels → ±1 one-vs-all
        WITHOUT pulling the label block to host (an O(block) fetch per
        partial_fit call on the streaming path).  Pad rows (mask 0) are
        exempt from the label-validity check; one scalar crosses to host.
        """
        classes = jnp.asarray(self.classes_, ydata.dtype)
        if float(labels_outside(ydata, mask, classes)) > 0:
            # scalar fetch, mirrors the host path's check
            raise ValueError("y contains labels not in `classes`")
        return ova_targets(ydata, classes)

    def _ensure_state(self, n_features: int):
        if not hasattr(self, "_state"):
            k = 1 if len(self.classes_) == 2 else len(self.classes_)
            self._state = sgd_init(n_features, k)
            self.n_features_in_ = int(n_features)

    def _apply_weights(self, yb, mask, sample_weight, n_real,
                      allow_balanced=True):
        """Fold sample/class weights into the block mask (the mask is the
        per-row weight in every masked reduction — sklearn's weighted
        loss for free).  The true class index is recovered from the ±1
        OvA target matrix, so no separate padded label array is needed."""
        cwd = getattr(self, "class_weight", None)
        if sample_weight is None and cwd is None:
            return mask
        from ..utils import effective_mask

        idx = None
        classes = None
        if cwd is not None:
            if cwd == "balanced" and not allow_balanced:
                # sklearn parity: balanced needs the full label
                # distribution, which a stream of blocks cannot give
                raise ValueError(
                    "class_weight 'balanced' is not supported for "
                    "partial_fit"
                )
            if isinstance(cwd, dict):
                from ..utils import _check_class_weight_keys

                _check_class_weight_keys(cwd, self.classes_)
                # keys are original labels; effective_mask works on the
                # recovered class INDICES, so re-key by position
                cwd = {
                    i: float(cwd.get(c, 1.0))
                    for i, c in enumerate(self.classes_.tolist())
                }
            if yb.shape[1] == 1:
                idx = (yb[:, 0] > 0).astype(jnp.float32)
            else:
                idx = jnp.argmax(yb, axis=1).astype(jnp.float32)
            classes = np.arange(len(self.classes_))
        # ONE call: effective_mask builds class indicators from the
        # ORIGINAL mask, so balanced counts stay unweighted and sample
        # weights apply exactly once (chaining two calls would square
        # them — the indicator would be built from the weighted mask)
        return effective_mask(
            mask, idx, sample_weight=sample_weight, class_weight=cwd,
            classes=classes, n_samples=n_real,
        )

    def _pf_stage(self, X, y, classes=None, sample_weight=None, **kwargs):
        """Host parse → ±1 OvA encode → bucket-pad → device upload for
        ONE stream block; returns the staged ``(xb, yb, mask)`` payload
        for :meth:`_BaseSGD._pf_consume`, or None to decline THAT block
        (the pipeline then routes it through serial ``partial_fit``).
        Safe on the prefetch worker thread: pure host work plus H2D
        puts, no device program dispatched.  ``classes_`` thread
        contract: the first writer wins-and-matches — stage k+1 happens
        strictly after stage k on the one worker (queue order), the
        consumer only consumes blocks whose stage already finished, and
        both the staged and the serial-fallback first call derive
        ``classes_`` from the SAME constant ``classes`` kwarg, so every
        writer writes the identical value and later calls only read."""
        if not self._pf_stage_ok(X, y, sample_weight, kwargs):
            return None
        if getattr(self, "class_weight", None) is not None:
            return None  # effective_mask is a device program: serial path
        self._validate()
        if not hasattr(self, "classes_"):
            if classes is None:
                raise ValueError(
                    "classes must be passed on the first partial_fit call"
                )
            self._set_classes(classes)
        # host tail directly: _pf_stage_ok declined device-resident X, so
        # _prep_block's ShardedRows branch (a device cast program) must
        # stay structurally unreachable from the worker thread
        staged = self._prep_block_host(X, self._encode_targets(np.asarray(y)))
        # compile-ahead: if this block's bucket is a new shape, its step
        # program builds on the blessed compile thread while the PREVIOUS
        # block's device step runs — the consumer lands on a warm program
        self._warm_step(staged[0].shape, staged[1].shape[1])
        return staged

    def _warm_k(self, classes=None):
        classes = self.classes_ if classes is None and \
            hasattr(self, "classes_") else classes
        if classes is None:
            return None
        return 1 if len(classes) == 2 else len(classes)

    def partial_fit(self, X, y, classes=None, sample_weight=None, **kwargs):
        self._validate()
        if not hasattr(self, "classes_"):
            if classes is None:
                raise ValueError(
                    "classes must be passed on the first partial_fit call"
                )
            self._set_classes(classes)
        if isinstance(y, ShardedRows):
            if isinstance(X, ShardedRows):
                # all-device block: encode labels in place, zero host I/O
                targets = self._encode_targets_device(y.data, y.mask)
            else:
                from ..core.sharded import unshard

                targets = self._encode_targets(np.asarray(unshard(y)))
        else:
            targets = self._encode_targets(np.asarray(y))
        xb, yb, mask = self._prep_block(X, targets)
        n_real = X.n_samples if isinstance(X, ShardedRows) else int(
            np.asarray(X).shape[0])
        mask = self._apply_weights(
            yb, mask, sample_weight, n_real, allow_balanced=False
        )
        # the device step is the shared _pf_consume tail, so the serial
        # path and the prefetch pipeline can never drift apart
        return self._pf_consume((xb, yb, mask))

    def fit(self, X, y, sample_weight=None, **kwargs):
        self._validate()
        if isinstance(y, ShardedRows):
            from ..core.sharded import unshard

            y = unshard(y)
        y = np.asarray(y)
        if self.warm_start and hasattr(self, "classes_"):
            # Keep the fitted class set (the coef matrix's K columns);
            # refitting on labels outside it cannot be reconciled with the
            # kept state, so reject instead of training wrong columns.
            extra = np.setdiff1d(np.unique(y), self.classes_)
            if extra.size:
                raise ValueError(
                    f"warm_start refit saw labels {extra.tolist()} not in "
                    f"the fitted classes_ {self.classes_.tolist()}"
                )
        else:
            for attr in ("_state", "classes_"):
                if hasattr(self, attr):
                    delattr(self, attr)
            self._set_classes(np.unique(y))
        # Encode/pad/transfer ONCE; every epoch is then just the fused step.
        xb, yb, mask = self._prep_block(X, self._encode_targets(y))
        mask = self._apply_weights(yb, mask, sample_weight, len(y))
        self._ensure_state(xb.shape[1])
        self.n_iter_ = _run_epochs(self, xb, yb, mask, n_real=len(y))
        return self

    # -- inference (device; sliced back at the boundary) ------------------
    def _margins(self, X):
        if isinstance(X, ShardedRows):
            m = X.data.astype(jnp.float32) @ self._state["coef"] + self._state["intercept"]
            return m[: X.n_samples]
        return jnp.asarray(np.asarray(X, np.float32)) @ self._state["coef"] + self._state["intercept"]

    def decision_function(self, X):
        m = self._margins(X)
        return m[:, 0] if m.shape[1] == 1 else m

    def predict(self, X):
        m = self._margins(X)
        if m.shape[1] == 1:
            idx = (m[:, 0] > 0).astype(jnp.int32)
        else:
            idx = jnp.argmax(m, axis=1)
        return self.classes_[np.asarray(idx)]

    def predict_proba(self, X):
        if self.loss not in ("log_loss", "modified_huber"):
            raise AttributeError(
                f"probability estimates are not available for loss={self.loss!r}"
            )
        m = self._margins(X)
        if self.loss == "modified_huber":
            # sklearn's formula: linear clip of the margin to [-1, 1].
            p = (jnp.clip(m, -1.0, 1.0) + 1.0) / 2.0
        else:
            p = jax.nn.sigmoid(m)
        if m.shape[1] == 1:
            return jnp.stack([1.0 - p[:, 0], p[:, 0]], axis=1)
        if self.loss == "modified_huber":
            # all-zero rows (every class clipped to -1) → uniform
            z = jnp.sum(p, axis=1, keepdims=True)
            return jnp.where(z > 0, p / z, 1.0 / p.shape[1])
        return p / jnp.sum(p, axis=1, keepdims=True)

    def predict_log_proba(self, X):
        return jnp.log(self.predict_proba(X))

    @property
    def coef_(self):
        return np.asarray(self._state["coef"]).T  # sklearn: (K, d) / (1, d)

    @property
    def intercept_(self):
        return np.asarray(self._state["intercept"])

    def score(self, X, y, sample_weight=None):
        """Mean accuracy.  All-device inputs score as ONE replicated
        scalar fetch — the only legal form when the arrays span processes
        (a multi-host global array cannot be pulled to host row-wise, and
        even single-host it avoids the O(n) transfer)."""
        from ..core.sharded import ShardedRows as _SR

        from ..utils import classes_f32_exact, masked_device_accuracy

        if sample_weight is not None:
            if isinstance(y, _SR):
                # device labels stay on device — no O(n) pull
                from ..metrics import accuracy_score

                return float(accuracy_score(
                    y, self.predict(X), sample_weight=sample_weight
                ))
            # host labels may be strings/objects: compare on host
            yv = np.asarray(y)
            hits = np.asarray(self.predict(X)) == yv
            return float(np.average(hits, weights=np.asarray(sample_weight)))
        if (isinstance(X, _SR) and isinstance(y, _SR)
                and classes_f32_exact(self.classes_)):
            md = (X.data.astype(jnp.float32) @ self._state["coef"]
                  + self._state["intercept"])
            if md.shape[1] == 1:
                idx = (md[:, 0] > 0).astype(jnp.int32)
            else:
                idx = jnp.argmax(md, axis=1).astype(jnp.int32)
            return masked_device_accuracy(idx, y.data, X.mask, self.classes_)
        from ..metrics import accuracy_score

        return accuracy_score(y, self.predict(X))


class SGDRegressor(RegressorMixin, _BaseSGD):
    """Linear regressor trained by minibatch SGD on device."""

    def __init__(self, loss="squared_error", penalty="l2", alpha=1e-4,
                 l1_ratio=0.15, fit_intercept=True, max_iter=1000, tol=1e-3,
                 learning_rate="invscaling", eta0=0.01, power_t=0.25,
                 epsilon=0.1, n_iter_no_change=5, random_state=None,
                 warm_start=False, batch_size=None, early_stopping=False,
                 validation_fraction=0.1, fit_checkpoint=None):
        self.batch_size = batch_size
        self.early_stopping = early_stopping
        self.validation_fraction = validation_fraction
        self.fit_checkpoint = fit_checkpoint
        self.loss = loss
        self.penalty = penalty
        self.alpha = alpha
        self.l1_ratio = l1_ratio
        self.fit_intercept = fit_intercept
        self.max_iter = max_iter
        self.tol = tol
        self.learning_rate = learning_rate
        self.eta0 = eta0
        self.power_t = power_t
        self.epsilon = epsilon
        self.n_iter_no_change = n_iter_no_change
        self.random_state = random_state
        self.warm_start = warm_start

    def _validate(self):
        super()._validate()
        if self.loss not in _REG_LOSSES:
            raise ValueError(f"loss must be one of {_REG_LOSSES}")

    def _targets(self, y, X=None):
        if isinstance(y, ShardedRows):
            if isinstance(X, ShardedRows):
                # all-device block: targets stay on device, row-aligned
                # with X.data (pad rows masked out in sgd_step)
                return y.data.astype(jnp.float32).reshape(-1, 1)
            # mixed host-X + device-y: the host bucketing path needs
            # exactly n unpadded rows
            from ..core.sharded import unshard

            y = unshard(y)
        return self._targets_host(y)

    @staticmethod
    def _targets_host(y):
        """Host-only tail of :meth:`_targets` — the staged protocol's
        entry (worker thread: no device cast, no unshard fetch;
        ``_pf_stage_ok`` already declined device-resident ``y``)."""
        return np.asarray(y, dtype=np.float32).reshape(-1, 1)

    def _ensure_state(self, n_features: int):
        if not hasattr(self, "_state"):
            self._state = sgd_init(n_features, 1)
            self.n_features_in_ = int(n_features)

    @staticmethod
    def _weighted_mask(X, mask, sample_weight):
        if sample_weight is None:
            return mask
        from ..utils import effective_mask

        n_real = X.n_samples if isinstance(X, ShardedRows) else int(
            np.asarray(X).shape[0])
        return effective_mask(
            mask, sample_weight=sample_weight, n_samples=n_real
        )

    def _pf_stage(self, X, y, sample_weight=None, **kwargs):
        """Regressor twin of :meth:`SGDClassifier._pf_stage`: host
        reshape + bucket-pad + upload, no device program dispatch —
        host-only tails directly (``_pf_stage_ok`` declined device
        input, so ``_targets``/``_prep_block``'s device branches must
        stay structurally unreachable from the worker thread)."""
        if not self._pf_stage_ok(X, y, sample_weight, kwargs):
            return None
        self._validate()
        staged = self._prep_block_host(X, self._targets_host(y))
        self._warm_step(staged[0].shape, 1)
        return staged

    def _warm_k(self, classes=None):
        return 1

    def partial_fit(self, X, y, sample_weight=None, **kwargs):
        self._validate()
        xb, yb, mask = self._prep_block(X, self._targets(y, X))
        mask = self._weighted_mask(X, mask, sample_weight)
        return self._pf_consume((xb, yb, mask))

    def fit(self, X, y, sample_weight=None, **kwargs):
        self._validate()
        if not self.warm_start and hasattr(self, "_state"):
            delattr(self, "_state")
        xb, yb, mask = self._prep_block(X, self._targets(y, X))
        mask = self._weighted_mask(X, mask, sample_weight)
        self._ensure_state(xb.shape[1])
        n_real = X.n_samples if isinstance(X, ShardedRows) else int(
            np.asarray(X).shape[0])
        self.n_iter_ = _run_epochs(self, xb, yb, mask, n_real=n_real)
        return self

    def predict(self, X):
        if isinstance(X, ShardedRows):
            p = X.data.astype(jnp.float32) @ self._state["coef"] + self._state["intercept"]
            return p[: X.n_samples, 0]
        X = jnp.asarray(np.asarray(X, np.float32))
        return (X @ self._state["coef"] + self._state["intercept"])[:, 0]

    @property
    def coef_(self):
        return np.asarray(self._state["coef"])[:, 0]

    @property
    def intercept_(self):
        return np.asarray(self._state["intercept"])

    def score(self, X, y, sample_weight=None):
        from ..metrics import r2_score

        return r2_score(y, self.predict(X), sample_weight=sample_weight)
