"""Drop-in CV search — twin of ``dask_ml/model_selection/_search.py``
(``GridSearchCV``, ``RandomizedSearchCV``; SURVEY.md §2 #21).

The reference's signature trick is a merged task graph keyed by
``tokenize(est, params, data, split)`` so shared pipeline prefixes are fit
once.  Here the equivalent is a host-side **fit cache** keyed the same way:
for ``Pipeline`` candidates, prefix steps whose (step params, data split)
repeat across candidates are fit/transformed once and reused; the per-
candidate math itself runs on device through the estimators.

Candidate×fold fits fan out over a thread pool honoring ``n_jobs`` (the
reference gets this parallelism from the distributed scheduler executing
the merged graph; host sklearn estimators release the GIL in their C
kernels, and device estimators overlap through JAX's async dispatch).  The
prefix cache is compute-once under concurrency: the first thread to need a
prefix fits it, later threads block on that entry rather than refitting.
"""

from __future__ import annotations

import logging
import os
import threading

from .._locks import make_lock
from concurrent.futures import ThreadPoolExecutor, as_completed
from contextlib import nullcontext

import numpy as np

from .. import obs as _obs
from ..base import TPUEstimator, clone
from ..core.sharded import ShardedRows, masked_unique, unshard
from ..metrics.scorer import check_scoring
from ..utils import check_random_state
from ._split import KFold, _fold_slabs, _take_host_bytes
from ._split import _take as _rows  # pandas/array/ShardedRows row subset


def _sweep_kernels_make():
    # lazy: jax import deferred to first use, kernels jitted ONCE at
    # module scope (a per-call closure would retrace every call)
    import jax
    import jax.numpy as jnp
    from functools import partial

    def _eta(data, B, fit_intercept):
        if fit_intercept:
            return data @ B[:, :-1].T + B[:, -1]  # (n, K)
        return data @ B.T

    @partial(jax.jit, static_argnames=("fit_intercept",))
    def acc(data, mask, y01v, B, *, fit_intercept):
        eta = _eta(data, B, fit_intercept)
        pred = (eta > 0).astype(jnp.float32)
        hit = (pred == y01v[:, None]).astype(jnp.float32) * mask[:, None]
        return jnp.sum(hit, axis=0) / jnp.maximum(jnp.sum(mask), 1.0)

    @partial(jax.jit, static_argnames=("fit_intercept",))
    def r2(data, mask, yv, B, *, fit_intercept):
        eta = _eta(data, B, fit_intercept)
        m = mask[:, None]
        ss_res = jnp.sum((eta - yv[:, None]) ** 2 * m, axis=0)
        tot = jnp.maximum(jnp.sum(mask), 1.0)
        mean_y = jnp.sum(yv * mask) / tot
        ss_tot = jnp.sum((yv - mean_y) ** 2 * mask)
        # constant-y fold: sklearn's r2_score returns 1.0 when the fit is
        # also perfect, else 0.0 — the clamped division would instead
        # produce a huge negative score, diverging from the per-candidate
        # scorer path on degenerate folds.  The constancy test is
        # RELATIVE to y's magnitude (Σy²·1e-10 ≈ (eps32·|y|)²·n scale):
        # an absolute epsilon would misread small-magnitude targets
        # (std ~1e-6) as constant and hide their true R².
        y_sq = jnp.sum(yv * yv * mask)
        tol_deg = 1e-10 * y_sq + 1e-30
        r2v = 1.0 - ss_res / jnp.maximum(ss_tot, 1e-30)
        return jnp.where(
            ss_tot > tol_deg,
            r2v,
            jnp.where(ss_res <= tol_deg, 1.0, 0.0),
        )

    return acc, r2


_SWEEP_KERNELS = None


def _sweep_kernels():
    global _SWEEP_KERNELS
    if _SWEEP_KERNELS is None:
        _SWEEP_KERNELS = _sweep_kernels_make()
    return _SWEEP_KERNELS


def _sweep_x(X):
    from ..core.sharded import shard_rows

    return X if isinstance(X, ShardedRows) else shard_rows(
        np.asarray(X, dtype=np.float32))


def _sweep_pad(vec, n_padded):
    import jax.numpy as jnp

    if isinstance(vec, ShardedRows):
        return vec.data
    vec = np.asarray(vec, dtype=np.float32)
    return jnp.asarray(np.pad(vec, (0, n_padded - vec.shape[0])))


def _sweep_accuracy(X, y, betas, classes, fit_intercept):
    """Per-lane accuracy for a (K, p) stack of binary GLM coefficients:
    one gemm scores every grid candidate at once; only the (K,) accuracy
    vector leaves the device.  X is sharded ONCE; the raw labels are
    never float-coerced (string classes flow through binary_indicator)."""
    from ..linear_model.utils import binary_indicator

    acc, _ = _sweep_kernels()
    Xs = _sweep_x(X)
    y01 = _sweep_pad(binary_indicator(y, classes[1]), Xs.data.shape[0])
    return acc(Xs.data, Xs.mask, y01, betas,
               fit_intercept=bool(fit_intercept))


def _sweep_r2(X, y, betas, fit_intercept):
    """Per-lane R² for a (K, p) stack of identity-link GLM coefficients
    (the LinearRegression default score), one gemm for all lanes."""
    _, r2 = _sweep_kernels()
    Xs = _sweep_x(X)
    yv = _sweep_pad(y, Xs.data.shape[0])
    return r2(Xs.data, Xs.mask, yv, betas,
              fit_intercept=bool(fit_intercept))

logger = logging.getLogger(__name__)


def _host(a):
    return unshard(a) if isinstance(a, ShardedRows) else a


def _publish_lanes(span, counts):
    """A finished sweep's lanes onto ``search.sweep``, from each lane's
    counts (``lambda_sweep(return_counts=True)``, on the host): the
    iterations' extremes and ``lane_idle_iters``, the iterations the
    finished lanes sat out while the slowest ran (one vmapped
    ``while_loop`` turns until its last lane is done); where the runner
    counts them (``SOLVE_COUNTS``), ``passes_max``, the most reads of the
    train rows any lane's solve made, and ``trials_max``, the most of them
    any lane's line searches made: the lanes' objective is a black box,
    whose every trial is a pass, and a solve's other passes are one at
    its start (ADMM: a round) and one an iteration.  Both are least
    counts of the program's own: the lanes search in lock-step, so a turn
    costs what its slowest lane takes.  The lanes go into the always-on
    registry."""
    from ..solvers.algorithms import SOLVE_COUNTS

    counts = np.asarray(counts).astype(np.int64)
    col = dict(zip(SOLVE_COUNTS, counts.reshape(len(counts), -1).T))
    iters = col["rounds"]
    span.set(iters_max=int(iters.max()), iters_min=int(iters.min()),
             lane_idle_iters=int((iters.max() - iters).sum()))
    if "passes" in col:
        starts = iters if "rho_moves" in col else 1
        span.set(passes_max=int(col["passes"].max()), trials_max=int(
            (col["passes"] - col["inner_iters"] - starts).max()))
    _obs.registry().counter("search.lanes").inc(int(iters.size))


def _fold_classes_ok(ytr, yte) -> bool:
    """Packed-sweep fold eligibility: train labels exactly binary AND
    test labels a subset of them.  For sharded labels the subset check
    runs ON DEVICE (one scalar fetch) — pulling the whole label vector
    to host per fold would cost an O(n) device->host fetch."""
    import jax.numpy as jnp

    if isinstance(ytr, ShardedRows):
        classes = masked_unique(ytr.data, ytr.mask)
        if classes.shape[0] != 2:
            return False
        if isinstance(yte, ShardedRows):
            ok = jnp.all((yte.mask <= 0) | jnp.isin(yte.data, classes))
            return bool(ok)
        return bool(np.isin(np.asarray(yte), classes).all())
    classes = np.unique(np.asarray(ytr))
    if classes.shape[0] != 2:
        return False
    return bool(np.isin(np.asarray(_host(yte)), classes).all())


class _CacheKey:
    """Token for (estimator-class, params, fold) — the host analogue of the
    reference's ``tokenize`` dedup key (``_search.py :: build_graph``)."""

    @staticmethod
    def make(step, params, fold_idx):
        items = tuple(sorted((k, repr(v)) for k, v in params.items()))
        return (type(step).__name__, items, fold_idx)


class _OnceCache:
    """Compute-once concurrent cache with REFCOUNT eviction.

    The first caller of a token computes; concurrent callers of the SAME
    token wait for that result instead of refitting (the thread-pool
    analogue of graph-node dedup).  ``set_expected_uses`` declares how
    many tasks will consume each token; ``release`` decrements, and a
    token whose uses hit zero drops its value — the analogue of the
    reference scheduler freeing intermediates when refcounts drop
    (``dask_ml/model_selection/_search.py :: build_graph`` inputs are
    freed by the dask scheduler).  Without this, a wide grid over a fat
    pipeline pins every fitted prefix AND its transformed fold data in
    memory for the whole fit.
    """

    def __init__(self):
        self._lock = make_lock("search.folds")
        self._entries: dict = {}
        self._uses: dict = {}

    def set_expected_uses(self, counts: dict):
        with self._lock:
            self._uses = dict(counts)

    def get_or_compute(self, token, fn):
        with self._lock:
            entry = self._entries.get(token)
            if entry is None:
                entry = {"event": threading.Event(), "value": None, "error": None}
                self._entries[token] = entry
                owner = True
            else:
                owner = False
        if owner:
            try:
                entry["value"] = fn()
            except BaseException as e:  # propagate to waiters too
                entry["error"] = e
                raise
            finally:
                entry["event"].set()
            return entry["value"]
        entry["event"].wait()
        if entry["error"] is not None:
            raise entry["error"]
        return entry["value"]

    def release(self, token):
        """One consumer of ``token`` is done; evict at zero uses."""
        with self._lock:
            if token not in self._uses:
                return
            self._uses[token] -= 1
            if self._uses[token] <= 0:
                self._uses.pop(token)
                self._entries.pop(token, None)

    def __len__(self):
        with self._lock:
            return len(self._entries)


class _CachedPredictor:
    """Memoizing proxy for multimetric scoring: K scorers over the same
    (estimator, X) pair compute predict / predict_proba / decision_function
    ONCE instead of once per metric (sklearn's ``_MultimetricScorer``
    rationale — on device estimators each call is a dispatch)."""

    _CACHEABLE = ("predict", "predict_proba", "decision_function",
                  "transform")

    def __init__(self, est):
        self._est = est
        self._memo: dict = {}

    def __getattr__(self, name):
        # No methods are defined on the proxy itself, so hasattr()
        # probes (e.g. the roc_auc scorer's decision_function fallback)
        # see exactly what the wrapped estimator exposes; an estimator
        # without the method raises AttributeError here, truthfully.
        attr = getattr(self._est, name)
        if name in self._CACHEABLE and callable(attr):
            memo = self._memo

            def cached(X, _name=name, _fn=attr):
                key = (_name, id(X))
                if key not in memo:
                    memo[key] = _fn(X)
                return memo[key]

            return cached
        return attr


def _resolve_n_jobs(n_jobs) -> int:
    if n_jobs is None or n_jobs == 1:
        return 1
    if n_jobs < 0:  # sklearn convention: -1 -> all cores
        cpus = os.cpu_count() or 1
        return max(1, cpus + 1 + n_jobs)
    # honor an explicit request as-is: fit threads block in GIL-releasing
    # kernels, so oversubscribing cores is deliberate and cheap
    return int(n_jobs)


def _uses_device_estimator(est) -> bool:
    """Does fitting ``est`` dispatch device programs — a TPUEstimator
    anywhere in it, including pipeline steps?"""
    if isinstance(est, TPUEstimator):
        return True
    steps = getattr(est, "steps", None)
    if steps is not None:
        return any(
            _uses_device_estimator(step) for _, step in steps
            if step is not None and step != "passthrough"
        )
    return False


class _BaseSearchCV(TPUEstimator):
    def __init__(self, estimator, scoring=None, cv=None, refit=True,
                 error_score="raise", return_train_score=False,
                 scheduler=None, n_jobs=-1, cache_cv=True):
        self.estimator = estimator
        self.scoring = scoring
        self.cv = cv
        self.refit = refit
        self.error_score = error_score
        self.return_train_score = return_train_score
        self.scheduler = scheduler
        self.n_jobs = n_jobs
        self.cache_cv = cache_cv

    def _get_param_iterator(self):
        raise NotImplementedError

    def _resolve_cv(self, yh=None):
        cv = self.cv
        if cv is None or isinstance(cv, int):
            # sklearn/reference semantics: an int (or default) stratifies
            # for classifiers — the splits run on host labels anyway
            from sklearn.base import is_classifier
            from sklearn.model_selection import check_cv

            return check_cv(
                cv, yh, classifier=is_classifier(self.estimator)
            )
        return cv

    def _resolve_scorers(self):
        """Normalize ``scoring`` to an ordered {name: scorer} dict.

        Single-metric (None / str / callable) keeps the reference's
        ``"score"`` key; a list/tuple/dict is sklearn's multimetric form
        and requires ``refit`` to name one of the metrics (or be False).
        """
        from ..metrics.scorer import get_scorer

        sc = self.scoring
        if sc is None or isinstance(sc, str) or callable(sc):
            return {"score": check_scoring(self.estimator, sc)}, False
        if isinstance(sc, (list, tuple, set)):
            scorers = {name: get_scorer(name) for name in sc}
        elif isinstance(sc, dict):
            scorers = {
                name: (v if callable(v) else get_scorer(v))
                for name, v in sc.items()
            }
        else:
            raise ValueError(f"Invalid scoring: {sc!r}")
        if (self.refit is not False and not callable(self.refit)
                and self.refit not in scorers):
            raise ValueError(
                "For multimetric scoring, refit must be False, a callable "
                "selecting best_index_ from cv_results_, or the name of "
                f"the metric used to pick the best candidate; got "
                f"{self.refit!r} with metrics {sorted(scorers)}"
            )
        return scorers, True

    def _device_capable(self):
        """True when every fit/score consumer of the data is a device
        estimator, so sharded input can stay device-resident end to end."""
        from sklearn.pipeline import Pipeline

        est = self.estimator
        if isinstance(est, Pipeline):
            return all(isinstance(s, TPUEstimator) for _, s in est.steps)
        return isinstance(est, TPUEstimator)

    def _prefix_tokens_for(self, est, fold_idx):
        """Cumulative prefix tokens this pipeline candidate touches in one
        (candidate, fold) task — shared by the fit path and the refcount
        precompute so the two can never disagree."""
        from sklearn.pipeline import Pipeline

        if not (self.cache_cv and isinstance(est, Pipeline)):
            return []
        toks, acc = [], []
        for _name, step in est.steps[:-1]:
            acc.append(_CacheKey.make(step, step.get_params(), fold_idx))
            toks.append(tuple(acc))
        return toks

    def fit(self, X, y=None, **fit_params):
        # the search's spans (live under ``obs.enable()`` or a profiler
        # session): ``search.fit`` is the root; ``search.split``, a
        # ``search.fold`` for every fold made, the packed path's
        # ``search.sweep`` and ``search.score`` a fold, and
        # ``search.refit``, under which the winner's own tree hangs
        with _obs.span("search.fit", search=type(self).__name__,
                       estimator=type(self.estimator).__name__) as root:
            _obs.registry().counter("search.fits").inc()
            return self._fit(X, y, fit_params, root)

    def _slab_splitter(self, y):
        """This package's unshuffled ``KFold`` where the search's folds are
        its contiguous slabs and every label lives on the device (so that
        no stratification was asked of host labels): what ``cv=None`` and
        ``cv=<int>`` mean there, or such a ``KFold`` handed in.  None for
        every other splitter, whose folds are index arrays."""
        if not (y is None or isinstance(y, ShardedRows)):
            return None
        if self.cv is None or isinstance(self.cv, int):
            return KFold(n_splits=5 if self.cv is None else self.cv)
        if type(self.cv) is KFold and not self.cv.shuffle:
            return self.cv
        return None

    def _fit(self, X, y, fit_params, root):
        from ..core.sharded import as_sharded
        from ..utils import check_consistent_length

        # raw device arrays ride the ShardedRows device path (wrapping
        # is a device-side reshard; np.asarray on them would be an O(n)
        # device->host fetch).  Length consistency must be checked HERE:
        # past the wrap, the device split slices y by X-derived indices
        # and jnp.take would silently clamp a shorter y instead of
        # raising the sklearn error
        if y is not None:
            check_consistent_length(X, y)
        X, y = as_sharded(X), as_sharded(y)
        device_path = isinstance(X, ShardedRows) and self._device_capable()
        slabs = False
        with _obs.span("search.split") as split_span:
            if device_path:
                # sharded input stays ON DEVICE through the whole search:
                # folds are cut on the device (slabs by _split._fold_slabs,
                # index arrays by the gather in _split._take), models
                # fit/score sharded folds, and only scalar scores come
                # back to host.  The reference keeps blocks
                # worker-resident the same way (``_search.py ::
                # build_graph``).
                Xh, yh = X, y
                n = X.n_samples
                cv = self._slab_splitter(y)
                if cv is not None:
                    # index-free KFold, like the reference's array path (a
                    # lazy dask array cannot be stratified either): a fold
                    # is its bounds, and nothing of the table's length is
                    # made on the host.  This DIFFERS from the host path's
                    # stratified default for classifiers: say so, and how
                    # to get stratification.
                    slabs = True
                    edges = cv.bounds(n)
                    splits = list(zip(edges[:-1].tolist(), edges[1:].tolist()))
                    from sklearn.base import is_classifier

                    if (y is not None and cv is not self.cv
                            and is_classifier(self.estimator)):
                        import warnings

                        warnings.warn(
                            "sharded input uses unshuffled KFold (no "
                            "stratification) — class-sorted labels can "
                            "yield single-class folds; pass an explicit "
                            "splitter (e.g. StratifiedKFold) to stratify at "
                            "the cost of one 1-D label fetch",
                            UserWarning, stacklevel=3,
                        )
                else:
                    if y is not None and not isinstance(y, ShardedRows):
                        # y already lives on host: stratified defaults
                        # cost nothing — keep round-2 semantics for
                        # classifiers
                        y_split = np.asarray(y)
                    elif y is not None:
                        # a user-chosen splitter may stratify on labels —
                        # that takes a host copy of y (1-D, the only O(n)
                        # fetch here)
                        y_split = np.asarray(_host(y))
                    else:
                        y_split = None
                    cv = self._resolve_cv(y_split)
                    splits = list(cv.split(np.empty((n, 0)), y_split))
            else:
                Xh, yh = _host(X), _host(y) if y is not None else None
                cv = self._resolve_cv(yh)
                splits = list(cv.split(Xh, yh))
            split_span.set(splitter=type(cv).__name__, slabs=int(slabs),
                           folds=len(splits))
        candidates = list(self._get_param_iterator())
        if not candidates:
            raise ValueError("No candidate parameters")
        scorers, multimetric = self._resolve_scorers()
        root.set(candidates=len(candidates), folds=len(splits))

        # prefix-transform cache: (pipeline prefix token) -> fitted step +
        # transformed data, compute-once under the thread pool, entries
        # refcount-evicted as their last consumer finishes
        prefix_cache = _OnceCache()
        from sklearn.pipeline import Pipeline as _Pipeline

        if self.cache_cv and isinstance(self.estimator, _Pipeline):
            # non-Pipeline estimators have no prefixes: skip the
            # O(n_candidates) clone/set_params precompute entirely
            use_counts: dict = {}
            for params in candidates:
                est0 = clone(self.estimator).set_params(**params)
                for fi in range(len(splits)):
                    for tok in self._prefix_tokens_for(est0, fi):
                        use_counts[tok] = use_counts.get(tok, 0) + 1
            prefix_cache.set_expected_uses(use_counts)

        n_cand = len(candidates)
        test_scores = {m: np.zeros((n_cand, len(splits))) for m in scorers}
        train_scores = (
            {m: np.zeros((n_cand, len(splits))) for m in scorers}
            if self.return_train_score else None
        )
        fit_failed = np.zeros(n_cand, dtype=bool)

        # Fold slices computed ONCE per fold and shared across candidates
        # — the analogue of dask's graph deduplicating the X[train_idx]
        # nodes: re-gathering per (candidate, fold) cost ~9 eager device
        # gathers per fit and dominated warm-search wall time (r4
        # profile: 1.0 s of 1.5 s on a 12x3 grid).  REFCOUNTED, not a
        # plain list: pinning every fold's train+test slices for the
        # whole search would hold ~(cv+1)x the dataset resident (device
        # OOM at scale); with fold-major task order below, at most
        # ~n_workers folds are live at once — the old transient peak,
        # dedup kept.
        fold_lock = make_lock("search.folds")
        fold_cache: dict = {}
        fold_refs = {fi: n_cand for fi in range(len(splits))}
        # share fold slices ONLY for device inputs: jax arrays are
        # immutable, so candidates cannot corrupt each other.  Host numpy
        # slices are mutable (a Pipeline step with copy=False would
        # scale the shared Xtr in place and poison later candidates), so
        # hosts keep the old fresh-copy-per-task behavior — numpy fancy
        # indexing is cheap; the expensive case (eager device gathers)
        # is exactly the ShardedRows one.
        _fold_cacheable = isinstance(Xh, ShardedRows)

        root_id = root.span_id

        def _fold_slices(fi, span=None):
            """Fold ``fi``'s (Xtr, ytr, Xte, yte), under the caller's
            ``search.fold`` span or one of its own (a worker thread's has
            no open parent: the root is named); what the fold took goes
            on the span."""
            with (_obs.span("search.fold", parent=root_id, fold=fi)
                  if span is None else nullcontext(span)) as span:
                _obs.registry().counter("search.folds").inc()
                if slabs:
                    lo, hi = splits[fi]
                    span.set(rows_train=n - (hi - lo), rows_test=hi - lo,
                             host_index_bytes=0)
                    return _fold_slabs(Xh, yh, lo, hi)
                tr, te = splits[fi]
                span.set(rows_train=len(tr), rows_test=len(te),
                         host_index_bytes=sum(
                             np.asarray(i).nbytes for i in (tr, te)) + sum(
                             _take_host_bytes(a, i)
                             for i in (tr, te) for a in (Xh, yh)))
                return (
                    _rows(Xh, tr),
                    _rows(yh, tr) if yh is not None else None,
                    _rows(Xh, te),
                    _rows(yh, te) if yh is not None else None,
                )

        def fold_get(fi, span=None):
            if not _fold_cacheable:
                return _fold_slices(fi, span)
            with fold_lock:
                if fi not in fold_cache:
                    fold_cache[fi] = _fold_slices(fi, span)
                return fold_cache[fi]

        def fold_release(fi):
            with fold_lock:
                fold_refs[fi] -= 1
                if fold_refs[fi] <= 0:
                    fold_cache.pop(fi, None)

        packed_done = self._maybe_packed_glm_sweep(
            candidates, len(splits), fold_get, fold_release, scorers,
            fit_params, test_scores, train_scores,
        )
        root.set(packed=int(packed_done))
        if not packed_done:
            # a mid-way packed fallback consumed some folds' refcounts;
            # restore the full budget for the per-task path
            with fold_lock:
                fold_cache.clear()
                for fi in fold_refs:
                    fold_refs[fi] = n_cand

        def run_task(ci, fi):
            params = candidates[ci]
            Xtr, ytr, Xte, yte = fold_get(fi)
            est = clone(self.estimator).set_params(**params)
            tokens = self._prefix_tokens_for(est, fi)
            try:
                est = self._fit_candidate(
                    est, Xtr, ytr, prefix_cache, tokens, fit_params
                )
                if len(scorers) > 1:
                    # one predict per (X, method) across all metrics — the
                    # _MultimetricScorer caching idea, as a proxy
                    est = _CachedPredictor(est)
                for m, scorer in scorers.items():
                    test_scores[m][ci, fi] = scorer(est, Xte, yte)
                    if self.return_train_score:
                        train_scores[m][ci, fi] = scorer(est, Xtr, ytr)
            except Exception:
                if self.error_score == "raise":
                    raise
                for m in scorers:
                    test_scores[m][ci, fi] = float(self.error_score)
                    if self.return_train_score:
                        train_scores[m][ci, fi] = float(self.error_score)
                fit_failed[ci] = True
            finally:
                # this task's reservation on its prefixes is spent either
                # way; the last consumer's release evicts the entry
                for tok in tokens:
                    prefix_cache.release(tok)
                fold_release(fi)

        # FOLD-MAJOR order: all candidates of fold 0, then fold 1, ... so
        # the refcounted fold cache retires each fold's slices before the
        # next fold's are gathered (candidate-major order would keep
        # every fold live for the whole search)
        tasks = (
            [] if packed_done
            else [(ci, fi) for fi in range(len(splits))
                  for ci in range(n_cand)]
        )
        n_workers = min(_resolve_n_jobs(self.n_jobs), max(len(tasks), 1))
        if n_workers > 1 and (
            _uses_device_estimator(self.estimator)
            # a grid may SUBSTITUTE a device estimator via set_params
            # (e.g. {'clf': [LogisticRegression()]}): scan candidate
            # param values too, or the guard below is bypassed
            or any(
                _uses_device_estimator(v)
                for params in candidates for v in params.values()
            )
        ):
            # collective-safety: a library estimator's fit dispatches
            # multi-device programs (sharded solves, psum reductions) on
            # the one shared mesh, and two threads submitting such
            # programs concurrently can interleave enqueue order across
            # devices and deadlock the runtime — the intra-process
            # analogue of the multi-controller boundary contract
            # (resilience.preemption).  A device fit already occupies
            # every device, so threads buy no speedup here: serialize.
            n_workers = 1
        if n_workers <= 1:
            for ci, fi in tasks:
                run_task(ci, fi)
        else:
            # mesh scoping is thread-local: re-establish the caller's mesh
            # inside each worker (device estimators would otherwise fall
            # back to the all-devices default mesh)
            from ..core.mesh import get_mesh, use_mesh

            mesh = get_mesh()

            def run_on_mesh(ci, fi):
                with use_mesh(mesh):
                    run_task(ci, fi)

            with ThreadPoolExecutor(max_workers=n_workers) as pool:
                futures = [pool.submit(run_on_mesh, ci, fi) for ci, fi in tasks]
                try:
                    for f in as_completed(futures):
                        f.result()  # re-raise the FIRST failure...
                except BaseException:
                    # ...and don't run the rest of a doomed grid
                    pool.shutdown(wait=False, cancel_futures=True)
                    raise

        self._build_results(
            candidates, splits, test_scores, train_scores,
            primary=(
                False if callable(self.refit)
                else (self.refit if multimetric else "score")
            ),
        )
        self.multimetric_ = multimetric
        if callable(self.refit):
            # sklearn semantics: a callable refit selects best_index_ from
            # cv_results_ (best_score_ is undefined in this mode)
            picked = self.refit(self.cv_results_)
            if not isinstance(picked, (int, np.integer)):
                raise TypeError(
                    "refit callable must return an integer index, got "
                    f"{type(picked).__name__} ({picked!r})"
                )
            self.best_index_ = int(picked)
            if not 0 <= self.best_index_ < len(candidates):
                raise IndexError(
                    f"refit callable returned index {self.best_index_} "
                    f"outside [0, {len(candidates)})"
                )
            self.best_params_ = candidates[self.best_index_]
        if self.refit:
            with _obs.span("search.refit", candidate=self.best_index_):
                best = clone(self.estimator).set_params(**self.best_params_)
                if yh is not None:
                    best.fit(Xh, yh, **fit_params)
                else:
                    best.fit(Xh, **fit_params)
            self.best_estimator_ = best
        return self

    def _maybe_packed_glm_sweep(self, candidates, n_folds, fold_get,
                                fold_release, scorers, fit_params,
                                test_scores, train_scores):
        """Packed fast path for the commonest grid: a binary device-native
        LogisticRegression searched over ONLY ``C``.  All candidates of a
        fold run as ONE vmapped solve (``solvers.lambda_sweep``) and are
        scored with one gemm — K fits collapse from K dispatches to 1.
        The reference builds K independent task graphs here; this is the
        TPU-native counterpart of its graph-level dedup.

        Gated on ``pack_strategy() == "packed"`` (vmap packing measured
        SLOWER on CPU, r3 ``packed_speedup 0.684``); ineligible grids
        fall through to the per-task path.  Returns True when it filled
        the score arrays, and then leaves ``coefs_paths_`` (folds,
        candidates, p), each fold's lanes as they came to the host, the
        intercept last (what ``LogisticRegressionCV`` keeps under that
        name); a search that took the per-task path has no such
        attribute.
        """
        from ..linear_model import LinearRegression as _OLS
        from ..linear_model import LogisticRegression as _LR
        from ..solvers import grid_pack_strategy

        self.__dict__.pop("coefs_paths_", None)  # an earlier fit's
        est = self.estimator
        is_clf = type(est) is _LR
        is_reg = type(est) is _OLS  # identity link: R² scores by gemm
        if not (is_clf or is_reg):
            return False
        if grid_pack_strategy() != "packed":
            return False
        if fit_params or self.scoring is not None:
            return False
        if is_clf and (est.class_weight is not None
                       or est.multi_class == "multinomial"):
            return False
        if not candidates or any(set(p) != {"C"} for p in candidates):
            return False
        if set(scorers) != {"score"}:
            return False
        Cs = [p["C"] for p in candidates]
        paths = []
        filled_test = np.empty((len(Cs), n_folds))
        filled_train = (
            np.empty_like(filled_test) if self.return_train_score else None
        )
        try:
            for fi in range(n_folds):
                try:
                    # eligibility BEFORE the K-lane fit (a doomed fold
                    # must not execute the whole vmapped solve only to
                    # discard it): the train fold must be exactly binary,
                    # and every test label must be among the train
                    # classes — the packed scorer encodes labels against
                    # the TRAIN fold's 2 classes, so an unseen test label
                    # would encode to 0 and count as a hit whenever
                    # eta<=0 (the per-candidate path counts it as a
                    # miss).  The check's wait is the fold's: it is where
                    # the host first needs what the fold's program made.
                    with _obs.span("search.fold", fold=fi) as span:
                        Xtr, ytr, Xte, yte = fold_get(fi, span)
                        if ytr is None or yte is None or (
                                is_clf and not _fold_classes_ok(ytr, yte)):
                            return False
                    sweep_est = clone(est)
                    with _obs.span("search.sweep", fold=fi,
                                   lanes=len(Cs)) as span:
                        if is_clf:
                            betas, classes, counts = (
                                sweep_est._sweep_fit_binary(Xtr, ytr, Cs))

                            def sc(Xf, yf):
                                return _sweep_accuracy(
                                    Xf, yf, betas, classes,
                                    est.fit_intercept)
                        else:
                            betas, counts = sweep_est._sweep_fit_values(
                                Xtr, ytr, Cs)

                            def sc(Xf, yf):
                                return _sweep_r2(
                                    Xf, yf, betas, est.fit_intercept)
                        _publish_lanes(span, counts)
                        paths.append(betas)
                    with _obs.span("search.score", fold=fi):
                        filled_test[:, fi] = np.asarray(sc(Xte, yte))
                        if filled_train is not None:
                            filled_train[:, fi] = np.asarray(sc(Xtr, ytr))
                finally:
                    # one fold live at a time: this path consumes ALL
                    # n_cand reservations of the fold it just finished,
                    # and lets go of it before the next one is cut
                    Xtr = ytr = Xte = yte = None
                    for _ in range(len(Cs)):
                        fold_release(fi)
        except Exception:
            # ANY failure here (non-binary labels discovered late, a
            # solver rejecting the config, ...) falls back to the
            # per-candidate path, which owns the real error_score
            # semantics and will re-raise genuine errors properly
            logger.info(
                "packed GLM sweep ineligible/failed; falling back to "
                "per-candidate fits", exc_info=True,
            )
            return False
        self.coefs_paths_ = np.stack(paths)
        test_scores["score"][:, :] = filled_test
        if train_scores is not None and filled_train is not None:
            train_scores["score"][:, :] = filled_train
        return True

    def _fit_candidate(self, est, Xtr, ytr, prefix_cache, tokens, fit_params):
        from sklearn.pipeline import Pipeline

        if not (self.cache_cv and isinstance(est, Pipeline)):
            if ytr is not None:
                est.fit(Xtr, ytr, **fit_params)
            else:
                est.fit(Xtr, **fit_params)
            return est

        # pipeline-prefix caching: walk steps; reuse cached fitted
        # transformers + transformed data while the prefix key matches
        # (``tokens[i]`` is the cumulative token for steps[0..i], built by
        # _prefix_tokens_for so the refcount precompute stays in sync).
        # Cached host arrays are handed to consumers as COPIES: the cache
        # shares ONE transformed array object across candidates, so a
        # step that mutates its input in place (the sklearn copy=False
        # hazard) would silently poison every later candidate's view —
        # a real order-dependent score corruption found by
        # tests/test_search_parallel.py :: TestFoldCacheMutationSafety.
        # Device arrays are immutable; only numpy needs the defense.
        def _host_copy(a):
            return a.copy() if isinstance(a, np.ndarray) else a

        steps = est.steps
        data = Xtr
        fitted_steps = []
        cached_data = False  # does `data` alias a cache-shared object?
        for (name, step), token in zip(steps[:-1], tokens):

            def fit_prefix(step=step, data_in=data, shared=cached_data):
                fitted = clone(step)
                x_in = _host_copy(data_in) if shared else data_in
                return fitted, fitted.fit_transform(x_in, ytr)

            fitted_step, data = prefix_cache.get_or_compute(token, fit_prefix)
            fitted_steps.append((name, fitted_step))
            cached_data = True
        final_name, final = steps[-1]
        final = clone(final)
        fit_x = _host_copy(data) if cached_data else data
        if ytr is not None:
            final.fit(fit_x, ytr, **fit_params)
        else:
            final.fit(fit_x, **fit_params)
        fitted_steps.append((final_name, final))
        est.steps = fitted_steps
        return est

    def _build_results(self, candidates, splits, test_scores, train_scores,
                       *, primary):
        """``test_scores``/``train_scores``: {metric: (n_cand, n_folds)}.

        ``primary`` selects best_*; the single-metric key "score" keeps
        the reference's ``*_test_score`` result names; multimetric adds
        one column family per metric (sklearn's convention).  ``primary``
        may be False (multimetric + refit=False): per-metric columns are
        built but no best_* attributes exist, per sklearn.
        """
        cv_results = {"params": candidates}
        for metric, scores in test_scores.items():
            mean_test = scores.mean(axis=1)
            std_test = scores.std(axis=1)
            # error_score=nan candidates rank (and select) WORST: a raw
            # argsort/argmax treats NaN as the maximum
            mean_ranked = np.where(np.isnan(mean_test), -np.inf, mean_test)
            ranks = np.argsort(np.argsort(-mean_ranked)) + 1
            cv_results[f"mean_test_{metric}"] = mean_test.tolist()
            cv_results[f"std_test_{metric}"] = std_test.tolist()
            cv_results[f"rank_test_{metric}"] = ranks.tolist()
            for fi in range(len(splits)):
                cv_results[f"split{fi}_test_{metric}"] = scores[:, fi].tolist()
            if train_scores is not None:
                tr = train_scores[metric]
                cv_results[f"mean_train_{metric}"] = tr.mean(axis=1).tolist()
                for fi in range(len(splits)):
                    cv_results[f"split{fi}_train_{metric}"] = tr[:, fi].tolist()
        keys = {k for p in candidates for k in p}
        for k in sorted(keys):
            cv_results[f"param_{k}"] = [p.get(k) for p in candidates]
        self.cv_results_ = cv_results
        self.n_splits_ = len(splits)
        if primary is False:
            return
        mean_test = np.asarray(cv_results[f"mean_test_{primary}"])
        if np.all(np.isnan(mean_test)):
            raise ValueError(
                "every candidate's fit failed (all mean test scores are "
                "NaN); re-run with error_score='raise' to see the cause"
            )
        self.best_index_ = int(np.nanargmax(mean_test))
        self.best_score_ = float(mean_test[self.best_index_])
        self.best_params_ = candidates[self.best_index_]

    # -- post-fit API --------------------------------------------------
    def _check_refit(self, method):
        if not self.refit:
            raise AttributeError(f"{method} requires refit=True")

    def _inference_input(self, X):
        """Sharded input stays sharded when the winner runs on device;
        only a host (sklearn) winner forces the O(n) unshard."""
        if isinstance(X, ShardedRows) and self._device_capable():
            return X
        return _host(X)

    def predict(self, X):
        self._check_refit("predict")
        return self.best_estimator_.predict(self._inference_input(X))

    def predict_proba(self, X):
        self._check_refit("predict_proba")
        return self.best_estimator_.predict_proba(self._inference_input(X))

    def transform(self, X):
        self._check_refit("transform")
        return self.best_estimator_.transform(self._inference_input(X))

    def score(self, X, y=None):
        self._check_refit("score")
        scorers, multimetric = self._resolve_scorers()
        if multimetric and callable(self.refit):
            raise ValueError(
                "score() is ambiguous with multimetric scoring and a "
                "callable refit (no single refit metric); score the "
                "best_estimator_ directly or pass refit=<metric name>"
            )
        scorer = scorers[self.refit] if multimetric else scorers["score"]
        Xi = self._inference_input(X)
        yi = y if isinstance(Xi, ShardedRows) else _host(y)
        return scorer(self.best_estimator_, Xi, yi)


class GridSearchCV(_BaseSearchCV):
    def __init__(self, estimator, param_grid, scoring=None, cv=None,
                 refit=True, error_score="raise", return_train_score=False,
                 scheduler=None, n_jobs=-1, cache_cv=True):
        self.param_grid = param_grid
        super().__init__(
            estimator, scoring=scoring, cv=cv, refit=refit,
            error_score=error_score, return_train_score=return_train_score,
            scheduler=scheduler, n_jobs=n_jobs, cache_cv=cache_cv,
        )

    def _get_param_iterator(self):
        from sklearn.model_selection import ParameterGrid

        return ParameterGrid(self.param_grid)


class RandomizedSearchCV(_BaseSearchCV):
    def __init__(self, estimator, param_distributions, n_iter=10,
                 random_state=None, scoring=None, cv=None, refit=True,
                 error_score="raise", return_train_score=False,
                 scheduler=None, n_jobs=-1, cache_cv=True):
        self.param_distributions = param_distributions
        self.n_iter = n_iter
        self.random_state = random_state
        super().__init__(
            estimator, scoring=scoring, cv=cv, refit=refit,
            error_score=error_score, return_train_score=return_train_score,
            scheduler=scheduler, n_jobs=n_jobs, cache_cv=cache_cv,
        )

    def _get_param_iterator(self):
        from sklearn.model_selection import ParameterSampler

        return ParameterSampler(
            self.param_distributions, self.n_iter,
            random_state=check_random_state(self.random_state),
        )
