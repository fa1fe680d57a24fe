"""GLM estimators — twin of ``dask_ml/linear_model/glm.py``
(``LogisticRegression``, ``LinearRegression``, ``PoissonRegression``, base
``_GLM``): an sklearn facade that maps ``C``/``penalty``/``solver`` onto the
solver library (``lamduh = 1/C``, reference convention), asks the solver for
an intercept beside the weights (``intercept=fit_intercept``: the table is
handed over as the caller gave it, no column of ones is appended), and
exposes ``coef_``/``intercept_``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import ClassifierMixin, RegressorMixin, TPUEstimator
from ..core.mesh import data_axes_size
from ..core.sharded import ShardedRows, masked_unique
from ..preprocessing.data import _ingest_float
from .. import obs as _obs
from .. import sanitize as _san
from ..solvers import (
    Logistic,
    Normal,
    Poisson,
    admm,
    get_regularizer,
    gradient_descent,
    lbfgs,
    newton,
    proximal_grad,
)
from ..solvers.algorithms import COUNTED_SOLVERS, EXITS, unpack_counts
from .utils import binary_indicator

_SOLVERS = {
    "admm": admm,
    "lbfgs": lbfgs,
    "newton": newton,
    "gradient_descent": gradient_descent,
    "proximal_grad": proximal_grad,
}


def _fetch_counts(runs):
    """The fit's one device-to-host transfer of its iteration counts:
    ``(n_iter_, counts, ratios)``.  ``runs`` holds one entry per solver
    run, a scalar iteration count or, from a counted solver, the
    ``SOLVE_COUNTS`` vector; ``counts`` and ``ratios`` are what goes on
    the ``glm.solve`` span: all of a single counted run's vector
    (``solvers.algorithms.unpack_counts``), else the rounds."""
    got = np.asarray(runs, dtype=np.int32)
    if got.ndim == 2:
        return (got[:, 0], *unpack_counts(got[0]))
    return got, {"rounds": int(got.max())}, {}


def _publish_counts(span, counts, ratios, est):
    """A finished solve's counts: onto its span, and into the always-on
    registry (``solve.count`` solves, and their summed counts).  Where
    and why it stopped (``_stop_attrs``) goes on the span alone."""
    span.set(**counts, **_stop_attrs(est, counts, ratios))
    reg = _obs.registry()
    reg.counter("solve.count").inc()
    for name, value in counts.items():
        reg.counter(f"solve.{name}").inc(value)


def _appended_bytes(X, Xi):
    """What ``glm.prepare`` reports as ``appended_bytes``: the size of a
    table made for the solver in the place of the ingested ``X`` (a
    column appended, a copy); 0 while the solver reads ``X`` itself
    (weights change the mask alone)."""
    return 0 if Xi.data is X.data else int(Xi.data.nbytes)


class _GLM(TPUEstimator):
    family: type = None

    def __init__(self, penalty="l2", dual=False, tol=1e-4, C=1.0,
                 fit_intercept=True, intercept_scaling=1.0, class_weight=None,
                 random_state=None, solver="admm", max_iter=100,
                 multi_class="ovr", verbose=0, warm_start=False, n_jobs=1,
                 solver_kwargs=None, fit_checkpoint=None):
        self.penalty = penalty
        self.dual = dual
        self.tol = tol
        self.C = C
        self.fit_intercept = fit_intercept
        self.intercept_scaling = intercept_scaling
        self.class_weight = class_weight
        self.random_state = random_state
        self.solver = solver
        self.max_iter = max_iter
        self.multi_class = multi_class
        self.verbose = verbose
        self.warm_start = warm_start
        self.n_jobs = n_jobs
        self.solver_kwargs = solver_kwargs
        self.fit_checkpoint = fit_checkpoint

    def _solver_call_kwargs(self):
        """Solver kwargs shared by the single and packed dispatch paths —
        one place for the tol-vs-abstol mapping and solver validation."""
        if self.solver not in _SOLVERS:
            raise ValueError(
                f"Unknown solver {self.solver!r}; valid: {sorted(_SOLVERS)}"
            )
        kwargs = dict(
            regularizer=get_regularizer(self.penalty),
            lamduh=1.0 / self.C,
            max_iter=self.max_iter,
            intercept=bool(self.fit_intercept),
            **(self.solver_kwargs or {}),
        )
        if self.solver == "admm":
            kwargs["abstol"] = self.tol
        else:
            kwargs["tol"] = self.tol
        return kwargs

    def _solve(self, X: ShardedRows, y, family=None, beta0=None):
        kwargs = self._solver_call_kwargs()  # validates self.solver
        # graftsan region: every GLM solver dispatch path funnels through
        # here (plain, chunked, and the OvR/multinomial branches that
        # call _solve per class), so compile attribution names the lane
        with _san.region("glm.fit.solve"):
            if getattr(self, "fit_checkpoint", None) is not None:
                return self._solve_chunked(
                    X, y, family or self.family, beta0, kwargs,
                    self.fit_checkpoint,
                )
            return self._run_solver(
                X, y, family or self.family, beta0, kwargs)

    def _solve_span(self):
        """``glm.solve``: from the solver call to ``n_iter_`` on the host,
        so the wait for the device is inside it."""
        return _obs.span(
            "glm.solve", shards=data_axes_size(),
            line_search=(self.solver_kwargs or {}).get(
                "line_search", "default"))

    def _run_solver(self, X, y, family, beta0, kwargs):
        """One whole-solve dispatch: ``(beta, n_it)``, both still on the
        device, where ``n_it`` is the ``SOLVE_COUNTS`` vector from a
        counted solver and the scalar iteration count from the others
        (``_fetch_counts`` reads either)."""
        flag = ("return_counts" if self.solver in COUNTED_SOLVERS
                else "return_n_iter")
        return _SOLVERS[self.solver](
            X, y, family=family, beta0=beta0, **{flag: True}, **kwargs)

    def _solve_chunked(self, X, y, family, beta0, kwargs, ckpt):
        """Preemption-safe solve: the fused device solver runs in SEGMENTS
        of the checkpoint cadence, warm-started from the previous
        segment's beta, with an atomic snapshot at every boundary.

        Restarting a solver segment resets its internal machinery (LBFGS
        curvature history, ADMM duals/rho, line-search step sizes), so the
        CHUNKED trajectory differs from the single-dispatch solve — but it
        is deterministic: a fit killed at any boundary and resumed from
        its snapshot replays the identical remaining segments, and the
        converged optimum is the same within ``tol``.  Pick a cadence of
        tens of iterations so the restart overhead amortizes (see
        :class:`~dask_ml_tpu.resilience.FitCheckpoint`).  The packed
        one-vs-rest plane ignores the checkpoint (one vmapped program for
        ALL classes — there is no per-class boundary to snapshot).
        """
        from ..resilience.preemption import check_preemption
        from ..resilience.testing import maybe_fault

        max_iter = int(kwargs.get("max_iter", 100))
        chunk = ckpt.chunk_iters(max(1, min(20, max_iter)))
        it = 0
        snap = ckpt.load_if_matches(self)
        if snap is not None:
            it, state = snap
            beta0 = np.asarray(state["beta"])
        solver = _SOLVERS[self.solver]
        beta = beta0
        while it < max_iter:
            maybe_fault("step")
            seg = min(chunk, max_iter - it)
            kw = dict(kwargs, max_iter=seg)
            beta, n_it = solver(
                X, y, return_n_iter=True, family=family, beta0=beta, **kw
            )
            n = int(n_it)
            it += n
            if ckpt.due(it):
                ckpt.save(self, {"beta": beta}, it)
            check_preemption(ckpt, self, {"beta": beta}, it)
            if n < seg:
                break  # the segment's own tol stop fired: converged
        ckpt.complete()
        return beta, it

    @staticmethod
    def _warm_ok(prev, shape, *, was_multinomial=False,
                 want_multinomial=False, classes_match=True):
        """THE warm-start geometry gate (one implementation for the
        regression, binary, OvR, and multinomial paths): previous betas
        are reusable only for the SAME problem geometry — matching
        classes, matching parameter shape, and the same
        multinomial-ness.  A mismatch means a different problem, so the
        solve cold-starts silently (sklearn errors only on changed
        classes; shape is the device-native analogue)."""
        if prev is None or not classes_match:
            return None
        if was_multinomial != want_multinomial:
            return None
        if tuple(np.asarray(prev).shape) != shape:
            return None
        return prev

    def _sweep_fit_values(self, X, y, Cs):
        """``len(Cs)`` REGRESSION fits differing only in ``C`` as one
        vmapped program (``solvers.lambda_sweep``); the grid-search fast
        path calls this for identity-link families.  Eligibility (no
        sample weights) is the caller's job.  Returns (betas (K, p),
        counts (K, n)), both on the host: each lane's counts
        (``lambda_sweep(return_counts=True)``: iterations first) come in
        the one ``device_get`` that brings its coefficients."""
        from ..solvers import lambda_sweep

        X = _ingest_float(self, X)
        kwargs = self._solver_call_kwargs()
        kwargs.pop("lamduh")
        return jax.device_get(lambda_sweep(
            self.solver, X, y, [1.0 / float(c) for c in Cs],
            family=self.family, return_counts=True, **kwargs,
        ))

    def fit(self, X, y=None, sample_weight=None):
        # the fit's spans (live under ``obs.enable()`` or a profiler
        # session): ``glm.fit`` is the root, its id the fit's identifier
        # on every child; what lies outside ``glm.classes``,
        # ``glm.prepare`` and ``glm.solve`` is its self time
        with _obs.span("glm.fit", estimator=type(self).__name__,
                       solver=self.solver) as root:
            return self._fit(X, y, sample_weight, root)

    def _prepare(self, X, root, span, **root_attrs):
        """Ingest X: ``(X, p)``, the table the solver reads and the
        number of parameters per class (one more than X is wide with an
        intercept, which the solver carries as a scalar: no column is
        appended).  The fit's sizes go on the root span."""
        X = _ingest_float(self, X)
        self.n_features_in_ = X.data.shape[1]
        root.set(rows=X.n_samples, features=self.n_features_in_,
                 chips=len(X.data.sharding.device_set),
                 n_shards=data_axes_size(), **root_attrs)
        span.set(padded_rows=X.data.shape[0],
                 intercept="scalar" if self.fit_intercept else "none")
        return X, self.n_features_in_ + bool(self.fit_intercept)

    def _fit(self, X, y, sample_weight, root):
        with _obs.span("glm.prepare") as span:
            X0, p = self._prepare(X, root, span)
            Xi = X0
            if sample_weight is not None:
                from ..utils import reweight_rows

                Xi = reweight_rows(Xi, sample_weight=sample_weight)
            span.set(appended_bytes=_appended_bytes(X0, Xi))
            warm = None
            if self.warm_start:
                warm = self._warm_ok(
                    getattr(self, "betas_", None), (1, p),
                    was_multinomial=getattr(self, "_multinomial", False),
                )
        with self._solve_span() as span:
            beta, n_it = self._solve(
                Xi, y, beta0=None if warm is None else warm[0])
            # sklearn contract: iteration count(s) of the solver run(s);
            # converted only now, after the solve is dispatched (the wait
            # for the device is here, inside the span)
            self.n_iter_, *counts = _fetch_counts([n_it])
            _publish_counts(span, *counts, self)
        if self.fit_intercept:
            self.coef_ = beta[:-1]
            self.intercept_ = float(beta[-1])
        else:
            self.coef_ = beta
            self.intercept_ = 0.0
        self._coef = beta
        self.betas_ = beta[None, :]
        return self

    def _eta(self, X):
        X = _ingest_float(self, X)
        eta = X.data @ self.coef_ + self.intercept_
        return X, eta

    def predict(self, X):
        raise NotImplementedError

    def score(self, X, y):
        raise NotImplementedError


class LogisticRegression(ClassifierMixin, _GLM):
    """Binary and multiclass logistic regression over the solver library.

    Multiclass is one-vs-rest (``multi_class='ovr'``): ALL K class solves
    run as ONE vmapped XLA program (``solvers.packed_solve``), or a true
    softmax fit with ``multi_class='multinomial'``.  ``classes_`` is
    fitted and ``predict`` returns original labels.  ``class_weight``
    (dict or ``'balanced'``) and ``fit(..., sample_weight=)`` scale the
    row mask — the solvers' masked reductions become sklearn's weighted
    loss.  ``warm_start=True`` seeds every solver with the previous
    fit's coefficients when the problem geometry (classes + parameter
    shape) is unchanged — an improvement over the reference (dask_glm
    ignores it): a warm refit on similar data converges in a fraction
    of the iterations (binary, packed OvR, and multinomial paths all
    warm-start; ADMM re-seeds consensus z and the per-shard betas).
    """

    family = Logistic

    def _sweep_fit_binary(self, X, y, Cs):
        """Fit ``len(Cs)`` variants differing ONLY in ``C`` as ONE
        vmapped program (``solvers.lambda_sweep`` — the lanes share X
        and y; the regularization strength is a traced scalar).  The
        grid-search fast path calls this; eligibility (binary labels,
        no sample/class weights, plain ovr) is the CALLER's job.

        Returns (betas (K, p), classes (2,), counts (K, n)), all on the
        host: each lane's counts (``lambda_sweep(return_counts=True)``:
        iterations first) come in the one ``device_get`` that brings its
        coefficients.
        """
        from ..core.sharded import ShardedRows as _SR
        from ..core.sharded import as_sharded
        from ..solvers import lambda_sweep

        y = as_sharded(y)
        if isinstance(y, _SR):
            classes = masked_unique(y.data, y.mask)
        else:
            classes = np.unique(np.asarray(y))
        if len(classes) != 2:
            raise ValueError(
                f"_sweep_fit_binary needs exactly 2 classes, got "
                f"{classes.tolist()}"
            )
        X = _ingest_float(self, X)
        y01 = binary_indicator(y, classes[1])
        kwargs = self._solver_call_kwargs()
        kwargs.pop("lamduh")
        betas, counts = jax.device_get(lambda_sweep(
            self.solver, X, y01, [1.0 / float(c) for c in Cs],
            family=self.family, return_counts=True, **kwargs,
        ))
        return betas, classes, counts

    def _fit(self, X, y, sample_weight, root):
        # warm start (an improvement over the reference: dask_glm ignores
        # it): capture the PREVIOUS fit's parameters before this fit
        # overwrites them; they seed the solver when the problem geometry
        # (classes + parameter shape) is unchanged
        prev_betas = (
            np.asarray(self.betas_)
            if self.warm_start and hasattr(self, "betas_") else None
        )
        prev_classes = (
            self.classes_
            if self.warm_start and hasattr(self, "classes_") else None
        )
        prev_multinomial = getattr(self, "_multinomial", False)
        if self.multi_class not in ("ovr", "auto", "multinomial"):
            raise ValueError(
                f"multi_class must be 'ovr', 'auto' or 'multinomial'; got "
                f"{self.multi_class!r}"
            )
        from ..core.sharded import ShardedRows as _SR
        from ..core.sharded import as_sharded

        with _obs.span("glm.classes") as span:
            # raw device label vectors ride the ShardedRows no-fetch paths
            y = as_sharded(y)
            if isinstance(y, _SR):
                # device-side class discovery: one program scans the
                # sharded labels for their distinct values in ascending
                # order (a masked min per value, so pad rows are no
                # class) and only those VALUES cross to host in one
                # fetch, never the n-row label vector — a full unshard of
                # device-resident labels is an O(n) device->host
                # transfer, and illegal for multi-host global arrays.
                # Only a vector of more values than the scan holds, or
                # with a NaN, is sorted (core.sharded.masked_unique).
                self.classes_ = masked_unique(y.data, y.mask, span)
                yv = None
            else:
                yv = np.asarray(y)
                self.classes_ = np.unique(yv)
            K = len(self.classes_)
            span.set(classes=K)
        if K < 2:
            raise ValueError(
                "LogisticRegression needs samples of at least 2 classes; "
                f"got {self.classes_.tolist()}"
            )

        def _warm(shape, want_multinomial=False):
            """Previous betas when classes and parameter shape match
            (delegates to the shared ``_warm_ok`` geometry gate)."""
            return self._warm_ok(
                prev_betas, shape,
                was_multinomial=prev_multinomial,
                want_multinomial=want_multinomial,
                classes_match=(
                    prev_classes is not None
                    and len(prev_classes) == K
                    and np.array_equal(np.asarray(prev_classes),
                                       np.asarray(self.classes_))
                ),
            )

        # binary: one sigmoid solve.  'multinomial' with 2 classes is the
        # SAME loss reparameterized (w = w1 - w0); for L2 the softmax
        # penalty ||w0||² + ||w1||² equals ||w||²/2 at the symmetric
        # optimum — i.e. the sigmoid fit at HALF the penalty.  That
        # scalar equivalence is L2-ONLY (L1 of the split pair is |w|,
        # elasticnet has no single scale), so non-L2 multinomial takes
        # the true 2-class softmax solve.
        binary = K == 2 and not (
            self.multi_class == "multinomial" and self.penalty != "l2")
        softmax = not binary and self.multi_class == "multinomial"

        with _obs.span("glm.prepare") as span:
            X0, p = self._prepare(X, root, span, classes=K)
            Xi = X0
            if sample_weight is not None or self.class_weight is not None:
                # weights scale the mask: every masked reduction in the
                # solvers becomes the sklearn weighted loss (the mask
                # machinery IS the per-row weight)
                from ..utils import host_class_weight_rows, reweight_rows

                if self.class_weight is not None and yv is not None:
                    # host labels can be strings or big ints that a
                    # device cast would corrupt: resolve the per-row class
                    # weight on host and fold it into sample_weight
                    row_w = host_class_weight_rows(
                        self.class_weight, self.classes_, yv
                    )
                    if sample_weight is not None:
                        row_w = row_w * np.asarray(
                            sample_weight, np.float32)
                    Xi = reweight_rows(Xi, sample_weight=row_w)
                elif self.class_weight is not None:
                    # device labels are numeric by construction: count
                    # and weight classes on device, no label round-trip
                    Xi = reweight_rows(
                        Xi, sample_weight=sample_weight,
                        class_weight=self.class_weight,
                        classes=self.classes_, y_padded=y.data,
                    )
                else:
                    Xi = reweight_rows(Xi, sample_weight=sample_weight)
            span.set(appended_bytes=_appended_bytes(X0, Xi))
            # the solve's target and its warm start (previous betas_)
            if binary:
                # one-vs-rest target via the SHARED encoding helper
                target = binary_indicator(
                    yv if yv is not None else y, self.classes_[1])
                warm = _warm((1, p))
            elif softmax:
                if yv is None:
                    yd2 = jnp.where(y.mask > 0, y.data, y.data[0])
                    target = _SR(
                        data=jnp.searchsorted(
                            jnp.asarray(self.classes_, yd2.dtype), yd2
                        ).astype(jnp.float32),
                        mask=y.mask, n_samples=y.n_samples,
                    )
                else:
                    target = np.searchsorted(
                        self.classes_, yv).astype(np.float32)
                warm = _warm((K, p), want_multinomial=True)
            else:
                n_pad = Xi.data.shape[0]
                if yv is None:
                    target = (
                        y.data[None, :]
                        == jnp.asarray(self.classes_, y.data.dtype)[:, None]
                    ).astype(jnp.float32)
                else:
                    Yh = (yv[None, :] == self.classes_[:, None]).astype(
                        np.float32
                    )
                    target = jnp.asarray(
                        np.pad(Yh, ((0, 0), (0, n_pad - Yh.shape[1])))
                    )
                warm = _warm((K, p))

        self._multinomial = False
        with self._solve_span() as span:
            if binary:
                w0 = None if warm is None else warm[0]
                if self.multi_class == "multinomial":
                    kwargs = self._solver_call_kwargs()
                    kwargs["lamduh"] = kwargs["lamduh"] / 2.0
                    beta, n_it = self._run_solver(
                        Xi, target, self.family, w0, kwargs)
                else:
                    beta, n_it = self._solve(Xi, target, beta0=w0)
                self.betas_ = beta[None, :]
                n_iter_runs = [n_it]
            elif softmax:
                # true softmax: ONE solve over a flat (features*K)
                # parameter vector (solvers/families.py :: multinomial);
                # closes the reference's binary-only dask_glm gap
                from ..solvers import multinomial as _mn

                # warm start: betas_ stores W (K, p); the flat vector the
                # softmax family consumes is its (p, K) transpose raveled
                beta_flat, n_it = self._solve(
                    Xi, target, family=_mn(K),
                    beta0=None if warm is None else warm.T.ravel())
                W = beta_flat.reshape(p, K).T  # (K, p)
                if K == 2:
                    # non-L2 binary softmax (the L2 case took the sigmoid
                    # shortcut above): collapse to the sigmoid form — the
                    # decision function w = w1 - w0 gives the EXACT
                    # softmax posterior, and the binary coef_/predict
                    # contract holds
                    self.betas_ = (W[1] - W[0])[None, :]
                else:
                    self.betas_ = W
                    self._multinomial = True
                # sklearn multinomial reports ONE solver run replicated
                # per class in n_iter_; keep a single honest count instead
                n_iter_runs = [n_it]
            else:
                # packed one-vs-rest: the K independent solves run as ONE
                # vmapped XLA program (solvers.packed_solve) — the
                # reference dispatches a task graph per class; a K-long
                # Python loop of device solves was the round-2 shape
                from ..solvers import packed_solve

                self.betas_, n_iter_runs = packed_solve(  # betas_ (K, p)
                    self.solver, Xi, target, family=self.family,
                    Beta0=warm, **self._solver_call_kwargs(),
                )
            # sklearn contract: one count per OvR solve — device scalars
            # are converted only here, after every class's solve has
            # dispatched (the wait for the device is here, in the span)
            self.n_iter_, *counts = _fetch_counts(n_iter_runs)
            _publish_counts(span, *counts, self)
        if self.fit_intercept:
            self.coef_ = (
                self.betas_[0, :-1] if len(self.classes_) == 2
                else self.betas_[:, :-1]
            )
            self.intercept_ = (
                float(self.betas_[0, -1]) if len(self.classes_) == 2
                else np.asarray(self.betas_[:, -1])
            )
        else:
            self.coef_ = (
                self.betas_[0] if len(self.classes_) == 2 else self.betas_
            )
            self.intercept_ = (
                0.0 if len(self.classes_) == 2
                else np.zeros(len(self.classes_))
            )
        self._coef = self.betas_[0] if len(self.classes_) == 2 else self.betas_
        return self

    def _etas(self, X):
        """(X, per-class raw margins [n, K_or_1])."""
        X = _ingest_float(self, X)
        if self.fit_intercept:
            eta = X.data @ self.betas_[:, :-1].T + self.betas_[:, -1]
        else:
            eta = X.data @ self.betas_.T
        return X, eta

    def predict(self, X):
        X, eta = self._etas(X)
        eta = eta[: X.n_samples]
        if len(self.classes_) == 2:
            idx = (eta[:, 0] > 0).astype(jnp.int32)
        else:
            idx = jnp.argmax(eta, axis=1)
        return self.classes_[np.asarray(idx)]

    def predict_proba(self, X):
        import jax

        X, eta = self._etas(X)
        eta = eta[: X.n_samples]
        if len(self.classes_) == 2:
            p1 = Logistic.predict(eta[:, 0])
            return jnp.stack([1.0 - p1, p1], axis=1)
        if getattr(self, "_multinomial", False):
            return jax.nn.softmax(eta, axis=1)  # true joint posterior
        p = Logistic.predict(eta)  # per-class sigmoid, OvR-normalized
        return p / jnp.sum(p, axis=1, keepdims=True)

    def predict_log_proba(self, X):
        """Log class probabilities, in numerically stable forms: binary
        uses ``log_sigmoid(±eta)``, multinomial ``log_softmax``; the OvR
        path logs its normalized sigmoids."""
        X, eta = self._etas(X)
        eta = eta[: X.n_samples]
        if len(self.classes_) == 2:
            return jnp.stack([
                jax.nn.log_sigmoid(-eta[:, 0]), jax.nn.log_sigmoid(eta[:, 0])
            ], axis=1)
        if getattr(self, "_multinomial", False):
            return jax.nn.log_softmax(eta, axis=1)
        p = Logistic.predict(eta)
        return jnp.log(p / jnp.sum(p, axis=1, keepdims=True))

    def decision_function(self, X):
        X, eta = self._etas(X)
        eta = eta[: X.n_samples]
        return eta[:, 0] if len(self.classes_) == 2 else eta

    def score(self, X, y, sample_weight=None):
        """Mean accuracy (reference forwards to dask accuracy_score);
        accepts plain or ShardedRows y.  All-device inputs score as ONE
        replicated scalar fetch — no O(n) label transfer (the form the
        device-resident CV search relies on, and the only legal one for
        multi-host global arrays)."""
        from ..core.sharded import ShardedRows as _SR
        from ..core.sharded import as_sharded, unshard

        from ..utils import classes_f32_exact, masked_device_accuracy

        X, y = as_sharded(X), as_sharded(y)
        if sample_weight is not None:
            if isinstance(y, _SR):
                # device labels stay on device: accuracy_score consumes
                # ShardedRows natively — no O(n) pull (multi-host safe)
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
            Xi, eta = self._etas(X)
            if len(self.classes_) == 2:
                idx = (eta[:, 0] > 0).astype(jnp.int32)
            else:
                idx = jnp.argmax(eta, axis=1).astype(jnp.int32)
            return masked_device_accuracy(
                idx, y.data, Xi.mask, self.classes_
            )
        yv = unshard(y) if isinstance(y, _SR) else np.asarray(y)
        return float((self.predict(X) == yv).mean())


class LinearRegression(RegressorMixin, _GLM):
    family = Normal

    def predict(self, X):
        X, eta = self._eta(X)
        return eta[: X.n_samples]

    def score(self, X, y, sample_weight=None):
        from ..metrics import r2_score

        return r2_score(y, self.predict(X), sample_weight=sample_weight)


class PoissonRegression(RegressorMixin, _GLM):
    family = Poisson

    def predict(self, X):
        X, eta = self._eta(X)
        return jnp.exp(eta)[: X.n_samples]

    def get_deviance(self, X, y, sample_weight=None):
        from ..core.sharded import unshard

        mu = np.asarray(self.predict(X))
        yv = unshard(y) if isinstance(y, ShardedRows) else np.asarray(y)
        with np.errstate(divide="ignore", invalid="ignore"):
            term = np.where(yv > 0, yv * np.log(yv / mu), 0.0)
        dev = term - (yv - mu)
        if sample_weight is not None:
            dev = dev * np.asarray(sample_weight)
        return 2 * np.sum(dev)

    def score(self, X, y, sample_weight=None):
        return -self.get_deviance(X, y, sample_weight=sample_weight)


def _stop_attrs(est, counts, ratios):
    """What ``glm.solve`` says of a counted solve's stop beside its
    counts: the solver's ``ratios`` (where ADMM's consensus and its last
    round's local solves stood against their tests; one of the latter
    that is no number, because ``inner_tol`` is 0 or a solve took no
    iteration, is left off) and ``stopped``, why the solver's own loop
    ended: ADMM's by Boyd's rule (``"boyd"``) or at ``max_iter`` rounds
    (``"budget"``), ``lbfgs``'s by the name of its exit
    (``lbfgs_core.EXITS``).  Known on the host: no device work."""
    attrs = {name: value for name, value in ratios.items()
             if name not in ("grad_ratio", "dec_ratio")
             or np.isfinite(value)}
    if "exit_gtol" in counts:  # a counted solve's whole vector
        attrs["stopped"] = (
            ("budget" if counts["rounds"] >= est.max_iter else "boyd")
            if est.solver == "admm" else
            next(name for name in EXITS if counts["exit_" + name]))
    return attrs
