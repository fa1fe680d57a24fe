"""Incremental (adaptive) search core.

Reference: ``dask_ml/model_selection/_incremental.py`` — the dynamic
futures plane (SURVEY.md §1 style 2, §3.3): an async loop scatters data
blocks, submits per-model ``partial_fit`` (one block per call — the unit of
training budget) and ``score`` tasks, and a pluggable
``additional_calls(info) -> {model_id: n_more_calls}`` policy decides at
runtime what trains next, until it returns ``{}``.

TPU design: the control plane survives as a host asyncio loop (the policy
logic is identical); the data plane changes — blocks are row chunks of a
host/ sharded array, models train in-process (sklearn ``partial_fit`` on
host, or device-native estimators whose step is a jitted program).  JAX's
async dispatch pipelines the device models without extra machinery.
"""

from __future__ import annotations

import asyncio
import logging
import threading

from .._locks import make_lock
import time
from collections import defaultdict

import numpy as np

from .. import obs as _obs
from ..base import TPUEstimator, clone
from ..core.sharded import ShardedRows, unshard
from ..metrics.scorer import check_scoring
from ..utils import check_random_state
from ._split import train_test_split
from .. import sanitize as _san

logger = logging.getLogger(__name__)

# Shared training pool for the adaptive searches (the scheduler+worker
# threadpools of the reference, collapsed to one process).  Module-level so
# concurrent Hyperband brackets share workers instead of oversubscribing.
_EXECUTOR = None
_EXECUTOR_LOCK = make_lock("search.executor")


def _train_executor():
    global _EXECUTOR
    with _EXECUTOR_LOCK:
        if _EXECUTOR is None:
            import os
            from concurrent.futures import ThreadPoolExecutor

            # training threads mostly wait inside GIL-releasing kernels
            # (sklearn C, XLA dispatch), so size past the core count the
            # way an IO pool would — never below 4
            # graftlint: disable=thread-dispatch -- shared HOST pool: device-estimator units never race here (run_round's _uses_device_estimator gate serializes them before dispatch)
            _EXECUTOR = ThreadPoolExecutor(
                max_workers=min(16, max(4, os.cpu_count() or 1)),
                thread_name_prefix="dask_ml_tpu_train",
            )
        return _EXECUTOR


def _partial_fit(model_and_meta, X, y, fit_params):
    """One unit of budget: partial_fit on ONE block (reference
    ``_incremental.py :: _partial_fit``)."""
    model, meta = model_and_meta
    start = time.time()
    model.partial_fit(X, y, **(fit_params or {}))
    meta = dict(meta)
    meta["partial_fit_calls"] += 1
    meta["partial_fit_time"] = time.time() - start
    return model, meta


def _score(model_and_meta, X_test, y_test, scorer):
    model, meta = model_and_meta
    start = time.time()
    score = scorer(model, X_test, y_test)
    meta = dict(meta)
    meta["score_time"] = time.time() - start
    meta["score"] = float(score)
    return meta


def _create_model(estimator, params, random_state):
    model = clone(estimator).set_params(**params)
    if "random_state" in model.get_params():
        model.set_params(random_state=random_state)
    return model


class BaseIncrementalSearchCV(TPUEstimator):
    """Adaptive search over partial_fit estimators.

    Subclasses supply ``_additional_calls(info)``; ``info`` maps model_id →
    list of records (dicts with ``partial_fit_calls``, ``score``, …).
    """

    # policy counters a round-granular checkpoint must capture (subclasses
    # override; see dask_ml_tpu.checkpoint)
    _policy_state_attrs: tuple = ()

    def __init__(self, estimator, parameters, n_initial_parameters=10,
                 test_size=None, random_state=None, scoring=None,
                 max_iter=100, patience=False, tol=1e-3, fits_per_score=1,
                 verbose=False, prefix="", chunk_size=None, checkpoint=None):
        self.estimator = estimator
        self.parameters = parameters
        self.n_initial_parameters = n_initial_parameters
        self.test_size = test_size
        self.random_state = random_state
        self.scoring = scoring
        self.checkpoint = checkpoint
        self.max_iter = max_iter
        self.patience = patience
        self.tol = tol
        self.fits_per_score = fits_per_score
        self.verbose = verbose
        self.prefix = prefix
        self.chunk_size = chunk_size

    # -- policy hooks --------------------------------------------------
    def _additional_calls(self, info):
        raise NotImplementedError

    def _patience_calls(self) -> int:
        """Resolved patience budget in partial_fit calls; 0 = disabled.
        ``patience=True`` auto-sizes to ``max_iter // aggressiveness``
        (the reference's Hyperband convention for its bool form; policies
        without an aggressiveness use the Hyperband default of 3)."""
        if not self.patience:
            return 0
        if self.patience is True:
            eta = int(getattr(self, "aggressiveness", 3) or 3)
            return max(int(self.max_iter) // eta, 1)
        return int(self.patience)

    def _filter_plateaued(self, info, instructions):
        """Drop positive instructions for models whose score has not
        improved by ``tol`` over the last ``patience`` partial_fit calls.

        Applied by the fit loop AFTER every policy's ``_additional_calls``
        so plateau stopping works uniformly for IncrementalSearchCV, SHA,
        Hyperband brackets and InverseDecay (reference: ``patience``/
        ``tol`` are base-class semantics, not per-policy).

        The window is measured in ``partial_fit_calls`` DISTANCE, not
        record count: SHA appends one score record per geometrically
        growing burst (1, 3, 9, … calls), so counting records would make
        large patience values silent no-ops for exactly the policies this
        filter exists to cover.
        """
        patience = self._patience_calls()
        if not patience:
            return instructions
        out = {}
        for ident, n_calls in instructions.items():
            if n_calls > 0:
                recs = info[ident]
                edge = recs[-1]["partial_fit_calls"] - patience
                window = [
                    r["score"] for r in recs if r["partial_fit_calls"] > edge
                ]
                older = [
                    r["score"] for r in recs if r["partial_fit_calls"] <= edge
                ]
                # plateaued: a full patience window exists and nothing in
                # it beat the last pre-window score by tol
                if older and window and all(
                    s < older[-1] + self.tol for s in window
                ):
                    continue
            out[ident] = n_calls
        return out

    def _reset_policy(self):
        """Clear per-fit mutable policy state (re-fit safety)."""

    # -- parameter sampling -------------------------------------------
    def _get_params(self):
        from sklearn.model_selection import ParameterSampler

        rng = check_random_state(self.random_state)
        if self.n_initial_parameters == "grid":
            from sklearn.model_selection import ParameterGrid

            return list(ParameterGrid(self.parameters))
        return list(
            ParameterSampler(
                self.parameters, self.n_initial_parameters,
                random_state=rng,
            )
        )

    # -- data plumbing -------------------------------------------------
    def _to_blocks(self, X, y):
        """Row blocks, kept WHERE THE DATA LIVES.

        Device-resident (ShardedRows) input yields device-slice blocks —
        an O(n) unshard here would pull the training set to host only
        for device-native models to re-upload it every round.  Host input yields host blocks (what
        sklearn models consume); host models consuming device blocks get
        a once-per-block cached host view (``block_for`` in ``_fit``).

        NOTE: the sliced blocks deliberately RELAX ShardedRows' "rows
        divisible by the data axis" invariant (core/sharded.py) — they
        are plain-jit views for partial_fit consumers, not shard_map
        operands; do not feed them to P(DATA_AXIS) shard_map programs.
        """
        if isinstance(X, ShardedRows):
            n = X.n_samples
            chunk = self.chunk_size or max(1, n // 10)
            ysr = y if isinstance(y, ShardedRows) else None
            yh = None if ysr is not None else np.asarray(y)
            blocks = []
            for lo in range(0, n, chunk):
                hi = min(lo + chunk, n)
                xb = ShardedRows(
                    data=X.data[lo:hi], mask=X.mask[lo:hi], n_samples=hi - lo
                )
                if ysr is not None:
                    yb = ShardedRows(
                        data=ysr.data[lo:hi], mask=ysr.mask[lo:hi],
                        n_samples=hi - lo,
                    )
                else:
                    yb = yh[lo:hi]
                blocks.append((xb, yb))
            return blocks
        Xh = np.asarray(X)
        yh = unshard(y) if isinstance(y, ShardedRows) else np.asarray(y)
        n = Xh.shape[0]
        chunk = self.chunk_size or max(1, n // 10)
        return [
            (Xh[lo: lo + chunk], yh[lo: lo + chunk])
            for lo in range(0, n, chunk)
        ]

    # -- checkpoint plumbing (see dask_ml_tpu.checkpoint) ---------------
    def _checkpointer(self):
        if not self.checkpoint:
            return None
        from ..checkpoint import SearchCheckpoint, search_fingerprint

        return SearchCheckpoint(
            self.checkpoint, fingerprint=search_fingerprint(self),
            keep_on_complete=getattr(self, "_ckpt_keep_on_complete", False),
        )

    def _capture_policy_state(self):
        return {a: getattr(self, a) for a in self._policy_state_attrs}

    def _restore_policy_state(self, state):
        for a, v in state.items():
            setattr(self, a, v)

    async def _fit(self, X_train, y_train, X_test, y_test, **fit_params):
        self._reset_policy()
        self._fit_failures = 0
        self._fit_failures_lock = make_lock("search.scores")
        # per-fit shared fault budget (design.md §13): every unit's
        # requeue retry AND every streamed burst's elastic recovery
        # draw from this ONE pool, so cascading faults across many
        # concurrent units stop at the fit-wide ceiling instead of
        # multiplying per-site budgets
        from ..resilience.elastic import FaultBudget

        self._fault_budget = FaultBudget.from_env(
            name=f"search:{type(self).__name__}")
        # span parentage (design.md §11): async scopes use DETACHED
        # spans with an explicit parent — concurrent brackets interleave
        # coroutines on one loop thread, so stack parentage would
        # cross-link them.  A Hyperband bracket hands its bracket-span
        # id in via _obs_parent; a direct fit() parents under the
        # search.fit span fit() opened on this (the calling) thread.
        fit_parent = getattr(self, "_obs_parent", None)
        if fit_parent is None:
            fit_parent = _obs.current_span_id()
        round_span = {"id": fit_parent}  # units parent here per round
        scorer = check_scoring(self.estimator, self.scoring)
        params = self._get_params()
        rng = check_random_state(self.random_state)
        seeds = rng.randint(0, 2 ** 31 - 1, size=len(params))
        blocks = self._to_blocks(X_train, y_train)
        n_blocks = len(blocks)

        ckpt = self._checkpointer()
        resumed = False
        models = {}
        info = defaultdict(list)
        start_time = time.time()
        snap = ckpt.load_if_matches() if ckpt is not None else None
        if ckpt is not None and snap is None and ckpt.exists():
            logger.warning(
                "checkpoint %s belongs to a different search configuration; "
                "ignoring it and starting fresh", ckpt.path,
            )
        if snap is not None:
            saved_models, saved_info, policy_state, prior_elapsed = snap
            models.update(saved_models)
            for k, v in saved_info.items():
                info[k] = list(v)
            self._restore_policy_state(policy_state)
            # keep history_'s chronological contract across the restart:
            # post-resume records continue from the accumulated wall time
            start_time = time.time() - prior_elapsed
            resumed = True
            logger.info("resumed %d models from checkpoint %s", len(models), ckpt.path)
        if not resumed:
            for ident, (p, seed) in enumerate(zip(params, seeds)):
                model = _create_model(self.estimator, p, int(seed))
                meta = {
                    "model_id": ident,
                    "params": p,
                    "partial_fit_calls": 0,
                    "partial_fit_time": 0.0,
                    "score_time": 0.0,
                    "elapsed_wall_time": 0.0,
                }
                models[ident] = (model, meta)

        # host (sklearn) models consume host views of device blocks; fetch
        # each block's host copy ONCE for the whole search, not per call
        # (benign write race from pool threads: all writers store the same
        # value)
        host_block_cache: dict = {}

        def block_for(model, block_idx):
            Xb, yb = blocks[block_idx]
            if isinstance(Xb, ShardedRows) and not isinstance(
                model, TPUEstimator
            ):
                if block_idx not in host_block_cache:
                    host_block_cache[block_idx] = (
                        unshard(Xb),
                        unshard(yb) if isinstance(yb, ShardedRows) else yb,
                    )
                return host_block_cache[block_idx]
            return Xb, yb

        # search-ingest prefetch: multi-call bursts on a staged-protocol
        # (device-native) model stream their blocks through the input
        # pipeline, so block k+1's host fetch + H2D staging overlaps
        # block k's device step (DASK_ML_TPU_PREFETCH_DEPTH; 0 = serial)
        from ..pipeline import resolve_depth, stream_partial_fit

        prefetch_depth = resolve_depth(None)

        def _warm_unit(model, calls0, n_calls):
            """Compile-ahead (programs/, design.md §12): heterogeneous
            configs whose static hyperparams differ each need their own
            step program — pre-build this unit's from the next block's
            shape on the blessed compile thread, so the burst starts on
            a warm executable instead of stalling on XLA."""
            warm = getattr(model, "_pf_warm", None)
            if warm is None or n_calls <= 0:
                return
            from .. import programs as _programs

            Xw, _yw = blocks[calls0 % n_blocks]
            # knob check OUTSIDE the best-effort net: a typo'd
            # DASK_ML_TPU_COMPILE_AHEAD must raise loudly (the
            # strict-parse contract), not read as a shapeless block.
            # Host blocks only: device-resident blocks take the
            # unbucketed ShardedRows step, whose signature the
            # shape-based warm cannot predict
            if _programs.compile_ahead_enabled() and \
                    not isinstance(Xw, ShardedRows) and \
                    isinstance(getattr(Xw, "shape", None), tuple) and \
                    not hasattr(Xw, "aval"):
                try:
                    warm(Xw.shape,
                         classes=(fit_params or {}).get("classes"))
                except (TypeError, ValueError):
                    pass  # shapeless/1-D blocks: warm is best-effort

        def train_one(ident, n_calls):
            model, meta = models[ident]
            calls0 = meta["partial_fit_calls"]
            _warm_unit(model, calls0, n_calls)
            if (n_calls > 1 and prefetch_depth > 0
                    and hasattr(model, "_pf_stage")):
                from ..resilience.elastic import ElasticPolicy

                t0 = time.time()
                with _san.region("search.train_one"):
                    stream_partial_fit(
                        model,
                        (block_for(model, (calls0 + j) % n_blocks)
                         for j in range(n_calls)),
                        depth=prefetch_depth, fit_kwargs=fit_params,
                        label="search_ingest",
                        # burst recovery draws from the fit-wide budget
                        elastic=ElasticPolicy(
                            budget=self._fault_budget,
                            label="search_ingest"),
                    )
                meta = dict(meta)
                meta["partial_fit_calls"] += n_calls
                # train_one semantics: partial_fit_time is ONE call's
                # duration — amortize the streamed burst over its calls
                meta["partial_fit_time"] = (time.time() - t0) / n_calls
            else:
                for _ in range(n_calls):
                    block_idx = meta["partial_fit_calls"] % n_blocks
                    Xb, yb = block_for(model, block_idx)
                    model, meta = _partial_fit(
                        (model, meta), Xb, yb, fit_params
                    )
            meta = _score((model, meta), X_test, y_test, scorer)
            meta["elapsed_wall_time"] = time.time() - start_time
            models[ident] = (model, meta)
            info[ident].append(meta)
            return meta

        def _score_cohort(cohort, idents):
            """Packed scoring: with the default (accuracy) scorer the
            whole cohort scores as ONE vmapped dispatch + one (M,)
            fetch, instead of M separate model.score round-trips — and
            it is the multi-controller-safe form (single collective
            program).  Returns (scores_or_None, per_model_score_time)."""
            if self.scoring is not None:
                return None, 0.0
            try:
                t0s = time.time()
                scores = cohort.packed_accuracy(X_test, y_test)
                return scores, (time.time() - t0s) / max(len(idents), 1)
            except (TypeError, ValueError):
                return None, 0.0  # non-classifier/custom: fall back

        def _finish_cohort(idents, n_calls, pf_time, packed_scores,
                           packed_score_time):
            """Write one trained cohort's records back per member —
            shared by the serialized and the orchestrated paths."""
            for i, ident in enumerate(idents):
                model, meta = models[ident]
                meta = dict(meta)
                meta["partial_fit_calls"] += n_calls
                meta["partial_fit_time"] = pf_time
                if packed_scores is not None:
                    # packed_scores is host numpy already: packed_accuracy
                    # fetched the whole (M,) vector in ONE round-trip
                    meta["score"] = float(packed_scores[i])
                    meta["score_time"] = packed_score_time
                else:
                    meta = _score((model, meta), X_test, y_test, scorer)
                meta["elapsed_wall_time"] = time.time() - start_time
                models[ident] = (model, meta)
                info[ident].append(meta)

        def train_cohort(idents, n_calls):
            """Lockstep group of packable models: ONE fused dispatch per
            block advances the whole group (see _packing module docstring).
            Equivalent to train_one per ident, minus the dispatches."""
            from ._packing import Cohort

            cohort = Cohort(
                [models[i][0] for i in idents],
                classes=(fit_params or {}).get("classes"),
            )
            calls0 = models[idents[0]][1]["partial_fit_calls"]
            t0 = time.time()
            for j in range(n_calls):
                Xb, yb = blocks[(calls0 + j) % n_blocks]
                cohort.step(Xb, yb)
            t_fit_end = time.time()  # scoring must not inflate pf_time
            packed_scores, packed_score_time = _score_cohort(cohort, idents)
            cohort.finalize()
            # train_one semantics: partial_fit_time is the duration of ONE
            # model's ONE block call — amortize the cohort-wide wall time
            # over (models x calls) so packed and unpacked timings compare
            pf_time = (t_fit_end - t0) / max(n_calls * len(idents), 1)
            _finish_cohort(idents, n_calls, pf_time, packed_scores,
                           packed_score_time)

        def pack_groups(instructions):
            """Group instructed models by (static config, budget, step
            counter) — members of a group are in lockstep and can train as
            one stacked program.  Returns (groups, leftovers)."""
            from ._packing import pack_key

            groups = defaultdict(list)
            singles = []
            for ident, n_calls in instructions.items():
                if n_calls <= 0:
                    continue
                model, meta = models[ident]
                key = pack_key(model)
                if key is None:
                    singles.append((ident, n_calls))
                else:
                    groups[(key, n_calls, meta["partial_fit_calls"])].append(ident)
            packed = {k: v for k, v in groups.items() if len(v) > 1}
            for k, v in groups.items():
                if len(v) == 1:
                    singles.append((v[0], k[1]))
            return packed, singles

        # multi-controller lockstep: on a multi-process group EVERY process
        # must issue device programs in the SAME order (computed once here;
        # used by both the retry policy and the round dispatcher)
        try:
            import jax as _jax

            lockstep = _jax.process_count() > 1
        except Exception:
            lockstep = False

        # intra-process collective-safety (the PR-1 deadlock class, same
        # contract as _search.py): a device estimator's partial_fit
        # dispatches multi-device programs on the one shared mesh, and
        # thread-scheduled units can interleave enqueue order across
        # devices and deadlock the runtime.  A device fit occupies every
        # device anyway, so the pool buys no overlap for these — run
        # device units sequentially; host (sklearn) units keep the pool.
        from ._search import _uses_device_estimator

        serialize_units = lockstep or _uses_device_estimator(self.estimator)

        def run_unit(fn, unit_ids, first_arg, n_calls):
            """One training unit with single-retry fault recovery.

            The reference's resilience comes from the scheduler: a task
            lost to a dead worker is resubmitted and lineage recomputes
            its inputs (SURVEY.md §5 failure detection).  Here the unit
            rides the shared :func:`dask_ml_tpu.resilience.retry`
            primitive (tag ``"search-unit"`` in the global fault stats)
            with an ``on_error`` hook that restores the deep-copied
            round-start state — exact-state recovery (sklearn partial_fit
            mutates in place, so re-running without the snapshot would
            double-apply blocks).  One retry, no backoff (the fault is a
            dead unit, not a contended resource); a second failure
            propagates: persistent faults must surface, not spin.

            On a multi-process group there is NO retry (``retries=0``):
            an exception seen by one process only would make that process
            re-issue the unit's device programs while its peers move on —
            the fleet's collective streams diverge and deadlock.  State is
            rolled back and the fault propagates so every process stops
            loudly.

            Elastic additions (design.md §13): the unit registers a
            supervisor heartbeat (one beat per unit run — the search
            domain's liveness books), and the retry draws from the
            FIT-WIDE shared :class:`~dask_ml_tpu.resilience.FaultBudget`
            — one flaky unit still gets its single requeue, but a
            CASCADE of failing units (a sick device, a poisoned split)
            exhausts the shared budget and propagates loudly instead of
            retrying once per unit forever.
            """
            import copy

            from ..resilience import supervisor as _supervisor
            from ..resilience.retry import retry as _retry

            snapshot = {i: copy.deepcopy(models[i]) for i in unit_ids}
            # a cohort can fail after appending SOME members' history
            # records — roll info back too, or the policy sees phantom
            # rounds for the members that finished before the fault
            info_snapshot = {i: len(info[i]) for i in unit_ids}

            def rollback(exc, attempt):
                with self._fit_failures_lock:
                    self._fit_failures += len(unit_ids)
                for i in unit_ids:
                    models[i] = snapshot[i]
                    del info[i][info_snapshot[i]:]

            # a regular (stack) span: run_unit executes synchronously on
            # its thread (pool worker or, serialized, the loop thread),
            # so nested pipeline.stream spans parent here naturally
            hb = _supervisor.register(
                f"search-unit:{'-'.join(map(str, unit_ids))}", "search")
            try:
                with _obs.span("search.unit", parent=round_span["id"],
                               models=len(unit_ids), n_calls=n_calls):
                    hb.beat()
                    return _retry(
                        fn, first_arg, n_calls,
                        retries=0 if lockstep else 1,
                        backoff=0.0, jitter=0.0,
                        budget=self._fault_budget,
                        tag="search-unit", on_error=rollback,
                    )
            finally:
                hb.retire()

        # -- concurrent orchestrator unit bodies (design.md §17) ---------
        # These run ONLY on the blessed ``dask-ml-tpu-search`` loop
        # thread (_orchestrator.run_search): every device dispatch stays
        # on this one thread, staging rides the per-unit UnitStream
        # (prefetch worker / pool threads, host-only), and units yield
        # between block dispatches so sibling units — and sibling
        # Hyperband brackets on the same loop — keep the device fed.

        async def _drive_stream(sched, stream):
            """Interleaved consume loop of one unit's staged feed:
            await the next staged block off-thread, take a dispatch
            turn (graftscope in-flight throttle), dispatch."""
            try:
                while True:
                    item = await sched.stage(stream.next_staged)
                    if item is stream.DONE:
                        return
                    await sched.turn()
                    stream.consume(item)
            finally:
                stream.close()

        def _unit_stream(sched, consumer, blocks_iter, unit_span):
            from ..pipeline import UnitStream
            from ..resilience.elastic import ElasticPolicy

            return UnitStream(
                consumer, blocks_iter, depth=prefetch_depth,
                fit_kwargs=fit_params, label="search_ingest",
                # burst recovery draws from the fit-wide budget
                elastic=ElasticPolicy(budget=self._fault_budget,
                                      label="search_ingest"),
                parent_span=unit_span)

        async def _single_body(sched, ident, n_calls, unit_span):
            model, meta = models[ident]
            calls0 = meta["partial_fit_calls"]
            _warm_unit(model, calls0, n_calls)
            t0 = time.time()
            if n_calls > 0 and hasattr(model, "_pf_stage") \
                    and hasattr(model, "_pf_consume"):
                # NO _san.region here, unlike train_one: regions are a
                # thread-local STACK, and interleaved unit coroutines
                # on the one dispatcher thread would cross-attribute
                # and corrupt it (the detached-span problem, which
                # regions don't solve) — orchestrated units attribute
                # at the scope level instead
                await _drive_stream(sched, _unit_stream(
                    sched, model,
                    (block_for(model, (calls0 + j) % n_blocks)
                     for j in range(n_calls)),
                    unit_span))
                meta = dict(meta)
                meta["partial_fit_calls"] += n_calls
                # train_one semantics: partial_fit_time is ONE call's
                # duration — amortize the streamed burst over its calls
                meta["partial_fit_time"] = \
                    (time.time() - t0) / max(n_calls, 1)
            else:
                for _ in range(n_calls):
                    await sched.turn()
                    block_idx = meta["partial_fit_calls"] % n_blocks
                    Xb, yb = block_for(model, block_idx)
                    model, meta = _partial_fit(
                        (model, meta), Xb, yb, fit_params
                    )
            await sched.turn()  # the score is a dispatch + fetch too
            meta = _score((model, meta), X_test, y_test, scorer)
            meta["elapsed_wall_time"] = time.time() - start_time
            models[ident] = (model, meta)
            info[ident].append(meta)
            return meta

        async def _cohort_body(sched, idents, n_calls, unit_span):
            from ._packing import Cohort

            cohort = Cohort(
                [models[i][0] for i in idents],
                classes=(fit_params or {}).get("classes"),
            )
            calls0 = models[idents[0]][1]["partial_fit_calls"]
            t0 = time.time()
            # no _san.region: see _single_body (thread-local stack vs
            # interleaved coroutines)
            await _drive_stream(sched, _unit_stream(
                sched, cohort,
                (blocks[(calls0 + j) % n_blocks]
                 for j in range(n_calls)),
                unit_span))
            t_fit_end = time.time()  # scoring must not inflate pf_time
            await sched.turn()
            packed_scores, packed_score_time = _score_cohort(cohort, idents)
            cohort.finalize()
            pf_time = (t_fit_end - t0) / max(n_calls * len(idents), 1)
            _finish_cohort(idents, n_calls, pf_time, packed_scores,
                           packed_score_time)

        async def run_unit_async(sched, body_factory, unit_ids, n_calls):
            """Async twin of :func:`run_unit`: the same round-start
            snapshot rollback, the same ``search-unit`` fault books and
            fit-wide :class:`FaultBudget` draw, the same supervisor
            heartbeat — but a failed unit REQUEUES (re-enters this
            round's gather after yielding) instead of stalling its
            siblings while it recovers.  One requeue; a second failure
            propagates loudly, exactly the sync contract.

            The bookkeeping below deliberately mirrors
            :func:`resilience.retry.retry` (retries=1, no backoff) —
            an awaitable body cannot ride the sync primitive.  The
            parity contract (faults == retries + failures per tag,
            budget drawn only when a retry is scheduled, retry/failure
            obs events) is PINNED by tests/test_search_orchestrator.py
            ::TestFaultParity against the same assertions
            tests/test_fault_injection.py holds the sync path to — a
            change to the shared primitive's accounting must update
            both or those tests disagree."""
            import copy

            from ..resilience import supervisor as _supervisor
            from ..resilience.retry import fault_stats as _fault_stats

            snapshot = {i: copy.deepcopy(models[i]) for i in unit_ids}
            info_snapshot = {i: len(info[i]) for i in unit_ids}
            stats = _fault_stats()
            hb = _supervisor.register(
                f"search-unit:{'-'.join(map(str, unit_ids))}", "search")
            attempt = 0
            try:
                while True:
                    try:
                        # a DETACHED span: interleaved units on one loop
                        # thread must never stack-parent (design.md §11)
                        with _obs.span("search.unit",
                                       parent=round_span["id"],
                                       detached=True,
                                       models=len(unit_ids),
                                       n_calls=n_calls,
                                       prefix=self.prefix) as us:
                            hb.beat()
                            return await body_factory(
                                us.span_id or round_span["id"])
                    except Exception as exc:
                        stats.record_fault("search-unit")
                        with self._fit_failures_lock:
                            self._fit_failures += len(unit_ids)
                        for i in unit_ids:
                            models[i] = snapshot[i]
                            del info[i][info_snapshot[i]:]
                        if attempt >= 1 or \
                                not self._fault_budget.acquire(
                                    "search-unit"):
                            stats.record_failure("search-unit")
                            _obs.event("resilience.failure",
                                       tag="search-unit", attempt=attempt,
                                       error=_obs.fmt_exc(exc))
                            raise
                        stats.record_retry("search-unit")
                        _obs.event("resilience.retry", tag="search-unit",
                                   attempt=attempt,
                                   error=_obs.fmt_exc(exc))
                        sched.note_requeue()
                        attempt += 1
                        await asyncio.sleep(0)  # requeue: siblings first
            finally:
                hb.retire()

        async def run_round(instructions):
            """Fan this round's training units over the shared thread pool
            so independent models — and, above us, concurrent Hyperband
            brackets on the same event loop — overlap in WALL CLOCK, not
            just cooperatively (reference: the futures plane gets this from
            the cluster; host sklearn fits release the GIL in C kernels and
            device fits overlap via JAX async dispatch).

            On the orchestrated path (this coroutine running on the
            blessed ``dask-ml-tpu-search`` loop — see
            :mod:`._orchestrator`) device units instead become
            coroutines interleaved at BLOCK granularity on this one
            dispatch thread: while one unit's step program runs, the
            next unit's staged block dispatches and further units'
            blocks parse + H2D-stage on the host workers."""
            from . import _orchestrator as _orch

            loop = asyncio.get_running_loop()
            pool = _train_executor()
            packed, singles = pack_groups(instructions)
            sched = _orch.current_scheduler()
            if sched is not None:
                coros = [
                    run_unit_async(
                        sched,
                        lambda us, idents=list(idents), n=n_calls:
                            _cohort_body(sched, idents, n, us),
                        list(idents), n_calls)
                    for (key, n_calls, _), idents in
                    sorted(packed.items(), key=lambda kv: repr(kv[0]))
                ]
                coros += [
                    run_unit_async(
                        sched,
                        lambda us, ident=ident, n=n_calls:
                            _single_body(sched, ident, n, us),
                        [ident], n_calls)
                    for ident, n_calls in sorted(singles)
                ]
                if coros:
                    await asyncio.gather(*coros)
                return
            # mesh scoping is thread-local: re-establish the CALLER's mesh
            # inside each worker so device-native fits keep the fleet/user
            # mesh instead of falling back to the all-devices default
            from ..core.mesh import get_mesh, use_mesh

            mesh = get_mesh()

            def on_mesh(fn, *args):
                with use_mesh(mesh):
                    return fn(*args)

            # serialize_units (computed above): the round's units run
            # sequentially in a deterministic order (sorted pack keys,
            # then sorted single idents) instead of racing on the thread
            # pool — cross-process, collectives emitted from
            # thread-scheduled units would interleave differently per
            # process and deadlock the fleet; single-process, device
            # units interleaving multi-device enqueues deadlock the
            # runtime the same way
            packed_items = sorted(packed.items(), key=lambda kv: repr(kv[0]))
            singles_items = sorted(singles)
            if serialize_units:
                for (key, n_calls, _), idents in packed_items:
                    on_mesh(run_unit, train_cohort, list(idents), idents,
                            n_calls)
                for ident, n_calls in singles_items:
                    on_mesh(run_unit, train_one, [ident], ident, n_calls)
                return

            futs = [
                loop.run_in_executor(
                    pool, on_mesh, run_unit, train_cohort, list(idents),
                    idents, n_calls,
                )
                for (key, n_calls, _), idents in packed_items
            ]
            futs += [
                loop.run_in_executor(
                    pool, on_mesh, run_unit, train_one, [ident], ident,
                    n_calls,
                )
                for ident, n_calls in singles_items
            ]
            if futs:
                await asyncio.gather(*futs)

        def _record_round(t0_round: float) -> None:
            # per-round latency feeds the `search.round_s` histogram
            # (p50/p99 round latency under search load, design.md §17)
            _obs.registry().histogram("search.round_s").record(
                time.perf_counter() - t0_round)

        # initial round: one call each (skipped when resuming — the
        # snapshot already contains at least the initial round)
        if not resumed:
            t0_round = time.perf_counter()
            with _obs.span("search.round", parent=fit_parent,
                           detached=True, round=0,
                           models=len(models)) as rs:
                round_span["id"] = rs.span_id or fit_parent
                await run_round({ident: 1 for ident in models})
            _record_round(t0_round)
            if ckpt is not None:
                ckpt.save(models, info, self._capture_policy_state(),
                          elapsed=time.time() - start_time)

        # adaptive loop — an EMPTY dict stops the search; zero-valued
        # instructions keep a model alive without training (the policy's
        # internal step counter advances, reference semantics)
        round_no = 0
        while True:
            instructions = self._filter_plateaued(
                info, self._additional_calls(dict(info))
            )
            if self.verbose:
                # the reference logs each adaptive decision; mirror with
                # one INFO line per round (policy output + current best)
                best = max(
                    (recs[-1]["score"] for recs in info.values()),
                    default=float("nan"),
                )
                active = sum(1 for v in instructions.values() if v > 0)
                logger.info(
                    "%s[round %d] %d/%d models continue, best score %.4f",
                    self.prefix, round_no, active, len(info), best,
                )
            if not instructions:
                break
            round_no += 1
            t0_round = time.perf_counter()
            with _obs.span("search.round", parent=fit_parent,
                           detached=True, round=round_no,
                           models=sum(1 for v in instructions.values()
                                      if v > 0)) as rs:
                round_span["id"] = rs.span_id or fit_parent
                await run_round(instructions)
            _record_round(t0_round)
            if ckpt is not None:
                ckpt.save(models, info, self._capture_policy_state(),
                          elapsed=time.time() - start_time)

        if ckpt is not None:
            ckpt.complete()
        return models, dict(info)

    def _process_results(self, models, info):
        best_id = max(
            info, key=lambda ident: info[ident][-1]["score"]
        )
        best_model, best_meta = models[best_id]
        self.best_estimator_ = best_model
        self.best_index_ = int(best_id)
        self.best_score_ = best_meta["score"]
        self.best_params_ = best_meta["params"]

        self.history_ = sorted(
            (rec for recs in info.values() for rec in recs),
            key=lambda r: (r["elapsed_wall_time"], r["model_id"]),
        )
        self.model_history_ = {k: list(v) for k, v in info.items()}

        cv_results = {
            "model_id": [], "params": [], "test_score": [],
            "partial_fit_calls": [],
        }
        for ident, recs in sorted(info.items()):
            last = recs[-1]
            cv_results["model_id"].append(ident)
            cv_results["params"].append(last["params"])
            cv_results["test_score"].append(last["score"])
            cv_results["partial_fit_calls"].append(last["partial_fit_calls"])
        keys = {k for rec in cv_results["params"] for k in rec}
        for k in sorted(keys):
            cv_results[f"param_{k}"] = [p.get(k) for p in cv_results["params"]]
        ranks = np.argsort(np.argsort(-np.asarray(cv_results["test_score"]))) + 1
        cv_results["rank_test_score"] = ranks.tolist()
        self.cv_results_ = cv_results
        self.n_models_ = len(info)
        # observability for the fault-recovery path: how many training
        # units were retried from their round-start snapshot this fit
        self.fit_failures_ = getattr(self, "_fit_failures", 0)
        return self

    def fit(self, X, y=None, **fit_params):
        from . import _orchestrator as _orch

        X_train, X_test, y_train, y_test = self._split(X, y)
        # the search loop blocks this thread either way (asyncio.run
        # here, or a join on the blessed orchestrator thread), so a
        # regular stack span is the whole-search root; the coroutine's
        # detached round spans parent under it via fit_parent (see
        # _fit — run_search's adopt() carries the id across the hop)
        with _obs.span("search.fit", search=type(self).__qualname__):
            models, info = _orch.run_search(
                lambda: self._fit(X_train, y_train, X_test, y_test,
                                  **fit_params),
                threaded=_orch.device_concurrency(self.estimator),
            )
        return self._process_results(models, info)

    def _split(self, X, y):
        if y is None:
            raise ValueError(
                "y is required: incremental searches score models on a "
                "held-out (X_test, y_test) split"
            )
        test_size = self.test_size if self.test_size is not None else 0.15
        X_train, X_test, y_train, y_test = train_test_split(
            X, y, test_size=test_size, random_state=self.random_state
        )
        device_scoring_ok = self.scoring is None or isinstance(
            self.scoring, str
        )  # registry scorers are ShardedRows-aware; user callables may not be
        if not (isinstance(self.estimator, TPUEstimator)
                and device_scoring_ok):
            # host (sklearn) models score host arrays; device models keep
            # the held-out split SHARDED — unsharding here would pull it
            # to host once and re-upload it at every scoring round
            X_test = (
                unshard(X_test) if isinstance(X_test, ShardedRows) else X_test
            )
            y_test = (
                unshard(y_test) if isinstance(y_test, ShardedRows) else y_test
            )
        return X_train, X_test, y_train, y_test

    # -- inference forwards to the winner ------------------------------
    def predict(self, X):
        return self.best_estimator_.predict(
            unshard(X) if isinstance(X, ShardedRows) else X
        )

    def predict_proba(self, X):
        return self.best_estimator_.predict_proba(
            unshard(X) if isinstance(X, ShardedRows) else X
        )

    def transform(self, X):
        return self.best_estimator_.transform(
            unshard(X) if isinstance(X, ShardedRows) else X
        )

    def score(self, X, y=None):
        scorer = check_scoring(self.estimator, self.scoring)
        return scorer(
            self.best_estimator_,
            unshard(X) if isinstance(X, ShardedRows) else X,
            unshard(y) if isinstance(y, ShardedRows) else y,
        )


class IncrementalSearchCV(BaseIncrementalSearchCV):
    """Train many models incrementally; stop each when its score plateaus.

    Reference: ``_incremental.py :: IncrementalSearchCV`` (``patience``,
    ``tol``, ``max_iter``, ``fits_per_score``); with ``patience`` False the
    policy trains every model to ``max_iter``.
    """

    def _additional_calls(self, info):
        # plateau stopping (patience/tol) is the base fit loop's
        # _filter_plateaued post-pass, shared with SHA/Hyperband
        out = {}
        for ident, recs in info.items():
            calls = recs[-1]["partial_fit_calls"]
            if calls >= self.max_iter:
                continue
            out[ident] = min(self.fits_per_score, self.max_iter - calls)
        return out


class InverseDecaySearchCV(BaseIncrementalSearchCV):
    """Keep n_models ∝ 1/(1+k) of the initial population each round.

    Reference: ``_incremental.py :: InverseDecaySearchCV`` (decay_rate).
    """

    _policy_state_attrs = ("_step",)

    def __init__(self, estimator, parameters, n_initial_parameters=10,
                 test_size=None, random_state=None, scoring=None,
                 max_iter=100, patience=False, tol=1e-3, fits_per_score=1,
                 decay_rate=1.0, verbose=False, prefix="", chunk_size=None,
                 checkpoint=None):
        self.decay_rate = decay_rate
        super().__init__(
            estimator, parameters,
            n_initial_parameters=n_initial_parameters, test_size=test_size,
            random_state=random_state, scoring=scoring, max_iter=max_iter,
            patience=patience, tol=tol, fits_per_score=fits_per_score,
            verbose=verbose, prefix=prefix, chunk_size=chunk_size,
            checkpoint=checkpoint,
        )
        self._step = 1

    def _reset_policy(self):
        self._step = 1

    def _additional_calls(self, info):
        n_initial = len(info)
        keep = max(1, int(np.ceil(n_initial / (1 + self._step) ** self.decay_rate)))
        by_score = sorted(
            info, key=lambda ident: info[ident][-1]["score"], reverse=True
        )
        survivors = by_score[:keep]
        self._step += 1
        out = {}
        for ident in survivors:
            calls = info[ident][-1]["partial_fit_calls"]
            if calls < self.max_iter:
                out[ident] = min(self.fits_per_score, self.max_iter - calls)
        return out
