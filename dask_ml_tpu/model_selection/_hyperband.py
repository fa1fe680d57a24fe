"""HyperbandSearchCV.

Reference: ``dask_ml/model_selection/_hyperband.py`` — computes the
Hyperband bracket schedule from ``max_iter`` (+``aggressiveness``),
instantiates one SuccessiveHalvingSearchCV per bracket, runs ALL brackets
concurrently on one event loop, and exposes ``metadata``/``metadata_``
(``n_models``, ``partial_fit_calls`` per bracket) — SURVEY.md §3.3.

``sequential_brackets=True`` runs one bracket at a time instead — with the
per-round lockstep dispatch in ``_incremental.run_round``, the
multi-controller-legal form for a multi-process (multi-host) mesh, where
thread-concurrent brackets would emit collectives in different orders on
different processes and deadlock (``core/distributed.py``).  Concurrent
brackets on a multi-process group are rejected with a clear error.

Single-process, concurrent brackets now run on the TRUE concurrent
control plane (``_orchestrator.py``, design.md §17): all brackets share
one event loop hosted on the blessed ``dask-ml-tpu-search`` dispatch
thread, their units interleave at block granularity (one bracket's
staged block dispatches while another's program runs and a third's
block H2D-stages on the host workers), and homogeneous survivors
re-pack into vmapped cohorts after every halving round.  This closes
the single-controller sequentialization bound round 5 accepted as a
"known asterisk" (no chip reading exists: PERF.md section 7 row 9).
``DASK_ML_TPU_SEARCH_CONCURRENCY=off`` restores the
serialized round loop exactly.
"""

from __future__ import annotations

import asyncio
import logging
import math

import numpy as np

from .. import obs as _obs
from ._incremental import BaseIncrementalSearchCV
from ._successive_halving import SuccessiveHalvingSearchCV

logger = logging.getLogger(__name__)


def _get_hyperband_params(R, eta=3):
    """Bracket schedule (Li et al. 2016, alg. 1): list of (bracket, n, r).

    Reference symbol: ``_hyperband.py :: _get_hyperband_params``.
    """
    s_max = int(math.floor(math.log(R) / math.log(eta)))
    B = (s_max + 1) * R
    out = []
    for s in range(s_max, -1, -1):
        n = int(math.ceil(B / R * eta ** s / (s + 1)))
        r = int(R * eta ** -s)
        out.append((s, n, max(r, 1)))
    return out


def _simulate_sha_calls(n, r, R, eta):
    """Total partial_fit calls an (n, r) SHA bracket will make, mirroring
    SuccessiveHalvingSearchCV's policy (initial 1-call round + adapt loop)."""
    calls = {i: 1 for i in range(n)}  # initial round: one call each
    total = n
    steps = 0
    while True:
        n_i = int(math.floor(n * eta ** -steps))
        raw_target = int(round(r * eta ** steps))
        r_i = min(raw_target, R)
        steps += 1
        survivors = sorted(calls)[: max(n_i, 1)]
        if len(survivors) in (0, 1) and steps > 1:
            # the EXECUTED policy keeps escalating the final survivor's
            # rung (r_i × eta per round, capped at R) until it holds the
            # full budget — so the survivor always ends at exactly R
            # calls, not at the current rung (property-test find at
            # R=3, eta=2: brackets whose pool shrinks to 1 BEFORE the
            # rung ladder reaches R under-predicted by the difference)
            for ident in survivors:
                total += max(0, R - calls[ident])
            break
        added = 0
        for ident in survivors:
            more = max(0, r_i - calls[ident])
            calls[ident] += more
            added += more
        total += added
        if added == 0 and raw_target >= R:
            break  # every survivor at the max_iter budget
        calls = {i: calls[i] for i in survivors}
    return total


class HyperbandSearchCV(BaseIncrementalSearchCV):
    def __init__(self, estimator, parameters, max_iter=81, aggressiveness=3,
                 test_size=None, random_state=None, scoring=None,
                 patience=False, tol=1e-3, verbose=False, prefix="",
                 chunk_size=None, checkpoint=None,
                 sequential_brackets=False):
        self.max_iter = max_iter
        self.aggressiveness = aggressiveness
        self.sequential_brackets = sequential_brackets
        super().__init__(
            estimator, parameters, test_size=test_size,
            random_state=random_state, scoring=scoring, max_iter=max_iter,
            patience=patience, tol=tol, verbose=verbose, prefix=prefix,
            chunk_size=chunk_size, checkpoint=checkpoint,
        )

    # -- schedule ------------------------------------------------------
    @property
    def metadata(self):
        """Theoretical budget before fitting (reference ``metadata``)."""
        brackets = []
        n_models = 0
        total_calls = 0
        for s, n, r in _get_hyperband_params(self.max_iter, self.aggressiveness):
            calls = _simulate_sha_calls(n, r, self.max_iter, self.aggressiveness)
            brackets.append(
                {"bracket": s, "n_models": n, "partial_fit_calls": calls}
            )
            n_models += n
            total_calls += calls
        return {
            "n_models": n_models,
            "partial_fit_calls": total_calls,
            "brackets": brackets,
        }

    def _make_brackets(self):
        import os

        brackets = []
        rng_seed = self.random_state
        for s, n, r in _get_hyperband_params(self.max_iter, self.aggressiveness):
            seed = None if rng_seed is None else int(rng_seed) + s
            # each bracket checkpoints independently: a restart resumes
            # every bracket from its own last completed round
            ckpt = (
                os.path.join(str(self.checkpoint), f"bracket{s}.pkl")
                if self.checkpoint
                else None
            )
            sha = SuccessiveHalvingSearchCV(
                self.estimator, self.parameters,
                n_initial_parameters=n, n_initial_iter=r,
                max_iter=self.max_iter, aggressiveness=self.aggressiveness,
                test_size=self.test_size, random_state=seed,
                scoring=self.scoring, prefix=f"{self.prefix}bracket={s}",
                chunk_size=self.chunk_size, checkpoint=ckpt,
                patience=self.patience, tol=self.tol, verbose=self.verbose,
            )
            # a finished bracket KEEPS its final snapshot until the whole
            # Hyperband fit completes: a crash in bracket k must not force
            # brackets 0..k-1 to retrain (their restored policies replay
            # as an immediate no-op round)
            sha._ckpt_keep_on_complete = True
            brackets.append((s, sha))
        return brackets

    def fit(self, X, y=None, **fit_params):
        import jax

        if jax.process_count() > 1 and not self.sequential_brackets:
            raise ValueError(
                "concurrent Hyperband brackets interleave collectives "
                "nondeterministically across processes and would deadlock "
                "a multi-process mesh; pass sequential_brackets=True "
                "(see core/distributed.py)"
            )
        X_train, X_test, y_train, y_test = self._split(X, y)
        brackets = self._make_brackets()

        # span tree (design.md §11): one regular root span for the whole
        # Hyperband fit; each bracket is a DETACHED child (brackets
        # interleave as coroutines on this thread, so stack parentage
        # would cross-link them), and each bracket hands its span id to
        # its SHA so that SHA's round/unit spans nest under the bracket
        hb_span = _obs.span("search.fit",
                            search=type(self).__qualname__,
                            brackets=len(brackets))

        async def bracket_fit(s, sha):
            with _obs.span("search.bracket", parent=hb_span.span_id,
                           detached=True, bracket=s) as bs:
                sha._obs_parent = bs.span_id or hb_span.span_id
                return await sha._fit(
                    X_train, y_train, X_test, y_test, **fit_params
                )

        async def run_all():
            if self.sequential_brackets:
                # one bracket at a time (coroutines created LAZILY so a
                # failing bracket leaves no never-awaited coroutines);
                # with run_round's lockstep dispatch each bracket issues
                # identical collectives on every process
                return [await bracket_fit(s, sha) for s, sha in brackets]
            return await asyncio.gather(
                *[bracket_fit(s, sha) for s, sha in brackets]
            )

        from . import _orchestrator as _orch

        with hb_span:
            # device estimators: the whole multi-bracket loop runs on
            # the blessed orchestrator thread — every bracket's device
            # work shares the ONE dispatch thread (design.md §17)
            results = _orch.run_search(
                run_all, threaded=_orch.device_concurrency(self.estimator))

        # merge results across brackets with globally unique model ids
        all_models, all_info = {}, {}
        meta_observed = []
        offset = 0
        for (s, sha), (models, info) in zip(brackets, results):
            meta_observed.append(
                {
                    "bracket": s,
                    "n_models": len(info),
                    "partial_fit_calls": sum(
                        recs[-1]["partial_fit_calls"] for recs in info.values()
                    ),
                }
            )
            for ident, recs in info.items():
                new_id = offset + ident
                all_info[new_id] = [
                    {**rec, "model_id": new_id, "bracket": s} for rec in recs
                ]
                all_models[new_id] = models[ident]
            offset += len(info)

        # fault-recovery accounting rolls up from the bracket SHAs (each
        # ran its own _fit with its own retry counter)
        self._fit_failures = sum(
            getattr(sha, "_fit_failures", 0) for _, sha in brackets
        )
        if self.checkpoint:
            # the whole fit finished: bracket snapshots (kept on bracket
            # completion for crash recovery) are no longer needed
            for _, sha in brackets:
                ck = sha._checkpointer()
                if ck is not None:
                    ck.complete(force=True)
        self._process_results(all_models, all_info)
        self.metadata_ = {
            "n_models": sum(m["n_models"] for m in meta_observed),
            "partial_fit_calls": sum(m["partial_fit_calls"] for m in meta_observed),
            "brackets": meta_observed,
        }
        return self
