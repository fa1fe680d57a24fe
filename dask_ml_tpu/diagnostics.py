"""Tracing / profiling utilities.

Reference posture (SURVEY.md §5): dask-ml keeps only a ``_timer`` phase
logger in-repo and delegates real profiling to the external dask dashboard
and ``dask.diagnostics``.  The TPU equivalents are XProf device traces
(``jax.profiler``) and a ``block_until_ready`` timing harness — thin, also
in-repo, so every estimator keeps the reference's pattern of named, timed
phases with zero heavyweight machinery.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

import jax

from .utils import _timer  # noqa: F401  (re-export: phase logging)

# fault observability (re-export): the process-global retry/fault
# counters live in resilience.retry; surfacing them here keeps one
# diagnostics namespace for "what happened during that fit" — timings,
# traces, AND absorbed/propagated faults (resilience faults must be
# observable, never silent)
from .resilience.retry import (  # noqa: F401
    FaultStats,
    fault_stats,
    reset_fault_stats,
)

# input-pipeline observability (re-export): the parse/transfer/compute
# stage split every streamed fit records (pipeline.stats) — the round-5
# verdict's "measure the disk->device bottleneck instead of asserting
# it", kept in the same "what happened during that fit" namespace
from .pipeline import (  # noqa: F401
    pipeline_report,
    reset_pipeline_stats,
)

# grafttrace (re-export): the unified span/metrics/flight spine the
# reporters above publish through (dask_ml_tpu/obs/, design.md §11) —
# run_report() below is its merged per-fit view
from . import obs  # noqa: F401
from .obs import (  # noqa: F401
    event,
    flight_dump,
    metrics_snapshot,
    span,
)

__all__ = [
    "trace", "benchmark_step", "benchmark_slope", "_timer",
    "FaultStats", "fault_stats", "reset_fault_stats", "fault_report",
    "pipeline_report", "reset_pipeline_stats",
    "lint_report", "sanitize_report", "program_report", "serve_report",
    "obs", "span", "event", "metrics_snapshot",
    "flight_dump", "run_report", "reset",
]


def fault_report() -> dict:
    """The elastic fault-domain runtime's books (design.md §13), next to
    :func:`fault_stats`'s raw counters::

        {"faults":    {faults, retries, failures}   # fault_stats view
         "budgets":   {name: {spent, denied, remaining}},
         "backoff_s": {tag: total_sleep_seconds},
         "degraded_skips": {stream_label: n},
         "supervisor": {domain: {units, late, dead, beats,
                                 deaths, restarts}}}

    Everything is registry-backed (``resilience.budget_*``,
    ``resilience.backoff_s``, ``resilience.degraded_skip``,
    ``supervisor.*``) so the same numbers appear in
    :func:`run_report`'s metrics snapshot and survive the owning
    objects — a finished fit's budget consumption stays reportable.
    """
    from .resilience import supervisor as _supervisor
    from .resilience.elastic import budget_report

    reg = obs.registry()
    snap = reg.snapshot()
    backoff = {}
    for key, h in snap.get("histograms", {}).items():
        if key.startswith("resilience.backoff_s"):
            tag = key[len("resilience.backoff_s"):].strip("{}")
            backoff[tag or ""] = h.get("sum", 0.0)
    return {
        "faults": fault_stats().snapshot(),
        "budgets": budget_report(),
        "backoff_s": backoff,
        "degraded_skips": reg.family("resilience.degraded_skip"),
        "supervisor": _supervisor.report(),
    }


def program_report() -> dict:
    """The central compiled-program cache's books, next to
    :func:`pipeline_report` (design.md §12)::

        {"programs": {name: {hits, misses, ahead_hits, ahead_submitted,
                             bypass, fallback, compile_s,
                             ahead_compile_s, saved_s, wait_s,
                             programs, inflight}},
         "totals": {...same keys summed...},
         "bucket": {blocks, padded_blocks, pad_rows},
         "persistent_cache": dir_or_None}

    ``saved_s`` is the compile wall time the blessed compile-ahead
    thread hid from consumers (ahead-compiled programs that were
    subsequently hit); ``bucket`` is the shape-bucketing pad split
    (``padded_blocks == 0`` means every reader emitted bucket-sized
    chunks — the no-op fast path).  Reset with
    :func:`dask_ml_tpu.programs.reset_counters` (compiled executables
    are kept)."""
    from . import programs

    return programs.report()


def serve_report() -> dict:
    """The online inference plane's books (design.md §15)::

        {"servers": [{label, alive, queued, budget, residency, ...}],
         "metrics": {"serve.request_s{model}": {p50, p95, p99, ...},
                     "serve.rejected{reason}": n, ...}}

    Per-model request latency quantiles (queue wait included — the
    client's number), queue-wait and batch-occupancy histograms,
    rejections by reason, and each live server's residency/budget
    state.  The same ``serve.*`` registry families export through the
    live ``/metrics`` endpoint."""
    from . import serve

    return serve.report()


def run_report() -> dict:
    """The merged "what happened, in order, during THAT fit" view.

    One dict over the whole observability spine:

    * ``span_tree`` — the most recent ROOT span (the last whole
      fit/stream/search) assembled as a nested tree: pipeline stage
      children (parse/stage/compute, prefetch-worker spans stitched
      in), search rounds/units, with retry/checkpoint/violation events
      attached to the spans they occurred under.  ``None`` when tracing
      is disabled or nothing has completed.
    * ``critical_path`` — graftpath's causal join of that root with the
      graftscope device timeline and the queue-wait signals
      (design.md §19): parse/stage/queue-wait/dispatch/device/fetch/
      idle category seconds summing to the wall within
      ``DASK_ML_TPU_CRITICAL_TOL``, overlap efficiency, and the
      bottleneck verdict with its evidence chain.  Falls back to the
      serve window's per-request queue/window/device/fetch split when
      no root span exists.
    * ``metrics`` — the registry snapshot: counters, gauges, and
      histograms with p50/p95/p99 (``pipeline.block_s``,
      ``compile.duration_s``, ...).
    * ``device`` — graftscope's occupancy view (design.md §14):
      per-program dispatches + busy seconds, utilization over the
      device window, idle seconds, and the top-3 idle gaps — the
      device-side half of the host stage split next to it.  The read
      settles briefly (≤1 s) so a just-finished fit's last in-flight
      program closes its interval.
    * ``pipeline`` / ``faults`` / ``sanitize`` / ``serve`` — the
      per-plane reporters, unchanged shapes (views over the same
      registry).

    Call :func:`reset` first to scope the report to one fit; to see its
    host spans against the real device lanes, run it under
    :func:`trace`.
    """
    resilience = fault_report()
    # graftpath AFTER the settled device read below would re-settle;
    # compute it first on its own settle so the last in-flight program
    # closes before the window is attributed
    obs.scope.settle(1.0)
    return {
        "schema": obs.SCHEMA_VERSION,
        "span_tree": obs.span_tree(),
        # the causal critical path of the most recent root (fit/search),
        # falling back to the serve window when no root exists —
        # categories sum to wall within the documented tolerance and
        # the bottleneck verdict carries its evidence (design.md §19)
        "critical_path": obs.critical_path(),
        "metrics": obs.metrics_snapshot(),
        "device": obs.scope.device_report(settle_s=1.0),
        "pipeline": pipeline_report(),
        # the legacy top-level key IS the resilience view's snapshot —
        # one read, so the two can never disagree mid-call
        "faults": resilience["faults"],
        "resilience": resilience,
        "sanitize": sanitize_report(),
        "serve": serve_report(),
    }


def reset() -> None:
    """One-call observability reset: fault stats, pipeline stats, the
    metrics registry, the span rings, the flight recorder, and the
    graftscope device timeline — the test/bench isolation idiom
    (replaces hand-chained ``reset_fault_stats()`` +
    ``reset_pipeline_stats()`` calls).  The live metrics endpoint and
    the graftscope sampler survive a reset: their books zero, and
    their supervisor heartbeats re-register immediately below (the
    unit-table wipe must not orphan a unit that is still serving)."""
    obs.reset_all()
    # the legacy reporters' registry families are already gone; these
    # clear their residual module state (the last-stream slot; private
    # books if the global stats object was ever swapped out; the
    # supervisor's registered-unit table)
    reset_fault_stats()
    reset_pipeline_stats()
    from .resilience import supervisor as _supervisor

    _supervisor.reset()
    obs.serve.rearm()
    obs.scope.rearm()


def sanitize_report() -> dict | None:
    """The graftsan runtime-sanitizer counters, next to
    :func:`pipeline_report`'s stage split: per-region compile / dispatch
    / d2h-sync counters, violations, allow-site passes, and the
    dispatching thread set.

    Returns the ACTIVE sanitizer's live report when one is open (inside
    a ``sanitize.sanitize()`` scope or a ``DASK_ML_TPU_SANITIZE=1``
    ambient stream), else the report of the most recently completed
    scope, else None (no sanitizer has run in this process).  See
    :mod:`dask_ml_tpu.sanitize` for the detector semantics and
    ``tools/sanitize_baseline.json`` for the committed per-workload
    contract these counters are ratcheted against.
    """
    from . import sanitize as _san

    s = _san.active_sanitizer()
    if s is not None:
        return s.report()
    return _san.last_report()


def lint_report(paths=None, baseline="auto") -> dict:
    """Per-rule graftlint finding counts for benches and CI trending.

    Runs the repo's static analyzer (:mod:`dask_ml_tpu.analysis`) over
    ``paths`` (default: this installed package) and returns::

        {"counts": {rule_id: {"active": n, "suppressed": m}},
         "active": total_active, "suppressed": total_suppressed,
         "errors": [parse errors],
         "baseline": {"path": ..., "new": n, "fixed": m,
                      "per_rule": {rule_id: {"new": x, "fixed": y}}}}

    ``active`` must trend to (and stay at) zero — tier-1 gates on it via
    tests/test_graftlint.py; ``suppressed`` is the debt metric to trend
    down release over release.  The ``baseline`` block is the per-PR
    delta vs the committed ratchet snapshot — what CHANGES/bench tooling
    trends ("this PR removed two suppressions, added none").
    ``baseline="auto"`` finds the committed snapshot next to a repo
    checkout (``tools/graftlint_baseline.json``); pass a path to pin it
    or ``None`` to skip; the block is ``None`` when no snapshot exists.
    """
    import os

    from . import analysis

    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    if paths is None:
        paths = [pkg_dir]
    # cache=True: trending callers re-lint an unchanged tree constantly;
    # the digest-keyed cache makes that free and can never serve stale
    # results (any source edit changes the digest)
    findings, errors = analysis.lint_paths(paths, cache=True)
    counts = analysis.per_rule_counts(findings)
    if baseline == "auto":
        cand = os.path.join(os.path.dirname(pkg_dir), "tools",
                            "graftlint_baseline.json")
        baseline = cand if os.path.isfile(cand) else None
    delta_block = None
    if baseline is not None:
        try:
            snap = analysis.baseline.load(baseline)
        except (OSError, ValueError):
            snap = None
        if snap is not None:
            root = analysis.baseline.baseline_root(paths)
            try:
                delta = analysis.baseline.compare(snap, findings, root)
            except ValueError:
                # scope mismatch (an auto-discovered baseline vs
                # explicit non-package paths): no comparable snapshot,
                # report no delta rather than crash a trending call
                snap = None
        if snap is not None:
            per_rule: dict = {}
            for f in delta["new"]:
                per_rule.setdefault(f.rule, {"new": 0, "fixed": 0})
                per_rule[f.rule]["new"] += 1
            for e in delta["fixed"]:
                per_rule.setdefault(e["rule"], {"new": 0, "fixed": 0})
                per_rule[e["rule"]]["fixed"] += 1
            delta_block = {
                "path": baseline,
                "new": len(delta["new"]),
                "fixed": len(delta["fixed"]),
                "per_rule": dict(sorted(per_rule.items())),
            }
    return {
        "counts": counts,
        "active": sum(c["active"] for c in counts.values()),
        "suppressed": sum(c["suppressed"] for c in counts.values()),
        "errors": list(errors),
        "baseline": delta_block,
    }


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XProf/TensorBoard device trace of the enclosed block.

    The TPU analogue of watching the distributed dashboard's task stream:
    ``with diagnostics.trace('/tmp/prof'): est.fit(X)`` then point
    TensorBoard (or xprof) at the directory.

    The one file holds the program's own spans too: while the session
    runs every ``obs.span`` is live and also a ``TraceAnnotation`` on the
    host plane (``glm.fit`` > ``glm.classes`` / ``glm.prepare`` /
    ``glm.solve``, each carrying the fit's id as ``fit``), on the clock
    of the device lanes.  Inside the solver programs the device
    operations are named by ``jax.named_scope``: ``admm.local_solve``,
    ``admm.consensus``, ``lbfgs.direction``, ``lbfgs.line_search``,
    ``lbfgs.update`` — read them in XProf's op names (the trace viewer,
    or the op profile's tree).

    Exception-safe: ``start_trace`` itself can raise (unwritable
    directory, a trace already active) — the stop only runs if the
    start succeeded, so the REAL error propagates instead of being
    masked by ``stop_trace`` complaining about a never-started trace.
    """
    started = False
    try:
        jax.profiler.start_trace(log_dir)
        started = True
        yield
    finally:
        if started:
            jax.profiler.stop_trace()


def benchmark_step(fn, *args, warmup: int = 1, iters: int = 10, **kwargs):
    """Time a jitted step function honestly (async dispatch flushed).

    Returns ``{"mean_s", "std_s", "min_s", "iters"}``.  The first
    ``warmup`` calls (compilation) are excluded; every timed call ends in
    ``block_until_ready`` so XLA's async dispatch cannot hide device
    time.  Each call still pays one dispatch — for per-iteration numbers
    free of that constant, time a CHAINED loop at two iteration counts
    and divide the difference (:func:`benchmark_slope`).
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    arr = np.asarray(times)
    return {
        "mean_s": float(arr.mean()),
        "std_s": float(arr.std()),
        "min_s": float(arr.min()),
        "iters": iters,
    }


def benchmark_slope(run, counts=(4, 24), reps: int = 3):
    """Per-iteration time via the slope method (the constant dispatch
    cost cancels).

    ``run(n)`` must execute n chained iterations (a traced-bound
    ``lax.fori_loop``/``scan``/``while_loop`` program) and wait for the
    result before returning.  Returns ``{"per_iter_s", "counts", "raw_s"}``.
    """
    lo, hi = counts
    run(hi)  # compile
    run(lo)  # a static-bound run(n) compiles per count: warm BOTH
    raw = {}
    for n in (lo, hi):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(n)
            best = min(best, time.perf_counter() - t0)
        raw[n] = best
    per = (raw[hi] - raw[lo]) / (hi - lo)
    if per <= 0:
        # a non-positive slope means the measurement is broken (noise
        # larger than the signal, or per-count recompilation): surface it
        # as NaN — a silent 0.0 reads as "infinitely fast"
        per = float("nan")
    return {
        "per_iter_s": per,
        "counts": (lo, hi),
        "raw_s": raw,
    }
