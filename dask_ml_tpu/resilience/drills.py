"""Chaos drill suite: every fault point proves its recovery path.

The runtime twin of the fault model table (docs/design.md §13): for
EVERY registered :data:`~dask_ml_tpu.resilience.testing.INJECTION_POINTS`
entry there is a drill that injects the fault into a real streamed fit
(SGD / MiniBatchKMeans / IncrementalPCA, prefetch depth 0 AND 2) and
asserts the three things recovery means here:

* **recovered** — the fit completes despite the fault (worker restart,
  staging replay, budgeted retry, checkpoint resume, degraded skip, or
  sink drop — whichever the fault domain's recovery path is);
* **model_match** — the recovered model equals the unfaulted twin's
  (same data, same order; the drills' paths are same-shape, so the
  match is near-bit-exact and ``max_rel_diff`` is recorded);
* **bounded retries** — the recovery spent no more re-attempts than
  the committed ceiling.

The suite exists to be *committed*: ``tools/drill_baseline.json``
snapshots each drill's metrics and the gate (``tools/lint.sh --drills``,
tests/test_drills.py in tier-1) re-runs the suite and ratchets against
the snapshot — same semantics as the graftlint/graftsan baselines
(new drill → fail, stale entry → fail, retry counts above ceiling →
fail) plus one coverage invariant: an injection point with NO drill
fails the suite, so a new fault point cannot ship without a recovery
drill.  ``recovered`` / ``model_match`` / ``steady_violations`` are
hard invariants a snapshot can never grandfather.

The two thread-death drills (prefetch-worker crash, compile-ahead
crash) run under an ARMED graftsan scope: recovery must not smuggle a
steady-state compile, transfer, or rogue dispatch past the sanitizer.

CLI (exit contract mirrors graftlint/graftsan: 0 clean, 1 failed,
2 the harness itself broke)::

    python -m dask_ml_tpu.resilience.drills
    python -m dask_ml_tpu.resilience.drills --baseline tools/drill_baseline.json
    python -m dask_ml_tpu.resilience.drills --write-baseline tools/drill_baseline.json
    python -m dask_ml_tpu.resilience.drills --drills ingest_retry_sgd_d0
"""

from __future__ import annotations

import errno
import json
import os

import numpy as np

from .elastic import ElasticPolicy
from .retry import fault_stats
from .retry import retry as _retry
from .testing import (FaultInjected, FaultPlan, ThreadCrash, fault_plan,
                      maybe_fault)
from .testing import INJECTION_POINTS

__all__ = [
    "BASELINE_ENV",
    "DRILLS",
    "run_drill",
    "run_suite",
    "compare",
    "default_baseline_path",
    "load_baseline",
    "write_baseline",
    "emit_baseline",
    "main",
]

#: which committed snapshot the suite ratchets against
BASELINE_ENV = "DASK_ML_TPU_DRILL_BASELINE"

_VERSION = 1
_SEED = 11
_BLOCKS = 6

#: per-drill metrics that must hold exactly, run AND snapshot — a
#: baseline can never grandfather a broken recovery path
HARD_INVARIANTS = ("recovered", "model_match")
HARD_ZEROS = ("steady_violations",)

#: per-drill metrics ratcheted as ceilings (run > snapshot fails)
RATCHETED_COUNTS = ("retries", "faults_injected", "degraded_skips")

#: model-equality bound: the drills replay identical blocks through
#: identical program shapes, so agreement is reassociation-tight
_MATCH_RTOL = 1e-5


# -- data / model helpers -------------------------------------------------

def _class_blocks(n=24, d=4, blocks=_BLOCKS, offset=0):
    rng = np.random.RandomState(_SEED + offset)
    out = []
    for _ in range(blocks):
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] + 0.1 * rng.normal(size=n) > 0).astype(np.int32)
        out.append((X, y))
    return out


def _row_blocks(n=16, d=4, blocks=_BLOCKS, offset=0):
    rng = np.random.RandomState(_SEED + offset)
    return [(rng.normal(size=(n, d)).astype(np.float32), None)
            for _ in range(blocks)]


class _RestartableBlocks:
    """A block source that survives its own parse faults: ``__next__``
    fires the given injection point BEFORE advancing, so a faulted pull
    re-serves the SAME block on retry — the contract
    ``restartable_source`` declares to the elastic driver (plain
    generators are finished by a raise; this is the opt-in shape the
    future dataset layer's readers will share)."""

    restartable_source = True

    def __init__(self, blocks, fire: str | None = None):
        self._blocks = list(blocks)
        self._fire = fire
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= len(self._blocks):
            raise StopIteration
        if self._fire:
            maybe_fault(self._fire)
        blk = self._blocks[self._i]
        self._i += 1
        return blk


def _model_vec(model) -> np.ndarray:
    parts = []
    for attr in ("coef_", "intercept_", "cluster_centers_", "components_",
                 "singular_values_"):
        v = getattr(model, attr, None)
        if v is not None:
            parts.append(np.asarray(v, dtype=np.float64).ravel())
    if not parts:
        raise ValueError(f"no comparable fitted attrs on {type(model)}")
    return np.concatenate(parts)


def _match(model, twin_vec) -> tuple[bool, float]:
    vec = _model_vec(model)
    if vec.shape != twin_vec.shape:
        return False, float("inf")
    denom = np.maximum(np.abs(twin_vec), 1e-12)
    rel = float(np.max(np.abs(vec - twin_vec) / denom)) if vec.size else 0.0
    return bool(np.allclose(vec, twin_vec, rtol=_MATCH_RTOL, atol=1e-12)), rel


def _fit_sgd(blocks, depth, *, elastic=None, on_block=None, model=None,
             label="drill_sgd"):
    from ..linear_model import SGDClassifier
    from ..pipeline import stream_partial_fit

    if model is None:
        model = SGDClassifier(random_state=0)
    stream_partial_fit(
        model, blocks, depth=depth,
        fit_kwargs={"classes": np.array([0, 1])},
        on_block=on_block, label=label, elastic=elastic,
    )
    return model


def _fit_mbk(blocks, depth, *, elastic=None, label="drill_mbk"):
    from ..cluster import MiniBatchKMeans
    from ..pipeline import stream_partial_fit

    model = MiniBatchKMeans(n_clusters=3, random_state=0)
    stream_partial_fit(model, blocks, depth=depth, label=label,
                       elastic=elastic)
    return model


def _fit_ipca(blocks, depth, *, elastic=None, label="drill_ipca"):
    from ..decomposition import IncrementalPCA
    from ..pipeline import stream_partial_fit

    model = IncrementalPCA(n_components=2)
    stream_partial_fit(model, blocks, depth=depth, label=label,
                       elastic=elastic)
    return model


_TWINS: dict = {}


def _twin(key: str, build) -> np.ndarray:
    """Unfaulted reference model vector, computed once per recipe (NO
    fault plan may be active — the twin defines 'correct')."""
    from .testing import active_plan

    assert active_plan() is None, "twin computed under an active plan"
    if key not in _TWINS:
        _TWINS[key] = _model_vec(build())
    return _TWINS[key]


class _EnvOverride:
    def __init__(self, **overrides):
        self._overrides = {k: v for k, v in overrides.items()}
        self._saved: dict = {}

    def __enter__(self):
        for k, v in self._overrides.items():
            self._saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        return self

    def __exit__(self, *exc):
        for k, v in self._saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        return False


# -- the drills -----------------------------------------------------------

DRILLS: dict = {}


def _drill_ingest_retry_sgd(depth, m):
    """Transient parse fault on a restartable source: the elastic driver
    re-pulls the SAME block (position not advanced) within the budget."""
    blocks = _class_blocks(offset=0)
    twin = _twin(f"sgd_d{depth}", lambda: _fit_sgd(list(blocks), depth))
    plan = FaultPlan().inject("ingest", at_call=3, times=1)
    src = _RestartableBlocks(blocks, fire="ingest")
    with fault_plan(plan):
        model = _fit_sgd(src, depth, label=f"drill_ingest_d{depth}")
    m["faults_injected"] = sum(plan.fired.values())
    m["recovered"] = True
    m["model_match"], m["max_rel_diff"] = _match(model, twin)


def _drill_stage_skip_ipca(depth, m):
    """Staging-poisoned block (post-parse H2D fault that persists):
    after its per-block retries the block is SKIPPED under the degraded
    knob, with an exact record — the model must equal a twin trained
    WITHOUT that block."""
    blocks = _row_blocks(offset=0)
    twin = _twin(
        f"ipca_skip2_d{depth}",
        lambda: _fit_ipca([b for i, b in enumerate(blocks) if i != 2],
                          depth))
    # block index 2 = stage arrivals 3 and 4 (original + one retry)
    plan = FaultPlan().inject("stage", at_call=(3, 4), times=2)
    policy = ElasticPolicy(degraded_blocks=1, block_retries=1,
                           label=f"drill_stage_skip_d{depth}")
    with fault_plan(plan):
        model = _fit_ipca(list(blocks), depth, elastic=policy,
                          label=f"drill_stage_skip_d{depth}")
    m["faults_injected"] = sum(plan.fired.values())
    m["degraded_skips"] = len(policy.skips)
    m["recovered"] = len(policy.skips) == 1 \
        and policy.skips[0]["block"] == 2
    m["model_match"], m["max_rel_diff"] = _match(model, twin)


def _drill_step_retry_mbk(depth, m):
    """Transient device-step fault: ``step_retries`` re-runs the SAME
    staged block (the step faults before mutating state), so the block
    trains exactly once and the model matches the unfaulted twin."""
    blocks = _row_blocks(offset=0)
    twin = _twin(f"mbk_d{depth}", lambda: _fit_mbk(list(blocks), depth))
    plan = FaultPlan().inject("step", at_call=3, times=1)
    policy = ElasticPolicy(step_retries=1,
                           label=f"drill_step_retry_d{depth}")
    with fault_plan(plan):
        model = _fit_mbk(list(blocks), depth, elastic=policy,
                         label=f"drill_step_retry_d{depth}")
    m["faults_injected"] = sum(plan.fired.values())
    m["recovered"] = True
    m["model_match"], m["max_rel_diff"] = _match(model, twin)


def _drill_step_ckpt_resume_ipca(depth, m):
    """Terminal step fault mid-fit + requeue from the last
    FitCheckpoint: the first fit dies at batch 3, the re-entered fit
    resumes from the snapshot (not from scratch) and must land on the
    unfaulted twin's model."""
    import shutil
    import tempfile

    from ..decomposition import IncrementalPCA
    from .fit_checkpoint import FitCheckpoint

    rng = np.random.RandomState(_SEED)
    X = rng.normal(size=(96, 4)).astype(np.float32)

    def _fresh(ckpt=None):
        return IncrementalPCA(n_components=2, batch_size=16,
                              fit_checkpoint=ckpt)

    twin = _twin(f"ipca_fit_d{depth}",
                 lambda: _model_vec_of_fit(_fresh(), X, depth))
    d = tempfile.mkdtemp(prefix="graftdrill-ckpt-")
    try:
        plan = FaultPlan().inject("step", at_call=3, times=1)
        with _EnvOverride(DASK_ML_TPU_PREFETCH_DEPTH=str(depth)):
            faulted = False
            try:
                with fault_plan(plan):
                    _fresh(FitCheckpoint(os.path.join(d, "ck"))).fit(X)
            except Exception:
                faulted = True
            # requeue: a fresh estimator with the same configuration
            # resumes from the snapshot the dead fit left behind
            ck = FitCheckpoint(os.path.join(d, "ck"))
            resumed = _fresh(ck).fit(X)
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = faulted  # the fault fired AND the refit finished
        m["model_match"], m["max_rel_diff"] = _match(resumed, twin)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _model_vec_of_fit(model, X, depth) -> object:
    with _EnvOverride(DASK_ML_TPU_PREFETCH_DEPTH=str(depth)):
        return model.fit(X)


def _drill_ckpt_write_sgd(depth, m):
    """Transient ENOSPC during a checkpoint write: the atomic-pickle
    choke point retries (tmp rewritten whole, rename still atomic); the
    fit never notices and the snapshot on disk is loadable."""
    import shutil
    import tempfile

    from .. import checkpoint as _ckpt

    blocks = _class_blocks(offset=0)
    twin = _twin(f"sgd_d{depth}", lambda: _fit_sgd(list(blocks), depth))
    d = tempfile.mkdtemp(prefix="graftdrill-ckptw-")
    try:
        save_dir = os.path.join(d, "est")

        def _on_block(i, model):
            if i == 2:
                _ckpt.save_estimator(model, save_dir)

        plan = FaultPlan().inject(
            "checkpoint-write", at_call=1, times=1,
            exc=OSError(errno.ENOSPC, "injected: no space left"))
        with fault_plan(plan):
            model = _fit_sgd(list(blocks), depth, on_block=_on_block,
                             label=f"drill_ckpt_write_d{depth}")
        loaded = _ckpt.load_estimator(save_dir)
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = hasattr(loaded, "coef_")
        m["model_match"], m["max_rel_diff"] = _match(model, twin)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _drill_collective_sgd(depth, m):
    """Transient collective/reshard fault at a block boundary of a
    streamed fit: the boundary reshard rides a budgeted retry; the
    resharded data must round-trip exactly and the fit is untouched."""
    from ..core.sharded import shard_rows, unshard

    blocks = _class_blocks(offset=0)
    twin = _twin(f"sgd_d{depth}", lambda: _fit_sgd(list(blocks), depth))
    probe = np.arange(16, dtype=np.float32).reshape(8, 2)
    roundtrip_ok = [False]

    def _on_block(i, model):
        if i == 2:
            sharded = _retry(shard_rows, probe, retries=2, backoff=0.01,
                             jitter=0.0, tag="collective")
            roundtrip_ok[0] = bool(
                np.array_equal(np.asarray(unshard(sharded)), probe))

    plan = FaultPlan().inject("collective", at_call=1, times=1)
    with fault_plan(plan):
        model = _fit_sgd(list(blocks), depth, on_block=_on_block,
                         label=f"drill_collective_d{depth}")
    m["faults_injected"] = sum(plan.fired.values())
    m["recovered"] = roundtrip_ok[0]
    m["model_match"], m["max_rel_diff"] = _match(model, twin)


def _drill_prefetch_crash_sgd(depth, m):
    """The prefetch worker dies WITHOUT reporting (simulated hard
    death) mid-steady-stream: the dead-thread verdict restarts it and
    replays the in-flight block exactly — under an armed graftsan
    scope, so the recovery path itself smuggles zero steady compiles /
    transfers / rogue dispatches.  At depth 0 there is no worker; the
    drill degenerates to the serial fit (0 faults fired, trivially
    recovered) and the baseline records that honestly."""
    from ..sanitize import sanitize
    from .. import programs

    twin = _twin(
        f"sgd_tworound_d{depth}",
        lambda: _fit_sgd(_class_blocks(offset=1), depth,
                         model=_fit_sgd(_class_blocks(offset=0), depth)))
    from ..linear_model import SGDClassifier

    model = SGDClassifier(random_state=0)
    plan = FaultPlan().inject("prefetch-worker", at_call=3, times=1,
                              exc=ThreadCrash("drill: worker death"))
    with sanitize(label=f"drill_prefetch_crash_d{depth}") as s:
        _fit_sgd(_class_blocks(offset=0), depth, model=model,
                 label=f"drill_prefetch_crash_d{depth}")
        programs.drain_ahead()
        with s.steady():
            with fault_plan(plan):
                _fit_sgd(_class_blocks(offset=1), depth, model=model,
                         label=f"drill_prefetch_crash_d{depth}")
            programs.drain_ahead()
    rep = s.report()
    m["faults_injected"] = sum(plan.fired.values())
    m["steady_violations"] = (len(rep["violations"])
                              + rep["totals"]["steady_compiles"])
    m["recovered"] = depth == 0 or m["faults_injected"] == 1
    m["model_match"], m["max_rel_diff"] = _match(model, twin)


def _drill_ahead_crash_sgd(depth, m):
    """The blessed compile-ahead thread dies mid-build: the in-flight
    marker fails WITH the error attached, the consumer falls through to
    a synchronous (warmup-phase) compile, and the NEXT warm restarts
    the worker — so the steady round runs entirely on warm programs
    with zero steady-state compiles under the armed sanitizer.  At
    depth 0 the staged warm hooks never run; the drill degenerates to
    the plain fit."""
    from ..sanitize import sanitize
    from .. import programs
    from ..programs import ahead as _ahead
    from ..linear_model import SGDClassifier

    _ahead._reset_restarts_for_tests()
    # the drill only fires if ITS step programs are not already cached
    # (a cached signature short-circuits warm()): a depth-distinct
    # feature width plus statics no other workload uses makes the
    # signatures unique to this drill
    dd = 9 + depth

    def _mk():
        return SGDClassifier(random_state=0, penalty="l1",
                             fit_intercept=False)

    with _EnvOverride(DASK_ML_TPU_BUCKET="auto",
                      DASK_ML_TPU_COMPILE_AHEAD="on"):
        model = _mk()
        plan = FaultPlan().inject("compile-ahead", at_call=1, times=1,
                                  exc=ThreadCrash("drill: builder death"))
        with sanitize(label=f"drill_ahead_crash_d{depth}") as s:
            # warmup round A: the FIRST ahead build dies; consumers
            # fall through to the synchronous compile path (warmup-
            # class work — legal)
            with fault_plan(plan):
                _fit_sgd(_class_blocks(n=24, d=dd, offset=0), depth,
                         model=model,
                         label=f"drill_ahead_crash_d{depth}")
                programs.drain_ahead()
            # warmup round B: NEW bucket (300 → 1024); the warm hook's
            # submit restarts the blessed worker, which builds ahead
            _fit_sgd(_class_blocks(n=300, d=dd, offset=1), depth,
                     model=model, label=f"drill_ahead_crash_d{depth}")
            programs.drain_ahead()
            with s.steady():
                # steady: same shapes as round B — every program warm
                _fit_sgd(_class_blocks(n=300, d=dd, offset=2), depth,
                         model=model,
                         label=f"drill_ahead_crash_d{depth}")
                programs.drain_ahead()
        rep = s.report()
        m["faults_injected"] = sum(plan.fired.values())
        m["steady_violations"] = (len(rep["violations"])
                                  + rep["totals"]["steady_compiles"])
        m["recovered"] = depth == 0 or (
            m["faults_injected"] == 1 and _ahead.worker_alive())
        # the drill model consumed rounds A (24-row bucket), B and C
        # (300-row bucket): compare against the same three-round twin
        twin = _twin(
            f"sgd_bucketed_threeround_d{depth}",
            lambda: _fit_sgd(
                _class_blocks(n=300, d=dd, offset=2), depth,
                model=_fit_sgd(
                    _class_blocks(n=300, d=dd, offset=1), depth,
                    model=_fit_sgd(_class_blocks(n=24, d=dd, offset=0),
                                   depth, model=_mk()))))
        m["model_match"], m["max_rel_diff"] = _match(model, twin)


def _drill_serve_crash_sgd(depth, m):
    """The serve loop dies (simulated hard death) WITH a drained request
    batch in hand: the supervisor's dead-thread verdict surfaces in
    ``/healthz`` while it is down, a caller already parked on a future
    triggers the budgeted restart, and the in-flight batch is REPLAYED —
    every submitted request resolves with a result or an explicit
    rejection record, and every served prediction equals the direct
    ``model.predict``.  ``depth`` is the prefetch depth the served model
    was streamed-fitted at (the drill matrix's streaming dimension)."""
    import time

    from ..serve import ModelServer
    from . import supervisor as _sup
    from .elastic import FaultBudget

    blocks = _class_blocks(offset=0)
    model = _fit_sgd(list(blocks), depth,
                     label=f"drill_serve_fit_d{depth}")
    Xq = blocks[0][0]
    twin = np.asarray(model.predict(Xq))

    plan = FaultPlan().inject("serve-loop", at_call=3, times=1,
                              exc=ThreadCrash("drill: serve loop death"))
    server = ModelServer(
        label=f"drill_serve_d{depth}", window_s=0.0,
        budget=FaultBudget(4, 60.0, name=f"drill_serve_d{depth}"))
    # the server's ACTUAL supervised unit name (repeat constructions of
    # one label uniquify with #n — a hardcoded name would miss them)
    unit = server._unit
    try:
        server.load("m", model)
        results = []
        with fault_plan(plan):
            for _ in range(2):  # batches 1-2: healthy traffic
                results.append(server.predict("m", Xq))
            # batch 3: the loop crashes AFTER draining this request
            fut = server.submit("m", Xq)
            for _ in range(500):
                if not server._thread.is_alive():
                    break
                time.sleep(0.01)
            died = not server._thread.is_alive()
            hz_dead = unit in _sup.healthz()["dead"]
            # the parked future wait IS the recovery trigger: restart
            # within the budget, replay the drained batch exactly
            results.append(fut.result(timeout=30.0))
            hz_back = unit not in _sup.healthz()["dead"]
            results.append(server.predict("m", Xq))  # post-restart
        rep = server.report()
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = (died and hz_dead and hz_back
                          and m["faults_injected"] == 1
                          and rep["budget"]["spent"] >= 1
                          and rep["alive"])
        ok = all(np.array_equal(np.asarray(r), twin) for r in results)
        m["model_match"] = ok
        m["max_rel_diff"] = 0.0 if ok else float("inf")
    finally:
        server.close()


def _drill_data_reader_crash_sgd(depth, m):
    """A sharded-dataset reader thread dies WITHOUT reporting
    (simulated hard death) mid-epoch: the merged stream's liveness poll
    catches it, a BUDGETED restart spawns a replacement that replays
    the dead reader's in-flight shard range, and the merge queue's
    sequence dedup keeps delivery exactly-once — so the fit completes
    with exactly one restart charged and the model equals a twin
    streamed from the unfaulted dataset (the global key-derived order
    is a value: faulted and unfaulted runs see identical streams).
    ``depth`` is the downstream prefetch depth (the drill matrix's
    streaming dimension: at depth 0 the consumer pulls the merge queue
    inline; at 2 through the staging worker)."""
    import shutil
    import tempfile

    from .. import data as _data
    from ..linear_model import SGDClassifier
    from ..obs.metrics import registry as _registry
    from ..pipeline import stream_partial_fit
    from .elastic import FaultBudget

    rng = np.random.RandomState(_SEED)
    X = rng.normal(size=(2048, 4)).astype(np.float32)
    y = (X[:, 0] + 0.1 * rng.normal(size=2048) > 0).astype(np.int32)
    d = tempfile.mkdtemp(prefix="graftdrill-data-")
    try:
        manifest = _data.write_dataset(d, X, y, shards=4, block_rows=256)
        label = f"drill_data_reader_d{depth}"

        def _fit_ds(budget=None):
            model = SGDClassifier(random_state=0)
            ds = _data.ShardedDataset(d, key=_SEED, readers=2,
                                      budget=budget, label=label)
            stream_partial_fit(
                model, ds, depth=depth,
                fit_kwargs={"classes": np.array([0, 1])}, label=label)
            return model

        twin = _model_vec(_fit_ds())
        budget = FaultBudget(4, 60.0, name=label)
        plan = FaultPlan().inject("data-reader", at_call=3, times=1,
                                  exc=ThreadCrash("drill: reader death"))
        blocks0 = _registry().family("data.blocks").get(label, 0)
        with fault_plan(plan):
            model = _fit_ds(budget=budget)
        delivered = _registry().family("data.blocks").get(label, 0) - blocks0
        m["faults_injected"] = sum(plan.fired.values())
        # recovery = the crash fired, exactly one budgeted restart was
        # charged, and the merge queue delivered every block exactly
        # once (no skip, no duplicate)
        m["recovered"] = (m["faults_injected"] == 1
                          and budget.spent == 1
                          and delivered == manifest.n_blocks)
        m["model_match"], m["max_rel_diff"] = _match(model, twin)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _drill_exporter_enospc_mbk(depth, m):
    """Disk-full on the grafttrace JSONL sink mid-fit: the sink is
    dropped with one warning (ring + flight recording continue) and the
    fit — and its model — are untouched.

    Recording is left armed or disarmed as the drill found it.  A JSONL
    sink the CALLER had armed is the one thing lost: arming the drill's
    own sink closes it (``obs.enable`` holds one sink), and the drill
    re-arms the rings only."""
    import tempfile

    from .. import obs

    was_enabled = obs.enabled()
    blocks = _row_blocks(offset=0)
    twin = _twin(f"mbk_d{depth}", lambda: _fit_mbk(list(blocks), depth))
    fd, path = tempfile.mkstemp(prefix="graftdrill-trace-",
                                suffix=".jsonl")
    os.close(fd)
    try:
        obs.enable(jsonl_path=path)  # header write precedes the plan
        # times=1, not persistent: two completing threads (consumer +
        # prefetch worker) can race write() before the sink-drop lands,
        # and the drill's fired count must stay deterministic
        plan = FaultPlan().inject(
            "exporter-write", at_call=1, times=1,
            exc=lambda: OSError(errno.ENOSPC, "injected: no space left"))
        with fault_plan(plan):
            model = _fit_mbk(list(blocks), depth,
                             label=f"drill_exporter_d{depth}")
        m["faults_injected"] = sum(plan.fired.values())
        # one fault, one warning, sink dropped — no retry storm against
        # a full disk — and the fit itself never noticed
        m["recovered"] = m["faults_injected"] == 1
        m["model_match"], m["max_rel_diff"] = _match(model, twin)
    finally:
        obs.disable()  # closes the drill's sink
        if was_enabled:
            obs.enable()
        try:
            os.unlink(path)
        except OSError:
            pass


def _fleet_fixture(depth, *, replicas=3, hedge_ms=0.0,
                   replica_fault_attempts=0, retries=3):
    """A fitted SGD served hot across a small fleet (the shared fleet-
    drill rig): returns (fleet, model, Xq, twin-predictions)."""
    from ..serve.fleet import ServeFleet
    from .elastic import FaultBudget

    blocks = _class_blocks(offset=0)
    model = _fit_sgd(list(blocks), depth,
                     label=f"drill_fleet_fit_d{depth}")
    Xq = blocks[0][0]
    twin = np.asarray(model.predict(Xq))
    fleet = ServeFleet(
        replicas=replicas, label=f"drill_fleet_d{depth}",
        window_s=0.0, hedge_ms=hedge_ms, retries=retries,
        replica_fault_attempts=replica_fault_attempts,
        budget=FaultBudget(16, 60.0, name=f"drill_fleet_d{depth}"))
    fleet.load("m", model, hot=True)
    return fleet, model, Xq, twin


def _drill_fleet_kill_sgd(depth, m):
    """A replica's serve loop is hard-killed mid-burst with requests in
    flight on it (and its OWN restart budget already spent, so the slot
    is terminally dead): the corpse's sweep rejects its in-flight
    requests LOUDLY, the fleet futures replay them exactly on the
    survivors, the router respawns the slot within the FLEET budget —
    and every accepted request resolves to the direct-predict answer.
    Zero lost, zero fleet-level rejections."""
    import time as _time

    from ..obs.metrics import registry as _registry

    fleet, model, Xq, twin = _fleet_fixture(depth)
    reg = _registry()
    respawns0 = reg.counter("fleet.respawn").value
    rejected0 = sum(reg.family("fleet.rejected").values())
    plan = FaultPlan().inject("replica-kill", at_call=5, times=1,
                              exc=ThreadCrash("drill: replica kill"))
    try:
        with fault_plan(plan):
            futs = [fleet.submit("m", Xq) for _ in range(12)]
            results = [f.result(timeout=30.0) for f in futs]
        # the kill lands at the victim's NEXT loop cycle — anything it
        # still held replays on the survivors via the futures above,
        # and a replay that meets the corpse respawns the slot at once
        # (``_note_trouble``); a victim that held nothing stays a corpse
        # (budget 0: death is terminal) until the routing sweep of the
        # predicts below.  Either is the death this drill plants
        def _victim_down():
            return (reg.counter("fleet.respawn").value > respawns0
                    or any(rep.state() == "dead"
                           for rep in fleet._replicas))

        for _ in range(500):
            if _victim_down():
                break
            _time.sleep(0.01)
        died = _victim_down()
        results.extend(fleet.predict("m", Xq, timeout=30.0)
                       for _ in range(3))
        respawned = reg.counter("fleet.respawn").value - respawns0
        fleet_rejected = sum(reg.family("fleet.rejected").values()) \
            - rejected0
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = (m["faults_injected"] == 1
                          and died
                          and respawned >= 1
                          and fleet_rejected == 0
                          and len(results) == 15)
        ok = all(np.array_equal(np.asarray(r), twin) for r in results)
        m["model_match"] = ok
        m["max_rel_diff"] = 0.0 if ok else float("inf")
    finally:
        fleet.close()


def _drill_fleet_slow_sgd(depth, m):
    """One replica stalls mid-dispatch (an armed 250ms sleep — the
    straggler tail): a request parked past the hedge delay launches a
    duplicate on the other replica, the fast response wins, the
    straggler's duplicate spend is COUNTED — and every answer still
    equals the direct predict (predict is stateless; hedging is always
    exact)."""
    from ..obs.metrics import registry as _registry

    fleet, model, Xq, twin = _fleet_fixture(depth, replicas=2,
                                            hedge_ms=30.0)
    reg = _registry()
    won0 = reg.counter("fleet.hedge", "won").value
    plan = FaultPlan().inject("replica-slow", at_call=3, times=1,
                              exc=FaultInjected("drill: replica stall"))
    try:
        with fault_plan(plan):
            results = [fleet.predict("m", Xq, timeout=30.0)
                       for _ in range(5)]
        for rep in fleet._replicas:  # disarm the stall before close
            rep.server._test_dispatch_delay_s = 0.0
        hedge_won = reg.counter("fleet.hedge", "won").value - won0
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = m["faults_injected"] == 1 and hedge_won >= 1
        ok = all(np.array_equal(np.asarray(r), twin) for r in results)
        m["model_match"] = ok
        m["max_rel_diff"] = 0.0 if ok else float("inf")
    finally:
        fleet.close()


def _drill_fleet_partition_sgd(depth, m):
    """The router loses sight of one replica (a timed quarantine — the
    in-process stand-in for a network partition): traffic routes around
    it with no retry storm, the replica's own loop keeps running, and
    when the partition expires the replica is re-admitted as a
    candidate with no operator action."""
    import time as _time

    fleet, model, Xq, twin = _fleet_fixture(depth, replicas=2)
    plan = FaultPlan().inject("router-partition", at_call=2, times=1,
                              exc=FaultInjected("drill: partition"))
    try:
        with fault_plan(plan):
            results = [fleet.predict("m", Xq, timeout=30.0)
                       for _ in range(4)]
            partitioned = list(fleet._router.report()["partitioned"])
        _time.sleep(0.4)  # the quarantine expires...
        results.append(fleet.predict("m", Xq, timeout=30.0))
        healed = not fleet._router.report()["partitioned"]
        readmitted = len(fleet._router.candidates("m")) == 2
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = (m["faults_injected"] == 1
                          and len(partitioned) == 1
                          and healed and readmitted)
        ok = all(np.array_equal(np.asarray(r), twin) for r in results)
        m["model_match"] = ok
        m["max_rel_diff"] = 0.0 if ok else float("inf")
    finally:
        fleet.close()


def _drill_fleet_deploy_sgd(depth, m):
    """Rolling refresh under live traffic with a replica killed AT the
    drain barrier: the walk must still complete (the kill lands within
    the replica's own restart budget), the pilot stays held for the
    duration, rejections stay confined to reason ``draining`` — and
    every request served during the window answers as EXACTLY the old
    or the new model, never a blend, with the fleet fully on the new
    model afterwards."""
    import threading as _threading

    from ..control import pilot as _pilot
    from ..obs.metrics import registry as _registry
    from ..serve.fleet import ServeFleet
    from .elastic import FaultBudget

    blocks_a = _class_blocks(offset=0)
    blocks_b = _class_blocks(offset=3)
    model_a = _fit_sgd(list(blocks_a), depth,
                       label=f"drill_deploy_fit_a_d{depth}")
    model_b = _fit_sgd(list(blocks_b), depth,
                       label=f"drill_deploy_fit_b_d{depth}")
    Xq = blocks_a[0][0]
    twin_a = np.asarray(model_a.predict(Xq))
    twin_b = np.asarray(model_b.predict(Xq))
    reg = _registry()
    reject0 = dict(reg.family("serve.rejected"))
    freject0 = dict(reg.family("fleet.rejected"))
    fleet = ServeFleet(
        replicas=2, label=f"drill_deploy_d{depth}", window_s=0.0,
        hedge_ms=0.0, retries=3, replica_fault_attempts=2,
        budget=FaultBudget(16, 60.0, name=f"drill_deploy_d{depth}"))
    plan = FaultPlan().inject("fleet-deploy", at_call=2, times=1,
                              exc=ThreadCrash("drill: death at barrier"))
    stop = _threading.Event()
    served: list = []
    held_seen: list = []

    def _traffic():
        while not stop.is_set():
            try:
                served.append(np.asarray(
                    fleet.predict("m", Xq, timeout=30.0)))
            except BaseException as exc:  # noqa: BLE001 - report, not die
                served.append(exc)
            if _pilot.active_holds():
                held_seen.append(True)

    try:
        fleet.load("m", model_a, hot=True)
        # graftlint: disable=thread-dispatch -- host-only client: fleet.predict() only ENQUEUES via ModelServer.submit and parks on the future; every device dispatch happens on the replicas' blessed dask-ml-tpu-serve loops (the serve dispatch contract), runtime-verified by graftsan's dispatch detector across the serve drills
        t = _threading.Thread(target=_traffic,
                              name="drill-fleet-traffic", daemon=True)
        t.start()
        try:
            with fault_plan(plan):
                out = fleet.rolling_refresh("m", model_b, timeout=30.0)
        finally:
            stop.set()
            t.join(timeout=30.0)
        finals = [np.asarray(fleet.predict("m", Xq, timeout=30.0))
                  for _ in range(2)]
        reject_d = {k: v - reject0.get(k, 0)
                    for k, v in reg.family("serve.rejected").items()
                    if v - reject0.get(k, 0)}
        freject_d = {k: v - freject0.get(k, 0)
                     for k, v in reg.family("fleet.rejected").items()
                     if v - freject0.get(k, 0)}
        clean_traffic = all(
            isinstance(r, np.ndarray)
            and (np.array_equal(r, twin_a) or np.array_equal(r, twin_b))
            for r in served)
        m["faults_injected"] = sum(plan.fired.values())
        m["recovered"] = (
            m["faults_injected"] == 1
            and not t.is_alive()
            and all(v.get("ready") for v in out.values())
            and bool(held_seen)
            and set(reject_d) <= {"draining"}
            and not freject_d)
        ok = clean_traffic and all(
            np.array_equal(r, twin_b) for r in finals)
        m["model_match"] = ok
        m["max_rel_diff"] = 0.0 if ok else float("inf")
    finally:
        stop.set()
        fleet.close()


# point → implementation (depth-expanded into DRILLS below); dict order
# is execution order, so the cheap non-sanitized drills run first
_IMPLS = {
    "ingest_retry_sgd": ("ingest", _drill_ingest_retry_sgd),
    "stage_skip_ipca": ("stage", _drill_stage_skip_ipca),
    "step_retry_mbk": ("step", _drill_step_retry_mbk),
    "step_ckpt_resume_ipca": ("step", _drill_step_ckpt_resume_ipca),
    "ckpt_write_sgd": ("checkpoint-write", _drill_ckpt_write_sgd),
    "collective_sgd": ("collective", _drill_collective_sgd),
    "prefetch_crash_sgd": ("prefetch-worker", _drill_prefetch_crash_sgd),
    "ahead_crash_sgd": ("compile-ahead", _drill_ahead_crash_sgd),
    "exporter_enospc_mbk": ("exporter-write", _drill_exporter_enospc_mbk),
    "serve_crash_sgd": ("serve-loop", _drill_serve_crash_sgd),
    "data_reader_crash_sgd": ("data-reader", _drill_data_reader_crash_sgd),
    "fleet_replica_kill_sgd": ("replica-kill", _drill_fleet_kill_sgd),
    "fleet_replica_slow_sgd": ("replica-slow", _drill_fleet_slow_sgd),
    "fleet_partition_sgd": ("router-partition", _drill_fleet_partition_sgd),
    "fleet_deploy_sgd": ("fleet-deploy", _drill_fleet_deploy_sgd),
}
for _name, (_point, _fn) in _IMPLS.items():
    for _depth in (0, 2):
        DRILLS[f"{_name}_d{_depth}"] = (_point, _fn, _depth)
del _name, _point, _fn, _depth


def _new_metrics(point: str, depth: int) -> dict:
    return {"point": point, "depth": depth, "recovered": False,
            "model_match": False, "max_rel_diff": 0.0, "retries": 0,
            "faults_injected": 0, "degraded_skips": 0,
            "steady_violations": 0}


def run_drill(name: str) -> dict:
    """Run one drill; any raise becomes an ``error`` metric (a hard
    failure in the ratchet), never a crash of the suite.  ``retries``
    is the global fault-stats retry delta across the drill — every
    recovery re-attempt the drill caused, whichever site spent it."""
    point, fn, depth = DRILLS[name]
    m = _new_metrics(point, depth)
    retries0 = fault_stats().total("retries")
    try:
        fn(depth, m)
    except BaseException as exc:  # noqa: BLE001 - the suite must report
        m["error"] = f"{type(exc).__name__}: {exc}"
        m["recovered"] = False
    m["retries"] = fault_stats().total("retries") - retries0
    m["max_rel_diff"] = round(float(m["max_rel_diff"]), 9)
    return m


def run_suite(names=None) -> dict:
    names = list(DRILLS) if names is None else list(names)
    unknown = [n for n in names if n not in DRILLS]
    if unknown:
        raise KeyError(f"unknown drill(s): {', '.join(unknown)}")
    return {name: run_drill(name) for name in names}


# -- baseline / ratchet ---------------------------------------------------

def default_baseline_path() -> str | None:
    env = os.environ.get(BASELINE_ENV, "").strip()
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cand = os.path.join(os.path.dirname(pkg), "tools",
                        "drill_baseline.json")
    return cand if os.path.isfile(cand) else None


def emit_baseline(results: dict) -> dict:
    import jax

    return {
        "version": _VERSION,
        "tool": "graftdrill",
        "jax": jax.__version__,
        "drills": {
            name: {k: m[k] for k in sorted(m)}
            for name, m in sorted(results.items())
        },
    }


def write_baseline(path: str, payload: dict) -> None:
    from ..analysis.cache import atomic_write_json

    atomic_write_json(path, payload, indent=2, sort_keys=True)


def load_baseline(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        payload = json.load(fh)
    if payload.get("version", 0) > _VERSION:
        raise ValueError(
            f"drill baseline {path} has version {payload['version']}, "
            f"newer than this suite understands ({_VERSION})")
    if not isinstance(payload.get("drills"), dict):
        raise ValueError(
            f"drill baseline {path} is malformed: no drills table")
    return payload


def compare(snapshot: dict, results: dict, *, partial: bool = False) -> dict:
    """The ratchet delta (same CI semantics as the graftlint/graftsan
    baselines)::

        {"new":        [drills in the run, absent from the snapshot],
         "stale":      [snapshot entries absent from the run],
         "uncovered":  [registered injection points with no drill],
         "regressions":[count-ceiling regressions],
         "violations": [hard-invariant failures, run AND snapshot]}

    ``partial=True`` (an explicit subset) checks hard invariants only —
    stale/coverage are meaningless for a subset and retry ceilings are
    calibrated against the full suite's execution order (a warm program
    cache changes which drill pays which compile)."""
    snap = snapshot["drills"]
    new = [] if partial else sorted(set(results) - set(snap))
    stale = [] if partial else sorted(set(snap) - set(results))
    uncovered: list[str] = []
    if not partial:
        covered = {m.get("point") for m in results.values()}
        uncovered = [
            f"injection point {p!r} has no recovery drill — a new fault "
            f"point cannot ship without one (resilience/drills.py)"
            for p in INJECTION_POINTS if p not in covered
        ]
    regressions: list[str] = []
    violations: list[str] = []

    for name, m in sorted(results.items()):
        err = m.get("error")
        if err:
            violations.append(f"{name}: drill errored: {err}")
            continue
        for k in HARD_INVARIANTS:
            if not m.get(k, False):
                violations.append(
                    f"{name}: hard invariant {k} is false — the "
                    f"recovery path for {m.get('point')!r} is broken")
        for k in HARD_ZEROS:
            if m.get(k, 0):
                violations.append(
                    f"{name}: hard invariant {k} = {m[k]} (must be 0): "
                    f"recovery smuggled work past the armed sanitizer")
        base = snap.get(name)
        if base is None or partial:
            continue
        for k in RATCHETED_COUNTS:
            if m.get(k, 0) > base.get(k, 0):
                regressions.append(
                    f"{name}: {k} {m.get(k, 0)} > baseline "
                    f"{base.get(k, 0)} — recovery now spends more "
                    f"re-attempts than the committed ceiling; fix it or "
                    f"rebaseline deliberately (tools/lint.sh "
                    f"--rebaseline)")

    for name, m in sorted(snap.items()):
        for k in HARD_INVARIANTS:
            if not m.get(k, False):
                violations.append(
                    f"baseline entry {name} carries {k} = false: a "
                    f"snapshot cannot grandfather a broken recovery "
                    f"path — fix the drill and rebaseline")
        for k in HARD_ZEROS:
            if m.get(k, 0):
                violations.append(
                    f"baseline entry {name} carries {k} = {m[k]}: a "
                    f"snapshot cannot grandfather a sanitizer "
                    f"violation")

    return {"new": new, "stale": stale, "uncovered": uncovered,
            "regressions": regressions, "violations": violations}


def is_clean(delta: dict) -> bool:
    return not any(delta[k] for k in ("new", "stale", "uncovered",
                                      "regressions", "violations"))


# -- CLI ------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="python -m dask_ml_tpu.resilience.drills",
        description="chaos drill suite + recovery ratchet",
    )
    p.add_argument("--drills", default=None,
                   help="comma-separated subset (default: all)")
    p.add_argument("--baseline", metavar="PATH", default=None)
    p.add_argument("--write-baseline", metavar="PATH", default=None)
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--list-drills", action="store_true")
    try:
        args = p.parse_args(argv)
    except SystemExit as e:
        return 0 if (e.code in (0, None)) else 2

    if args.list_drills:
        for name in sorted(DRILLS):
            print(name)
        return 0

    names = None
    if args.drills:
        names = [w.strip() for w in args.drills.split(",") if w.strip()]
    if args.write_baseline and names is not None:
        print("error: --write-baseline requires the full suite (drop "
              "--drills): a partial snapshot cannot be ratcheted "
              "against", file=sys.stderr)
        return 2
    try:
        results = run_suite(names)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    snap_path = args.write_baseline or args.baseline
    if args.write_baseline:
        # gate BEFORE writing: a violating run must leave the committed
        # snapshot untouched
        probe = compare({"drills": dict(results)}, results)
        if probe["violations"] or probe["uncovered"]:
            for line in probe["violations"] + probe["uncovered"]:
                print(f"VIOLATION: {line}", file=sys.stderr)
            print(f"drills: refusing to write a violating baseline to "
                  f"{args.write_baseline} (file untouched)",
                  file=sys.stderr)
            return 1
        write_baseline(args.write_baseline, emit_baseline(results))
    if snap_path is None:
        snap_path = default_baseline_path()

    if snap_path is not None:
        try:
            snap = load_baseline(snap_path)
        except (OSError, ValueError) as e:
            print(f"error: cannot load baseline {snap_path}: {e}",
                  file=sys.stderr)
            return 2
        delta = compare(snap, results, partial=names is not None)
    else:
        delta = compare({"drills": dict(results)}, results,
                        partial=names is not None)

    clean = is_clean(delta)
    if args.format == "json":
        print(json.dumps({"drills": results, "delta": delta,
                          "baseline": snap_path, "clean": clean},
                         indent=2, sort_keys=True))
    else:
        for name, m in sorted(results.items()):
            print(f"{name}: point={m['point']} "
                  f"recovered={m['recovered']} "
                  f"model_match={m['model_match']} "
                  f"retries={m['retries']} "
                  f"faults={m['faults_injected']} "
                  f"skips={m['degraded_skips']} "
                  f"steady_violations={m['steady_violations']}"
                  + (f" ERROR={m['error']}" if m.get("error") else ""))
        for key in ("violations", "uncovered", "regressions", "new",
                    "stale"):
            for line in delta[key]:
                print(f"{key.upper()}: {line}")
        print("drills: " + ("clean" if clean else "FAILED")
              + (f" (vs {snap_path})" if snap_path else " (no baseline)"))
    return 0 if clean else 1


if __name__ == "__main__":  # pragma: no cover
    import sys

    sys.exit(main())
