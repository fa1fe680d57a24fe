"""Overlapped host→device input pipeline: bounded-depth block prefetch.

Every streaming fit in this repo moves blocks through three stages:

1. **parse** — the host reads/parses the next block (native CSV/binary
   loader, a generator, or a slice of an in-memory array);
2. **transfer** — the block is staged onto the device (bucket-pad +
   ``device_put``-style upload, target encoding for classifiers);
3. **compute** — the device step consumes it (``partial_fit`` — one
   fused XLA program for the device-native estimators).

The seed ran them strictly serially: the device idled through every
parse and upload (no chip reading of a streamed fit exists yet: PERF.md
section 7 row 8).  This module is the
tf.data-style fix: a single **host-only worker thread** runs stages 1–2
for block *k+1* while the consumer thread runs stage 3 for block *k*,
through a bounded queue of ``depth`` staged blocks — double-buffering at
``depth=1``, deeper pipelining above.

Concurrency contract (docs/design.md §7, enforced by graftlint): the
worker thread NEVER dispatches a device program.  It parses host bytes
and issues host→device transfers (``jnp.asarray`` of numpy blocks — a
put, not a program); all program dispatch — the jitted step, any dtype
cast or reshard of device-resident data — stays on the consumer thread.
That is why the staged protocol below declines device-resident
(``ShardedRows``) inputs: "staging" those would mean dispatching
programs off-thread, the exact PR-1 deadlock class.

Determinism contract: blocks are consumed in source order at every
depth, and staging is the same pure host→device conversion the serial
path performs — so results are bit-identical to ``depth=0`` by
construction (asserted across estimators in tests/test_pipeline.py).

Resilience (docs/design.md §13): the io readers' per-block ``retry``
runs INSIDE the worker (a transient read fault is absorbed without
stalling the device longer than the backoff).  Above that, the stream
runs under an ELASTIC restart driver (``resilience.elastic``): the
worker registers a supervisor heartbeat, and a worker fault — or a
silent thread death (the dead-thread verdict) — triggers domain-scoped
recovery within the stream's shared :class:`~dask_ml_tpu.resilience.
FaultBudget`: a fresh worker is started and the in-flight block is
REPLAYED exactly (the raw parsed item is held until its staged form is
delivered, so a crash between parse and enqueue loses nothing).  A
staging-poisoned block past its per-block retries can — policy knob
``DASK_ML_TPU_DEGRADED_BLOCKS``, default off — be skipped with an
exact flight-recorder record instead of killing the fit.  A propagated
failure surfaces on the consumer thread carrying the failed block's
position and phase (``pipeline.fault`` flight event).  Prefetched-but-
unconsumed blocks are dropped on close and never reach the model, so a
``FitCheckpoint`` resume replays exactly the blocks after the last
consumed one.
"""

from __future__ import annotations

import os
import queue
import threading

from .._locks import make_lock
import time

from .. import obs
from ..control import knobs as _knobs
from ..control.pilot import maybe_autostart as _maybe_autostart
from ..resilience import supervisor as _supervisor
from ..resilience.elastic import ElasticPolicy, WorkerLost
from ..resilience.testing import ThreadCrash as _ThreadCrash
from ..resilience.testing import maybe_fault as _maybe_fault
from .stats import PipelineStats

__all__ = [
    "DEPTH_ENV",
    "PREFETCH_THREAD_NAME",
    "UnitStream",
    "as_block_source",
    "resolve_depth",
    "prefetch_blocks",
    "stream_partial_fit",
]

#: policy knob: default prefetch depth for every streaming consumer.
#: 0 = the seed's serial behavior; k >= 1 = k blocks staged ahead.
DEPTH_ENV = "DASK_ML_TPU_PREFETCH_DEPTH"

#: the staging worker's thread name — the identity the graftsan dispatch
#: sanitizer watches: this thread stages transfers and must NEVER appear
#: as a program-dispatching or compiling thread (design.md §8; the
#: runtime check behind the pipeline/core.py thread-dispatch
#: suppression below)
PREFETCH_THREAD_NAME = "dask-ml-tpu-prefetch"

_DEFAULT_DEPTH = 2

_DONE = object()  # worker sentinel: source exhausted

#: consumer-side poll interval: how long a q.get waits before checking
#: the worker's liveness (the dead-thread verdict's detection latency)
_POLL_S = 0.05

#: a contiguous consumer wait on the staged queue shorter than this is
#: loop overhead, not a stall — no ``pipeline.stall`` span is recorded
#: for it (the stats.stall_s scalar still counts every microsecond)
_STALL_SPAN_MIN_S = 0.002

#: producer-side park while the staged queue sits at the LIVE capacity
#: ceiling (graftpilot streams): the worker re-checks the gate at this
#: cadence, so a consumer pop or a deepened override frees it fast
_GATE_POLL_S = 0.0005


class _BlockFault(Exception):
    """Internal: one block's pipeline failure with position + phase
    (``parse`` / ``stage`` / ``crash`` / ``worker``) attribution.  For
    staging faults ``item`` holds the already-parsed raw block so a
    retry re-stages it instead of losing it."""

    __slots__ = ("blk", "phase", "exc", "item")

    def __init__(self, blk: int, phase: str, exc: BaseException,
                 item=None):
        super().__init__(f"block {blk} {phase} fault: {exc!r}")
        self.blk = int(blk)
        self.phase = phase
        self.exc = exc
        self.item = item


def resolve_depth(depth: int | None = None) -> int:
    """Resolve a prefetch depth: explicit argument, else the live
    graftpilot override, else the ``DASK_ML_TPU_PREFETCH_DEPTH`` env
    knob, else the default (2)."""
    if depth is None:
        ov = _knobs.override("prefetch_depth")
        if ov is not None:
            depth = int(ov)
    if depth is None:
        raw = os.environ.get(DEPTH_ENV, "").strip()
        if raw:
            try:
                depth = int(raw)
            except ValueError:
                raise ValueError(
                    f"{DEPTH_ENV} must be an integer, got {raw!r}"
                ) from None
        else:
            depth = _DEFAULT_DEPTH
    depth = int(depth)
    if depth < 0:
        raise ValueError(f"prefetch depth must be >= 0, got {depth}")
    return depth


def _parse_and_stage(src, stage, stats: PipelineStats, blk: int,
                     item=None):
    """One pipeline step, identical on BOTH paths (inline depth-0 loop
    and the worker thread): timed+spanned parse of the next item, then
    timed+spanned staging.  Returns the staged item, or ``_DONE`` on
    source exhaustion; failures raise :class:`_BlockFault` with the
    position, phase, and (for staging faults) the raw item so the
    elastic driver can replay exactly.  ``item`` replays a held raw
    block (skipping the parse leg) after a worker restart."""
    if item is None:
        t0 = time.perf_counter()
        try:
            with obs.span("pipeline.parse", block=blk):
                item = next(src)
        except StopIteration:
            return _DONE
        except BaseException as exc:
            raise _BlockFault(blk, "parse", exc) from exc
        finally:
            stats.parse_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    try:
        with obs.span("pipeline.stage", block=blk):
            _maybe_fault("stage")
            staged = stage(item)
    except BaseException as exc:
        raise _BlockFault(blk, "stage", exc, item=item) from exc
    finally:
        stats.transfer_s += time.perf_counter() - t0
    return staged


#: sentinel: `_staged_iter`'s trace_parent default — "capture the
#: consumer's innermost open span at first next()", the historical
#: behavior; an orchestrating caller passes its unit span id instead
#: (its first next() runs on a helper thread with an empty stack).
_CAPTURE_PARENT = object()


def _staged_iter(src, stage, depth: int, stats: PipelineStats,
                 policy: ElasticPolicy, trace_parent=_CAPTURE_PARENT,
                 live: bool = False):
    """Yield ``stage(item)`` for each item of ``src``, staged up to
    ``depth`` blocks ahead on a host worker thread, under the elastic
    restart driver.

    ``depth <= 0`` degrades to the inline serial loop (same timings and
    fault policy, no thread).  Worker faults consult ``policy``: retry
    (restart the worker, replay the held raw item), degraded-mode skip,
    or re-raise on the consumer thread at the failed block's position.
    Closing the generator stops the worker promptly even when it is
    blocked on a full queue.

    ``live=True`` (caller resolved ``depth`` from env/default rather
    than an explicit arg) makes the staging capacity LIVE: the worker
    gates on the graftpilot ``prefetch_depth`` override per block
    instead of a frozen ``Queue(maxsize=depth)``, so the controller can
    deepen (or shallow) the stage-ahead window mid-stream.  The gate
    clamps at >= 1 — a live stream that entered the threaded path stays
    threaded — and a depth-0 stream stays structurally serial either
    way (the seed's behavior is pinned, not tunable).
    """
    restartable = bool(getattr(src, "restartable_source", False))
    # shared driver state: ONE worker exists at a time (start happens
    # only after the previous join), so these see no concurrent writers
    state = {"blk": 0, "pending": None}

    def _handle(fault: _BlockFault) -> str:
        verdict = policy.on_block_fault(fault.blk, fault.phase, fault.exc,
                                        restartable=restartable)
        if verdict == "raise":
            exc = fault.exc
            try:
                # position + phase attribution for the pipeline.fault
                # flight event (stream_partial_fit's handler) — staging
                # faults carry their true block index even when the
                # consumer is blocks behind the worker
                exc.__dmlt_block__ = fault.blk
                exc.__dmlt_phase__ = fault.phase
            except Exception:  # pragma: no cover - exotic exception types
                pass
            raise exc
        if verdict == "skip":
            # degraded mode: drop the poisoned block exactly (recorded
            # by the policy) and continue at the next position
            state["pending"] = None
            state["blk"] += 1
        return verdict

    # thread stitching (design.md §11): the worker's parse/stage spans
    # attach under the consumer's innermost open span (the
    # pipeline.stream span) instead of becoming orphan roots — this
    # generator body runs on the consumer thread at first next(), so
    # the default capture happens in the right place.  An orchestrated
    # UnitStream advances the generator from helper threads and passes
    # its stream-span id explicitly instead.
    if trace_parent is _CAPTURE_PARENT:
        trace_parent = obs.current_span_id()

    if depth <= 0:
        while True:
            item, state["pending"] = state["pending"], None
            try:
                # adopt: with an empty stack on the advancing thread
                # (the orchestrated depth-0 case) the parse/stage spans
                # still attach under the owning stream span; with a
                # live stack (the classic consumer-thread loop) stack
                # parentage wins and adopt is inert
                with obs.adopt(trace_parent):
                    staged = _parse_and_stage(src, stage, stats,
                                              state["blk"], item=item)
            except _BlockFault as fault:
                if _handle(fault) == "retry":
                    state["pending"] = fault.item
                continue
            if staged is _DONE:
                return
            state["blk"] += 1
            yield staged

    # depth >= 1: bounded queue + one host-only staging worker per
    # (re)start — the driver below restarts it on recoverable faults

    def _live_depth(base=depth) -> int:
        return max(1, int(_knobs.override_or("prefetch_depth", base)))

    while True:
        # live streams use an UNBOUNDED queue with a capacity gate in
        # _put (re-read per block): a bounded Queue's maxsize is frozen
        # at construction, which is exactly what blocked mid-run depth
        # changes.  One producer means occupancy overshoots the live
        # ceiling by at most the one block in hand.
        q: queue.Queue = queue.Queue(maxsize=0 if live else depth)
        stop = threading.Event()
        hb_box: list = [None]

        def _put(msg, q=q, stop=stop) -> bool:
            """Queue-put that stays responsive to consumer shutdown
            (and, for live streams, to the live capacity ceiling)."""
            while not stop.is_set():
                if live and q.qsize() >= _live_depth():
                    time.sleep(_GATE_POLL_S)  # park: queue at live depth
                    continue
                try:
                    q.put(msg, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def _work(stop=stop, put=_put):
            try:
                with obs.adopt(trace_parent):
                    while not stop.is_set():
                        # drill point: a ThreadCrash here simulates the
                        # worker dying WITHOUT reporting — the silent
                        # failure mode the liveness poll below catches
                        _maybe_fault("prefetch-worker")
                        hb = hb_box[0]
                        if hb is not None:
                            hb.beat()
                        item, state["pending"] = state["pending"], None
                        try:
                            staged = _parse_and_stage(
                                src, stage, stats, state["blk"], item=item)
                        except _BlockFault as fault:
                            state["pending"] = fault.item
                            put(("fault", fault))
                            return
                        if staged is _DONE:
                            put(("done",))
                            return
                        blk = state["blk"]
                        if not put(("blk", blk, staged)):
                            return  # consumer shut the stream down
                        state["blk"] = blk + 1
            except _ThreadCrash:
                return  # simulated hard death: vanish without reporting
            except BaseException as exc:  # driver bug: surface, don't hang
                put(("fault", _BlockFault(state["blk"], "worker", exc)))

        # host-only staging worker: parses blocks and issues host->device
        # transfers; it never dispatches a device program (the jitted step
        # and any device-resident cast/reshard stay on the consumer thread
        # -- module docstring / design.md "input pipeline"), so it cannot
        # interleave multi-device enqueue order
        # graftlint: disable=thread-dispatch -- host-only prefetch worker: parse + H2D staging puts, never device program dispatch (design.md input-pipeline contract)
        worker = threading.Thread(
            target=_work, daemon=True, name=PREFETCH_THREAD_NAME,
        )
        hb = _supervisor.register(
            f"prefetch:{stats.label}", "pipeline", thread=worker)
        hb_box[0] = hb
        worker.start()
        fault: _BlockFault | None = None
        # consumer-starvation interval tracking (graftpath, design.md
        # §19): a contiguous wait on the staged queue spans several
        # _POLL_S-bounded gets; wait_t0 marks where it began and the
        # whole interval lands as ONE ``pipeline.stall`` span when the
        # block finally arrives — the queue-wait signal the critical-
        # path engine attributes (to the producer's concurrent parse/
        # stage when one explains it, to queue_wait when nothing does).
        wait_t0: float | None = None
        try:
            while True:
                t0 = time.perf_counter()
                if wait_t0 is None:
                    wait_t0 = t0
                try:
                    msg = q.get(timeout=_POLL_S)
                except queue.Empty:
                    stats.stall_s += time.perf_counter() - t0
                    if worker.is_alive():
                        continue
                    # dead without reporting — but a message may have
                    # landed between our Empty and the liveness check
                    # (the worker puts, THEN dies): drain before the
                    # crash verdict, or that staged block is silently
                    # lost.  is_alive() False means every put the
                    # worker ever made has completed, so one final
                    # Empty here is definitive.
                    try:
                        msg = q.get_nowait()
                    except queue.Empty:
                        break  # crash verdict below
                else:
                    stats.stall_s += time.perf_counter() - t0
                now = time.perf_counter()
                if now - wait_t0 >= _STALL_SPAN_MIN_S:
                    obs.record_span("pipeline.stall", wait_t0, now,
                                    block=state["blk"])
                wait_t0 = None
                if msg[0] == "done":
                    return
                if msg[0] == "fault":
                    fault = msg[1]
                    break
                yield msg[2]
        finally:
            stop.set()
            try:  # unblock a worker stuck in q.put full-wait
                q.get_nowait()
            except queue.Empty:
                pass
            worker.join(timeout=5.0)
            hb.retire()
        # reached only via break: a reported fault or a silent death.
        # (A reported stage fault already parked its raw item in
        # state["pending"] from the worker before it exited.)
        if fault is None:
            _supervisor.note_death(
                "pipeline", hb.name,
                error="prefetch worker died without reporting")
            fault = _BlockFault(
                state["blk"], "crash",
                WorkerLost("prefetch worker died without reporting"))
        _handle(fault)  # raises on "raise"; advances past block on "skip"
        _supervisor.note_restart("pipeline", hb.name)
        # loop: a fresh worker resumes from state (held raw item first)


def as_block_source(blocks):
    """Normalize a stream source to ONE block iterator — the pipeline's
    multi-source staged feed entry.

    A sharded dataset (the ``iter_blocks`` protocol,
    :mod:`dask_ml_tpu.data`) opens its merged stream here: N parallel
    reader threads producing into a bounded reorder queue, re-serialized
    into the single deterministic sequence this pipeline's one staging
    worker consumes — so "many sources" (shard files, readers, epochs)
    compose UNDER the existing single-feed contract instead of widening
    it (the worker still never dispatches; order is still a value).
    Anything else is plain ``iter()``.  The returned iterator's
    ``restartable_source`` attribute (the dataset streams set it) opts
    parse faults into the elastic driver's budgeted re-pull.
    """
    if hasattr(blocks, "iter_blocks"):
        return blocks.iter_blocks()
    return iter(blocks)


def _close_source(src) -> None:
    """Release a source that holds live resources (a dataset stream's
    reader threads, a generator's frame) once its stream is finished or
    abandoned.  Plain iterators without ``close`` are untouched."""
    close = getattr(src, "close", None)
    if close is not None:
        try:
            close()
        except Exception:  # pragma: no cover - source teardown is best-effort
            pass


def _identity(x):
    return x


def prefetch_blocks(blocks, *, depth: int | None = None,
                    stage=None, label: str = "stream", elastic=None):
    """Generator over ``blocks`` with bounded host-thread prefetch.

    The building block the consumers share: ``stage`` (default identity)
    runs on the worker thread — host parse is timed around the source
    pull, staging around ``stage``.  ``elastic`` (an
    :class:`~dask_ml_tpu.resilience.ElasticPolicy`) governs worker
    restarts / degraded-mode skips; default: a fresh policy from the
    env knobs.  Records a :class:`PipelineStats` when the stream
    completes or closes.
    """
    live = depth is None  # env/default-resolved: graftpilot retunes
    depth = resolve_depth(depth)
    if live:
        _knobs.observe("prefetch_depth", depth)
    stage = stage or _identity
    policy = elastic if elastic is not None else ElasticPolicy(label=label)
    stats = PipelineStats(label=label, depth=depth, staged=stage is not _identity)
    # the stream span opens at first next() and closes when the
    # generator finishes/closes — both on the consumer thread, so stack
    # discipline holds; the worker's parse/stage spans stitch under it
    with obs.span("pipeline.stream", label=label, depth=depth):
        src = as_block_source(blocks)
        feed = _staged_iter(src, stage, depth, stats, policy, live=live)
        try:
            for staged in feed:
                t0 = time.perf_counter()
                with obs.span("pipeline.compute", block=stats.blocks):
                    yield staged
                stats.compute_s += time.perf_counter() - t0
                stats.blocks += 1
        finally:
            feed.close()  # stop the worker promptly on early exit
            _close_source(src)  # …and the source's readers/frame
            stats.finish()


def _supports_staging(model) -> bool:
    return hasattr(model, "_pf_stage") and hasattr(model, "_pf_consume")


def _protocol_fns(model, kw: dict, staged_proto: bool):
    """The (stage, consume) pair of one partial_fit stream — THE shared
    prefetch discipline: ``stage`` runs on the host worker
    (``_pf_stage`` or identity), ``consume`` on the dispatch thread
    (``_pf_consume`` or plain ``partial_fit``), with the per-block
    decline fallback.  Used by :func:`stream_partial_fit` and
    :class:`UnitStream` so the two planes cannot drift."""

    def _raw_consume(blk):
        bx, by = blk
        if by is None:
            model.partial_fit(bx, **kw)
        else:
            model.partial_fit(bx, by, **kw)

    if not staged_proto:
        return (lambda blk: blk), _raw_consume

    # the raw block rides along ONLY when staging declined (None),
    # so the fallback can serial-partial_fit exactly that block;
    # a successfully staged block drops its host copy immediately —
    # queued memory stays one copy per block, not two
    def _stage(blk):
        staged = model._pf_stage(blk[0], blk[1], **kw)
        return (blk if staged is None else None), staged

    def _consume(item):
        blk, staged = item
        if staged is None:
            _raw_consume(blk)
        else:
            model._pf_consume(staged)

    return _stage, _consume


def stream_partial_fit(model, blocks, *, depth: int | None = None,
                       fit_kwargs: dict | None = None, on_block=None,
                       label: str = "partial_fit_stream", elastic=None):
    """Drive ``model.partial_fit`` over an iterator of ``(X, y)`` block
    pairs with prefetch + early H2D staging.

    When the model implements the staged protocol (``_pf_stage``/
    ``_pf_consume``) and ``depth >= 1``, the worker stages each block
    ahead — block k+1's parse/pad/upload overlaps block k's device
    step.  ``_pf_stage`` decides PER BLOCK: a ``None`` return (device-
    resident input, unsupported kwargs) routes that block — and only
    that block — through plain ``partial_fit`` on the consumer thread,
    so heterogeneous streams degrade gracefully instead of erroring.
    Models without the protocol get raw-block prefetch (still hiding
    reader latency behind host estimators' compute).  ``depth=0`` is
    the serial seed path: plain ``partial_fit`` per block, no thread,
    no staging.

    ``on_block(i, model)`` (1-based consumed count) fires after each
    consumed block — the checkpoint/preemption hook: it runs on the
    consumer thread between device steps, so a ``FitCheckpoint`` save or
    a ``TrainingPreempted`` raise sees a model state that reflects
    exactly the first ``i`` blocks, never an in-flight prefetched one.

    ``elastic`` is the stream's recovery policy (an
    :class:`~dask_ml_tpu.resilience.ElasticPolicy`; default: one built
    from the ``DASK_ML_TPU_FAULT_BUDGET`` / ``DASK_ML_TPU_DEGRADED_BLOCKS``
    knobs): it bounds worker restarts and staging replays under the
    per-fit shared budget, enables degraded-mode block skips, and —
    opt-in via ``step_retries`` — retries a failed device step on the
    same staged block.

    Returns ``model``.  Records a :class:`PipelineStats` either way.
    """
    from .. import sanitize as _san

    if _san.enabled_by_env() and _san.active_sanitizer() is None:
        # DASK_ML_TPU_SANITIZE=1: ambient observe-don't-crash sanitizer
        # around this one stream — counters land in
        # diagnostics.sanitize_report() with no code changes at the
        # call site.  Entry is atomic-or-skip (sanitize.ambient): a
        # concurrent stream that loses the race runs unobserved rather
        # than crashing on the no-nesting rule, and fail_fast is off so
        # an ambient run records violations instead of raising mid-fit.
        with _san.ambient(f"ambient:{label}"):
            return stream_partial_fit(
                model, blocks, depth=depth, fit_kwargs=fit_kwargs,
                on_block=on_block, label=label, elastic=elastic,
            )

    kw = dict(fit_kwargs or {})
    live = depth is None  # env/default-resolved: graftpilot may retune
    depth = resolve_depth(depth)
    if live:
        _knobs.observe("prefetch_depth", depth)
        _maybe_autostart()  # DASK_ML_TPU_AUTOPILOT=1 arms the controller
    policy = elastic if elastic is not None else ElasticPolicy(label=label)
    staged_proto = depth > 0 and _supports_staging(model)
    stats = PipelineStats(label=label, depth=depth, staged=staged_proto)
    _stage, _consume = _protocol_fns(model, kw, staged_proto)

    def _consume_elastic(item, blk):
        """Step-fault recovery (opt-in, ``policy.step_retries``): retry
        the SAME staged block — exact-once only for steps that either
        complete or leave state untouched, which holds for the device-
        native functional steps (state reassigned after the program
        returns), hence the opt-in."""
        while True:
            try:
                _consume(item)
                return
            except Exception as exc:
                if policy.step_retries <= 0:
                    raise
                if policy.on_block_fault(blk, "step", exc) != "retry":
                    raise

    # per-block device-step latency feeds the registry histogram the
    # serving lane will ratchet SLOs on; re-fetched per block (the
    # registry contract: a cached handle would silently record into an
    # orphan after a concurrent diagnostics.reset())
    with obs.span("pipeline.stream", label=label, depth=depth,
                  staged=staged_proto,
                  estimator=type(model).__name__):
        src = as_block_source(blocks)
        feed = _staged_iter(src, _stage, depth, stats, policy, live=live)
        done = 0
        try:
            for item in feed:
                t0 = time.perf_counter()
                with obs.span("pipeline.compute", block=done):
                    _consume_elastic(item, done)
                dt = time.perf_counter() - t0
                stats.compute_s += dt
                obs.registry().histogram("pipeline.block_s").record(dt)
                stats.blocks += 1
                done += 1
                del item  # release the staged buffers: bounded HBM = depth+1 blocks
                if on_block is not None:
                    on_block(done, model)
            return model
        except BaseException as exc:
            # flight-recorder breadcrumb at the failed position: a
            # post-mortem of a dead stream shows WHICH block was in
            # flight — staging faults carry their true (worker-side)
            # position and phase even when the consumer is behind
            obs.event("pipeline.fault", label=label,
                      block=getattr(exc, "__dmlt_block__", done),
                      phase=getattr(exc, "__dmlt_phase__", "consume"),
                      error=obs.fmt_exc(exc))
            raise
        finally:
            feed.close()
            _close_source(src)
            stats.finish()


class UnitStream:
    """One training unit's staged block feed, consumption handed to an
    EXTERNAL orchestrator (the concurrent search control plane,
    design.md §17).

    :func:`stream_partial_fit` owns its whole loop: stage on the
    worker, consume inline, done.  A scheduler multiplexing MANY units
    on one dispatch thread needs the same staging discipline with the
    two halves split apart:

    * :meth:`next_staged` — block (host-only: a queue get against the
      prefetch worker, or the inline parse+stage at depth 0) until the
      next staged item is ready; returns :data:`DONE` at exhaustion.
      Safe on a helper thread — it never dispatches a device program.
    * :meth:`consume` — run the device step for one staged item.  MUST
      be called on the orchestrator's one dispatch thread, in source
      order (the determinism contract is per unit, exactly as in
      ``stream_partial_fit``).

    Everything else is shared verbatim with the classic stream: the
    same ``_pf_stage``/``_pf_consume`` protocol (with per-block decline
    fallback), the same elastic worker-restart policy, the same
    :class:`~.stats.PipelineStats` books and ``pipeline.block_s``
    latency histogram, and the same span tree — the stream span is
    DETACHED under the caller's unit span (``parent_span``), with the
    worker's parse/stage spans stitched beneath it.
    """

    #: source-exhausted sentinel returned by :meth:`next_staged`
    DONE = _DONE

    def __init__(self, model, blocks, *, depth: int | None = None,
                 fit_kwargs: dict | None = None,
                 label: str = "search_ingest", elastic=None,
                 parent_span: int | None = None):
        kw = dict(fit_kwargs or {})
        live = depth is None  # env/default-resolved: graftpilot retunes
        depth = resolve_depth(depth)
        if live:
            _knobs.observe("prefetch_depth", depth)
        policy = elastic if elastic is not None else \
            ElasticPolicy(label=label)
        staged_proto = depth > 0 and _supports_staging(model)
        self.model = model
        self.blocks = 0
        self._stats = PipelineStats(label=label, depth=depth,
                                    staged=staged_proto)
        stage, self._consume = _protocol_fns(model, kw, staged_proto)
        # detached stream span: entered here (construction, any thread)
        # and closed at close() — it never touches a thread stack, so
        # interleaved units cannot cross-link (design.md §11)
        self._span = obs.span(
            "pipeline.stream", parent=parent_span, detached=True,
            label=label, depth=depth, staged=staged_proto,
            estimator=type(model).__name__)
        self._span.__enter__()
        self._parent = self._span.span_id or parent_span
        self._src = as_block_source(blocks)
        self._feed = _staged_iter(self._src, stage, depth,
                                  self._stats, policy,
                                  trace_parent=self._parent, live=live)
        self._closed = False
        # close/advance handshake: an orchestrator cancelled mid-await
        # calls close() from its loop thread while next_staged() is
        # still executing the generator on a pool thread — gen.close()
        # on an executing generator raises and would LEAK the prefetch
        # worker.  The flag pair defers the actual close to the
        # in-flight advance's exit (which runs it safely on that
        # thread the moment next() returns).
        self._close_lock = make_lock("pipeline.close")
        self._advancing = False
        self._close_deferred = False

    # -- staging half (any host thread) ----------------------------------
    def next_staged(self):
        """The next staged item, or :data:`DONE`.  Blocking, host-only."""
        with self._close_lock:
            if self._closed:
                return _DONE
            self._advancing = True
        try:
            try:
                return next(self._feed)
            except StopIteration:
                return _DONE
        finally:
            with self._close_lock:
                self._advancing = False
                deferred = self._close_deferred
                self._close_deferred = False
            if deferred:
                self._finish_close()

    # -- device half (the orchestrator's dispatch thread) ----------------
    def consume(self, item) -> None:
        """Dispatch one staged block's device step (or the serial
        ``partial_fit`` fallback for a block staging declined)."""
        t0 = time.perf_counter()
        with obs.span("pipeline.compute", parent=self._parent,
                      detached=True, block=self.blocks):
            self._consume(item)
        dt = time.perf_counter() - t0
        self._stats.compute_s += dt
        self._stats.blocks += 1
        obs.registry().histogram("pipeline.block_s").record(dt)
        self.blocks += 1

    def close(self) -> None:
        """Stop the worker, record the stats, close the stream span.
        Idempotent; safe from any thread (the classic stream's
        ``finally``).  If a :meth:`next_staged` is mid-flight on a pool
        thread, the feed close DEFERS to that call's exit — closing an
        executing generator would raise and leak the worker."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            if self._advancing:
                self._close_deferred = True
                return
        self._finish_close()

    def _finish_close(self) -> None:
        try:
            self._feed.close()
        finally:
            _close_source(self._src)
            self._stats.finish()
            self._span.__exit__(None, None, None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
