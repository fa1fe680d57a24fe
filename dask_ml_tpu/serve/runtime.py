"""The online inference runtime: one supervised micro-batch serve loop.

:class:`ModelServer` is the serving plane's front door (design.md §15):

* callers ``load()`` fitted models and ``submit()`` / ``predict()``
  single rows or small row batches; every device interaction — model
  admission, warm compiles, lane-stack builds, batch staging, program
  dispatch, result fetch — happens on ONE dedicated thread (the
  dispatch-blessed ``dask-ml-tpu-serve``), so the serve plane can never
  interleave multi-device enqueues with itself;
* queued requests coalesce through the :class:`~.batcher.MicroBatcher`
  into bucket-ladder shapes, dispatch through the warm cached programs
  (:mod:`.programs`), and decode/slice back per request on the host;
* the loop is a supervised unit (domain ``"serve"``, one heartbeat per
  drained batch): a dead loop flips ``/healthz``, and the next submit —
  or a caller already blocked on a future — restarts it within the
  server's :class:`~dask_ml_tpu.resilience.FaultBudget`, REPLAYING the
  in-flight batch (predict is stateless, so replay is exact); past the
  budget every pending request is rejected loudly with
  ``serve_down``, never left hanging;
* per-model request latency (``serve.request_s``), queue wait, batch
  occupancy, and rejection counters land in the obs metrics registry —
  the live ``/metrics`` endpoint (obs/serve.py) exports them with no
  extra wiring.

Honesty contract (mirrors graftscope's): request latency INCLUDES queue
wait and the adaptive gather window — the number a client experiences —
while ``serve.batch_window_s`` and ``serve.queue_wait_s`` split out how
much of it was the batcher's own choice.  Since graftpath (design.md
§19) every fulfilled request additionally records its EXACT four-leg
split — ``serve.req_{queue,window,device,fetch}_s``, contiguous stamps
on one clock so they sum to ``serve.request_s`` — keyed by the
request's trace id from submit through coalesce, dispatch, and fetch;
the slowest request seen leaves a flight-recorder exemplar carrying
that id and its split.
"""

from __future__ import annotations

import logging
import threading

from .._locks import make_lock
import time

import numpy as np

from .. import obs
from ..control import knobs as _knobs
from ..control.pilot import maybe_autostart as _maybe_autostart
from ..obs.metrics import registry as _registry
from ..resilience import supervisor as _supervisor
from ..resilience.elastic import FaultBudget
from ..resilience.testing import ThreadCrash as _ThreadCrash
from ..resilience.testing import maybe_fault as _maybe_fault
from .batcher import MicroBatcher, Request, RequestRejected, ServeFuture, \
    reject
from .config import (
    resolve_deadline_s,
    resolve_hbm_budget_bytes,
    resolve_max_batch,
    resolve_queue_depth,
    resolve_window_s,
)
from .residency import ModelRegistry

logger = logging.getLogger(__name__)

__all__ = ["SERVE_THREAD_NAME", "ModelServer", "report"]

#: the serve loop's literal thread name — the identity both halves of
#: the dispatch contract key on: graftlint's thread-dispatch rule
#: accepts it statically (_spmd.BLESSED_DISPATCH_THREADS) and graftsan
#: permits its dispatches at runtime while still hard-failing a steady
#: compile attributed to it.
SERVE_THREAD_NAME = "dask-ml-tpu-serve"

#: live servers, for the module-level :func:`report`
_SERVERS: list = []
_SERVERS_LOCK = make_lock("serve.servers")

#: constructions per label, to uniquify supervisor unit names — two
#: servers sharing a label must NOT share a heartbeat entry, or a dead
#: loop hides behind its twin's live thread and /healthz never flips
_LABEL_SEQ: dict = {}


def _unit_name(label: str) -> str:
    with _SERVERS_LOCK:
        n = _LABEL_SEQ.get(label, 0) + 1
        _LABEL_SEQ[label] = n
    return f"serve:{label}" if n == 1 else f"serve:{label}#{n}"


class _Control:
    """A queued control operation (load/unload) — handled on the serve
    loop like a request, so registry mutations and their warm compiles
    stay on the one dispatch thread."""

    __slots__ = ("op", "name", "model", "future")

    def __init__(self, op: str, name: str, model=None, future=None):
        self.op = op
        self.name = name
        self.model = model
        self.future = future


class ModelServer:
    """Online inference over a registry of resident fitted models."""

    def __init__(self, *, label: str = "serve", max_batch: int | None = None,
                 window_s: float | None = None, queue_depth: int | None = None,
                 deadline_s: float | None = None,
                 hbm_budget_mb: float | None = None,
                 budget: FaultBudget | None = None,
                 metrics_tag: str | None = None):
        from .. import programs as _programs

        self.label = str(label)
        self._unit = _unit_name(self.label)
        self.max_batch = resolve_max_batch(max_batch)
        #: the construction max-batch is the COMPILE CEILING: warmup
        #: covers bucket rungs up to it, so a live knob raise past it
        #: would force a steady-state compile on the serve thread (a
        #: hard graftsan violation) — _refresh_knobs clamps to this.
        self._max_batch_ceiling = self.max_batch
        self.window_s = resolve_window_s(window_s)
        # explicit ctor args PIN (graftpilot doctrine: a test asking for
        # window_s=0 gets exactly that); env/default sizing stays live
        self._max_batch_pinned = max_batch is not None
        self._window_pinned = window_s is not None
        if not self._max_batch_pinned:
            _knobs.observe("serve_max_batch", self.max_batch)
        if not self._window_pinned:
            _knobs.observe("serve_window_ms", self.window_s * 1e3)
        _maybe_autostart()  # DASK_ML_TPU_AUTOPILOT=1 arms the controller
        self.default_deadline_s = resolve_deadline_s(deadline_s)
        self.registry = ModelRegistry(
            budget_bytes=resolve_hbm_budget_bytes(hbm_budget_mb),
            policy=_programs.resolve_policy(),
            max_batch=self.max_batch,
        )
        self._batcher = MicroBatcher(
            depth=resolve_queue_depth(queue_depth),
            max_batch=self.max_batch, window_s=self.window_s)
        self._budget = budget if budget is not None else \
            FaultBudget.from_env(name=f"serve:{self.label}")
        self._stop = threading.Event()
        self._lock = make_lock("serve.server")
        self._inflight: list = []
        self._replay: list = []
        self._failed: BaseException | None = None
        self._closed = False
        self._draining = False
        #: control futures (loads/unloads) not yet resolved — the
        #: readiness signal: a replica with residency warmup still in
        #: flight must not be routed cold traffic (/readyz is 503)
        self._pending_controls: list = []
        #: per-replica latency attribution (fleets): when set, the
        #: request-latency histogram families record under this tag
        #: instead of the model name, so per-replica graftpath verdicts
        #: stay separable while the global sums are unchanged
        self._metrics_tag = metrics_tag
        #: chaos hook (drills/self-test): armed by :meth:`kill`, raises
        #: ThreadCrash at the top of the loop's next cycle — same
        #: test-only posture as ``_test_dispatch_delay_s`` below
        self._crash_armed = False
        self._hb = None
        self._thread: threading.Thread | None = None
        #: test hook: an injected per-dispatch sleep (the fleet's slow
        #: replica, the serve drills, tests/test_serve.py)
        self._test_dispatch_delay_s = 0.0
        #: slowest request seen (monotone): the flight-recorder
        #: exemplar threshold — serve-loop-only state, no lock needed
        self._slowest_s = 0.0
        #: test hook: an injected per-control sleep so tests can
        #: pin the /readyz warmup window deterministically
        self._test_control_delay_s = 0.0
        self._start_loop()
        with _SERVERS_LOCK:
            _SERVERS.append(self)
        from ..obs.serve import register_readiness

        register_readiness(self._unit, self.ready)

    # -- lifecycle -------------------------------------------------------
    def _start_loop(self) -> None:
        # the ONE sanctioned off-main dispatch thread: the literal name
        # is the contract (see SERVE_THREAD_NAME); all device work for
        # serving is serialized inside this loop
        thread = threading.Thread(
            target=self._loop, daemon=True, name="dask-ml-tpu-serve",
        )
        self._thread = thread
        self._hb = _supervisor.register(
            self._unit, "serve", thread=thread)
        thread.start()

    def close(self, timeout: float = 5.0) -> None:
        """Stop the loop, reject everything still queued (reason
        ``shutdown``), and retire the supervised unit."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        for item in self._batcher.drain_pending() + self._drain_inflight():
            if isinstance(item, Request):
                reject(item, "shutdown", "server closed")
            elif isinstance(item, _Control) and item.future is not None:
                item.future.set_exception(
                    RequestRejected("shutdown", "server closed"))
        if self._hb is not None:
            self._hb.retire()
        from ..obs.serve import unregister_readiness

        unregister_readiness(self._unit)
        with _SERVERS_LOCK:
            if self in _SERVERS:
                _SERVERS.remove(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    @staticmethod
    def _unresolved(item) -> bool:
        fut = getattr(item, "future", None)
        return fut is not None and not fut.done()

    def _drain_inflight(self) -> list:
        with self._lock:
            out, self._inflight = self._inflight, []
            out += self._replay
            self._replay = []
        return [r for r in out if self._unresolved(r)]

    # -- public request API (caller threads) -----------------------------
    def _offer_control(self, item: _Control) -> ServeFuture:
        self._check_open()
        with self._lock:
            self._pending_controls.append(item.future)
        self._batcher.offer_control(item)
        self._ensure_alive()
        return item.future

    def submit_load(self, name: str, model) -> ServeFuture:
        """Queue a model admission WITHOUT blocking (the fleet respawn /
        rolling-deploy path: warmup runs on the serve thread while the
        caller keeps routing traffic elsewhere; :meth:`ready` — and the
        ``/readyz`` probe — stay false until every queued control has
        resolved)."""
        return self._offer_control(_Control("load", name, model,
                                            ServeFuture(self)))

    def load(self, name: str, model, timeout: float = 60.0):
        """Admit a fitted model under ``name`` (replacing any previous
        holder).  Blocks until the model is resident and its predict
        programs are warm — load is the expensive moment, so the steady
        request path never compiles."""
        return self.submit_load(name, model).result(timeout)

    def unload(self, name: str, timeout: float = 30.0) -> bool:
        fut = self._offer_control(
            _Control("unload", name, future=ServeFuture(self)))
        return fut.result(timeout)

    # -- drain / readiness / chaos (caller threads) ----------------------
    def drain(self, timeout_s: float = 5.0) -> bool:
        """The rolling-deploy drain barrier: stop admitting requests
        (``submit()`` rejects with reason ``draining`` immediately —
        never queued into a loop about to be refreshed) and wait for
        the queue plus the in-flight batch to flush.  Control items
        (loads/unloads) stay admissible: the refresh itself rides the
        drained loop.  Returns True when quiesced within the timeout."""
        with self._lock:
            self._draining = True
        _registry().counter("serve.drain").inc()
        obs.event("serve.drain", label=self.label)
        deadline = time.monotonic() + float(timeout_s)
        while True:
            if self._quiesced():
                return True
            if time.monotonic() >= deadline:
                return self._quiesced()
            # a dead loop can never flush: the liveness poll restarts
            # it (or sweeps, past the budget) so drain cannot hang
            self._ensure_alive()
            time.sleep(0.005)

    def resume(self) -> None:
        """Re-admit traffic after a drain (the deploy's re-admission
        edge; the router additionally gates on :meth:`ready`)."""
        with self._lock:
            self._draining = False

    def _quiesced(self) -> bool:
        """No queued requests and no unresolved in-flight work.  The
        gather loop holds a popped batch for a moment before publishing
        it as in-flight — a microsecond window the drain poll may race;
        the deploy path is still safe because the refresh is a queued
        control, ordered after any such batch on the same loop."""
        if self._batcher.qsize() > 0:
            return False
        with self._lock:
            pending = any(self._unresolved(r)
                          for r in self._inflight + self._replay)
        return not pending

    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def ready(self) -> bool:
        """The READINESS half of the health split (satellite of
        design.md §22): alive AND not draining AND residency warmup
        complete (no queued control still unresolved).  ``/healthz``
        keeps answering liveness (503 only on a DEAD unit); ``/readyz``
        is 503 until this is true — the router must not route cold
        traffic to a replica still compiling its rungs."""
        t = self._thread
        if (self._closed or self._failed is not None
                or t is None or not t.is_alive()):
            return False
        with self._lock:
            if self._draining:
                return False
            self._pending_controls = [
                f for f in self._pending_controls if not f.done()]
            return not self._pending_controls

    def kill(self) -> None:
        """Chaos hook (drills / fleet self-test): arm a simulated hard
        death — the serve loop raises ThreadCrash at the top of its
        next cycle, exactly as if the runtime killed the thread, with
        whatever was queued left behind for the supervised-restart /
        fleet-respawn paths to recover."""
        self._crash_armed = True

    @staticmethod
    def _reject_submit(reason: str, detail: str, model: str = ""):
        """The submit-time shed path: counted + flight-recorded like
        every other rejection (the every-rejection-is-a-record
        contract), then raised to the caller."""
        _registry().counter("serve.rejected", reason).inc()
        obs.event("serve.reject", model=model, reason=reason)
        raise RequestRejected(reason, detail)

    def submit(self, name: str, X, *, deadline_s: float | None = None,
               proba: bool = False) -> ServeFuture:
        """Queue one predict request; returns its future.  Admission
        control happens HERE: a full queue, an unknown model, an
        oversize batch, or a proba request the model's loss cannot
        honor raises :class:`RequestRejected` immediately."""
        self._check_open()
        with self._lock:
            draining = self._draining
        if draining:
            # reject NOW, loudly — queueing into a loop behind a drain
            # barrier would strand the request in a dying generation
            self._reject_submit(
                "draining",
                f"server {self.label!r} is draining for a refresh",
                name)
        _registry().counter("serve.requests").inc()
        xa = np.asarray(X, dtype=np.float32)
        if xa.ndim == 1:
            xa = xa[None, :]
        if xa.ndim != 2:
            self._reject_submit(
                "bad_input",
                f"expected 1-D or 2-D rows, got ndim={xa.ndim}", name)
        rm = self.registry.get(name)
        if rm is None:
            self._reject_submit(
                "unknown_model",
                f"no model {name!r} loaded (have {self.registry.names()})",
                name)
        if proba and rm.proba_loss is None:
            self._reject_submit(
                "bad_input",
                f"model {name!r} cannot serve probabilities "
                f"(kind={rm.kind}, loss without a probability transform)",
                name)
        if rm.n_features >= 0 and xa.shape[1] != rm.n_features:
            self._reject_submit(
                "bad_input",
                f"model {name!r} expects {rm.n_features} features, "
                f"got {xa.shape[1]}", name)
        if xa.shape[0] > self.max_batch:
            self._reject_submit(
                "oversize",
                f"{xa.shape[0]} rows > max_batch {self.max_batch}; bulk "
                f"scoring belongs to _partial.predict", name)
        fut = ServeFuture(self)
        if xa.shape[0] == 0:
            if proba:
                fut.set_result(np.empty((0, max(len(rm.classes), 2)),
                                        np.float32))
            else:
                dtype = (rm.classes.dtype if rm.classes is not None
                         else np.float32)
                fut.set_result(np.empty((0,), dtype=dtype))
            return fut
        dl = self.default_deadline_s if deadline_s is None \
            else float(deadline_s)
        req = Request(name, xa, fut, dl,
                      mode="proba" if proba else "label")
        self._ensure_alive()
        self._batcher.offer(req)  # raises queue_full here, not later
        return fut

    def predict(self, name: str, X, *, timeout: float | None = 30.0,
                deadline_s: float | None = None):
        """Synchronous predict: ``submit`` + ``result``."""
        return self.submit(name, X, deadline_s=deadline_s).result(timeout)

    def predict_proba(self, name: str, X, *, timeout: float | None = 30.0,
                      deadline_s: float | None = None):
        """Synchronous per-class probabilities (classifiers with a
        probability loss): the margins transform runs on device with
        the margins buffer DONATED — probabilities overwrite margins in
        place in HBM."""
        return self.submit(name, X, deadline_s=deadline_s,
                           proba=True).result(timeout)

    def _check_open(self) -> None:
        if self._closed:
            self._reject_submit("shutdown", "server closed")
        if self._failed is not None:
            self._reject_submit(
                "serve_down",
                f"serve loop failed terminally: {self._failed}")

    # -- liveness / recovery (caller threads) ----------------------------
    def _ensure_alive(self) -> None:
        """The consumer-side liveness poll: a dead serve loop is
        detected at the next submit or future wait, restarted within
        the fault budget with its in-flight batch replayed.  A closed
        or terminally-failed server SWEEPS instead: a request that
        raced past ``_check_open`` into the queue after the shutdown
        drain would otherwise be orphaned — every waiter's poll runs
        this, so such a straggler resolves within one poll interval."""
        if self._closed or self._failed is not None:
            reason = "shutdown" if self._closed else "serve_down"
            for item in self._batcher.drain_pending():
                if isinstance(item, Request):
                    reject(item, reason, "server is down; late arrival "
                           "swept at the liveness poll")
                elif getattr(item, "future", None) is not None:
                    item.future.set_exception(RequestRejected(
                        reason, "server is down"))
            return
        t = self._thread
        if t is None or t.is_alive():
            return
        with self._lock:
            t = self._thread
            if t is None or t.is_alive() or self._closed or self._failed:
                return
            _supervisor.note_death(
                "serve", self._hb.name,
                error="serve loop died without reporting")
            if not self._budget.acquire("serve-restart"):
                self._failed = RuntimeError(
                    f"serve loop for {self.label!r} is dead and the "
                    f"fault budget is exhausted "
                    f"({self._budget.snapshot()})")
                pending = [r for r in self._inflight + self._replay
                           if self._unresolved(r)]
                self._inflight, self._replay = [], []
            else:
                pending = None
                # replay the batch the dead loop had drained — control
                # items (loads/unloads) included, so no future is ever
                # left hanging: predict mutates nothing and admit
                # replaces-by-name, so re-running either is exact;
                # expired requests get their deadline rejection at
                # dispatch
                self._replay = [r for r in self._inflight + self._replay
                                if self._unresolved(r)]
                self._inflight = []
                # restart INSIDE the lock: a concurrent caller's
                # liveness check must see the fresh thread, not race a
                # second restart (and a second budget spend)
                self._start_loop()
        if pending is not None:
            for r in pending:
                if isinstance(r, Request):
                    reject(r, "serve_down",
                           "serve loop dead, budget spent")
                elif r.future is not None:
                    r.future.set_exception(RequestRejected(
                        "serve_down", "serve loop dead, budget spent"))
            for item in self._batcher.drain_pending():
                if isinstance(item, Request):
                    reject(item, "serve_down",
                           "serve loop dead, budget spent")
                elif isinstance(item, _Control) and item.future is not None:
                    item.future.set_exception(
                        RequestRejected("serve_down",
                                        "serve loop dead, budget spent"))
            return
        _supervisor.note_restart("serve", self._hb.name)
        obs.event("serve.restart", label=self.label)

    def _beat(self) -> None:
        # a diagnostics.reset() wiped the supervisor table: re-register
        # so the unit stays supervised (same posture as the metrics
        # endpoint's _beat)
        if _supervisor.lookup(self._hb.name) is not self._hb:
            self._hb = _supervisor.register(
                self._hb.name, "serve", thread=self._thread)
        self._hb.beat()

    def _refresh_knobs(self) -> None:
        """Per-DRAIN-CYCLE knob refresh (graftpilot): pick up live
        window / max-batch overrides before each gather.  Lock-free
        attribute reads, never ``os.environ`` — the config-module
        posture holds.  Max-batch clamps to the construction value (the
        compile ceiling): a live raise must never force a steady-state
        compile on this thread."""
        w_ms = (None if self._window_pinned
                else _knobs.override_or("serve_window_ms", None))
        if w_ms is not None:
            w_s = max(float(w_ms), 0.0) / 1e3
            if w_s != self.window_s:
                self.window_s = w_s
                self._batcher.window_s = w_s
        mb = (None if self._max_batch_pinned
              else _knobs.override_or("serve_max_batch", None))
        if mb is not None:
            mb = min(max(int(mb), 1), self._max_batch_ceiling)
            if mb != self.max_batch:
                self.max_batch = mb
                self._batcher.max_batch = mb

    # -- the loop (serve thread) -----------------------------------------
    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                if self._crash_armed:
                    self._crash_armed = False
                    raise _ThreadCrash("injected replica kill")
                self._refresh_knobs()
                with self._lock:
                    replay, self._replay = self._replay, []
                batch = replay or self._batcher.gather(self._stop)
                if not batch:
                    continue
                requests = [b for b in batch if isinstance(b, Request)]
                controls = [b for b in batch if isinstance(b, _Control)]
                # the WHOLE drained batch — controls included — is
                # in-flight until fully processed: a crash mid-batch
                # must replay queued loads too, not leave their
                # futures hanging
                with self._lock:
                    self._inflight = list(batch)
                if requests:
                    # drill point: a ThreadCrash here simulates the loop
                    # dying WITH a drained batch in hand — the replay
                    # path's exact case.  Fired once per drained batch
                    # OF REQUESTS, so drill call numbers are
                    # deterministic.
                    _maybe_fault("serve-loop")
                    self._beat()
                    self._dispatch(requests)
                for c in controls:
                    self._handle_control(c)
                with self._lock:
                    self._inflight = []
        except _ThreadCrash:
            return  # simulated hard death: vanish without reporting
        except BaseException as exc:  # driver bug: fail loud, then die
            obs.event("serve.fault", label=self.label,
                      error=obs.fmt_exc(exc))
            logger.exception("serve loop %r died", self.label)
            for r in self._drain_inflight():
                r.future.set_exception(exc)
            return

    def _handle_control(self, c: _Control) -> None:
        try:
            if self._test_control_delay_s:
                time.sleep(self._test_control_delay_s)
            if c.op == "load":
                self.registry.admit(c.name, c.model)
                out = True
            elif c.op == "unload":
                out = self.registry.evict(c.name)
            else:  # pragma: no cover - future ops
                raise ValueError(f"unknown control op {c.op!r}")
            if c.future is not None:
                c.future.set_result(out)
        except BaseException as exc:
            if c.future is not None:
                c.future.set_exception(exc)
            else:  # pragma: no cover - loads always carry futures
                logger.exception("serve control %s(%r) failed", c.op,
                                 c.name)

    def _tag(self, model: str) -> str:
        """Latency-family tag: the per-replica label when fleet-owned
        (per-replica verdicts stay separable), else the model name."""
        return self._metrics_tag if self._metrics_tag else model

    # -- dispatch (serve thread) -----------------------------------------
    def _dispatch(self, requests: list) -> None:
        now = time.monotonic()
        reg = _registry()
        live: dict[str, list] = {}
        for r in requests:
            reg.histogram("serve.queue_wait_s", self._tag(r.model)).record(
                now - r.t_enqueue)
            if r.expired(now):
                # stale before any device work: the deadline's whole
                # point — drop with an explicit record, spend nothing
                reject(r, "deadline",
                       f"request {r.id} expired in queue "
                       f"({now - r.t_enqueue:.3f}s > deadline)")
            else:
                live.setdefault(r.model, []).append(r)
        if not live:
            return
        if self._test_dispatch_delay_s:
            time.sleep(self._test_dispatch_delay_s)
        # group same-pack models dispatched THIS batch into one lane
        # program; everything else goes single-model
        by_pack: dict = {}
        singles: list = []
        for name, reqs in live.items():
            rm = self.registry.get(name)
            if rm is None:
                for r in reqs:
                    reject(r, "unknown_model",
                           f"model {name!r} unloaded while queued")
                continue
            # re-validate against the CURRENT model: a hot-swap/reload
            # between submit and dispatch can change the feature width
            # or drop proba capability — shed exactly the now-invalid
            # requests (recorded, per the contract) instead of letting
            # a raw shape error poison the whole coalesced group
            ok = []
            for r in reqs:
                if rm.n_features >= 0 and r.x.shape[1] != rm.n_features:
                    reject(r, "bad_input",
                           f"model {name!r} was replaced while queued "
                           f"(now expects {rm.n_features} features, "
                           f"request has {r.x.shape[1]})")
                elif r.mode == "proba" and rm.proba_loss is None:
                    reject(r, "bad_input",
                           f"model {name!r} was replaced while queued "
                           f"and no longer serves probabilities")
                else:
                    ok.append(r)
            if not ok:
                continue
            reqs = ok
            if rm.pack_key is not None and \
                    all(r.mode == "label" for r in reqs):
                # proba requests stay single-model: the probability
                # transform is static per model loss, which may differ
                # across a pack's lanes
                by_pack.setdefault(rm.pack_key, []).append((rm, reqs))
            else:
                singles.append((rm, reqs))
        for key, groups in by_pack.items():
            if len(groups) >= 2:
                self._run_group(lambda g=groups, k=key:
                                self._dispatch_pack(k, g),
                                [r for _, reqs in groups for r in reqs])
            else:
                singles.extend(groups)
        for rm, reqs in singles:
            self._run_group(lambda rm=rm, reqs=reqs:
                            self._dispatch_single(rm, reqs), reqs)

    def _run_group(self, fn, reqs: list) -> None:
        """One dispatch group: a failure poisons ONLY its requests'
        futures — the loop (and the other groups in the batch) keep
        serving."""
        try:
            fn()
        except BaseException as exc:
            if isinstance(exc, _ThreadCrash):
                # simulated hard death (drills): vanish WITHOUT
                # resolving the futures — they are in-flight state the
                # restart path must replay, exactly like a real crash
                raise
            obs.event("serve.dispatch_fault", label=self.label,
                      error=obs.fmt_exc(exc))
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(exc)

    def _fulfill(self, reqs: list, preds_by_req: list,
                 t_dispatch0: float | None = None,
                 t_dispatched: float | None = None) -> None:
        """Resolve the group's futures and record each request's exact
        latency split (design.md §19).  Four CONTIGUOUS legs per
        request — stamped on one clock, so they sum to ``request_s``
        exactly:

        * ``queue``  — submit → popped off the admission queue;
        * ``window`` — popped → this group's dispatch began (the gather
          window's coalescing wait plus batch grouping);
        * ``device`` — dispatch began → the device program call
          returned (staging put + program enqueue; on an inline/sync
          backend the execution itself — on an async one the residual
          device time surfaces in the fetch leg, same honesty note as
          ``diagnostics._sync``);
        * ``fetch``  — program call returned → future resolved (result
          fetch, host decode, per-request slice-back).
        """
        reg = _registry()
        done = time.monotonic()
        for r, p in zip(reqs, preds_by_req):
            r.future.set_result(p)
            lat = done - r.t_enqueue
            reg.histogram("serve.request_s", self._tag(r.model)).record(lat)
            if t_dispatch0 is None or t_dispatched is None or \
                    r.t_dequeue is None:
                continue  # a path without stamps records only the total
            split = {
                "queue": max(r.t_dequeue - r.t_enqueue, 0.0),
                "window": max(t_dispatch0 - r.t_dequeue, 0.0),
                "device": max(t_dispatched - t_dispatch0, 0.0),
                "fetch": max(done - t_dispatched, 0.0),
            }
            for leg, dt in split.items():
                reg.histogram(f"serve.req_{leg}_s",
                              self._tag(r.model)).record(dt)
            # slowest-request exemplar: a monotone-max record in the
            # flight recorder, so a post-mortem shows WHERE the worst
            # request's time went (trace id + split), not just that a
            # p99 existed
            if lat > self._slowest_s:
                self._slowest_s = lat
                obs.event(
                    "serve.slow_request", request=r.id, model=r.model,
                    request_ms=round(lat * 1e3, 3),
                    queue_ms=round(split["queue"] * 1e3, 3),
                    window_ms=round(split["window"] * 1e3, 3),
                    device_ms=round(split["device"] * 1e3, 3),
                    fetch_ms=round(split["fetch"] * 1e3, 3))

    @staticmethod
    def _concat_rows(reqs: list) -> np.ndarray:
        return (reqs[0].x if len(reqs) == 1
                else np.concatenate([r.x for r in reqs]))

    def _dispatch_single(self, rm, reqs: list) -> None:
        import jax.numpy as jnp

        from .._partial import stage_predict_block
        from . import programs as _sprog

        reg = _registry()
        t_dispatch0 = time.monotonic()  # the group's device leg begins
        X = self._concat_rows(reqs)
        n_real = X.shape[0]
        self.registry.touch(rm)
        probs = None
        if rm.kind == "generic":
            if rm.device_native:
                # device-native generics dispatch their own jitted
                # predict over BUCKET-PADDED rows (the same shared
                # stage_predict_block discipline, same slice-back
                # contract) so every request shape resolves to a rung
                # the load-time warmup already compiled — the steady
                # request path never compiles for ANY admitted model
                padded, n = stage_predict_block(X, self.registry.policy)
                m = rm.model.predict(padded)
                t_dispatched = time.monotonic()
                preds = np.asarray(m)
                if n is not None:
                    preds = preds[:n]
            else:
                # host estimators see RAW rows — the same device-native
                # gate _partial.predict applies: padding a host model's
                # input wastes its whole-batch compute and is only
                # exact for strictly row-wise predicts
                preds = rm.model.predict(X)
                t_dispatched = time.monotonic()
                preds = np.asarray(preds)
        else:
            # the ONE predict-staging entry the offline plane also
            # uses, so the pad discipline cannot drift between planes
            padded, _ = stage_predict_block(X, self.registry.policy)
            self.registry.ensure_resident(rm)
            xb = jnp.asarray(padded)
            m = _sprog.margins(rm.coef, rm.intercept, xb)
            t_dispatched = time.monotonic()  # program enqueued
            mnp = np.asarray(m)  # fetched BEFORE the transform below
            if any(r.mode == "proba" for r in reqs):
                # in-place on device: proba donates (and overwrites)
                # the margins buffer — the host copy above serves the
                # label decodes in the same coalesced batch
                p = _sprog.proba(m, loss=rm.proba_loss)
                probs = rm.decode_proba(np.asarray(p))
            preds = rm.decode(mnp)
        reg.counter("serve.dispatches", rm.name).inc()
        reg.histogram("serve.batch_rows").record(float(n_real))
        reg.histogram("serve.batch_requests").record(float(len(reqs)))
        out, lo = [], 0
        for r in reqs:
            src = probs if r.mode == "proba" else preds
            out.append(src[lo:lo + r.n])
            lo += r.n
        self._fulfill(reqs, out, t_dispatch0, t_dispatched)

    def _dispatch_pack(self, key, groups: list) -> None:
        """Requests for >= 2 homogeneous models in one window: ONE
        vmapped program over the residency registry's lane stack.  Each
        requested lane carries its own bucket-padded rows; lanes with no
        requests this window ride along as zeros (the lane win is
        amortized dispatch, measured 1.6–7.6x at K=4–64)."""
        import jax.numpy as jnp

        from . import programs as _sprog

        reg = _registry()
        t_dispatch0 = time.monotonic()  # the group's device leg begins
        pack = self.registry._packs[key]
        for rm, _ in groups:
            self.registry.ensure_resident(rm)
            self.registry.touch(rm)
        coefs, intercepts = self.registry.ensure_pack(pack)
        lanes = pack.lanes()
        d = int(coefs.shape[1])
        from .. import programs as _programs

        rows = {rm.name: sum(r.n for r in reqs) for rm, reqs in groups}
        b = _programs.bucket_rows(max(rows.values()),
                                  policy=self.registry.policy)
        xs = np.zeros((len(pack.members), b, d), np.float32)
        for rm, reqs in groups:
            lo = 0
            lane = lanes[rm.name]
            for r in reqs:
                xs[lane, lo:lo + r.n] = r.x
                lo += r.n
        m = _sprog.lane_margins(coefs, intercepts, jnp.asarray(xs))
        t_dispatched = time.monotonic()  # program enqueued
        out = np.asarray(m)
        n_requests = 0
        for rm, reqs in groups:
            lane_m = out[lanes[rm.name]]
            preds = rm.decode(lane_m)
            outs, lo = [], 0
            for r in reqs:
                outs.append(preds[lo:lo + r.n])
                lo += r.n
            self._fulfill(reqs, outs, t_dispatch0, t_dispatched)
            reg.counter("serve.dispatches", rm.name).inc()
            n_requests += len(reqs)
        reg.counter("serve.lane_dispatches").inc()
        reg.histogram("serve.batch_rows").record(
            float(sum(rows.values())))
        reg.histogram("serve.batch_requests").record(float(n_requests))

    # -- books -----------------------------------------------------------
    def report(self) -> dict:
        """This server's residency + queue books (the registry metrics
        themselves are global: ``serve.*`` families in
        ``diagnostics.serve_report()``)."""
        return {
            "label": self.label,
            "alive": bool(self._thread is not None
                          and self._thread.is_alive()),
            "ready": self.ready(),
            "draining": self.draining(),
            "closed": self._closed,
            "failed": (None if self._failed is None
                       else str(self._failed)),
            "max_batch": self.max_batch,
            "window_s": self.window_s,
            "queue_depth": self._batcher.depth,
            "queued": self._batcher.qsize(),
            "budget": self._budget.snapshot(),
            "residency": self.registry.report(),
        }


def report() -> dict:
    """Module-level serving view — ``diagnostics.serve_report()``:
    every live server's books plus the registry's ``serve.*`` metric
    families (request/queue-wait latency quantiles, batch occupancy,
    rejections by reason, residency gauges)."""
    reg = _registry()
    with _SERVERS_LOCK:
        servers = list(_SERVERS)
    metrics: dict = {}
    for name, tag, inst in reg.export_items():
        if not name.startswith("serve."):
            continue
        key = f"{name}{{{tag}}}" if tag else name
        snap = getattr(inst, "snapshot", None)
        metrics[key] = snap() if callable(snap) else inst.value
    return {
        "servers": [s.report() for s in servers],
        "metrics": dict(sorted(metrics.items())),
    }
