"""Structured spans: the event spine of one fit, as a tree.

A **span** is a named, timed scope (``with obs.span("fit"): ...``); an
**event** is a point-in-time record attached to the innermost open span
(a retry, a checkpoint save, a sanitizer violation).  Completed records
land in a per-thread **ring buffer** — appends never contend across
threads (each thread owns its deque; the global registry of rings is
touched once per thread lifetime) — and, when a JSONL sink is armed
(``DASK_ML_TPU_TRACE``), stream to disk as they complete.

Parentage rules (docs/design.md §11):

1. Default: the innermost open span on the CURRENT thread's stack.
2. ``parent=``: explicit parent id — used with ``detached=True`` for
   async scopes (search rounds/brackets interleave many coroutines on
   one loop thread, so stack-parentage would cross-link them; a
   detached span never touches the thread stack).
3. ``adopt(parent_id)``: thread stitching — a worker thread (the
   prefetch worker, an executor unit) enters ``adopt`` with the owning
   fit's span id; spans it opens with an empty local stack attach there
   instead of becoming roots.  This is how the prefetch worker's
   ``pipeline.parse``/``pipeline.stage`` spans appear inside the
   consumer's ``pipeline.stream`` tree.

A span that completes with no parent by any rule is a **root**; the most
recent root is what ``diagnostics.run_report()`` assembles into the
per-fit tree.  Recording is live when ``enable()`` (or a set
``DASK_ML_TPU_TRACE``) armed it, **or while a jax profiler session is
running** (``jax.profiler.start_trace``, ``diagnostics.trace``): the
session is the switch, so a traced run needs no other.  Under a session
a span that nests on its thread also opens a
``jax.profiler.TraceAnnotation`` of the same name and attributes plus
``fit=<root span id>``, so the program's spans lie in the ``.xplane.pb``
on the device trace's own clock; the ring record is written either way.
Detached spans and ``record_span`` (which do not nest on a thread) stay
ring-only.  With neither armed ``span()`` costs one flag read and the
session check and returns a shared no-op.  The conftest arms recording
for every test run so a hung test's watchdog dump can show the open
span path.
Events additionally feed the always-on flight recorder (:mod:`.flight`)
even while tracing is disabled — faults and checkpoints must leave a
post-mortem regardless.
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading

from .._locks import make_lock
import time

from . import flight as _flight

__all__ = [
    "SCHEMA_VERSION",
    "TRACE_ENV",
    "RING_ENV",
    "Span",
    "span",
    "record_span",
    "event",
    "fmt_exc",
    "adopt",
    "current_span_id",
    "enable",
    "disable",
    "enabled",
    "open_span_paths",
    "last_root",
    "span_records",
    "span_tree",
    "clear_spans",
]

#: grafttrace record-schema version, stamped into every JSONL header and
#: bumped on any field rename/removal (additions are compatible)
SCHEMA_VERSION = 1

#: policy knob: a path arms tracing at import and streams every
#: completed span/event there as schema-versioned JSONL
TRACE_ENV = "DASK_ML_TPU_TRACE"

#: policy knob: per-thread completed-span ring capacity (default 8192)
RING_ENV = "DASK_ML_TPU_TRACE_RING"

_DEFAULT_RING = 8192

_ids = itertools.count(1)  # CPython next() is atomic: lock-free span ids

_TLS = threading.local()  # .stack: open spans; .ring: completed records
_REG_LOCK = make_lock("obs.spans")
_RINGS: dict[int, tuple[str, collections.deque, list]] = {}
_LAST_ROOT: "SpanRecord | None" = None
#: open span id -> its root's id, so that a span parented across threads
#: (``adopt``, ``parent=``) still names the fit it belongs to
_OPEN_ROOTS: dict[int, int] = {}


class _State:
    __slots__ = ("enabled", "ring_size", "sink", "hooks", "session")

    def __init__(self):
        self.enabled = False
        self.ring_size = _DEFAULT_RING
        self.sink = None  # JsonlSink | None
        # obs.jaxhooks and its session check, once jax is in the process
        self.hooks = self.session = None


_STATE = _State()


def _session() -> bool:
    """Whether a jax profiler session is running.  Resolved through
    :mod:`.jaxhooks` (the one obs module that touches jax) the first
    time jax is in the process: before that no session can exist, and
    ``obs`` itself never imports jax."""
    running = _STATE.session
    if running is None:
        if "jax" not in sys.modules:
            return False
        from . import jaxhooks

        _STATE.hooks = jaxhooks
        running = _STATE.session = jaxhooks.session_check()
    return running()


def _live() -> bool:
    return _STATE.enabled or _session()


class SpanRecord:
    """One completed span or point event (events have ``t1 == t0``)."""

    __slots__ = ("kind", "span_id", "parent_id", "name", "t0", "t1",
                 "thread", "attrs", "error")

    def __init__(self, kind, span_id, parent_id, name, t0, t1, thread,
                 attrs, error=None):
        self.kind = kind
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.thread = thread
        self.attrs = attrs
        self.error = error

    def as_dict(self) -> dict:
        d = {
            "kind": self.kind, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "t0": round(self.t0, 9), "t1": round(self.t1, 9),
            "dur_s": round(self.t1 - self.t0, 9), "thread": self.thread,
        }
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.error:
            d["error"] = self.error
        return d


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
        with _REG_LOCK:
            ident = threading.get_ident()
            ring = _RINGS.get(ident, (None, None, None))[1]
            if ring is None:
                ring = collections.deque(maxlen=_STATE.ring_size)
            _RINGS[ident] = (threading.current_thread().name, ring, st)
    return st


def _ring() -> collections.deque:
    _stack()  # ensure registration
    return _RINGS[threading.get_ident()][1]


def _emit(rec: SpanRecord) -> None:
    global _LAST_ROOT
    _ring().append(rec)
    if rec.kind == "span" and rec.parent_id is None:
        _LAST_ROOT = rec
    sink = _STATE.sink
    if sink is not None:
        sink.write(rec)


class _Noop:
    """Shared do-nothing span for the disabled path (one flag read, no
    allocation)."""

    __slots__ = ()
    span_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _Noop()


class Span:
    """An OPEN span; completes (and records) on ``__exit__``."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "_detached",
                 "_t0", "_pushed", "_annotate", "_annotation")

    def __init__(self, name: str, parent_id: int | None,
                 detached: bool, attrs: dict, annotate: bool = False):
        self.name = name
        self.attrs = attrs
        self.span_id = next(_ids)
        self.parent_id = parent_id
        self._detached = detached
        self._pushed = False
        self._t0 = 0.0
        self._annotate = annotate and not detached
        self._annotation = None

    def __enter__(self):
        st = None
        if not self._detached:
            st = _stack()
            if self.parent_id is None:
                if st:
                    self.parent_id = st[-1].span_id
                else:
                    self.parent_id = getattr(_TLS, "adopt", None)
            st.append(self)
            self._pushed = True
        root = (self.span_id if self.parent_id is None
                else _OPEN_ROOTS.get(self.parent_id, self.parent_id))
        _OPEN_ROOTS[self.span_id] = root
        if self._annotate:
            self._annotation = _STATE.hooks.annotation(
                self.name, **self.attrs, fit=root)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (a solve's
        counts): onto the record, and onto the open annotation."""
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        _OPEN_ROOTS.pop(self.span_id, None)
        if self._pushed:
            self._pushed = False
            st = _stack()
            if st and st[-1] is self:
                st.pop()
            else:  # pragma: no cover - misnested exit: drop, don't corrupt
                try:
                    st.remove(self)
                except ValueError:
                    pass
        # StopIteration/GeneratorExit are control flow, not failures: a
        # span around a source pull (pipeline.parse wraps next(src))
        # ends every healthy stream with one — stamping it as an error
        # would put a false failure on every successful fit's tree
        failed = exc_type is not None and not issubclass(
            exc_type, (StopIteration, GeneratorExit))
        _emit(SpanRecord(
            "span", self.span_id, self.parent_id, self.name, self._t0,
            t1, threading.current_thread().name, self.attrs,
            error=(fmt_exc(exc) if failed and exc is not None
                   else f"{exc_type.__name__}" if failed else None),
        ))
        return False


def span(name: str, *, parent: int | None = None, detached: bool = False,
         **attrs):
    """Open a named span (see module docstring for parentage rules).

    ``detached=True`` skips the thread stack: the span is parented ONLY
    by the explicit ``parent`` and never becomes an implicit parent —
    the form async scopes must use.  Returns a no-op when recording is
    neither enabled nor inside a profiler session; inside one, a span
    that is not detached also opens a ``TraceAnnotation``.
    """
    if _session():
        return Span(name, parent, detached, attrs, annotate=True)
    if not _STATE.enabled:
        return _NOOP
    return Span(name, parent, detached, attrs)


def record_span(name: str, t0: float, t1: float, *,
                parent: int | None = None, **attrs) -> None:
    """Record an ALREADY-ELAPSED interval as a completed span.

    The retroactive form :mod:`.critical`'s wait signals need: a
    contiguous queue wait is only known to have been a wait once it
    ends (the consumer's ``q.get`` loop, the reorder-merge wait), so
    the producer stamps ``t0`` when the wait begins and calls this when
    it resolves.  Parentage follows :func:`event`'s rule (innermost
    open span on this thread, else the adopt target) unless ``parent``
    is given — pass an explicit parent from rootless threads (dataset
    readers), or skip the call entirely when no parent exists, so a
    retroactive record can never steal ``last_root`` from a real fit.
    Ring-only (it opens no annotation: the interval is already over).
    No-op while recording is not live."""
    if not _live():
        return
    if parent is None:
        st = getattr(_TLS, "stack", None)
        parent = (st[-1].span_id if st
                  else getattr(_TLS, "adopt", None))
    if parent is None:
        # a retroactive record may not become a root: _emit would
        # publish it as last_root and run_report's tree would show a
        # stray wait instead of the fit — drop instead (the registry
        # histograms the producers also write keep the totals)
        return
    _emit(SpanRecord(
        "span", next(_ids), parent, name, float(t0),
        max(float(t1), float(t0)), threading.current_thread().name,
        attrs))


def event(name: str, *, parent: int | None = None, **attrs) -> None:
    """Record a point event: onto the span tree when recording is live
    (enabled, or inside a profiler session), and ALWAYS into the flight recorder (faults/checkpoints must leave a
    post-mortem even in an untraced process)."""
    _flight.record("event", name, attrs)
    if not _live():
        return
    if parent is None:
        st = getattr(_TLS, "stack", None)
        parent = (st[-1].span_id if st
                  else getattr(_TLS, "adopt", None))
    t = time.perf_counter()
    _emit(SpanRecord("event", next(_ids), parent, name, t, t,
                     threading.current_thread().name, attrs))


def fmt_exc(exc: BaseException) -> str:
    """The ONE error-string format of the event schema (design.md §11):
    ``Type: message``, capped at 200 chars — every producer (span
    errors, retry/failure events, pipeline.fault) uses this so flight
    and JSONL payloads cannot drift per site."""
    return f"{type(exc).__name__}: {exc}"[:200]


class adopt:
    """Stitch this thread's parentless spans/events under ``parent_id``
    (a span id captured on the owning thread).  Nestable; ``None``
    restores root behavior."""

    __slots__ = ("_parent", "_prev")

    def __init__(self, parent_id: int | None):
        self._parent = parent_id
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_TLS, "adopt", None)
        _TLS.adopt = self._parent
        return self

    def __exit__(self, *exc):
        _TLS.adopt = self._prev
        return False


def current_span_id() -> int | None:
    """The innermost open span id on THIS thread (None outside any span
    or with tracing disabled) — what a consumer captures before handing
    work to a worker thread for :class:`adopt` stitching."""
    st = getattr(_TLS, "stack", None)
    if st:
        return st[-1].span_id
    return getattr(_TLS, "adopt", None)


# -- lifecycle -----------------------------------------------------------
def enable(jsonl_path: str | None = None,
           ring_size: int | None = None) -> None:
    """Arm span recording.  ``jsonl_path`` additionally streams every
    completed record to a schema-versioned JSONL file (the
    ``DASK_ML_TPU_TRACE`` form); ``ring_size`` resizes FUTURE threads'
    rings (``DASK_ML_TPU_TRACE_RING``)."""
    if ring_size is not None:
        ring_size = int(ring_size)
        if ring_size < 1:
            raise ValueError(f"ring size must be >= 1, got {ring_size}")
        _STATE.ring_size = ring_size
    if jsonl_path:
        from .export import JsonlSink

        # construct BEFORE swapping: a failed re-arm (unwritable path)
        # must raise without destroying a working sink
        new_sink = JsonlSink(jsonl_path)
        old, _STATE.sink = _STATE.sink, new_sink
        if old is not None:  # re-arming: release the previous file
            old.close()
    _STATE.enabled = True
    # compile counters are part of the spine: arm the (idempotent,
    # listener-only) jax.monitoring hook alongside tracing — lazily
    # imported so the obs package itself stays jax-free for the static
    # host-only proofs
    try:
        from . import jaxhooks

        jaxhooks.install()
    except Exception:  # pragma: no cover - jax-less analysis contexts
        pass


def disable() -> None:
    """Disarm recording (rings and the flight recorder keep their
    contents; the JSONL sink is closed)."""
    _STATE.enabled = False
    sink, _STATE.sink = _STATE.sink, None
    if sink is not None:
        sink.close()


def enabled() -> bool:
    return _STATE.enabled


# -- introspection / assembly -------------------------------------------
def open_span_paths() -> dict:
    """``{thread_name: "fit > pipeline.stream > ..."}`` of currently-open
    spans — read racily by the watchdog/flight dump (forensics, not
    synchronization).  Threads sharing a name (concurrent prefetch
    workers under a pool search) are disambiguated as ``name#ident`` so
    a hang dump shows EVERY in-flight worker, not one survivor."""
    with _REG_LOCK:
        items = [(ident, name, list(st))
                 for ident, (name, _, st) in _RINGS.items()]
    open_items = [(ident, name, st) for ident, name, st in items if st]
    dup_names = {name for _, name, _ in open_items
                 if sum(1 for _, n, _ in open_items if n == name) > 1}
    out = {}
    for ident, name, st in open_items:
        key = f"{name}#{ident}" if name in dup_names else name
        out[key] = " > ".join(s.name for s in st)
    return out


def span_records() -> list:
    """All retained records across every thread ring, oldest-ish first
    (per-ring order is exact; cross-ring merged by start time)."""
    with _REG_LOCK:
        rings = [ring for _, ring, _ in _RINGS.values()]
    records: list = []
    for ring in rings:
        records.extend(ring)  # deque iteration is GIL-atomic enough
    records.sort(key=lambda r: (r.t0, r.span_id))
    return records


def last_root() -> SpanRecord | None:
    """The most recently completed ROOT span (the last whole fit/stream,
    by parentage rule)."""
    return _LAST_ROOT


def span_tree(root: SpanRecord | None = None) -> dict | None:
    """Assemble the tree under ``root`` (default: :func:`last_root`)
    from the retained rings: nested ``{name, t0, t1, dur_s, thread,
    attrs, children: [...], events: [...]}``.

    Ring-bounded by design: a tree bigger than the rings loses its
    OLDEST spans (the tail of a long fit is the interesting part); a
    child whose parent was evicted attaches to the root.
    """
    root = root if root is not None else _LAST_ROOT
    if root is None:
        return None
    records = span_records()
    by_id = {r.span_id: r for r in records}
    by_id[root.span_id] = root

    # membership: walk each record's parent chain to see if it reaches
    # the root (memoized); evicted parents inside the root's window
    # count as members parented to the root
    member: dict[int, bool] = {root.span_id: True}

    def reaches(rec0) -> bool:
        rid = rec0.span_id
        chain = []
        verdict = False
        while rid is not None and rid not in member:
            chain.append(rid)
            rec = by_id.get(rid)
            if rec is None:
                # evicted ancestor: adopt into the root iff the orphan
                # started inside the root's window (docstring contract)
                verdict = rec0.t0 >= root.t0
                rid = None
                break
            rid = rec.parent_id
        if rid is not None:
            verdict = member[rid]
        for c in chain:
            member[c] = verdict
        return verdict

    nodes: dict[int, dict] = {}

    def node_for(rec) -> dict:
        n = nodes.get(rec.span_id)
        if n is None:
            n = nodes[rec.span_id] = rec.as_dict()
            n["children"] = []
            n["events"] = []
        return n

    root_node = node_for(root)
    for rec in records:
        if rec.span_id == root.span_id or not reaches(rec):
            continue
        parent = by_id.get(rec.parent_id)
        pnode = node_for(parent) if parent is not None else root_node
        if rec.kind == "event":
            pnode["events"].append(rec.as_dict())
        else:
            pnode["children"].append(node_for(rec))
    return root_node


def clear_spans() -> None:
    """Drop retained records, the last-root pointer, and DEAD threads'
    rings (open span stacks on live threads are untouched)."""
    global _LAST_ROOT
    live = {t.ident for t in threading.enumerate()}
    with _REG_LOCK:
        for ident in [i for i in _RINGS if i not in live]:
            del _RINGS[ident]
        for _, ring, _ in _RINGS.values():
            ring.clear()
    _LAST_ROOT = None


# env arming: a set DASK_ML_TPU_TRACE turns the whole process on at
# import, streaming to that path — zero code changes at call sites.
# DASK_ML_TPU_TRACE_RING alone only SIZES the rings (api.md: a
# memory/history knob, not an arming switch — a later enable() uses it).
_env_ring = os.environ.get(RING_ENV, "").strip()
if _env_ring:
    _STATE.ring_size = int(_env_ring)
    if _STATE.ring_size < 1:
        raise ValueError(f"{RING_ENV} must be >= 1, got {_env_ring!r}")
_env_path = os.environ.get(TRACE_ENV, "").strip()
if _env_path:
    try:
        enable(jsonl_path=_env_path)
    except OSError:
        # ambient env arming must not kill `import dask_ml_tpu` over
        # an unwritable trace directory — the traced job matters more
        # than its trace.  Degrade to ring+flight recording, loudly.
        # (The explicit obs.enable(jsonl_path=...) API still raises:
        # a caller who ASKED for a file gets the error.)
        import logging

        logging.getLogger(__name__).warning(
            "grafttrace: %s=%s is unwritable; tracing continues "
            "ring-only (no JSONL stream)", TRACE_ENV, _env_path,
            exc_info=True,
        )
        enable()
