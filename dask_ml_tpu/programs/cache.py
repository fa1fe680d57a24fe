"""The central compiled-program cache every dispatch path goes through.

``jax.jit``'s own executable cache is per-wrapped-function and
invisible: nothing can ask it what is warm, pre-compile the next shape
on another thread, or report how much compile time a stream paid.  A
:class:`CachedProgram` replaces the bare ``partial(jax.jit, ...)``
idiom at the repo's step-program definitions with a cache this code
owns:

* every distinct *signature* — pytree structure + per-leaf
  (shape, dtype, weak_type, sharding) + static argument values — maps
  to ONE ahead-of-time compiled executable
  (``jitted.lower(...).compile()``), dispatched directly on later
  calls (measured: warm AOT dispatch costs the same ~18µs as the jit
  fastpath on this image);
* a *miss* compiles on the calling thread (warmup-class work, exactly
  what ``jax.jit`` would have done);
* :meth:`CachedProgram.warm` registers the signature as in-flight and
  hands the compile to the dedicated ``dask-ml-tpu-compile-ahead``
  thread (:mod:`.ahead`) — a consumer that arrives before the compile
  finishes WAITS on it (one compile, attributed to the blessed
  thread) instead of racing a duplicate;
* anything the cache cannot prove it handles — tracer arguments (the
  program is being inlined into an outer jit), unexpected keyword
  arrays, an executable that rejects the concrete operands
  (sharding/layout drift) — falls back to the plain jitted path, which
  is bit-identical by construction (same function, same jit options).

Hit / miss / ahead-hit / fallback counters and compile seconds land in
the obs metrics registry (``program.*``, tagged per program name) and
in :func:`report` — surfaced as ``diagnostics.program_report()``.

jax's persistent compilation cache (cold starts across processes: chip
runs, multihost workers) is armed when the package is imported; its
directory is placed from outside, see :func:`enable_persistent_cache`.
"""

from __future__ import annotations

import logging
import os
import threading

from .._locks import make_lock
import time

import numpy as np

import jax

from ..obs import roofline as _roofline
from ..obs import scope as _scope
from ..obs.metrics import registry as _registry

__all__ = [
    "DEFAULT_CACHE_DIR",
    "CachedProgram",
    "cached_program",
    "enable_persistent_cache",
    "report",
    "reset_counters",
]

logger = logging.getLogger(__name__)

#: where the compile cache lives when ``JAX_COMPILATION_CACHE_DIR`` does
#: not say: a FIXED path in the checkout (ignored by git).  The path is
#: part of how a later process finds the cache again, so it must not
#: come from a temporary directory, a pid or the time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: how long a consumer waits on an in-flight compile-ahead build before
#: giving up and compiling on its own thread (a safety valve, not a
#: steady-state path — ahead compiles are small step programs).
_AHEAD_WAIT_S = 120.0

_REG_LOCK = make_lock("programs.registry")
_BY_NAME: dict[str, "CachedProgram"] = {}


def enable_persistent_cache() -> str:
    """Arm jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already taken it
    and no directory is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Called when the package is imported,
    before anything compiles, so eager operations and programs outside
    :class:`CachedProgram` are kept as well.  The thresholds are opened
    (min size/time -> 0) so that the small step programs this repo
    streams are cached too.  An unwritable directory is jax's to report:
    it warns once and compiles as if there were no cache."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# -- signatures ----------------------------------------------------------

def _structure(tree, leaves: list):
    """Deterministic hashable structure token; appends leaves in order.
    Hand-rolled (tuple/list/dict/None only) so the signature path stays
    provably host-only for the stage-purity reachability analysis —
    ``_pf_stage`` implementations call :meth:`CachedProgram.warm` from
    the prefetch worker thread."""
    if tree is None:
        return "-"
    if isinstance(tree, (tuple, list)):
        return ("T", tuple(_structure(x, leaves) for x in tree))
    if isinstance(tree, dict):
        return ("D", tuple((k, _structure(tree[k], leaves))
                           for k in sorted(tree)))
    leaves.append(tree)
    return "*"


def _leaf_key(x):
    """(shape, dtype, weak_type, sharding-token) for one leaf, or None
    for a leaf the cache must not reason about (tracers, opaque
    objects).  A ShapeDtypeStruct keys identically to the concrete
    array it stands for, so a warm() built from shapes matches the
    consumer's real operands."""
    if isinstance(x, jax.core.Tracer):
        return None
    if isinstance(x, jax.ShapeDtypeStruct):
        sh = getattr(x, "sharding", None)
        return (tuple(x.shape), str(np.dtype(x.dtype)), False,
                None if sh is None else repr(sh))
    if isinstance(x, jax.Array):
        aval = x.aval
        return (tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)), _sharding_token(x))
    if isinstance(x, np.ndarray):
        return (tuple(x.shape), str(x.dtype), False, "host")
    if isinstance(x, (bool, int, float, complex, np.generic)):
        return ("py", type(x).__name__)
    return None


def _sharding_token(x) -> str | None:
    """None for plain default-device placement (what an unsharded
    lowering binds to), a repr for anything committed elsewhere —
    NamedSharding'd ShardedRows data keys distinctly from host-upload
    blocks, so one program never sees both layouts."""
    try:
        sh = x.sharding
        from jax.sharding import SingleDeviceSharding

        if isinstance(sh, SingleDeviceSharding):
            (dev,) = sh.device_set
            return None if dev == jax.devices()[0] else repr(sh)
        return repr(sh)
    except Exception:  # pragma: no cover - exotic array types
        return "unknown"


class _Entry:
    __slots__ = ("compiled", "source", "compile_s", "consumer_hits", "bad",
                 "cost")

    def __init__(self, compiled, source: str, compile_s: float):
        self.compiled = compiled
        self.source = source          # "demand" | "ahead"
        self.compile_s = compile_s
        self.consumer_hits = 0
        self.bad = False
        # XLA's static flop/byte estimate for THIS signature's
        # executable (obs/roofline.py; None when the backend cannot
        # say) — joined with the dispatch's device interval so
        # device_report() can attribute achieved FLOP/s per program
        self.cost = _roofline.capture_cost(compiled)


def _new_counters() -> dict:
    return {
        "hits": 0, "misses": 0, "ahead_hits": 0, "ahead_submitted": 0,
        "ahead_errors": 0, "bypass": 0, "fallback": 0,
        "compile_s": 0.0, "ahead_compile_s": 0.0, "saved_s": 0.0,
        "wait_s": 0.0,
    }


class CachedProgram:
    """One jit-wrapped step function behind the central cache.

    Drop-in for the ``partial(jax.jit, static_argnames=...,
    donate_argnames=...)(fn)`` idiom: call it exactly like the jitted
    function (statics as keywords).  Unknown keyword arrays, tracer
    operands, and executable/operand mismatches all route through the
    plain jitted twin — the cache can only ever change WHERE a compile
    happens, never what runs.
    """

    def __init__(self, fn, *, name: str, static_argnames=(),
                 donate_argnames=(), **jit_kwargs):
        self.name = name
        self.fn = fn
        self._static = tuple(static_argnames)
        # the one sanctioned direct jit wrap: every CachedProgram's
        # fallback/lowering twin is built here
        # graftlint: disable=jit-outside-cache -- the cache's own internal jit wrap; all call sites route through CachedProgram
        self._jitted = jax.jit(
            fn, static_argnames=tuple(static_argnames) or None,
            donate_argnames=tuple(donate_argnames) or None, **jit_kwargs)
        self._lock = make_lock("programs.cache")
        self._entries: dict = {}
        self._inflight: dict = {}
        self.counters = _new_counters()
        with _REG_LOCK:
            _BY_NAME[name] = self

    # expose the jitted twin's surface (lower/trace/etc.) for callers
    # that need the raw AOT API
    def __getattr__(self, item):
        jitted = self.__dict__.get("_jitted")
        if jitted is None:  # mid-__init__ / unpickle: no twin yet
            raise AttributeError(item)
        return getattr(jitted, item)

    # -- signature -------------------------------------------------------
    def signature(self, args, static: dict):
        leaves: list = []
        tok = _structure(args, leaves)
        keys = []
        for leaf in leaves:
            k = _leaf_key(leaf)
            if k is None:
                return None
            keys.append(k)
        try:
            stat = tuple(sorted(static.items()))
            hash(stat)
        except TypeError:
            return None
        return (tok, tuple(keys), stat)

    # -- dispatch --------------------------------------------------------
    def _run_tracked(self, fn, args, kwargs=None, cost=None):
        """Dispatch through ``fn`` with graftscope device-time tracking:
        the in-flight interval opens at the enqueue and closes when the
        outputs report ready (obs/scope.py).  ``absorb()`` keeps the
        graftsan ``ExecuteReplicated`` hook — which this same call
        funnels through while a sanitizer is active — from opening a
        duplicate interval; the cache end owns the attribution (it
        knows the program's registry name).  ``cost`` is the entry's
        captured cost_analysis on the AOT path (None on the jitted-twin
        fallback — an unattributed dispatch reports time but no work,
        honest either way)."""
        t0 = time.perf_counter()
        with _scope.absorb():
            out = fn(*args, **kwargs) if kwargs else fn(*args)
        _scope.track(self.name, t0, jax.tree_util.tree_leaves(out),
                     cost=cost)
        return out

    def __call__(self, *args, **kwargs):
        static = {k: v for k, v in kwargs.items() if k in self._static}
        if len(static) != len(kwargs):
            # non-static keyword operands: shapes the cache does not
            # model — the jitted twin handles them identically
            self._count("bypass")
            return self._run_tracked(self._jitted, args, kwargs)
        sig = self.signature(args, static)
        if sig is None:
            self._count("bypass")
            return self._run_tracked(self._jitted, args, kwargs)
        entry, how = self._lookup_or_compile(sig, args, static)
        if entry is None or entry.bad:
            self._count("fallback")
            return self._run_tracked(self._jitted, args, kwargs)
        try:
            out = self._run_tracked(entry.compiled, args, cost=entry.cost)
        except (TypeError, ValueError) as e:
            # operand/executable mismatch (these raise BEFORE execution,
            # so donated buffers are intact): permanently route this
            # signature through the jitted twin
            entry.bad = True
            self._count("fallback")
            logger.debug("program %s: compiled-call mismatch (%s); "
                         "falling back to jit", self.name, e)
            return self._run_tracked(self._jitted, args, kwargs)
        # first-consumer accounting under the lock: two threads
        # dispatching the same warm entry concurrently must not both
        # read consumer_hits == 0 and double-book the ahead hit
        with self._lock:
            first = entry.consumer_hits == 0
            entry.consumer_hits += 1
            if first and entry.source == "ahead":
                self.counters["saved_s"] += entry.compile_s
        if how == "hit":
            self._count("hits")
        if first and entry.source == "ahead":
            self._count("ahead_hits")
            _registry().counter("program.ahead_hit", self.name).inc()
        return out

    def _lookup_or_compile(self, sig, args, static):
        # single-flight per signature: whoever registers the in-flight
        # marker under the lock is THE builder; everyone else waits on
        # its event (an ahead build, or a concurrent demand miss from a
        # search-pool thread) instead of racing a duplicate backend
        # compile of the identical program
        while True:
            with self._lock:
                e = self._entries.get(sig)
                if e is not None:
                    return e, "hit"
                ev = self._inflight.get(sig)
                if ev is None:
                    self._inflight[sig] = threading.Event()
                    break  # we are the builder
            t0 = time.perf_counter()
            done = self._wait_inflight(ev)
            with self._lock:
                self.counters["wait_s"] += time.perf_counter() - t0
                e = self._entries.get(sig)
            if e is not None:
                return e, "hit"
            if not done:
                # builder wedged past the deadline — or the blessed
                # ahead thread died with this build still queued (the
                # liveness poll in _wait_inflight): safety-valve compile
                # on this thread (its eventual finish pops the marker
                # benignly; _lookup's own marker registration below is
                # what makes the duplicate at worst one extra compile)
                with self._lock:
                    if self._inflight.get(sig) is ev:
                        self._inflight.pop(sig, None)
                break
            # builder finished with no entry (its build failed — the
            # event carries the error when the ahead worker died): loop
            # — the marker is gone, so we register and build ourselves,
            # surfacing the real error on this thread
        self._count("misses")
        return self._compile_entry(sig, args, static, source="demand"), \
            "miss"

    @staticmethod
    def _wait_inflight(ev) -> bool:
        """Wait on another builder's in-flight event, with a liveness
        poll when the builder is the blessed ahead thread: a dead
        builder will never set its event (its dying drain fails queued
        markers, but a submit racing the death can strand one), so a
        dead-thread verdict converts the 120 s safety valve into an
        immediate fall-through to the synchronous compile path."""
        from . import ahead as _ahead

        if not getattr(ev, "ahead", False):
            return ev.wait(_AHEAD_WAIT_S)
        deadline = time.perf_counter() + _AHEAD_WAIT_S
        while True:
            if ev.wait(0.2):
                return True
            if not _ahead.worker_alive():
                return ev.wait(0.05)  # one last look: it may have just set
            if time.perf_counter() >= deadline:
                return False

    def _ahead_failed(self, sig, exc: BaseException) -> None:
        """The blessed compile-ahead worker could not build ``sig`` (the
        build raised past its own net, or the worker died with the task
        queued/in hand): pop the in-flight marker and SET the event with
        the error attached, so a consumer waiting on it falls through to
        the synchronous compile path immediately — a dead builder must
        never read as an in-flight one (design.md §13)."""
        with self._lock:
            ev = self._inflight.pop(sig, None)
        self._count("ahead_errors")
        if ev is not None:
            ev.error = exc
            ev.set()
        logger.warning(
            "compile-ahead build of %s failed (%s: %s); consumers fall "
            "back to the synchronous compile path",
            self.name, type(exc).__name__, exc)

    # -- compilation (consumer thread on miss; blessed thread on warm) ---
    def _compile_entry(self, sig, args, static, source: str):
        t0 = time.perf_counter()
        entry = None
        try:
            compiled = self._jitted.lower(*args, **static).compile()
            entry = _Entry(compiled, source, time.perf_counter() - t0)
            # tell the roofline layer what device the cost estimates
            # belong to (roofline itself never imports jax, so the
            # host-only sampler/scrape threads can read it freely)
            _roofline.note_device_kind(jax.devices()[0].device_kind)
        except Exception as e:
            if source == "ahead":
                # the consumer's own demand path still works; record and
                # move on (warm() must never be able to break a fit)
                self._count("ahead_errors")
                logger.warning("compile-ahead of %s failed: %s",
                               self.name, e)
            else:
                with self._lock:
                    ev = self._inflight.pop(sig, None)
                if ev is not None:
                    ev.set()
                raise
        finally:
            if entry is not None:
                key = ("ahead_compile_s" if source == "ahead"
                       else "compile_s")
                with self._lock:
                    self._entries[sig] = entry
                    self.counters[key] += entry.compile_s
                    ev = self._inflight.pop(sig, None)
                if ev is not None:
                    ev.set()
                _registry().histogram(f"program.{key}").record(
                    entry.compile_s)
            elif source == "ahead":
                with self._lock:
                    ev = self._inflight.pop(sig, None)
                if ev is not None:
                    ev.set()
        return entry

    # -- compile-ahead ---------------------------------------------------
    def warm(self, args, **static) -> bool:
        """Request an ahead-of-time compile of the program for ``args``
        (a pytree of ``jax.ShapeDtypeStruct`` — or concrete arrays —
        matching a future call's operands) on the dedicated
        ``dask-ml-tpu-compile-ahead`` thread.

        Returns True when a compile was enqueued; False when the
        signature is already built/in-flight, compile-ahead is off, or
        the worker could not take it.  Registers the in-flight marker
        SYNCHRONOUSLY, so a consumer that calls before the build
        finishes waits on it instead of compiling a duplicate.  Safe on
        the prefetch worker thread: signature math and a queue put,
        nothing device-touching."""
        from . import ahead

        if not ahead.enabled():
            return False
        sig = self.signature(args, static)
        if sig is None:
            return False
        ev = threading.Event()
        ev.ahead = True  # waiters poll the blessed thread's liveness
        with self._lock:
            if sig in self._entries or sig in self._inflight:
                return False
            self._inflight[sig] = ev
        if not ahead.submit(self, sig, args, static):
            with self._lock:
                self._inflight.pop(sig, None)
            ev.set()
            return False
        self._count("ahead_submitted")
        return True

    # -- books -----------------------------------------------------------
    def _count(self, key: str) -> None:
        with self._lock:
            self.counters[key] += 1
        name = {"hits": "program.hit", "misses": "program.miss",
                "bypass": "program.bypass", "fallback": "program.fallback",
                "ahead_submitted": "program.ahead_submit",
                "ahead_errors": "program.ahead_error"}.get(key)
        if name is not None:
            _registry().counter(name, self.name).inc()

    def report(self) -> dict:
        with self._lock:
            out = dict(self.counters)
            out["programs"] = len(self._entries)
            out["inflight"] = len(self._inflight)
            out["cost_known"] = sum(1 for e in self._entries.values()
                                    if e.cost is not None)
        for k in ("compile_s", "ahead_compile_s", "saved_s", "wait_s"):
            out[k] = round(out[k], 6)
        return out

    def reset_counters(self) -> None:
        with self._lock:
            self.counters = _new_counters()
            for e in self._entries.values():
                e.consumer_hits = 0

    def clear(self) -> None:
        """Drop every compiled executable (test isolation; the next call
        per signature recompiles)."""
        with self._lock:
            self._entries.clear()
            self.counters = _new_counters()


def cached_program(fn, *, name: str, static_argnames=(),
                   donate_argnames=(), **jit_kwargs) -> CachedProgram:
    """Factory for the module-level ``_jitted_* = cached_program(...)``
    idiom (mirrors ``partial(jax.jit, ...)(fn)``)."""
    return CachedProgram(fn, name=name, static_argnames=static_argnames,
                         donate_argnames=donate_argnames, **jit_kwargs)


def report() -> dict:
    """Per-program cache books + totals — the
    ``diagnostics.program_report()`` payload."""
    with _REG_LOCK:
        progs = dict(_BY_NAME)
    per = {name: p.report() for name, p in sorted(progs.items())}
    totals = _new_counters()
    totals["programs"] = 0
    for r in per.values():
        for k in totals:
            totals[k] += r.get(k, 0)
    for k in ("compile_s", "ahead_compile_s", "saved_s", "wait_s"):
        totals[k] = round(totals[k], 6)
    from .bucket import counters_snapshot

    return {
        "programs": per,
        "totals": totals,
        "bucket": counters_snapshot(),
        "persistent_cache": jax.config.jax_compilation_cache_dir,
    }


def reset_counters() -> None:
    """Zero every program's books and the ``bucket.*`` /`` program.*``
    registry families (bench / test isolation; compiled executables are
    kept — recompiling warm programs would change what a later section
    measures)."""
    with _REG_LOCK:
        progs = list(_BY_NAME.values())
    for p in progs:
        p.reset_counters()
    _registry().reset(prefix="program.")
    _registry().reset(prefix="bucket.")
