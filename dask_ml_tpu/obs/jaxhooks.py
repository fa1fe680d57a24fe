"""The one obs module that touches jax: compile-event publication, and
the profiler session that arms :mod:`.spans`.

``jax.monitoring`` emits ``/jax/core/compile/backend_compile_duration``
once per XLA backend compile (never on a cache hit) — the same signal
graftsan's compile detector attributes per-region.  This listener is the
UNGATED twin: it publishes ``compile.count`` / ``compile.duration_s``
into the metrics registry on every compile, sanitizer or not, so
``diagnostics.run_report()`` can trend compilation alongside
throughput in any process.

Kept out of ``obs/__init__`` imports deliberately: the rest of the obs
package is pure stdlib (provably host-only for graftlint's
thread-dispatch/stage-purity reachability), and this module is imported
lazily by :func:`~.spans.enable` and by graftsan's hook installer.
``install()`` is idempotent and is the SINGLE registry publisher for
compile events — graftsan's own listener only does per-region
attribution, so double-installation can never double-count.  The
listener also records a ``compile`` event onto the span open on the
compiling thread, so ``run_report()["span_tree"]`` says which step
recompiled.

``session_check()`` / ``annotation()`` are what :mod:`.spans` needs of
``jax.profiler.TraceAnnotation``: whether a profiler session is running
(its static ``is_enabled()``), and a host span in that session's trace.
"""

from __future__ import annotations

import threading

from .._locks import make_lock

from . import metrics as _metrics
from . import spans as _spans

__all__ = ["install", "COMPILE_EVENT", "session_check", "annotation"]

#: jax.monitoring event key: one firing per XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_LOCK = make_lock("obs.jaxhooks")
_INSTALLED = False
_ANNOTATION = None  # jax.profiler.TraceAnnotation, once asked for


def _annotation_type():
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def session_check():
    """The check for "a jax profiler session is running in this
    process": ``TraceAnnotation``'s own static ``is_enabled``, handed
    out so that the caller pays one builtin call a time."""
    return _annotation_type().is_enabled


def annotation(name: str, **attrs):
    """A ``TraceAnnotation`` for the running session (not yet entered).
    A traced run is also when compiles should land on spans, so this
    arms the compile listener."""
    install()
    return _annotation_type()(name, **attrs)


def install() -> None:
    """Register the compile-event listener exactly once per process."""
    global _INSTALLED
    if _INSTALLED:
        return
    with _LOCK:
        if _INSTALLED:
            return
        import jax.monitoring as _mon

        def _on_event_duration(event: str, duration: float, **_kw) -> None:
            if event == COMPILE_EVENT:
                reg = _metrics.registry()
                reg.counter("compile.count").inc()
                reg.histogram("compile.duration_s").record(float(duration))
                _spans.event("compile", duration_s=float(duration))

        _mon.register_event_duration_secs_listener(_on_event_duration)
        _INSTALLED = True
