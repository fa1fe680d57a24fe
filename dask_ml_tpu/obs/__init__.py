"""grafttrace: unified structured tracing, metrics, and flight recorder.

The one event spine every runtime layer reports through (docs/design.md
§11).  Four pieces:

* :mod:`.spans` — the span tree (``obs.span("fit")`` → rounds → blocks
  → parse/stage/compute children) in lock-free per-thread rings, with
  worker-thread stitching (``adopt``) and async-safe detached spans;
* :mod:`.metrics` — the counters/gauges/HDR-histogram registry
  (``pipeline.stall_s``, ``resilience.retry``, ``compile.count``) that
  ``PipelineStats``, ``FaultStats``, and graftsan publish into — the
  old reporters keep their shapes as views;
* :mod:`.export` — schema-versioned JSONL streaming
  (``DASK_ML_TPU_TRACE=path``); host spans against the device are read
  in the profiler's own trace, where :mod:`.spans` writes every span as
  a ``TraceAnnotation`` while a session runs (``diagnostics.trace``);
* :mod:`.flight` — the always-on last-N-events post-mortem ring dumped
  by the conftest watchdog and the preemption/fault paths;
* :mod:`.scope` — graftscope device-time accounting: per-program
  in-flight intervals from the dispatch choke points, the
  utilization/idle-gap report (``run_report()["device"]``);
* :mod:`.serve` — the live Prometheus ``/metrics`` + ``/healthz``
  endpoint (``DASK_ML_TPU_METRICS_PORT``), supervised like the
  compile-ahead thread.

Everything importable from here is pure-stdlib host code (no jax) —
safe in any thread including the prefetch worker; the jax compile
listener and the profiler-session check live in :mod:`.jaxhooks`, which
is imported lazily (by :func:`enable` / :func:`install_jax_hooks`, and
by the first ``span()`` once jax is in the process).
"""

from __future__ import annotations

from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    metrics_snapshot,
    registry,
    reset_metrics,
)
from .spans import (  # noqa: F401
    RING_ENV,
    SCHEMA_VERSION,
    TRACE_ENV,
    Span,
    SpanRecord,
    adopt,
    clear_spans,
    current_span_id,
    disable,
    enable,
    enabled,
    event,
    fmt_exc,
    last_root,
    open_span_paths,
    record_span,
    span,
    span_records,
    span_tree,
)
from .export import read_jsonl  # noqa: F401
from . import flight  # noqa: F401
from .flight import (  # noqa: F401
    dump as flight_dump,
    post_mortem as flight_post_mortem,
    tail as flight_tail,
)
from . import roofline  # noqa: F401
from . import scope  # noqa: F401
from .scope import device_report  # noqa: F401
from . import serve  # noqa: F401
from .serve import prometheus_text  # noqa: F401
from . import critical  # noqa: F401
from .critical import critical_path, serve_critical  # noqa: F401

__all__ = [
    # spans
    "SCHEMA_VERSION", "TRACE_ENV", "RING_ENV",
    "span", "record_span", "event", "fmt_exc", "adopt",
    "current_span_id",
    "enable", "disable", "enabled",
    "open_span_paths", "last_root", "span_records", "span_tree",
    "clear_spans", "Span", "SpanRecord",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "registry", "metrics_snapshot", "reset_metrics",
    # export
    "read_jsonl",
    # flight
    "flight", "flight_dump", "flight_post_mortem", "flight_tail",
    # graftscope: device-time accounting + roofline + scrape endpoint
    "scope", "roofline", "device_report", "serve", "prometheus_text",
    # graftpath: the causal critical-path engine (design.md §19)
    "critical", "critical_path", "serve_critical",
    # lifecycle
    "install_jax_hooks", "reset_all",
]


def install_jax_hooks() -> None:
    """Arm the compile-event registry listener without enabling span
    recording (bench processes that only want counters)."""
    from . import jaxhooks

    jaxhooks.install()


def reset_all() -> None:
    """Zero the whole spine: metrics registry, span rings + last root,
    the flight recorder, the graftscope device timeline, and the
    graftpath last-verdict join.  ``diagnostics.reset()`` is the public
    one-call form (it also clears the legacy reporters' residue and
    re-registers the live metrics-endpoint/sampler heartbeats)."""
    reset_metrics()
    clear_spans()
    flight.clear()
    scope.reset()
    critical.reset()


# graftscope endpoint env arming (DASK_ML_TPU_METRICS_PORT): a set port
# starts the scrape surface at import, same posture as DASK_ML_TPU_TRACE
# above — strict knob parse (a typo raises), fail-soft bind (a taken
# port warns; the fit matters more than its scrape).
serve.start_from_env()
