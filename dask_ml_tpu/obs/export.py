"""The exporter: a schema-versioned JSONL event log.

**JSONL** (``DASK_ML_TPU_TRACE=path`` or ``obs.enable(jsonl_path=...)``)
streams every completed span/event as one JSON line the moment it
completes, so a crashed process keeps everything up to the crash.  The
first line is a header ``{"schema": "grafttrace", "version": 1, ...}``;
:func:`read_jsonl` validates it on read-back and refuses a NEWER major
version (an older one is fine — the schema only grows).

To see host spans against the device there is one way, the profiler's
own trace: ``with diagnostics.trace(dir): est.fit(X, y)`` — inside a
profiler session every span is also a ``TraceAnnotation`` in the
``.xplane.pb`` (:mod:`.spans`), on the device lanes' clock.
"""

from __future__ import annotations

import json
import os
import sys
import threading

from .._locks import make_lock
import time

from . import spans as _spans

__all__ = [
    "JsonlSink",
    "read_jsonl",
]


class JsonlSink:
    """Append-one-line-per-record writer (thread-safe: the prefetch
    worker completes spans too).  Each line is flushed so a kill -9
    loses at most the record being written."""

    def __init__(self, path: str):
        self.path = str(path)
        self._lock = make_lock("obs.export")
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        self._f = open(self.path, "a", encoding="utf-8")
        self._write_obj({
            "schema": "grafttrace",
            "version": _spans.SCHEMA_VERSION,
            "pid": os.getpid(),
            "unix_time": round(time.time(), 3),
            # perf_counter epoch at header time: lets a reader map the
            # records' monotonic stamps onto wall clock
            "perf_counter": round(time.perf_counter(), 9),
        })

    def _write_obj(self, obj: dict) -> None:
        # drill point (resilience.testing): an injected
        # OSError(ENOSPC) here exercises the disk-full degradation
        # below — drop the sink, keep training.  Looked up through
        # sys.modules, NOT imported: obs is imported BY resilience, and
        # a DASK_ML_TPU_TRACE sink writes its header DURING obs's own
        # import, where importing resilience back would be a cycle.  If
        # the module is absent no plan can be active (plans live in it),
        # so skipping the fire is exact, not a best-effort.
        testing = sys.modules.get("dask_ml_tpu.resilience.testing")
        if testing is not None:
            testing.maybe_fault("exporter-write")
        line = json.dumps(obj, separators=(",", ":"), default=repr)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def write(self, rec) -> None:
        try:
            self._write_obj(rec.as_dict())
        # graftlint: disable=swallowed-fault -- write-after-close during interpreter/sink shutdown: the sink was already dropped WITH its one warning (OSError branch below); a second message per straggling span would be noise, not observability
        except ValueError:  # closed file on shutdown: quiet drop
            pass
        except OSError:
            # disk full / filesystem gone read-only: the TRACED FIT
            # must not die for its trace.  Warn once, drop the sink
            # (ring + flight recording continue), keep training.
            import logging

            logging.getLogger(__name__).warning(
                "grafttrace: JSONL sink %s failed; disabling file "
                "streaming for this process", self.path, exc_info=True,
            )
            self.close()
            from . import spans as _sp

            if _sp._STATE.sink is self:
                _sp._STATE.sink = None

    def close(self) -> None:
        with self._lock:
            try:
                self._f.close()
            except Exception:  # pragma: no cover
                pass


def read_jsonl(path: str) -> tuple[dict, list[dict]]:
    """``(first_header, records)`` from a grafttrace JSONL file; raises
    ``ValueError`` on a malformed header or a newer schema version.

    The sink appends, so a file may hold SEVERAL sessions (the
    documented multi-process ``DASK_ML_TPU_TRACE=path`` usage), each
    opening with its own header line.  Every header is validated and
    excluded from ``records``; note each session's ``t0``/``t1`` stamps
    are that process's monotonic clock — map them to wall time via its
    own header's ``perf_counter``/``unix_time`` pair before comparing
    across sessions.
    """
    with open(path, encoding="utf-8") as f:
        lines = [ln for ln in (ln.strip() for ln in f) if ln]
    if not lines:
        raise ValueError(f"{path}: empty trace file")
    first = json.loads(lines[0])
    if first.get("schema") != "grafttrace":
        raise ValueError(f"{path}: not a grafttrace JSONL (header {first!r})")
    records = []
    for i, ln in enumerate(lines):
        try:
            obj = json.loads(ln)
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                # a torn FINAL line is the expected kill-9/OOM artifact
                # ("a crashed process keeps everything up to the
                # crash"): drop it, keep the intact records
                break
            raise ValueError(
                f"{path}: malformed record at line {i + 1}"
            ) from None
        if obj.get("schema") == "grafttrace":  # a session header
            if int(obj.get("version", 0)) > _spans.SCHEMA_VERSION:
                raise ValueError(
                    f"{path}: schema version {obj['version']} is newer "
                    f"than this reader ({_spans.SCHEMA_VERSION})"
                )
            continue
        records.append(obj)
    return first, records
