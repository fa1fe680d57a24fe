"""The metrics registry: counters, gauges, and HDR-style histograms.

One process-global registry is the numeric spine every layer reports
through (docs/design.md §11): the input pipeline publishes its stage
split per stream, the resilience layer its fault/retry/failure counts
per tag, graftsan its compile/dispatch/d2h counters, checkpoints their
save counts.  The pre-existing reporters (``pipeline_report()``,
``fault_stats()``, ``sanitize_report()``) keep their shapes as VIEWS
over (or alongside) this registry, so nothing downstream breaks while
new consumers — ``diagnostics.run_report()``, the serving plane's
latency SLOs — read one coherent store.

Instruments are cheap and thread-safe: a counter increment is one lock
plus one integer add; a histogram record is one lock, one ``math.log``
and one dict add.  Histograms are HDR-style **log-bucketed** (growth
factor 2^(1/4), ~19% relative resolution per bucket) so p50/p95/p99
over microseconds-to-minutes latencies cost O(buckets touched) memory
with no stored samples, exactly the shape a long-running serving
process needs.  Everything here is pure host stdlib — no jax, no
numpy — so instruments are legal anywhere, including the prefetch
worker thread (stage-purity/thread-dispatch provably host-only).

Naming contract (enforced by convention, documented in design.md §11):
``<layer>.<what>[_<unit>]`` — ``pipeline.stall_s``, ``resilience.retry``,
``compile.count``, ``checkpoint.save``.  Tags (one optional label per
instrument) separate books within a name: ``resilience.retry`` is
tagged by the retry site's tag, mirroring ``FaultStats``.
"""

from __future__ import annotations

import math
import threading

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
    "metrics_snapshot",
    "reset_metrics",
]

#: histogram bucket growth factor: 2^(1/4) ≈ 1.189 (~19% relative error,
#: 4 buckets per octave — 150 buckets span 1 µs .. 10 min)
_GROWTH = 2.0 ** 0.25
_LOG_GROWTH = math.log(_GROWTH)
#: smallest distinguishable value; anything at or below lands in bucket 0
_FLOOR = 1e-9


class Counter:
    """Monotone integer counter."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-write-wins float (queue depth, ring occupancy, ...)."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Log-bucketed value distribution with quantile estimation.

    ``record(v)`` files ``v`` into bucket ``floor(log(v/1e-9) /
    log(2^0.25))`` (sparse dict); quantiles walk the sorted buckets and
    return each bucket's geometric midpoint, so a reported p99 is within
    ~19% of the true p99 — HDR semantics without storing samples.
    Exact ``count``/``sum``/``min``/``max`` ride alongside.
    """

    __slots__ = ("_lock", "_buckets", "count", "sum", "min", "max")

    def __init__(self):
        self._lock = threading.Lock()
        self._buckets: dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def record(self, v: float) -> None:
        v = float(v)
        if v <= _FLOOR:
            idx = 0
        else:
            idx = int(math.log(v / _FLOOR) / _LOG_GROWTH) + 1
        with self._lock:
            self._buckets[idx] = self._buckets.get(idx, 0) + 1
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v

    def quantile(self, q: float) -> float:
        """Estimated ``q``-quantile (0 <= q <= 1); NaN when empty."""
        with self._lock:
            return self._quantile_locked(q)

    def _quantile_locked(self, q: float) -> float:
        if not self.count:
            return math.nan
        # nearest-rank: p99 of 5 samples is the max, not the 4th —
        # the convention an SLO reader expects from small samples
        rank = q * self.count
        seen = 0
        for idx in sorted(self._buckets):
            seen += self._buckets[idx]
            if seen >= rank:
                if idx == 0:
                    return 0.0
                # geometric midpoint of the bucket, clamped to the
                # exact observed range so a 1-sample histogram
                # reports its sample, not a bucket boundary
                mid = _FLOOR * _GROWTH ** (idx - 0.5)
                return min(max(mid, self.min), self.max)
        return self.max  # pragma: no cover - rank < count always hits

    def snapshot(self) -> dict:
        # one acquisition across every field read: releasing after the
        # empty-check and reading count/sum/min/max bare let a
        # concurrent record() interleave mid-update and produce a torn
        # snapshot (count bumped, sum not yet)
        with self._lock:
            if not self.count:
                return {"count": 0}
            return {
                "count": self.count,
                "sum": round(self.sum, 9),
                "min": round(self.min, 9),
                "max": round(self.max, 9),
                "p50": round(self._quantile_locked(0.50), 9),
                "p95": round(self._quantile_locked(0.95), 9),
                "p99": round(self._quantile_locked(0.99), 9),
            }


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class MetricsRegistry:
    """Name+tag keyed instrument store.

    ``counter("resilience.retry", "ingest")`` returns the one counter
    for that (name, tag) pair, creating it on first use — callers keep
    no handles they must coordinate.  A name must keep one instrument
    kind (asking for a histogram under an existing counter name raises:
    silent kind drift would corrupt every reader).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict[tuple[str, str], object] = {}
        self._kinds: dict[str, str] = {}

    def _get(self, kind: str, name: str, tag: str | None):
        key = (name, tag or "")
        inst = self._instruments.get(key)
        if inst is not None:
            if type(inst) is not _KINDS[kind]:
                raise ValueError(
                    f"metric {name!r} is a {self._kinds.get(name)}, "
                    f"not a {kind}"
                )
            return inst
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                prev = self._kinds.get(name)
                if prev is not None and prev != kind:
                    raise ValueError(
                        f"metric {name!r} is a {prev}, not a {kind}"
                    )
                self._kinds[name] = kind
                inst = self._instruments[key] = _KINDS[kind]()
            return inst

    def counter(self, name: str, tag: str | None = None) -> Counter:
        return self._get("counter", name, tag)

    def gauge(self, name: str, tag: str | None = None) -> Gauge:
        return self._get("gauge", name, tag)

    def histogram(self, name: str, tag: str | None = None) -> Histogram:
        return self._get("histogram", name, tag)

    def family(self, name: str) -> dict:
        """All tags of one counter/gauge name → ``{tag: value}`` (the
        ``FaultStats`` per-tag view); empty dict when the name is
        unknown."""
        with self._lock:
            items = [
                (k[1], inst) for k, inst in self._instruments.items()
                if k[0] == name
            ]
        return {tag: inst.value for tag, inst in items
                if isinstance(inst, (Counter, Gauge))}

    def export_items(self) -> list:
        """``[(name, tag, instrument)]`` sorted by (name, tag) — the
        structured walk the Prometheus exporter (:mod:`.serve`) formats
        from.  Unlike :meth:`snapshot`'s ``name{tag}`` composite keys,
        tags stay separate so label values can be escaped correctly
        (a tag may itself contain braces, quotes, or newlines)."""
        with self._lock:
            return [(name, tag, inst) for (name, tag), inst
                    in sorted(self._instruments.items())]

    def snapshot(self) -> dict:
        """``{"counters": {key: n}, "gauges": {...}, "histograms":
        {key: {count, sum, min, max, p50, p95, p99}}}`` where ``key`` is
        ``name`` or ``name{tag}``."""
        with self._lock:
            items = sorted(self._instruments.items())
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for (name, tag), inst in items:
            key = f"{name}{{{tag}}}" if tag else name
            if isinstance(inst, Counter):
                out["counters"][key] = inst.value
            elif isinstance(inst, Gauge):
                out["gauges"][key] = inst.value
            else:
                out["histograms"][key] = inst.snapshot()
        return out

    def reset(self, prefix: str | None = None) -> None:
        """Drop instruments (all, or those whose name starts with
        ``prefix``).  Handles cached by callers go stale by design —
        in-repo publishers re-fetch by name per observation."""
        with self._lock:
            if prefix is None:
                self._instruments.clear()
                self._kinds.clear()
                return
            for key in [k for k in self._instruments
                        if k[0].startswith(prefix)]:
                del self._instruments[key]
            for name in [n for n in self._kinds if n.startswith(prefix)]:
                del self._kinds[name]


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-global registry every in-repo publisher reports to."""
    return _REGISTRY


def metrics_snapshot() -> dict:
    return _REGISTRY.snapshot()


def reset_metrics(prefix: str | None = None) -> None:
    _REGISTRY.reset(prefix)
