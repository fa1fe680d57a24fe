"""graftpath: the causal critical-path engine (docs/design.md §19).

Every plane already reports *time* — grafttrace spans (host), graftscope
in-flight intervals (device), the registry's wait histograms — but
nothing joins them causally: on a saturated gate box every A/B reads as
a meaningless wall ratio and the bottleneck is argued in prose.  This
module turns the existing substrate into a **verdict**: for one fit /
search / serve window, an exhaustive category attribution of the wall
clock plus the bottleneck class the evidence supports.

Causal model
------------
The observation window is a completed ROOT span (a ``pipeline.stream``
fit, a ``search.fit``).  Every retained record that overlaps the window
— regardless of tree membership, so rootless reader-thread records and
detached async-unit records join the same timeline — is clipped to it
and bucketed into one of seven categories by a **priority layering**:
each instant of the window is attributed to exactly ONE category, the
most causally specific signal that covers it:

1. ``device``     — graftscope in-flight intervals (enqueue→ready; the
                    device is busy-or-fed, so host work underneath is
                    *hidden*, off the critical path);
2. ``parse``      — the reader threads' ``data.parse``
                    (pread+decompress+decode, recorded per block): the
                    concurrent ground truth of who was working, so 4
                    readers on 1 core show up as parse pressure, not
                    mystery waiting — this layer claims its time
                    BEFORE the wait layer, because the worker's wait
                    below is *caused* by this work;
3. ``fetch``      — ``data.fetch`` (remote-store / emulated block
                    fetch RTT) and any future ``*.fetch`` span;
4. ``queue_wait`` — specific wait signals: the data plane's
                    reorder-merge wait (``data.queue_wait``), the
                    search scheduler's throttle park
                    (``search.queue_wait``);
5. *(parse again)* — ``pipeline.parse``: the staging worker's source
                    pull, net of the reader work and waits it wraps;
6. ``stage``      — ``pipeline.stage`` (bucket-pad + H2D put);
7. *(queue_wait again)* — ``pipeline.stall``: the consumer's staged-
                    queue starvation NOT explained by any concurrent
                    producer work above (a stall covered by a worker's
                    parse attributes to parse — the cause — and only
                    the unexplained remainder lands here);
8. ``dispatch``   — ``pipeline.compute`` net of the device time inside
                    it (the host cost of driving a step), plus every
                    other non-container host span (``search.unit``
                    bodies: scoring, cohort packing, control flow);
9. ``idle_gap``   — the unattributed remainder.

The categories therefore sum to the wall EXACTLY by construction — on
the span plane the tolerance check is an invariant guard (it can only
fire if a future change breaks the constructive partition) — while the
documented tolerance (``DASK_ML_TPU_CRITICAL_TOL``) is LIVE on the
joins that are not constructive: the serve plane's per-request
identity (queue+window+device+fetch vs ``request_s``).  A window whose
``idle_gap`` exceeds 50% of the wall refuses to name a bottleneck
(verdict ``unknown``: honesty over invention).

Verdict rules
-------------
The bottleneck class is the largest non-idle category::

    device → device-bound      parse → parse-bound
    stage  → stage-bound       queue_wait → queue-bound
    dispatch → dispatcher-bound  fetch → fetch-bound

with the winning share reported as ``confidence`` and the evidence
chain (per-category seconds, the top spans of the winning category,
device occupancy over the window) attached — the verdict is never a
bare string.  ``overlap_efficiency`` = hidden host time / host time:
the fraction of host LANE time (parse/stage/fetch, one lane per
producing thread *name* — concurrent same-named workers, e.g. the
four ``dask-ml-tpu-data-reader`` threads, merge into one lane, which
keeps the number a structural property rather than one that scales
with the worker count) that ran CONCURRENTLY with consumption work on
a *different* lane (1.0 = the pipeline hides everything it stages;
0.0 = strictly serial — a depth-0 stream measures ~0 by construction,
because its parse, stage, and compute share one lane).  Hiding is
judged against the host-side dispatch-scope spans, not the device
intervals, whose end-detection slack on a GIL-starved box would
fabricate overlap where none exists.

Everything here is pure host stdlib (no jax, no numpy) — legal on any
thread, same posture as the rest of :mod:`dask_ml_tpu.obs`.
"""

from __future__ import annotations

import os
import threading

from .._locks import make_lock

from .metrics import registry as _registry
from . import scope as _scope
from . import spans as _spans

__all__ = [
    "CRITICAL_TOL_ENV",
    "CRITICAL_DOMINANCE_ENV",
    "BOTTLENECK_CLASSES",
    "CATEGORIES",
    "resolve_tolerance",
    "resolve_dominance",
    "critical_path",
    "serve_critical",
    "last_verdicts",
    "reset",
]

#: policy knob: sum-to-wall / serve-identity tolerance as a fraction of
#: the wall (default 0.05).  Strict parse; the verdict degrades to
#: ``unknown`` when a non-constructive join misses the tolerance.
CRITICAL_TOL_ENV = "DASK_ML_TPU_CRITICAL_TOL"

#: policy knob: the share the winning category needs for a CONFIDENT
#: verdict (default 0.35) — below it the verdict still names the
#: largest category but ``confident`` is False (a 32/30/28 split is not
#: a bottleneck).
CRITICAL_DOMINANCE_ENV = "DASK_ML_TPU_CRITICAL_DOMINANCE"

_DEFAULT_TOL = 0.05
_DEFAULT_DOMINANCE = 0.35

#: the attribution taxonomy, in report order
CATEGORIES = ("parse", "stage", "queue_wait", "dispatch", "device",
              "fetch", "idle_gap")

#: verdict classes, index == the ``critical.bottleneck`` gauge value on
#: ``/metrics`` (a Prometheus label cannot carry the class name as a
#: value, so the gauge speaks this enum; the tag names the plane)
BOTTLENECK_CLASSES = (
    "unknown",           # 0
    "device-bound",      # 1
    "parse-bound",       # 2
    "stage-bound",       # 3
    "dispatcher-bound",  # 4
    "queue-bound",       # 5
    "fetch-bound",       # 6
)

_CLASS_OF = {
    "device": "device-bound",
    "parse": "parse-bound",
    "stage": "stage-bound",
    "dispatch": "dispatcher-bound",
    "queue_wait": "queue-bound",
    "fetch": "fetch-bound",
}

#: span names that are pure CONTAINERS (they cover their children's
#: whole lifetime including idle): excluded from the dispatch catch-all
#: so control-plane scaffolding cannot masquerade as host work
_CONTAINER_NAMES = frozenset({
    "pipeline.stream", "search.fit", "search.round", "search.bracket",
})

#: name → category SOURCE for the specific (non-catch-all) layers.
#: ``data.parse`` is split from ``pipeline.parse`` because the two
#: nest causally: the worker's ``pipeline.parse`` span wraps a source
#: pull that may be a reorder-queue WAIT, while the readers'
#: ``data.parse`` spans are the concurrent ground truth of who was
#: actually working — the reader layer must claim its time before the
#: wait layer does, and the wait layer before the worker's wrapper.
_SPECIFIC = {
    "data.parse": "parse_src",
    "data.fetch": "fetch",
    "data.queue_wait": "queue_wait_src",
    "search.queue_wait": "queue_wait_src",
    "pipeline.parse": "parse",
    "pipeline.stage": "stage",
    "pipeline.stall": "stall",
}

_LOCK = make_lock("obs.critical")
_LAST: dict[str, dict] = {}  # plane -> last computed verdict block


def _resolve_fraction(env: str, default: float, what: str,
                      value=None) -> float:
    if value is None:
        raw = os.environ.get(env, "").strip()
        if not raw:
            return default
        try:
            value = float(raw)
        except ValueError:
            raise ValueError(
                f"{env} must be a number, got {raw!r}") from None
    value = float(value)
    if not 0.0 < value < 1.0:
        raise ValueError(f"{what} must be in (0, 1), got {value}")
    return value


def resolve_tolerance(tol: float | None = None) -> float:
    """Sum-to-wall tolerance fraction: explicit, else the
    ``DASK_ML_TPU_CRITICAL_TOL`` knob, else 0.05.  Strict parse."""
    return _resolve_fraction(CRITICAL_TOL_ENV, _DEFAULT_TOL,
                             "critical tolerance", tol)


def resolve_dominance(dom: float | None = None) -> float:
    """Confident-verdict share: explicit, else the
    ``DASK_ML_TPU_CRITICAL_DOMINANCE`` knob, else 0.35."""
    return _resolve_fraction(CRITICAL_DOMINANCE_ENV, _DEFAULT_DOMINANCE,
                             "dominance threshold", dom)


# -- interval algebra (disjoint sorted [a, b] lists) ---------------------

def _union(intervals):
    """Sorted disjoint union of (a, b) pairs."""
    ivs = sorted((a, b) for a, b in intervals if b > a)
    out: list[list[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(ivs) -> float:
    return sum(b - a for a, b in ivs)


def _overlap(xs, ys) -> float:
    """Total overlap length between two disjoint sorted lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip(t0: float, t1: float, lo: float, hi: float):
    a, b = max(t0, lo), min(t1, hi)
    return (a, b) if b > a else None


def _hist_sum(name: str) -> float:
    """Summed histogram value across tags WITHOUT creating the
    instrument (``registry().histogram(name)`` would seed an empty
    family on a read — the family() posture, applied to histograms)."""
    return sum(getattr(inst, "sum", 0.0)
               for n, _tag, inst in _registry().export_items()
               if n == name)


# -- the per-window engine ----------------------------------------------

def _verdict_block(shares: dict, dominance: float,
                   idle_frac: float) -> dict:
    candidates = {k: v for k, v in shares.items() if k != "idle_gap"}
    top = max(candidates, key=candidates.get) if candidates else None
    if top is None or candidates[top] <= 0.0 or idle_frac > 0.5:
        return {"class": "unknown", "confidence": 0.0,
                "confident": False,
                "reason": ("idle_gap dominates the window"
                           if idle_frac > 0.5 else "no attributed time")}
    return {"class": _CLASS_OF[top],
            "confidence": round(candidates[top], 4),
            "confident": candidates[top] >= dominance}


def _publish(plane: str, result: dict) -> None:
    """Land the verdict on the scrape surface: one gauge pair per plane
    (class as the documented enum index, overlap efficiency as-is) and
    the module's last-verdict join for ``device_report()``."""
    reg = _registry()
    cls = result.get("verdict", {}).get("class", "unknown")
    reg.gauge("critical.bottleneck", plane).set(
        float(BOTTLENECK_CLASSES.index(cls)))
    oe = result.get("overlap_efficiency")
    if oe is not None:
        reg.gauge("critical.overlap_efficiency", plane).set(float(oe))
    with _LOCK:
        _LAST[plane] = {
            "verdict": cls,
            "confidence": result.get("verdict", {}).get("confidence"),
            "overlap_efficiency": oe,
        }


def last_verdicts() -> dict:
    """``{plane: {verdict, confidence, overlap_efficiency}}`` of the
    most recent :func:`critical_path` / :func:`serve_critical` calls —
    the lightweight join ``device_report()`` attaches (occupancy and
    its interpretation belong on one page)."""
    with _LOCK:
        return {k: dict(v) for k, v in _LAST.items()}


def reset() -> None:
    """Drop the last-verdict join (test/bench isolation; the gauges are
    cleared by the caller's registry reset)."""
    with _LOCK:
        _LAST.clear()


def _plane_of(root) -> str:
    name = getattr(root, "name", "") or ""
    if name.startswith("search."):
        return "search"
    if name.startswith("serve."):
        return "serve"
    return "fit"


def critical_path(root=None, *, records=None, device=None,
                  tolerance: float | None = None,
                  dominance: float | None = None,
                  publish: bool = True) -> dict:
    """Assemble the critical path of one completed root span (default:
    :func:`~.spans.last_root`) — see the module docstring for the
    causal model.  Returns::

        {"plane": "fit" | "search",
         "wall_s": w, "t0": ..., "t1": ...,
         "categories": {parse, stage, queue_wait, dispatch, device,
                        fetch, idle_gap},       # seconds, sum == wall
         "shares":     {... same keys ...},     # fractions of wall
         "coverage": attributed_fraction,       # 1 - idle share
         "tolerance": tol, "within_tolerance": bool,
         "overlap_efficiency": hidden_host/host or None,
         "host_s": ..., "hidden_host_s": ...,
         "device": {dispatches, busy_s, utilization},   # window-scoped
         "verdict": {"class", "confidence", "confident"},
         "evidence": {top spans of the winning category, wait books}}

    With no root (tracing disabled, nothing completed) the serve plane
    is tried (:func:`serve_critical`); failing that, an explicit
    ``{"plane": None, "verdict": {"class": "unknown"}}`` — the report
    never invents a story.
    """
    tol = resolve_tolerance(tolerance)
    dom = resolve_dominance(dominance)
    root = root if root is not None else _spans.last_root()
    if root is None:
        serve = serve_critical(tolerance=tol, dominance=dom,
                               publish=publish)
        if serve is not None:
            return serve
        return {"plane": None, "wall_s": 0.0, "categories": {},
                "shares": {}, "coverage": 0.0, "tolerance": tol,
                "within_tolerance": True, "overlap_efficiency": None,
                "verdict": {"class": "unknown", "confidence": 0.0,
                            "confident": False,
                            "reason": "no completed root span and no "
                                      "serve traffic"},
                "evidence": {}}
    lo, hi = float(root.t0), float(root.t1)
    wall = max(hi - lo, 1e-12)
    if records is None:
        records = _spans.span_records()
    if device is None:
        device = _scope.timeline(open_until=hi)

    # bucket clipped intervals by category source
    src: dict[str, list] = {k: [] for k in
                            ("device", "parse_src", "queue_wait_src",
                             "parse", "stage", "fetch", "stall",
                             "dispatch")}
    top_spans: dict[str, list] = {}
    for iv in device:
        c = _clip(iv["t0"], iv["t1"], lo, hi)
        if c is not None:
            src["device"].append(c)
            top_spans.setdefault("device", []).append(
                (c[1] - c[0], iv.get("program", "device"), {}))
    root_id = getattr(root, "span_id", None)
    host_by_thread: dict[str, list] = {}     # parse/stage/fetch work
    consume_by_thread: dict[str, list] = {}  # dispatch-scope spans
    for r in records:
        if getattr(r, "kind", "span") != "span":
            continue
        rid = getattr(r, "span_id", None)
        if rid is not None and rid == root_id:
            continue
        name = r.name
        cat = _SPECIFIC.get(name)
        if cat is None:
            if name in _CONTAINER_NAMES:
                continue
            cat = "dispatch"  # pipeline.compute + generic host work
        c = _clip(r.t0, r.t1, lo, hi)
        if c is None:
            continue
        thread = getattr(r, "thread", "") or ""
        if cat in ("parse_src", "parse", "stage", "fetch"):
            host_by_thread.setdefault(thread, []).append(c)
        elif cat == "dispatch":
            consume_by_thread.setdefault(thread, []).append(c)
        src[cat].append(c)
        # evidence: remember the biggest few raw spans per category
        key = ("queue_wait" if cat in ("queue_wait_src", "stall")
               else "parse" if cat == "parse_src" else cat)
        bucket = top_spans.setdefault(key, [])
        bucket.append((c[1] - c[0], name,
                       dict(getattr(r, "attrs", None) or {})))

    unions = {k: _union(v) for k, v in src.items()}

    # priority layering: most-specific-first disjoint attribution
    order = (("device", "device"),
             ("parse_src", "parse"),      # reader ground truth first
             ("fetch", "fetch"),
             ("queue_wait_src", "queue_wait"),
             ("parse", "parse"),          # worker wrapper residue
             ("stage", "stage"),
             ("stall", "queue_wait"),
             ("dispatch", "dispatch"))
    attributed: list = []
    cats = {k: 0.0 for k in CATEGORIES}
    for source, cat in order:
        u = unions[source]
        if not u:
            continue
        net = _length(u) - _overlap(u, attributed)
        cats[cat] += max(net, 0.0)
        attributed = _union(attributed + u)
    covered = _length(attributed)
    cats["idle_gap"] = max(wall - covered, 0.0)

    shares = {k: round(v / wall, 4) for k, v in cats.items()}
    # constructive partition: the only miss a tolerance can see here is
    # accumulated clipping/rounding — still checked, still reported
    total = sum(cats.values())
    within = abs(total - wall) <= tol * wall

    # overlap efficiency: host LANE time (parse/stage/fetch; one lane
    # per thread NAME — concurrent same-named workers merge, see the
    # module docstring) hidden under CONCURRENT consumption work on a
    # DIFFERENT lane (the dispatch-scope spans).  Deliberately NOT the
    # device intervals: their t1 carries detection slack (one sampler
    # period, worse on a GIL-starved 1-core box), and a slack-extended
    # interval lapping the NEXT block's parse would fabricate overlap
    # in a strictly serial depth-0 stream — the host-side concurrency
    # structure is the stable truth of whether the pipeline overlaps,
    # and it is exactly what a depth knob changes.
    host_s = 0.0
    hidden_s = 0.0
    for thread, ivs in host_by_thread.items():
        u = _union(ivs)
        host_s += _length(u)
        other = _union([iv for t, civs in consume_by_thread.items()
                        if t != thread for iv in civs])
        hidden_s += _overlap(u, other)
    overlap_eff = (round(hidden_s / host_s, 4) if host_s > 1e-9
                   else None)

    verdict = _verdict_block(shares, dom, shares["idle_gap"])
    if not within:
        verdict = {"class": "unknown", "confidence": 0.0,
                   "confident": False,
                   "reason": f"category sum {total:.6f}s misses wall "
                             f"{wall:.6f}s beyond tolerance {tol}"}

    win_cat = next((k for k, v in _CLASS_OF.items()
                    if v == verdict["class"]), None)
    evidence = {
        "wait_books": {
            "pipeline_stall_s": round(_length(unions["stall"]), 6),
            # session-cumulative registry sums (read-only scan: a
            # report must not seed instruments it only wants to read)
            "data_queue_wait_s": round(_hist_sum("data.queue_wait_s"), 6),
            "search_queue_wait_s": round(
                _hist_sum("search.queue_wait_s"), 6),
        },
        "n_records": sum(len(v) for v in src.values()),
    }
    if win_cat is not None:
        # sort on duration only: a (dur, name, attrs) tuple comparison
        # would fall through to dict.__lt__ on a tie and raise
        spans_list = sorted(top_spans.get(win_cat, []), reverse=True,
                            key=lambda t: t[0])[:3]
        evidence["top_spans"] = [
            {"name": n, "dur_s": round(d, 6), "attrs": a}
            for d, n, a in spans_list]

    dev_busy = _length(unions["device"])
    result = {
        "plane": _plane_of(root),
        "root": root.name,
        "wall_s": round(wall, 6),
        "t0": round(lo, 6),
        "t1": round(hi, 6),
        "categories": {k: round(v, 6) for k, v in cats.items()},
        "shares": shares,
        "coverage": round(covered / wall, 4),
        "tolerance": tol,
        "within_tolerance": within,
        "overlap_efficiency": overlap_eff,
        "host_s": round(host_s, 6),
        "hidden_host_s": round(hidden_s, 6),
        "device": {
            "dispatches": len(src["device"]),
            "busy_s": round(dev_busy, 6),
            "utilization": round(dev_busy / wall, 4),
        },
        "verdict": verdict,
        "evidence": evidence,
    }
    if publish:
        _publish(result["plane"], result)
    return result


# -- the serve plane -----------------------------------------------------

_SERVE_SEGMENTS = ("queue", "window", "device", "fetch")

#: serve segment → verdict class: the request path has no parse/stage,
#: so the taxonomy maps onto its four legs (window = the batcher's own
#: coalescing choice, i.e. the dispatcher's behavior)
_SERVE_CLASS = {"queue": "queue-bound", "window": "dispatcher-bound",
                "device": "device-bound", "fetch": "fetch-bound"}


def serve_critical(*, tolerance: float | None = None,
                   dominance: float | None = None,
                   publish: bool = True,
                   tag: str | None = None) -> dict | None:
    """The serve window's critical path, from the per-request split the
    runtime records (``serve.req_{queue,window,device,fetch}_s`` —
    four contiguous legs per request, stamped with the request's trace
    id through submit → coalesce → dispatch → fetch).  Aggregate form:
    total seconds per leg across the retained window, shares of total
    request time, the identity check ``queue+window+device+fetch ≈
    Σ request_s`` within the tolerance, and the verdict.  ``None`` when
    no split has been recorded (no serve traffic — the report must not
    invent an empty story).

    ``tag`` restricts the aggregation to one latency-histogram tag —
    normally a model name, or a replica tag (``r0``, ``r1``, ...) when
    the servers were built with ``metrics_tag`` (the fleet's
    per-replica bottleneck verdicts);
    ``None`` keeps the global all-tags sum."""
    tol = resolve_tolerance(tolerance)
    dom = resolve_dominance(dominance)
    reg = _registry()
    totals = {}
    count = 0
    for seg in _SERVE_SEGMENTS:
        s = 0.0
        for name, _tag, inst in reg.export_items():
            if name == f"serve.req_{seg}_s" and \
                    (tag is None or _tag == tag):
                s += inst.sum
                if seg == "queue":
                    count += inst.count
        totals[seg] = s
    if count == 0:
        return None
    request_s = sum(inst.sum for name, _tag, inst in reg.export_items()
                    if name == "serve.request_s"
                    and (tag is None or _tag == tag))
    total = sum(totals.values())
    denom = max(request_s, 1e-12)
    within = abs(total - request_s) <= tol * denom
    shares = {k: round(v / max(total, 1e-12), 4)
              for k, v in totals.items()}
    top = max(shares, key=shares.get)
    if within:
        verdict = {"class": _SERVE_CLASS[top],
                   "confidence": shares[top],
                   "confident": shares[top] >= dom}
    else:
        verdict = {"class": "unknown", "confidence": 0.0,
                   "confident": False,
                   "reason": f"split sum {total:.6f}s misses "
                             f"request_s {request_s:.6f}s beyond "
                             f"tolerance {tol}"}
    result = {
        "plane": "serve" if tag is None else f"serve:{tag}",
        "requests": count,
        "wall_s": round(request_s, 6),  # summed request seconds
        "categories": {k: round(v, 6) for k, v in totals.items()},
        "shares": shares,
        "coverage": round(total / denom, 4),
        "tolerance": tol,
        "within_tolerance": within,
        "overlap_efficiency": None,
        "verdict": verdict,
        "evidence": {
            "identity": f"queue+window+device+fetch = {total:.6f}s "
                        f"vs sum(request_s) = {request_s:.6f}s",
        },
    }
    if publish:
        # a tagged (per-replica / per-model) verdict publishes under
        # its own plane key so it never clobbers the global serve one
        _publish(result["plane"], result)
    return result
