"""graftscope: device-time accounting — the device-side half of grafttrace.

grafttrace (design.md §11) records host wall spans; this module makes
**device occupancy** a first-class observable.  Every dispatched program
is tracked at the two choke points the repo already owns — the central
program cache's dispatch (:mod:`dask_ml_tpu.programs.cache`) and the
graftsan ``ExecuteReplicated`` hook — as an **in-flight interval**:

* ``t0`` — the moment the program was enqueued on the dispatching
  thread (jax dispatch is asynchronous on every backend this repo
  runs, measured on this image: a 270 ms program returns from its
  dispatch call in 3 ms);
* ``t1`` — the moment its outputs were observed ready.  Readiness is
  detected by duck-typed ``leaf.is_ready()`` polling (a ~0.3 µs
  host-only future check): at every subsequent tracked dispatch, and —
  so the end of a busy period is found even when the host goes quiet —
  on a dedicated **sampler thread** (:data:`SCOPE_THREAD_NAME`,
  supervised under the ``"obs"`` domain) that polls every
  :data:`_SAMPLE_S` seconds while work is in flight and parks on a
  condition variable otherwise.

Each interval may additionally carry the dispatched executable's
captured XLA cost estimate (flops / bytes accessed — the program cache
hands it to :func:`track`), which :func:`device_report` joins with
measured busy time into per-program achieved FLOP/s and a roofline
fraction against :mod:`.roofline`'s peak table.

The union of in-flight intervals is the "device busy-or-fed" timeline:
its complement inside the observation window is **device idle time** —
the budget currency the ROADMAP's [search-scale] lane names, and the
occupancy number the [serving] lane's SLOs sit next to.  Per-program
seconds land in the metrics registry (``device.busy_s{program}``
histograms, ``device.dispatches{program}`` counters — scraped by
:mod:`.serve`), closed intervals in a bounded ring consumed by
:func:`device_report` (``diagnostics.run_report()["device"]``) and
the critical-path engine (:mod:`.critical`).

Honesty contract: ``t1`` carries a detection slack of at most one
sampler period (~2 ms) — fine for the ms-scale block programs this
repo streams.  An interval covers enqueue→ready,
i.e. queue wait counts as *fed*, not idle — exactly the currency a
scheduler that wants to keep the device fed should budget.  The XProf
device trace is the authority for reported device time; this lane is
the in-process signal code can steer by.  The jitted-twin fallback path
may fold its own cold trace/compile into one interval (the AOT cache path
never does) — warm rounds, which is what the ratchet measures, are
unaffected.

Everything here is pure host stdlib — no jax import (the obs package's
host-only posture): callers hand in output leaves and this module only
ever calls ``is_ready()`` on them.  A leaf whose ``is_ready`` raises
(a buffer donated into the next step) counts as ready — the consuming
program's own interval is already open, so the lane stays continuous.
"""

from __future__ import annotations

import threading

from .._locks import make_condition, make_lock
import time

from .metrics import registry as _registry

__all__ = [
    "SCOPE_THREAD_NAME",
    "track",
    "absorb",
    "absorbed",
    "sweep",
    "settle",
    "cursor",
    "timeline",
    "device_report",
    "pending_count",
    "open_intervals",
    "rearm",
    "reset",
]

#: the sampler thread's literal name.  It is HOST-ONLY: it polls
#: ``is_ready()`` futures, beats a supervisor heartbeat, and records
#: into the metrics registry — it must never compile or dispatch
#: (``analysis.rules._spmd.HOST_ONLY_THREAD_NAMES``; the graftsan
#: dispatch detector holds it to that at runtime, same as the prefetch
#: worker).
SCOPE_THREAD_NAME = "dask-ml-tpu-scope"

#: sampler poll period while work is in flight: the end-detection slack
#: of every interval is at most this (plus scheduler jitter).
_SAMPLE_S = 0.002

#: how many closed intervals the timeline ring retains (registry totals
#: survive eviction; the ring bounds what device_report / the critical
#: path can SEE, same posture as the span rings).
_RING_CAP = 8192

#: sampler deaths tolerated before degrading to sweep-on-dispatch only
#: (detection slack grows to the inter-dispatch gap; totals stay exact).
_MAX_RESTARTS = 5

#: supervisor-beat decimation: one beat per this many sampler sweeps
#: (a 500 Hz poller must not turn the beat counter into noise).
_BEATS_EVERY = 50


class _Pending:
    __slots__ = ("program", "t0", "leaves", "seq", "cost")

    def __init__(self, program, t0, leaves, seq, cost=None):
        self.program = program
        self.t0 = t0
        self.leaves = leaves
        self.seq = seq
        self.cost = cost  # {"flops", "bytes", ...} | None (roofline.py)


_LOCK = make_lock("obs.scope")
_COND = make_condition("obs.scope", _LOCK)
_PENDING: list[_Pending] = []
_CLOSED: list[dict] = []  # ring: trimmed to _RING_CAP on append
_SEQ = 0
_SAMPLER: threading.Thread | None = None
_SAMPLER_DEATHS = 0
_TLS = threading.local()


def _leaf_ready(leaf) -> bool:
    try:
        return bool(leaf.is_ready())
    except Exception:
        # a buffer donated into the next program (or an exotic array
        # type): its producing program is chained into the consumer's
        # already-open interval — treat as ready, the lane stays whole
        return True


# -- recording (choke-point callbacks; any dispatching thread) -----------

def track(program: str, t0: float, leaves, cost=None) -> bool:
    """Open an in-flight interval for one dispatched program.

    ``leaves`` are the dispatch's output leaves; only leaves exposing
    ``is_ready()`` participate (tracer outputs — a program inlining
    into an outer trace — have none, and are deliberately not counted
    as dispatches).  ``cost`` is the dispatched executable's captured
    cost_analysis (:func:`~.roofline.capture_cost`; the program cache
    passes it on the AOT path) — it rides the interval so the closed
    timeline carries flops/bytes per dispatch.  Returns True when an
    interval was opened.  Host-only: a time read, a lock, a list
    append, a registry increment."""
    live = [x for x in leaves if hasattr(x, "is_ready")]
    if not live:
        return False
    now = time.perf_counter()
    global _SEQ
    with _COND:
        _sweep_locked(now)
        seq = _SEQ
        _SEQ += 1
        _PENDING.append(_Pending(str(program), float(t0), live, seq, cost))
        _ensure_sampler_locked()
        _COND.notify()
    _registry().counter("device.dispatches", str(program)).inc()
    return True


class absorb:
    """Suppress inner-choke-point tracking on this thread: the program
    cache wraps its dispatch call in one of these so the graftsan
    ``ExecuteReplicated`` hook (which the same call funnels through
    while a sanitizer is active) does not open a duplicate interval
    for the identical execution."""

    __slots__ = ()

    def __enter__(self):
        _TLS.absorb = getattr(_TLS, "absorb", 0) + 1
        return self

    def __exit__(self, *exc):
        _TLS.absorb -= 1
        return False


def absorbed() -> bool:
    return getattr(_TLS, "absorb", 0) > 0


# -- interval closing ----------------------------------------------------

def _close_locked(p: _Pending, t1: float) -> None:
    iv = {
        "program": p.program,
        "t0": p.t0,
        "t1": max(float(t1), p.t0),
        "seq": p.seq,
    }
    if p.cost is not None:
        iv["flops"] = p.cost.get("flops", 0.0)
        iv["bytes"] = p.cost.get("bytes", 0.0)
    _CLOSED.append(iv)
    if len(_CLOSED) > _RING_CAP:
        del _CLOSED[: len(_CLOSED) - _RING_CAP]


def _sweep_locked(now: float) -> list[tuple]:
    done = [p for p in _PENDING if all(_leaf_ready(x) for x in p.leaves)]
    if not done:
        return []
    closed = []
    for p in done:
        _PENDING.remove(p)
        _close_locked(p, now)
        closed.append((p.program, max(now - p.t0, 0.0), p.cost))
    # registry publication outside the hot predicate but still under
    # _LOCK: instrument locks nest inside, never the other way around.
    # roofline.py is pure host stdlib, so the attribution stays legal
    # on the sampler thread; the peaks lookup is loop-invariant and
    # hoisted so a busy sweep pays it once, not per interval.
    reg = _registry()
    peaks = None
    if any(cost is not None for _, _, cost in closed):
        from . import roofline as _roofline

        # fail-soft lookup: a malformed DASK_ML_TPU_PEAKS must raise on
        # the reporting surfaces, not kill the sampler or a dispatch
        peaks = _roofline.try_peaks_for(_roofline.detected_device_kind())
    for program, dur, cost in closed:
        reg.histogram("device.busy_s", program).record(dur)
        if cost is None:
            continue
        # roofline attribution lands with the interval: flops/bytes as
        # monotone counters (a /metrics scraper can rate() them), the
        # last closed interval's roofline fraction as a live gauge
        reg.counter("device.flops", program).inc(int(cost["flops"]))
        reg.counter("device.bytes", program).inc(int(cost["bytes"]))
        att = _roofline.attribution(cost["flops"], cost["bytes"], dur,
                                    peaks)
        if att["roofline_frac"] is not None:
            reg.gauge("device.roofline_frac", program).set(
                att["roofline_frac"])
    return closed


def sweep() -> None:
    """Close every pending interval whose outputs are ready (called by
    the sampler; safe from any thread — host-only)."""
    with _COND:
        _sweep_locked(time.perf_counter())


def settle(timeout_s: float = 5.0) -> bool:
    """Poll until no tracked dispatch is in flight (a report/bench
    boundary, never the hot path).  Returns False on timeout — a
    wedged program must not wedge its report."""
    deadline = time.monotonic() + timeout_s
    while True:
        with _COND:
            _sweep_locked(time.perf_counter())
            if not _PENDING:
                return True
        if time.monotonic() >= deadline:
            return False
        time.sleep(_SAMPLE_S)


# -- the sampler thread --------------------------------------------------

def _sampler_loop() -> None:
    from ..resilience import supervisor as _supervisor

    hb = _supervisor.register(SCOPE_THREAD_NAME, "obs",
                              thread=threading.current_thread())
    beats = 0
    while True:
        with _COND:
            while not _PENDING:
                _COND.wait()
            _sweep_locked(time.perf_counter())
        beats += 1
        if beats % _BEATS_EVERY == 0:
            # diagnostics.reset() wipes the supervisor table; a live
            # sampler re-registers itself so the endpoint's /healthz
            # keeps seeing it (rearm() covers the no-pending case)
            if _supervisor.lookup(SCOPE_THREAD_NAME) is not hb:
                hb = _supervisor.register(
                    SCOPE_THREAD_NAME, "obs",
                    thread=threading.current_thread())
            hb.beat()
        time.sleep(_SAMPLE_S)


def _ensure_sampler_locked() -> None:
    global _SAMPLER, _SAMPLER_DEATHS
    t = _SAMPLER
    if t is not None and t.is_alive():
        return
    if t is not None:
        _SAMPLER_DEATHS += 1
        if _SAMPLER_DEATHS > _MAX_RESTARTS:
            return  # degraded: sweep-on-dispatch + settle() only
        from ..resilience import supervisor as _supervisor

        _supervisor.note_death("obs", SCOPE_THREAD_NAME)
        _supervisor.note_restart("obs", SCOPE_THREAD_NAME)
    # host-only sampler: is_ready futures + heartbeat + registry — never
    # compiles, never dispatches (runtime-checked by graftsan, which
    # does NOT bless this name)
    _SAMPLER = threading.Thread(
        target=_sampler_loop, daemon=True, name=SCOPE_THREAD_NAME,
    )
    _SAMPLER.start()


def rearm() -> None:
    """Re-register a live sampler's supervisor heartbeat (called by
    ``diagnostics.reset()`` right after it wipes the unit table)."""
    from ..resilience import supervisor as _supervisor

    t = _SAMPLER
    if t is not None and t.is_alive() \
            and _supervisor.lookup(SCOPE_THREAD_NAME) is None:
        _supervisor.register(SCOPE_THREAD_NAME, "obs", thread=t)


# -- reading -------------------------------------------------------------

def cursor() -> int:
    """An opaque position in the interval sequence: pass to
    :func:`timeline` / :func:`device_report` as ``since`` to scope a
    read to dispatches tracked after this call (the bench per-workload
    delta idiom)."""
    with _LOCK:
        return _SEQ


def pending_count() -> int:
    with _LOCK:
        return len(_PENDING)


def open_intervals() -> list[dict]:
    """Every still-in-flight dispatch as ``{"program", "t0", "age_s"}``
    (oldest first) — the forensic read the flight-recorder dump uses: a
    hang DURING a long device program must show which program was in
    flight and for how long, not just the host-side open spans.  Pure
    host read; no sweep (the dump path must not poll readiness)."""
    now = time.perf_counter()
    with _LOCK:
        out = [{"program": p.program, "t0": p.t0,
                "age_s": round(max(now - p.t0, 0.0), 6)}
               for p in _PENDING]
    out.sort(key=lambda iv: iv["t0"])
    return out


def timeline(since: int | None = None, open_until: float | None = None):
    """Retained intervals (oldest first): closed ones from the ring
    plus — so a live scrape mid-fit sees the current busy period —
    every still-pending dispatch as ``[t0, open_until]`` (default: now)
    with ``"open": True``."""
    now = time.perf_counter() if open_until is None else float(open_until)
    with _COND:
        _sweep_locked(time.perf_counter())
        out = [dict(iv) for iv in _CLOSED
               if since is None or iv["seq"] >= since]
        for p in _PENDING:
            if since is None or p.seq >= since:
                out.append({"program": p.program, "t0": p.t0,
                            "t1": max(now, p.t0), "seq": p.seq,
                            "open": True})
    out.sort(key=lambda iv: (iv["t0"], iv["seq"]))
    return out


def _search_section() -> dict | None:
    """The adaptive-search registry families (``search.*`` —
    model_selection, design.md §17), rendered next to the device
    occupancy they budget against.  ``round_s`` records for EVERY
    search path (the sequential loop included); the scheduler families
    — ``dispatch_turns``, ``throttled``, ``queue_wait_s``,
    ``requeues``, the ``inflight`` gauge — appear only when the
    concurrent orchestrator actually ran (their absence next to
    ``round_s`` means the searches took the serialized path).  None
    when no search ran in this process (the section must not invent an
    empty story).  Pure registry reads — host-only, scrape-safe."""
    reg = _registry()
    counters: dict = {}
    gauges: dict = {}
    hists: dict = {}
    for name, tag, inst in reg.export_items():
        if not name.startswith("search."):
            continue
        key = f"{name[len('search.'):]}" + (f"{{{tag}}}" if tag else "")
        snap = getattr(inst, "snapshot", None)
        if callable(snap):
            s = snap()
            hists[key] = {k: s[k] for k in ("count", "sum", "p50", "p99")
                          if k in s}
        elif type(inst).__name__ == "Gauge":
            gauges[key] = inst.value
        else:
            counters[key] = inst.value
    if not (counters or gauges or hists):
        return None
    out: dict = dict(sorted(counters.items()))
    out.update(sorted(gauges.items()))
    out.update(sorted(hists.items()))
    return out


def _merge(intervals):
    """Union-merge sorted-by-t0 intervals -> (busy_s, merged, gaps)."""
    merged: list[list[float]] = []
    for iv in intervals:
        if merged and iv["t0"] <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], iv["t1"])
        else:
            merged.append([iv["t0"], iv["t1"]])
    busy = sum(b - a for a, b in merged)
    gaps = [{"t0": merged[i][1], "t1": merged[i + 1][0],
             "dur_s": merged[i + 1][0] - merged[i][1]}
            for i in range(len(merged) - 1)
            if merged[i + 1][0] > merged[i][1]]
    return busy, merged, gaps


def device_report(since: int | None = None, *, settle_s: float = 0.0,
                  top_gaps: int = 3) -> dict:
    """Device occupancy over the retained window::

        {"dispatches": n, "busy_s": s, "window_s": w, "idle_s": w - s,
         "utilization": s / w,            # 0.0 when nothing dispatched
         "idle_gaps": [{"t0", "t1", "dur_s"} x top-3, largest first],
         "programs": {name: {"dispatches": n, "busy_s": s}},
         "pending": still-in-flight count}

    The window is ``[first interval start, last interval end]`` of the
    retained (``since``-scoped) timeline — i.e. utilization of the
    period the device was actually in use.  ``settle_s > 0`` first waits (bounded) for in-flight
    dispatches so a *post-fit* report closes its last interval; a live
    scrape must pass 0 (the default — never wait on the device in a
    handler thread).

    Each program whose dispatches carried captured cost_analysis
    (:mod:`.roofline`) additionally reports its accumulated ``flops`` /
    ``bytes`` and the joined ``achieved_flops_per_s`` /
    ``achieved_bytes_per_s`` / ``intensity`` / ``roofline_frac``
    against the peak table; the top-level ``roofline`` block names the
    device kind and peaks (with provenance) those fractions used —
    absent when no device is detected, None fractions when the kind has
    no peaks (honesty over invention).

    When an adaptive search has run in this process, a ``search`` block
    rides along (``search.*`` registry families — per-round latency for
    every search path, plus the orchestrator's dispatch turns, throttle
    events, requeues, in-flight gauge, and queue-wait when the
    CONCURRENT plane ran): the scheduler budgets against exactly this
    report's idle time, so its books belong next to the occupancy they
    defend (design.md §17)."""
    if settle_s > 0:
        settle(settle_s)
    ivs = timeline(since)
    programs: dict[str, dict] = {}
    work: dict[str, list] = {}  # program -> [flops, bytes, costed_busy]
    for iv in ivs:
        p = programs.setdefault(iv["program"],
                                {"dispatches": 0, "busy_s": 0.0})
        p["dispatches"] += 1
        p["busy_s"] += iv["t1"] - iv["t0"]
        if "flops" in iv and not iv.get("open"):
            w = work.setdefault(iv["program"], [0.0, 0.0, 0.0])
            w[0] += iv["flops"]
            w[1] += iv["bytes"]
            w[2] += iv["t1"] - iv["t0"]
    from . import roofline as _roofline

    device_kind = _roofline.detected_device_kind()
    peaks = _roofline.peaks_for(device_kind)
    for name, p in programs.items():
        p["busy_s"] = round(p["busy_s"], 6)
        w = work.get(name)
        if w is not None:
            p.update(_roofline.attribution(w[0], w[1], w[2], peaks))
    search = _search_section()
    from . import critical as _critical

    verdicts = _critical.last_verdicts()
    if not ivs:
        out = {"dispatches": 0, "busy_s": 0.0, "window_s": 0.0,
               "idle_s": 0.0, "utilization": 0.0, "idle_gaps": [],
               "programs": {}, "pending": pending_count()}
        if search is not None:
            out["search"] = search
        if verdicts:
            out["critical"] = verdicts
        return out
    busy, merged, gaps = _merge(ivs)
    window = max(iv["t1"] for iv in ivs) - ivs[0]["t0"]
    gaps.sort(key=lambda g: -g["dur_s"])
    out = {
        "dispatches": len(ivs),
        "busy_s": round(busy, 6),
        "window_s": round(window, 6),
        "idle_s": round(max(window - busy, 0.0), 6),
        "utilization": round(busy / window, 4) if window > 0 else 0.0,
        "idle_gaps": [{k: round(v, 6) for k, v in g.items()}
                      for g in gaps[:top_gaps]],
        "programs": dict(sorted(programs.items())),
        "pending": pending_count(),
    }
    if device_kind is not None:
        out["roofline"] = {"device_kind": device_kind, "peaks": peaks}
    if search is not None:
        out["search"] = search
    # graftpath join (design.md §19): the most recent per-plane
    # bottleneck verdicts next to the occupancy they interpret —
    # absent when no verdict has been computed (no invented story)
    if verdicts:
        out["critical"] = verdicts
    return out


def reset() -> None:
    """Drop the timeline ring and every pending interval (test/bench
    isolation; the registry's ``device.*`` families are cleared by the
    caller's registry reset — ``obs.reset_all()`` does both)."""
    with _COND:
        _PENDING.clear()
        _CLOSED.clear()
