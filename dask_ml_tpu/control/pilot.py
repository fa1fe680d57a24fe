"""graftpilot: the verdict-driven closed-loop knob controller.

PR 15 (graftpath, design.md §19) turned "why is my fit slow" into a
machine-readable bottleneck VERDICT; :mod:`.knobs` turned every
performance lever into a live, bounded setter.  This module closes the
loop: a host-only supervised unit (literal thread name
``dask-ml-tpu-pilot``, declared in ``_spmd.HOST_ONLY_THREAD_NAMES`` so
graftlint accepts it statically and graftsan runtime-verifies it) polls
the live critical-path attribution on a cadence and applies the policy
table (design.md §21)::

    plane   verdict class      knob            direction
    ------  -----------------  --------------  ---------
    fit     parse-bound        data_readers    up    (then prefetch_depth)
    fit     fetch-bound        data_readers    up    (readers parallelize
                                                     the fetch RTT — the
                                                     recorded 1.45x lever;
                                                     then prefetch_depth)
    fit     stage-bound        prefetch_depth  up
    fit     queue-bound        data_queue      up
    search  dispatcher-bound   search_inflight up
    search  queue-bound        search_inflight up    (the scheduler's own
                                                     throttle IS the queue)
    search  stage-bound        search_inflight up    (cross-unit overlap)
    serve   queue-bound        serve_window_ms up    (then serve_max_batch)
    serve   dispatcher-bound   serve_window_ms down  (window dominates the
                                                     request: stop waiting)
    *       device-bound       —               (goal state: freeze)

Hysteresis, because a controller that thrashes is worse than no
controller:

* **confidence threshold** — only CONFIDENT verdicts (graftpath's
  dominance gate) move anything; low confidence freezes the cycle;
* **cooldown** — after a move, ``cooldown`` cycles must pass before the
  next move, so the effect lands in the books first;
* **step limits** — multiplicative steps (x2 / ÷2), each knob capped at
  ``max_moves`` moves per pilot lifetime plus the registry's hard
  ``[lo, hi]`` clamp;
* **revert-on-regression** — each move's before/after progress rate
  (blocks + serve requests per second) is compared after the cooldown:
  a regression reverts the knob to its prior value and burns that
  (knob, direction); a measurably-flat result (below the noise floor,
  above the revert line) keeps the value but burns the direction so the
  pilot cannot ratchet a dead knob forever.

And one HARD guard ahead of everything else: **saturation freeze**.
When the process is CPU-pinned (Δprocess_time/Δwall ≥ 0.9 over the
cycle), more host threads cannot help and every
move would thrash the GIL, so the pilot freezes
(``control.freeze{saturation_pinned}``) — the 1-core gate box can never
be thrashed, and the seeded false-verdict liveness test asserts this
guard wins even over an injected verdict.

Seeded-fault liveness (the gate-of-the-gate, same posture as graftlock's
``--inject-*``): ``DASK_ML_TPU_PILOT_INJECT=false-verdict`` forces a
synthetic CONFIDENT parse-bound fit verdict each cycle; the self-test
(``python -m dask_ml_tpu.control --self-test``, wired into
``tools/lint.sh``) asserts the controller both MOVES the readers knob
under the injected verdict and still FREEZES under synthetic
saturation — a blind or disabled controller exits nonzero and can never
gate.
"""

from __future__ import annotations

import os
import threading
import time

from contextlib import contextmanager

from .._locks import make_lock
from ..obs import event as _obs_event
from ..obs import spans as _spans
from ..obs.critical import critical_path as _critical_path
from ..obs.metrics import registry as _registry
from ..resilience import supervisor as _supervisor
from . import knobs as _knobs

__all__ = [
    "AUTOPILOT_ENV",
    "CADENCE_ENV",
    "INJECT_ENV",
    "PILOT_THREAD_NAME",
    "Autopilot",
    "autopilot",
    "active_holds",
    "hold",
    "maybe_autostart",
    "current_pilot",
    "stop_pilot",
    "self_test",
]

AUTOPILOT_ENV = "DASK_ML_TPU_AUTOPILOT"
CADENCE_ENV = "DASK_ML_TPU_PILOT_CADENCE_MS"
INJECT_ENV = "DASK_ML_TPU_PILOT_INJECT"

#: the literal supervised host-only thread name — declared in
#: analysis/rules/_spmd.HOST_ONLY_THREAD_NAMES (graftlint static roster)
#: and runtime-verified by graftsan's thread sweep.
PILOT_THREAD_NAME = "dask-ml-tpu-pilot"

_DEFAULT_CADENCE_MS = 100.0
#: saturation_pinned: Δprocess_time/Δwall over a cycle >= 0.9
_SATURATION_FRAC = 0.9
#: minimum progress events in a settle window before the before/after
#: rate comparison is trusted (see :meth:`Autopilot._settle_pending`)
_SETTLE_MIN_ITEMS = 8

#: (plane, verdict class) -> ordered (knob, direction) escalation chain.
#: The first un-burned, un-capped knob in the chain moves; classes with
#: no entry (device-bound, unknown) freeze — device-bound IS the goal.
POLICY: dict[tuple, tuple] = {
    ("fit", "parse-bound"): (("data_readers", "up"),
                             ("prefetch_depth", "up")),
    ("fit", "fetch-bound"): (("data_readers", "up"),
                             ("prefetch_depth", "up")),
    ("fit", "stage-bound"): (("prefetch_depth", "up"),),
    ("fit", "queue-bound"): (("data_queue", "up"),),
    ("search", "dispatcher-bound"): (("search_inflight", "up"),),
    ("search", "queue-bound"): (("search_inflight", "up"),),
    ("search", "stage-bound"): (("search_inflight", "up"),),
    ("search", "parse-bound"): (("data_readers", "up"),),
    ("search", "fetch-bound"): (("data_readers", "up"),
                                ("prefetch_depth", "up")),
    ("serve", "queue-bound"): (("serve_window_ms", "up"),
                               ("serve_max_batch", "up")),
    ("serve", "dispatcher-bound"): (("serve_window_ms", "down"),),
}

#: histograms whose exact counts proxy end-to-end progress (blocks
#: consumed + requests served) for revert-on-regression rates.
_PROGRESS_FAMILIES = ("pipeline.block_s", "serve.request_s")

#: external hold latches: while any is set the pilot freezes every
#: cycle (counted under ``control.freeze{<reason>}``) instead of
#: reading books a drain barrier is actively disturbing — the fleet's
#: rolling deploy holds ``fleet_drain`` across each replica's drain
#: window, so half-drained latency never trains a knob move.
_HOLDS: dict = {}
_HOLDS_LOCK = make_lock("control.holds")


def active_holds() -> tuple:
    """The currently-held freeze reasons (sorted; empty = none)."""
    with _HOLDS_LOCK:
        return tuple(sorted(k for k, n in _HOLDS.items() if n > 0))


@contextmanager
def hold(reason: str):
    """Freeze the pilot for the duration of the block (re-entrant:
    nested holds of one reason count)."""
    reason = str(reason)
    with _HOLDS_LOCK:
        _HOLDS[reason] = _HOLDS.get(reason, 0) + 1
    try:
        yield
    finally:
        with _HOLDS_LOCK:
            n = _HOLDS.get(reason, 1) - 1
            if n <= 0:
                _HOLDS.pop(reason, None)
            else:
                _HOLDS[reason] = n


def _env_on(env: str, default: bool = False) -> bool:
    raw = os.environ.get(env)
    if raw is None or raw.strip() == "":
        return default
    v = raw.strip().lower()
    if v in ("1", "on", "true", "yes"):
        return True
    if v in ("0", "off", "false", "no"):
        return False
    raise ValueError(f"{env} must be on/off (1/0/true/false), got {raw!r}")


def resolve_cadence_ms(cadence_ms: float | None = None) -> float:
    """Pilot cycle cadence in ms: explicit arg > env > 100.0 (strict
    parse, >= 1 ms — a sub-ms controller would be pure overhead)."""
    if cadence_ms is None:
        raw = os.environ.get(CADENCE_ENV)
        if raw is None:
            return _DEFAULT_CADENCE_MS
        try:
            cadence_ms = float(raw)
        except ValueError:
            raise ValueError(
                f"{CADENCE_ENV} must be a float, got {raw!r}") from None
    cadence_ms = float(cadence_ms)
    if cadence_ms < 1.0:
        raise ValueError(
            f"pilot cadence must be >= 1 ms, got {cadence_ms}")
    return cadence_ms


def resolve_inject() -> str | None:
    """The seeded-fault mode (``false-verdict``) or None; junk raises."""
    raw = os.environ.get(INJECT_ENV)
    if raw is None or raw.strip() == "":
        return None
    v = raw.strip()
    if v != "false-verdict":
        raise ValueError(
            f"{INJECT_ENV} must be 'false-verdict' (or unset), got {raw!r}")
    return v


def _progress_count() -> int:
    """Exact end-to-end progress: blocks consumed + requests served."""
    total = 0
    for name, _tag, inst in _registry().export_items():
        if name in _PROGRESS_FAMILIES:
            total += inst.count
    return total


class _Window:
    """A synthetic root span over ``[t0, t1]`` — graftpath only reads
    ``t0/t1/name/span_id``, so a live window needs no completed root."""

    __slots__ = ("name", "t0", "t1", "span_id")

    def __init__(self, t0: float, t1: float, plane: str):
        # _plane_of() keys off the root-name prefix
        self.name = f"{'search' if plane == 'search' else 'fit'}.window"
        self.t0 = t0
        self.t1 = t1
        self.span_id = None


class Autopilot:
    """The controller loop.  ``start()`` spawns the supervised host-only
    thread; tests and the self-test drive ``_cycle()`` synchronously."""

    def __init__(self, *, cadence_ms: float | None = None,
                 confidence_min: float | None = None,
                 cooldown: int = 3, max_moves: int = 8,
                 _test_cpu_frac: float | None = None):
        self.cadence_s = resolve_cadence_ms(cadence_ms) / 1e3
        #: verdicts must be CONFIDENT (graftpath dominance) AND at least
        #: this sure before anything moves
        self.confidence_min = (0.35 if confidence_min is None
                               else float(confidence_min))
        self.cooldown = max(1, int(cooldown))
        self.max_moves = max(1, int(max_moves))
        self._test_cpu_frac = _test_cpu_frac
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._hb = None
        # cycle state
        self.cycles = 0
        self.moves: list[dict] = []
        self.reverts: list[dict] = []
        self.freezes: dict[str, int] = {}
        self._last_t: float | None = None
        self._last_cpu: float | None = None
        self._samples: list[tuple] = []   # (t, progress) per cycle
        self._burned: set = set()         # (knob, direction)
        self._moves_per_knob: dict[str, int] = {}
        self._pending: dict | None = None  # move awaiting its verdict
        self._cycles_since_move = 10 ** 9
        self._serve_prev: dict | None = None
        self.errors = 0

    # -- lifecycle ------------------------------------------------------
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> "Autopilot":
        if self.running():
            return self
        # the verdict engine reads span records; arm tracing if the host
        # has not (the cost is three records a streamed block:
        # tests/test_obs.py; PERF.md section 6, PR 26, on the chip)
        if not _spans.enabled():
            _spans.enable()
        self._stop.clear()
        self._hb = _supervisor.register(
            "control:pilot", "control",
            interval_s=max(self.cadence_s * 20.0, 2.0))
        # host-only controller by contract (_spmd.HOST_ONLY_THREAD_NAMES,
        # runtime-held by graftsan): it reads span/metric books and
        # writes knob overrides — never compiles, never dispatches; the
        # unprovable calls are obs.spans.event() stdlib bookkeeping
        # graftlint: disable=thread-dispatch -- host-only pilot: verdict reads + knob writes + stdlib span events, never device program dispatch (runtime-verified via HOST_ONLY_THREAD_NAMES)
        t = threading.Thread(target=self._run, name=PILOT_THREAD_NAME,
                             daemon=True)
        self._thread = t
        self._hb._thread = t
        t.start()
        _obs_event("control.pilot_start", cadence_ms=self.cadence_s * 1e3)
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        if self._hb is not None:
            self._hb.retire()

    def _run(self) -> None:
        while not self._stop.wait(self.cadence_s):
            try:
                self._cycle()
            except Exception as exc:  # the pilot must never take the
                self.errors += 1      # process down: count and carry on
                _registry().counter("control.error", "pilot").inc()
                _obs_event("control.pilot_error", error=repr(exc))

    # -- one control cycle ----------------------------------------------
    def _beat(self) -> None:
        if self._hb is None:
            return
        if _supervisor.lookup(self._hb.name) is not self._hb:
            # diagnostics.reset() dropped the registry entry mid-run;
            # re-register so /healthz keeps covering the pilot
            self._hb = _supervisor.register(
                self._hb.name, self._hb.domain, thread=self._thread,
                interval_s=self._hb.interval_s)
        self._hb.beat()

    # NOTE on the single-owner suppressions below: every attribute the
    # cycle path writes (freezes/moves/reverts/_samples/_burned/
    # _moves_per_knob) is owned by whichever ONE thread drives
    # ``_cycle()`` — the pilot thread once ``start()`` ran, or the
    # main-thread self-test/tests on a pilot that never starts.  No
    # instance is ever driven from two threads (start() refuses while
    # running; the self-test pilots have no thread), so there is no
    # interleaving to guard; a lock here would be pure overhead held
    # every 100 ms on a host-only thread.

    def _freeze(self, reason: str) -> None:
        # graftlint: disable=unguarded-shared-state -- single-owner cycle state (see NOTE above _freeze)
        self.freezes[reason] = self.freezes.get(reason, 0) + 1
        _registry().counter("control.freeze", reason).inc()

    def _cycle(self) -> None:
        now = time.monotonic()
        cpu = time.process_time()
        self._beat()
        self.cycles += 1
        self._cycles_since_move += 1
        # graftlint: disable=unguarded-shared-state -- single-owner cycle state (see NOTE above _freeze)
        self._samples.append((now, _progress_count()))
        if len(self._samples) > 4 * self.cooldown + 8:
            del self._samples[:-(4 * self.cooldown + 8)]
        last_t, last_cpu = self._last_t, self._last_cpu
        self._last_t, self._last_cpu = now, cpu
        if last_t is None or now - last_t <= 0.0:
            return  # first cycle primes the cpu/progress baselines

        # external hold latch (fleet drain barriers): the books are
        # being deliberately disturbed — freeze, don't learn from them
        held = active_holds()
        if held:
            self._freeze(held[0])
            return

        # settle any pending move before considering a new one; while
        # the settle window is still growing, no stacked moves
        self._settle_pending()
        if self._pending is not None:
            return

        # HARD guard: a CPU-pinned process cannot benefit from more host
        # threads; every policy below would thrash.  Wins over inject.
        if self._test_cpu_frac is not None:
            cpu_frac = float(self._test_cpu_frac)
        else:
            cpu_frac = (cpu - last_cpu) / (now - last_t)
        if cpu_frac >= _SATURATION_FRAC:
            self._freeze("saturation_pinned")
            return

        inject = resolve_inject()
        if inject == "false-verdict":
            plane, verdict = "fit", {"class": "parse-bound",
                                     "confidence": 1.0,
                                     "confident": True,
                                     "injected": True}
        else:
            got = self._live_verdict(last_t, now)
            if got is None:
                return  # nothing ran this window: hold, not a freeze
            plane, verdict = got

        if self._cycles_since_move < self.cooldown:
            return  # cooldown: let the last move land in the books
        self._apply(plane, verdict)

    # -- verdict acquisition --------------------------------------------
    def _live_verdict(self, lo: float, hi: float):
        """(plane, verdict) for the just-elapsed window, or None when
        nothing ran.  fit/search comes from graftpath over a synthetic
        window root; serve from the per-leg request split deltas."""
        records = [r for r in _spans.span_records()
                   if getattr(r, "kind", "span") == "span"
                   and r.t1 > lo and r.t0 < hi]
        fit_like = None
        if records:
            plane = ("search" if any(r.name.startswith("search.")
                                     for r in records) else "fit")
            res = _critical_path(root=_Window(lo, hi, plane),
                                 records=records, publish=False)
            v = res.get("verdict") or {}
            if v.get("class") not in (None, "unknown"):
                fit_like = (res.get("plane") or plane, v)
        serve = self._serve_window_verdict()
        if fit_like is not None and serve is not None:
            # one move per cycle: follow the more confident story
            return (fit_like if fit_like[1].get("confidence", 0.0)
                    >= serve[1].get("confidence", 0.0) else serve)
        return fit_like if fit_like is not None else serve

    def _serve_window_verdict(self):
        """Windowed serve verdict from per-leg sum deltas (the
        cumulative histograms behind :func:`~..obs.critical.serve_critical`,
        differenced per cycle so the pilot sees the CURRENT regime, not
        the whole process history)."""
        sums = {seg: 0.0 for seg in ("queue", "window", "device",
                                     "fetch")}
        count = 0
        for name, _tag, inst in _registry().export_items():
            for seg in sums:
                if name == f"serve.req_{seg}_s":
                    sums[seg] += inst.sum
                    if seg == "queue":
                        count += inst.count
        prev, self._serve_prev = self._serve_prev, {"sums": sums,
                                                    "count": count}
        if prev is None or count <= prev["count"]:
            return None  # no (new) serve traffic this window
        delta = {seg: max(sums[seg] - prev["sums"][seg], 0.0)
                 for seg in sums}
        total = sum(delta.values())
        if total <= 0.0:
            return None
        shares = {seg: v / total for seg, v in delta.items()}
        top = max(shares, key=shares.get)
        cls = {"queue": "queue-bound", "window": "dispatcher-bound",
               "device": "device-bound", "fetch": "fetch-bound"}[top]
        return ("serve", {"class": cls, "confidence": shares[top],
                          "confident": shares[top] >= self.confidence_min})

    # -- the move engine -------------------------------------------------
    def _rate(self, n_cycles: int) -> float | None:
        """Progress rate (items/s) over the last ``n_cycles`` samples."""
        if len(self._samples) < n_cycles + 1:
            return None
        t1, p1 = self._samples[-1]
        t0, p0 = self._samples[-1 - n_cycles]
        if t1 <= t0:
            return None
        return (p1 - p0) / (t1 - t0)

    def _settle_pending(self) -> None:
        """After a move's cooldown: regression reverts + burns, a flat
        result keeps the value but burns the direction (no ratcheting a
        dead knob), an improvement keeps the chain alive.

        The judgment window GROWS until it holds at least
        ``_SETTLE_MIN_ITEMS`` progress events (up to ``4 * cooldown``
        cycles): a cooldown-sized window on a slow plane sees two or
        three blocks, and judging on that much quantization reverts
        good moves.  A window with ZERO progress is an idle gap between
        fits — a sizing knob cannot halt a plane — so the move is kept
        unjudged rather than read as a collapse."""
        p = self._pending
        if p is None or self._cycles_since_move < self.cooldown:
            return
        n = min(self._cycles_since_move, 4 * self.cooldown)
        before = p["rate_before"]
        if (before is None or before <= 0.0
                or len(self._samples) < n + 1):
            self._pending = None
            return  # progress meter blind around the move: keep it
        t1, p1 = self._samples[-1]
        t0, p0 = self._samples[-1 - n]
        items = p1 - p0
        if items <= 0 or t1 <= t0:
            self._pending = None
            return  # idle gap (nothing ran in the window): keep it
        if items < _SETTLE_MIN_ITEMS:
            if n < 4 * self.cooldown:
                return  # window too thin to judge yet: let it grow
            self._pending = None
            return  # capped and still thin: too quantized to judge
        self._pending = None
        after = items / (t1 - t0)
        if after < 0.95 * before:
            _knobs.set_knob(p["knob"], p["prev"], source="pilot-revert")
            # graftlint: disable=unguarded-shared-state -- single-owner cycle state (see NOTE above _freeze)
            self._burned.add((p["knob"], p["direction"]))
            rec = dict(p, rate_after=after, action="revert")
            # graftlint: disable=unguarded-shared-state -- single-owner cycle state (see NOTE above _freeze)
            self.reverts.append(rec)
            _registry().counter("control.revert", p["knob"]).inc()
            _obs_event("control.knob_revert", knob=p["knob"],
                       to=p["prev"], rate_before=round(before, 3),
                       rate_after=round(after, 3))
        elif after < 0.98 * before:
            # measurably-not-helping (below the noise floor but above
            # the revert line): keep the value, burn the direction so
            # the chain moves on.  An ambiguous settle (~1.0x) keeps
            # the chain ALIVE — cooldown-sized rate windows on a loaded
            # box flap several percent, and max_moves still bounds a
            # genuinely dead knob.
            self._burned.add((p["knob"], p["direction"]))

    def _step(self, k: "_knobs.Knob", cur, direction: str):
        if k.kind is int:
            new = cur * 2 if direction == "up" else cur // 2
            if direction == "up":
                new = max(new, cur + 1)
        else:
            if direction == "up":
                new = cur * 2.0 if cur > 0.0 else 1.0
            else:
                new = cur / 2.0 if cur > 0.5 else 0.0
        return k.clamp(new)

    def _apply(self, plane: str, verdict: dict) -> None:
        cls = verdict.get("class", "unknown")
        chain = POLICY.get((plane, cls))
        if chain is None:
            self._freeze("no_policy")  # device-bound / unknown: the
            return                     # goal state, nothing to fix
        if (not verdict.get("confident")
                or verdict.get("confidence", 0.0) < self.confidence_min):
            self._freeze("low_confidence")
            return
        for name, direction in chain:
            if (name, direction) in self._burned:
                continue
            if self._moves_per_knob.get(name, 0) >= self.max_moves:
                continue
            k = _knobs.knob(name)
            cur = k.effective()
            if cur is None:
                continue  # dynamic default, never observed: no base
            new = self._step(k, cur, direction)
            if new == cur:
                self._burned.add((name, direction))  # at a hard bound
                continue
            _knobs.set_knob(name, new, source="pilot")
            # graftlint: disable=unguarded-shared-state -- single-owner cycle state (see NOTE above _freeze)
            self._moves_per_knob[name] = (
                self._moves_per_knob.get(name, 0) + 1)
            # pre-move rate over the widest window that is still all
            # post-previous-move: short windows are integer-quantized
            # (a 50 ms window sees a handful of blocks) and a biased
            # ``before`` mis-judges the settle either way
            n_before = min(self._cycles_since_move, 4 * self.cooldown)
            rate_before = self._rate(n_before)
            self._cycles_since_move = 0
            move = {"knob": name, "direction": direction, "prev": cur,
                    "to": new, "plane": plane, "class": cls,
                    "confidence": round(
                        float(verdict.get("confidence", 0.0)), 4),
                    "injected": bool(verdict.get("injected", False)),
                    "cycle": self.cycles}
            # graftlint: disable=unguarded-shared-state -- single-owner cycle state (see NOTE above _freeze)
            self.moves.append(move)
            if not move["injected"]:
                # injected verdicts have no real throughput to judge
                self._pending = dict(move, rate_before=rate_before)
            _registry().counter("control.knob_move",
                                f"{name}:{direction}").inc()
            _obs_event("control.knob_move", knob=name,
                       direction=direction, prev=cur, to=new,
                       plane=plane, verdict=cls)
            return
        self._freeze("policy_exhausted")

    # -- reporting -------------------------------------------------------
    def converged(self, quiet_cycles: int | None = None) -> bool:
        """True once the pilot has gone ``quiet_cycles`` (default: one
        cooldown) cycles without a move."""
        q = self.cooldown if quiet_cycles is None else int(quiet_cycles)
        return self._cycles_since_move >= q and self._pending is None

    def report(self) -> dict:
        return {
            "running": self.running(),
            "cadence_ms": self.cadence_s * 1e3,
            "cycles": self.cycles,
            "moves": list(self.moves),
            "reverts": list(self.reverts),
            "freezes": dict(self.freezes),
            "burned": sorted(f"{k}:{d}" for k, d in self._burned),
            "converged": self.converged(),
            "errors": self.errors,
            "knobs": _knobs.report(),
        }


# -- process-global pilot (env-armed) ------------------------------------

_PILOT_LOCK = make_lock("control.pilot")
_PILOT: Autopilot | None = None


def current_pilot() -> Autopilot | None:
    return _PILOT


def maybe_autostart() -> Autopilot | None:
    """Arm the process-global pilot iff ``DASK_ML_TPU_AUTOPILOT`` is on.
    Called from the planes' entry points (stream construction, server
    construction, search run) — idempotent and cheap when off."""
    if not _env_on(AUTOPILOT_ENV):
        return None
    global _PILOT
    with _PILOT_LOCK:
        p = _PILOT
        if p is None or not p.running():
            p = _PILOT = Autopilot()
    if not p.running():
        p.start()
    return p


def stop_pilot() -> None:
    """Stop (and forget) the process-global pilot, if any."""
    global _PILOT
    with _PILOT_LOCK:
        p, _PILOT = _PILOT, None
    if p is not None:
        p.stop()


@contextmanager
def autopilot(**kwargs):
    """Scoped pilot for benches/tests: start, yield, always stop and
    clear the overrides it installed."""
    p = Autopilot(**kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()
        _knobs.clear_overrides()


# -- seeded-fault liveness (the gate-of-the-gate) -------------------------

def self_test(verbose: bool = True) -> int:
    """Exit-code semantics for ``python -m dask_ml_tpu.control
    --self-test``: 0 = the controller is LIVE (the injected false
    verdict moved the readers knob AND synthetic saturation froze a
    second pilot); nonzero = blind, broken, or explicitly disabled —
    and a blind controller must never gate."""
    def say(msg):
        if verbose:
            print(f"graftpilot self-test: {msg}")

    try:
        if not _env_on(AUTOPILOT_ENV, default=True):
            say(f"controller DISABLED via {AUTOPILOT_ENV} — failing the "
                "gate (a disabled controller cannot vouch for itself)")
            return 1
    except ValueError as exc:
        say(f"bad {AUTOPILOT_ENV}: {exc}")
        return 1
    prior_inject = os.environ.get(INJECT_ENV)
    os.environ.setdefault(INJECT_ENV, "false-verdict")
    if resolve_inject() != "false-verdict":
        say(f"unexpected {INJECT_ENV}={os.environ.get(INJECT_ENV)!r}")
        return 1
    reg = _registry()
    rc = 0
    _knobs.clear_overrides()
    try:
        # half 1: the injected parse-bound verdict must move readers UP
        p = Autopilot(cadence_ms=5.0, cooldown=1, _test_cpu_frac=0.0)
        base = _knobs.knob("data_readers").effective()
        for _ in range(3):
            p._cycle()
        moved = [m for m in p.moves if m["knob"] == "data_readers"
                 and m["direction"] == "up"]
        booked = reg.family("control.knob_move").get(
            "data_readers:up", 0)
        if not moved or _knobs.override("data_readers") is None:
            say("FAIL: injected false verdict did not move data_readers")
            rc = 1
        elif _knobs.override("data_readers") <= base or not booked:
            say("FAIL: data_readers move not upward / not booked")
            rc = 1
        else:
            say(f"move ok: data_readers {base} -> "
                f"{_knobs.override('data_readers')} "
                f"({len(moved)} move(s), injected verdict)")
        # half 2: saturation_pinned must freeze even an injected verdict
        _knobs.clear_overrides()
        frozen = Autopilot(cadence_ms=5.0, cooldown=1,
                           _test_cpu_frac=1.0)
        for _ in range(3):
            frozen._cycle()
        if frozen.moves or not frozen.freezes.get("saturation_pinned"):
            say("FAIL: saturation_pinned did not freeze the controller "
                f"(moves={frozen.moves}, freezes={frozen.freezes})")
            rc = 1
        else:
            say(f"freeze ok: {frozen.freezes['saturation_pinned']} "
                "saturation_pinned cycle(s), zero moves")
    finally:
        _knobs.clear_overrides()
        if prior_inject is None:
            os.environ.pop(INJECT_ENV, None)
        else:
            os.environ[INJECT_ENV] = prior_inject
    if rc == 0:
        say("PASS (move + freeze)")
    return rc
