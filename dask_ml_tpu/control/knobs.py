"""graftpilot knob registry: every tuned parameter as a live, bounded value.

Every performance lever in the runtime is a documented env knob
(``DASK_ML_TPU_PREFETCH_DEPTH``, ``DATA_READERS``, ``DATA_QUEUE``,
``SERVE_WINDOW_MS``, ``SERVE_MAX_BATCH``, ``SEARCH_INFLIGHT`` — docs/api.md
§env) — but until this module they were constants frozen at construction:
every recorded win (the 1.45x 4-vs-1 readers under remote-store emulation,
the 1.27-1.55x concurrent search under emulated staging latency) required a human to read
the graftpath verdict and re-run.  This registry makes each of those
parameters a :class:`Knob`: bounded, strictly parsed, with a runtime
setter (:func:`set_knob`) and a change counter, so the controller loop
(:mod:`.pilot`) — or an operator over a debug console — can move them
mid-run and the owning planes pick the new value up at their natural
re-read points (block boundary / drain cycle / scheduler turn).

Resolution order, everywhere a plane sizes itself::

    explicit ctor arg  >  live override  >  env (strict parse)  >  default

The explicit arg pins the plane (a test that asks for ``readers=2`` gets
2 and the pilot leaves it alone — planes consult the override only when
the caller passed ``None``); the env path keeps its existing strict
parse-and-raise semantics in each plane's own resolver so a typo'd
deployment still fails loudly at construction.  :func:`set_knob` by
contrast CLAMPS to the knob's ``[lo, hi]`` — a controller step can never
push a plane out of its safe envelope, and a clamped move is still a
counted move.

Concurrency contract (graftlock-clean by construction): hot paths read
overrides through :func:`override_or` — one attribute load, no lock, no
``os.environ`` — so the serve drain loop / prefetch worker / reader
threads stay exactly as lock-free as before this module existed.  Only
:func:`set_knob` / :func:`clear_overrides` take the ``control.knobs``
lock, and they acquire nothing else while holding it: zero new
lock-order edges vs ``tools/lock_baseline.json``.  Planes additionally
:func:`observe` the value they are actually running with (also a bare
attribute store) so the pilot steps from the live base — not from the
env default — when a bench detunes a plane with an explicit arg.

Pure host stdlib + the obs metrics registry: importable from any thread,
including the stage-purity-constrained prefetch worker.
"""

from __future__ import annotations

import os

from .._locks import make_lock
from ..obs.metrics import registry as _registry

__all__ = [
    "Knob",
    "KNOBS",
    "knob",
    "set_knob",
    "override",
    "override_or",
    "observe",
    "effective",
    "clear_override",
    "clear_overrides",
    "report",
]

#: one lock guards every override WRITE; reads are bare attribute loads
#: (CPython attribute stores are atomic — a reader sees the old value or
#: the new one, never a torn value).  Nothing else is ever acquired while
#: this is held, and it is never acquired while holding another lock on
#: the setter paths: no new lock-order edges.
_SET_LOCK = make_lock("control.knobs")


class Knob:
    """One live-tunable parameter: bounds, strict parse, change counter.

    ``_override`` is the runtime-set value (None = untouched: planes fall
    through to their env/default resolution).  ``_observed`` is the value
    the owning plane most recently sized itself with — the pilot's
    stepping base when no override exists yet.
    """

    __slots__ = ("name", "env", "kind", "default", "lo", "hi", "unit",
                 "doc", "changes", "_override", "_observed")

    def __init__(self, name: str, env: str, kind: type, default,
                 lo, hi, unit: str, doc: str):
        self.name = name
        self.env = env
        self.kind = kind          # int or float
        self.default = default    # None = dynamic (data_queue: 2x readers)
        self.lo = lo
        self.hi = hi
        self.unit = unit
        self.doc = doc
        self.changes = 0
        self._override = None
        self._observed = None

    # -- strict parse + clamp -------------------------------------------
    def parse(self, value):
        """Strictly parse ``value`` to this knob's kind; raise on junk.

        Accepts the kind itself, a string spelling of it, and (for float
        knobs) ints.  Booleans and floats-for-int-knobs are rejected —
        ``set_knob("data_readers", 2.5)`` is a bug, not a request.
        """
        if isinstance(value, bool):
            raise ValueError(
                f"knob {self.name!r} takes {self.kind.__name__}, "
                f"got bool {value!r}")
        if isinstance(value, str):
            try:
                value = self.kind(value)
            except ValueError:
                raise ValueError(
                    f"knob {self.name!r} must be {self.kind.__name__}, "
                    f"got {value!r}") from None
        elif self.kind is float and isinstance(value, int):
            value = float(value)
        elif not isinstance(value, self.kind):
            raise ValueError(
                f"knob {self.name!r} must be {self.kind.__name__}, "
                f"got {value!r}")
        return value

    def clamp(self, value):
        return min(max(value, self.lo), self.hi)

    # -- resolution helpers ---------------------------------------------
    def env_value(self):
        """Strict env resolution (no override, no observation): the
        knob's env var parsed with parse-or-raise semantics, else its
        static default (None for dynamic defaults)."""
        raw = os.environ.get(self.env)
        if raw is None:
            return self.default
        try:
            return self.kind(raw)
        except ValueError:
            raise ValueError(
                f"{self.env} must be {self.kind.__name__}, "
                f"got {raw!r}") from None

    def effective(self):
        """The value the system is (best-knowledge) running with:
        override > plane-observed > env > static default."""
        if self._override is not None:
            return self._override
        if self._observed is not None:
            return self._observed
        return self.env_value()

    def __repr__(self):
        return (f"Knob({self.name!r}, override={self._override!r}, "
                f"observed={self._observed!r}, changes={self.changes})")


#: the six live knobs — one per documented performance lever.  ``hi`` is
#: a thrash guard, not a promise of benefit (effective reader parallelism
#: still caps at the epoch's shard count; serve max-batch is additionally
#: ceilinged at the server's construction value so a live raise can never
#: force a steady-state compile past the warmed bucket rungs).
KNOBS: dict[str, Knob] = {k.name: k for k in (
    Knob("prefetch_depth", "DASK_ML_TPU_PREFETCH_DEPTH", int, 2, 0, 64,
         "blocks", "staged-block queue capacity between the prefetch "
         "worker and the consumer (pipeline/core.py)"),
    Knob("data_readers", "DASK_ML_TPU_DATA_READERS", int, 4, 1, 64,
         "threads", "parallel shard readers per dataset stream "
         "(data/readers.py)"),
    Knob("data_queue", "DASK_ML_TPU_DATA_QUEUE", int, None, 1, 256,
         "blocks", "reorder-window blocks readers may run ahead of the "
         "consumer (default 2x readers)"),
    Knob("serve_window_ms", "DASK_ML_TPU_SERVE_WINDOW_MS", float, 2.0,
         0.0, 1000.0, "ms", "micro-batch coalescing window ceiling "
         "(serve/batcher.py)"),
    Knob("serve_max_batch", "DASK_ML_TPU_SERVE_MAX_BATCH", int, 1024, 1,
         1 << 20, "rows", "micro-batch row cap (live moves clamp to the "
         "server's construction value: the compile ceiling)"),
    Knob("search_inflight", "DASK_ML_TPU_SEARCH_INFLIGHT", int, 8, 1,
         256, "programs", "device-queue cap per scheduler turn "
         "(model_selection/_orchestrator.py)"),
)}


def knob(name: str) -> Knob:
    """The named :class:`Knob`; unknown names raise (strict registry)."""
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"unknown knob {name!r} (have: {', '.join(sorted(KNOBS))})"
        ) from None


def set_knob(name: str, value, source: str = "api") -> object:
    """Set a live override: strict-parse, CLAMP to bounds, count the
    change, publish the ``control.knob_value{name}`` gauge.  Returns the
    clamped value actually installed."""
    k = knob(name)
    v = k.clamp(k.parse(value))
    with _SET_LOCK:
        k._override = v
        k.changes += 1
    # instruments outside the knob lock: the registry has its own plain
    # (unmonitored) locks and must not nest under control.knobs
    _registry().gauge("control.knob_value", name).set(float(v))
    _registry().counter("control.knob_set", source).inc()
    return v


def override(name: str):
    """The live override (or None) — lock-free."""
    return knob(name)._override


def override_or(name: str, base):
    """Hot-path read: the live override if one is set, else ``base``.
    One attribute load, no lock, never touches ``os.environ`` — legal
    per drain cycle / scheduler turn / block boundary."""
    ov = KNOBS[name]._override
    return base if ov is None else ov


def observe(name: str, value) -> None:
    """Plane-side: record the value this plane is actually running with
    (bare attribute store).  Gives the pilot a stepping base when the
    plane was sized by an explicit arg or env rather than an override."""
    KNOBS[name]._observed = value


def effective(name: str):
    return knob(name).effective()


def clear_override(name: str) -> None:
    k = knob(name)
    with _SET_LOCK:
        k._override = None


def clear_overrides() -> None:
    """Drop every override and observation (test/bench isolation; change
    counters are cumulative and survive, like every other counter)."""
    with _SET_LOCK:
        for k in KNOBS.values():
            k._override = None
            k._observed = None


def report() -> dict:
    """``{name: {override, observed, effective, changes, lo, hi, env}}``
    — the diagnostics view of the live knob table."""
    out = {}
    for name, k in sorted(KNOBS.items()):
        try:
            eff = k.effective()
        except ValueError:
            eff = None  # junk env var: construction would raise loudly
        out[name] = {
            "override": k._override,
            "observed": k._observed,
            "effective": eff,
            "changes": k.changes,
            "lo": k.lo,
            "hi": k.hi,
            "env": k.env,
            "unit": k.unit,
        }
    return out
