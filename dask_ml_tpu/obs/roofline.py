"""Roofline accounting: per-program FLOP/byte attribution vs peaks.

graftscope (:mod:`.scope`) measures per-program device *time*; this
module supplies the other two axes the ROADMAP ``[speed]`` lane needs —
**work** (FLOPs, bytes moved) and **capability** (the platform's peak
FLOP/s and bytes/s) — so a program's roofline share is a per-program
quantity instead of a hand estimate.

Work comes from XLA itself: at compile time the program cache
(:mod:`dask_ml_tpu.programs.cache`) calls :func:`capture_cost` on each
freshly built executable — ``compiled.cost_analysis()``, XLA's own
static estimate of flops and bytes accessed — and hands the numbers to
every subsequent dispatch's in-flight interval.  The scope sampler then
accumulates ``device.flops``/``device.bytes`` per program in the
metrics registry (scraped by ``/metrics``) and
:func:`~.scope.device_report` joins work with measured busy time into
achieved FLOP/s, achieved bytes/s, arithmetic intensity, and a roofline
fraction against the peak table below.

Honesty contract (design.md §16):

* ``cost_analysis`` is XLA's **static estimate** of one dispatch: a
  fused ``while_loop`` program (the Lloyd loop) counts its body ONCE —
  the trip count is data-dependent — so attributed flops for such
  programs are a lower bound and the roofline fraction is a *floor*,
  not a measurement of the loop body.  Straight-line step programs
  (the streamed SGD/MBK/serve hot loops) have no such slack.
* The peak table is keyed by ``device_kind`` as jax reports it on the
  device (``jax.devices()[0].device_kind``) and labelled by provenance:
  ``measured`` entries were timed on the box this repo gates on,
  ``published`` entries are the vendor's figures with their source,
  ``env`` entries came from the operator's :data:`PEAKS_ENV` knob.  An
  unknown kind yields no peaks and no roofline fraction — never a
  made-up one, and never another device's.
* Busy time here is graftscope's readiness-polled interval (scope.py
  honesty note); the XProf device trace stays the authority for a
  reported roofline share.

Pure host stdlib — no jax import (the obs posture).  The device kind is
NOTED by the program cache's compile path (:func:`note_device_kind`, on
a thread that is already compiling) rather than probed here: the scope
sampler and the metrics endpoint read it as a plain string, so they
stay provably host-only for the thread-dispatch analysis.
"""

from __future__ import annotations

import os
import threading

from .._locks import make_lock

__all__ = [
    "PEAKS_ENV",
    "DEFAULT_PEAKS",
    "parse_peaks",
    "peaks_for",
    "try_peaks_for",
    "note_device_kind",
    "detected_device_kind",
    "capture_cost",
    "attribution",
    "reset_cache",
]

#: policy knob: override/extend the peak table.  Format is
#: ``device_kind:flops=<float>,bytes=<float>[;device_kind:...]`` — e.g.
#: ``cpu:flops=1.4e11,bytes=2.6e10;TPU v5 lite:flops=1.97e14,bytes=8.19e11``
#: (kinds compare case-insensitively).  Strict parse (the repo's knob
#: posture): a malformed value raises at first use instead of silently
#: reading as defaults.
PEAKS_ENV = "DASK_ML_TPU_PEAKS"

#: peak capability per ``device_kind`` (lower-cased), labelled by
#: provenance.  The ``cpu`` row was MEASURED on the 2-core gate box
#: (best-of numpy fp32 gemm for flops, best-of 64 MiB memcpy read+write
#: for bytes, 2026-08-04 — the procedure is reproduced in design.md
#: §16).  The ``tpu v5 lite`` row (what a v5e chip reports) is the
#: PUBLISHED figure for one chip; its flops peak is the bf16 one, so an
#: fp32 program's fraction against it is a floor.
DEFAULT_PEAKS = {
    "cpu": {"flops_per_s": 1.4e11, "bytes_per_s": 2.6e10,
            "source": "measured (gate box: numpy fp32 gemm + memcpy, "
                      "2026-08-04)"},
    "tpu v5 lite": {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11,
                    "source": "published (Google Cloud documentation, "
                              "\"TPU v5e\": 197 bf16 TFLOP/s, 819 GB/s "
                              "HBM per chip)"},
}

_LOCK = make_lock("obs.roofline")
_CACHE: dict | None = None  # parsed env + defaults, resolved once


def parse_peaks(raw: str) -> dict:
    """Parse the :data:`PEAKS_ENV` format into ``{device_kind:
    {flops_per_s, bytes_per_s, source}}``.  Strict: unknown keys, missing fields, and
    non-positive numbers raise ``ValueError``."""
    out: dict = {}
    for part in raw.split(";"):
        part = part.strip()
        if not part:
            continue
        plat, sep, body = part.partition(":")
        plat = plat.strip().lower()
        if not sep or not plat:
            raise ValueError(
                f"{PEAKS_ENV}: expected 'device_kind:flops=...,bytes=...', "
                f"got {part!r}")
        entry: dict = {}
        for item in body.split(","):
            key, sep2, val = item.partition("=")
            key = key.strip().lower()
            if not sep2 or key not in ("flops", "bytes"):
                raise ValueError(
                    f"{PEAKS_ENV}: expected flops=<v>/bytes=<v>, got "
                    f"{item.strip()!r}")
            try:
                fv = float(val)
            except ValueError:
                raise ValueError(
                    f"{PEAKS_ENV}: {key} must be a number, got {val!r}"
                ) from None
            if fv <= 0:
                raise ValueError(f"{PEAKS_ENV}: {key} must be > 0")
            entry[f"{key}_per_s"] = fv
        if set(entry) != {"flops_per_s", "bytes_per_s"}:
            raise ValueError(
                f"{PEAKS_ENV}: device kind {plat!r} needs BOTH flops= and "
                f"bytes=")
        entry["source"] = "env"
        out[plat] = entry
    return out


def _table() -> dict:
    global _CACHE
    with _LOCK:
        if _CACHE is None:
            table = {k: dict(v) for k, v in DEFAULT_PEAKS.items()}
            raw = os.environ.get(PEAKS_ENV, "").strip()
            if raw:
                table.update(parse_peaks(raw))
            _CACHE = table
        return _CACHE


def try_peaks_for(device_kind: str | None) -> dict | None:
    """:func:`peaks_for` for the accounting hot paths (the scope
    sampler's sweep): a malformed :data:`PEAKS_ENV` returns None (one
    warning) instead of raising — the strict parse must surface on the
    loud reporting surface (``device_report``), never kill the daemon
    sampler or abort a fit from inside
    dispatch-time accounting."""
    try:
        return peaks_for(device_kind)
    except ValueError as e:
        global _WARNED
        if not _WARNED:
            _WARNED = True
            import logging

            logging.getLogger(__name__).warning(
                "roofline peaks unavailable on the accounting path "
                "(%s); roofline fractions will be absent until the "
                "knob is fixed", e)
        return None


_WARNED = False


def peaks_for(device_kind: str | None) -> dict | None:
    """Peak capability for ``device_kind`` (``{"flops_per_s",
    "bytes_per_s", "source"}``), or None for an unknown/undetected kind
    — the honest answer, never a made-up peak or another device's.
    Returns a copy: the entries end up embedded in reports callers may
    mutate, and a shared cache dict must not be corruptible from
    outside."""
    if not device_kind:
        return None
    entry = _table().get(str(device_kind).lower())
    return None if entry is None else dict(entry)


_DEVICE_KIND: str | None = None


def note_device_kind(device_kind) -> None:
    """Record the device kind (called by the program cache right after
    a compile, on a thread that is already device-blessed — this module
    must never touch jax itself)."""
    global _DEVICE_KIND
    if device_kind:
        _DEVICE_KIND = str(device_kind)


def detected_device_kind() -> str | None:
    """The kind of device the program cache last compiled for, or None
    before any cached compile — when nothing has compiled there is
    nothing to attribute, and an unknown device honestly has no peaks."""
    return _DEVICE_KIND


def reset_cache() -> None:
    """Forget the resolved peak table (test isolation: the next read
    re-applies :data:`PEAKS_ENV`; the noted device kind survives — it
    is a fact about the process, not a policy)."""
    global _CACHE, _WARNED
    with _LOCK:
        _CACHE = None
        _WARNED = False


# -- compile-time cost capture -------------------------------------------

def capture_cost(compiled) -> dict | None:
    """``{"flops": f, "bytes": b, "out_bytes": o}`` from an XLA
    executable's ``cost_analysis()``, or None when the backend cannot
    say (exotic programs, plug-in backends).  Fail-soft by contract:
    cost capture must never be able to break a compile."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops", 0.0)
    bytes_ = ca.get("bytes accessed", 0.0)
    out_b = ca.get("bytes accessedout{}", 0.0)
    try:
        flops, bytes_, out_b = float(flops), float(bytes_), float(out_b)
    except (TypeError, ValueError):
        return None
    if flops < 0 or bytes_ < 0:  # XLA's "unknown" sentinel
        return None
    return {"flops": flops, "bytes": bytes_, "out_bytes": max(out_b, 0.0)}


# -- the join ------------------------------------------------------------

def attribution(flops: float, bytes_: float, busy_s: float,
                peaks: dict | None) -> dict:
    """Achieved rates + roofline fraction for one program's accumulated
    (flops, bytes, busy seconds).

    The roofline bound at the program's arithmetic intensity ``I =
    flops/bytes`` is ``min(peak_flops, I * peak_bytes)``; the fraction
    is achieved FLOP/s over that bound — i.e. "how close to the best
    this machine could possibly do for a program of this intensity".  A
    zero-flop program (pure data movement) is scored on bandwidth
    alone.  Without peaks the rates still report; the fraction is None.
    """
    out: dict = {
        "flops": round(flops, 1),
        "bytes": round(bytes_, 1),
        "achieved_flops_per_s": (round(flops / busy_s, 1)
                                 if busy_s > 0 else 0.0),
        "achieved_bytes_per_s": (round(bytes_ / busy_s, 1)
                                 if busy_s > 0 else 0.0),
        "intensity": round(flops / bytes_, 4) if bytes_ > 0 else None,
        "roofline_frac": None,
    }
    if peaks is None or busy_s <= 0:
        return out
    pf, pb = peaks["flops_per_s"], peaks["bytes_per_s"]
    if flops > 0 and bytes_ > 0:
        bound = min(pf, (flops / bytes_) * pb)
        out["roofline_frac"] = round((flops / busy_s) / bound, 6)
    elif bytes_ > 0:
        out["roofline_frac"] = round((bytes_ / busy_s) / pb, 6)
    elif flops > 0:
        out["roofline_frac"] = round((flops / busy_s) / pf, 6)
    return out
