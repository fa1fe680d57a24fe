"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

``reduce_dir`` finds the newest trace under a directory and returns

- ``window_s``: the traced window, from the start of the first window
  span (``bench.fit``) to the end of the last (``bench.between``);
- ``busy_by_device`` / ``busy_s``: per device, the union of the intervals
  in which an XLA operation ran inside the window; and their mean;
- ``fits``: per ``bench.fit`` span its wall, the device-busy union inside
  it and the device seconds of every XLA module run inside it;
- ``breakdown``: the device operations that took most time, named
  ``<module>/<op> <result shape> <opcode>`` (each less what is nested
  inside it, averaged over the devices), and the
  idle time of the busiest device by the innermost window span open on
  the host meanwhile (a gap is cut where a span opens or closes).

Only ``jax.profiler.ProfileData`` is needed to read the file.  Device
planes are those named ``/device:TPU:<n>``; on each, operations are the
``XLA Ops`` line and modules the ``XLA Modules`` line.  Host spans are
``TraceAnnotation`` events on any line of the ``/host:CPU`` plane.  All
times are nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def union(intervals):
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def clipped_length(merged, lo, hi):
    """Length of disjoint sorted intervals inside ``[lo, hi]``."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def gaps(merged, lo, hi):
    """The idle intervals of ``[lo, hi]``: what ``merged`` leaves open."""
    out, at = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def innermost(spans, t):
    """Name of the shortest span that holds time ``t``, or ``"outside"``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside"


def self_seconds(events):
    """Per name, the seconds an operation ran less the operations nested
    inside it (a ``while`` holds its body's fusions on the same line)."""
    out, stack = {}, []  # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[800000]{0:T(1024)} fusion(...)`` ->
    ``%fusion.3 f32[800000] fusion``: name, first result shape, opcode."""
    name, _, rest = event_name.partition(" = ")
    if not rest:
        return event_name[:80]
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    opcode = re.search(r"[ )]([a-z][a-z\-]*)\(", rest)
    return " ".join(p for p in (name, shape and shape.group(0),
                                opcode and opcode.group(1)) if p)


def module_of(modules):
    """A lookup from a time to the name of the module running then."""
    spans = sorted((s, e, module_name(n)) for n, s, e in modules)
    starts = [s for s, _, _ in spans]

    def lookup(t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and t < spans[i][1] else "?"

    return lookup


def module_name(event_name: str) -> str:
    """``jit__admm_run(123456)`` -> ``jit__admm_run``: the run id goes."""
    return re.sub(r"\(\d+\)$", "", event_name)


def read_planes(path: str):
    """``(devices, host_spans)``: per device id its op and module events
    as ``(name, start_ns, end_ns)``, and every host event likewise."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            devices[int(found.group(1))] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return devices, host


def reduce(devices: dict, host: list, window=("bench.fit",)):
    """The numbers, from ``read_planes``' output.  ``window`` names the
    benchmark's own spans; the first is the fit."""
    spans = sorted((e for e in host if e[0] in window), key=lambda e: e[1])
    fit_spans = [e for e in spans if e[0] == window[0]]
    if not fit_spans:
        return None
    lo, hi = fit_spans[0][1], max(e[2] for e in spans)
    busy, by_device, op_seconds = {}, [], {}
    for dev, lines in sorted(devices.items()):
        ops = lines[OPS_LINE] or lines[MODULES_LINE]
        busy[dev] = union((s, e) for _, s, e in ops)
        by_device.append(clipped_length(busy[dev], lo, hi) / 1e9)
        module = module_of(lines[MODULES_LINE])
        inside = [(module(s) + "/" + op_name(n), max(s, lo), min(e, hi))
                  for n, s, e in ops if e > lo and s < hi]
        for name, own in self_seconds(inside).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + own / len(devices)
    fits = []
    for _, s, e in fit_spans:
        modules: dict = {}
        for lines in devices.values():
            for name, ms, me in lines[MODULES_LINE]:
                if me > s and ms < e:
                    key = module_name(name)
                    modules[key] = modules.get(key, 0.0) + (
                        min(me, e) - max(ms, s)) / 1e9 / len(devices)
        inside = [clipped_length(b, s, e) / 1e9 for b in busy.values()]
        fits.append({"wall_s": (e - s) / 1e9,
                     "busy_s": max(inside) if inside else 0.0,
                     "modules": modules})
    gap_seconds: dict = {}
    if by_device:
        busiest = sorted(busy)[by_device.index(max(by_device))]
        edges = sorted({t for _, s, e in spans for t in (s, e)})
        for s, e in gaps(busy[busiest], lo, hi):
            # a gap is cut where a span opens or closes, and each piece
            # goes to the innermost span open at its middle
            cuts = [s] + [t for t in edges if s < t < e] + [e]
            for a, b in zip(cuts, cuts[1:]):
                name = innermost(spans, (a + b) / 2)
                gap_seconds[name] = gap_seconds.get(name, 0.0) + (b - a) / 1e9

    def top(seconds):
        return [[k, v] for k, v in sorted(
            seconds.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_by_device": by_device,
        "busy_s": sum(by_device) / len(by_device) if by_device else 0.0,
        "fits": fits,
        "breakdown": {"device_ops": top(op_seconds),
                      "idle_gaps": top(gap_seconds)},
    }


def reduce_dir(trace_dir: str, window=("bench.fit",)):
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    summary = reduce(*read_planes(found[-1]), window=window)
    if summary is None:
        raise ValueError(f"no {window[0]!r} span in {found[-1]}")
    return summary
