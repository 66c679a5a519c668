"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The device planes (``/device:<kind>:<n>``) carry one event per executed
operation on their ``XLA Ops`` line and one per executable run on their
``XLA Modules`` line.  The host plane carries the benchmark's own spans
(``jax.profiler.TraceAnnotation``) on the thread that ran the window.
All of it is read with ``jax.profiler.ProfileData``; this module turns it
into:

* ``busy_s``: the union of the intervals in which an operation ran, per
  device, averaged over the devices;
* ``idle_share``: one minus busy over the stretch;
* ``loop_s``: time of the executables whose name matches a pattern;
* ``device_ops``: the operations that took most time;
* ``idle_gaps``: the longest idle gaps, each named by the innermost host
  span open at its middle.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Iterable, Optional

Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def read_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def merge(intervals: Iterable) -> list:
    """Sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(ev: Event, lo: float, hi: float):
    s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.dur_ns, hi)
    return (s, e) if e > s else None


def op_name(name: str) -> str:
    """``%fusion.12 = s32[..] fusion(..), ..`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def span(events, name: str) -> Optional[tuple]:
    """``(start_ns, end_ns)`` of the first host event called ``name``."""
    for ev in events:
        if ev.plane.startswith(HOST_PREFIX) and ev.name == name:
            return ev.start_ns, ev.start_ns + ev.dur_ns
    return None


def reduce(events: list, lo: float, hi: float,
           loop_pattern: str = "", host_span: str = "") -> Optional[dict]:
    """Device metrics of the stretch ``[lo, hi]`` (nanoseconds), or
    ``None`` where the trace holds no device operations."""
    ops = collections.defaultdict(list)
    mods = collections.defaultdict(list)
    for ev in events:
        if not ev.plane.startswith(DEVICE_PREFIX):
            continue
        if ev.line == OPS_LINE:
            ops[ev.plane].append(ev)
        elif ev.line == MODULES_LINE:
            mods[ev.plane].append(ev)
    if not ops or hi <= lo:
        return None
    planes = sorted(ops)
    busy, loop = [], []
    op_time = collections.Counter()
    pat = re.compile(loop_pattern) if loop_pattern else None
    for p in planes:
        clipped = [(ev, c) for ev in ops[p] if (c := _clip(ev, lo, hi))]
        merged = merge(c for _, c in clipped)
        busy.append(sum(e - s for s, e in merged))
        for ev, (s, e) in clipped:
            op_time[op_name(ev.name)] += e - s
        if pat is not None:
            loop.append(sum(c[1] - c[0] for ev in mods.get(p, ())
                            if pat.search(ev.name)
                            and (c := _clip(ev, lo, hi))))
    n = len(planes)
    window = hi - lo
    busy_ns = sum(busy) / n
    gaps = _gaps(merge(c for ev in ops[planes[0]]
                       if (c := _clip(ev, lo, hi))), lo, hi)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    host = _host_events(events, host_span)
    named = [(e - s, _label(host, (s + e) / 2)) for s, e in longest]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy_ns / window,
        "loop_s": (sum(loop) / n / 1e9) if pat is not None and any(mods.values())
        else None,
        "device_ops": [[k, v / n / 1e9] for k, v in op_time.most_common(TOP)],
        "idle_gaps": [[label, g / 1e9] for g, label in named],
    }


def _gaps(merged: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _host_events(events: list, host_span: str) -> list:
    """Events of the host thread that holds the span ``host_span``."""
    where = {(ev.plane, ev.line) for ev in events
             if ev.plane.startswith(HOST_PREFIX) and ev.name == host_span}
    return [ev for ev in events if (ev.plane, ev.line) in where]


def _label(host: list, t: float) -> str:
    best = None
    for ev in host:
        if ev.start_ns <= t <= ev.start_ns + ev.dur_ns:
            if best is None or ev.dur_ns < best.dur_ns:
                best = ev
    return best.name if best is not None else "no host span"
