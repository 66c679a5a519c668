"""The program's own names in a profile of the traced stretch.

The simulator names the phases of its step with ``jax.named_scope``
(``inject``, ``vc_prearb``, ``route``, ``out_arb``, ``moves``, ``link``,
``program``) and its host work with ``repro.runtime.tracing`` spans
(``api.run``, ``api.admission``, ``runner.prepare``) and a counter
(``engine.slots_stepped``, a zero-length event with the stat ``n``).

A device operation's op path (the HLO ``op_name``, which holds the
scopes) is the stat ``tf_op`` of the operation's event *metadata*;
``jax.profiler.ProfileData``, which ``trace.py`` reads, gives only an
event's own stats.  So this module decodes the ``.xplane.pb`` itself,
with ``google.protobuf`` and the few fields of the XSpace schema it
reads.  Times are whole nanoseconds from ``line.timestamp_ns`` and
``offset_ps``, as ``ProfileData`` gives them.

Everything here reads a trace that ``run.py`` wrote in this run: it is
found under ``.bench_trace`` and taken only if its stretch is the one
``run.trace`` was reduced over.  A program without these names yields
``None`` for every reading.
"""
from __future__ import annotations

import collections
import glob
import os
import pathlib
import re
from typing import Optional

from . import trace

PHASES = ("inject", "vc_prearb", "route", "out_arb", "moves", "link",
          "program")
OP_PATH = "tf_op"
STRETCH = "bench stretch"            # run.py's span around the stretch
LOOP_EXECUTABLES = r"program_loop|run_chunk|completion_loop"   # run.py's
ANSWER = "api.run"
STEPPED = "engine.slots_stepped"

Event = collections.namedtuple("Event",
                               "plane line name start_ns dur_ns path stats")

_SCHEMA = {   # message: [(field, number, type, repeated, message type)]
    "XSpace": [("planes", 1, "m", True, "XPlane")],
    "XPlane": [("name", 2, "s", False, None),
               ("lines", 3, "m", True, "XLine"),
               ("event_metadata", 4, "m", True, "EventEntry"),
               ("stat_metadata", 5, "m", True, "StatEntry")],
    "XLine": [("name", 2, "s", False, None),
              ("timestamp_ns", 3, "i", False, None),
              ("events", 4, "m", True, "XEvent")],
    "XEvent": [("metadata_id", 1, "i", False, None),
               ("offset_ps", 2, "i", False, None),
               ("duration_ps", 3, "i", False, None),
               ("stats", 4, "m", True, "XStat")],
    "XStat": [("metadata_id", 1, "i", False, None),
              ("double_value", 2, "d", False, None),
              ("uint64_value", 3, "u", False, None),
              ("int64_value", 4, "i", False, None),
              ("str_value", 5, "s", False, None),
              ("ref_value", 7, "u", False, None)],
    "EventEntry": [("key", 1, "i", False, None),
                   ("value", 2, "m", False, "XEventMetadata")],
    "XEventMetadata": [("name", 2, "s", False, None),
                       ("stats", 5, "m", True, "XStat")],
    "StatEntry": [("key", 1, "i", False, None),
                  ("value", 2, "m", False, "XStatMetadata")],
    "XStatMetadata": [("name", 2, "s", False, None)],
}
_space = []


def _space_class():
    if _space:
        return _space[0]
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    types = {"m": F.TYPE_MESSAGE, "s": F.TYPE_STRING, "i": F.TYPE_INT64,
             "u": F.TYPE_UINT64, "d": F.TYPE_DOUBLE}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="simbench_xplane.proto", package="simbench_xplane",
        syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind, repeated, type_name in fields:
            f = m.field.add(name=name, number=number, type=types[kind],
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if type_name:
                f.type_name = f".simbench_xplane.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    _space.append(message_factory.GetMessageClass(
        pool.FindMessageTypeByName("simbench_xplane.XSpace")))
    return _space[0]


def _value(s, names):
    if s.ref_value:
        return names.get(s.ref_value)
    return s.str_value or s.int64_value or s.uint64_value or s.double_value


def read(path: str) -> list:
    """Every event of the trace: device operations carry their op path,
    host events their stats (a span's args)."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            path_ = ""
            for s in e.value.stats:
                if names.get(s.metadata_id) == OP_PATH:
                    path_ = _value(s, names) or ""
            meta[e.key] = (e.value.name, path_)
        host = plane.name.startswith(trace.HOST_PREFIX)
        for line in plane.lines:
            t0 = line.timestamp_ns
            for ev in line.events:
                name, path_ = meta.get(ev.metadata_id, ("", ""))
                stats = ({names.get(s.metadata_id): _value(s, names)
                          for s in ev.stats} if host and ev.stats else None)
                out.append(Event(plane.name, line.name, name,
                                 t0 + ev.offset_ps // 1000,
                                 ev.duration_ps // 1000, path_, stats))
    return out


# ---------------------------------------------------------------------- #
# this run's trace
# ---------------------------------------------------------------------- #
_cache: dict = {}


def _newest() -> Optional[str]:
    roots = {pathlib.Path.cwd(), pathlib.Path(__file__).resolve().parents[2]}
    paths = [p for r in roots for p in glob.glob(
        str(r / ".bench_trace" / "**" / "*.xplane.pb"), recursive=True)]
    return max(paths, key=os.path.getmtime) if paths else None


def of_run(run) -> Optional[tuple]:
    """``(events, lo, hi)`` of the trace ``run.trace`` was reduced from,
    or ``None``."""
    path = _newest() if run.trace else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = read(path)
    events = _cache[key]
    bounds = trace.span(events, STRETCH)
    if bounds is None or abs((bounds[1] - bounds[0]) / 1e9
                             - run.trace["window_s"]) > 1e-6:
        return None
    return (events,) + bounds


# ---------------------------------------------------------------------- #
# readings
# ---------------------------------------------------------------------- #
def _device_lines(events, line: str) -> dict:
    out = collections.defaultdict(list)
    for ev in events:
        if ev.plane.startswith(trace.DEVICE_PREFIX) and ev.line == line:
            out[ev.plane].append(ev)
    return out


def self_times(ops: list, lo: float, hi: float) -> list:
    """``(op, seconds)`` for each operation of one line: its time in
    ``[lo, hi]`` not covered by an operation nested in it.  A leaf keeps
    its whole time; a ``while``, ``conditional`` or ``call`` keeps only
    what it spends outside the operations it runs, so no time counts
    twice.  A zero-length event (a ``custom-call`` the compiler folded
    into a fusion) takes nothing from the operation it sits in."""
    own = {}
    stack = []
    for i in sorted(range(len(ops)),
                    key=lambda i: (ops[i].start_ns, -ops[i].dur_ns)):
        ev = ops[i]
        end = ev.start_ns + ev.dur_ns
        while stack and ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns \
                <= ev.start_ns:
            stack.pop()
        c = trace._clip(ev, lo, hi)
        own[i] = (c[1] - c[0]) if c else 0.0
        if stack and end <= ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns:
            own[stack[-1]] -= own[i]
        stack.append(i)
    return [(ops[i], t / 1e9) for i, t in own.items() if t > 0]


def phase_s(events, lo: float, hi: float) -> Optional[dict]:
    """Seconds of operation self time in ``[lo, hi]`` per step phase, per
    device (averaged over devices), with ``"loop"`` the self time of the
    loop executables' operations and ``"all"`` that of every operation;
    ``None`` where no operation carries a phase name."""
    planes = _device_lines(events, trace.OPS_LINE)
    if not planes:
        return None
    loop = re.compile(LOOP_EXECUTABLES)
    out = collections.Counter()
    for ops in planes.values():
        for ev, dt in self_times(ops, lo, hi):
            parts = set(ev.path.split("/"))
            out["all"] += dt
            if loop.search(ev.path.split("/", 1)[0]):
                out["loop"] += dt
            for p in PHASES:
                if p in parts:
                    out[p] += dt
    if not any(out[p] for p in PHASES):
        return None
    return {k: v / len(planes) for k, v in out.items()}


def _host(events, name: str, lo: float, hi: float) -> list:
    return [ev for ev in events if ev.plane.startswith(trace.HOST_PREFIX)
            and ev.name == name and lo <= ev.start_ns <= hi]


def tails(events, lo: float, hi: float) -> list:
    """Per answer (``api.run`` span) in ``[lo, hi]``: nanoseconds from the
    end of its last loop-executable run on the device to its return."""
    loop = re.compile(LOOP_EXECUTABLES)
    ends = sorted(ev.start_ns + ev.dur_ns
                  for mods in _device_lines(events,
                                            trace.MODULES_LINE).values()
                  for ev in mods if loop.search(ev.name))
    out = []
    for a in _host(events, ANSWER, lo, hi):
        end = a.start_ns + a.dur_ns
        inside = [e for e in ends if a.start_ns <= e <= end]
        if inside:
            out.append(end - inside[-1])
    return out


def span_ms_per_answer(events, lo: float, hi: float,
                       name: str) -> Optional[float]:
    """Mean host time of the spans ``name`` per answer in ``[lo, hi]``."""
    answers = _host(events, ANSWER, lo, hi)
    spans = _host(events, name, lo, hi)
    if not answers or not spans:
        return None
    return sum(ev.dur_ns for ev in spans) / len(answers) / 1e6


def counted(events, lo: float, hi: float, name: str) -> Optional[int]:
    """Sum of the counter ``name`` over ``[lo, hi]``."""
    marks = _host(events, name, lo, hi)
    if not marks:
        return None
    return sum(int((ev.stats or {}).get("n") or 0) for ev in marks)


# ---------------------------------------------------------------------- #
# what the metric readers call
# ---------------------------------------------------------------------- #
def phase_ms_per_slot(run, phase: str) -> Optional[float]:
    found = of_run(run)
    if found is None or run.trace_slots <= 0:
        return None
    key = ("phase_s",) + found[1:]
    if key not in _cache:           # one reduction for the seven readers
        _cache[key] = phase_s(*found)
    split = _cache[key]
    if not split or not split.get(phase):
        return None
    return 1e3 * split[phase] / run.trace_slots


def host_ms_per_answer(run, name: str) -> Optional[float]:
    found = of_run(run)
    return None if found is None else span_ms_per_answer(*found, name)


def answer_tail_ms(run) -> Optional[float]:
    found = of_run(run)
    t = tails(*found) if found is not None else []
    return sum(t) / len(t) / 1e6 if t else None


def stepped_per_counted(run) -> Optional[float]:
    found = of_run(run)
    if found is None or run.trace_slots <= 0:
        return None
    n = counted(*found, STEPPED)
    return None if n is None else n / run.trace_slots
