"""Host spans and counters of the simulator, on the profiler's clock.

``span(name, **args)`` marks a stretch of host work.  It always enters a
``jax.profiler.TraceAnnotation`` (about a microsecond when no profiler
runs), so a profile shows the span on the clock of the device's
operations.  While a :func:`record` recorder is open the span is also
kept in memory, with its parent (the span it nests in).  ``count(name,
n)`` adds ``n`` to the open recorder; it also leaves a zero-length
profiler event carrying ``n``, so a profile sees the counter without a
recorder.

Nothing here issues a JAX computation or a device-to-host transfer, and
nothing inside a jitted function reads it: the device phases of a step
are ``jax.named_scope`` metadata (``simulator/engine.py``), so an
executable is the same whether a recorder is open or not.

Names::

    api.run               one answer of repro.api.run (arg ``answer``: seed)
    api.admission         admission control of an answer
    runner.prepare        collective program build, simulator state build
    topology.build        registry.build_network
    routing.tables        core.routing.build_tables
    engine.slots_stepped  counter: slots a run entry stepped, per replica
    engine.route_rows     counter: routing-table rows the route phase gathered
                          in those slots (slots x speedup x requesters x rows
                          per requester: 2 polarized, 1 otherwise)
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["Recorder", "Span", "count", "record", "span"]


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int = -1                 # -1 while the span is open
    parent: Optional[int] = None     # index into Recorder.spans
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Recorder:
    """Spans (in the order they opened) and counter totals."""

    def __init__(self):
        self.spans: list = []
        self.counts = collections.Counter()
        self._local = threading.local()

    def _stack(self) -> list:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def seconds(self, name: str) -> float:
        return sum(s.seconds for s in self.named(name))

    def answer(self, s: Span):
        """The ``answer`` arg of the root span that ``s`` nests in."""
        while s.parent is not None:
            s = self.spans[s.parent]
        return s.args.get("answer")


_recorder: Optional[Recorder] = None


@contextlib.contextmanager
def record():
    """Opens the process's one recorder for the ``with`` block."""
    global _recorder
    if _recorder is not None:
        raise RuntimeError("a tracing recorder is already open")
    _recorder = rec = Recorder()
    try:
        yield rec
    finally:
        _recorder = None


@contextlib.contextmanager
def span(name: str, **args):
    with TraceAnnotation(name, **args):
        rec = _recorder
        if rec is None:
            yield
            return
        stack = rec._stack()
        i = len(rec.spans)
        rec.spans.append(Span(name, time.perf_counter_ns(),
                              parent=stack[-1] if stack else None, args=args))
        stack.append(i)
        try:
            yield
        finally:
            stack.pop()
            rec.spans[i].end_ns = time.perf_counter_ns()


def count(name: str, n: int) -> None:
    rec = _recorder
    if rec is not None:
        rec.counts[name] += int(n)
    with TraceAnnotation(name, n=int(n)):
        pass
