"""Answers of a run and the accounting of its measured window.

An answer is one ``repro.api.Result``.  :func:`record` keeps the fields
that the counting and the comparison read, one value per replica, and
``final``: the packet counters of the answer's final state
(:mod:`simbench.probe`), filled in once the window has closed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

_PER_REPLICA = ("slots", "completed", "throughput", "avg_hops", "ejected")


def record(result) -> dict:
    """Per-replica view of a ``Result`` (scalar runs are one replica)."""
    exp = result.experiment
    per = result.per_replica or {}
    rec = {"metric": result.metric, "replicas": int(exp.replicas)}
    for f in _PER_REPLICA:
        if f in per:
            rec[f] = list(per[f])
        else:
            v = getattr(result, f)
            rec[f] = None if v is None else [v]
    if "phase_slots" in per:
        rec["phase_slots"] = [list(r) for r in per["phase_slots"]]
    elif result.phase_slots is not None:
        rec["phase_slots"] = [list(result.phase_slots)]
    else:
        rec["phase_slots"] = None
    rec["mean_throughput"] = result.throughput
    rec["final"] = None
    return rec


def answer_slots(rec: dict, traffic: dict) -> int:
    """Simulated slots one answer counts, summed over its replicas.

    ``completion``: each replica's completion slot (``Result.slots``), not
    the chunk-rounded slots stepped.  ``window``: ``warm + measure`` per
    replica.
    """
    how = traffic["slots"]
    if how == "completion":
        return int(sum(rec["slots"]))
    if how == "window":
        return (int(traffic["warm"]) + int(traffic["measure"])) \
            * int(rec["replicas"])
    raise ValueError(f"unknown slot count {how!r}")


def incomplete(rec: dict) -> bool:
    return rec["completed"] is not None and not all(rec["completed"])


@dataclasses.dataclass
class Answer:
    index: int
    seed: int
    t_start: float
    t_end: float
    slots: int = 0
    rec: Optional[dict] = None      # None when the call raised
    error: str = ""
    counts: object = None           # device counters of the final state

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


@dataclasses.dataclass
class Window:
    """Answers run back to back from ``t0`` until the clock passed the
    window's length; the answer in flight at that moment is finished and
    counted."""
    t0: float
    answers: list = dataclasses.field(default_factory=list)

    @property
    def attempted(self) -> int:
        return len(self.answers)

    @property
    def wall_s(self) -> float:
        return (self.answers[-1].t_end - self.t0) if self.answers else 0.0

    @property
    def raised(self) -> int:
        return sum(a.rec is None for a in self.answers)

    @property
    def failed(self) -> int:
        """Answers that raised, plus collectives that did not complete."""
        return self.raised + sum(a.rec is not None and incomplete(a.rec)
                                 for a in self.answers)

    def slots(self) -> int:
        return sum(a.slots for a in self.answers)

    def slots_per_s(self) -> Optional[float]:
        wall = self.wall_s
        return self.slots() / wall if wall > 0 else None

    def records(self) -> list:
        return [a.rec for a in self.answers if a.rec is not None]

    def fetch_counts(self) -> None:
        """Brings each answer's final-state counters to its record."""
        from . import probe
        for a in self.answers:
            if a.rec is not None:
                a.rec["final"] = probe.fetch(a.counts)
            a.counts = None


def nearest_rank(values, q: float) -> Optional[float]:
    """Nearest-rank ``q`` quantile (``None`` for no values): the smallest
    value with at least a share ``q`` of the values at or below it."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(q * len(xs)) - 1)]
