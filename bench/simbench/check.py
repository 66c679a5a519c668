"""The comparison that decides ``correct``.

Every answer of the window is held to the guarantees its configuration
states, as the reference (:mod:`simbench.reference`) computes them.  Each
number below passes when it is at most its limit, which the traffic
file's ``limits`` gives.  ``final`` is an answer's final-state counters
(:mod:`simbench.probe`); a replica without them fails the numbers that
read them.

``raised``            answers whose call raised.
``incomplete``        collective answers that did not deliver every
                      packet, or whose phase record is malformed: not one
                      slot per round, decreasing, or ending elsewhere than
                      ``slots``.
``early_phase``       phases that completed before the reference's least
                      completion slot.
``delivered_gap``     packets by which a collective's ejections differ
                      from its program's ``S * rounds``, summed over
                      replicas.
``lost``              packets created that were neither ejected nor held
                      in a queue at the answer's end, summed over
                      replicas (``lossless``).
``missing_replicas``  replicas an answer lacks, and answers whose mean is
                      not the mean of their replicas.
``bad_counts``        replica readings that break conservation: window
                      deliveries that are no whole number, exceed the
                      run's deliveries or the offered load, a run that
                      delivered more than its endpoints could inject, or
                      a final state whose ejections are not the answer's.
``hops_gap``          uniform traffic: the widest relative gap between a
                      replica's mean hops and the reference's
                      shortest-path mean, both ways where the
                      configuration guarantees minimal routes, only below
                      it otherwise.  A shifted exchange: the widest
                      relative distance of a replica's links crossed in
                      all outside the reference's least and most.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from . import reference


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def _finals(rec: dict) -> list:
    """Per-replica final-state counters, ``None`` where not seen."""
    f = rec.get("final")
    R = rec["replicas"]
    if f is None or any(len(f[k]) != R for k in f):
        return [None] * R
    return [{k: f[k][i] for k in f} for i in range(R)]


def _lost(fin: Optional[dict]) -> int:
    if fin is None:
        return 0
    return abs(fin["created"] - fin["ejected"] - fin["queued"])


def _outside(value: float, least: float, most: float) -> float:
    """Relative distance of ``value`` outside ``[least, most]``."""
    return max(0.0, (least - value) / least, (value - most) / most)


def _completion(records, traffic, config, ref) -> dict:
    rounds = int(traffic["workload"]["rounds"])
    due = int(config["endpoints"]) * rounds
    lb = reference.completion_bounds(rounds)
    hops = ref.get("exchange_hops")
    bad = early = delivered = lost = 0
    gap = 0.0
    for rec in records:
        for i, fin in enumerate(_finals(rec)):
            ph = rec["phase_slots"][i] if rec["phase_slots"] else None
            slots = rec["slots"][i]
            ok = (bool(rec["completed"][i]) and ph is not None
                  and len(ph) == rounds and ph[-1] == slots
                  and all(a <= b for a, b in zip(ph, ph[1:]))
                  and slots <= int(traffic["max_slots"]))
            bad += not ok
            if ph is not None:
                early += sum(s < b for s, b in zip(ph, lb))
            delivered += due if fin is None else abs(fin["ejected"] - due)
            lost += _lost(fin)
            if hops is not None:
                gap = max(gap, 1.0 if fin is None
                          else _outside(fin["hop_sum"], *hops))
    out = {"incomplete": bad, "early_phase": early,
           "delivered_gap": delivered, "lost": lost}
    if hops is not None:
        out["hops_gap"] = gap
    return out


def _throughput(records, traffic, config, ref) -> dict:
    S = int(config["endpoints"])
    warm, measure = int(traffic["warm"]), int(traffic["measure"])
    load = float(traffic["workload"].get("load", 1.0))
    minimal = bool(config["guarantees"].get("minimal_routes"))
    mean_hops = ref.get("uniform_hops")
    missing = bad = lost = 0
    gap = 0.0
    for rec in records:
        R = rec["replicas"]
        thr, hops, ej = rec["throughput"], rec["avg_hops"], rec["ejected"]
        n = min(len(thr), len(hops), len(ej))
        missing += (R - n) + (n != len(thr) or n != len(hops) or n != len(ej))
        mean = rec["mean_throughput"]
        if n and abs(mean - sum(thr) / len(thr)) > 1e-9 * max(1.0, abs(mean)):
            missing += 1
        finals = _finals(rec)
        missing += sum(f is None for f in finals)
        for i in range(n):
            win = thr[i] * S * measure
            whole = round(win)
            fin = finals[i] if i < len(finals) else None
            bad += (abs(win - whole) > 1e-6 * max(1.0, win)
                    or whole > ej[i] or ej[i] > S * (warm + measure)
                    or thr[i] > load + 1e-12
                    or (fin is not None and fin["ejected"] != ej[i]))
            lost += _lost(fin)
            if mean_hops is not None:
                d = (mean_hops - hops[i]) / mean_hops
                gap = max(gap, abs(d) if minimal else d)
    out = {"missing_replicas": missing, "bad_counts": bad, "lost": lost}
    if mean_hops is not None:
        out["hops_gap"] = gap
    return out


def compare(records, raised: int, traffic: dict, config: dict,
            ref: Optional[dict] = None) -> list:
    """Numbers of the comparison, each beside its limit, in a fixed order.

    ``ref`` is :func:`reference_for` of the cell, worked out here when
    not given.
    """
    if ref is None:
        ref = reference_for(traffic, config)
    values = {"raised": raised}
    metric = traffic["metric"]
    if metric == "completion":
        values.update(_completion(records, traffic, config, ref))
    elif metric == "throughput":
        values.update(_throughput(records, traffic, config, ref))
    else:
        raise ValueError(f"no comparison for metric {metric!r}")
    limits = traffic["limits"]
    return [Check(k, v, float(limits[k])) for k, v in values.items()]


def reference_for(traffic: dict, config: dict) -> dict:
    """What the reference says of a cell: ``uniform_hops`` for uniform
    traffic, ``exchange_hops`` (least, most) for a shifted exchange."""
    w = traffic["workload"]
    out = {}
    if traffic["metric"] == "throughput" and w["pattern"] == "uniform":
        h = reference.uniform_mean_hops(config)
        if h is not None:
            out["uniform_hops"] = h
    if traffic["metric"] == "completion" and w["pattern"] == "all2all":
        h = reference.shifted_exchange_hops(config, int(w["rounds"]))
        if h is not None:
            out["exchange_hops"] = h
    return out
