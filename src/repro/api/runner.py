"""One-call experiment execution: ``run(experiment) -> Result``.

Owns the four-stage pipeline every driver used to hand-wire —
topology builder -> ``build_tables`` -> ``Simulator(SimConfig)`` ->
``Traffic`` — plus simulator lifetime (context-managed; teardown clears
the jit caches that otherwise accumulate one executable per instance)
and collective orchestration: collectives compile to device-resident
workload programs (:mod:`repro.workloads`) and run as **one** device
computation per experiment — the old per-phase host loop (fresh
``Traffic("phase")`` state + ``run_completion`` per Rabenseifner phase)
is gone, with bitwise-identical ``phase_slots``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Mapping, Optional, Tuple

import jax
import numpy as np

from ..core import build_tables
from ..runtime import tracing
from ..simulator.engine import Simulator, Traffic
from ..workloads import build_collective_program, compile_program
from .registry import build_network
from .specs import Experiment, NetworkSpec, RouteSpec

__all__ = ["Result", "SimulatorCache", "open_simulator", "routing_tables",
           "run", "run_all"]


def routing_tables(network: NetworkSpec, full: bool = False):
    """Build the network and its precomputed routing tables in one call."""
    return build_tables(build_network(network), full=full)


# ---------------------------------------------------------------------- #
# results
# ---------------------------------------------------------------------- #
def _retuple(v):
    """JSON arrays -> tuples, recursively (inverse of JSON serialization)."""
    if isinstance(v, (list, tuple)):
        return tuple(_retuple(x) for x in v)
    return v


def _aggregate(values) -> Optional[dict]:
    """mean/std/min/max over per-replica values (``None`` entries dropped;
    bools averaged as completion fractions)."""
    vals = [float(v) for v in values if v is not None]
    if not vals:
        return None
    arr = np.asarray(vals, np.float64)
    return {"mean": float(arr.mean()), "std": float(arr.std()),
            "min": float(arr.min()), "max": float(arr.max())}


@dataclasses.dataclass(frozen=True)
class Result:
    """Structured record of one experiment run.

    Only the fields relevant to ``metric`` are populated; the rest stay
    ``None``.  ``latency`` maps percentile labels (``p50``/``p99``/
    ``p999``/``p9999``) to slot counts — uniformly ``float`` (``None``
    when the measurement window ejected nothing), never a mix of int and
    float; ``phase_slots`` holds per-phase completion slots for
    collectives with a phase schedule (allreduce).  The ``serving``
    metric populates ``throughput`` (delivered), ``offered`` (accepted +
    dropped arrivals, packets/slot/endpoint), ``dropped`` (packets the
    full arrival FIFOs rejected in the window), ``pool_stall``, and
    ``latency`` — the open loop means ``throughput`` may fall below
    ``offered``.

    For a batched run (``experiment.replicas > 1``) the scalar metric
    fields hold the across-replica *mean* (``completed`` is the AND), and
    three extra fields are populated: ``replica_seeds`` (the seeds, in
    replica order), ``per_replica`` (field name -> tuple of exact
    per-replica values), and ``aggregates`` (field name ->
    ``{"mean","std","min","max"}``).
    """

    experiment: Experiment
    metric: str
    throughput: Optional[float] = None
    avg_hops: Optional[float] = None
    ejected: Optional[float] = None
    pool_stall: Optional[float] = None
    offered: Optional[float] = None
    dropped: Optional[float] = None
    fail_drop: Optional[float] = None
    latency: Optional[Mapping[str, float]] = None
    slots: Optional[float] = None
    completed: Optional[bool] = None
    phase_slots: Optional[Tuple[float, ...]] = None
    replica_seeds: Optional[Tuple[int, ...]] = None
    per_replica: Optional[Mapping[str, Tuple]] = None
    aggregates: Optional[Mapping[str, Mapping[str, float]]] = None

    @property
    def name(self) -> str:
        return self.experiment.label()

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["experiment"] = self.experiment.to_dict()
        if self.latency is not None:
            d["latency"] = dict(self.latency)
        if self.phase_slots is not None:
            d["phase_slots"] = list(self.phase_slots)
        if self.replica_seeds is not None:
            d["replica_seeds"] = list(self.replica_seeds)
        if self.per_replica is not None:
            d["per_replica"] = {k: list(v) for k, v in self.per_replica.items()}
        if self.aggregates is not None:
            d["aggregates"] = {k: dict(v) for k, v in self.aggregates.items()}
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "Result":
        d = dict(d)
        d["experiment"] = Experiment.from_dict(d["experiment"])
        for key in ("phase_slots", "replica_seeds"):
            if d.get(key) is not None:
                d[key] = _retuple(d[key])
        if d.get("per_replica") is not None:
            d["per_replica"] = {k: _retuple(v)
                                for k, v in d["per_replica"].items()}
        return cls(**d)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_json(cls, s: str) -> "Result":
        return cls.from_dict(json.loads(s))


# ---------------------------------------------------------------------- #
# simulator lifetime
# ---------------------------------------------------------------------- #
def _make_simulator(network: NetworkSpec, route: RouteSpec,
                    masks: str = "auto") -> Simulator:
    topo = build_network(network)
    if network.failures is not None:
        network.failures.validate(topo)   # fail before the table build
    tables = build_tables(topo, masks=masks)
    return Simulator(tables, route.to_sim_config(),
                     failures=network.failures)


def _admitted_masks(experiment: Experiment) -> str:
    """Admission-control gate for every ``run``/``run_all`` entry: price
    the experiment (resident estimate x empirical compile-RAM multiplier)
    against host RAM *before* building anything, and return the mask
    layout to build tables with (``"blocked"`` when admission downgraded
    a dense layout to fit).  Raises :class:`repro.api.admission.
    AdmissionError` with actionable alternatives when nothing fits;
    ``REPRO_ADMISSION=warn|off`` relaxes the gate."""
    from .admission import check_admission
    with tracing.span("api.admission"):
        return check_admission(experiment).masks


class SimulatorCache:
    """Compiled-simulator reuse across experiments.

    Keyed on ``(NetworkSpec, RouteSpec)`` — both frozen and hashable — so
    a sweep over loads/patterns/seeds on one fabric compiles once.  Also a
    context manager: closing tears down every cached simulator (one cache
    clear total, matching the old manual ``del sim; jax.clear_caches()``).
    """

    def __init__(self):
        self._sims: dict = {}

    def get(self, network: NetworkSpec, route: RouteSpec,
            masks: str = "auto") -> Simulator:
        key = (network, route, masks)
        sim = self._sims.get(key)
        if sim is None:
            sim = self._sims[key] = _make_simulator(network, route, masks)
        return sim

    def __len__(self) -> int:
        return len(self._sims)

    def release(self, network: NetworkSpec, route: RouteSpec,
                masks: str = "auto",
                *, clear: Optional[bool] = None) -> None:
        """Drop one simulator (no-op if absent) — for drivers that know a
        fabric won't be needed again before the cache as a whole closes.

        ``clear=None`` (default) clears the process-global jit cache only
        when this was the last cached simulator: clearing while other
        fabrics are still cached would evict their executables too and
        force silent recompiles.
        """
        sim = self._sims.pop((network, route, masks), None)
        if sim is not None:
            if clear is None:
                clear = not self._sims
            sim.close(clear=clear)

    def close(self) -> None:
        sims, self._sims = list(self._sims.values()), {}
        for sim in sims:
            sim.close(clear=False)
        if sims:
            jax.clear_caches()

    def __enter__(self) -> "SimulatorCache":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


@contextlib.contextmanager
def open_simulator(network: NetworkSpec, route: RouteSpec = RouteSpec()):
    """Low-level escape hatch: a context-managed Simulator for a spec pair."""
    sim = _make_simulator(network, route)
    try:
        yield sim
    finally:
        sim.close()


# ---------------------------------------------------------------------- #
# execution
# ---------------------------------------------------------------------- #
def _to_traffic(exp: Experiment) -> Traffic:
    from ..workloads.patterns import check_pattern
    w = exp.workload
    if check_pattern(w.pattern) == "arrival":
        # arrival families reach the engine as Traffic("arrival") with the
        # process name in ``process`` — never by family name
        return Traffic("arrival", process=w.pattern, load=w.load,
                       pareto_alpha=w.pareto_alpha,
                       pareto_cap=w.pareto_cap,
                       diurnal_amp=w.diurnal_amp,
                       diurnal_period=w.diurnal_period,
                       arr_depth=w.arr_depth)
    return Traffic(pattern=w.pattern, load=w.load, rounds=w.rounds,
                   elephant_frac=w.elephant_frac,
                   elephant_size=w.elephant_size,
                   shift=w.shift, hot_frac=w.hot_frac,
                   hot_count=w.hot_count, burst_len=w.burst_len,
                   burst_load=w.burst_load)


# Result latency labels -> engine percentile keys (p999 is the serving
# SLO tail added alongside the coarse ladder)
_LATENCY_KEYS = (("p50", "p0.5"), ("p99", "p0.99"), ("p999", "p0.999"),
                 ("p9999", "p0.9999"))


def _nan_none(v) -> Optional[float]:
    """NaN (empty measurement window) -> None so Results stay strict-JSON
    and round-trip losslessly."""
    v = float(v)
    return None if np.isnan(v) else v


def _is_program(exp: Experiment) -> bool:
    """Collectives with a program builder execute device-resident.
    ``all2all`` only joins when a schedule is requested (its default is
    the legacy free-running engine pattern); everything else in
    ``PROGRAM_BUILDERS`` — built-in or registered via
    ``register_program_builder`` — always compiles."""
    from ..workloads.programs import PROGRAM_BUILDERS
    w = exp.workload
    if w.pattern == "all2all":
        return bool(w.schedule)
    return w.pattern in PROGRAM_BUILDERS


def _collective_program(sim: Simulator, exp: Experiment):
    """Build + compile the workload program for a collective experiment.

    The allreduce family defaults to the parity-locked ``barrier``
    schedule (bitwise the old host loop); a scheduled ``all2all``
    compiles its shifted-exchange rounds under the requested mode.
    """
    w = exp.workload
    with tracing.span("runner.prepare"):
        prog = build_collective_program(
            w.pattern, sim.S, rounds=w.rounds, ranks=w.ranks,
            vec_packets=w.vec_packets)
        return compile_program(prog, schedule=w.schedule or "barrier",
                               window=w.window)


def _run_collective(sim: Simulator, exp: Experiment) -> Result:
    """One device-resident program run replaces the old per-phase host
    loop (fresh ``Traffic("phase")`` state + ``run_completion`` per
    Rabenseifner phase) — same ``phase_slots``, zero host round-trips."""
    cp = _collective_program(sim, exp)
    r = sim.run_program(cp, chunk=exp.chunk, max_slots=exp.max_slots,
                        seed=exp.seed)
    return Result(experiment=exp, metric="completion",
                  slots=int(r["slots"]), completed=bool(r["completed"]),
                  pool_stall=int(r["pool_stall"]),
                  phase_slots=tuple(int(s) for s in r["phase_slots"]))


# ---------------------------------------------------------------------- #
# batched (vmapped-replica) execution
# ---------------------------------------------------------------------- #
def _batched_metrics(sim: Simulator, exp: Experiment, seeds) -> Tuple[str, dict]:
    """Run ``exp`` once per seed inside one vmapped executable.

    Returns ``(metric, per)`` where ``per`` maps metric field names to
    tuples of exact per-replica python scalars (``phase_slots``: tuple of
    per-replica tuples).  Replica ``i`` is bitwise-identical to a scalar
    run with ``seed=seeds[i]``.
    """
    metric = exp.resolved_metric()
    w = exp.workload
    seeds = [int(s) for s in seeds]

    if _is_program(exp):
        if metric != "completion":
            raise ValueError(f"{w.pattern} only supports the completion "
                             "metric")
        # one device computation for all R replicas x P phases: the phase
        # counters and per-phase completion slots live on device
        cp = _collective_program(sim, exp)
        r = sim.run_program(cp, chunk=exp.chunk, max_slots=exp.max_slots,
                            seeds=seeds)
        return metric, {
            "slots": tuple(int(x) for x in r["slots"]),
            "completed": tuple(bool(x) for x in r["completed"]),
            "pool_stall": tuple(int(x) for x in r["pool_stall"]),
            "phase_slots": tuple(tuple(int(v) for v in row)
                                 for row in r["phase_slots"]),
        }

    traffic = _to_traffic(exp)
    if metric == "throughput":
        r = sim.run_throughput_batch(traffic, seeds, warm=exp.warm,
                                     measure=exp.measure)
        return metric, {
            "throughput": tuple(float(x) for x in r["throughput"]),
            "avg_hops": tuple(float(x) for x in r["avg_hops"]),
            "ejected": tuple(int(x) for x in r["ejected"]),
            "pool_stall": tuple(int(x) for x in r["pool_stall"]),
        }
    if metric == "latency":
        r = sim.run_latency_batch(traffic, seeds, warm=exp.warm,
                                  measure=exp.measure)
        return metric, {
            lbl: tuple(_nan_none(v) for v in r[k])
            for lbl, k in _LATENCY_KEYS
        }
    if metric == "serving":
        r = sim.run_serving_batch(traffic, seeds, warm=exp.warm,
                                  measure=exp.measure)
        per = {
            "throughput": tuple(float(x) for x in r["delivered"]),
            "offered": tuple(float(x) for x in r["offered"]),
            "dropped": tuple(int(x) for x in r["dropped"]),
            "pool_stall": tuple(int(x) for x in r["pool_stall"]),
        }
        per.update({lbl: tuple(_nan_none(v) for v in r[k])
                    for lbl, k in _LATENCY_KEYS})
        return metric, per
    if metric == "resilience":
        # Failure transitions mutate host routing tables mid-run, so
        # replicas cannot share one vmapped executable; loop scalar runs
        # (replica i stays bitwise the scalar run with seed=seeds[i]).
        per = {"throughput": [], "avg_hops": [], "ejected": [],
               "pool_stall": [], "fail_drop": []}
        lat = {lbl: [] for lbl, _ in _LATENCY_KEYS}
        for s in seeds:
            r = sim.run_resilience(traffic, warm=exp.warm,
                                   measure=exp.measure, seed=s)
            per["throughput"].append(float(r["throughput"]))
            per["avg_hops"].append(float(r["avg_hops"]))
            per["ejected"].append(int(r["ejected"]))
            per["pool_stall"].append(int(r["pool_stall"]))
            per["fail_drop"].append(int(r["fail_drop"]))
            for lbl, k in _LATENCY_KEYS:
                lat[lbl].append(_nan_none(r[k]))
        out = {k: tuple(v) for k, v in per.items()}
        out.update({lbl: tuple(v) for lbl, v in lat.items()})
        return metric, out
    if metric == "completion":
        if w.pattern != "all2all":
            raise ValueError(
                f"completion metric needs a collective workload, got "
                f"{w.pattern!r}")
        r = sim.run_completion_batch(traffic, expected=sim.S * w.rounds,
                                     seeds=seeds, chunk=exp.chunk,
                                     max_slots=exp.max_slots)
        return metric, {
            "slots": tuple(int(x) for x in r["slots"]),
            "completed": tuple(bool(x) for x in r["completed"]),
            "pool_stall": tuple(int(x) for x in r["pool_stall"]),
        }
    raise ValueError(f"unknown metric {metric!r}")


def _batched_result(exp: Experiment, seeds, metric: str, per: dict) -> Result:
    agg = {}
    for k, vals in per.items():
        if k == "phase_slots":
            continue
        a = _aggregate(vals)
        if a is not None:
            agg[k] = a

    def mean(k):
        return agg[k]["mean"] if k in agg else None

    if metric == "throughput":
        kw = dict(throughput=mean("throughput"), avg_hops=mean("avg_hops"),
                  ejected=mean("ejected"), pool_stall=mean("pool_stall"))
    elif metric == "latency":
        kw = dict(latency={lbl: mean(lbl) for lbl, _ in _LATENCY_KEYS})
    elif metric == "serving":
        kw = dict(throughput=mean("throughput"), offered=mean("offered"),
                  dropped=mean("dropped"), pool_stall=mean("pool_stall"),
                  latency={lbl: mean(lbl) for lbl, _ in _LATENCY_KEYS})
    elif metric == "resilience":
        kw = dict(throughput=mean("throughput"), avg_hops=mean("avg_hops"),
                  ejected=mean("ejected"), pool_stall=mean("pool_stall"),
                  fail_drop=mean("fail_drop"),
                  latency={lbl: mean(lbl) for lbl, _ in _LATENCY_KEYS})
    else:
        kw = dict(slots=mean("slots"),
                  completed=bool(all(per["completed"])),
                  pool_stall=mean("pool_stall"))
        if "phase_slots" in per:
            rows = per["phase_slots"]
            kw["phase_slots"] = tuple(
                float(np.mean([row[i] for row in rows]))
                for i in range(len(rows[0])))
    return Result(experiment=exp, metric=metric,
                  replica_seeds=tuple(int(s) for s in seeds),
                  per_replica=per, aggregates=agg, **kw)


def _unfold_batch(group, metric: str, per: dict) -> list:
    """Split one batched run back into per-experiment scalar Results (used
    when ``run_all`` folds a seed-only group — replica i is bitwise the
    scalar run of ``group[i]``, so the Results are interchangeable)."""
    out = []
    for i, e in enumerate(group):
        if metric == "throughput":
            kw = dict(throughput=per["throughput"][i],
                      avg_hops=per["avg_hops"][i],
                      ejected=per["ejected"][i],
                      pool_stall=per["pool_stall"][i])
        elif metric == "latency":
            kw = dict(latency={lbl: per[lbl][i]
                               for lbl, _ in _LATENCY_KEYS})
        elif metric == "serving":
            kw = dict(throughput=per["throughput"][i],
                      offered=per["offered"][i],
                      dropped=per["dropped"][i],
                      pool_stall=per["pool_stall"][i],
                      latency={lbl: per[lbl][i]
                               for lbl, _ in _LATENCY_KEYS})
        elif metric == "resilience":
            kw = dict(throughput=per["throughput"][i],
                      avg_hops=per["avg_hops"][i],
                      ejected=per["ejected"][i],
                      pool_stall=per["pool_stall"][i],
                      fail_drop=per["fail_drop"][i],
                      latency={lbl: per[lbl][i]
                               for lbl, _ in _LATENCY_KEYS})
        else:
            kw = dict(slots=per["slots"][i], completed=per["completed"][i],
                      pool_stall=per["pool_stall"][i])
            if "phase_slots" in per:
                kw["phase_slots"] = per["phase_slots"][i]
        out.append(Result(experiment=e, metric=metric, **kw))
    return out


def _fold_key(e: Experiment) -> Experiment:
    return dataclasses.replace(e, seed=0, name="")


def _fold_groups(experiments) -> list:
    """Group consecutive experiments that differ only in ``seed``/``name``
    (unbatched ones) — each group becomes one vmapped run."""
    groups = []
    for e in experiments:
        if (groups and e.replicas == 1 and groups[-1][0].replicas == 1
                and _fold_key(groups[-1][0]) == _fold_key(e)):
            groups[-1].append(e)
        else:
            groups.append([e])
    return groups


# ---------------------------------------------------------------------- #
# entry points
# ---------------------------------------------------------------------- #
def run(experiment: Experiment, *,
        cache: Optional[SimulatorCache] = None) -> Result:
    """Execute ``experiment`` end to end and return a :class:`Result`.

    With ``cache`` given, the compiled simulator is fetched from / stored
    into it and left open; otherwise a private simulator is built and
    closed before returning.

    Admission control runs first (see :mod:`repro.api.admission`): an
    experiment predicted to exceed host RAM — resident estimate times the
    empirical compile-RAM multiplier — is auto-downgraded to blocked
    routing masks when that closes the gap, and refused with an
    actionable :class:`~repro.api.admission.AdmissionError` otherwise
    (``REPRO_ADMISSION=warn|off`` relaxes the gate).
    """
    with tracing.span("api.run", answer=int(experiment.seed)):
        masks = _admitted_masks(experiment)
        owns = cache is None
        sim = (_make_simulator(experiment.network, experiment.route, masks)
               if owns
               else cache.get(experiment.network, experiment.route, masks))
        try:
            return _run_on(sim, experiment)
        finally:
            if owns:
                sim.close()


def run_all(experiments, *, cache: Optional[SimulatorCache] = None,
            fold_seeds: bool = True) -> list:
    """Run a sequence of experiments, sharing simulators across same-fabric
    entries.  With a private cache (none passed in), each fabric's simulator
    is evicted right after its last use so multi-fabric suites don't
    accumulate ~25 live instances (the documented host-OOM mode).

    ``fold_seeds=True`` (default) folds consecutive experiments that differ
    only in ``seed`` (e.g. a ``sweep`` seed axis) into one vmapped batched
    run, then splits the Results back out — same Results, one compile and
    no per-replica host loops.
    """
    experiments = list(experiments)
    owns = cache is None
    if owns:
        cache = SimulatorCache()
    groups = (_fold_groups(experiments) if fold_seeds
              else [[e] for e in experiments])
    # admission decisions are memoized per fabric, so pricing every
    # experiment up front costs one topology build per distinct fabric
    masks = {id(e): _admitted_masks(e) for e in experiments}
    last_use = {(e.network, e.route, masks[id(e)]): i
                for i, e in enumerate(experiments)}
    results = []
    pos = 0
    try:
        for group in groups:
            if len(group) == 1:
                results.append(run(group[0], cache=cache))
            else:
                m = masks[id(group[0])]
                sim = cache.get(group[0].network, group[0].route, m)
                metric, per = _batched_metrics(
                    sim, group[0], [e.seed for e in group])
                results.extend(_unfold_batch(group, metric, per))
            pos += len(group)
            e = group[-1]
            if owns and last_use[(e.network, e.route,
                                  masks[id(e)])] == pos - 1:
                cache.release(e.network, e.route, masks[id(e)])
        return results
    finally:
        if owns:
            cache.close()


def _run_on(sim: Simulator, exp: Experiment) -> Result:
    metric = exp.resolved_metric()
    if exp.replicas > 1:
        seeds = exp.replica_seeds()
        metric, per = _batched_metrics(sim, exp, seeds)
        return _batched_result(exp, seeds, metric, per)
    if _is_program(exp):
        if metric != "completion":
            raise ValueError(f"{exp.workload.pattern} only supports the "
                             "completion metric")
        return _run_collective(sim, exp)

    traffic = _to_traffic(exp)
    if metric == "throughput":
        r = sim.run_throughput(traffic, warm=exp.warm, measure=exp.measure,
                               seed=exp.seed)
        return Result(experiment=exp, metric=metric,
                      throughput=float(r["throughput"]),
                      avg_hops=float(r["avg_hops"]),
                      ejected=int(r["ejected"]),
                      pool_stall=int(r["pool_stall"]))
    if metric == "latency":
        r = sim.run_latency(traffic, warm=exp.warm, measure=exp.measure,
                            seed=exp.seed)
        # zero ejections in the window -> NaN percentiles; map to None so
        # the Result stays strict-JSON and round-trips losslessly
        lat = {lbl: _nan_none(r[k]) for lbl, k in _LATENCY_KEYS}
        return Result(experiment=exp, metric=metric, latency=lat)
    if metric == "serving":
        r = sim.run_serving(traffic, warm=exp.warm, measure=exp.measure,
                            seed=exp.seed)
        lat = {lbl: _nan_none(r[k]) for lbl, k in _LATENCY_KEYS}
        return Result(experiment=exp, metric=metric,
                      throughput=float(r["delivered"]),
                      offered=float(r["offered"]),
                      dropped=int(r["dropped"]),
                      pool_stall=int(r["pool_stall"]), latency=lat)
    if metric == "resilience":
        r = sim.run_resilience(traffic, warm=exp.warm, measure=exp.measure,
                               seed=exp.seed)
        lat = {lbl: _nan_none(r[k]) for lbl, k in _LATENCY_KEYS}
        return Result(experiment=exp, metric=metric,
                      throughput=float(r["throughput"]),
                      avg_hops=float(r["avg_hops"]),
                      ejected=int(r["ejected"]),
                      pool_stall=int(r["pool_stall"]),
                      fail_drop=int(r["fail_drop"]), latency=lat)
    if metric == "completion":
        if exp.workload.pattern != "all2all":
            raise ValueError(
                f"completion metric needs a collective workload, got "
                f"{exp.workload.pattern!r}")
        expected = sim.S * exp.workload.rounds
        r = sim.run_completion(traffic, expected=expected, chunk=exp.chunk,
                               max_slots=exp.max_slots, seed=exp.seed)
        return Result(experiment=exp, metric=metric, slots=int(r["slots"]),
                      completed=bool(r["completed"]),
                      pool_stall=int(r["pool_stall"]))
    raise ValueError(f"unknown metric {metric!r}")
