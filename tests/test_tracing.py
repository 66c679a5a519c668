"""repro.runtime.tracing: host spans and counters, and the named phases of
the simulator's step in the compiled HLO."""
import contextlib
import re

import jax
import pytest

from repro.api import Experiment, SimulatorCache, registry, run
from repro.core import build_tables, mrls
from repro.runtime import tracing
from repro.simulator.engine import ROUTE_ROWS, SLOTS_STEPPED, SimConfig, \
    Simulator, Traffic
from repro.workloads import all2all_program, compile_program

TINY = {"family": "mrls", "params": {"n_leaves": 14, "u": 3, "d": 3,
                                     "seed": 0}}
ROUTE = {"policy": "polarized", "max_hops": 10, "pool": 4096}
STEP_SCOPES = ("inject", "vc_prearb", "route", "out_arb", "moves", "link")


def experiment(**kw):
    d = dict(network=TINY, route=ROUTE, seed=5)
    d.update(kw)
    return Experiment.from_dict(d)


def window_a2a(**kw):
    return experiment(metric="completion", chunk=8, max_slots=4000,
                      workload={"pattern": "all2all", "rounds": 4,
                                "schedule": "window", "window": 2}, **kw)


def uniform(**kw):
    return experiment(metric="throughput", warm=6, measure=10,
                      workload={"pattern": "uniform", "load": 0.5}, **kw)


# ---------------------------------------------------------------------- #
# spans and counters
# ---------------------------------------------------------------------- #
def test_span_nesting_parent_and_answer():
    with tracing.record() as rec:
        with tracing.span("api.run", answer=42):
            with tracing.span("api.admission"):
                pass
            with tracing.span("runner.prepare"):
                with tracing.span("topology.build"):
                    pass
        with tracing.span("api.run", answer=43):
            tracing.count("c", 3)
            tracing.count("c", 4)
    names = [s.name for s in rec.spans]
    assert names == ["api.run", "api.admission", "runner.prepare",
                     "topology.build", "api.run"]
    parents = [s.parent for s in rec.spans]
    assert parents == [None, 0, 0, 2, None]
    assert [rec.answer(s) for s in rec.spans] == [42, 42, 42, 42, 43]
    assert all(s.end_ns >= s.start_ns >= 0 for s in rec.spans)
    outer, inner = rec.spans[2], rec.spans[3]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert rec.counts == {"c": 7}
    assert rec.seconds("api.run") == pytest.approx(
        rec.spans[0].seconds + rec.spans[4].seconds)


def test_span_closes_on_error_and_one_recorder_at_a_time():
    with tracing.record() as rec:
        with pytest.raises(ValueError):
            with tracing.span("api.run", answer=1):
                raise ValueError("boom")
        with pytest.raises(RuntimeError, match="already open"):
            with tracing.record():
                pass
        with tracing.span("after"):
            pass
    assert rec.spans[0].end_ns >= rec.spans[0].start_ns
    assert rec.spans[1].parent is None


def test_without_a_recorder_nothing_is_recorded():
    with tracing.span("api.run", answer=1):
        tracing.count(SLOTS_STEPPED, 5)
    assert tracing._recorder is None
    with tracing.record() as rec:
        pass
    with tracing.span("late"):
        tracing.count("late", 1)
    assert rec.spans == [] and not rec.counts


def test_topology_builds_equal_build_network_calls(monkeypatch):
    calls = []
    builder = registry._REGISTRY["mrls"]

    def counted(**params):
        calls.append(params)
        return builder(**params)

    monkeypatch.setitem(registry._REGISTRY, "mrls", counted)
    with tracing.record() as rec, SimulatorCache() as sims:
        run(uniform(), cache=sims)
        run(uniform(seed=6), cache=sims)
    builds = rec.named("topology.build")
    assert calls and len(builds) == len(calls)
    assert len(rec.named("routing.tables")) == 1
    runs = rec.named("api.run")
    assert [s.args["answer"] for s in runs] == [5, 6]
    for s in rec.named("api.admission") + rec.named("runner.prepare"):
        assert rec.spans[s.parent].name == "api.run"


@pytest.mark.parametrize("replicas", [1, 2])
def test_slots_stepped_on_throughput(replicas):
    with tracing.record() as rec, SimulatorCache() as sims:
        run(uniform(replicas=replicas), cache=sims)
    assert rec.counts[SLOTS_STEPPED] == (6 + 10) * replicas


@pytest.mark.parametrize("replicas", [1, 2])
def test_slots_stepped_on_a_window_program(replicas):
    with tracing.record() as rec, SimulatorCache() as sims:
        res = run(window_a2a(replicas=replicas), cache=sims)
    assert res.completed
    slots = res.per_replica["slots"] if replicas > 1 else [res.slots]
    # the loop stops at the slowest replica's completion slot, not at the
    # next chunk boundary, and every replica steps that far
    assert rec.counts[SLOTS_STEPPED] == max(slots) * replicas
    assert max(slots) % 8     # completion falls inside a chunk of 8


def test_uncounted_entries_record_no_steps():
    # barrier programs and run_completion would need a device fetch
    with tracing.record() as rec, SimulatorCache() as sims:
        run(experiment(metric="completion", chunk=8, max_slots=4000,
                       workload={"pattern": "all2all", "rounds": 2}),
            cache=sims)
        run(experiment(metric="completion", max_slots=4000,
                       workload={"pattern": "allreduce", "ranks": 4,
                                 "vec_packets": 2}), cache=sims)
    assert rec.counts[SLOTS_STEPPED] == 0
    assert len(rec.named("api.run")) == 2


# ---------------------------------------------------------------------- #
# named phases in the compiled step
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tables():
    return build_tables(mrls(n_leaves=14, u=3, d=3, seed=0))


def _scopes(hlo: str) -> set:
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    return {part for p in paths for part in p.split("/")}


def _chunk_hlo(sim, traffic):
    st = sim.make_state(traffic, seed=0)
    return Simulator._run_chunk_jit.lower(
        sim, st, sim._tables(), traffic, 2).compile().as_text()


def _program_hlo(sim):
    cp = compile_program(all2all_program(sim.S, rounds=2),
                         schedule="window", window=2)
    st = sim.make_program_state(cp, seed=0)
    return Simulator._program_loop.lower(
        sim, st, sim._tables(), sim.program_traffic(cp), 4,
        4000).compile().as_text()


@pytest.mark.parametrize("policy,rows", [("polarized", 2),
                                         ("minimal_adaptive", 1)])
def test_route_rows_per_requester_and_sub_round(tables, policy, rows):
    """``engine.route_rows``: table rows the route phase gathered, 2 a
    requester and crossbar sub-round under polarized (source and target),
    1 under the minimal policies; counted beside ``engine.slots_stepped``."""
    with Simulator(tables, SimConfig(policy=policy, max_hops=10,
                                     pool=4096)) as sim:
        with tracing.record() as rec:
            sim.run_throughput(Traffic("uniform", load=0.5), warm=3,
                               measure=4, seed=0)
            sim.run_throughput_batch(Traffic("uniform", load=0.5), [0, 1],
                                     warm=3, measure=4)
        requesters = sim.NR
    assert requesters == tables.topo.n_switches * tables.topo.max_ports \
        + tables.topo.n_endpoints
    assert rec.counts[SLOTS_STEPPED] == 7 + 2 * 7
    assert rec.counts[ROUTE_ROWS] == (rec.counts[SLOTS_STEPPED] * 2
                                      * requesters * rows)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_step_phases_are_named_in_the_compiled_hlo(tables, backend):
    with Simulator(tables, SimConfig(policy="polarized", max_hops=10,
                                     pool=4096, backend=backend)) as sim:
        chunk = _scopes(_chunk_hlo(sim, Traffic("uniform", load=0.7)))
        prog = _scopes(_program_hlo(sim))
    assert set(STEP_SCOPES) <= chunk
    assert "program" not in chunk
    assert set(STEP_SCOPES) | {"program"} <= prog


def test_executables_are_the_same_with_a_recorder_open(tables):
    tr = Traffic("uniform", load=0.7)

    def compiled(sim, recorder):
        # one call site, so the source locations in the metadata agree
        jax.clear_caches()
        with recorder:
            return _chunk_hlo(sim, tr)

    with Simulator(tables, SimConfig(policy="polarized", max_hops=10,
                                     pool=4096)) as sim:
        off, on = [compiled(sim, r) for r in (contextlib.nullcontext(),
                                               tracing.record())]
    assert on == off
