"""repro.api: registry coverage, spec serialization, run()/sweep() parity."""
import json

import numpy as np
import pytest

from repro.api import (
    Experiment, NetworkSpec, Result, RouteSpec, SimulatorCache, WorkloadSpec,
    build_network, expand_axes, open_simulator, register_topology, run,
    sweep, topology_families,
)
from repro.core import build_tables, mrls, route_row_words
from repro.simulator.engine import SimConfig, Simulator, Traffic

TINY = NetworkSpec("mrls", {"n_leaves": 14, "u": 3, "d": 3, "seed": 0})
ROUTE = RouteSpec(policy="polarized", max_hops=10, pool=4096)

# one buildable spec per registered family (tiny instances)
FAMILY_SPECS = {
    "mrls": TINY,
    "fat_tree": NetworkSpec("fat_tree", {"radix": 4, "h": 1}),
    "oft": NetworkSpec("oft", {"q": 2}),
    "dragonfly": NetworkSpec("dragonfly", {"a": 2, "p": 1, "h": 1}),
    "dragonfly_plus": NetworkSpec("dragonfly_plus", {
        "n_groups": 3, "leaves_per_group": 2, "spines_per_group": 2,
        "p": 2, "global_per_spine": 1}),
    "rfc": NetworkSpec("rfc", {"n_leaves": 6, "u": 4, "d": 2, "seed": 0}),
}


# ---------------------------------------------------------------------- #
# registry
# ---------------------------------------------------------------------- #
def test_registry_lists_all_six_families():
    assert set(FAMILY_SPECS) <= set(topology_families())


@pytest.mark.parametrize("family", sorted(FAMILY_SPECS))
def test_registry_builds_every_family(family):
    topo = build_network(FAMILY_SPECS[family])
    topo.validate()
    assert topo.n_endpoints > 0


def test_registry_unknown_family():
    with pytest.raises(KeyError, match="unknown topology family"):
        build_network(NetworkSpec("torus", {}))


def test_register_topology_roundtrip():
    register_topology("tiny_mrls_alias", mrls, overwrite=True)
    topo = build_network(NetworkSpec("tiny_mrls_alias",
                                     {"n_leaves": 14, "u": 3, "d": 3}))
    assert topo.n_leaves == 14
    register_topology("mrls", mrls)      # same builder: idempotent no-op
    with pytest.raises(ValueError, match="already registered"):
        register_topology("mrls", lambda **kw: None)   # conflicting builder


# ---------------------------------------------------------------------- #
# spec serialization
# ---------------------------------------------------------------------- #
def test_experiment_json_roundtrip_lossless():
    exp = Experiment(
        network=TINY, route=ROUTE,
        workload=WorkloadSpec("mice_elephant", load=0.4, elephant_frac=0.2),
        name="rt", metric="latency", seed=3, warm=10, measure=20,
        chunk=8, max_slots=123,
    )
    again = Experiment.from_json(exp.to_json())
    assert again == exp
    assert hash(again) == hash(exp)
    # dict form is plain-JSON (no tuples) and stable under a second trip
    d = json.loads(exp.to_json())
    assert d["network"]["params"] == {"n_leaves": 14, "u": 3, "d": 3,
                                      "seed": 0}
    assert Experiment.from_dict(d) == exp


def test_latency_result_uniformly_float_json_roundtrip():
    # Result.latency values are uniformly float (None for empty windows) —
    # never a mix of int and float — and survive a JSON round trip intact
    exp = Experiment(network=TINY, route=ROUTE, metric="latency",
                     warm=10, measure=20)
    res = Result(experiment=exp, metric="latency",
                 latency={"p50": 12.0, "p99": 30.0, "p9999": None})
    again = Result.from_json(res.to_json())
    assert again == res
    assert all(v is None or type(v) is float
               for v in again.latency.values())


def test_latency_run_emits_floats():
    exp = Experiment(network=TINY, route=ROUTE, metric="latency",
                     workload=WorkloadSpec("uniform", load=0.5),
                     warm=30, measure=60)
    res = run(exp)
    assert res.latency is not None
    assert all(v is None or type(v) is float for v in res.latency.values())
    again = Result.from_json(res.to_json())
    assert again.latency == res.latency


def test_route_spec_backend_round_trips_and_reaches_sim_config():
    r = RouteSpec(policy="polarized", backend="pallas")
    assert RouteSpec.from_dict(r.to_dict()) == r
    assert r.to_sim_config().backend == "pallas"
    assert RouteSpec().to_sim_config().backend == "xla"


def test_network_spec_param_order_insensitive():
    a = NetworkSpec("mrls", {"u": 3, "n_leaves": 14, "d": 3})
    b = NetworkSpec("mrls", {"d": 3, "u": 3, "n_leaves": 14})
    assert a == b and hash(a) == hash(b)


def test_workload_rejects_unknown_pattern():
    with pytest.raises(ValueError, match="unknown pattern"):
        WorkloadSpec("phase")


def test_workload_all2all_requires_rounds():
    with pytest.raises(ValueError, match="rounds > 0"):
        WorkloadSpec("all2all")


def test_workload_allreduce_requires_pow2_ranks():
    with pytest.raises(ValueError, match="power of two"):
        WorkloadSpec("allreduce", ranks=12)
    assert WorkloadSpec("allreduce", ranks=16).ranks == 16


def test_network_spec_rejects_nested_non_scalars():
    with pytest.raises(TypeError, match="JSON scalar"):
        NetworkSpec("mrls", {"m": [[1, 2], {"a": 1}]})
    nested = NetworkSpec("mrls", {"m": [1, 2]})
    hash(nested)                              # lists frozen recursively


def test_experiment_override_paths():
    exp = Experiment(network=TINY)
    assert exp.override("seed", 7).seed == 7
    assert exp.override("workload.load", 0.3).workload.load == 0.3
    assert exp.override("route.policy", "ksp").route.policy == "ksp"
    assert exp.override("network.params.u", 6).network.param_dict()["u"] == 6


# ---------------------------------------------------------------------- #
# run() parity with the hand-wired Simulator path
# ---------------------------------------------------------------------- #
def test_run_matches_handwired_simulator():
    exp = Experiment(network=TINY, route=ROUTE,
                     workload=WorkloadSpec("uniform", load=0.5),
                     warm=60, measure=100)
    res = run(exp)

    sim = Simulator(build_tables(mrls(14, u=3, d=3, seed=0)),
                    SimConfig(policy="polarized", max_hops=10, pool=4096))
    with sim:
        ref = sim.run_throughput(Traffic("uniform", load=0.5),
                                 warm=60, measure=100)
    assert res.throughput == pytest.approx(ref["throughput"])
    assert res.avg_hops == pytest.approx(ref["avg_hops"])
    assert res.ejected == int(ref["ejected"])


def test_run_allreduce_first_class():
    exp = Experiment(network=TINY, route=ROUTE,
                     workload=WorkloadSpec("allreduce", ranks=16,
                                           vec_packets=8),
                     max_slots=3000)
    res = run(exp)
    assert res.metric == "completion"
    assert res.completed
    assert res.slots == sum(res.phase_slots)
    assert len(res.phase_slots) == 2 * 4          # log2(16) each direction
    # completion counts ALL deliveries (incl. self-partnered local ones), so
    # no phase can finish faster than its per-endpoint packet count
    from repro.core.collectives import rabenseifner_phases
    assert all(s >= ph["packets"] for s, ph in
               zip(res.phase_slots, rabenseifner_phases(16, 8)))
    # result record JSON round-trips
    again = Result.from_json(res.to_json())
    assert again == res


def test_run_result_metric_auto():
    a2a = Experiment(network=TINY, route=ROUTE,
                     workload=WorkloadSpec("all2all", rounds=2),
                     max_slots=2000)
    assert a2a.resolved_metric() == "completion"
    res = run(a2a)
    assert res.completed and res.slots >= 2


# ---------------------------------------------------------------------- #
# sweep
# ---------------------------------------------------------------------- #
@pytest.mark.slow
def test_sweep_one_result_per_grid_point():
    base = Experiment(network=TINY, route=ROUTE,
                      workload=WorkloadSpec("uniform", load=0.5),
                      warm=20, measure=40)
    axes = {"workload.load": [0.2, 0.4], "seed": [0, 1, 2]}
    results = sweep(base, axes)
    assert len(results) == 6
    got = {(r.experiment.workload.load, r.experiment.seed) for r in results}
    assert got == {(l, s) for l in (0.2, 0.4) for s in (0, 1, 2)}
    assert all(r.throughput is not None for r in results)


@pytest.mark.slow
def test_sweep_reuses_simulators_per_fabric():
    base = Experiment(network=TINY, route=ROUTE,
                      workload=WorkloadSpec("uniform", load=0.5),
                      warm=10, measure=20)
    cache = SimulatorCache()
    sweep(base, {"workload.load": [0.2, 0.4], "seed": [0, 1]}, cache=cache)
    assert len(cache) == 1                 # one fabric -> one simulator
    sweep(base, {"route.policy": ["polarized", "ksp"]}, cache=cache)
    assert len(cache) == 2                 # new policy -> one more
    cache.close()
    assert len(cache) == 0


def test_expand_axes_fabric_outermost():
    base = Experiment(network=TINY, route=ROUTE)
    grid = expand_axes(base, {"seed": [0, 1],
                              "route.policy": ["polarized", "ksp"]})
    # fabric axis must vary slowest so consecutive points share simulators
    policies = [e.route.policy for e in grid]
    assert policies == ["polarized", "polarized", "ksp", "ksp"]


def test_expand_axes_seed_varies_fastest():
    # seed innermost regardless of insertion order, so run_all can fold
    # each seed-only stretch into one batched run
    base = Experiment(network=TINY, route=ROUTE)
    grid = expand_axes(base, {"seed": [0, 1], "workload.load": [0.2, 0.4]})
    coords = [(e.workload.load, e.seed) for e in grid]
    assert coords == [(0.2, 0), (0.2, 1), (0.4, 0), (0.4, 1)]


def test_expand_axes_relabels_named_base():
    base = Experiment(network=TINY, route=ROUTE, name="fig.base")
    grid = expand_axes(base, {"route.policy": ["polarized", "ksp"]})
    names = [e.label() for e in grid]
    assert names == ["fig.base[route.policy=polarized]",
                     "fig.base[route.policy=ksp]"]


# ---------------------------------------------------------------------- #
# batched replicas: vmapped runs must match scalar runs bitwise
# ---------------------------------------------------------------------- #
FT = NetworkSpec("fat_tree", {"radix": 4, "h": 1})
FT_ROUTE = RouteSpec(policy="minimal_adaptive", max_hops=4, pool=4096)


@pytest.mark.parametrize("net,route", [(TINY, ROUTE), (FT, FT_ROUTE)],
                         ids=["mrls", "fat_tree"])
@pytest.mark.slow
def test_batched_throughput_parity_with_scalar(net, route):
    base = dict(network=net, route=route,
                workload=WorkloadSpec("uniform", load=0.5),
                warm=30, measure=60)
    with SimulatorCache() as cache:
        res = run(Experiment(replicas=4, seed=1, **base), cache=cache)
        assert res.replica_seeds == (1, 2, 3, 4)
        for i, s in enumerate(res.replica_seeds):
            ref = run(Experiment(seed=s, **base), cache=cache)
            # bitwise, not approx: replica i IS the scalar run with seed s
            assert res.per_replica["throughput"][i] == ref.throughput
            assert res.per_replica["avg_hops"][i] == ref.avg_hops
            assert res.per_replica["ejected"][i] == ref.ejected
    agg = res.aggregates["throughput"]
    assert agg["min"] <= res.throughput <= agg["max"]
    assert res.throughput == pytest.approx(
        np.mean(res.per_replica["throughput"]))


@pytest.mark.parametrize("net,route", [(TINY, ROUTE), (FT, FT_ROUTE)],
                         ids=["mrls", "fat_tree"])
def test_batched_completion_parity_and_exact_slots(net, route):
    base = dict(network=net, route=route,
                workload=WorkloadSpec("all2all", rounds=3),
                chunk=64, max_slots=4000)
    with SimulatorCache() as cache:
        res = run(Experiment(replicas=4, **base), cache=cache)
        assert res.completed
        sim = cache.get(net, route)
        for i, s in enumerate(res.replica_seeds):
            ref = run(Experiment(seed=s, **base), cache=cache)
            assert res.per_replica["slots"][i] == ref.slots      # bitwise
            assert res.per_replica["completed"][i] == ref.completed
            # exact completion slot <= the old chunk-granular loop's value
            tr = Traffic("all2all", rounds=3)
            st = sim.make_state(tr, seed=s)
            while int(st["slot"]) < 4000:
                st = sim.run_chunk(st, tr, 64)
                if int(st["ejected"]) >= sim.S * 3:
                    break
            old_chunk_granular = int(st["slot"])
            assert ref.slots <= old_chunk_granular < ref.slots + 64


def test_batched_allreduce_parity_with_scalar():
    base = dict(network=TINY, route=ROUTE,
                workload=WorkloadSpec("allreduce", ranks=16, vec_packets=8),
                max_slots=3000)
    with SimulatorCache() as cache:
        res = run(Experiment(replicas=2, **base), cache=cache)
        assert res.completed and res.metric == "completion"
        for i, s in enumerate(res.replica_seeds):
            ref = run(Experiment(seed=s, **base), cache=cache)
            assert res.per_replica["slots"][i] == ref.slots
            assert res.per_replica["phase_slots"][i] == ref.phase_slots


def test_batched_collective_result_json_roundtrip_and_aggregates():
    # a batched (replicas=R) collective Result carries per-replica
    # phase_slots tuples + slots aggregates, and survives a JSON round
    # trip losslessly
    res = run(Experiment(network=TINY, route=ROUTE,
                         workload=WorkloadSpec("allreduce", ranks=16,
                                               vec_packets=8),
                         max_slots=3000, replicas=3, seed=2))
    assert res.replica_seeds == (2, 3, 4)
    rows = res.per_replica["phase_slots"]
    assert len(rows) == 3 and all(len(row) == 8 for row in rows)
    assert all(isinstance(v, int) for row in rows for v in row)
    # scalar conveniences are across-replica means; phase_slots means are
    # per-phase columns
    assert set(res.aggregates) >= {"slots", "pool_stall"}
    assert res.slots == pytest.approx(res.aggregates["slots"]["mean"])
    assert res.phase_slots == tuple(
        pytest.approx(np.mean([row[i] for row in rows]))
        for i in range(8))
    per_rep_totals = [sum(row) for row in rows]
    assert list(res.per_replica["slots"]) == per_rep_totals
    again = Result.from_json(res.to_json())
    assert again == res
    assert again.per_replica["phase_slots"] == rows


@pytest.mark.slow
def test_run_new_collectives_end_to_end():
    with SimulatorCache() as cache:
        for wl in (WorkloadSpec("ring_allreduce", ranks=8, vec_packets=16),
                   WorkloadSpec("rd_allreduce", ranks=16, vec_packets=8),
                   WorkloadSpec("all2all", rounds=3, schedule="window",
                                window=3),
                   WorkloadSpec("allreduce", ranks=16, vec_packets=8,
                                schedule="window", window=4)):
            res = run(Experiment(network=TINY, route=ROUTE, workload=wl,
                                 max_slots=4000), cache=cache)
            assert res.metric == "completion" and res.completed
            assert res.slots >= 1 and res.phase_slots is not None
            assert Result.from_json(res.to_json()) == res


@pytest.mark.slow
def test_run_adversarial_bernoulli_end_to_end():
    with SimulatorCache() as cache:
        for wl in (WorkloadSpec("tornado", load=0.3),
                   WorkloadSpec("shift", load=0.3, shift=5),
                   WorkloadSpec("hotspot", load=0.3, hot_frac=0.3,
                                hot_count=2),
                   WorkloadSpec("bursty", load=0.2, burst_len=6.0,
                                burst_load=0.8)):
            res = run(Experiment(network=TINY, route=ROUTE, workload=wl,
                                 warm=20, measure=40), cache=cache)
            assert res.metric == "throughput"
            assert res.throughput is not None and res.throughput > 0


def test_batched_result_json_roundtrip():
    res = run(Experiment(network=TINY, route=ROUTE,
                         workload=WorkloadSpec("uniform", load=0.5),
                         warm=20, measure=40, replicas=3))
    assert res.replica_seeds == (0, 1, 2)
    assert set(res.aggregates) >= {"throughput", "avg_hops", "ejected"}
    again = Result.from_json(res.to_json())
    assert again == res


def test_replicas_validation_and_seeds():
    with pytest.raises(ValueError, match="replicas"):
        Experiment(network=TINY, replicas=0)
    exp = Experiment(network=TINY, seed=5, replicas=3)
    assert exp.replica_seeds() == (5, 6, 7)
    assert Experiment.from_json(exp.to_json()) == exp


@pytest.mark.slow
def test_sweep_folds_seed_axis_same_results():
    base = Experiment(network=TINY, route=ROUTE,
                      workload=WorkloadSpec("uniform", load=0.5),
                      warm=20, measure=40)
    axes = {"workload.load": [0.2, 0.4], "seed": [0, 1, 2]}
    folded = sweep(base, axes)
    scalar = sweep(base, axes, fold_seeds=False)
    assert len(folded) == 6
    assert folded == scalar       # fold is an optimization, not a semantic


# ---------------------------------------------------------------------- #
# lifetime
# ---------------------------------------------------------------------- #
def test_simulator_context_manager_closes():
    with open_simulator(TINY, ROUTE) as sim:
        r = sim.run_throughput(Traffic("uniform", load=0.3),
                               warm=10, measure=20)
        assert 0 <= r["throughput"] <= 1.5
    assert sim.closed
    with pytest.raises(RuntimeError, match="closed"):
        sim.make_state(Traffic("uniform", load=0.3))


# ---------------------------------------------------------------------- #
# CLI
# ---------------------------------------------------------------------- #
def test_cli_run_spec_json(tmp_path, capsys):
    from repro.api.cli import main

    exp = Experiment(network=TINY, route=ROUTE,
                     workload=WorkloadSpec("uniform", load=0.5),
                     name="cli.tiny", warm=20, measure=40)
    spec = tmp_path / "spec.json"
    spec.write_text(exp.to_json())
    out = tmp_path / "results.json"
    assert main(["run", str(spec), "--out", str(out)]) == 0
    assert "cli.tiny" in capsys.readouterr().out
    records = json.loads(out.read_text())
    assert len(records) == 1
    res = Result.from_dict(records[0])
    assert res.experiment == exp and res.throughput is not None


def test_cli_run_replicas_flag(tmp_path, capsys):
    from repro.api.cli import main

    exp = Experiment(network=TINY, route=ROUTE,
                     workload=WorkloadSpec("uniform", load=0.5),
                     name="cli.batched", warm=20, measure=40)
    spec = tmp_path / "spec.json"
    spec.write_text(exp.to_json())
    out = tmp_path / "results.json"
    assert main(["run", str(spec), "--replicas", "2",
                 "--out", str(out)]) == 0
    assert "replicas=2" in capsys.readouterr().out
    res = Result.from_dict(json.loads(out.read_text())[0])
    assert res.experiment.replicas == 2
    assert len(res.per_replica["throughput"]) == 2


@pytest.mark.slow
def test_cli_sweep_spec_json(tmp_path):
    from repro.api.cli import main

    base = Experiment(network=TINY, route=ROUTE,
                      workload=WorkloadSpec("uniform", load=0.5),
                      warm=10, measure=20)
    doc = {"base": json.loads(base.to_json()),
           "axes": {"workload.load": [0.2, 0.5]}}
    spec = tmp_path / "sweep.json"
    spec.write_text(json.dumps(doc))
    out = tmp_path / "results.json"
    assert main(["sweep", str(spec), "--out", str(out)]) == 0
    loads = [r["experiment"]["workload"]["load"]
             for r in json.loads(out.read_text())]
    assert loads == [0.2, 0.5]


# ---------------------------------------------------------------------- #
# memory estimator (ISSUE 5)
# ---------------------------------------------------------------------- #
def test_estimate_memory_exact_table_and_state_bytes():
    from repro.api import estimate_memory

    est = estimate_memory(TINY, ROUTE)
    tb = build_tables(build_network(TINY), masks="dense")
    assert est["tables"]["dist_leaf_bytes"] == tb.dist_leaf.nbytes
    # dense layout retains both numpy mask twins on the host
    assert est["tables"]["host_mask_bytes"] == (tb.min_mask.nbytes
                                                + tb.away_mask.nbytes)
    assert est["tables"]["mask_layout"] == "dense"
    # state estimate == the real state's array bytes, exactly
    with Simulator(tb, ROUTE.to_sim_config()) as sim:
        # polarized keeps one device table: the fused route rows
        tables = {k: v for k, v in sim._tables().items() if v is not None}
        assert set(tables) == {"route_rows"}
        assert est["tables"]["device_table_bytes"] == \
            tables["route_rows"].nbytes
        st = sim.make_state(Traffic("uniform", load=0.5), 0)
        counted = ("qbuf", "qhead", "qlen", "oq_buf", "oq_head", "oq_len",
                   "eq_buf", "eq_head", "eq_len", "fl_buf", "p_sd",
                   "p_mid", "p_bh", "msg_rem", "msg_dst", "prog",
                   "lat_hist")
        actual = sum(np.asarray(st[k]).nbytes for k in counted)
    assert est["state_bytes_per_replica"] == actual
    assert est["dims"]["n_endpoints"] == 42
    assert est["peak_bytes"] > est["total_bytes"] > 0


def test_estimate_memory_from_experiment_and_replicas():
    from repro.api import estimate_memory

    exp = Experiment(network=TINY, route=ROUTE, replicas=4)
    est = estimate_memory(exp)
    est1 = estimate_memory(TINY, ROUTE, replicas=1)
    assert est["replicas"] == 4
    assert (est["total_bytes"] - est1["total_bytes"]
            == 3 * est1["state_bytes_per_replica"])
    # minimal policies hold the toward-bit words and int16 distances of a
    # (leaf, switch) pair; polarized one fused row of route_row_words
    est_min = estimate_memory(TINY, RouteSpec(policy="minimal_adaptive",
                                              pool=4096))
    dims = est["dims"]
    pairs = dims["n_leaves"] * dims["n_switches"]
    assert (est_min["tables"]["device_table_bytes"]
            == pairs * (4 * dims["mask_words"] + 2))
    assert (est["tables"]["device_table_bytes"]
            == pairs * 4 * route_row_words(dims["max_ports"]))


def test_estimate_memory_prices_failure_schedule_state():
    """With a non-empty FailureSchedule the tables move into the state
    (plus live up-masks and the drop counter); the estimator's add-on
    must match the real armed state's extra array bytes exactly."""
    import dataclasses
    from repro.api import estimate_memory, FailureSchedule

    topo = build_network(TINY)
    sched = FailureSchedule.random_links(topo, 2, down_slot=10, seed=0)
    tiny_f = dataclasses.replace(TINY, failures=sched)
    est = estimate_memory(tiny_f, ROUTE)
    est0 = estimate_memory(TINY, ROUTE)
    assert est0["failures"] == {"armed": False,
                                "state_bytes_per_replica": 0}
    assert est["failures"]["armed"]
    add_on = est["failures"]["state_bytes_per_replica"]
    assert (est["state_bytes_per_replica"]
            == est0["state_bytes_per_replica"] + add_on)

    tb = build_tables(topo, masks="dense")
    with Simulator(tb, ROUTE.to_sim_config(), failures=sched) as sim:
        st = sim.make_state(Traffic("uniform", load=0.5), 0)
        extra = ("tbl_rows", "link_up", "switch_up", "fail_drop")
        assert set(extra) <= set(st)
        assert not {"tbl_min", "tbl_away", "tbl_dist"} & set(st)
        actual = sum(np.asarray(st[k]).nbytes for k in extra)
    assert add_on == actual


def test_estimate_memory_resolves_blocked_layout_at_scale():
    """Above DENSE_MASK_LIMIT the estimator predicts the blocked layout
    and zero retained host-mask bytes — priced analytically, no tables
    are ever built."""
    from repro.api import estimate_memory
    from repro.core import routing as routing_mod

    old = routing_mod.DENSE_MASK_LIMIT
    try:
        routing_mod.DENSE_MASK_LIMIT = 64
        est = estimate_memory(TINY, ROUTE)
    finally:
        routing_mod.DENSE_MASK_LIMIT = old
    assert est["tables"]["mask_layout"] == "blocked"
    assert est["tables"]["host_mask_bytes"] == 0


def test_cli_estimate_spec_json(tmp_path, capsys):
    from repro.api.cli import main

    exp = Experiment(network=TINY, route=ROUTE, name="est.tiny")
    spec = tmp_path / "spec.json"
    spec.write_text(exp.to_json())
    out = tmp_path / "est.json"
    assert main(["estimate", str(spec), "--replicas", "3",
                 "--out", str(out)]) == 0
    assert "est.tiny" in capsys.readouterr().out
    rec = json.loads(out.read_text())[0]
    assert rec["name"] == "est.tiny"
    assert rec["replicas"] == 3
    assert rec["total_bytes"] > 0
