"""Fault injection + degraded-mode routing (ISSUE 7).

Four layers under test:

* **Schedule spec** — :class:`FailureSchedule` JSON round-trips, validates
  against the topology, and its seeded random ladders are deterministic.
* **Delta rebuilds** — :meth:`RoutingTables.apply_failures` must agree
  with a from-scratch rebuild on the pruned topology for every affected
  leaf row (distances exactly; masks bitwise under the live-port words,
  since masks stay packed against the static adjacency by design), and
  restoring every failed element must return the tables to the pristine
  state *bitwise*.
* **Live engine** — the static no-op branch keeps zero-failure runs
  bitwise on the committed goldens; an armed-but-all-up schedule is
  value-identical to pristine; ``run_resilience`` applies transitions on
  slot boundaries, frees packets under the ``drop`` policy, and always
  restores pristine tables; pristine ``degraded`` routing is bitwise
  ``minimal_adaptive``.
* **Driver + runtime satellites** — the ``resilience`` metric flows
  through ``run()``, ``degrade_sweep`` emits retention curves, the
  straggler detector's variance EMA uses the pre-update residual, and
  ``schedule_fault_hook`` drives schedule transitions from the
  fault-tolerant runner's step clock.
"""
import dataclasses
import json
import pathlib

import numpy as np
import pytest

from repro.core import (FailureEvent, FailureSchedule, UNREACHABLE,
                        build_tables, canonical_link_ids, mrls)
from repro.api import (Experiment, NetworkSpec, RouteSpec, WorkloadSpec,
                       degrade_sweep, run)
from repro.api.registry import build_network
from repro.simulator.engine import SimConfig, Simulator, Traffic

TOPO = mrls(n_leaves=14, u=3, d=3, seed=0)
GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "engine_parity.json")
    .read_text())
MASK_LAYOUTS = ("dense", pytest.param("blocked", marks=pytest.mark.slow))


def _link_events(topo, k, *, down_slot=0, seed=0):
    return FailureSchedule.random_links(topo, k, down_slot=down_slot,
                                        seed=seed).events


# ---------------------------------------------------------------------- #
# schedule spec layer
# ---------------------------------------------------------------------- #
def test_event_validation():
    FailureEvent("link", 3, 10)                       # transient failure ok
    FailureEvent("switch", 0, 0, up_slot=5)
    with pytest.raises(ValueError, match="kind"):
        FailureEvent("cable", 0, 0)
    with pytest.raises(ValueError, match="id"):
        FailureEvent("link", -1, 0)
    with pytest.raises(ValueError, match="up_slot"):
        FailureEvent("link", 0, 10, up_slot=10)


def test_schedule_json_round_trip():
    sched = FailureSchedule(
        events=(FailureEvent("link", 7, 5, up_slot=40),
                FailureEvent("switch", 14, 12)),
        policy="drop")
    back = FailureSchedule.from_json(sched.to_json())
    assert back == sched
    # permanent failures omit up_slot from the JSON
    d = sched.to_dict()
    assert "up_slot" not in d["events"][1]


def test_network_spec_failures_round_trip():
    sched = FailureSchedule(events=_link_events(TOPO, 2, down_slot=9))
    net = NetworkSpec("mrls", {"n_leaves": 14, "u": 3, "d": 3, "seed": 0},
                      failures=sched)
    back = NetworkSpec.from_dict(json.loads(json.dumps(net.to_dict())))
    assert back == net
    assert back.failures == sched
    # no schedule -> no key in the dict (older specs parse unchanged)
    bare = dataclasses.replace(net, failures=None)
    assert "failures" not in bare.to_dict()


def test_schedule_validate():
    n, p = TOPO.n_switches, TOPO.max_ports
    good = FailureSchedule(events=_link_events(TOPO, 1))
    assert good.validate(TOPO) is good
    with pytest.raises(ValueError, match="link"):
        FailureSchedule(events=(FailureEvent("link", n * p, 0),)) \
            .validate(TOPO)
    # an unconnected port slot is not a link
    dead = int(np.nonzero(TOPO.nbrs.reshape(-1) < 0)[0][0])
    with pytest.raises(ValueError, match="link"):
        FailureSchedule(events=(FailureEvent("link", dead, 0),)) \
            .validate(TOPO)
    leaf = int(TOPO.leaf_ids[0])
    with pytest.raises(ValueError, match="leaf"):
        FailureSchedule(events=(FailureEvent("switch", leaf, 0),)) \
            .validate(TOPO)


def test_random_links_deterministic_and_canonical():
    canon = set(int(i) for i in canonical_link_ids(TOPO))
    a = FailureSchedule.random_links(TOPO, 5, down_slot=3, seed=11)
    b = FailureSchedule.random_links(TOPO, 5, down_slot=3, seed=11)
    c = FailureSchedule.random_links(TOPO, 5, down_slot=3, seed=12)
    assert a == b and a != c
    assert len(a) == 5
    assert all(ev.kind == "link" and ev.id in canon for ev in a.events)
    assert len({ev.id for ev in a.events}) == 5        # no repeats


def test_random_ladder_slots():
    sched = FailureSchedule.random_ladder(TOPO, 3, start_slot=10,
                                          step_slots=7, seed=2)
    assert [ev.down_slot for ev in sched.events] == [10, 17, 24]


def test_transitions_grouped_and_sorted():
    sched = FailureSchedule(events=(
        FailureEvent("link", 3, 20, up_slot=50),
        FailureEvent("link", 9, 20),
        FailureEvent("switch", 15, 35)))
    trans = sched.transitions()
    assert [t[0] for t in trans] == [20, 35, 50]
    assert len(trans[0][1]) == 2 and not trans[0][2]   # two downs at 20
    assert not trans[2][1] and len(trans[2][2]) == 1   # one up at 50


# ---------------------------------------------------------------------- #
# delta rebuilds vs full rebuild on the pruned topology
# ---------------------------------------------------------------------- #
def _dead_arrays(topo, events):
    n, p = topo.n_switches, topo.max_ports
    dead_ports = np.zeros((n, p), bool)
    sw_up = np.ones(n, bool)
    for ev in events:
        if ev.kind == "switch":
            sw_up[ev.id] = False
            continue
        c, pt = divmod(ev.id, p)
        dead_ports[c, pt] = True
        dead_ports[int(topo.nbrs[c, pt]), int(topo.nbr_port[c, pt])] = True
    return dead_ports, sw_up


def _pruned(topo, dead_ports, sw_up):
    valid = topo.nbrs >= 0
    nbr_safe = np.where(valid, topo.nbrs, 0)
    eff = topo.nbrs.copy()
    eff[dead_ports] = -1
    eff[~sw_up] = -1
    eff[valid & ~sw_up[nbr_safe]] = -1
    effp = np.where(eff >= 0, topo.nbr_port, -1)
    return dataclasses.replace(topo, nbrs=eff, nbr_port=effp)


def _port_words(live):
    """[N, P] bool -> [N, W] uint32 in _pack_mask_block bit order."""
    n, p = live.shape
    w = (p + 31) // 32
    words = np.zeros((n, w), np.uint32)
    for j in range(p):
        words[:, j // 32] |= live[:, j].astype(np.uint32) << np.uint32(j % 32)
    return words


def _assert_matches_pruned(tables, topo, events):
    dead_ports, sw_up = _dead_arrays(topo, events)
    ref = build_tables(_pruned(topo, dead_ports, sw_up), masks="dense")
    ref_dist = np.where(ref.dist_leaf < 0, UNREACHABLE,
                        ref.dist_leaf).astype(np.int16)
    np.testing.assert_array_equal(tables.dist_leaf, ref_dist)
    # masks agree wherever a live port exists (dead-port bits are
    # intentionally retained -- the engine's up-mask excludes them)
    valid = topo.nbrs >= 0
    nbr_safe = np.where(valid, topo.nbrs, 0)
    live = valid & ~dead_ports & sw_up[:, None] & sw_up[nbr_safe]
    lw = _port_words(live)[None]
    np.testing.assert_array_equal(tables.min_mask & lw, ref.min_mask & lw)
    np.testing.assert_array_equal(tables.away_mask & lw,
                                  ref.away_mask & lw)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_delta_matches_full_rebuild_and_restores(seed):
    tables = build_tables(TOPO, masks="dense")
    pristine = (tables.dist_leaf.copy(), tables.min_mask.copy(),
                tables.away_mask.copy())
    events = _link_events(TOPO, 4, seed=seed)
    delta = tables.apply_failures(down=events)
    assert 0 < delta.n_affected <= TOPO.n_leaves
    assert delta.link_up.sum() == (TOPO.nbrs >= 0).sum() - 2 * len(events)
    _assert_matches_pruned(tables, TOPO, events)
    # restore every link -> pristine, bitwise
    d2 = tables.apply_failures(up=events)
    assert d2.link_up.sum() == (TOPO.nbrs >= 0).sum()
    np.testing.assert_array_equal(tables.dist_leaf, pristine[0])
    np.testing.assert_array_equal(tables.min_mask, pristine[1])
    np.testing.assert_array_equal(tables.away_mask, pristine[2])


def test_switch_failure_recomputes_every_leaf():
    tables = build_tables(TOPO, masks="dense")
    spine = int(np.nonzero(~TOPO.is_leaf)[0][0])
    ev = FailureEvent("switch", spine, 0)
    delta = tables.apply_failures(down=(ev,))
    assert delta.n_affected == TOPO.n_leaves
    assert not delta.switch_up[spine]
    _assert_matches_pruned(tables, TOPO, (ev,))
    tables.apply_failures(up=(ev,))
    ref = build_tables(TOPO, masks="dense")
    np.testing.assert_array_equal(tables.dist_leaf, ref.dist_leaf)


def test_duplicate_and_noop_events_are_safe():
    tables = build_tables(TOPO, masks="dense")
    ev = _link_events(TOPO, 1, seed=3)
    tables.apply_failures(down=ev)
    again = tables.apply_failures(down=ev)             # already dead
    assert again.n_affected == 0
    tables.apply_failures(up=ev)
    noop = tables.apply_failures(up=ev)                # already up
    assert noop.n_affected == 0
    assert noop.link_up.sum() == (TOPO.nbrs >= 0).sum()


def test_blocked_layout_delta_keeps_streamed_blocks_consistent():
    dense = build_tables(TOPO, masks="dense")
    blocked = build_tables(TOPO, masks="blocked", leaf_block=4)
    events = _link_events(TOPO, 3, seed=5)
    dd = dense.apply_failures(down=events)
    bd = blocked.apply_failures(down=events)
    np.testing.assert_array_equal(bd.dist_rows, dd.dist_rows)
    np.testing.assert_array_equal(bd.min_rows, dd.min_rows)
    np.testing.assert_array_equal(blocked.dist_leaf, dense.dist_leaf)
    # streamed blocks repack from the mutated distances
    got = np.concatenate([b for _, _, b, _ in blocked.mask_blocks()])
    np.testing.assert_array_equal(got, dense.min_mask)


# ---------------------------------------------------------------------- #
# live engine: zero-failure parity, degraded policy, resilience runs
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module", params=MASK_LAYOUTS)
def golden_tables(request):
    return build_tables(mrls(**GOLDEN["fabric"]), masks=request.param)


@pytest.mark.parametrize(
    "policy", ["polarized",
               pytest.param("minimal_adaptive", marks=pytest.mark.slow)])
def test_empty_schedule_replays_golden_bitwise(golden_tables, policy):
    """An empty FailureSchedule must leave the engine on the static
    no-failure branch: the committed golden replays bitwise."""
    gp = GOLDEN["policies"][policy]
    cfg = SimConfig(policy=policy, max_hops=10, pool=4096)
    with Simulator(golden_tables, cfg, failures=FailureSchedule()) as sim:
        assert not sim.has_failures
        thr = sim.run_throughput(Traffic("uniform", load=0.7),
                                 warm=GOLDEN["warm"],
                                 measure=GOLDEN["measure"], seed=0)
    assert thr["throughput"] == gp["throughput"]      # bitwise, no approx
    assert thr["avg_hops"] == gp["avg_hops"]
    assert thr["ejected"] == gp["ejected"]
    assert thr["pool_stall"] == gp["pool_stall"]


@pytest.mark.slow
def test_empty_schedule_replays_collective_golden_bitwise():
    from repro.workloads import compile_program, rabenseifner_program
    coll = json.loads(
        (pathlib.Path(__file__).parent / "golden" /
         "collective_parity.json").read_text())
    gp = coll["policies"]["polarized"]
    tb = build_tables(mrls(**coll["fabric"]))
    cfg = SimConfig(policy="polarized", max_hops=10, pool=4096)
    with Simulator(tb, cfg, failures=FailureSchedule()) as sim:
        cp = compile_program(
            rabenseifner_program(sim.S, coll["ranks"], coll["vec_packets"]),
            schedule="barrier")
        r = sim.run_program(cp, chunk=coll["chunk"],
                            max_slots=coll["max_slots"], seed=coll["seed"])
    assert int(r["slots"]) == gp["slots"]
    assert [int(s) for s in r["phase_slots"]] == gp["phase_slots"]
    assert int(r["pool_stall"]) == gp["pool_stall"]


def test_degraded_pristine_is_bitwise_minimal_adaptive():
    tb = build_tables(TOPO)
    tr = Traffic("uniform", load=0.7)
    out = {}
    for pol in ("minimal_adaptive", "degraded"):
        with Simulator(tb, SimConfig(policy=pol, max_hops=10,
                                     pool=4096)) as sim:
            out[pol] = sim.run_throughput(tr, warm=30, measure=60, seed=0)
    assert out["degraded"]["throughput"] == \
        out["minimal_adaptive"]["throughput"]
    assert out["degraded"]["ejected"] == out["minimal_adaptive"]["ejected"]
    assert out["degraded"]["avg_hops"] == \
        out["minimal_adaptive"]["avg_hops"]


def test_armed_future_schedule_is_value_identical():
    """Arming a schedule whose first event lies beyond the run moves the
    tables into the state but must not change any result value (the
    failure branches consume no extra PRNG keys by design)."""
    tb = build_tables(TOPO)
    tr = Traffic("uniform", load=0.7)
    sched = FailureSchedule(events=_link_events(TOPO, 2, down_slot=10_000))
    cfg = SimConfig(policy="polarized", max_hops=10, pool=4096)
    with Simulator(tb, cfg) as sim:
        ref = sim.run_throughput(tr, warm=30, measure=60, seed=0)
    with Simulator(tb, cfg, failures=sched) as sim:
        assert sim.has_failures
        got = sim.run_throughput(tr, warm=30, measure=60, seed=0)
    for k in ("throughput", "avg_hops", "ejected", "pool_stall"):
        assert got[k] == ref[k], k


def test_run_resilience_end_to_end_and_restores_tables():
    tb = build_tables(TOPO)
    pristine = tb.dist_leaf.copy()
    sched = FailureSchedule(events=tuple(
        dataclasses.replace(ev, down_slot=20, up_slot=60)
        for ev in _link_events(TOPO, 5, seed=7)))
    cfg = SimConfig(policy="degraded", max_hops=12, pool=4096)
    with Simulator(tb, cfg, failures=sched) as sim:
        r = sim.run_resilience(Traffic("uniform", load=0.5),
                               warm=40, measure=80, seed=0)
    assert 0.0 < r["throughput"] <= 1.0
    assert r["ejected"] > 0
    assert r["fail_drop"] == 0                        # requeue never drops
    assert r["p0.5"] > 0
    # transient failure window fully unwound: tables pristine again
    np.testing.assert_array_equal(tb.dist_leaf, pristine)
    assert not tb.dead_ports.any()


def test_drop_policy_frees_stranded_packets():
    tb = build_tables(TOPO)
    # the failure lands inside the measure window -- counters report the
    # windowed delta, so a warm-phase drop would read as zero
    sched = FailureSchedule(events=_link_events(TOPO, 10, down_slot=30),
                            policy="drop")
    cfg = SimConfig(policy="degraded", max_hops=12, pool=4096)
    with Simulator(tb, cfg, failures=sched) as sim:
        r = sim.run_resilience(Traffic("uniform", load=0.9),
                               warm=20, measure=60, seed=0)
    assert r["fail_drop"] > 0
    assert 0.0 < r["throughput"] <= 1.0


@pytest.mark.parametrize("batched", [False, True])
def test_armed_polarized_update_tables_writes_repacked_delta_rows(batched):
    """Armed polarized state holds one fused route-row table; after
    update_tables its affected rows are the delta repacked, the others
    untouched, and the whole table equals a fresh pack of the updated host
    tables (replica for replica when batched)."""
    from repro.core import pack_route_rows

    tb = build_tables(TOPO, masks="dense")
    p = TOPO.max_ports
    events = _link_events(TOPO, 4, seed=2)
    cfg = SimConfig(policy="polarized", max_hops=10, pool=4096)
    tr = Traffic("uniform", load=0.5)
    with Simulator(tb, cfg,
                   failures=FailureSchedule(events=events)) as sim:
        st = (sim.make_batch_state(tr, [0, 1]) if batched
              else sim.make_state(tr, 0))
        assert "tbl_rows" in st
        assert not {"tbl_min", "tbl_away", "tbl_dist"} & set(st)
        shape = (-1, sim.n1, sim.N, sim.K)
        before = np.asarray(st["tbl_rows"]).reshape(shape)
        delta = tb.apply_failures(down=events)
        assert 0 < delta.n_affected < sim.n1
        st = sim.update_tables(st, delta)
        got = np.asarray(st["tbl_rows"]).reshape(shape)
    assert got.shape[0] == (2 if batched else 1)
    want = pack_route_rows(delta.min_rows, delta.away_rows, delta.dist_rows,
                           p)
    other = np.setdiff1d(np.arange(TOPO.n_leaves), delta.leaf_rows)
    for rep in got:
        np.testing.assert_array_equal(rep[delta.leaf_rows], want)
        np.testing.assert_array_equal(rep[other], before[0][other])
        np.testing.assert_array_equal(
            rep, pack_route_rows(tb.min_mask, tb.away_mask, tb.dist_leaf, p))


def test_failure_apis_require_armed_simulator():
    tb = build_tables(TOPO)
    with Simulator(tb, SimConfig(policy="polarized", pool=4096)) as sim:
        st = sim.make_state(Traffic("uniform", load=0.5), 0)
        delta = tb.apply_failures()
        with pytest.raises(RuntimeError, match="failure schedule"):
            sim.update_tables(st, delta)
        with pytest.raises(ValueError, match="FailureSchedule"):
            sim.run_resilience(Traffic("uniform", load=0.5))


# ---------------------------------------------------------------------- #
# driver layer: resilience metric + degradation sweep
# ---------------------------------------------------------------------- #
NET = NetworkSpec("mrls", {"n_leaves": 14, "u": 3, "d": 3, "seed": 0})
DEGRADED = RouteSpec(policy="degraded", max_hops=12, pool=4096)


def test_resilience_metric_through_run():
    topo = build_network(NET)
    sched = FailureSchedule.random_links(topo, 3, down_slot=10, seed=1)
    exp = Experiment(network=dataclasses.replace(NET, failures=sched),
                     route=DEGRADED,
                     workload=WorkloadSpec("uniform", load=0.5),
                     warm=30, measure=60, seed=0)
    assert exp.resolved_metric() == "resilience"
    res = run(exp)
    assert res.metric == "resilience"
    assert 0.0 < res.throughput <= 1.0
    assert res.fail_drop == 0
    assert res.latency["p50"] is not None
    back = Result_round_trip(res)
    assert back.fail_drop == res.fail_drop


def Result_round_trip(res):
    from repro.api import Result
    return Result.from_dict(json.loads(json.dumps(res.to_dict())))


def test_degrade_sweep_retention_curve():
    base = Experiment(network=NET, route=DEGRADED,
                      workload=WorkloadSpec("uniform", load=0.5),
                      warm=30, measure=60, seed=0)
    from repro.api import DegradeSpec
    rec = degrade_sweep(DegradeSpec(base=base, rates=(0.0, 0.10),
                                    fail_seed=4))
    assert rec["n_links"] == len(canonical_link_ids(build_network(NET)))
    assert [p["rate"] for p in rec["points"]] == [0.0, 0.10]
    assert rec["points"][0]["n_links_down"] == 0
    assert rec["points"][0]["retention"] == 1.0
    assert rec["points"][1]["n_links_down"] > 0
    for p in rec["points"]:
        assert 0.0 < p["delivered"] <= 1.0
        assert p["retention"] > 0.0


@pytest.mark.slow
def test_cli_degrade_smoke(tmp_path, capsys):
    from repro.api.cli import main
    spec = tmp_path / "degrade.json"
    base = Experiment(network=NET, route=DEGRADED,
                      workload=WorkloadSpec("uniform", load=0.5),
                      warm=30, measure=60, seed=0)
    spec.write_text(json.dumps({"base": base.to_dict(),
                                "rates": [0.0, 0.05]}))
    out = tmp_path / "faults.json"
    assert main(["degrade", str(spec), "--out", str(out)]) == 0
    records = json.loads(out.read_text())
    assert len(records) == 1 and len(records[0]["points"]) == 2
    assert "retention=" in capsys.readouterr().out


# ---------------------------------------------------------------------- #
# runtime satellites: straggler EMA fix + schedule-driven fault hook
# ---------------------------------------------------------------------- #
def test_straggler_warmup_boundary():
    from repro.runtime.fault_tolerance import FTConfig, StragglerDetector

    W = StragglerDetector.WARMUP
    det = StragglerDetector(FTConfig())
    for i in range(W - 1):
        assert det.observe(i, 1.0) is False
    # n == WARMUP: still inside warmup, a huge step must NOT flag
    assert det.observe(W - 1, 100.0) is False
    assert det.n == W and det.flagged == []
    # n == WARMUP + 1: first eligible observation
    det2 = StragglerDetector(FTConfig())
    for i in range(W):
        det2.observe(i, 1.0)
    assert det2.observe(W, 100.0) is True
    assert det2.flagged == [(W, 100.0)]


def test_straggler_variance_uses_preupdate_residual():
    from repro.runtime.fault_tolerance import FTConfig, StragglerDetector

    det = StragglerDetector(FTConfig(ema=0.9))
    det.observe(0, 1.0)                               # seeds mean only
    det.observe(1, 2.0)
    # resid vs the PRE-update mean: (2.0 - 1.0)^2 * 0.1 = 0.1; the old
    # post-update residual gave (2.0 - 1.1)^2 * 0.1 = 0.081
    assert det.mean == pytest.approx(1.1)
    assert det.var == pytest.approx(0.1)
    # constant inputs keep variance at zero
    det3 = StragglerDetector(FTConfig(ema=0.9))
    for i in range(10):
        det3.observe(i, 3.0)
    assert det3.var == 0.0 and det3.mean == 3.0


def test_schedule_fault_hook_applies_transitions_on_step_clock():
    import jax
    from repro.runtime.fault_tolerance import schedule_fault_hook

    tb = build_tables(TOPO)
    events = _link_events(TOPO, 2, down_slot=3, seed=6)
    sched = FailureSchedule(events=events)
    cfg = SimConfig(policy="degraded", max_hops=12, pool=4096)
    with Simulator(tb, cfg, failures=sched) as sim:
        tr = Traffic("uniform", load=0.5)
        holder = [sim.make_state(tr, 0)]
        full = int(np.asarray(jax.device_get(holder[0]["link_up"])).sum())
        hook = schedule_fault_hook(sim, holder, slots_per_step=2)
        hook(0)                                       # boundary 2 < slot 3
        assert int(jax.device_get(holder[0]["link_up"]).sum()) == full
        hook(1)                                       # boundary 4 >= slot 3
        assert (int(jax.device_get(holder[0]["link_up"]).sum())
                == full - 2 * len(events))
        holder[0] = sim.run_chunk(holder[0], tr, 8)   # still runs
    tb.apply_failures(up=events)                      # leave tables clean

    with Simulator(tb, SimConfig(policy="polarized", pool=4096)) as sim:
        with pytest.raises(ValueError, match="FailureSchedule"):
            schedule_fault_hook(sim, [None])
