"""repro.workloads: pattern registry, program IR/compiler invariants, and
the engine's on-device program executor."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api.specs import WorkloadSpec
from repro.core import build_tables, mrls
from repro.simulator.engine import SimConfig, Simulator, Traffic
from repro.workloads import (WorkloadProgram, all2all_program,
                             build_collective_program, compile_program,
                             pattern_kinds, rabenseifner_program,
                             rd_allreduce_program, ring_allreduce_program)
from repro.workloads.patterns import (BERNOULLI_PATTERNS,
                                      COLLECTIVE_PATTERNS, check_pattern)


# ---------------------------------------------------------------------- #
# shared pattern registry: WorkloadSpec and engine Traffic raise the same
# way on unknowns (regression: the engine used to accept any string and
# silently inject nothing)
# ---------------------------------------------------------------------- #
def test_engine_traffic_rejects_unknown_pattern():
    with pytest.raises(ValueError, match="unknown pattern"):
        Traffic("nonsense")


def test_workload_spec_rejects_unknown_pattern():
    with pytest.raises(ValueError, match="unknown pattern"):
        WorkloadSpec("nonsense")


def test_engine_only_patterns_hidden_from_specs():
    # engine-level patterns stay constructible as Traffic but are not
    # WorkloadSpec vocabulary (reached via collectives instead)
    for pat in ("phase", "program"):
        assert check_pattern(pat, engine=True) == "engine"
        with pytest.raises(ValueError, match="unknown pattern"):
            WorkloadSpec(pat)


def test_spec_bernoulli_patterns_are_engine_patterns():
    # every Bernoulli spec pattern must be executable by the raw engine —
    # one registry, no drift
    for pat in BERNOULLI_PATTERNS:
        assert check_pattern(pat) == "bernoulli"
        assert check_pattern(pat, engine=True) == "bernoulli"
    # built-ins are a subset: register_program_builder may have added more
    assert set(BERNOULLI_PATTERNS + COLLECTIVE_PATTERNS) <= {
        n for n, k in pattern_kinds().items() if k != "engine"}


def test_workload_spec_schedule_validation():
    with pytest.raises(ValueError, match="unknown schedule"):
        WorkloadSpec("allreduce", schedule="eager")
    with pytest.raises(ValueError, match="collective"):
        WorkloadSpec("uniform", schedule="barrier")
    with pytest.raises(ValueError, match="schedule='window'"):
        WorkloadSpec("allreduce", schedule="barrier", window=4)
    with pytest.raises(ValueError, match="window"):
        WorkloadSpec("all2all", rounds=2, schedule="window", window=0)
    assert WorkloadSpec("all2all", rounds=2, schedule="window",
                        window=4).window == 4


def test_adversarial_knob_validation():
    with pytest.raises(ValueError, match="shift"):
        WorkloadSpec("shift", shift=0)
    with pytest.raises(ValueError, match="hot_frac"):
        WorkloadSpec("hotspot", hot_frac=0.0)
    with pytest.raises(ValueError, match="hot_count"):
        WorkloadSpec("hotspot", hot_count=0)
    with pytest.raises(ValueError, match="burst_load"):
        WorkloadSpec("bursty", burst_load=0.0)
    with pytest.raises(ValueError, match="burst_len"):
        WorkloadSpec("bursty", burst_len=0.5)
    # an in-burst intensity below the requested long-run load could never
    # realize that load — reject rather than silently cap
    with pytest.raises(ValueError, match="exceeds burst_load"):
        WorkloadSpec("bursty", load=0.8, burst_load=0.5)
    # even load <= burst_load can be unreachable once the ON fraction
    # saturates at burst_len/(burst_len+1): reject, don't undershoot
    with pytest.raises(ValueError, match="unreachable"):
        WorkloadSpec("bursty", load=0.99, burst_load=1.0, burst_len=8.0)
    with pytest.raises(ValueError, match="power of two"):
        WorkloadSpec("rd_allreduce", ranks=12)
    with pytest.raises(ValueError, match="ranks >= 2"):
        WorkloadSpec("ring_allreduce", ranks=-3)


# ---------------------------------------------------------------------- #
# IR validation
# ---------------------------------------------------------------------- #
def test_ir_rejects_malformed_programs():
    with pytest.raises(ValueError, match="shape"):
        WorkloadProgram("bad", np.zeros((2, 4)), np.ones((2, 5)))
    with pytest.raises(ValueError, match=r"\[0, S\)"):
        WorkloadProgram("bad", np.full((1, 4), 7), np.ones((1, 4)))
    with pytest.raises(ValueError, match="packets"):
        WorkloadProgram("bad", np.zeros((1, 4)), np.full((1, 4), -1))
    with pytest.raises(ValueError, match="no packets"):
        WorkloadProgram("bad", np.zeros((2, 4)),
                        np.stack([np.ones(4), np.zeros(4)]))


def test_compile_rejects_int32_overflow():
    prog = WorkloadProgram("big", np.zeros((1, 4), np.int32),
                           np.full((1, 4), 1 << 29, np.int32))
    with pytest.raises(ValueError, match="int32"):
        compile_program(prog)


def test_program_builder_registry_unknown():
    with pytest.raises(KeyError, match="no program builder"):
        build_collective_program("uniform", 16)


# ---------------------------------------------------------------------- #
# compiler invariants (hypothesis): every library program's phases are
# valid pairings/permutations, expected == sum(packets) per phase, and a
# windowed compilation conserves total packets vs the barrier one
# ---------------------------------------------------------------------- #
def _build(kind: str, S: int, logn: int, vec: int, rounds: int):
    if kind == "all2all":
        return all2all_program(S, rounds)
    if kind == "ring":
        return ring_allreduce_program(S, (1 << logn) + 1, vec)  # non-pow2 ok
    if kind == "rabenseifner":
        return rabenseifner_program(S, 1 << logn, vec)
    return rd_allreduce_program(S, 1 << logn, vec)


@settings(max_examples=20, deadline=None)
@given(kind=st.sampled_from(["all2all", "ring", "rabenseifner", "rd"]),
       logn=st.integers(1, 5), vec=st.integers(1, 64),
       rounds=st.integers(1, 12), window=st.integers(1, 6))
def test_program_invariants(kind, logn, vec, rounds, window):
    S = 40
    prog = _build(kind, S, logn, vec, rounds)
    # every phase's partner row is a permutation of the endpoints (pairing
    # or rotation on the active ranks, identity on the idle ones)
    for p in range(prog.n_phases):
        row = prog.partner[p]
        assert np.array_equal(np.sort(row), np.arange(S))
    barrier = compile_program(prog, schedule="barrier")
    windowed = compile_program(prog, schedule="window", window=window)
    # per-phase ejection target is exactly the phase's packet total
    np.testing.assert_array_equal(np.asarray(barrier.expected),
                                  prog.packets.sum(axis=1))
    np.testing.assert_array_equal(
        np.asarray(barrier.expected_cum),
        np.cumsum(prog.packets.sum(axis=1)))
    # schedule choice never creates or drops packets
    assert barrier.total_packets == windowed.total_packets
    assert windowed.window == window and barrier.window == 1


@settings(max_examples=10, deadline=None)
@given(logn=st.integers(1, 6), vec=st.integers(1, 128))
def test_rabenseifner_program_matches_phase_list(logn, vec):
    from repro.core.collectives import rabenseifner_phases
    S, n = 80, 1 << logn
    prog = rabenseifner_program(S, n, vec)
    phases = rabenseifner_phases(n, vec)
    assert prog.n_phases == len(phases)
    for p, ph in enumerate(phases):
        np.testing.assert_array_equal(prog.partner[p, :n], ph["partner"])
        np.testing.assert_array_equal(prog.partner[p, n:],
                                      np.arange(n, S))
        assert (prog.packets[p] == ph["packets"]).all()


# ---------------------------------------------------------------------- #
# on-device program executor semantics (tiny fabric)
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def sim():
    tables = build_tables(mrls(n_leaves=14, u=3, d=3, seed=0))
    with Simulator(tables, SimConfig(policy="polarized", max_hops=10,
                                     pool=4096)) as s:
        yield s


def test_windowed_all2all_with_full_window_is_legacy_all2all(sim):
    # window >= rounds removes every dependency, which is exactly the
    # engine's free-running all2all — same PRNG stream, bitwise-equal slots
    rounds = 4
    cp = compile_program(all2all_program(sim.S, rounds), schedule="window",
                         window=rounds)
    r = sim.run_program(cp, chunk=16, max_slots=4000)
    legacy = sim.run_completion(Traffic("all2all", rounds=rounds),
                                expected=sim.S * rounds, chunk=16,
                                max_slots=4000)
    assert r["completed"] and legacy["completed"]
    assert int(r["phase_slots"][-1]) == legacy["slots"]
    assert int(r["slots"]) == legacy["slots"]


def test_window_tightens_to_barrier_like_and_loosens_to_pipelined(sim):
    rounds = 4
    slots = {}
    for w in (1, 2, rounds):
        cp = compile_program(all2all_program(sim.S, rounds),
                             schedule="window", window=w)
        r = sim.run_program(cp, chunk=16, max_slots=4000)
        assert r["completed"]
        done = np.asarray(r["phase_slots"])
        assert (np.diff(done) >= 0).all()     # cumulative, monotone
        slots[w] = int(r["slots"])
    # a pipelined window beats the fully-serialized one (the arbitration
    # noise between two deep windows can go either way, so only the
    # serialized endpoint is ordered)
    assert slots[rounds] <= slots[1] and slots[2] <= slots[1]


def test_barrier_program_records_per_phase_durations(sim):
    cp = compile_program(rabenseifner_program(sim.S, 16, 8))
    r = sim.run_program(cp, chunk=16, max_slots=3000)
    assert r["completed"]
    done = np.asarray(r["phase_slots"])
    assert done.shape == (8,) and (done >= 1).all()
    assert int(r["slots"]) == int(done.sum())
    # phase durations mirror the message-size schedule (rs == reversed ag)
    assert list(done) == list(done[::-1])


def test_program_batch_matches_scalar_bitwise(sim):
    cp = compile_program(ring_allreduce_program(sim.S, 8, 16))
    # the compiled schedule arrays are replica-invariant: ONE shared device
    # copy, not an R-fold stack (they ride the vmap with in_axes=None)
    bst = sim.make_program_batch_state(cp, [3, 4])
    assert bst["prog_partner"].shape == (cp.n_phases, sim.S)
    assert bst["phase_done"].shape == (2, cp.n_phases)
    rb = sim.run_program(cp, chunk=16, max_slots=4000, seeds=[3, 4])
    for i, s in enumerate((3, 4)):
        rs = sim.run_program(cp, chunk=16, max_slots=4000, seed=s)
        assert list(rb["phase_slots"][i]) == list(rs["phase_slots"])
        assert int(rb["slots"][i]) == rs["slots"]
        assert bool(rb["completed"][i]) == rs["completed"]


@pytest.fixture(scope="module")
def chunk_runs(sim):
    """``run_program`` outputs and final-state counters, memoised per
    (schedule, replicas, chunk): a windowed all2all and a barrier
    Rabenseifner allreduce, scalar (seed 5) and two replicas (3, 4)."""
    programs = {
        "window": compile_program(all2all_program(sim.S, 4),
                                  schedule="window", window=2),
        "barrier": compile_program(rabenseifner_program(sim.S, 16, 8))}
    memo = {}

    def get(schedule, replicas, chunk):
        key = (schedule, replicas, chunk)
        if key not in memo:
            seeds = dict(seeds=[3, 4]) if replicas == 2 else dict(seed=5)
            r = sim.run_program(programs[schedule], chunk=chunk,
                                max_slots=4000, **seeds)
            st = jax.device_get(r.pop("state"))
            counters = {k: np.asarray(st[k])
                        for k in ("created", "ejected", "hop_sum", "slot")}
            counters["queued"] = np.asarray(
                st["eq_len"].sum(-1) + st["qlen"].sum(-1)
                + st["oq_len"].sum(-1))
            memo[key] = r, counters
        return memo[key]
    return get


@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
@pytest.mark.parametrize("replicas", [1, 2])
@pytest.mark.parametrize("schedule", ["window", "barrier"])
def test_program_stops_at_its_completion_slot_for_every_chunk(
        chunk_runs, schedule, replicas, chunk):
    # chunk 1 tests for completion after every slot: the reference
    ref, ref_counters = chunk_runs(schedule, replicas, 1)
    r, counters = chunk_runs(schedule, replicas, chunk)
    assert np.all(ref["completed"])
    for k in ("slots", "phase_slots", "pool_stall", "completed"):
        np.testing.assert_array_equal(r[k], ref[k], err_msg=k)
    for k in ("created", "ejected", "hop_sum", "queued"):
        np.testing.assert_array_equal(counters[k], ref_counters[k],
                                      err_msg=k)
    slots = np.asarray(r["slots"])
    if schedule == "window":
        # every replica stepped to the slowest one's completion slot
        np.testing.assert_array_equal(counters["slot"],
                                      np.full_like(slots, slots.max()))
    else:
        # the last phase's completion resets the slot; a replica that
        # finished early keeps stepping until the slowest one completes
        np.testing.assert_array_equal(counters["slot"], slots.max() - slots)


def test_program_endpoint_count_must_match_fabric(sim):
    cp = compile_program(all2all_program(sim.S + 2, 1))
    with pytest.raises(ValueError, match="endpoints"):
        sim.make_program_state(cp)


def test_engine_rejects_degenerate_adversarial_traffic(sim):
    with pytest.raises(ValueError, match="shift"):
        sim.make_state(Traffic("shift", shift=sim.S))
    with pytest.raises(ValueError, match="exceeds burst_load"):
        sim.make_state(Traffic("bursty", load=0.8, burst_load=0.5))
    with pytest.raises(ValueError, match="hot_count"):
        sim.make_state(Traffic("hotspot", hot_count=sim.S + 1))


def test_register_program_builder_end_to_end(sim):
    # the documented extension point: one registration call makes a custom
    # collective valid WorkloadSpec vocabulary, resolves metric=auto to
    # completion, and executes device-resident through run()
    from repro.api import Experiment, NetworkSpec, RouteSpec, run
    from repro.workloads import WorkloadProgram
    from repro.workloads.programs import register_program_builder

    def neighbour_exchange(S, **_kw):
        e = np.arange(S, dtype=np.int64)
        partner = np.where(e % 2 == 0, (e + 1) % S, (e - 1) % S)
        return WorkloadProgram("neighbour_exchange", partner[None, :],
                               np.ones((1, S), np.int32))

    register_program_builder("neighbour_exchange", neighbour_exchange,
                             overwrite=True)
    with pytest.raises(ValueError, match="already registered"):
        register_program_builder("neighbour_exchange", neighbour_exchange)
    with pytest.raises(ValueError, match="already registered"):
        register_program_builder("uniform", neighbour_exchange,
                                 overwrite=True)   # bernoulli name clash

    wl = WorkloadSpec("neighbour_exchange")
    exp = Experiment(
        network=NetworkSpec("mrls", {"n_leaves": 14, "u": 3, "d": 3,
                                     "seed": 0}),
        route=RouteSpec(policy="polarized", max_hops=10, pool=4096),
        workload=wl, max_slots=2000)
    assert exp.resolved_metric() == "completion"
    res = run(exp)
    assert res.completed and len(res.phase_slots) == 1


def test_bursty_traffic_runs_and_respects_load(sim):
    r = sim.run_throughput(Traffic("bursty", load=0.2, burst_len=6.0,
                                   burst_load=0.9), warm=100, measure=300)
    # long-run offered load ~0.2; delivered throughput must be in that
    # neighbourhood (generous band: the Markov modulation is noisy)
    assert 0.05 < r["throughput"] < 0.35


def test_tornado_is_leaf_permutation(sim):
    # tornado's destination map never targets the source leaf (n1 even
    # half-rotation) => zero local fast-path deliveries
    r = sim.run_throughput(Traffic("tornado", load=0.3), warm=50,
                           measure=100)
    assert r["throughput"] > 0.0
    assert r["avg_hops"] >= 1.0
