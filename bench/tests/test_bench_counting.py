"""Accounting of a run's window: slots, the tail, failed answers."""
import pytest

import benchtiny
from simbench import answers, check

A2A = benchtiny.traffic("a2a_w4")
UNIFORM = benchtiny.traffic("uniform_sat")


DUE = 42 * 8          # packets of an 8-round exchange over 42 endpoints


def final(created, ejected, hop_sum, queued=0, replicas=1):
    return {"created": [created] * replicas, "ejected": [ejected] * replicas,
            "hop_sum": [hop_sum] * replicas, "queued": [queued] * replicas}


def rec(**kw):
    base = {"metric": "completion", "replicas": 1,
            "slots": [23], "completed": [True], "throughput": None,
            "avg_hops": None, "ejected": None,
            "phase_slots": [[2, 3, 4, 5, 7, 8, 11, 23]],
            "mean_throughput": None, "final": final(DUE, DUE, 200)}
    base.update(kw)
    return base


def test_completion_counts_completion_slot_not_stepped():
    # chunk 16: the loop steps 32 slots for a completion at slot 23
    assert A2A["chunk"] == 16
    assert answers.answer_slots(rec(), A2A) == 23


def test_completion_counts_every_replica():
    r = rec(replicas=3, slots=[20, 22, 25], completed=[True] * 3)
    assert answers.answer_slots(r, A2A) == 67


def test_window_mix_counts_warm_and_measure_per_replica():
    r = rec(metric="throughput", replicas=4)
    assert answers.answer_slots(r, UNIFORM) == (300 + 300) * 4


def test_answer_straddling_the_end_counts_to_its_return():
    win = answers.Window(t0=100.0)
    for i, (a, b) in enumerate([(100.0, 101.0), (101.0, 102.5),
                                (102.5, 104.0)]):   # window of 3 s
        win.answers.append(answers.Answer(i, i + 1, a, b, slots=30,
                                          rec=rec()))
    assert win.attempted == 3
    assert win.wall_s == pytest.approx(4.0)
    assert win.slots_per_s() == pytest.approx(90 / 4.0)


@pytest.mark.parametrize("n,want", [(100, 90), (115, 104), (20, 18),
                                    (1, 1)])
def test_p90_nearest_rank(n, want):
    # values 1..n: ten beyond the p90 first holds at n = 100
    assert answers.nearest_rank(range(n, 0, -1), 0.90) == want
    assert n - want >= (10 if n >= 100 else 0)


def test_percentile_of_nothing():
    assert answers.nearest_rank([], 0.90) is None


def test_failed_counts_raised_and_incomplete():
    win = answers.Window(t0=0.0)
    win.answers += [
        answers.Answer(0, 1, 0.0, 1.0, 23, rec()),
        answers.Answer(1, 2, 1.0, 2.0, 0, None, "RuntimeError: x"),
        answers.Answer(2, 3, 2.0, 3.0, 4000, rec(
            completed=[False], slots=[4000], final=final(DUE, 300, 180,
                                                         queued=DUE - 300))),
    ]
    assert (win.raised, win.failed) == (1, 2)
    got = {c.name: c.value for c in check.compare(
        win.records(), win.raised, A2A, {"endpoints": 42}, ref={})}
    assert got == {"raised": 1, "incomplete": 1, "early_phase": 0,
                   "delivered_gap": DUE - 300, "lost": 0}


def test_collective_that_does_not_complete_fails_on_a_tiny_fabric():
    from repro.api import Experiment, SimulatorCache, run
    from simbench import cells
    cfg = benchtiny.CONFIGS["tiny_mrls"]
    cell = cells.Cell("a2a_w4.tiny_mrls", 1, cfg, dict(A2A, max_slots=4),
                      (), ())
    with SimulatorCache() as sims:
        r = answers.record(run(Experiment.from_dict(
            cells.experiment_dict(cell, 5)), cache=sims))
    assert answers.incomplete(r)
    win = answers.Window(t0=0.0)
    win.answers.append(answers.Answer(0, 5, 0.0, 1.0,
                                      answers.answer_slots(r, cell.traffic),
                                      r))
    assert win.failed == 1
    got = {c.name: c for c in check.compare(win.records(), 0, cell.traffic,
                                            cfg)}
    assert got["incomplete"].value == 1 and not got["incomplete"].ok
    # the final state was not seen: every number that reads it fails
    assert got["delivered_gap"].value == DUE and got["hops_gap"].value == 1


def test_early_phase_and_malformed_record():
    early = rec(phase_slots=[[0, 3, 4, 5, 7, 8, 11, 23]])
    backwards = rec(phase_slots=[[2, 3, 9, 5, 7, 8, 11, 23]])
    got = {c.name: c.value for c in check.compare(
        [early, backwards], 0, A2A, {"endpoints": 42}, ref={})}
    assert got["early_phase"] == 1 and got["incomplete"] == 1


def test_throughput_conservation_and_replicas():
    cfg = benchtiny.CONFIGS["tiny_ft"]
    S, m = cfg["endpoints"], UNIFORM["measure"]
    ok = rec(metric="throughput", replicas=2, slots=None, completed=None,
             phase_slots=None, throughput=[0.5, 0.75],
             avg_hops=[3.7, 3.7], ejected=[60000, 60000],
             mean_throughput=0.625,
             final=final(60500, 60000, 200000, queued=500, replicas=2))
    values = lambda recs: {c.name: c.value for c in check.compare(
        recs, 0, UNIFORM, cfg, {"uniform_hops": 3.6875})}
    assert values([ok]) == {"raised": 0, "missing_replicas": 0,
                            "bad_counts": 0, "lost": 0,
                            "hops_gap": pytest.approx(0.0034, abs=1e-4)}
    short = dict(ok, throughput=[0.5], avg_hops=[3.7], ejected=[60000])
    assert values([short])["missing_replicas"] >= 1
    over = dict(ok, ejected=[0.5 * S * m - 1, 60000])
    assert values([over])["bad_counts"] == 1
    unlike = dict(ok, final=final(60500, 59999, 200000, queued=501,
                                  replicas=2))
    assert values([unlike])["bad_counts"] == 2   # final state is not the answer's
    leak = dict(ok, final=final(60500, 60000, 200000, queued=400,
                                replicas=2))
    assert values([leak])["lost"] == 200
    unseen = dict(ok, final=None)
    assert values([unseen])["missing_replicas"] == 2
    assert check.Check("x", 1.0, 0.0).ok is False


def test_collective_delivered_lost_and_hops():
    cfg = benchtiny.CONFIGS["tiny_mrls"]
    ref = check.reference_for(A2A, cfg)
    least, most = ref["exchange_hops"]
    values = lambda r: {c.name: c.value for c in check.compare(
        [r], 0, A2A, cfg, ref)}
    sound = rec(final=final(DUE, DUE, least + 10))
    assert set(values(sound).values()) == {0}
    doubled = rec(final=final(DUE, 2 * DUE, 2 * (least + 10)))
    assert values(doubled)["delivered_gap"] == DUE
    dropped = rec(final=final(DUE, DUE - 5, least, queued=0))
    assert values(dropped)["lost"] == 5
    short = rec(final=final(DUE, DUE, least - 1))
    assert values(short)["hops_gap"] == pytest.approx(1 / least)
    long = rec(final=final(DUE, DUE, most + 2))
    assert values(long)["hops_gap"] == pytest.approx(2 / most)


def _digit_hops(radix: int, h: int, rounds: int) -> int:
    """Links of a shifted exchange on a two-level-pod Fat-Tree, from the
    endpoint digits: same leaf 0, same pod 2, else 4 (h = 2)."""
    k = radix // 2
    S = radix * k ** h
    total = 0
    for r in range(rounds):
        for e in range(S):
            a, b = e // k, ((e + r + 1) % S) // k
            total += 0 if a == b else (2 if a // k == b // k else 4)
    return total


def test_exchange_hops_reference_on_a_fat_tree():
    cfg = benchtiny.CONFIGS["tiny_ft"]
    least, most = check.reference_for(A2A, cfg)["exchange_hops"]
    assert least == most == _digit_hops(8, 2, 8)


def test_exchange_hops_reference_on_mrls():
    cfg = benchtiny.CONFIGS["tiny_mrls"]
    least, most = check.reference_for(A2A, cfg)["exchange_hops"]
    d, S = cfg["params"]["d"], cfg["endpoints"]
    cross = sum((e // d) != (((e + r + 1) % S) // d)
                for r in range(8) for e in range(S))
    assert (least, most) == (2 * cross, cfg["route"]["max_hops"] * cross)
