"""Trace reduction: busy union, idle share, loop time and gap attribution,
on hand-made events."""

import pytest

import benchtiny  # noqa: F401  (puts bench/ on the path)
from simbench import trace

E = trace.Event
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def events():
    return [
        E(HOST, "python", "bench stretch", 0, 1000),
        E(HOST, "python", "answer 0", 0, 500),
        E(HOST, "python", "build program", 20, 80),
        E(HOST, "python", "answer 1", 500, 500),
        E(DEV, trace.MODULES_LINE, "jit__program_loop(1)", 100, 300),
        E(DEV, trace.MODULES_LINE, "jit_copy(2)", 450, 20),
        E(DEV, trace.OPS_LINE, "fusion.1", 100, 200),
        E(DEV, trace.OPS_LINE, "fusion.2", 250, 150),    # overlaps fusion.1
        E(DEV, trace.OPS_LINE, "copy", 450, 20),
        E(DEV, trace.OPS_LINE, "fusion.1", 600, 100),
        E(DEV, trace.OPS_LINE, "late", 950, 200),          # clipped at 1000
    ]


def test_merge_and_gaps():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace._gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]


def test_reduce_hand_made():
    r = trace.reduce(events(), 0, 1000, r"program_loop", "bench stretch")
    busy = 300 + 20 + 100 + 50           # [100,400) [450,470) [600,700) [950,1000)
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert r["window_s"] == pytest.approx(1000 / 1e9)
    assert r["idle_share"] == pytest.approx(1 - busy / 1000)
    assert r["loop_s"] == pytest.approx(300 / 1e9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300 / 1e9)
    assert ops["late"] == pytest.approx(50 / 1e9)
    gaps = r["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["answer 1", "answer 1"]  # 700-950, 470-600
    assert gaps[0][1] == pytest.approx(250 / 1e9)
    assert ("build program", pytest.approx(100 / 1e9)) in [
        tuple(g) for g in gaps]                                   # 0-100
    assert trace.span(events(), "bench stretch") == (0, 1000)


def test_no_device_events_read_nothing():
    host_only = [e for e in events() if e.plane == HOST]
    assert trace.reduce(host_only, 0, 1000, "x", "bench stretch") is None

