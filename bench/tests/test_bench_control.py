"""The comparison that decides ``correct``, on tiny fabrics on the CPU.

Sound runs read correct; the controls (``simbench.controls``) and faults
planted under the timed path read not correct.  The runs go through
``bench/run.py``'s ``main`` past its chip check.
"""
import numpy as np
import pytest

import benchtiny
from simbench import cells

control = benchtiny.load("control")

# each cell's controls: every one has to read not correct
SOUND = {"a2a_w4.tiny_mrls": ("lossy",),
         "a2a_w4.tiny_ft": ("lossy", "valiant"),
         "uniform_sat.tiny_ft": ("valiant", "lossy")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", sorted(SOUND))
def test_sound_run_is_correct(root, cell):
    rc, line = benchtiny.run_cell(root, cell, seed=2 ** 31 + 7)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) >= {"slots_per_s", "setup_s"}


@pytest.mark.parametrize("cell", sorted(SOUND))
def test_control_is_not_correct(root, cell):
    c = cells.resolve(cell, root)
    for kind in SOUND[cell]:
        lines = control.readings(c, kind, [11, 12, 13], 0.1,
                                 out=lambda s: None)
        assert len(lines) == 3 and not any(ln["correct"] for ln in lines)


def _frozen_step(self, st, traffic, chunk=None, max_slots=None):
    # nothing moves; only the clock runs, so a program still ends
    return dict(st, slot=st["slot"] + 1)


def _double_delivery(step):
    # every ejection is counted twice, so the program ends early
    def run(self, st, *args, **kw):
        out = step(self, st, *args, **kw)
        return dict(out, ejected=2 * out["ejected"] - st["ejected"])
    return run


def _half_batch(run_batch):
    def run(self, traffic, seeds, **kw):
        return run_batch(self, traffic, list(seeds)[:len(seeds) // 2], **kw)
    return run


def _altered_hops(run_batch):
    def run(self, traffic, seeds, **kw):
        out = run_batch(self, traffic, seeds, **kw)
        out["avg_hops"] = out["avg_hops"] * np.where(
            np.arange(len(out["avg_hops"])) == 0, 1.25, 1.0)
        return out
    return run


def _altered_slot(run_program):
    def run(self, program, **kw):
        out = run_program(self, program, **kw)
        out["slots"] = out["slots"] + 1
        return out
    return run


FAULTS = {
    "frozen_step.a2a": ("a2a_w4.tiny_ft", "_step", lambda f: _frozen_step),
    "frozen_step.uniform": ("uniform_sat.tiny_ft", "_step",
                            lambda f: _frozen_step),
    "double_delivery.a2a": ("a2a_w4.tiny_ft", "_step", _double_delivery),
    "half_batch.uniform": ("uniform_sat.tiny_ft", "run_throughput_batch",
                           _half_batch),
    "altered_answer.uniform": ("uniform_sat.tiny_ft", "run_throughput_batch",
                               _altered_hops),
    "altered_answer.a2a": ("a2a_w4.tiny_mrls", "run_program", _altered_slot),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_under_the_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro.simulator.engine import Simulator
    cell, attr, make = FAULTS[fault]
    monkeypatch.setattr(Simulator, attr, make(getattr(Simulator, attr)))
    rc, line = benchtiny.run_cell(root, cell, seed=99)
    assert rc == 0 and line["correct"] is False
