"""Drives a cell through ``repro.api.run``: set-up and the answer loop.

Shared by ``run.py`` (the benchmark) and ``control.py`` (readings of the
comparison for sound and control runs).
"""
from __future__ import annotations

import contextlib
import time
import traceback

from . import answers, cells


def experiment(cell: cells.Cell, seed: int, route: dict | None = None):
    from repro.api import Experiment
    return Experiment.from_dict(cells.experiment_dict(cell, seed, route))


def build(exp, sims) -> float:
    """Admission plus the simulator's construction; returns seconds."""
    from repro.api import check_admission
    t = time.perf_counter()
    masks = check_admission(exp).masks
    sims.get(exp.network, exp.route, masks)
    return time.perf_counter() - t


def window(cell: cells.Cell, sims, base_seed: int, seconds: float, *,
           used: set, sink: list, route: dict | None = None, annotate=None,
           after_answer=None) -> answers.Window:
    """Answers back to back until ``seconds`` have passed; the answer in
    flight then finishes.  ``used`` holds seeds not to repeat; answer
    ``i`` takes ``cells.answer_seed(base_seed, i)``.  ``sink`` is the list
    an open ``probe.tally`` fills; each answer keeps the last entry its
    call added.  ``annotate(name)`` gives a context manager around each
    call (a profiler span); ``after_answer(window)`` runs after each."""
    from repro.api import run
    annotate = annotate or (lambda name: contextlib.nullcontext())
    win = answers.Window(t0=time.perf_counter())
    deadline = win.t0 + seconds
    i = 0
    while not win.answers or time.perf_counter() < deadline:
        seed = cells.answer_seed(base_seed, i)
        i += 1
        if seed in used:
            continue
        used.add(seed)
        exp = experiment(cell, seed, route)
        rec, err = None, ""
        n0 = len(sink)
        t_a = time.perf_counter()
        with annotate(f"answer {len(win.answers)}"):
            try:
                rec = answers.record(run(exp, cache=sims))
            except Exception:               # a raised answer is a failed one
                err = traceback.format_exc()
        t_b = time.perf_counter()
        slots = answers.answer_slots(rec, cell.traffic) if rec else 0
        counts = sink[-1] if len(sink) > n0 else None
        del sink[:]
        win.answers.append(answers.Answer(len(win.answers), seed, t_a, t_b,
                                          slots, rec, err, counts))
        if after_answer is not None:
            after_answer(win)
    return win
