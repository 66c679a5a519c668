"""Simulated slots of the answers finished in the window, per wall second
of the window (to the return of its last answer)."""


def read(run):
    return run.window.slots_per_s()
