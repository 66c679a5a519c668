"""Device time of the simulator's loop executables in the traced stretch,
per simulated slot of that stretch (slots counted as ``slots_per_s``
counts them)."""


def read(run):
    t = run.trace
    if not t or not t.get("loop_s") or run.trace_slots <= 0:
        return None
    return 1e3 * t["loop_s"] / run.trace_slots
