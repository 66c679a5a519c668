"""Device time of the step's ``inject`` scope, injection (``_inject``):
self time (``simbench.phases.self_times``) of the operations of the
traced stretch whose op path holds the scope, per device, per simulated
slot of the stretch (``loop_ms_per_slot``'s slots)."""
from simbench import phases


def read(run):
    return phases.phase_ms_per_slot(run, "inject")
