"""Host time of the program's ``runner.prepare`` spans per answer of the
traced stretch (collective program build, simulator state build)."""
from simbench import phases


def read(run):
    return phases.host_ms_per_answer(run, "runner.prepare")
