"""Host time of the program's ``api.admission`` span per answer of the
traced stretch (admission control priced before each answer)."""
from simbench import phases


def read(run):
    return phases.host_ms_per_answer(run, "api.admission")
