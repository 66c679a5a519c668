"""Per answer of the traced stretch, from the end of its last run of a
loop executable on the device to the end of its ``api.run`` span (the
result's fetch and the host work after it), mean."""
from simbench import phases


def read(run):
    return phases.answer_tail_ms(run)
