"""90th percentile (nearest rank) of the wall seconds from a
``repro.api.run`` call to its returned ``Result``, over every answer of
the window."""
from simbench.answers import nearest_rank


def read(run):
    return nearest_rank((a.seconds for a in run.window.answers), 0.90)
