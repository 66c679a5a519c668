"""Slots the simulator stepped (the program's ``engine.slots_stepped``
counter) over the slots the answers count, in the traced stretch: above
1 where a collective runs whole chunks past its completion slot."""
from simbench import phases


def read(run):
    return phases.stepped_per_counted(run)
