"""Share of the traced stretch in which no operation ran on the device,
in percent."""


def read(run):
    t = run.trace
    return None if not t else 100.0 * t["idle_share"]
