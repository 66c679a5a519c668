"""Host set-up span: admission plus ``SimulatorCache.get`` (topology,
routing tables, ``Simulator.__init__``)."""


def read(run):
    return run.spans.get("table_build")
