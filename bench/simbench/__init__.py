"""The benchmark's yardstick: cells, answer accounting, the reference,
the comparison that decides ``correct``, and the trace reduction.

Nothing here imports the simulator at module level; only ``run.py`` and
``control.py`` drive it.
"""
