"""Packet counters of the final simulator state of each answer.

A collective's ``Result`` holds no packet counts, and no ``Result`` holds
the packets still in flight.  While :func:`tally` is open, the simulator's
run entries that return their final state also reduce that state on the
device, in one small jitted call that waits for nothing, and append the
reduction to a list: per replica, packets ``created``, ``ejected``,
``hop_sum`` (links crossed by the ejected packets) and ``queued`` (packets
held in the endpoint, input and output queues).  :func:`fetch` brings
them to the host once the window has closed.
"""
from __future__ import annotations

import contextlib

ENTRIES = ("run_program", "run_throughput", "run_throughput_batch",
           "run_completion")
FIELDS = ("created", "ejected", "hop_sum", "queued")


def _reduce(st):
    return {"created": st["created"], "ejected": st["ejected"],
            "hop_sum": st["hop_sum"],
            "queued": (st["eq_len"].sum(-1) + st["qlen"].sum(-1)
                       + st["oq_len"].sum(-1))}


_jitted = []


@contextlib.contextmanager
def tally(sink: list):
    """Appends the counters of every final state to ``sink``."""
    import jax
    from repro.simulator.engine import Simulator
    if not _jitted:
        _jitted.append(jax.jit(_reduce))
    reduce = _jitted[0]
    keys = ("created", "ejected", "hop_sum", "eq_len", "qlen", "oq_len")
    originals = {name: getattr(Simulator, name) for name in ENTRIES}

    def wrap(entry):
        def run(self, *args, **kwargs):
            out = entry(self, *args, **kwargs)
            st = out["state"]
            sink.append(reduce({k: st[k] for k in keys}))
            return out
        return run

    for name, entry in originals.items():
        setattr(Simulator, name, wrap(entry))
    try:
        yield sink
    finally:
        for name, entry in originals.items():
            setattr(Simulator, name, entry)


def fetch(counts) -> dict | None:
    """Host lists, one value per replica, of one reduction."""
    if counts is None:
        return None
    import jax
    import numpy as np
    host = jax.device_get(counts)
    return {k: [int(x) for x in np.atleast_1d(host[k])] for k in FIELDS}
