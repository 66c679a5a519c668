"""Controls of the comparison: the simulator with one stated guarantee
broken.  A sound comparison reads every control as not correct.

``lossy``    breaks ``lossless``: after every link phase the packets held
             in the input queues of the first leaf switch are lost.
``valiant``  breaks ``minimal_routes``: the simulator's own Valiant path
             (a random intermediate leaf) in place of the configured
             minimal policy.

Neither runs in a benchmark run; ``control.py`` runs them on the chip and
``tests/test_bench_control.py`` on the CPU.
"""
from __future__ import annotations

import contextlib

KINDS = ("lossy", "valiant")
# A lossy collective never completes and would step to the mix's
# max_slots (4000: about 1,000 s an answer at 104,976 endpoints); its
# controls stop at 64, above every sound completion read on the chip (13
# on ft_11k, 22-25 on mrls_100k).  ``lost`` and ``delivered_gap`` read a
# loss whenever the run stops.
LOSSY_MAX_SLOTS = 64


def route(kind: str, config: dict) -> dict | None:
    """The route a control runs under (``None``: the configured one)."""
    if kind == "valiant":
        return dict(config["route"], policy="valiant", max_hops=8)
    return None


def traffic(kind: str, mix: dict) -> dict:
    """The traffic mix a control runs under."""
    if kind == "lossy" and "max_slots" in mix:
        return dict(mix, max_slots=LOSSY_MAX_SLOTS)
    return mix


@contextlib.contextmanager
def patched(kind: str):
    """Simulator code of the control, for simulators built inside."""
    if kind != "lossy":
        yield
        return
    import jax.numpy as jnp
    from repro.simulator.engine import Simulator
    link_phase = Simulator._link_phase

    def lossy(self, st, key):
        st = link_phase(self, st, key)
        q = self.leaf_ids[0] * self.P * self.V + jnp.arange(
            self.P * self.V, dtype=jnp.int32)
        st["qlen"] = st["qlen"].at[q].set(0)
        return st

    Simulator._link_phase = lossy
    try:
        yield
    finally:
        Simulator._link_phase = link_phase
