"""Capture the host-loop collective golden (tests/golden/collective_parity.json).

Records, for every routing policy on the tiny MRLS fabric, the per-phase
completion slots / total slots / pool stalls of the *host-loop* Rabenseifner
allreduce: one ``Traffic("phase")`` state per phase (fresh seed arrays, fresh
PRNG key, fresh pool), driven to completion with ``run_completion``.  This is
the execution the device-resident program scheduler (``Traffic("program")``
with ``schedule="barrier"``) must reproduce bitwise — see
``tests/test_engine_parity.py::test_collective_golden_parity``.

Regenerating this file is only legitimate for PRs that intentionally change
collective behaviour, or when a JAX upgrade changes the random stream (JAX
0.5 turned ``jax_threefry_partitionable`` on by default).
"""
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))

from repro.core import build_tables, mrls  # noqa: E402
from repro.core.collectives import rabenseifner_phases  # noqa: E402
from repro.simulator.engine import SimConfig, Simulator, Traffic  # noqa: E402

import numpy as np  # noqa: E402

FABRIC = {"n_leaves": 14, "u": 3, "d": 3, "seed": 0}
RANKS = 16
VEC_PACKETS = 8
MAX_SLOTS = 3000
CHUNK = 16
SEED = 0
POLICIES = ("polarized", "minimal_adaptive", "ksp", "ugal", "valiant")


def host_loop_allreduce(sim: Simulator, ranks: int, vec_packets: int,
                        seed: int, chunk: int, max_slots: int) -> dict:
    """The pre-program-scheduler path: one fresh state + completion run per
    Rabenseifner phase (full host sync and state re-init between phases)."""
    total, ok, stall, per_phase = 0, True, 0, []
    for ph in rabenseifner_phases(ranks, vec_packets):
        tr = Traffic("phase", phase_packets=ph["packets"])
        st = sim.make_state(tr, seed=seed)
        partner = np.arange(sim.S, dtype=np.int32)
        partner[:ranks] = ph["partner"]
        st["partner"] = np.asarray(partner)
        r = sim.run_completion(tr, expected=sim.S * ph["packets"],
                               chunk=chunk, max_slots=max_slots, state=st)
        ok &= r["completed"]
        total += r["slots"]
        stall += r["pool_stall"]
        per_phase.append(int(r["slots"]))
    return {"slots": int(total), "completed": bool(ok),
            "pool_stall": int(stall), "phase_slots": per_phase}


def main() -> None:
    tables = build_tables(mrls(**FABRIC))
    doc = {
        "fabric": FABRIC, "ranks": RANKS, "vec_packets": VEC_PACKETS,
        "max_slots": MAX_SLOTS, "chunk": CHUNK, "seed": SEED,
        "policies": {},
    }
    for policy in POLICIES:
        with Simulator(tables, SimConfig(policy=policy, max_hops=10,
                                         pool=4096)) as sim:
            doc["policies"][policy] = host_loop_allreduce(
                sim, RANKS, VEC_PACKETS, SEED, CHUNK, MAX_SLOTS)
        print(policy, doc["policies"][policy])
    out = _ROOT / "tests" / "golden" / "collective_parity.json"
    out.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
