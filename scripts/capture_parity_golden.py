"""Capture bitwise parity goldens for the simulator engine.

Runs every routing policy on the tiny MRLS fabric and records the exact
throughput / avg-hops / latency-histogram outputs.  The committed file
``tests/golden/engine_parity.json`` is the acceptance gate for engine
refactors (compact routing tables, free-list pool, donated buffers): the
rebuilt ``backend="xla"`` engine must reproduce these numbers bitwise.

To regenerate (only legitimate when a PR *intentionally* changes simulated
behaviour, which parity-preserving perf work must not, or when a JAX
upgrade changes the random stream — JAX 0.5 turned
``jax_threefry_partitionable`` on by default):

    PYTHONPATH=src python scripts/capture_parity_golden.py
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import mrls, build_tables
from repro.simulator.engine import Simulator, SimConfig, Traffic

OUT = pathlib.Path(__file__).resolve().parents[1] / "tests" / "golden" / \
    "engine_parity.json"

FABRIC = {"n_leaves": 14, "u": 3, "d": 3, "seed": 0}
WARM, MEASURE = 60, 120
POLICIES = ("polarized", "minimal_adaptive", "ksp", "ugal", "valiant")


def main():
    topo = mrls(**FABRIC)
    tables = build_tables(topo)
    golden = {"fabric": FABRIC, "warm": WARM, "measure": MEASURE,
              "policies": {}}
    for policy in POLICIES:
        sim = Simulator(tables, SimConfig(policy=policy, max_hops=10,
                                          pool=4096))
        thr = sim.run_throughput(Traffic("uniform", load=0.7),
                                 warm=WARM, measure=MEASURE, seed=0)
        lat = sim.run_latency(Traffic("uniform", load=0.5),
                              warm=WARM, measure=MEASURE, seed=0)
        hist = np.asarray(lat["hist"])
        nz = np.nonzero(hist)[0]
        golden["policies"][policy] = {
            "throughput": float(thr["throughput"]),
            "avg_hops": float(thr["avg_hops"]),
            "ejected": int(thr["ejected"]),
            "pool_stall": int(thr["pool_stall"]),
            "lat_hist_nonzero": {int(i): int(hist[i]) for i in nz},
        }
        sim.close()
        print(policy, golden["policies"][policy]["throughput"],
              golden["policies"][policy]["ejected"])
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(golden, indent=1, sort_keys=True) + "\n")
    print("wrote", OUT)


if __name__ == "__main__":
    main()
