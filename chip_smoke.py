#!/usr/bin/env python3
"""Smoke test of the simulator on a TPU, through its normal entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: sharded paths only

One chip, in one process, phase by phase:

1. device  — platform, kind and count; anything but a TPU stops here;
2. goldens — ``tests/golden/engine_parity.json`` (polarized and
   minimal_adaptive) and the polarized row of
   ``tests/golden/collective_parity.json``, replayed bitwise;
3. pallas  — one 64-slot chunk on the ``headline.1k.mrls`` fabric with the
   compiled Pallas arbitration and with XLA: identical state pytrees, and
   ``tpu_custom_call`` in the Pallas executable;
4. headline — ``headline.100k.mrls`` (104,976 endpoints, windowed All2All,
   8 rounds) through ``repro.api.run``: it must complete.

``--four-chips`` runs only the sharded phase on ``headline.10k.mrls``: the
replica-axis ``shard_map`` batch and the switch-axis GSPMD chunk, each
against the same run on one device, bitwise.

Each phase prints one line.  The last line is one JSON object with
``"ok": true`` and the device; it is printed only when every phase passed
on a TPU.  Any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SPEC = ROOT / "examples" / "specs" / "headline_a2a.json"
GOLDEN = ROOT / "tests" / "golden"

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")


def same_tree(a: dict, b: dict, what: str) -> None:
    import numpy as np
    require(a.keys() == b.keys(), f"{what}: state keys differ")
    for k in a:
        require(np.array_equal(np.asarray(a[k]), np.asarray(b[k])),
                f"{what}: state[{k!r}] differs")


def experiment(name: str):
    from repro.api.cli import spec_experiments
    exps = {e.name: e for e in spec_experiments(str(SPEC))}
    return exps[name]


# ---------------------------------------------------------------------- #
def phase_device(n_chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    require(d0.platform == "tpu", f"no TPU: JAX runs on {d0.platform}")
    require(len(devs) >= n_chips, f"{n_chips} chips needed, "
                                  f"{len(devs)} found")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_goldens() -> None:
    import numpy as np
    from repro.core import build_tables, mrls
    from repro.simulator.engine import SimConfig, Simulator, Traffic
    from repro.workloads import compile_program, rabenseifner_program

    g = json.loads((GOLDEN / "engine_parity.json").read_text())
    tables = build_tables(mrls(**g["fabric"]))
    for policy in ("polarized", "minimal_adaptive"):
        gp = g["policies"][policy]
        with Simulator(tables, SimConfig(policy=policy, max_hops=10,
                                         pool=4096)) as sim:
            thr = sim.run_throughput(Traffic("uniform", load=0.7),
                                     warm=g["warm"], measure=g["measure"],
                                     seed=0)
            lat = sim.run_latency(Traffic("uniform", load=0.5),
                                  warm=g["warm"], measure=g["measure"],
                                  seed=0)
        for k in ("throughput", "avg_hops", "ejected", "pool_stall"):
            require(thr[k] == gp[k],
                    f"engine golden {policy}.{k}: {thr[k]} != {gp[k]}")
        hist = np.asarray(lat["hist"])
        want = np.zeros_like(hist)
        for b, count in gp["lat_hist_nonzero"].items():
            want[int(b)] = count
        require(np.array_equal(hist, want),
                f"engine golden {policy}: latency histogram differs")

    c = json.loads((GOLDEN / "collective_parity.json").read_text())
    tables = build_tables(mrls(**c["fabric"]))
    with Simulator(tables, SimConfig(policy="polarized", max_hops=10,
                                     pool=4096)) as sim:
        cp = compile_program(rabenseifner_program(sim.S, c["ranks"],
                                                  c["vec_packets"]),
                             schedule="barrier")
        r = sim.run_program(cp, chunk=c["chunk"], max_slots=c["max_slots"],
                            seed=c["seed"])
    got = {"slots": int(r["slots"]), "completed": bool(r["completed"]),
           "pool_stall": int(r["pool_stall"]),
           "phase_slots": [int(s) for s in r["phase_slots"]]}
    require(got == c["policies"]["polarized"],
            f"collective golden polarized: {got}")
    print("goldens: engine_parity polarized+minimal_adaptive bitwise, "
          f"collective_parity polarized bitwise (slots={got['slots']})",
          flush=True)


def phase_pallas(name: str = "headline.1k.mrls") -> None:
    import dataclasses
    import jax
    from repro.api import routing_tables
    from repro.simulator.engine import Simulator, Traffic

    exp = experiment(name)
    tables = routing_tables(exp.network)
    tr = Traffic("uniform", load=1.0)
    n_slots = 64
    states, has_kernel = {}, {}
    for backend in ("xla", "pallas"):
        cfg = dataclasses.replace(exp.route.to_sim_config(), backend=backend)
        with Simulator(tables, cfg) as sim:
            st, tb = sim.make_state(tr, seed=0), sim._tables()
            compiled = Simulator._run_chunk_jit.lower(
                sim, st, tb, tr, n_slots).compile()
            has_kernel[backend] = "tpu_custom_call" in compiled.as_text()
            states[backend] = jax.device_get(compiled(st, tb))
    same_tree(states["xla"], states["pallas"], "pallas vs xla")
    require(has_kernel["pallas"], "no tpu_custom_call in the Pallas chunk")
    require(not has_kernel["xla"], "tpu_custom_call in the XLA chunk")
    print(f"pallas: {name} uniform load 1.0, {n_slots} slots, "
          f"pallas == xla bitwise ({len(states['xla'])} state arrays), "
          "tpu_custom_call present", flush=True)


def phase_headline(name: str = "headline.100k.mrls") -> None:
    import jax
    from repro.api import SimulatorCache, check_admission, format_bytes, run

    exp = experiment(name)
    dec = check_admission(exp)
    print(f"headline admission: action={dec.action} masks={dec.masks} "
          f"predicted_rss={format_bytes(dec.predicted_bytes)} "
          f"resident={format_bytes(dec.resident_bytes)}", flush=True)
    with SimulatorCache() as cache:
        t0 = time.perf_counter()
        sim = cache.get(exp.network, exp.route, dec.masks)
        build_s = time.perf_counter() - t0
        print(f"headline set-up: host build {build_s:.1f} s "
              f"(S={sim.S}, switches={sim.N})", flush=True)
        t0 = time.perf_counter()
        res = run(exp, cache=cache)
        first_s = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    expected = sim.S * exp.workload.rounds
    print(f"headline set-up: first call {first_s:.1f} s (compile included), "
          "peak device memory "
          + (format_bytes(peak) if peak is not None else "not reported"),
          flush=True)
    require(res.completed is True,
            f"{name} did not deliver {expected} packets")
    require(8 <= res.slots < exp.max_slots,
            f"{name} slots={res.slots} outside "
            f"[8, {exp.max_slots})")
    print(f"headline: {exp.name} completed ({expected} packets) in "
          f"slots={res.slots}, phase_slots={list(res.phase_slots)}",
          flush=True)


def phase_four_chips(name: str = "headline.10k.mrls") -> None:
    import jax
    import numpy as np
    from repro.api import routing_tables
    from repro.parallel.sharding import Sharder
    from repro.simulator.engine import Simulator, Traffic

    exp = experiment(name)
    tables = routing_tables(exp.network)
    tr = Traffic("uniform", load=1.0)
    seeds = list(range(8))
    with Simulator(tables, exp.route.to_sim_config()) as sim:
        # warm == measure: one compiled chunk per path
        one = sim.run_throughput_batch(tr, seeds, warm=64, measure=64)
        four = sim.run_throughput_batch(
            tr, seeds, warm=64, measure=64,
            sharder=Sharder.for_simulator(n_devices=4))
        for k in ("throughput", "avg_hops", "ejected", "pool_stall"):
            require(np.array_equal(one[k], four[k]),
                    f"replica-sharded {k}: {four[k]} != {one[k]}")
        same_tree(jax.device_get(one["state"]),
                  jax.device_get(four["state"]), "replica-sharded")
        print(f"four chips: replica axis, {len(seeds)} seeds on "
              f"{exp.name}, sharded == one device bitwise "
              f"(throughput {one['throughput'].tolist()})", flush=True)

        sw = Sharder.for_simulator(n_devices=4, axis="switch")
        ref = jax.device_get(sim.run_chunk(sim.make_state(tr, 0), tr, 64))
        got = jax.device_get(sim.run_chunk(
            sim.shard_state(sim.make_state(tr, 0), sw), tr, 64))
        same_tree(ref, got, "switch-sharded")
        print(f"four chips: switch axis (GSPMD), 64 slots on {exp.name}, "
              f"sharded == one device bitwise (ejected {int(ref['ejected'])})",
              flush=True)


# ---------------------------------------------------------------------- #
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on four chips")
    args = ap.parse_args()

    from repro.runtime.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    counts = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event in _CACHE_EVENTS:
            counts[_CACHE_EVENTS[event]] += 1
    jax.monitoring.register_event_listener(count)

    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_goldens()
        phase_pallas()
        phase_headline()
    print(f"compile cache: {cache_dir} hits={counts['hits']} "
          f"misses={counts['misses']}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
