#!/usr/bin/env python3
"""What the program's tracing costs when a recorder is open, in one process.

    python bench/tracing_cost.py --workload <cell> --seed <n> \\
        --seconds <s> --pairs <k>

Sets the cell up as ``run.py`` does, with a ``repro.runtime.tracing``
recorder open over set-up (its ``topology.build`` and ``routing.tables``
spans: what set-up is made of), then runs ``2 * pairs`` windows of
``--seconds``, alternately without and with a recorder open, and prints
one JSON line: ``slots_per_s`` of each window, the median of each side
and their ratio, the set-up readings, and the open windows' host spans
and ``engine.slots_stepped`` as the recorder saw them.

Not run by the benchmark's runs.  Exits non-zero off a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import statistics
import sys
import time

T0 = time.perf_counter()

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from simbench import cells, chip, loop  # noqa: E402


def setup_readings(rec, table_build_s: float) -> dict:
    return {"topology_builds": len(rec.named("topology.build")),
            "topology_build_s": rec.seconds("topology.build"),
            "routing_tables_s": rec.seconds("routing.tables"),
            "table_build_s": table_build_s}


def window_readings(rec, win) -> dict:
    answers = len(rec.named("api.run")) or 1
    return {"admission_ms_per_answer":
            1e3 * rec.seconds("api.admission") / answers,
            "prepare_ms_per_answer":
            1e3 * rec.seconds("runner.prepare") / answers,
            "stepped_per_counted":
            rec.counts["engine.slots_stepped"] / max(win.slots(), 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=2)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)

    chip.compile_cache()
    try:
        chip.tpu_device(cell.chips)
    except chip.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    from repro.api import SimulatorCache, run
    from repro.runtime import tracing

    used = set()
    with SimulatorCache() as sims:
        with tracing.record() as rec:
            warm = loop.experiment(cell, cells.answer_seed(args.seed, -1))
            used.add(warm.seed)
            built = loop.build(warm, sims)
            run(warm, cache=sims)
        setup = dict(setup_readings(rec, built),
                     setup_s=time.perf_counter() - T0)
        print(json.dumps({"setup": setup}), file=sys.stderr, flush=True)
        rates = {"off": [], "on": []}
        opened = []
        for i in range(2 * args.pairs):
            side = "on" if i % 2 else "off"
            ctx = tracing.record() if side == "on" \
                else contextlib.nullcontext()
            with ctx as rec:
                win = loop.window(cell, sims, args.seed + i, args.seconds,
                                  used=used, sink=[])
            rates[side].append(win.slots_per_s())
            if rec is not None:
                opened.append(window_readings(rec, win))
            print(json.dumps({"window": i, "side": side,
                              "slots_per_s": win.slots_per_s(),
                              "answers": win.attempted}),
                  file=sys.stderr, flush=True)
    off, on = (statistics.median(rates[s]) for s in ("off", "on"))
    print(json.dumps({"workload": cell.name, "slots_per_s": rates,
                      "median_off": off, "median_on": on,
                      "on_over_off": on / off, "setup": setup,
                      "recorder": opened}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
