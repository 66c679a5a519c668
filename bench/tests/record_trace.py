#!/usr/bin/env python3
"""Records the small TPU trace that ``test_bench_phases.py`` reduces.

    python bench/tests/record_trace.py [--out bench/tests/trace_v5e.json]

Runs on a TPU: on a tiny MRLS fabric, one windowed All2All answer of a
few chunks and one uniform answer of a 2-replica batch, each warmed up
first, profiled inside the ``bench stretch`` span as ``run.py`` profiles
its window.  Keeps only what ``simbench.phases`` and ``simbench.trace``
read: the device's ``XLA Ops`` and ``XLA Modules`` events (short op name,
times, op path) and the host events of the stretch, the answers and the
program's spans and counter (name, times, stats), times in nanoseconds
from the first.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from simbench import phases, trace  # noqa: E402

NET = {"family": "mrls", "params": {"n_leaves": 14, "u": 3, "d": 3,
                                    "seed": 0}}
ROUTE = {"policy": "polarized", "max_hops": 10, "pool": 4096}
A2A = dict(network=NET, route=ROUTE, metric="completion", chunk=4,
           max_slots=4000, workload={"pattern": "all2all", "rounds": 2,
                                     "schedule": "window", "window": 2})
UNIFORM = dict(network=NET, route=ROUTE, metric="throughput", warm=2,
               measure=2, replicas=2,
               workload={"pattern": "uniform", "load": 1.0})
HOST_NAMES = {phases.STRETCH, phases.ANSWER, phases.STEPPED,
              "api.admission", "runner.prepare"}


def record(trace_dir: str) -> str:
    import jax
    from repro.api import Experiment, SimulatorCache, run
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("records a TPU trace: no TPU here")
    warm = [Experiment.from_dict(dict(A2A, seed=12)),
            Experiment.from_dict(dict(UNIFORM, seed=23))]
    exps = [Experiment.from_dict(dict(A2A, seed=11)),
            Experiment.from_dict(dict(UNIFORM, seed=21))]
    with SimulatorCache() as sims:
        for e in warm:
            run(e, cache=sims)                  # compile outside the trace
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(phases.STRETCH):
            for i, e in enumerate(exps):
                with jax.profiler.TraceAnnotation(f"answer {i}"):
                    run(e, cache=sims)
        jax.profiler.stop_trace()
    return max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)


def keep(ev) -> bool:
    if ev.plane.startswith(trace.DEVICE_PREFIX):
        return ev.line in (trace.OPS_LINE, trace.MODULES_LINE)
    return ev.name in HOST_NAMES or ev.name.startswith("answer ")


def fixture(events: list, device: str) -> dict:
    kept = [ev for ev in events if keep(ev)]
    t0 = min(ev.start_ns for ev in kept)
    tables = {"planes": [], "lines": [], "names": [], "paths": []}
    index = {k: {} for k in tables}

    def at(table, value):
        ix = index[table]
        if value not in ix:
            ix[value] = len(tables[table])
            tables[table].append(value)
        return ix[value]

    rows = []
    for ev in kept:
        name = trace.op_name(ev.name) if ev.line == trace.OPS_LINE \
            else ev.name
        rows.append([at("planes", ev.plane), at("lines", ev.line),
                     at("names", name), int(ev.start_ns - t0),
                     int(ev.dur_ns), at("paths", ev.path), ev.stats or 0])
    return {"about": __doc__.splitlines()[0], "device": device,
            **tables, "events": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(HERE / "trace_v5e.json"))
    args = ap.parse_args(argv)
    import jax
    trace_dir = HERE.parents[1] / ".bench_trace" / "record_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    events = phases.read(record(str(trace_dir)))
    fx = fixture(events, jax.devices()[0].device_kind)
    text = json.dumps(fx, separators=(",", ":"))
    pathlib.Path(args.out).write_text(text + "\n")
    print(f"{len(fx['events'])} events, {len(text)} bytes -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
