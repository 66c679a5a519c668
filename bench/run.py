#!/usr/bin/env python3
"""Benchmark of the network simulator on the chip, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a fabric
configuration (``bench/configs``) under a traffic mix (``bench/traffic``).
The run drives the simulator through its normal entry point,
``repro.api.run(experiment, cache=SimulatorCache)``, in a closed loop: one
answer (one ``Result``) at a time, back to back.

1. Refuses to run, with no result line, unless JAX runs on a TPU with as
   many chips as the cell asks for.
2. Set-up: compile cache on, admission and the simulator's tables built,
   one warm-up answer of the cell's shapes (compile or cache load).
3. Window: answers until ``--seconds`` have passed; the answer in flight
   then finishes and counts.  Answer ``i`` takes a seed derived from
   ``(--seed, i)``.
4. Compares every answer of the window with the reference
   (``simbench.check``) and prints each number beside its limit, on
   standard error and under ``checks`` in the result line.
5. Prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (end-to-end with ``--trace 0``, per-layer with
   ``--trace 1``), ``device``, and with ``--trace 1`` ``breakdown``.

With ``--trace 1`` the first seconds of the window are profiled and
reduced by ``simbench.trace``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()            # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from simbench import cells, check, chip, loop, metrics, probe  # noqa: E402
from simbench import trace as tracing  # noqa: E402

LOOP_EXECUTABLES = r"program_loop|run_chunk|completion_loop"
TRACE_SECONDS = 3.0                 # length of the profiled stretch
STRETCH = "bench stretch"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, *, root: pathlib.Path = ROOT, device=chip.tpu_device,
         t0: float = T0) -> int:
    args = parse(argv)
    cell = cells.resolve(args.workload, root)

    cache_dir = chip.compile_cache()
    try:
        dev = device(cell.chips)
    except chip.NoDevice as e:
        log(f"refused: {e}")
        return 3
    with probe.tally([]) as sink:
        return measure(args, cell, root, dev, cache_dir, sink, t0)


def measure(args, cell, root, dev, cache_dir, sink, t0) -> int:
    """Set-up, the window and the result line of one run."""
    import jax

    counts = {"hits": 0, "misses": 0, "window": 0}
    in_window = [False]

    def on_event(event, **_):
        if event in CACHE_EVENTS:
            counts[CACHE_EVENTS[event]] += 1

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT and in_window[0]:
            counts["window"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    from repro.api import SimulatorCache, run

    # ---- set-up ------------------------------------------------------- #
    spans = {}
    sims = SimulatorCache()
    warm = loop.experiment(cell, cells.answer_seed(args.seed, -1))
    spans["table_build"] = loop.build(warm, sims)
    t = time.perf_counter()
    run(warm, cache=sims)
    del sink[:]
    spans["warmup"] = time.perf_counter() - t
    spans["setup"] = time.perf_counter() - t0
    log(f"set-up: {spans['setup']:.3f} s (tables {spans['table_build']:.3f} s,"
        f" warm-up {spans['warmup']:.3f} s); compile cache {cache_dir} "
        f"hits={counts['hits']} misses={counts['misses']}")

    # ---- window ------------------------------------------------------- #
    trace_dir = root / ".bench_trace" / cell.name
    stretch = contextlib.ExitStack()
    traced = {"on": bool(args.trace), "slots": 0}

    def stop_trace(win):
        stretch.close()
        jax.profiler.stop_trace()
        traced["on"], traced["slots"] = False, win.slots()

    def after_answer(win):
        if traced["on"] and win.answers[-1].t_end - win.t0 >= TRACE_SECONDS:
            stop_trace(win)

    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        stretch.enter_context(jax.profiler.TraceAnnotation(STRETCH))
    in_window[0] = True
    win = loop.window(cell, sims, args.seed, args.seconds, used={warm.seed},
                      sink=sink, annotate=jax.profiler.TraceAnnotation,
                      after_answer=after_answer)
    in_window[0] = False
    if traced["on"]:
        stop_trace(win)
    trace_slots = traced["slots"]

    # ---- after the window --------------------------------------------- #
    peak = chip.peak_bytes(cell.chips)
    win.fetch_counts()
    sims.close()
    for a in win.answers:
        if a.error:
            log(f"answer {a.index} (seed {a.seed}) raised:\n{a.error[-2000:]}")
    checks = check.compare(win.records(), win.raised, cell.traffic,
                           cell.config)
    reduced = None
    if args.trace:
        path = tracing.find_xplane(str(trace_dir))
        if path is not None:
            events = tracing.read_xplane(path)
            bounds = tracing.span(events, STRETCH)
            if bounds is not None:
                reduced = tracing.reduce(events, *bounds, LOOP_EXECUTABLES,
                                         STRETCH)
    data = metrics.RunData(spans, win, counts["window"], peak, reduced,
                           trace_slots)
    line = {
        "correct": all(c.ok for c in checks),
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics.collect(
            cell.per_layer if args.trace else cell.end_to_end, data),
        "device": dict(dev, memory_peak_bytes=peak),
    }
    if args.trace and reduced is not None:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    log(f"window: {win.attempted} answers, {win.slots()} slots in "
        f"{win.wall_s:.3f} s, {counts['window']} executables built")
    for c in checks:
        log(f"check {c.name}: {c.value} (limit {c.limit})"
            f"{'' if c.ok else ' FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
